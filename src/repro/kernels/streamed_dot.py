"""BSPS inner product (paper §3.1, Algorithm 1) as a Pallas kernel.

The two vectors live in HBM ("external memory") as streams of C-element tokens;
every grid step is one hyperstep: the resident token pair is multiplied and
accumulated into the persistent partial sum α_s while Mosaic's pipeline
prefetches the next token pair. The final BROADCAST/SYNC reduction of the paper
happens across the grid's single core here (p=1 per chip); the cross-chip
reduction is a ``psum`` in the distributed layer.

Cost (paper): T = n·max(2C, 2Ce) + p + (p-1)g + l — bandwidth-heavy iff e > 1.
On v5e, e ≈ 481 FLOP/word (bf16), so this kernel is *always* bandwidth heavy:
its roofline is HBM, and block size only needs to be large enough to saturate
DMA (≥ ~512 lanes), which ``token_size``'s default respects. The plan
(:func:`dot_plan`) prices exactly the paper's closed form: 2C FLOPs per
hyperstep vs 2C streamed words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.kernels import pipeline

__all__ = ["streamed_dot", "dot_plan"]


#: One vreg of f32 partial sums, reduced to α on the final hyperstep.
_ACC = (8, 128)
#: Token sizes are whole bf16 tiles (16 × 128 words), so every block is
#: tile-aligned for 32- and 16-bit streams alike.
_TOKEN_ALIGN = 16 * 128


def _dot_kernel(v_ref, u_ref, out_ref, acc_ref, *, n_tok: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    prod = v_ref[...].astype(jnp.float32) * u_ref[...].astype(jnp.float32)
    acc_ref[...] += prod.reshape(-1, *_ACC).sum(axis=0)

    @pl.when(t == n_tok - 1)
    def _store():
        out_ref[...] = jnp.sum(acc_ref[...], keepdims=True)


def dot_plan(n_tok: int, c: int, *, dtype=jnp.float32) -> StreamPlan:
    """StreamPlan for α = v·u over ``n_tok`` hypersteps of C-word tokens.

    The backing arrays are viewed as lane-dense ``(n_tok·C/128, 128)``
    matrices, one ``(C/128, 128)`` block per token (C a multiple of 128).
    The partial sums are one ``(8, 128)`` f32 tile; the (1, 1) α is written
    up once, on the final hyperstep.
    """
    if c % 128:
        raise ValueError(f"token size {c} must be a multiple of 128 words")
    rows = c // 128
    return StreamPlan(
        name=f"dot_{n_tok}x{c}",
        grid=(n_tok,),
        inputs=(
            TokenSpec("v", (rows, 128), lambda t: (t, 0), dtype=dtype,
                      full_shape=(n_tok * rows, 128)),
            TokenSpec("u", (rows, 128), lambda t: (t, 0), dtype=dtype,
                      full_shape=(n_tok * rows, 128)),
        ),
        outputs=(
            # α is written up exactly once, on the final hyperstep:
            # constant map + rate 0 (write-once result, no revolving
            # output buffer)
            TokenSpec("alpha", (1, 1), lambda t: (0, 0), dtype=jnp.float32,
                      full_shape=(1, 1), direction="up", rate=0),
        ),
        scratch=(ScratchSpec("acc", _ACC, jnp.float32),),
        dimension_semantics=("arbitrary",),
        flops_per_hyperstep=2.0 * c,
    )


@functools.partial(jax.jit, static_argnames=("token_size", "interpret"))
def streamed_dot(
    v: jax.Array,
    u: jax.Array,
    *,
    token_size: int = 8 * 1024,
    interpret: bool = False,
) -> jax.Array:
    """α = v·u for 1-D vectors streamed token-by-token. Returns a scalar f32.

    The token size is rounded up to whole :data:`_TOKEN_ALIGN` words and
    never exceeds the aligned vector; the vectors are zero-padded to whole
    tokens.
    """
    if v.shape != u.shape or v.ndim != 1:
        raise ValueError(f"need equal 1-D shapes, got {v.shape}, {u.shape}")
    n = v.shape[0]
    c = min(-(-token_size // _TOKEN_ALIGN), -(-n // _TOKEN_ALIGN)) * _TOKEN_ALIGN
    pad = (-n) % c
    if pad:
        v = jnp.pad(v, (0, pad))
        u = jnp.pad(u, (0, pad))
    n_tok = v.shape[0] // c
    plan = dot_plan(n_tok, c, dtype=v.dtype)
    out = pipeline.lower(
        plan,
        functools.partial(_dot_kernel, n_tok=n_tok),
        interpret=interpret,
        vma=pipeline.operand_vma(v, u),
    )(v.reshape(-1, 128), u.reshape(-1, 128))
    return out[0, 0]

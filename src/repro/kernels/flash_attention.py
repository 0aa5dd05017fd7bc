"""Streaming (flash) attention as a BSPS algorithm, for GQA decoders.

Attention *is* a pseudo-streaming algorithm in the paper's sense: for each
resident Q token (a block of queries in VMEM), the K/V sequence is a stream of
tokens consumed one block per hyperstep, with the online-softmax running
statistics (m, l, acc) as the persistent local state — the analogue of the
paper's partial sum α_s in Algorithm 1. Mosaic's grid pipeline overlaps the
next K/V token's HBM→VMEM DMA with the current block's MXU compute, which is
exactly the hyperstep structure of Fig. 1.

Causal masking additionally uses the *pseudo*-streaming property: KV tokens
strictly above the diagonal are skipped (`pl.when` — the paper's "we are
allowed to revisit or skip tokens at any given time"), so the stream is only
read up to the diagonal. GQA is expressed through the K/V token index maps
(q-head h reads kv-head h // group), a token-reuse pattern like Cannon's
``MOVE(Σ, -M)``. Both facts live in the plan (:func:`attention_plan`): the
K/V maps are non-injective across q-heads, and ``flops_per_hyperstep`` is a
callable that returns 0 for skipped blocks, so Eq. 1 prices the causal
triangle correctly.

Grid: (batch, q_heads, q_blocks, kv_blocks), kv innermost/sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.kernels import pipeline

__all__ = ["flash_attention", "attention_plan"]

_NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, n_kv: int, block_q: int, block_kv: int, causal: bool, sm_scale: float,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Global token positions of this block's queries and keys. q_offset shifts
    # query positions for decode (queries are the *last* rows of the sequence).
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_kv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_ref[...]                             # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (block_q, block_kv)
        alpha = jnp.exp(m_prev - m_new)                 # rescale old state

        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)             # (block_kv, d)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # Skip KV tokens strictly above the diagonal (whole block masked out).
        block_needed = ki * block_kv <= qi * block_q + q_offset + block_q - 1
        pl.when(block_needed)(_body)
    else:
        _body()

    @pl.when(ki == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def attention_plan(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int,
    *,
    block_q: int, block_kv: int,
    causal: bool = True, q_offset: int = 0, dtype=jnp.bfloat16,
) -> StreamPlan:
    """StreamPlan for GQA flash attention on padded (sq, skv).

    Per hyperstep: one (block_q × block_kv) score tile — two MXU products
    (QKᵀ and PV, 4·bq·bkv·d FLOPs) plus ~10·bq·bkv vector ops for the online
    softmax. Causal hypersteps whose KV token lies strictly above the diagonal
    cost 0 (the token is skipped, not computed on — its DMA still runs, which
    is what the fetch side of Eq. 1 charges).
    """
    if sq % block_q or skv % block_kv:
        raise ValueError(f"({sq},{skv}) must be padded to ({block_q},{block_kv})")
    if hkv <= 0 or hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    n_q, n_kv = sq // block_q, skv // block_kv
    tile_flops = (4.0 * d + 10.0) * block_q * block_kv

    def flops(b_, h, i, j):
        if causal and j * block_kv > i * block_q + q_offset + block_q - 1:
            return 0.0
        return tile_flops

    if causal:
        # exact fraction of unskipped tiles (q_offset matters: decode's
        # sq=1 rows sit at the end of the key sequence, skipping ~nothing;
        # negative offsets can mask entire rows, hence the clamp at 0)
        computed = sum(
            max(0, min(n_kv, (i * block_q + q_offset + block_q - 1) // block_kv + 1))
            for i in range(n_q)
        )
        mean_flops = tile_flops * computed / (n_q * n_kv)
    else:
        mean_flops = tile_flops

    return StreamPlan(
        name=f"attn_b{b}h{hq}.{hkv}_{sq}x{skv}x{d}_b{block_q}.{block_kv}",
        grid=(b, hq, n_q, n_kv),
        inputs=(
            TokenSpec("Q", (1, 1, block_q, d),
                      lambda b_, h, i, j: (b_, h, i, 0),
                      dtype=dtype, full_shape=(b, hq, sq, d)),
            TokenSpec("K", (1, 1, block_kv, d),
                      lambda b_, h, i, j, g=group: (b_, h // g, j, 0),
                      dtype=dtype, full_shape=(b, hkv, skv, d)),
            TokenSpec("V", (1, 1, block_kv, d),
                      lambda b_, h, i, j, g=group: (b_, h // g, j, 0),
                      dtype=dtype, full_shape=(b, hkv, skv, d)),
        ),
        outputs=(
            # one O block streams up per resident Q block (when (b, h, i)
            # moves on), the attention analogue of Cannon's finished C tile
            TokenSpec("O", (1, 1, block_q, d),
                      lambda b_, h, i, j: (b_, h, i, 0),
                      dtype=dtype, full_shape=(b, hq, sq, d), direction="up"),
        ),
        scratch=(
            ScratchSpec("m", (block_q, 1), jnp.float32),
            ScratchSpec("l", (block_q, 1), jnp.float32),
            ScratchSpec("acc", (block_q, d), jnp.float32),
        ),
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        flops_per_hyperstep=flops,
        mean_flops_per_hyperstep=mean_flops,
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "sm_scale", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Streaming attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    Hq must be a multiple of Hkv (GQA). When Sq < Skv (decode with a KV cache),
    queries are placed at the *end* of the key sequence for causal masking.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5

    bq = min(block_q, sq)
    bk = min(block_kv, skv)
    pad_q, pad_k = (-sq) % bq, (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # Padded keys are masked via k_pos >= skv below only under causal; for
        # non-causal we must mask explicitly — simplest is to require divisible
        # shapes for non-causal use.
        if not causal:
            raise ValueError("non-causal flash_attention needs Skv % block_kv == 0")
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = q.shape[2], k.shape[2]
    q_offset = skv - sq  # decode: queries are the last sq positions

    plan = attention_plan(
        b, hq, hkv, sq_p, skv_p, d,
        block_q=bq, block_kv=bk, causal=causal, q_offset=q_offset,
        dtype=q.dtype,
    )
    out = pipeline.lower(
        plan,
        functools.partial(
            _attn_kernel,
            n_kv=plan.grid[3], block_q=bq, block_kv=bk,
            causal=causal, sm_scale=sm_scale, q_offset=q_offset,
        ),
        interpret=interpret,
        vma=pipeline.operand_vma(q, k, v),
    )(q, k, v)
    if pad_q:
        out = out[:, :, :sq, :]
    return out

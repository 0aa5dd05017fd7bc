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
read up to the diagonal: the K/V index maps clamp a skipped step to the last
block its Q row needs, so the resident token stays put and no DMA is issued.
GQA is expressed through the K/V token index maps (q-head h reads kv-head
h // group), a token-reuse pattern like Cannon's ``MOVE(Σ, -M)``. All of it
lives in the plan (:func:`attention_plan`): the K/V maps are non-injective
across q-heads and across skipped steps, and ``flops_per_hyperstep`` is a
callable that returns 0 for skipped blocks, so Eq. 1 prices the causal
triangle correctly.

A hyperstep has a fixed cost (~0.35 us on a v5e) whatever it computes, so
the token is sized to outweigh it (:func:`attention_tiles`): up to 512 × 512
scores per head, and several heads per hyperstep where that stays small.

Grid: (batch, q_head_blocks, q_blocks, kv_blocks), kv innermost/sequential.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bsp import TPU_V5E_CHIP
from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.kernels import pipeline

__all__ = ["flash_attention", "attention_plan", "attention_tiles"]

_NEG_INF = -1e30
_LOG2E = math.log2(math.e)
# Largest score tile of one head, per side, and the most scores (f32) one
# hyperstep holds over all its heads: 4 heads at 512 × 512 is 4 MiB. On a
# v5e this was the fastest hyperstep tried at S = 2048 for d = 64 and 128;
# 6 heads, or one 1024 × 512 tile, overflow Mosaic's 16 MiB of scoped VMEM.
_TILE = 512
_STEP_SCORES = 4 * 512 * 512
# Heads of one hyperstep are unrolled in the kernel body; more only grows code.
_MAX_HEADS = 8


def _side_tile(n: int) -> int:
    """Token length along one sequence axis: ``n`` itself up to ``_TILE``,
    else the MXU-aligned size that pads ``n`` least (the larger on a tie)."""
    if n <= _TILE:
        return n
    cands = (_TILE, _TILE // 2, _TILE // 4)
    return min(cands, key=lambda t: (-(-n // t) * t, -t))


def attention_tiles(
    hq: int, hkv: int, sq: int, skv: int, d: int,
    *, block_q: int | None = None, block_kv: int | None = None,
    dtype=jnp.bfloat16,
) -> tuple[int, int, int]:
    """The hyperstep :func:`flash_attention` streams: ``(block_q, block_kv,
    heads)``, chosen from the shapes.

    Each sequence axis takes the largest tile up to ``_TILE`` (an explicit
    ``block_q``/``block_kv`` wins, clamped to the axis as before); then as
    many q-heads per hyperstep as keep the step's scores within
    ``_STEP_SCORES``, dividing ``hq`` and nesting with the GQA group (each
    head block reads whole kv heads, or a share of one), and fitting VMEM
    (:meth:`StreamPlan.fits`).
    """
    bq = min(block_q, sq) if block_q else _side_tile(sq)
    bk = min(block_kv, skv) if block_kv else _side_tile(skv)
    group = hq // hkv
    heads = 1
    for h in range(2, min(hq, _MAX_HEADS) + 1):
        if h * bq * bk > _STEP_SCORES:
            break
        if hq % h or (h % group and group % h):
            continue
        plan = attention_plan(1, hq, hkv, -(-sq // bq) * bq, -(-skv // bk) * bk,
                              d, block_q=bq, block_kv=bk, heads=h, dtype=dtype)
        if plan.fits(TPU_V5E_CHIP):
            heads = h
    return bq, bk, heads


def _traced(x) -> bool:
    """Index maps run on Python ints when a plan is enumerated and on traced
    grid indices inside the kernel."""
    return isinstance(x, jax.core.Tracer)


def _last_kv_block(i, *, block_q: int, block_kv: int, q_offset: int):
    """Last K/V block Q block ``i`` needs under the causal mask (at least 0)."""
    top = i * block_q + q_offset + block_q - 1
    if _traced(i):
        return jax.lax.div(jnp.maximum(top, 0), jnp.int32(block_kv))
    return max(top, 0) // block_kv


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, n_kv: int, block_q: int, block_kv: int, heads: int, kv_heads: int,
    causal: bool, sm_scale: float, q_offset: int,
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
        for t in range(heads):
            # q-head t of the block reads kv-head t // group of the K/V block
            kt = t * kv_heads // heads
            # q and k enter the MXU as they arrive: bf16 products are exact
            # in the f32 accumulator. Scores are kept in log2 units (log2(e)
            # folded into the scale), so each exp is a bare exp2.
            s = jax.lax.dot_general(
                q_ref[0, t], k_ref[0, kt], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (sm_scale * _LOG2E)
            if causal:
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

            m_prev = m_ref[t]                               # (block_q, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)                         # (block_q, block_kv)
            alpha = jnp.exp2(m_prev - m_new)                # rescale old state
            l_ref[t] = alpha * l_ref[t] + jnp.sum(p, axis=-1, keepdims=True)
            v = v_ref[0, kt].astype(jnp.float32)            # (block_kv, d)
            acc_ref[t] = alpha * acc_ref[t] + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[t] = m_new

    if causal:
        # Skip KV tokens strictly above the diagonal (whole block masked out).
        block_needed = ki * block_kv <= qi * block_q + q_offset + block_q - 1
        pl.when(block_needed)(_body)
    else:
        _body()

    @pl.when(ki == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def attention_plan(
    b: int, hq: int, hkv: int, sq: int, skv: int, d: int,
    *,
    block_q: int, block_kv: int, heads: int = 1,
    causal: bool = True, q_offset: int = 0, dtype=jnp.bfloat16,
) -> StreamPlan:
    """StreamPlan for GQA flash attention on padded (sq, skv).

    Per hyperstep: ``heads`` q-heads' (block_q × block_kv) score tiles — two
    MXU products each (QKᵀ and PV, 4·bq·bkv·d FLOPs) plus ~10·bq·bkv vector
    ops for the online softmax. The K/V token holds the kv heads those
    q-heads read. Causal hypersteps whose KV token lies strictly above the
    diagonal cost 0: the token is skipped, and its index map repeats the
    last block the Q row needs, so nothing is fetched for it either.
    """
    if sq % block_q or skv % block_kv:
        raise ValueError(f"({sq},{skv}) must be padded to ({block_q},{block_kv})")
    if hkv <= 0 or hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if hq % heads or (heads % group and group % heads):
        raise ValueError(f"{heads} heads a step neither divide nor cover "
                         f"{hq} q heads in groups of {group}")
    kv_heads = max(1, heads // group)
    n_q, n_kv = sq // block_q, skv // block_kv
    tile_flops = (4.0 * d + 10.0) * block_q * block_kv * heads
    last = functools.partial(_last_kv_block, block_q=block_q,
                             block_kv=block_kv, q_offset=q_offset)

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

    def kv_map(b_, h, i, j):
        # the kv heads of q-head block h; a block narrower than the group
        # reads a share of one kv head
        if heads < group:
            h = (jax.lax.div(h * heads, jnp.int32(group)) if _traced(h)
                 else h * heads // group)
        if causal:
            j = jnp.minimum(j, last(i)) if _traced(j) else min(j, last(i))
        return (b_, h, j, 0)

    return StreamPlan(
        name=(f"attn_b{b}h{hq}.{hkv}_{sq}x{skv}x{d}"
              f"_b{block_q}.{block_kv}x{heads}"),
        grid=(b, hq // heads, n_q, n_kv),
        inputs=(
            TokenSpec("Q", (1, heads, block_q, d),
                      lambda b_, h, i, j: (b_, h, i, 0),
                      dtype=dtype, full_shape=(b, hq, sq, d)),
            TokenSpec("K", (1, kv_heads, block_kv, d), kv_map,
                      dtype=dtype, full_shape=(b, hkv, skv, d)),
            TokenSpec("V", (1, kv_heads, block_kv, d), kv_map,
                      dtype=dtype, full_shape=(b, hkv, skv, d)),
        ),
        outputs=(
            # one O block streams up per resident Q block (when (b, h, i)
            # moves on), the attention analogue of Cannon's finished C tile
            TokenSpec("O", (1, heads, block_q, d),
                      lambda b_, h, i, j: (b_, h, i, 0),
                      dtype=dtype, full_shape=(b, hq, sq, d), direction="up"),
        ),
        scratch=(
            ScratchSpec("m", (heads, block_q, 1), jnp.float32),
            ScratchSpec("l", (heads, block_q, 1), jnp.float32),
            ScratchSpec("acc", (heads, block_q, d), jnp.float32),
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
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Streaming attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    Hq must be a multiple of Hkv (GQA). When Sq < Skv (decode with a KV cache),
    queries are placed at the *end* of the key sequence for causal masking.
    Tiles left as None are chosen from the shapes (:func:`attention_tiles`).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5

    bq, bk, heads = attention_tiles(hq, hkv, sq, skv, d, block_q=block_q,
                                    block_kv=block_kv, dtype=q.dtype)
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
        block_q=bq, block_kv=bk, heads=heads, causal=causal,
        q_offset=q_offset, dtype=q.dtype,
    )
    out = pipeline.lower(
        plan,
        functools.partial(
            _attn_kernel,
            n_kv=plan.grid[3], block_q=bq, block_kv=bk, heads=heads,
            kv_heads=plan.inputs[1].block_shape[1],
            causal=causal, sm_scale=sm_scale, q_offset=q_offset,
        ),
        interpret=interpret,
        vma=pipeline.operand_vma(q, k, v),
    )(q, k, v)
    if pad_q:
        out = out[:, :, :sq, :]
    return out

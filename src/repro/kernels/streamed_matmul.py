"""BSPS two-level Cannon matmul, adapted to TPU as a Pallas kernel.

Paper §3.2 computes C = A·B with outer M×M blocks *streamed* from external
memory and an inner Cannon rotation across the 4×4 core grid. On TPU the two
levels map as (DESIGN.md §2):

  outer level  — HBM→VMEM block streams. The Pallas grid's K dimension is the
                 token stream: block (i, j, s) of A/B is the token of hyperstep
                 s, and Mosaic's automatic grid pipelining double-buffers the
                 next block's DMA against the current block's MXU compute —
                 exactly the paper's prefetch-overlapped hyperstep (Fig. 1).
  inner level  — the Cannon rotation becomes the MXU systolic array itself for
                 a single chip; the *multi-chip* rotation lives in
                 :mod:`repro.distributed.cannon` (shard_map + collective_permute).

Token identification: one (block_m × block_k) tile of A + one (block_k ×
block_n) tile of B form the two tokens resident per hyperstep; the fp32
accumulator tile is the persistent local state (the paper's C_ij block). Token
reuse via the stream cursor (`MOVE(Σ, -M)`) corresponds to the non-injective
BlockSpec index maps: A's tile (i, s) is re-fetched for every j — the paper's
"loop over groups of M blocks of A a number of M times".

The streaming structure lives in :func:`matmul_plan` (a
:class:`~repro.core.plan.StreamPlan`) and is lowered by
:func:`repro.kernels.pipeline.lower`; the planner scores the same plan with
Eq. 1 to pick block sizes (``plan_candidates`` + ``repro.core.plan.autotune``).
Defaults are 128/256 multiples so the MXU (128×128) stays aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.kernels import pipeline

__all__ = ["streamed_matmul", "matmul_plan", "plan_candidates", "vmem_bytes"]


def _matmul_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    """One hyperstep: multiply the resident A/B tokens into the local C block."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _store():
        # WRITE(σ_C, Σ_C): stream the finished block up to external memory.
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def matmul_plan(
    m: int, k: int, n: int,
    *,
    block_m: int, block_n: int, block_k: int,
    dtype=jnp.bfloat16, out_dtype=None,
) -> StreamPlan:
    """StreamPlan for C = A·B, shapes (m, k) × (k, n).

    Ragged shapes are rounded up to block multiples (the paper: "padding with
    zeros if necessary") — the plan describes the padded problem, matching
    what :func:`streamed_matmul` lowers. Grid (i, j, s): s is the hyperstep
    stream over K; A's map (i, s) ignores j (token reuse — each A tile is
    revisited for every j), B's map (s, j) ignores i.
    """
    m = -(-m // block_m) * block_m
    n = -(-n // block_n) * block_n
    k = -(-k // block_k) * block_k
    out_dtype = out_dtype or dtype
    return StreamPlan(
        name=f"matmul_{m}x{k}x{n}_b{block_m}.{block_n}.{block_k}",
        grid=(m // block_m, n // block_n, k // block_k),
        inputs=(
            TokenSpec("A", (block_m, block_k), lambda i, j, s: (i, s),
                      dtype=dtype, full_shape=(m, k)),
            TokenSpec("B", (block_k, block_n), lambda i, j, s: (s, j),
                      dtype=dtype, full_shape=(k, n)),
        ),
        outputs=(
            # the finished C block streams *up* when (i, j) moves on — one
            # write-back per output tile, priced by Eq. 1's up side
            TokenSpec("C", (block_m, block_n), lambda i, j, s: (i, j),
                      dtype=out_dtype, full_shape=(m, n), direction="up"),
        ),
        scratch=(ScratchSpec("acc", (block_m, block_n), jnp.float32),),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        flops_per_hyperstep=2.0 * block_m * block_n * block_k,
    )


def plan_candidates(m: int, k: int, n: int) -> list[dict[str, int]]:
    """MXU-aligned block-size grid for the planner, clipped to the problem."""
    sizes = (128, 256, 512)
    cands = []
    for bm in sizes:
        for bn in sizes:
            for bk in sizes:
                cands.append({
                    "block_m": min(bm, m), "block_n": min(bn, n),
                    "block_k": min(bk, k),
                })
    # dedupe after clipping
    return [dict(t) for t in sorted({tuple(sorted(c.items())) for c in cands})]


def vmem_bytes(block_m: int, block_n: int, block_k: int, itemsize: int = 2) -> int:
    """Resident VMEM footprint: A,B tokens double-buffered + fp32 accumulator.

    Legacy accessor kept for callers/tests (= ``plan.input_token_bytes +
    plan.scratch_bytes``); the general accounting is
    :attr:`StreamPlan.vmem_bytes`, which additionally counts the streamed
    output block.
    """
    tokens = (block_m * block_k + block_k * block_n) * itemsize * 2
    acc = block_m * block_n * 4
    return tokens + acc


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def streamed_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with BSPS block streaming. Shapes (m, k) x (k, n) -> (m, n).

    Ragged edges are zero-padded (the paper: "padding with zeros if necessary").
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad matmul shapes {a.shape} x {b.shape}")
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype

    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        b = jnp.pad(b, ((0, pad_k), (0, pad_n)))
    mp, kp = a.shape
    np_ = b.shape[1]

    plan = matmul_plan(mp, kp, np_, block_m=bm, block_n=bn, block_k=bk,
                       dtype=a.dtype, out_dtype=out_dtype)
    out = pipeline.lower(
        plan,
        functools.partial(_matmul_kernel, n_k=plan.grid[2]),
        interpret=interpret,
        vma=pipeline.operand_vma(a, b),
    )(a, b)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out

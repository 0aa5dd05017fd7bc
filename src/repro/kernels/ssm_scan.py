"""Mamba selective-scan as a BSPS chunked-stream kernel (jamba's SSM layers).

The recurrence
    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ B_t) x_t ,   y_t = C_t·h_t + D ⊙ x_t
is processed as a stream of sequence *chunks* (tokens): each hyperstep loads
one chunk of (x, Δ, B, C) into VMEM, advances the recurrent state h — the
persistent local memory of the core, exactly the paper's partial-result state —
and emits the chunk of y, while the next chunk's DMA is in flight. The state
h (d_inner × d_state) never leaves VMEM between hypersteps, which is the
whole point of the BSPS formulation: only the O(L·d) stream moves on the
HBM link, not the O(L·d·n) expanded state.

In the plan (:func:`ssm_plan`) A and D have *constant* index maps: they are
resident operands, fetched once at hyperstep 0 — the fetch schedule charges
them nothing afterwards, unlike the four per-chunk streams.

Grid: (batch, channel slices, n_chunks), chunks sequential (state carries
across grid steps, reset at chunk 0 of each batch element and slice).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.kernels import pipeline

__all__ = ["ssm_scan", "ssm_plan"]


def _slab_rows(dtype) -> int:
    """Rows of one sublane tile: 8 for 32-bit streams, 16 for bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref,
                 *, chunk: int, slab: int):
    """One chunk of the scan, in slabs of ``slab`` positions.

    Every ref access is tile-aligned: x, Δ and y move one whole
    ``(slab, d_slice)`` row group at a time, B and C arrive transposed as
    ``(d_state, chunk)`` and each position's column is picked with a lane
    mask. The state is kept as ``(d_state, d_slice)`` so its lanes are dense
    (``d_slice``: the grid's channel slice, see :func:`ssm_plan`).
    """
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)               # (d_state, d_slice)
    d_skip = d_ref[...].astype(jnp.float32)          # (1, d_slice)
    b_all = b_ref[0].astype(jnp.float32)             # (d_state, chunk)
    c_all = c_ref[0].astype(jnp.float32)             # (d_state, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, b_all.shape, 1)

    def column(m, t):                                # (d_state, 1)
        return jnp.sum(jnp.where(lane == t, m, 0.0), axis=1, keepdims=True)

    def step_slab(si, carry):
        row0 = pl.multiple_of(si * slab, slab)
        xs = x_ref[0, pl.ds(row0, slab), :].astype(jnp.float32)
        dts = dt_ref[0, pl.ds(row0, slab), :].astype(jnp.float32)
        h = h_ref[...]                               # (d_state, d_slice)
        ys = []
        for r in range(slab):
            x_t, dt_t = xs[r:r + 1], dts[r:r + 1]    # (1, d_slice)
            h = (jnp.exp(dt_t * a) * h
                 + column(b_all, row0 + r) * (dt_t * x_t))
            ys.append(jnp.sum(h * column(c_all, row0 + r), axis=0,
                              keepdims=True) + d_skip * x_t)
        h_ref[...] = h
        y_ref[0, pl.ds(row0, slab), :] = (
            jnp.concatenate(ys, axis=0).astype(y_ref.dtype))
        return carry

    jax.lax.fori_loop(0, chunk // slab, step_slab, 0)


#: Channels per grid slice: the state and each streamed block fit VMEM at
#: jamba's 8192 channels.
_BLOCK_D = 512


def _channel_block(d_inner: int) -> int:
    return _BLOCK_D if d_inner % _BLOCK_D == 0 else d_inner


def ssm_plan(
    bsz: int, seq: int, d_inner: int, d_state: int,
    *,
    chunk: int, dtype=jnp.float32, param_dtype=jnp.float32,
) -> StreamPlan:
    """StreamPlan for the chunked selective scan on a padded sequence.

    Channels are independent in the recurrence, so the grid also splits
    ``d_inner`` into :data:`_BLOCK_D`-wide slices (the whole width when
    that does not divide it).

    ~10·d_inner·d_state FLOPs per scanned position (exp/decay, state update,
    output contraction — same accounting as ``launch.dryrun``'s analytic scan
    correction), times ``chunk`` positions per hyperstep. ``param_dtype``
    prices the resident A/D operands, which the model keeps in fp32 even for
    bf16 activation streams.
    """
    if seq % chunk:
        raise ValueError(f"seq {seq} must be padded to chunk {chunk}")
    bd = _channel_block(d_inner)
    return StreamPlan(
        name=f"ssm_b{bsz}_{seq}x{d_inner}x{d_state}_c{chunk}_d{bd}",
        grid=(bsz, d_inner // bd, seq // chunk),
        inputs=(
            TokenSpec("x", (1, chunk, bd), lambda i, k, j: (i, j, k),
                      dtype=dtype, full_shape=(bsz, seq, d_inner)),
            TokenSpec("dt", (1, chunk, bd), lambda i, k, j: (i, j, k),
                      dtype=dtype, full_shape=(bsz, seq, d_inner)),
            # B and C stream transposed, (d_state, seq), so a chunk is a
            # lane-dense (d_state, chunk) block
            TokenSpec("B", (1, d_state, chunk), lambda i, k, j: (i, 0, j),
                      dtype=dtype, full_shape=(bsz, d_state, seq)),
            TokenSpec("C", (1, d_state, chunk), lambda i, k, j: (i, 0, j),
                      dtype=dtype, full_shape=(bsz, d_state, seq)),
            # A and D are resident per channel slice: rate 0 (fetched when
            # the slice changes, single-buffered — no prefetch buffer)
            TokenSpec("A", (d_state, bd), lambda i, k, j: (0, k),
                      dtype=param_dtype, full_shape=(d_state, d_inner), rate=0),
            TokenSpec("D", (1, bd), lambda i, k, j: (0, k),
                      dtype=param_dtype, full_shape=(1, d_inner), rate=0),
        ),
        outputs=(
            # each finished y chunk streams up as the cursor moves to the next
            TokenSpec("y", (1, chunk, bd), lambda i, k, j: (i, j, k),
                      dtype=dtype, full_shape=(bsz, seq, d_inner), direction="up"),
        ),
        scratch=(ScratchSpec("h", (d_state, bd), jnp.float32),),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        flops_per_hyperstep=10.0 * chunk * bd * d_state,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(
    x: jax.Array,      # (B, L, d_inner)
    dt: jax.Array,     # (B, L, d_inner)   Δ, already softplus'd
    b: jax.Array,      # (B, L, d_state)
    c: jax.Array,      # (B, L, d_state)
    a: jax.Array,      # (d_inner, d_state)  negative log-spaced
    d: jax.Array,      # (d_inner,) skip
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Selective scan over the sequence stream; returns y: (B, L, d_inner).

    The chunk is rounded up to whole slabs (:func:`_slab_rows`) and never
    exceeds the slab-padded sequence; the sequence is zero-padded to whole
    chunks.
    """
    bsz, seq, d_inner = x.shape
    d_state = a.shape[1]
    slab = _slab_rows(x.dtype)
    ck = min(-(-chunk // slab), -(-seq // slab)) * slab
    pad = (-seq) % ck
    if pad:
        x, dt = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt))
        b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (b, c))
    seq_p = x.shape[1]

    plan = ssm_plan(bsz, seq_p, d_inner, d_state, chunk=ck, dtype=x.dtype,
                    param_dtype=a.dtype)
    out = pipeline.lower(
        plan,
        functools.partial(_scan_kernel, chunk=ck, slab=slab),
        interpret=interpret,
        vma=pipeline.operand_vma(x, dt, b, c, a, d),
    )(x, dt, b.swapaxes(1, 2), c.swapaxes(1, 2), a.T, d.reshape(1, d_inner))
    if pad:
        out = out[:, :seq, :]
    return out

"""Two-level Cannon matmul: shard_map inner Cannon + BSPS outer streams (§3.2).

The *inner level* (:func:`cannon_matmul`) is the paper's Cannon algorithm
lifted from the Epiphany core grid to the TPU chip grid: matrices are
block-distributed over the (data × model) mesh treated as an N×N grid; each
of the N steps multiplies the resident blocks and rotates A left / B up with
``jax.lax.ppermute`` — the systolic schedule with zero data redundancy the
paper derives. Where GSPMD would emit all-gathers proportional to the full
operand, Cannon keeps per-step traffic at exactly one block per neighbour
per direction.

The *outer level* (Algorithm 2) wraps that inner BSP program in a hyperstep
loop that streams M×M outer blocks from external memory:
:func:`cannon_plan` prices the whole construction with Eq. 2
(``T̃ = M³·max(N(2k³+2k²g+l), 2k²e)``), :func:`cannon_streams` lays out the
per-core pseudo-streams Σ^A (row-major, re-read M times via ``MOVE``) and
Σ^B (column-major, rewound once per row group), and
:func:`two_level_cannon` runs the product end to end through a multi-core
:class:`~repro.core.hyperstep.HyperstepRunner` — one hyperstep per outer
block product, the inner Cannon (or a local matmul on a 1×1 grid) as the
per-hyperstep BSP program, C blocks written back once per M hypersteps on
the cores' DMA lanes.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.hyperstep import HyperstepRunner
from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.core.stream import Stream, StreamSet
from repro.models.layers import ops_matmul

__all__ = [
    "cannon_matmul",
    "cannon_plan",
    "cannon_streams",
    "make_cannon_step",
    "make_cannon_step_compiled",
    "cannon_move_schedule",
    "make_cannon_runner",
    "gather_c",
    "two_level_cannon",
]


def _local_mm(a, b):
    return ops_matmul(a, b)


@functools.partial(jax.jit, static_argnames=("mesh", "axis_a", "axis_b"))
def cannon_matmul(
    a: jax.Array, b: jax.Array, *, mesh: Mesh, axis_a: str = "data",
    axis_b: str = "model",
) -> jax.Array:
    """C = A @ B on an N×N (axis_a × axis_b) chip grid via Cannon rotation.

    Requires a square grid (mesh.shape[axis_a] == mesh.shape[axis_b]) — the
    16×16 production pod qualifies; tests use 2×2.
    """
    n = mesh.shape[axis_a]
    if mesh.shape[axis_b] != n:
        raise ValueError(f"Cannon needs a square grid, got {mesh.shape}")
    if a.shape[0] % n or a.shape[1] % n or b.shape[1] % n:
        raise ValueError("matrix dims must divide the grid (paper pads zeros)")

    def body(a_blk, b_blk):
        i = jax.lax.axis_index(axis_a)
        j = jax.lax.axis_index(axis_b)
        left = [(p, (p - 1) % n) for p in range(n)]   # along axis_b (cols)
        up = [(p, (p - 1) % n) for p in range(n)]     # along axis_a (rows)

        # initial skew: shift A left by i, B up by j (paper's distribution)
        def shift_a(k, ab):
            return jnp.where(k < i, jax.lax.ppermute(ab, axis_b, left), ab)

        def shift_b(k, bb):
            return jnp.where(k < j, jax.lax.ppermute(bb, axis_a, up), bb)

        a_blk = jax.lax.fori_loop(0, n - 1, shift_a, a_blk)
        b_blk = jax.lax.fori_loop(0, n - 1, shift_b, b_blk)

        acc = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        acc = jax.lax.pcast(acc, (axis_a, axis_b), to="varying")  # for the scan carry

        def step(_, carry):
            acc, a_blk, b_blk = carry
            acc = acc + _local_mm(a_blk, b_blk).astype(jnp.float32)
            a_blk = jax.lax.ppermute(a_blk, axis_b, left)
            b_blk = jax.lax.ppermute(b_blk, axis_a, up)
            return acc, a_blk, b_blk

        acc, a_blk, b_blk = jax.lax.fori_loop(0, n, step, (acc, a_blk, b_blk))
        return acc.astype(a_blk.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_a, axis_b), P(axis_a, axis_b)),
        out_specs=P(axis_a, axis_b),
    )(a, b)


# ---------------------------------------------------------------------------
# Outer level: Algorithm 2 as a StreamPlan + multi-core HyperstepRunner
# ---------------------------------------------------------------------------


def _check_dims(n: int, m_blocks: int, n_grid: int) -> tuple[int, int]:
    """(outer block side K, per-core inner block side k) for n, M, N."""
    if m_blocks <= 0 or n_grid <= 0:
        raise ValueError(f"need m_blocks>0 and n_grid>0, got {m_blocks}, {n_grid}")
    if n % (m_blocks * n_grid) != 0:
        raise ValueError(
            f"n={n} must be divisible by M·N={m_blocks * n_grid} "
            "(paper pads with zeros)")
    big = n // m_blocks
    return big, big // n_grid


def cannon_plan(n: int, m_blocks: int, n_grid: int = 1, *,
                dtype: jnp.dtype = jnp.float32) -> StreamPlan:
    """The paper's two-level Cannon (Algorithm 2) as a StreamPlan (Eq. 2).

    Grid (i, j, s): one hyperstep per outer-block product C_ij += A_is·B_sj,
    M per axis. Token specs describe *one core* of the N×N inner grid — each
    fetches its k×k sub-block of A and B every hyperstep (k = n/(N·M)) and
    flushes its k×k piece of C when the plan moves off an (i, j) output
    block, i.e. once per M hypersteps. The non-injective A map (i, s) is the
    ``MOVE(Σ^A, −M)`` row-group reuse; the inner BSP program term is N
    supersteps of work 2k³ and h-relation 2k² each, so ``cost()`` is exactly
    Eq. 2's ``Σ max(N(2k³ + 2k²g + l), e·C)`` with the C-block write-back
    charged on flush hypersteps.
    """
    _, k = _check_dims(n, m_blocks, n_grid)
    side = m_blocks * k   # one core's slice of the full matrix
    return StreamPlan(
        name=f"cannon2_n{n}_M{m_blocks}_N{n_grid}",
        grid=(m_blocks, m_blocks, m_blocks),
        inputs=(
            TokenSpec("A", (k, k), lambda i, j, s: (i, s), dtype=dtype,
                      full_shape=(side, side)),
            TokenSpec("B", (k, k), lambda i, j, s: (s, j), dtype=dtype,
                      full_shape=(side, side)),
        ),
        outputs=(
            TokenSpec("C", (k, k), lambda i, j, s: (i, j), dtype=dtype,
                      full_shape=(side, side), direction="up"),
        ),
        scratch=(ScratchSpec("C_acc", (k, k), dtype),),
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        flops_per_hyperstep=n_grid * 2.0 * k**3,
        comm_words_per_hyperstep=n_grid * 2.0 * k**2,
        supersteps_per_hyperstep=float(n_grid),
    )


def cannon_streams(
    a: np.ndarray, b: np.ndarray, m_blocks: int, n_grid: int = 1,
) -> tuple[list[list[Stream]], list[list[Stream]], StreamSet]:
    """Per-core stream sets for Algorithm 2 on an N×N core grid.

    Returns ``(ins, outs, stream_set)``: for each core (row-major order),
    ``ins[core] = [Σ^A, Σ^B]`` — the core's sub-blocks of A in row-major
    outer-block order and of B in column-major order (the layouts whose
    cursor walks are pure advances plus the ``MOVE`` seeks of
    :func:`cannon_move_schedule`) — and ``outs[core] = [Σ^C]``, a zeroed
    write-back stream with one token per outer C block.
    """
    n = a.shape[0]
    _, k = _check_dims(n, m_blocks, n_grid)
    ss = StreamSet()
    a_streams = ss.create_block_grid(a, m_blocks, n_grid, order="row", name="A")
    b_streams = ss.create_block_grid(b, m_blocks, n_grid, order="col", name="B")
    ins, outs = [], []
    for core in range(n_grid * n_grid):
        c_backing = np.zeros((m_blocks * m_blocks, k, k), np.asarray(a).dtype)
        sc = ss.create(c_backing, 1, name=f"C[{core // n_grid},{core % n_grid}]")
        ins.append([a_streams[core], b_streams[core]])
        outs.append([sc])
    return ins, outs, ss


def cannon_move_schedule(m_blocks: int):
    """The ``MOVE`` calls of Algorithm 2 as an ``on_hyperstep_end`` callback.

    Called with the hyperstep m whose tokens were just fetched; positions the
    cursors for hyperstep m+1 of the (i, j, s) grid walk: at the end of an
    outer product (s wraps), Σ^A seeks −M to replay row group i for the next
    j (``MOVE(Σ^A, −M)``), and at the end of a row group (j also wraps) Σ^B
    rewinds −M² for the next i (``MOVE(Σ^B, −M²)``). Works on the nested
    per-core stream sets of the multi-core runner.
    """
    total = m_blocks**3

    def on_end(m: int, per_core_streams) -> None:
        if m + 1 >= total:
            return
        j, s = (m // m_blocks) % m_blocks, m % m_blocks
        if s != m_blocks - 1:
            return
        for core, (sa, sb) in enumerate(per_core_streams):
            if j < m_blocks - 1:
                sa.seek(core, -m_blocks)
            else:
                sb.seek(core, -m_blocks * m_blocks)

    return on_end


def _assemble_grid(blocks: list, n_grid: int) -> jax.Array:
    """Per-core (1, k, k) tokens (row-major core order) -> the global block."""
    if n_grid == 1:
        return jnp.asarray(blocks[0][0])
    rows = [
        jnp.concatenate(
            [jnp.asarray(t[0]) for t in blocks[ci * n_grid:(ci + 1) * n_grid]],
            axis=1)
        for ci in range(n_grid)
    ]
    return jnp.concatenate(rows, axis=0)


def _split_grid(block: np.ndarray, n_grid: int) -> list[np.ndarray]:
    """The global C block -> per-core (k, k) pieces, row-major core order."""
    k = block.shape[0] // n_grid
    return [
        np.asarray(block[ci * k:(ci + 1) * k, cj * k:(cj + 1) * k])
        for ci in range(n_grid) for cj in range(n_grid)
    ]


def make_cannon_step(m_blocks: int, n_grid: int = 1, *,
                     mesh: Mesh | None = None, axis_a: str = "data",
                     axis_b: str = "model"):
    """The per-hyperstep inner BSP program of two-level Cannon.

    State is ``(s, acc)`` — the position within the current outer product and
    the accumulated C block (the plan's ``C_acc`` scratch). Each hyperstep
    assembles the cores' A/B tokens into the outer block, runs the inner
    Cannon (:func:`cannon_matmul` on ``mesh``; the degenerate local matmul
    when ``mesh`` is None or the grid is 1×1) and accumulates; when s wraps,
    the finished C block is split back into per-core tokens for the runner's
    write-back lanes.
    """
    if mesh is not None and n_grid > 1:
        inner = functools.partial(cannon_matmul, mesh=mesh, axis_a=axis_a,
                                  axis_b=axis_b)
    else:
        inner = jax.jit(lambda x, y: ops_matmul(x, y))

    def step(state, toks):
        s, acc = state
        a_blk = _assemble_grid(toks[0], n_grid)
        b_blk = _assemble_grid(toks[1], n_grid)
        part = inner(a_blk, b_blk)
        acc = part if acc is None else acc + part
        if s == m_blocks - 1:
            out = _split_grid(np.asarray(acc), n_grid)
            return (0, None), [out]
        return (s + 1, acc), [None]   # no C flush mid outer product

    return step


def make_cannon_step_compiled(m_blocks: int, n_grid: int = 1, *,
                              mesh: Mesh | None = None, axis_a: str = "data",
                              axis_b: str = "model"):
    """The compiled-mode twin of :func:`make_cannon_step` (pure JAX).

    Traceable into the runner's single ``lax.scan`` dispatch: state is
    ``(s, acc)`` with ``s`` a traced position counter and ``acc`` a concrete
    array (no ``None`` sentinel — it is reset with a ``where`` when a new
    outer product starts), and the per-core C pieces are returned *every*
    hyperstep; the runner's ``out_every`` flush mask keeps only the ones where
    the outer product completes. Initial state comes from
    :func:`cannon_compiled_state`.
    """
    if mesh is not None and n_grid > 1:
        inner = functools.partial(cannon_matmul, mesh=mesh, axis_a=axis_a,
                                  axis_b=axis_b)
    else:
        inner = ops_matmul

    def step(state, toks):
        s, acc = state
        a_blk = _assemble_grid(toks[0], n_grid)
        b_blk = _assemble_grid(toks[1], n_grid)
        part = inner(a_blk, b_blk).astype(acc.dtype)
        acc = jnp.where(s == 0, part, acc + part)
        k = acc.shape[0] // n_grid
        pieces = [acc[ci * k:(ci + 1) * k, cj * k:(cj + 1) * k]
                  for ci in range(n_grid) for cj in range(n_grid)]
        return ((s + 1) % m_blocks, acc), [pieces]

    return step


def cannon_compiled_state(n: int, m_blocks: int,
                          dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """Initial ``(s, acc)`` carry for :func:`make_cannon_step_compiled`."""
    big = n // m_blocks
    return jnp.int32(0), jnp.zeros((big, big), dtype)


def gather_c(outs: list[list[Stream]], n: int, m_blocks: int,
             n_grid: int = 1) -> np.ndarray:
    """Reassemble C from the per-core write-back streams' backing arrays."""
    big, k = _check_dims(n, m_blocks, n_grid)
    c = np.zeros((n, n), np.asarray(outs[0][0].data).dtype)
    for core, (sc,) in enumerate(outs):
        ci, cj = divmod(core, n_grid)
        data = np.asarray(sc.data)
        for i in range(m_blocks):
            for j in range(m_blocks):
                c[i * big + ci * k: i * big + (ci + 1) * k,
                  j * big + cj * k: j * big + (cj + 1) * k] = (
                    data[i * m_blocks + j])
    return c


def make_cannon_runner(
    a: np.ndarray,
    b: np.ndarray,
    m_blocks: int,
    *,
    n_grid: int = 1,
    mesh: Mesh | None = None,
    machine=None,
    plan: StreamPlan | None = None,
    compiled: bool = True,
    verify: bool = True,
) -> tuple[HyperstepRunner, list[list[Stream]], Any]:
    """Build (but do not run) the Algorithm 2 runner; returns (runner, outs,
    initial state).

    Reusable across runs — repeated ``runner.run(state,
    num_hypersteps=m_blocks**3, compiled=...)`` calls replay the product (and
    in compiled mode reuse the one traced program), which is what the
    dispatch benchmark times. ``verify=True`` statically replays the MOVE
    schedule before the first dispatch (DESIGN.md §9) — the non-injective
    down-stream maps are legal reuse and pass clean; a corrupted seek
    schedule raises ``PlanVerificationError`` instead of corrupting C.
    """
    n = a.shape[0]
    if a.shape != (n, n) or b.shape != (n, n):
        raise ValueError(f"need square same-shape matrices, got {a.shape}, {b.shape}")
    _check_dims(n, m_blocks, n_grid)
    if mesh is not None and n_grid > 1:
        shape = dict(mesh.shape)
        if shape.get("data") != n_grid or shape.get("model") != n_grid:
            raise ValueError(
                f"mesh shape {shape} does not match the {n_grid}×{n_grid} grid")
    dtype = jnp.asarray(a[:1, :1]).dtype
    if plan is None:
        plan = cannon_plan(n, m_blocks, n_grid, dtype=dtype)
    ins, outs, _ = cannon_streams(np.asarray(a), np.asarray(b), m_blocks, n_grid)
    if compiled:
        step = make_cannon_step_compiled(m_blocks, n_grid, mesh=mesh)
        state0: Any = cannon_compiled_state(n, m_blocks, dtype)
    else:
        step = make_cannon_step(m_blocks, n_grid, mesh=mesh)
        state0 = (0, None)
    runner = HyperstepRunner(
        step,
        ins,
        cores=n_grid * n_grid,
        out_streams=outs,
        out_every=[m_blocks],
        on_hyperstep_end=cannon_move_schedule(m_blocks),
        plan=plan,
        machine=machine,
        verify=verify,
    )
    return runner, outs, state0


def two_level_cannon(
    a: np.ndarray,
    b: np.ndarray,
    m_blocks: int,
    *,
    n_grid: int = 1,
    mesh: Mesh | None = None,
    machine=None,
    plan: StreamPlan | None = None,
    compiled: bool = True,
) -> tuple[np.ndarray, HyperstepRunner]:
    """C = A·B per Algorithm 2 on a (simulated) N×N core grid; returns (C, runner).

    The full paper construction: an outer hyperstep loop streaming M×M outer
    blocks (Σ^A re-read M times via ``MOVE``), the inner Cannon as the
    per-hyperstep BSP program on the core grid, C flushed up once per outer
    product. By default the whole loop runs as one compiled dispatch
    (``HyperstepRunner.compile`` — the MOVE schedule becomes static gather
    indices); pass ``compiled=False`` for the instrumented host loop with
    per-hyperstep records. With ``machine`` given the runner prices the run
    with Eq. 2 — read ``runner.predicted_vs_measured()`` after.
    """
    n = a.shape[0]
    runner, outs, state0 = make_cannon_runner(
        a, b, m_blocks, n_grid=n_grid, mesh=mesh, machine=machine, plan=plan,
        compiled=compiled)
    # explicit count: the seek-based MOVE reuse means the naive stream budget
    # (M² A tokens) undercounts the M³ hypersteps the walk actually performs
    runner.run(state0, num_hypersteps=m_blocks**3, compiled=compiled)
    return gather_c(outs, n, m_blocks, n_grid), runner

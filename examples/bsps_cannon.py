"""Two-level Cannon matrix multiplication as a BSPS program (paper §3.2).

The full Algorithm 2, executed through the repo's actual runtime instead of a
hand-rolled overlap loop: ``repro.distributed.cannon.cannon_plan`` prices the
construction with Eq. 2, ``autotune`` picks the outer block count M under the
machine's local-memory budget, and ``two_level_cannon`` runs the product
through a multi-core :class:`~repro.core.hyperstep.HyperstepRunner` — per-core
pseudo-streams Σ^A/Σ^B (the ``MOVE`` reuse as cursor seeks), the inner Cannon
(``shard_map`` + ``ppermute`` when a square device grid is available, the
degenerate local matmul otherwise) as the per-hyperstep BSP program, and C
blocks written back on the cores' DMA lanes.

The hyperstep loop runs in **compiled mode** (DESIGN.md §5): the whole M³
walk — including the MOVE seeks — is one ``lax.scan`` dispatch via
``HyperstepRunner.compile``; the instrumented host loop is run once for the
best M to show the dispatch-overhead gap.

Prints the Eq. 2 prediction next to the measured time, the paper's §6
validation. Run: PYTHONPATH=src python examples/bsps_cannon.py [n] [M]
"""

import sys
import time

import jax
import numpy as np

from repro.core import plan as planlib
from repro.core.calibrate import calibrate
from repro.distributed.cannon import (
    cannon_compiled_state,
    cannon_plan,
    gather_c,
    make_cannon_runner,
)
from repro.launch.mesh import auto_mesh


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    acc = calibrate()

    # a square device grid makes the inner level a real shard_map Cannon;
    # otherwise the 1×1 grid's inner program is the local device matmul
    n_grid = 2 if len(jax.devices()) >= 4 else 1
    mesh = (auto_mesh((n_grid, n_grid), ("data", "model"))
            if n_grid > 1 else None)

    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)

    # Eq. 2 selects M before anything runs (the paper's central claim):
    # larger outer blocks are predicted-cheaper until local memory runs out
    cands = [{"m_blocks": m} for m in (1, 2, 4, 8, 16)
             if n % (m * n_grid) == 0 and n // (m * n_grid) >= 8]
    best, choices = planlib.autotune(
        lambda m_blocks: cannon_plan(n, m_blocks, n_grid), cands, acc)
    for c in choices:
        tag = "ok " if c.feasible else "OOM"
        print(f"  [autotune] M={c.params['m_blocks']:2d} {tag} "
              f"predicted={c.predicted_seconds * 1e3:8.2f}ms "
              f"vmem={c.plan.vmem_bytes / 1e6:.1f}MB")
    print(f"  [autotune] picked M={best.params['m_blocks']} (Eq. 2)")

    run_ms = ([int(sys.argv[2])] if len(sys.argv) > 2
              else sorted({best.params["m_blocks"], 2, 4}))
    for m_blocks in run_ms:
        if n % (m_blocks * n_grid) != 0:
            continue
        # reuse one compiled runner and warm it, so the measured row times
        # the dispatch, not the one-off XLA trace of the scan
        runner, outs, _ = make_cannon_runner(a, b, m_blocks, n_grid=n_grid,
                                             mesh=mesh, machine=acc)
        runner.run(cannon_compiled_state(n, m_blocks, np.float32),
                   num_hypersteps=m_blocks**3, compiled=True)
        runner.reset_records()
        runner.run(cannon_compiled_state(n, m_blocks, np.float32),
                   num_hypersteps=m_blocks**3, compiled=True)
        c = gather_c(outs, n, m_blocks, n_grid)
        err = float(np.abs(c - a @ b).max())
        row = runner.predicted_vs_measured()
        k = n // (m_blocks * n_grid)
        print(f"n={n} N={n_grid} M={m_blocks} k={k}: err={err:.2e} "
              f"measured={row['measured_seconds'] * 1e3:.1f}ms "
              f"predicted={row['predicted_seconds'] * 1e3:.1f}ms "
              f"(x{row['pred_over_meas']:.2f}) "
              f"[compiled: {m_blocks**3} hypersteps, 1 dispatch] "
              f"bw_heavy pred={row['bandwidth_heavy_predicted']:.0f} "
              f"meas={row['bandwidth_heavy_measured']:.0f}")

    # the dispatch-overhead gap: the same program in both modes, one reused
    # runner each so the compiled timing excludes the one-off trace
    valid_ms = [m for m in run_ms if n % (m * n_grid) == 0]
    if not valid_ms:
        print(f"  [modes] no M in {run_ms} divides n={n} on the "
              f"{n_grid}×{n_grid} grid; skipping the mode comparison")
        return
    m_cmp = max(valid_ms)
    runner, outs, _ = make_cannon_runner(a, b, m_cmp, n_grid=n_grid, mesh=mesh,
                                         machine=acc)
    state0 = lambda: cannon_compiled_state(n, m_cmp, np.float32)
    runner.run(state0(), num_hypersteps=m_cmp**3, compiled=True)   # warm up
    t0 = time.perf_counter()
    runner.run(state0(), num_hypersteps=m_cmp**3, compiled=True)
    comp_s = time.perf_counter() - t0
    h_runner, h_outs, h_state0 = make_cannon_runner(
        a, b, m_cmp, n_grid=n_grid, mesh=mesh, machine=acc, compiled=False)
    h_runner.run(h_state0, num_hypersteps=m_cmp**3)     # warm the jitted step
    t0 = time.perf_counter()
    h_runner.run(h_state0, num_hypersteps=m_cmp**3)
    host_s = time.perf_counter() - t0
    assert float(np.abs(gather_c(outs, n, m_cmp, n_grid)
                        - gather_c(h_outs, n, m_cmp, n_grid)).max()) < 1e-4
    print(f"  [modes] M={m_cmp}: host loop {host_s * 1e3:.1f}ms vs "
          f"compiled {comp_s * 1e3:.1f}ms ({host_s / comp_s:.1f}x, "
          f"{m_cmp**3 / comp_s:.0f} hypersteps/s)")


if __name__ == "__main__":
    main()

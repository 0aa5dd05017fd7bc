"""Static lint over the repo's BSPS plan builders (DESIGN.md §9).

``python -m repro.lint`` builds every plan/runner reachable from the
in-repo examples, benchmarks, and kernel libraries — small dryrun shapes,
nothing executes or compiles — runs :func:`repro.core.verify.verify_plan` /
:func:`~repro.core.verify.verify_runner` over each, and prints a
diagnostics table. ``--check`` exits non-zero when any target fails to
build or produces an error-severity finding; CI runs that mode so a plan
regression (a corrupted seek schedule, an aliased up-stream, a blown
budget) fails the build instead of surfacing at dispatch time.

Targets are registered explicitly rather than discovered by import-walking:
each example's plan construction is reproduced at lint shapes (the examples
themselves run full demos), and the kernel builders are called with the
same candidate geometry their benchmarks use.

Run: ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.lint [--check]``
"""

from __future__ import annotations

import argparse
import importlib.util
import sys
import traceback
from pathlib import Path
from typing import Callable

from repro.core.verify import Diagnostic, format_diagnostics

#: repo root (src/repro/lint.py -> repo); examples/ and benchmarks/ live here
REPO_ROOT = Path(__file__).resolve().parents[2]

_TARGETS: list[tuple[str, Callable[[], list[Diagnostic]]]] = []


def target(name: str):
    def deco(fn: Callable[[], list[Diagnostic]]):
        _TARGETS.append((name, fn))
        return fn
    return deco


def _load_example(stem: str):
    """Import an examples/ module by path (examples/ is not a package)."""
    path = REPO_ROOT / "examples" / f"{stem}.py"
    if not path.exists():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"_lint_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- targets ----


@target("examples/quickstart:inner_product")
def _lint_quickstart() -> list[Diagnostic]:
    import numpy as np

    from repro.core import TPU_V5E_CHIP, HyperstepRunner, StreamSet
    from repro.core.verify import verify_runner

    ss = StreamSet()
    sv = ss.create(np.zeros(1 << 14, np.float32), 4096, name="v")
    su = ss.create(np.zeros(1 << 14, np.float32), 4096, name="u")
    runner = HyperstepRunner(lambda a, t: a, [sv, su], machine=TPU_V5E_CHIP)
    return verify_runner(runner)


@target("examples/bsps_cannon:two_level")
def _lint_cannon() -> list[Diagnostic]:
    import numpy as np

    from repro.core import TPU_V5E_CHIP
    from repro.core.verify import verify_runner
    from repro.distributed.cannon import make_cannon_runner

    m_blocks = 2
    a = np.ones((16, 16), np.float32)
    b = np.ones((16, 16), np.float32)
    runner, _, _ = make_cannon_runner(a, b, m_blocks, machine=TPU_V5E_CHIP)
    return verify_runner(runner, num_hypersteps=m_blocks ** 3)


@target("examples/bsps_spmv:ell_blocks")
def _lint_spmv() -> list[Diagnostic]:
    from repro.core.verify import verify_runner

    spmv = _load_example("bsps_spmv")
    cols, vals, x = spmv.make_ell_blocks(64, 0.1, block_rows=16)
    runner, _, _ = spmv.make_spmv_runner(cols, vals, x)
    return verify_runner(runner)


@target("benchmarks/serve_batch:packed_decode")
def _lint_packed_decode() -> list[Diagnostic]:
    from repro.core import TPU_V5E_CHIP
    from repro.core.plan import packed_decode_plan
    from repro.core.verify import verify_plan

    plan = packed_decode_plan(
        lanes=4, steps=16, flops_per_token=2e6,
        params_words=1 << 16, kv_words_per_lane=4096.0)
    return verify_plan(plan, TPU_V5E_CHIP)


@target("kernels/streamed_matmul:autotuned")
def _lint_matmul() -> list[Diagnostic]:
    from repro.core import TPU_V5E_CHIP
    from repro.core.plan import autotune
    from repro.kernels.streamed_matmul import matmul_plan, plan_candidates

    m = k = n = 512

    def build(block_m, block_n, block_k):
        return matmul_plan(m, k, n, block_m=block_m, block_n=block_n,
                           block_k=block_k)

    best, _ = autotune(build, plan_candidates(m, k, n), TPU_V5E_CHIP)
    return list(best.diagnostics)


@target("kernels/flash_attention:gqa")
def _lint_attention() -> list[Diagnostic]:
    from repro.core import TPU_V5E_CHIP
    from repro.core.verify import verify_plan
    from repro.kernels.flash_attention import attention_plan, attention_tiles

    # the hyperstep the kernel itself picks for this shape
    bq, bkv, heads = attention_tiles(4, 2, 256, 256, 64)
    plan = attention_plan(1, 4, 2, 256, 256, 64, block_q=bq, block_kv=bkv,
                          heads=heads)
    return verify_plan(plan, TPU_V5E_CHIP)


@target("kernels/streamed_dot:inner_product")
def _lint_dot() -> list[Diagnostic]:
    from repro.core import TPU_V5E_CHIP
    from repro.core.verify import verify_plan
    from repro.kernels.streamed_dot import dot_plan

    return verify_plan(dot_plan(16, 4096), TPU_V5E_CHIP)


@target("kernels/ssm_scan:chunked")
def _lint_ssm() -> list[Diagnostic]:
    from repro.core import TPU_V5E_CHIP
    from repro.core.verify import verify_plan
    from repro.kernels.ssm_scan import ssm_plan

    return verify_plan(ssm_plan(1, 256, 128, 16, chunk=64), TPU_V5E_CHIP)


@target("launch/dryrun:stream_plans")
def _lint_dryrun_plans() -> list[Diagnostic]:
    """The hot-spot plans dryrun records per cell, at a smoke shape."""
    from repro.configs import get_config
    from repro.core import TPU_V5E_CHIP
    from repro.core.plan import autotune
    from repro.kernels.streamed_matmul import matmul_plan, plan_candidates

    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    tokens, d_ff = 256, cfg.d_ff or cfg.moe_d_ff or 4 * cfg.d_model

    def build(block_m, block_n, block_k):
        return matmul_plan(tokens, cfg.d_model, d_ff, block_m=block_m,
                           block_n=block_n, block_k=block_k)

    best, _ = autotune(build, plan_candidates(tokens, cfg.d_model, d_ff),
                       TPU_V5E_CHIP, exact=False)
    return list(best.diagnostics)


# ------------------------------------------------------------------ CLI ----


def run_lint(check: bool = False) -> int:
    """Run every target; print the table; return the exit code."""
    failures = 0
    errors = 0
    rows: list[str] = []
    for name, fn in _TARGETS:
        try:
            diags = fn()
        except Exception:
            failures += 1
            rows.append(f"BUILD-FAIL  {name}")
            traceback.print_exc()
            continue
        n_err = sum(d.severity == "error" for d in diags)
        n_warn = sum(d.severity == "warn" for d in diags)
        n_info = len(diags) - n_err - n_warn
        errors += n_err
        status = "FAIL" if n_err else "ok"
        rows.append(f"{status:10s}  {name}  "
                    f"({n_err} error, {n_warn} warn, {n_info} info)")
        if diags:
            rows.append(format_diagnostics(diags))
    print(f"repro.lint: {len(_TARGETS)} plan targets")
    print("\n".join(rows))
    bad = failures + errors
    if bad:
        print(f"repro.lint: {errors} error finding(s), "
              f"{failures} target build failure(s)")
    else:
        print("repro.lint: all plans verify clean")
    return 1 if (check and bad) else 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.lint",
        description="statically verify the repo's BSPS plan builders")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on error findings or build failures")
    args = ap.parse_args(argv)
    return run_lint(check=args.check)


if __name__ == "__main__":
    sys.exit(main())

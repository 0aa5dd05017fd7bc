"""Chaos serve benchmark (writes ``BENCH_chaos.json``).

Drains the same request wave through :class:`repro.launch.engine.ServeEngine`
under seeded fault injection (DESIGN.md §10) at 0%, 5% and 20% fault rates —
every fault class at once: DMA stalls and stragglers stretch segments,
dispatch failures exercise the bounded retry, page exhaustion defers
admissions, corruption trips the BSPS203 output gate. The run is a
:class:`repro.core.faults.FaultPlan`, so a given rate injects the identical
fault sequence on every machine and every rerun.

Measured per rate: decode tokens/sec, per-token p99, whether the wave fully
drained, and the engine's health rollup (event counts by BSPS2xx code).
A fault-free baseline engine anchors the 0% run, and a crash-resume training
pair (dispatch failure mid-interval, auto-restore from checkpoint) asserts
the recovered loss history is token-for-token identical.

Floors (``--check``):

* the 20%-rate wave must drain completely — recovery, not collapse;
* 20%-rate throughput >= ``FLOOR_DEGRADED`` x the 0%-rate throughput
  (degraded, but above the CI floor);
* 0%-rate throughput >= ``FLOOR_CLEAN`` x the no-injector baseline (an idle
  injector must cost ~nothing);
* the resumed training history must equal the uncrashed one exactly.

Run:  python -m benchmarks.chaos_serve [--smoke] [--check] [--out PATH]
Also exposed as ``benchmarks.run chaos_serve`` CSV rows.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import tempfile

import jax
import numpy as np

from repro.core.calibrate import default_machine
from repro.core.faults import FaultPlan, FaultSpec

RATES = (0.0, 0.05, 0.20)
FLOOR_DEGRADED = 0.15      # r20 tokens/s vs r0 tokens/s
FLOOR_CLEAN = 0.5          # r0 tokens/s vs no-injector baseline
DELAY_S = 0.002            # injected stall/straggle per trigger


def _bench_cfg(smoke: bool):
    """Same weight-streaming decode shape as benchmarks.serve_batch."""
    from repro.configs import get_config
    cfg = get_config("minicpm-2b", smoke=True)
    layers = 2 if smoke else 4
    return dataclasses.replace(
        cfg, num_layers=layers, d_model=512, num_heads=8, num_kv_heads=8,
        d_ff=1536, vocab_size=16384, dtype="float32")


def _prompts(n: int, vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, size=4 + 3 * (i % 3)).astype(np.int32)
            for i in range(n)]


def _chaos_plan(rate: float, seed: int = 42) -> FaultPlan | None:
    if rate <= 0.0:
        return None
    return FaultPlan([
        FaultSpec("dma_stall", rate=rate, delay_s=DELAY_S),
        FaultSpec("straggler", rate=rate, delay_s=DELAY_S),
        FaultSpec("dispatch_fail", rate=rate),
        FaultSpec("page_exhaust", rate=rate),
        FaultSpec("corrupt", rate=rate / 4, mode="bitflip"),
    ], seed=seed, horizon=8192)


def _drain_wave(eng, prompts, steps: int) -> tuple[int, float]:
    seg0 = len(eng.segment_log)
    for i, p in enumerate(prompts):
        eng.submit(p, steps, seed=i)
    eng.run_until_drained()
    segs = eng.segment_log[seg0:]
    return (sum(s["tokens"] for s in segs),
            sum(s["wall_seconds"] for s in segs))


def _run_rate(cfg, params, acc, rate: float, smoke: bool) -> dict:
    from repro.launch.engine import ServeEngine

    n_req = 6 if smoke else 12
    steps = 16 if smoke else 32
    plan = _chaos_plan(rate)
    eng = ServeEngine(cfg, params, max_lanes=4, pool_seq=64 if smoke else 128,
                      segment_len=8, machine=acc,
                      faults=plan.replay() if plan else None,
                      retry_backoff_s=0.0)
    prompts = _prompts(n_req, cfg.vocab_size)
    _drain_wave(eng, prompts, steps)        # warm: trace + compile
    seg0 = len(eng.segment_log)
    tps_runs = []
    for _ in range(2 if smoke else 3):
        toks, wall = _drain_wave(eng, prompts, steps)
        tps_runs.append(toks / max(wall, 1e-12))
    # each token takes its segment's wall over the segment length
    segs = eng.segment_log[seg0:]
    lat = np.repeat([s["wall_seconds"] / eng.segment_len for s in segs],
                    [s["tokens"] for s in segs])
    want = (1 + (2 if smoke else 3)) * n_req * steps
    drained = (not eng.queue and not eng.running
               and sum(len(r.generated) for r in eng.finished.values())
               == want)
    return {
        "rate": rate,
        "tokens_per_s": float(np.median(tps_runs)),
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "drained": bool(drained),
        "requests": len(eng.finished),
        "faults_injected": (len(eng.faults.trace)
                            if eng.faults is not None else 0),
        "health": eng.health.rollup(),
    }


def _case_train_resume(smoke: bool) -> dict:
    """Crash a compiled train mid-interval; the resume must replay exactly."""
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import AdamW
    from repro.optim.schedule import constant
    from repro.train.loop import TrainConfig, train

    cfg = dataclasses.replace(get_config("minicpm-2b", smoke=True),
                              num_layers=2, dtype="float32")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2,
                      seed=0)

    def once(ckpt_dir, faults, max_restarts):
        tcfg = TrainConfig(steps=8, ckpt_dir=ckpt_dir, ckpt_every=4,
                           log_every=100, max_restarts=max_restarts)
        return train(cfg, tcfg, AdamW(schedule=constant(1e-3)),
                     data_cfg=dcfg, log=lambda s: None, faults=faults)

    with tempfile.TemporaryDirectory() as d:
        base = once(d, None, 0)
    inj = FaultPlan([FaultSpec("dispatch_fail", at=(1,))]).replay()
    with tempfile.TemporaryDirectory() as d:
        res = once(d, inj, 2)
    want = [h["loss"] for h in base["history"]]
    got = [h["loss"] for h in res["history"]]
    return {
        "resumes": res["resumes"],
        "loss_history_exact": want == got,
        "health": res["health"]["count_by_code"],
    }


def run(smoke: bool = True, out_path: str = "BENCH_chaos.json"):
    """Yield CSV rows (benchmarks.run convention) and write the JSON file."""
    from repro.models import model as M

    acc = default_machine()
    cfg = _bench_cfg(smoke)
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    rates = {f"{r:g}": _run_rate(cfg, params, acc, r, smoke) for r in RATES}
    resume = _case_train_resume(smoke)

    r0, r20 = rates["0"], rates["0.2"]
    baseline = rates["0"]["tokens_per_s"]   # rate-0 engine IS the clean run…
    # …but measure one engine with no injector object at all, so "idle
    # injector costs ~nothing" is a real claim, not a tautology
    clean = _run_rate(cfg, params, acc, -1.0, smoke)
    report = {
        "benchmark": "chaos_serve", "smoke": smoke,
        "rates": rates, "clean_baseline": clean,
        "train_resume": resume,
        "degraded_frac": r20["tokens_per_s"] / max(baseline, 1e-12),
        "clean_frac": baseline / max(clean["tokens_per_s"], 1e-12),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    rows = []
    for key, r in rates.items():
        rows.append((f"chaos_tokens_per_s_r{key}", r["tokens_per_s"],
                     f"{r['faults_injected']} faults injected"))
        rows.append((f"chaos_latency_p99_ms_r{key}",
                     r["latency_p99_s"] * 1e3, ""))
        rows.append((f"chaos_drained_r{key}", float(r["drained"]),
                     f"{r['requests']} requests"))
    rows.append(("chaos_degraded_frac", report["degraded_frac"],
                 f"floor {FLOOR_DEGRADED}"))
    rows.append(("chaos_clean_frac", report["clean_frac"],
                 f"floor {FLOOR_CLEAN}"))
    rows.append(("chaos_train_resume_exact",
                 float(resume["loss_history_exact"]),
                 f"{resume['resumes']} resume(s)"))
    return rows


def check(rows) -> list[str]:
    """Floor violations for ``--check`` / ``benchmarks.run --check``."""
    vals = {n: v for n, v, _ in rows}
    problems = []
    for key in ("0", "0.05", "0.2"):
        if vals[f"chaos_drained_r{key}"] != 1.0:
            problems.append(f"wave at rate {key} did not fully drain")
    if vals["chaos_degraded_frac"] < FLOOR_DEGRADED:
        problems.append(
            f"20%-fault throughput {vals['chaos_degraded_frac']:.2f}x of "
            f"clean < floor {FLOOR_DEGRADED}")
    if vals["chaos_clean_frac"] < FLOOR_CLEAN:
        problems.append(
            f"idle-injector throughput {vals['chaos_clean_frac']:.2f}x of "
            f"baseline < floor {FLOOR_CLEAN}")
    if vals["chaos_train_resume_exact"] != 1.0:
        problems.append("resumed loss history diverged from uncrashed run")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if a fault wave fails to drain, "
                         "degraded throughput dips below the CI floor, or "
                         "crash-resume diverges")
    ap.add_argument("--out", default="BENCH_chaos.json")
    args = ap.parse_args()

    print("name,value,derived")
    rows = run(smoke=args.smoke, out_path=args.out)
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    if args.check:
        problems = check(rows)
        if problems:
            raise SystemExit("; ".join(problems))


if __name__ == "__main__":
    main()

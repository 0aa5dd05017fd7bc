"""Continuous-batching serve benchmark (writes ``BENCH_serve_batch.json``).

Measures the :class:`repro.launch.engine.ServeEngine` serving tier
(DESIGN.md §7):

* **tokens/sec vs batch** — engines at max_lanes 1, 2, 4, 8 each drain that
  many mixed-prompt-length requests; throughput should scale with occupancy
  because the packed hyperstep amortises the params stream and the dispatch
  barrier across lanes (the Eq. 1 admission argument, measured);
* **per-token latency** — p50/p99 over every harvested token at batch 8
  (a token's latency is its segment's wall time / segment_len);
* **admission decisions** — every Eq. 1-priced verdict
  (compute_bound/bandwidth_heavy) next to the verdict measured by the
  segment that followed it; ``--check`` requires at least one match;
* **chunked prefill** — token-at-a-time vs autotuned-block prefill wall time
  on one long prompt (the prefill half of the serving tier).

Floor (``--check``): engine decode throughput at batch 8 must be >= 4x the
sequential ``generate()`` decode throughput — continuous batching has to
actually pay, not just run.

Run:  python -m benchmarks.serve_batch [--smoke] [--check] [--out PATH]
Also exposed as ``benchmarks.run serve_batch`` CSV rows.
"""

from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.core.calibrate import default_machine
from repro.core.plan import median_seconds

BATCHES = (1, 2, 4, 8)
FLOOR_BATCH = 8
FLOOR_SPEEDUP = 4.0


def _bench_cfg(smoke: bool):
    """A decode shape whose batch-1 step is weight-streaming-bound.

    The smoke-tiny configs fit their weights in cache, so a packed step costs
    ~batch × the batch-1 step and batching has nothing to amortise. At
    ``d_model=512, vocab=16k`` the batch-1 decode is GEMV (every step streams
    the full weight set), which is precisely the shared term Eq. 1 says a
    packed batch amortises — measured step scaling b1→b8 is ~4.8x here.
    """
    from repro.configs import get_config
    cfg = get_config("minicpm-2b", smoke=True)
    layers = 2 if smoke else 4
    return dataclasses.replace(
        cfg, num_layers=layers, d_model=512, num_heads=8, num_kv_heads=8,
        d_ff=1536, vocab_size=16384, dtype="float32")


def _prompts(n: int, vocab: int, smoke: bool) -> list[np.ndarray]:
    rng = np.random.default_rng(0)
    lens = [4 + 3 * (i % 3) for i in range(n)] if smoke else \
           [8 + 5 * (i % 4) for i in range(n)]
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in lens]


def _drain(eng, prompts, steps: int) -> tuple[int, float]:
    """Submit + drain one wave; returns (tokens, decode wall seconds)."""
    seg0 = len(eng.segment_log)
    for i, p in enumerate(prompts):
        eng.submit(p, steps, seed=i)
    eng.run_until_drained()
    segs = eng.segment_log[seg0:]
    return (sum(s["tokens"] for s in segs),
            sum(s["wall_seconds"] for s in segs))


def _case_batch_sweep(smoke: bool, acc) -> dict:
    from repro.launch.engine import ServeEngine
    from repro.launch.serve import generate
    from repro.models import model as M

    cfg = _bench_cfg(smoke)
    steps = 16 if smoke else 32
    seg = 8
    pool_seq = 64 if smoke else 128
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    # Admission pricing uses the calibrated machine but with the link ratio
    # clamped: on a loaded CI host the measured e can be large enough that
    # Eq. 1 prices *every* batch width in the sweep bandwidth-heavy, pushing
    # the compute-bound boundary outside 1..8 and making the verdict-match
    # audit vacuous (all-heavy predictions vs replayed segments that stage
    # nothing). Clamping e keeps the boundary inside the swept range; the
    # throughput and latency numbers are real wall-clock either way.
    acc = dataclasses.replace(acc, e=min(acc.e, 60.0))

    sweep = {}
    latency = {}
    admission_rows = []
    for batch in BATCHES:
        eng = ServeEngine(cfg, params, max_lanes=batch, pool_seq=pool_seq,
                          segment_len=seg, machine=acc)
        prompts = _prompts(batch, cfg.vocab_size, smoke)
        _drain(eng, prompts, steps)          # warm: trace + compile the program
        tps_runs = []
        seg0 = len(eng.segment_log)
        for _ in range(3):
            toks, wall = _drain(eng, prompts, steps)
            tps_runs.append(toks / max(wall, 1e-12))
        stats = eng.stats()
        sweep[batch] = {
            "tokens_per_s": float(np.median(tps_runs)),
            "segments_per_wave": -(-steps // seg),
            "mean_occupancy": stats["mean_occupancy"],
            # runtime BSPS2xx rollup (DESIGN.md §10): a clean sweep shows
            # zero events; anything else names the code that fired
            "health": stats["health"],
        }
        if batch == FLOOR_BATCH:
            # each token takes its segment's wall over the segment length
            segs = eng.segment_log[seg0:]
            lat = np.repeat([s["wall_seconds"] / eng.segment_len for s in segs],
                            [s["tokens"] for s in segs])
            latency = {"p50_s": float(np.percentile(lat, 50)),
                       "p99_s": float(np.percentile(lat, 99))}
        admission_rows += [
            {k: a[k] for k in ("rid", "occupancy_before", "admit", "verdict",
                               "measured_verdict", "throughput_gain")}
            for a in eng.admission_log]

    # sequential baseline: one generate() per request, decode-only seconds
    prompt = np.asarray(_prompts(1, cfg.vocab_size, smoke)[0][None, :])
    generate(cfg, params, prompt, steps=steps, machine=acc,
             max_len=pool_seq)               # warm
    seq_s = median_seconds(lambda: generate(
        cfg, params, prompt, steps=steps, machine=acc,
        max_len=pool_seq)[1].decode_total_seconds)
    _, stats = generate(cfg, params, prompt, steps=steps, machine=acc,
                        max_len=pool_seq)
    seq_tps = steps / max(stats.decode_total_seconds, 1e-12)

    matches = sum(1 for a in admission_rows
                  if a["measured_verdict"] == a["verdict"])
    return {
        "sweep": sweep,
        "latency": latency,
        "sequential_tokens_per_s": seq_tps,
        "sequential_decode_seconds": float(seq_s),
        "batch8_speedup_vs_sequential":
            sweep[FLOOR_BATCH]["tokens_per_s"] / max(seq_tps, 1e-12),
        "admission": {
            "decisions": len(admission_rows),
            "verdict_matches": matches,
            "rows": admission_rows,
        },
    }


def _case_prefill(smoke: bool, acc) -> dict:
    from repro.launch.serve import make_prefill, prefill_block_size
    from repro.models import model as M
    import jax.numpy as jnp

    cfg = _bench_cfg(smoke)
    prompt_len = 64 if smoke else 256
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          size=(1, prompt_len)), jnp.int32)
    block = prefill_block_size(cfg, 1, prompt_len, acc)

    def run_block(b: int) -> float:
        fn = make_prefill(cfg, b)
        def once():
            cache = M.init_cache(cfg, 1, prompt_len)
            logits, _ = fn(params, cache, prompt)
            jax.block_until_ready(logits)
        return median_seconds(once)

    token_s = run_block(1)
    chunk_s = run_block(block)
    return {
        "prompt_len": prompt_len,
        "autotuned_block": block,
        "token_at_a_time_seconds": token_s,
        "chunked_seconds": chunk_s,
        "speedup": token_s / max(chunk_s, 1e-12),
    }


def run(smoke: bool = True, out_path: str = "BENCH_serve_batch.json"):
    """Yield CSV rows (benchmarks.run convention) and write the JSON file."""
    acc = default_machine()
    batch = _case_batch_sweep(smoke, acc)
    prefill = _case_prefill(smoke, acc)
    report = {"benchmark": "serve_batch", "smoke": smoke,
              "batch": batch, "prefill": prefill}
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    rows = []
    for b in BATCHES:
        rows.append((f"serve_batch_tokens_per_s_b{b}",
                     batch["sweep"][b]["tokens_per_s"], ""))
    rows.append(("serve_batch_sequential_tokens_per_s",
                 batch["sequential_tokens_per_s"], ""))
    rows.append(("serve_batch8_speedup_vs_sequential",
                 batch["batch8_speedup_vs_sequential"],
                 f"floor {FLOOR_SPEEDUP}"))
    rows.append(("serve_batch_latency_p50_ms",
                 batch["latency"]["p50_s"] * 1e3, "batch 8"))
    rows.append(("serve_batch_latency_p99_ms",
                 batch["latency"]["p99_s"] * 1e3, "batch 8"))
    rows.append(("serve_batch_admission_matches",
                 batch["admission"]["verdict_matches"],
                 f"of {batch['admission']['decisions']} decisions"))
    rows.append(("serve_batch_prefill_speedup", prefill["speedup"],
                 f"block {prefill['autotuned_block']}"))
    return rows


def check(rows) -> list[str]:
    """Floor violations for ``--check`` / ``benchmarks.run --check``."""
    vals = {n: v for n, v, _ in rows}
    problems = []
    if vals["serve_batch8_speedup_vs_sequential"] < FLOOR_SPEEDUP:
        problems.append(
            f"batch-8 speedup {vals['serve_batch8_speedup_vs_sequential']:.2f} "
            f"< floor {FLOOR_SPEEDUP}")
    if vals["serve_batch_admission_matches"] < 1:
        problems.append("no admission verdict matched measurement")
    if vals["serve_batch_prefill_speedup"] < 1.0:
        problems.append(
            f"chunked prefill slower than token-at-a-time "
            f"({vals['serve_batch_prefill_speedup']:.2f}x)")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if batch-8 throughput < "
                         f"{FLOOR_SPEEDUP}x sequential, no admission verdict "
                         "matched measurement, or chunked prefill lost")
    ap.add_argument("--out", default="BENCH_serve_batch.json")
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

"""Continuous-batching example: the ServeEngine draining a mixed workload.

Submits a handful of requests with different prompt lengths and generation
budgets, lets the engine pack them into segment-sized decode hypersteps
(one compiled dispatch per segment), and prints the lifecycle: Eq. 1-priced
admission decisions, per-segment occupancy, page-table churn, and the final
throughput/latency stats (DESIGN.md §7).

Run: PYTHONPATH=src python examples/serve_engine.py
     (defaults to a smoke-sized attention arch; --lanes/--segment to resize)
"""

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import ServeEngine
from repro.models import model as M


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--segment", type=int, default=8)
    ap.add_argument("--pool-seq", type=int, default=96)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_lanes=args.lanes,
                      pool_seq=args.pool_seq, segment_len=args.segment,
                      temperature=args.temperature)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt_len = int(rng.integers(4, 17))
        steps = int(rng.integers(args.segment, 3 * args.segment))
        prompt = rng.integers(0, cfg.vocab_size, size=prompt_len)
        rid = eng.submit(prompt, steps, seed=i)
        print(f"submit rid={rid} prompt={prompt_len} tokens, gen={steps}")

    out = eng.run_until_drained()

    print("\nadmission decisions (Eq. 1 priced):")
    for a in eng.admission_log:
        print(f"  seg {a['segment']:>2}  rid {a['rid']}  B={a['occupancy_before']}"
              f"->{a['occupancy_before'] + a['admit']}  "
              f"predicted={a['verdict']:<15} measured={a['measured_verdict']:<15} "
              f"admit={a['admit']}")

    print("\nsegments:")
    for s in eng.segment_log:
        print(f"  seg {s['segment']:>2}  occupancy={s['occupancy']}  "
              f"{s['tokens']} tokens in {s['wall_seconds'] * 1e3:.1f}ms  "
              f"({s['tokens_per_s']:.0f} tok/s)")

    pages = eng.pool.table
    print(f"\npage table: {pages.num_pages} pages x {pages.page_tokens} tokens, "
          f"{len(pages.history)} assignments over the run "
          f"({pages.free_pages} free at drain)")

    stats = eng.stats()
    segs = eng.segment_log
    decode_s = sum(s["wall_seconds"] for s in segs)
    # each token takes its segment's wall over the segment length
    per_token = np.repeat([s["wall_seconds"] / eng.segment_len for s in segs],
                          [s["tokens"] for s in segs])
    print(f"\n{stats['requests']} requests, {stats['tokens']} tokens, "
          f"{stats['tokens'] / max(decode_s, 1e-12):.0f} tok/s decode, "
          f"p50={np.percentile(per_token, 50) * 1e3:.2f}ms "
          f"p99={np.percentile(per_token, 99) * 1e3:.2f}ms per token, "
          f"mean occupancy {stats['mean_occupancy']:.1f}")
    first = min(out)
    print(f"rid {first} tokens: {out[first][:24].tolist()}")


if __name__ == "__main__":
    main()

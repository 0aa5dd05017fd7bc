"""Find the knee of an open-loop serve cell: the highest offered rate the
program sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3,4,6 \
        [--sizes-seeds 0,1]

A tool to run by hand on the chip, once, when a cell's rate is chosen; the
benchmark's runs never call it. It builds and warms the cell's engine once,
then drives one window per rate and arrival schedule (the cell's traffic at
that rate, its sizes and gaps drawn from each of ``--sizes-seeds``), and
prints for each the requests due and finished, the backlog left at the
window's end, the time to first token's median and 90th percentile, and
the output tokens per second. The rate written into the cell's traffic file
is four fifths of the highest rate whose backlog stays flat on every
schedule.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--sizes-seeds", default="0",
                    help="comma-separated seeds of the arrival schedule")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import numpy as np

    from bench import spec
    from bench.drivers import serve
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = spec.cell(args.workload)
    eng, gen = serve.build(cell, args.seed)
    serve.warm((eng, gen))
    runs = [(float(r), int(z)) for r in args.rates.split(",")
            for z in args.sizes_seeds.split(",")]
    for rate, sizes_seed in runs:
        c = copy.copy(cell)
        c.traffic = copy.deepcopy(cell.traffic)
        c.traffic["arrivals"]["rate_per_s"] = rate
        c.traffic["sizes_seed"] = sizes_seed
        g = spec.generator(c, args.seed)
        rec = serve.window((eng, g), args.seconds, jax.profiler.TraceAnnotation)
        reqs = rec["requests"]
        ttft = [(r.first if r.first is not None else rec["t1"]) - r.due for r in reqs]
        row = {"rate_per_s": rate, "sizes_seed": sizes_seed, "due": len(reqs),
               "finished": sum(r.done is not None for r in reqs),
               "backlog_at_end": len(eng.queue) + len(eng.running),
               "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
               "ttft_p90_s": float(np.percentile(ttft, 90)) if ttft else None,
               "out_tok_s": serve.e2e(rec)["out_tok_s"]}
        print(json.dumps(row), flush=True)
        while eng.queue or eng.running:       # drain before the next rate
            eng.step_segment()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Record the small chip trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py [--out bench/tests/data/small_trace.xplane.pb]

Run it on a TPU, as the only process on the chip. It warms one jitted
program that calls both Pallas kernels of the main path (the streamed
matmul and the flash attention) and an XLA dot, then traces three calls of
it inside harness spans, with a 50 ms host sleep between the second and the
third, and copies the profiler's ``.xplane.pb`` to ``--out``. It prints each
plane, its lines and a few events with their stats, so that the names the
reduction in ``bench/trace.py`` matches can be read off by hand.
"""

from __future__ import annotations

import argparse
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402

SLEEP_S = 0.05


def program(a, b, q, k, v):
    c = ops.matmul(a, b)
    o = ops.attention(q, k, v)
    return c, o, jnp.dot(a, b, preferred_element_type=jnp.float32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "bench/tests/data"
                                         / "small_trace.xplane.pb"))
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    a = jax.random.normal(keys[0], (256, 512), jnp.bfloat16)
    b = jax.random.normal(keys[1], (512, 256), jnp.bfloat16)
    q, k, v = (jax.random.normal(kk, (1, 2, 256, 64), jnp.bfloat16)
               for kk in keys[2:])
    fn = jax.jit(program)
    jax.block_until_ready(fn(a, b, q, k, v))
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                if i == 2:
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.segment"):
                    jax.block_until_ready(fn(a, b, q, k, v))
        jax.profiler.stop_trace()
        path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, args.out)
    data = jax.profiler.ProfileData.from_file(args.out)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                print(f"    {ev.name!r} {ev.start_ns} {ev.duration_ns} "
                      f"{dict(ev.stats)}")
    print(f"wrote {args.out} ({Path(args.out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

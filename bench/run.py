"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; everything it needs is found by
name under ``bench/`` (see ``bench/spec.py``). A run draws its weights and
traffic from ``--seed``, warms every shape its traffic uses (set-up), drives
the program for ``--seconds`` (the window), reads the device's peak memory,
frees the program and compares a sample of what the window produced with
the plain float32 reference (its family's, ``bench/families``). The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace), ``device``, ``breakdown``
(traced runs) and ``compared``: each number the comparison judged, beside
its limit. Counts, compiles seen in the window and the other readings go
to standard error first; the compared numbers are its last lines.

A traced run traces the window's first ``trace_seconds`` (a key of the
cell's workload file; the whole window where it is absent), inside a
``bench.traced`` span, and reads every per-layer metric over that part of
the window alone. The window then runs on untraced to its end (stopping
the profiler takes a while), and the comparison draws its sample from it
as in an untraced run.

A run needs a TPU with as many chips as the cell asks for: on any other
platform it exits non-zero and prints no result. JAX's persistent
compilation cache lives in ``.jax_cache`` beside ``bench/``, so that only a
checkout's first run of a cell compiles. With ``--control`` the fp8
control, the reference one step of precision below the configuration's,
takes the program's place in the comparison: the same run is then judged
on the control's numbers, and has to come out not ``correct``. Benchmark
runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
clock = time.perf_counter


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


class Compiles:
    """Counts the XLA compilations (persistent-cache hits included) made
    while it is open."""

    def __enter__(self):
        import jax
        from jax._src import dispatch

        self.n = 0

        def listen(event, duration, **_):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.n += 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._listen)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def metric_readers(cell) -> dict:
    out = {}
    for m in cell.per_layer:
        path = ROOT / "bench" / "metrics" / f"{m['name']}.py"
        spec_ = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        out[m["name"]] = (mod.read, m["unit"])
    return out


class Tracer:
    """The profiler over the window's first ``seconds``, inside a
    ``bench.traced`` span. ``poll``, called between segments, stops it
    once they have passed; ``stop`` stops it if it still runs."""

    def __init__(self, logdir: str, seconds: float):
        self.logdir, self.seconds = logdir, seconds
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()
        self.t0 = clock()

    def poll(self) -> None:
        if self.t1 is None and clock() - self.t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.t1 is not None:
            return
        self.t1 = clock()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def reduce_trace(path: str, cell, rec: dict, peak: dict) -> tuple[dict, dict]:
    """(summary for the readers, breakdown) of the traced part of the window.
    Every kernel with a cost file (``bench/kernels``) and calls in the trace
    gets its calls' device time and least time."""
    from bench import flops, spec, trace as T

    tr = T.load(path)
    lo, hi = tr.window()
    say("ops per run of each program in the trace (runs, fewest, most): "
        + json.dumps(T.ops_per_run(tr)))
    pending = rec.get("pending")
    within = None
    if pending is not None:                 # host clock -> trace clock
        off = lo - rec["t0"]
        within = T.clip(T.merge([(s + off, e + off) for s, e in pending]), lo, hi)
    kernels = {}
    for name, kernel in sorted(spec.kernels().items()):
        calls = T.kernel_calls(tr, name)
        if not calls:
            continue
        least = [flops.least_seconds(*kernel.cost(k.operands, cell.config, cell.family), peak)
                 for k in calls]
        kernels[name] = {"calls": len(calls),
                         "seconds": sum(k.device_seconds for k in calls),
                         "fed_s": sum(k.fed_seconds for k in calls),
                         "least_s": sum(t for t, _ in least),
                         "compute_bound_s": sum(t for t, b in least if b == "compute"),
                         "max_call_share": max((t / k.device_seconds
                                                for k, (t, _) in zip(calls, least)
                                                if k.device_seconds > 0), default=None)}
        by_shape: dict[str, list[float]] = {}
        for k, (t, _) in zip(calls, least):
            row = by_shape.setdefault(str(k.operands), [0, 0.0, 0.0, 0.0, 0.0])
            row[0] += 1
            row[1] += k.seconds
            row[2] += k.fed_seconds
            row[3] += t
            if k.device_seconds > 0:
                row[4] = max(row[4], t / k.device_seconds)
        say(f"{name} by operand shapes: [calls, kernel s, fed s, least s, "
            f"largest share of one call] " + json.dumps(by_shape))
    summary = {"busy_s": T.busy(tr), "window_s": hi - lo, "bounds": [lo, hi],
               "kernels": kernels}
    if within is not None:
        summary.update(busy_pending_s=T.busy(tr, within), pending_s=T.total(within))
    return summary, {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_breakdown(tr)}


def run_cell(cell, seed: int, seconds: float, trace: bool, *, control: bool = False,
             t_start: float | None = None) -> dict:
    """One run; returns the result line's object. Needs no chip: the caller
    has made sure there is one. The cell's driver (``bench/drivers``) builds,
    warms and drives the system and judges what it produced."""
    import jax

    from bench import peaks

    t_start = clock() if t_start is None else t_start
    annotate = jax.profiler.TraceAnnotation
    driver = cell.driver
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        t_build = clock()
        sut = driver.build(cell, seed, tmp)
        t_warm = clock()
        warmed = driver.warm(sut)
        setup_s = clock() - t_start
        say(f"set-up {setup_s:.3f} s: start {t_build - t_start:.3f}, build "
            f"{t_warm - t_build:.3f}, warm {clock() - t_warm:.3f}")
        tracer = None
        if trace:
            tracer = Tracer(os.path.join(tmp, "trace"),
                            cell.workload.get("trace_seconds", seconds))
            tracer.start()
        tick = tracer.poll if tracer else None
        with Compiles() as compiles:
            rec = driver.window(sut, seconds, annotate, tick)
        in_window = compiles.n
        if tracer:
            tracer.stop()
        device = device_info()
        del sut
        gc.collect()

        e2e = driver.e2e(rec)
        counts = e2e.pop("counts")
        say(f"window {rec['t1'] - rec['t0']:.4f} s, counts {json.dumps(counts)}, "
            f"compiles in the window {in_window}, peak HBM {device['memory_peak_bytes']}")
        limits = cell.workload["check"]["limits"]
        chk = driver.check(cell, seed, rec, warmed, control=control)
        attempted, failed = driver.tally(cell, rec, warmed)
        if control:             # the control in the program's place
            say("the program's own numbers " + json.dumps({k: chk.get(k) for k in limits}))
            chk = {**chk, **{k: chk[f"control_{k}"] for k in limits if f"control_{k}" in chk}}
        compared = {k: {"value": chk.get(k), "limit": lim} for k, lim in limits.items()}
        correct = all(v["value"] is not None and v["value"] <= v["limit"]
                      for v in compared.values())
        say("check " + json.dumps({k: v for k, v in chk.items()
                                   if k not in compared and not k.startswith("control_")}))

        if trace:
            peak = peaks.peaks(device["kind"])
            path = next(Path(tracer.logdir).glob("plugins/profile/*/*.xplane.pb"))
            rec = driver.upto(rec, tracer.t1)
            say(f"traced {tracer.t1 - tracer.t0:.4f} s of the window; per-layer metrics "
                f"over its first {rec['t1'] - rec['t0']:.4f} s")
            rec["window_s"] = rec["t1"] - rec["t0"]
            rec["peak"] = peak
            rec.update(driver.for_readers(cell, rec))
            summary, breakdown = reduce_trace(str(path), cell, rec, peak)
            rec["trace"] = summary
            device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
            say("trace " + json.dumps(summary))
            metrics = {}
            for name, (read, unit) in metric_readers(cell).items():
                value = read(rec)
                if value is not None:
                    metrics[name] = {"value": value, "unit": unit}
        else:
            metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
            for m in cell.end_to_end:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        for k, v in compared.items():
            say(f"compared {k}{' (control)' if control else ''}: {v['value']} "
                f"(limit {v['limit']})")
        out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
               "metrics": metrics, "device": device}
        if trace:
            out["breakdown"] = breakdown
        out["compared"] = compared
        return out
    finally:
        gc.unfreeze()
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the fp8 control's compared numbers")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        say(f"no program beside the benchmark: {ROOT / 'src' / 'repro'} is missing")
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import spec
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        say(f"needs {cell.chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 3
    say(f"compilation cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=args.control, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

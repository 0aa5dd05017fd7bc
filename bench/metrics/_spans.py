"""What the readers of the program's own spans share.

The serve engine (``launch/engine.py``) and the hyperstep runtime
(``core/hyperstep.py``) open ``jax.profiler.TraceAnnotation`` spans at their
host-side boundaries: ``engine.segment`` holds ``engine.admit``,
``engine.join`` (with ``engine.prefill`` and ``engine.scatter``),
``engine.plan``, ``runtime.dispatch``, ``engine.harvest`` and
``engine.account`` (with ``engine.recalibrate`` when a drift refit runs);
``runtime.dispatch`` holds ``runtime.compile``, ``runtime.stage``,
``runtime.scan``, ``runtime.check``, ``runtime.drain`` and
``runtime.record``. A traced run's profile holds them on its host plane, on
the device planes' clock, each with its arguments (``rid``, ``prompt_len``,
``queued_s``, ...) as event stats.

``bench/run.py`` gives a reader the run's record, not the profile, so
``spans`` takes the profile's path from the harness's ``Tracer`` in the
frames that called the reader. It reads the profile once, keeps the program
spans of the traced part in ``rec["trace"]["spans"]`` for the next reader,
and prints what they hold on standard error: each span's count, seconds and
the seconds no nested span covers, then device 0's idle seconds summed by
the innermost span open at each gap's middle (harness spans included), and
the longest gaps so named.
"""

from __future__ import annotations

import bisect
import heapq
import json
import sys
import time
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench import trace as T
from bench.spec import say

PROGRAM = ("engine.", "runtime.")


class Span(NamedTuple):
    start: float        # seconds, on the device planes' clock
    end: float
    name: str
    args: dict


def _order(s: Span) -> tuple[float, float]:
    return s.start, s.end


def parse(name: str, stats=()) -> tuple[str, dict]:
    """A host event's span name and arguments: the arguments are its stats,
    or a ``#k=v,...#`` suffix of its name where the profiler left them
    encoded there."""
    base, _, rest = name.partition("#")
    args = dict(kv.split("=", 1) for kv in rest.rstrip("#").split(",") if "=" in kv)
    args.update(stats)
    return base, args


def read_profile(path: str):
    """(every harness and program span, device 0's ops as (starts, ends)
    arrays) of a profile, times in seconds."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans, ops = [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and ops is None:
            ops = np.zeros((0, 2))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = np.array([(ev.start_ns, ev.start_ns + ev.duration_ns)
                                    for ev in line.events], np.float64).reshape(-1, 2) * 1e-9
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", *PROGRAM)):
                        name, args = parse(ev.name, ev.stats)
                        spans.append(Span(ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns) * 1e-9, name, args))
    spans.sort(key=_order)
    if ops is None:
        ops = np.zeros((0, 2))
    return spans, (ops[:, 0], ops[:, 1])


def _profile_path() -> str | None:
    frame = sys._getframe()
    while frame is not None:
        tracer = frame.f_locals.get("tracer")
        if isinstance(getattr(tracer, "logdir", None), str):
            found = sorted(Path(tracer.logdir).glob("plugins/profile/*/*.xplane.pb"))
            return str(found[-1]) if found else None
        frame = frame.f_back
    return None


def spans(rec: dict) -> list[Span] | None:
    """The program's spans that lie inside the traced part, in start order;
    None where the run was not traced."""
    t = rec.get("trace")
    if not t:
        return None
    if "spans" not in t:
        path = _profile_path()
        if path is None:
            return None
        began = time.perf_counter()
        every, (starts, ends) = read_profile(path)
        lo, hi = t.get("bounds") or T.Trace(
            devices=[], spans=[(s.start, s.end, s.name) for s in every]).window()
        inside = [s for s in every if lo <= s.start and s.end <= hi]
        t["spans"] = [s for s in inside if s.name.startswith(PROGRAM)]
        idle = gaps(starts, ends, lo, hi) if len(starts) else ([], [])
        report(inside, idle, time.perf_counter() - began)
    return t["spans"]


def covered(outer: Span, inner: list[Span]) -> float:
    """Seconds of ``outer`` that the spans ``inner`` (in start order) nested
    in it cover."""
    lo = bisect.bisect_left(inner, (outer.start,))
    hi = bisect.bisect_right(inner, (outer.end, float("inf")))
    return T.total(T.merge([(s.start, s.end) for s in inner[lo:hi]
                            if s.end <= outer.end and s is not outer]))


def host_ms(found, outer: str, device: tuple[str, ...]):
    """Median over the ``outer`` spans of the milliseconds that no span
    named in ``device`` inside it covers: the host's share of each."""
    if not found:
        return None
    inner = sorted((s for s in found if s.name in device), key=_order)
    rows = [(s.end - s.start) - covered(s, inner) for s in found if s.name == outer]
    return 1e3 * float(np.median(rows)) if rows else None


def gaps(starts, ends, lo: float, hi: float) -> tuple[np.ndarray, np.ndarray]:
    """(starts, ends) of the idle intervals inside [lo, hi] of a device whose
    ops ran over (starts, ends): those of ``bench.trace.gaps``, on arrays,
    since a long trace holds millions of ops."""
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    keep = (e > lo) & (s < hi)
    s, e = np.clip(s[keep], lo, hi), np.clip(e[keep], lo, hi)
    left = np.concatenate([[lo], np.maximum.accumulate(e)]) if len(e) else np.array([lo])
    right = np.concatenate([s, [hi]])
    m = right > left
    return left[m], right[m]


def innermost(found) -> tuple[np.ndarray, list[str]]:
    """(points, names): on [points[k], points[k + 1]) the innermost span
    open, the shortest that holds it as ``bench.trace.open_span`` takes, is
    names[k] ("none" where no span is open)."""
    order = sorted(found, key=_order)
    points = sorted({x for s in order for x in (s.start, s.end)})
    heap: list[tuple[float, float, str]] = []
    names, i = [], 0
    for t in points:
        while i < len(order) and order[i].start <= t:
            s = order[i]
            heapq.heappush(heap, (s.end - s.start, s.end, s.name))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        names.append(heap[0][2] if heap else "none")
    return np.asarray(points), names


def idle_by_span(found, idle) -> list[list]:
    """Idle seconds of the gaps ``idle`` (starts, ends) summed by the
    innermost span open at each gap's middle, largest first."""
    points, names = innermost(found)
    left, right = (np.asarray(x, np.float64) for x in idle)
    k = np.searchsorted(points, (left + right) / 2, side="right") - 1
    k[k < 0] = len(names)
    sums = np.bincount(k, weights=right - left, minlength=len(names) + 1)
    acc: dict[str, float] = {}
    for name, v in zip(names + ["none"], sums.tolist()):
        if v > 0:
            acc[name] = acc.get(name, 0.0) + v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]


def self_seconds(found) -> dict[str, list]:
    """For each program span name: [count, seconds, seconds no span nested
    in it covers]."""
    out: dict[str, list] = {}
    program = sorted((s for s in found if s.name.startswith(PROGRAM)), key=_order)
    for s in program:
        row = out.setdefault(s.name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += s.end - s.start
        row[2] += (s.end - s.start) - covered(s, program)
    return out


def report(found, idle, read_s: float) -> None:
    say(f"program spans of the traced part (read in {read_s:.3f} s): "
        "{name: [count, s, s no nested span covers]} "
        + json.dumps(self_seconds(found)))
    left, right = idle
    if not len(left):
        return
    say("device 0 idle s by the innermost span open at each gap's middle "
        + json.dumps(idle_by_span(found, idle)))
    points, names = innermost(found)
    longest = np.argsort(left - right, kind="stable")[:10]
    k = np.searchsorted(points, (left[longest] + right[longest]) / 2, side="right") - 1
    say("longest idle gaps [span, s] " + json.dumps(
        [[names[j] if j >= 0 else "none", float(right[g] - left[g])]
         for g, j in zip(longest, k)]))

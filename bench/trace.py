"""The reduction from a profiler trace to busy time, idle gaps and kernel times.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes
are named ``/device:TPU:<n>``; each op that ran is an event on their
``XLA Ops`` line, named by its HLO instruction text, as in
``%streamed_matmul.1 = bf16[256,256]{...} custom-call(bf16[256,512]{...} %a,
bf16[512,256]{...} %b), custom_call_target="tpu_custom_call", ...``. A
Pallas kernel's instruction carries the name of the jitted function that
wraps its ``pallas_call`` (``streamed_matmul``, ``flash_attention``), and its
operands' shapes. Each run of a compiled program is an event on the
``XLA Modules`` line. The harness's own spans (``bench.*``) and the
program's (``engine.*``, ``runtime.*``), all from
``jax.profiler.TraceAnnotation``, are events on the host plane, on the same
clock to within about a millisecond; ``bench.traced`` marks the part of the
window that was traced. An op that runs others inside it (a scan's
``while``, a ``conditional``) is an event that encloses theirs.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

_OP = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = (\S+?)(?:\{[^}]*\})? ")
_KERNEL = re.compile(r"^%([A-Za-z_]\w*?)(?:\.\d+)? = .*?custom-call\((.*?)\), "
                     r"custom_call_target=\"tpu_custom_call\"")
SPANS = ("bench.", "engine.", "runtime.")
_ARG = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|u8|pred)\[([\d,]*)\](\{[^}]*\})? %([\w.-]+)")


@dataclasses.dataclass
class KernelCall:
    name: str
    seconds: float
    operands: list[tuple[str, tuple[int, ...]]]   # (dtype, shape)
    fed_seconds: float = 0.0    # the ops that brought its operands into VMEM

    @property
    def device_seconds(self) -> float:
        return self.seconds + self.fed_seconds


@dataclasses.dataclass
class Trace:
    """What one traced window holds."""
    devices: list[list[tuple[float, float, str]]]  # per device: (start s, end s, op)
    spans: list[tuple[float, float, str]]          # host spans: (start, end, name)
    copies: list[list[tuple[float, float, str]]] = dataclasses.field(default_factory=list)
    # per device, the asynchronous copies (``Async XLA Ops``)
    modules: list[list[tuple[float, float, str]]] = dataclasses.field(default_factory=list)
    # per device, the runs of compiled programs (``XLA Modules``)

    def window(self) -> tuple[float, float]:
        """The ``bench.traced`` span, else the ``bench.window`` span, else the
        extent of all device ops."""
        for want in ("bench.traced", "bench.window"):
            for s, e, n in self.spans:
                if n == want:
                    return s, e
        ops = [op for dev in self.devices for op in dev]
        return min(o[0] for o in ops), max(o[1] for o in ops)


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, copies, modules, spans = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                                  ev.name) for ev in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "Async XLA Ops", "XLA Modules")}
            devices.append(lines.get("XLA Ops", []))
            copies.append(lines.get("Async XLA Ops", []))
            modules.append(lines.get("XLA Modules", []))
        elif plane.name.startswith("/host:"):
            spans += [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                       ev.name.partition("#")[0])
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPANS)]
    return Trace(devices=devices, spans=sorted(spans), copies=copies, modules=modules)


def merge(intervals) -> list[tuple[float, float]]:
    """The union of (start, end) intervals, as disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(trace: Trace, within=None) -> float:
    """Seconds in which some op ran, averaged over the devices; counted only
    inside ``within`` (disjoint intervals) when given, else in the window."""
    lo, hi = trace.window()
    parts = within if within is not None else [(lo, hi)]
    out = []
    for dev in trace.devices:
        b = merge(dev)
        out.append(sum(total(clip(b, s, e)) for s, e in parts))
    return sum(out) / max(len(out), 1)


def gaps(trace: Trace, device: int = 0) -> list[tuple[float, float]]:
    """The idle intervals of one device inside the window."""
    lo, hi = trace.window()
    b = clip(merge(trace.devices[device]), lo, hi)
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def open_span(trace: Trace, t: float) -> str:
    """The innermost span open at instant ``t``, the harness's or the
    program's."""
    best, width = "none", float("inf")
    for s, e, n in trace.spans:
        if s <= t <= e and e - s < width:
            best, width = n, e - s
    return best


def idle_breakdown(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of device 0, each named by the innermost
    span open at its middle."""
    if not trace.devices:
        return []
    g = sorted(gaps(trace), key=lambda iv: iv[0] - iv[1])[:n]
    return [[open_span(trace, (s + e) / 2), e - s] for s, e in g]


def op_key(name: str) -> str:
    """An op's instruction name without its number, with its result type."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.split(" = ")[0]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` op kinds that took most device time in the window, device 0,
    each op by its self time: its own time less that of the ops nested in
    it, so that a scan's ``while`` does not count its body again."""
    lo, hi = trace.window()
    acc: dict[str, float] = {}
    open_: list[tuple[float, str]] = []        # (end, kind) of the enclosing ops
    for s, e, name in sorted((trace.devices or [[]])[0], key=lambda op: (op[0], -op[1])):
        while open_ and open_[-1][0] <= s:
            open_.pop()
        own = max(0.0, min(e, hi) - max(s, lo))
        k = op_key(name)
        acc[k] = acc.get(k, 0.0) + own
        if open_ and e <= open_[-1][0]:      # nested in the op below it
            parent = open_[-1][1]
            acc[parent] = acc.get(parent, 0.0) - own
        open_.append((e, k))
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _by_name(events) -> dict[str, list[tuple[float, float]]]:
    out: dict[str, list[tuple[float, float]]] = {}
    for s, e, name in events:
        out.setdefault(name.split(" ", 1)[0].lstrip("%"), []).append((s, e))
    for v in out.values():
        v.sort()
    return out


def _last_before(runs: list[tuple[float, float]], t: float) -> tuple[float, float] | None:
    """The last (start, end) in ``runs`` that started by ``t``."""
    i = bisect.bisect_right(runs, (t, float("inf"))) - 1
    return runs[i] if i >= 0 else None


def _fed(arg: str, t: float, ops: dict, copies: dict) -> float:
    """The seconds that brought operand ``arg`` of a call starting at ``t``
    into VMEM. For an asynchronous copy, from the issue of its
    ``copy-start`` to the end of its ``copy-done``, or the copy's own event
    where that is longer: the whole time the transfer may have taken, other
    ops running beside it included. For any other op, its run."""
    if not arg.startswith("copy-done"):
        run = _last_before(ops.get(arg, []), t)
        return run[1] - run[0] if run else 0.0
    start = arg.replace("copy-done", "copy-start")
    done = _last_before(ops.get(arg, []), t)
    if done is None:
        return 0.0
    issued = _last_before(ops.get(start, []), done[0])
    moved = _last_before(copies.get(start, []), done[0])
    spans = [done[1] - issued[0] if issued else done[1] - done[0]]
    if moved:
        spans.append(moved[1] - moved[0])
    return max(spans)


def kernel_calls(trace: Trace, kernel: str) -> list[KernelCall]:
    """Every call of the Pallas kernel wrapped by the jitted function
    ``kernel`` in the window, on every device. An operand the call reads
    from VMEM (memory space ``S(1)``) was brought there by another op (an
    asynchronous copy, or the op that computed it); that op's time (see
    ``_fed``) is the call's ``fed_seconds``."""
    lo, hi = trace.window()
    out = []
    for d, dev in enumerate(trace.devices):
        ops = _by_name(dev)
        copies = _by_name(trace.copies[d] if d < len(trace.copies) else [])
        for s, e, name in dev:
            if s < lo or e > hi or not name.startswith(f"%{kernel}"):
                continue
            m = _KERNEL.match(name)
            if not (m and m.group(1) == kernel):
                continue
            args = _ARG.findall(m.group(2))
            fed = sum(_fed(arg, s, ops, copies) for _, _, layout, arg in args
                      if "S(1)" in (layout or ""))
            out.append(KernelCall(kernel, e - s,
                                  [(t, tuple(int(x) for x in dims.split(",") if x))
                                   for t, dims, _, _ in args], fed))
    return out


def ops_per_run(trace: Trace, device: int = 0) -> dict[str, tuple[int, int, int]]:
    """For each compiled program run in the window: (runs, fewest ops in a
    run, most ops in a run). A program without data-dependent control flow
    runs the same ops every time, so fewer ops in some runs than in others
    means the profiler dropped events."""
    if device >= len(trace.modules):
        return {}
    lo, hi = trace.window()
    starts = sorted(s for s, _, _ in trace.devices[device])
    counts: dict[str, list[int]] = {}
    for s, e, name in trace.modules[device]:
        if s >= lo and e <= hi:
            n = bisect.bisect_right(starts, e) - bisect.bisect_left(starts, s)
            counts.setdefault(name.split("(")[0], []).append(n)
    return {k: (len(v), min(v), max(v)) for k, v in counts.items()}

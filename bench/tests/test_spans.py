"""The program's own spans (``engine.*``, ``runtime.*``) and their readers:
what a traced tiny serve run and a traced tiny train segment hold, each
reader on a hand-built record, idle gaps named by the innermost program
span, and the harness's reductions of the recorded small trace reading what
they read before the program had spans."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from bench import trace as T
from bench.metrics import _spans
from bench.metrics._spans import Span

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data" / "small_trace.xplane.pb"
ENGINE = {"engine.segment", "engine.admit", "engine.join", "engine.prefill",
          "engine.scatter", "engine.plan", "engine.harvest", "engine.account"}
RUNTIME = {"runtime.dispatch", "runtime.stage", "runtime.scan", "runtime.drain",
           "runtime.record"}


def reader(name: str):
    spec_ = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod.read


def traced_run(monkeypatch, name: str) -> tuple[dict, dict]:
    """(result line, record) of a traced run of the tiny ``name`` cell."""
    from bench import peaks, run as R
    from bench.tests import tiny

    cell = tiny.cell(name)
    cell.workload["trace_seconds"] = 1.0
    seen = {}
    reduce = R.reduce_trace

    def spy(path, cell, rec, peak):
        seen["rec"] = rec
        return reduce(path, cell, rec, peak)

    monkeypatch.setattr(R, "reduce_trace", spy)
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    out = R.run_cell(cell, 2**33 + 11, 2.0, True)
    assert out["correct"]
    return out, seen["rec"]


def inside(inner: Span, outer: Span) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


def parent(s: Span, found, name: str) -> Span:
    (p,) = [o for o in found if o.name == name and inside(s, o)]
    return p


def assert_scans_time_the_segments(found, segments):
    """Each traced dispatch's ``runtime.scan`` holds the interval its
    ``HyperstepRecord.step_seconds`` timed (a segment's ``wall``)."""
    scans = [s for s in found if s.name == "runtime.scan"]
    assert len(scans) == len(segments) > 0
    for s, seg in zip(scans, segments):
        assert seg["wall"] - 1e-6 <= s.end - s.start <= seg["wall"] + 5e-3


def test_a_traced_serve_run_holds_the_engine_and_runtime_spans(monkeypatch):
    out, rec = traced_run(monkeypatch, "starcoder2-15b.code-complete-unrolled")
    found = rec["trace"]["spans"]
    assert ENGINE | RUNTIME | {"runtime.check"} <= {s.name for s in found}
    for s in found:
        if s.name != "engine.segment":
            parent(s, found, "engine.segment")
        if s.name.startswith("runtime.") and s.name != "runtime.dispatch":
            parent(s, found, "runtime.dispatch")
    joins = [s for s in found if s.name == "engine.join"]
    rids = [s.args["rid"] for s in joins]
    assert len(set(rids)) == len(rids) > 0
    for kind in ("engine.prefill", "engine.scatter"):
        spans = [s for s in found if s.name == kind]
        assert sorted(s.args["rid"] for s in spans) == sorted(rids)
        assert all(parent(s, found, "engine.join").args["rid"] == s.args["rid"]
                   for s in spans)
    assert all(s.args["queued_s"] >= 0 for s in joins)
    # the prefill span is the interval Request.prefill_seconds times
    prefill = {s.args["rid"]: s.end - s.start for s in found if s.name == "engine.prefill"}
    served = [r for r in rec["requests"] if r.prefill_s > 0 and r.rid in prefill]
    assert served
    for r in served:
        assert r.prefill_s - 1e-6 <= prefill[r.rid] <= r.prefill_s + 5e-3
    segs = [s for s in found if s.name == "engine.segment"]
    assert [s.args["segment"] for s in segs] == sorted(s.args["segment"] for s in segs)
    assert_scans_time_the_segments(found, rec["segments"])
    for name in ("engine.host_ms.online", "engine.queue_wait_p90_ms.online"):
        assert out["metrics"][name]["value"] > 0
    assert reader("engine.host_ms.batch")(rec) == out["metrics"]["engine.host_ms.online"]["value"]


def test_a_traced_train_segment_holds_the_runtime_spans(monkeypatch):
    out, rec = traced_run(monkeypatch, "minicpm-2b.pretrain")
    found = rec["trace"]["spans"]
    assert {s.name for s in found} == RUNTIME
    for s in found:
        if s.name != "runtime.dispatch":
            parent(s, found, "runtime.dispatch")
    dispatches = [s for s in found if s.name == "runtime.dispatch"]
    assert all(s.args["hypersteps"] == 1 and s.args["plan"].startswith("train_")
               for s in dispatches)
    assert_scans_time_the_segments(found, rec["segments"])
    assert out["metrics"]["runtime.host_ms.train"]["value"] > 0


def hand_built() -> dict:
    # two segments: [0, 10] with a prefill [1, 3] and a scan [4, 9] (host
    # 10 - 2 - 5 = 3 ms) and [10, 14] with a scan [10.5, 13.5] (host 1 ms);
    # times in ms
    ms = 1e-3
    return {"trace": {"spans": [
        Span(0 * ms, 10 * ms, "engine.segment", {"segment": 0, "occupancy": 1}),
        Span(0.5 * ms, 3.5 * ms, "engine.join", {"rid": 0, "queued_s": 0.2}),
        Span(1 * ms, 3 * ms, "engine.prefill", {"rid": 0}),
        Span(3.8 * ms, 9.5 * ms, "runtime.dispatch", {"hypersteps": 8}),
        Span(4 * ms, 9 * ms, "runtime.scan", {}),
        Span(10 * ms, 14 * ms, "engine.segment", {"segment": 1, "occupancy": 1}),
        Span(10.2 * ms, 13.9 * ms, "runtime.dispatch", {"hypersteps": 8}),
        Span(10.5 * ms, 13.5 * ms, "runtime.scan", {}),
        Span(13.9 * ms, 14 * ms, "engine.join", {"rid": 1, "queued_s": "0.6"}),
    ]}}


@pytest.mark.parametrize("name, want", [
    ("engine.host_ms.batch", 2.0),             # median of 3 and 1 ms
    ("engine.host_ms.online", 2.0),
    ("runtime.host_ms.train", (0.7 + 0.7) / 2),  # 5.7 - 5 and 3.7 - 3
    ("engine.queue_wait_p90_ms.online", 1e3 * (0.2 + 0.9 * (0.6 - 0.2))),
])
def test_each_reader_on_a_hand_built_record(name, want):
    read = reader(name)
    assert read(hand_built()) == pytest.approx(want, rel=1e-9)
    assert read({"trace": {"spans": []}}) is None
    assert read({}) is None


def test_a_gap_is_named_by_the_innermost_program_span():
    ops = [(0.0, 1.0, "%a = f32[1] add()"), (2.0, 3.0, "%a = f32[1] add()"),
           (3.5, 6.0, "%a = f32[1] add()"), (8.0, 10.0, "%a = f32[1] add()")]
    found = [Span(0.0, 10.0, "bench.window", {}), Span(1.0, 9.0, "bench.step_segment", {}),
             Span(1.0, 9.0, "engine.segment", {}), Span(1.2, 2.5, "engine.join", {}),
             Span(1.5, 2.2, "engine.prefill", {}), Span(3.0, 3.5, "engine.plan", {})]
    tr = T.Trace(devices=[ops], spans=[(s.start, s.end, s.name) for s in found])
    # idle: [1, 2] (middle 1.5 in the prefill), [3, 3.5] (the plan), [6, 8]
    # (the segment itself: ties with the harness span go to the earlier one)
    assert T.idle_breakdown(tr) == [["bench.step_segment", 2.0], ["engine.prefill", 1.0],
                                    ["engine.plan", 0.5]]
    starts, ends = np.array([o[0] for o in ops]), np.array([o[1] for o in ops])
    idle = _spans.gaps(starts, ends, 0.0, 10.0)
    assert list(zip(*(x.tolist() for x in idle))) == T.gaps(tr)
    by = dict(_spans.idle_by_span(found[:1] + found[2:], idle))
    assert by == {"engine.segment": 2.0, "engine.prefill": 1.0, "engine.plan": 0.5}
    assert _spans.idle_by_span([], idle) == [["none", 3.5]]
    # seconds no nested span covers: the segment's 8 less the join and plan
    assert _spans.self_seconds(found)["engine.segment"] == [1, 8.0, pytest.approx(8.0 - 1.3 - 0.5)]


def test_span_arguments_encoded_in_the_name():
    assert _spans.parse("engine.join#rid=3,prompt_len=8#") == (
        "engine.join", {"rid": "3", "prompt_len": "8"})
    assert _spans.parse("engine.join", [("rid", 3)]) == ("engine.join", {"rid": 3})


@pytest.fixture(scope="module")
def recorded() -> T.Trace:
    return T.load(str(DATA))


# What each reduction read from the recorded trace before the program had
# spans (the trace holds only the harness's).
@pytest.mark.parametrize("reduction, want", [
    ("window", (0.044522196, 0.09879465300000001)),
    ("busy", 1.4593999999978902e-05),
    ("kernel_calls", {"streamed_matmul": [(1.1150000000029192e-06, 8.857000000001003e-06),
                                          (1.1149999999959803e-06, 8.853999999988704e-06)],
                      "flash_attention": [(3.5349999999989556e-06, 1.2329999999963204e-06),
                                          (3.533999999999482e-06, 1.0129999999941575e-06)]}),
    ("ops_per_run", {"jit_program": (2, 15, 15)}),
])
def test_the_harness_reductions_read_the_recorded_trace_as_before(recorded, reduction, want):
    got = {"window": lambda t: t.window(), "busy": T.busy, "ops_per_run": T.ops_per_run,
           "kernel_calls": lambda t: {k: [(c.seconds, c.fed_seconds) for c in T.kernel_calls(t, k)]
                                      for k in ("streamed_matmul", "flash_attention")}}
    assert got[reduction](recorded) == want


def test_the_recorded_trace_read_for_spans(recorded):
    found, (starts, ends) = _spans.read_profile(str(DATA))
    assert [(s.start, s.end, s.name) for s in found] == recorded.spans
    lo, hi = recorded.window()
    idle = _spans.gaps(starts, ends, lo, hi)
    assert list(zip(*(x.tolist() for x in idle))) == T.gaps(recorded)
    assert _spans.idle_by_span(found, idle)[0][0] == "bench.sleep"


def test_the_spans_are_read_over_the_window_the_harness_found(tmp_path, monkeypatch):
    """The program spans' reader takes the traced part that ``reduce_trace``
    found, so a profile whose harness spans were dropped still reads."""
    from types import SimpleNamespace

    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(DATA.read_bytes())
    found, ops = _spans.read_profile(str(DATA))
    monkeypatch.setattr(_spans, "read_profile", lambda path: (
        [s for s in found if not s.name.startswith("bench.")], ops))
    tracer = SimpleNamespace(logdir=str(tmp_path))  # found by _spans in this frame
    assert tracer.logdir
    lo, hi = T.load(str(DATA)).window()
    assert _spans.spans({"trace": {"bounds": [lo, hi]}}) == []

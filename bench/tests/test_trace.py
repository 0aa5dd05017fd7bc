"""The reduction from trace to metrics, on a hand-built event list whose
answer is known and on a small trace recorded on a TPU v5e
(``data/small_trace.xplane.pb``, made by ``record_trace.py``: one program
holding both Pallas kernels, called three times inside ``bench.segment``
spans, with a 50 ms ``bench.sleep`` before the third)."""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import flops, spec
from bench import trace as T
from bench.families import dense_decoder

DATA = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"

MATMUL = ('%streamed_matmul.3 = bf16[8,5760]{1,0:T(8,128)(2,1)} custom-call('
          'bf16[8,2304]{1,0:T(8,128)(2,1)} %a, bf16[2304,5888]{1,0:T(8,128)(2,1)S(1)} '
          '%b), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FLASH = ('%flash_attention.1 = bf16[2,36,2048,64]{3,2,1,0} custom-call('
         'bf16[2,36,2048,64]{3,2,1,0} %q, bf16[2,36,2048,64]{3,2,1,0} %k, '
         'bf16[2,36,2048,64]{3,2,1,0} %v), custom_call_target="tpu_custom_call", x=1')


def hand_built() -> T.Trace:
    # window [0, 10]; ops cover [1, 3] (two overlapping), [4, 5] and [9, 11]
    ops = [(1.0, 2.5, MATMUL), (2.0, 3.0, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x)"),
           (4.0, 5.0, FLASH), (9.0, 11.0, "%copy.2 = bf16[4]{0} copy(bf16[4]{0} %y)")]
    spans = [(0.0, 10.0, "bench.window"), (3.0, 4.0, "bench.submit"),
             (5.0, 9.0, "bench.step_segment"), (6.0, 8.0, "bench.stamp")]
    return T.Trace(devices=[ops], spans=spans)


def test_busy_idle_and_gaps_of_a_hand_built_trace():
    t = hand_built()
    assert t.window() == (0.0, 10.0)
    assert T.busy(t) == pytest.approx(2.0 + 1.0 + 1.0)       # clipped at 10
    assert T.gaps(t) == [(0.0, 1.0), (3.0, 4.0), (5.0, 9.0)]
    assert T.busy(t, within=[(0.0, 2.0), (4.5, 9.5)]) == pytest.approx(1.0 + 0.5 + 0.5)
    # each gap is named by the innermost span open at its middle
    assert T.idle_breakdown(t) == [["bench.stamp", 4.0], ["bench.window", 1.0],
                                   ["bench.submit", 1.0]]


def test_kernel_calls_and_their_least_time():
    t = hand_built()
    (mm,) = T.kernel_calls(t, "streamed_matmul")
    assert mm.seconds == pytest.approx(1.5) and mm.fed_seconds == 0.0
    assert mm.operands == [("bf16", (8, 2304)), ("bf16", (2304, 5888))]
    c = {"hidden_size": 2304, "num_attention_heads": 36, "num_key_value_heads": 36,
         "head_dim": 64, "intermediate_size": 5760, "vocab_size": 122753,
         "num_hidden_layers": 8, "hidden_act": "silu", "tie_word_embeddings": True}
    # the kernel padded 5760 to 5888 (blocks of 256): the least time is of
    # the 8 x 2304 x 5760 product it was sent
    kernels = spec.kernels()
    fl, nb = kernels["streamed_matmul"].cost(mm.operands, c, dense_decoder)
    assert fl == 2.0 * 8 * 2304 * 5760
    assert nb == 2.0 * (8 * 2304 + 2304 * 5760 + 8 * 5760)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(fl, nb, peak) == (nb / 819e9, "memory")
    (fa,) = T.kernel_calls(t, "flash_attention")
    fl, nb = kernels["flash_attention"].cost(fa.operands, c, dense_decoder)
    assert fl == 4.0 * 2 * 36 * 64 * 2048 * 2049 / 2
    assert flops.least_seconds(fl, nb, peak)[1] == "compute"
    top = T.top_ops(t)
    assert top[0] == ["streamed_matmul bf16[8,5760]", 1.5]
    assert sorted(top[1:]) == [["copy bf16[4]", 1.0], ["flash_attention bf16[2,36,2048,64]", 1.0],
                               ["fusion f32[8]", 1.0]]


def test_an_op_counts_its_self_time_and_not_that_of_the_ops_nested_in_it():
    # a scan's while [0, 10] runs a fusion [1, 3] and a kernel call [4, 8]
    # holding a nested copy [5, 6]; another while [12, 14] runs one fusion
    loop = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
    ops = [(0.0, 10.0, loop), (1.0, 3.0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)"),
           (4.0, 8.0, MATMUL), (5.0, 6.0, "%copy.2 = bf16[4]{0} copy(bf16[4]{0} %y)"),
           (12.0, 14.0, loop), (12.5, 13.0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)")]
    t = T.Trace(devices=[ops], spans=[(0.0, 20.0, "bench.window")])
    assert T.top_ops(t) == [["while (s32[],", (10 - 2 - 4) + (2 - 0.5)],
                            ["streamed_matmul bf16[8,5760]", 3.0],
                            ["fusion f32[8]", 2.5], ["copy bf16[4]", 1.0]]
    # the window clips an op and what it holds alike
    t.spans = [(0.0, 5.0, "bench.window")]
    assert dict(T.top_ops(t)) == {"while (s32[],": 5 - 2 - 1, "fusion f32[8]": 2.0,
                                  "streamed_matmul bf16[8,5760]": 1.0, "copy bf16[4]": 0.0}


def plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k, events=[SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                                        for n, s, d in v]) for k, v in lines.items()])


def test_load_keeps_the_program_spans_and_names_idle_gaps_by_them(monkeypatch):
    import jax

    ms = 1_000_000
    device = plane("/device:TPU:0", **{"XLA Ops": [("%a = f32[1] add()", 0, 2 * ms),
                                                   ("%a = f32[1] add()", 8 * ms, 2 * ms)]})
    host = plane("/host:CPU", python=[
        ("bench.window", 0, 10 * ms), ("bench.step_segment", 1 * ms, 8 * ms),
        ("engine.segment", 1 * ms, 8 * ms), ("engine.join#rid=3,prompt_len=8#", 2 * ms, 5 * ms),
        ("other.span", 3 * ms, 1 * ms)])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: SimpleNamespace(planes=[device, host])))
    t = T.load("unused")
    assert [n for _, _, n in t.spans] == ["bench.window", "bench.step_segment",
                                         "engine.segment", "engine.join"]
    # the one gap, [2, 8] ms, is named by the program's join open at its middle
    assert T.idle_breakdown(t) == [["engine.join", pytest.approx(6e-3)]]


def test_an_async_copy_feeds_from_its_issue_to_its_done():
    mm = MATMUL.replace("%b)", "%copy-done.4)")
    ops = [(1.0, 1.1, "%copy-start.4 = (bf16[2304,5888]) copy-start(bf16[2304,5888] %w)"),
           (1.1, 2.0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)"),
           (2.0, 2.5, "%copy-done.4 = bf16[2304,5888]{1,0:S(1)} copy-done(%copy-start.4)"),
           (2.5, 3.0, mm)]
    asynch = [(1.0, 1.6, "%copy-start.4 = (bf16[2304,5888]) copy-start(bf16[2304,5888] %w)")]
    t = T.Trace(devices=[ops], spans=[(0.0, 10.0, "bench.window")], copies=[asynch])
    (call,) = T.kernel_calls(t, "streamed_matmul")
    # issued at 1.0, done at 2.5: longer than the copy's own 0.6 s event
    assert call.fed_seconds == pytest.approx(1.5)
    assert call.device_seconds == pytest.approx(2.0)
    t.copies = [[(1.0, 2.8, asynch[0][2])]]
    assert T.kernel_calls(t, "streamed_matmul")[0].fed_seconds == pytest.approx(1.8)


def test_the_traced_span_is_the_window_and_runs_count_their_ops():
    ops = [(1.0, 1.5, "%a = f32[1] add()"), (1.5, 2.0, "%b = f32[1] add()"),
           (5.0, 5.5, "%a = f32[1] add()")]
    mods = [(1.0, 2.0, "jit_step(123)"), (5.0, 6.0, "jit_step(123)"), (11.0, 12.0, "jit_step(1)")]
    t = T.Trace(devices=[ops], spans=[(0.0, 20.0, "bench.window"), (0.0, 10.0, "bench.traced")],
                modules=[mods])
    assert t.window() == (0.0, 10.0)
    # the second run lost an op: the profiler dropped events
    assert T.ops_per_run(t) == {"jit_step": (2, 1, 2)}


def test_merge_and_clip():
    assert T.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert T.clip([(0, 2.5), (3, 4)], 1, 3.5) == [(1, 2.5), (3, 3.5)]
    assert T.total([(0, 2.5), (3, 4)]) == 3.5


@pytest.fixture(scope="module")
def recorded() -> T.Trace:
    return T.load(str(DATA))


def test_recorded_trace_planes_and_spans(recorded):
    assert len(recorded.devices) == 1
    names = [n for _, _, n in recorded.spans]
    assert names.count("bench.segment") == 3 and names.count("bench.sleep") == 1
    lo, hi = recorded.window()
    assert hi - lo == pytest.approx(0.054272457, abs=1e-9)


def test_recorded_trace_busy_and_the_sleep_gap(recorded):
    lo, hi = recorded.window()
    busy = T.busy(recorded)
    # two 7.5 us programs inside the window (the first starts 0.6 ms before
    # the window's span on the device clock); the rest is idle
    assert 1e-5 < busy < 3e-5
    name, seconds = T.idle_breakdown(recorded)[0]
    assert name == "bench.sleep"
    assert 0.050 < seconds < 0.053


def test_recorded_trace_kernels_by_name(recorded):
    mm = T.kernel_calls(recorded, "streamed_matmul")
    fa = T.kernel_calls(recorded, "flash_attention")
    assert len(mm) == 2 and len(fa) == 2
    assert mm[0].operands == [("bf16", (256, 512)), ("bf16", (512, 256))]
    assert fa[0].operands == [("bf16", (1, 2, 256, 64))] * 3
    assert all(0.5e-6 < k.seconds < 10e-6 for k in mm + fa)
    # both matmul operands came into VMEM by asynchronous copies of about
    # 4.4 us each; the attention's by three synchronous copies
    assert all(8e-6 < k.fed_seconds < 10e-6 for k in mm)
    assert all(0.5e-6 < k.fed_seconds < 2e-6 for k in fa)
    # every run of the program kept all its ops
    assert T.ops_per_run(recorded) == {"jit_program": (2, 15, 15)}
    ops = dict(T.top_ops(recorded))
    assert "flash_attention bf16[1,2,256,64]" in ops

"""Traffic generators, the end-to-end and per-layer arithmetic, the manifest,
and how ``bench/run.py`` refuses to run without a TPU. Nothing here needs a
chip, and nothing asks JAX about a TPU."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import spec
from bench.drivers import serve, train
from bench.families import dense_decoder as dense
from bench.metrics import _shared
from bench.traffic.batches import Batches
from bench.traffic.requests import Requests

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in spec.manifest()["workloads"]]


def mix(process="poisson", **arr):
    return {"generator": "requests", "pool": 512, "sizes_seed": 0,
            "arrivals": {"process": process, **arr},
            "prompt_len": {"ladder": [8, 16, 32], "p": [0.5, 0.25, 0.25]},
            "output_len": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                           "min": 4, "max": 60}}


def test_request_generator_repeats_exactly_from_a_seed():
    seed = 2**33 + 12345                 # wider than 32 bits
    a, b = Requests(mix(rate_per_s=4.0), seed, 1000), Requests(mix(rate_per_s=4.0), seed, 1000)
    for i in (0, 1, 7, 511, 600):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.new_tokens, ra.due_s) == (rb.new_tokens, rb.due_s)
        assert len(ra.prompt) in (8, 16, 32) and 4 <= ra.new_tokens <= 60
        assert ra.prompt.dtype == np.int32 and 0 <= ra.prompt.min() and ra.prompt.max() < 1000


def sizes(g):
    return [(len(g.request(i).prompt), g.request(i).new_tokens, g.due_s(i)) for i in range(512)]


def test_an_open_loop_replays_one_schedule_for_every_seed():
    a, b = Requests(mix(rate_per_s=4.0), 1, 1000), Requests(mix(rate_per_s=4.0), 2, 1000)
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a.request(0).prompt, b.request(0).prompt)


def test_a_backlog_takes_the_same_sizes_in_another_order():
    a = Requests(mix("backlog", queued_per_lane=2), 1, 1000)
    b = Requests(mix("backlog", queued_per_lane=2), 2, 1000)
    assert sorted(sizes(a)) == sorted(sizes(b)) and sizes(a) != sizes(b)


def test_open_loop_due_times():
    g = Requests(mix(rate_per_s=4.0), 3, 1000)
    due = np.array([g.due_s(i) for i in range(512)])
    assert np.all(np.diff(due) > 0)
    assert 512 / due[-1] == pytest.approx(4.0, rel=0.1)
    assert Requests(mix("backlog", queued_per_lane=2), 3, 1000).due_s(9) == 0.0


def test_batch_generator_repeats_and_writes_the_token_file(tmp_path):
    m = {"generator": "batches", "batch": 2, "seq_len": 16, "batches": 4}
    a, b = Batches(m, 5, 300), Batches(m, 5, 300)
    x, y = a.batch_at(1)
    assert np.array_equal(x, b.batch_at(1)[0]) and np.array_equal(x[:, 1:], y[:, :-1])
    assert np.array_equal(a.batch_at(5)[0], x)               # wraps after 4
    assert not np.array_equal(a.batch_at(2)[0], x)
    path = tmp_path / "t.u32"
    a.write(path)
    flat = np.fromfile(path, np.uint32).reshape(4, 2, 17)
    assert np.array_equal(flat[1], a.rows(1))


def tracked(due, first, done, new=11, prompt=8):
    t = serve.Tracked(0, np.zeros(prompt, np.int32), new, due, due)
    t.first, t.done = first, done
    t.tokens = [1] * new if done is not None else None
    return t


def test_ttft_from_due_times_with_censoring_and_tpot():
    reqs = [tracked(100.0 + i, 100.5 + i, 101.5 + i) for i in range(5)]
    reqs.append(tracked(108.0, None, None))       # due, never served: waits 2 s
    reqs.append(tracked(111.0, None, None))       # due after the window: left out
    segs = [{"tokens": 30}, {"tokens": 25}]
    rec = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "requests": reqs, "segments": segs}
    e = serve.e2e(rec)
    assert e["out_tok_s"] == pytest.approx(55 / 10.0)
    # ttft samples 0.5 x 5 and 2.0: numpy's linear 90th percentile
    assert e["ttft_p90_s"] == pytest.approx(np.percentile([0.5] * 5 + [2.0], 90))
    assert e["ttft_p90_s"] == pytest.approx(1.25)
    assert e["tpot_p90_ms"] == pytest.approx(1e3 * 1.0 / 10)
    assert e["counts"]["ttft_samples"] == 6 and e["counts"]["finished"] == 5


def test_a_record_cut_at_the_end_of_the_traced_part():
    a = tracked(100.0, 100.5, 101.5)
    a.stamps, a.served = [(100.5, 1), (101.0, 6), (101.5, 11)], 11
    b = tracked(101.2, None, None)
    b.stamps = [(102.0, 3)]
    c = tracked(103.0, None, None)
    segs = [{"end": 100.5, "tokens": 1}, {"end": 101.0, "tokens": 5},
            {"end": 101.5, "tokens": 5}, {"end": 102.0, "tokens": 3}]
    rec = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "requests": [a, b, c], "segments": segs}
    cut = serve.upto(rec, 101.2)
    assert cut["t1"] == 101.2 and [s["end"] for s in cut["segments"]] == [100.5, 101.0]
    first, second = cut["requests"]
    assert (first.served, first.done, first.tokens, first.first) == (6, None, None, 100.5)
    assert (second.served, second.first, second.prefill_s) == (0, None, 0.0)
    assert a.served == 11 and a.done == 101.5          # the record itself is kept
    assert serve.upto(rec, 111.0)["requests"] == rec["requests"]
    t = {"t0": 0.0, "t1": 2.0, "segments": [{"end": 0.5}, {"end": 1.5}], "tokens_per_step": 1}
    assert train.upto(t, 1.0)["segments"] == [{"end": 0.5}]


def test_train_rate_counts_host_time_between_segments():
    segs = [{"start": 0.0, "end": 0.4, "steps": 1, "wall": 0.3}] * 5
    rec = {"t0": 0.0, "t1": 2.0, "segments": segs, "tokens_per_step": 4096}
    assert train.e2e(rec)["train_tok_s"] == pytest.approx(5 * 4096 / 2.0)


def test_leaf_gap_takes_the_worst_leaf_against_the_median():
    base = {"a": np.array([1.0, 2.0, 3.0]), "b": np.float32(1e-6), "c": np.float32(4.0)}
    cand = {"a": np.array([1.0, 2.2, 3.0]), "b": np.float32(1e-3), "c": np.float32(4.0)}
    # median leaf norm 2.0: b is judged against 2.0, not its own 1e-6
    gap, where = train.leaf_gap(cand, base, skip=set())
    assert where == "a[1]" and gap == pytest.approx(0.1)
    assert train.leaf_gap(cand, {**base, "a": cand["a"]}, set())[0] == pytest.approx(
        (1e-3 - 1e-6) / 2.2, rel=1e-3)


def test_model_flops_of_the_configurations():
    c = spec.cell("minicpm-2b.pretrain").config
    n = dense.matmul_params(c)
    assert n == 8 * 61_046_784 + 2304 * 122753           # 771 M with the tied head
    step = dense.train_token_flops(c, 2048) * 2 * 2048
    assert step == pytest.approx(20.8e12, rel=0.01)
    s = spec.cell("starcoder2-15b.code-complete-unrolled").config
    assert dense.matmul_params(s) == pytest.approx(3.674e9 - 49152 * 6144, rel=0.002)
    assert dense.prefill_flops(s, 1) == dense.serve_token_flops(s, 1)
    assert dense.decode_flops(s, 10, 2) == pytest.approx(
        dense.serve_token_flops(s, 11) + dense.serve_token_flops(s, 12))


def test_mfu_idle_and_roofline_readers():
    rec = {"peak": {"flops_per_s": 100.0}, "window_s": 2.0, "model_flops": 50.0,
           "trace": {"busy_s": 1.5, "window_s": 2.0, "busy_pending_s": 0.9,
                     "pending_s": 1.0, "kernels": {"k": {"seconds": 4.0, "least_s": 3.0}}},
           "segments": [{"occupancy": 3, "wall": 0.1}, {"occupancy": 5, "wall": 0.3},
                        {"occupancy": 4, "wall": 0.2}]}
    assert _shared.mfu(rec["model_flops"], rec["window_s"], rec) == pytest.approx(25.0)
    assert _shared.idle_share(rec) == pytest.approx(25.0)
    assert _shared.idle_share(rec, pending=True) == pytest.approx(10.0)
    assert _shared.roofline(rec, "k") == pytest.approx(75.0)
    assert _shared.roofline(rec, "absent") is None
    assert _shared.mean_occupancy(rec) == 4.0
    assert _shared.median_segment_ms(rec) == pytest.approx(200.0)
    assert _shared.idle_share({}) is None and _shared.mean_occupancy({}) is None


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank|_size|hidden|intermediate|head|expert|latent|state|proj)",
                   re.I)


def test_the_manifest_names_files_that_exist():
    m = spec.manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert not [k for k in c["reduced"] if WIDTH.search(k) and not k.endswith("_layers")]
        assert sorted(c["reduced"]) == sorted(json.loads((ROOT / c["file"]).read_text())["reduced"])
    for w in m["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.per_layer and any(x["name"] != "setup_s" for x in cell.end_to_end)
        for x in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{x['name']}.py").is_file()
            assert x["moves"] in {e["name"] for e in cell.end_to_end}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 2)


def run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = run_bench(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_beside_no_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_traced_run_reads_its_metrics_over_the_traced_part(monkeypatch):
    from bench import peaks, run as R
    from bench.tests import tiny

    cell = tiny.cell("minicpm-2b.batch-chat-unrolled")
    cell.workload["trace_seconds"] = 0.5
    seen = {}
    reduce = R.reduce_trace

    def spy(path, cell, rec, peak):
        seen.update(rec)
        return reduce(path, cell, rec, peak)

    monkeypatch.setattr(R, "reduce_trace", spy)
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    out = R.run_cell(cell, 7, 2.0, True)
    assert out["correct"] and "breakdown" in out
    assert {"engine.occupancy.batch", "runtime.segment_ms.batch"} <= set(out["metrics"])
    # the metrics' part of the window ends where the trace stopped, well
    # before the window's two seconds; the comparison still saw the whole
    assert 0.5 <= seen["window_s"] < 1.9
    assert out["device"]["window_s"] == pytest.approx(seen["window_s"], abs=0.5)


def test_the_compile_counter_sees_compiles_only_while_open():
    import jax
    import jax.numpy as jnp

    from bench.run import Compiles

    x = jnp.arange(5.0)
    with Compiles() as c:
        jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    assert c.n == 1
    jax.jit(lambda v: v * 5 - 1)(x).block_until_ready()
    assert c.n == 1

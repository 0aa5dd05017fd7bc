"""Core BSPS model: streams, hypersteps, cost functions, HLO accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EPIPHANY_III,
    TPU_V5E_CHIP,
    HyperstepCost,
    HyperstepRunner,
    StreamSet,
    SuperstepCost,
    bsp_cost,
    bsps_cost,
    cannon_bsps_cost,
    cannon_k_equal,
    inner_product_cost,
)
from repro.core.hlo import collective_bytes, parse_shape_bytes
from repro.core.stream import StreamBusyError, StreamClosedError
from repro.launch.mesh import auto_mesh


# ------------------------------------------------------------- machines ----


def test_paper_machine_constants():
    acc = EPIPHANY_III
    assert acc.p == 16
    assert acc.e == pytest.approx(43.4)
    assert acc.g == pytest.approx(5.59)
    assert acc.l == pytest.approx(136.0)
    # 32 kB SRAM in 4-byte words; prefetch halves it (paper §2)
    assert acc.L == 8192
    assert acc.effective_local_words() == 4096


def test_v5e_chip_is_bandwidth_rich_vs_parallella():
    # e(v5e) ≈ 481 flop/word; still bandwidth-heavy for O(1)-intensity kernels
    assert 400 < TPU_V5E_CHIP.e < 600
    assert TPU_V5E_CHIP.balance > 1  # inner product is bandwidth heavy (e > 1)


def test_roofline_peaks_keyed_by_device_kind():
    """The kind JAX reports for a v5e chip maps to its published peaks; an
    unlisted kind is refused rather than priced as a v5e."""
    from repro.core import hardware_spec

    hw = hardware_spec("TPU v5 lite")
    assert (hw.peak_flops, hw.hbm_bandwidth, hw.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no published peaks"):
        hardware_spec("cpu")


# ---------------------------------------------------------------- streams ----


def test_stream_primitives_and_exclusivity():
    ss = StreamSet()
    s = ss.create(np.arange(12, dtype=np.float32), token_size=4)
    assert s.num_tokens == 3
    s.open(core=0)
    with pytest.raises(StreamBusyError):
        s.open(core=1)
    t0 = s.move_down(0)
    np.testing.assert_array_equal(t0, [0, 1, 2, 3])
    s.seek(0, -1)                       # pseudo-streaming: revisit
    np.testing.assert_array_equal(s.move_down(0), [0, 1, 2, 3])
    s.move_up(0, np.zeros(4, np.float32))  # mutable stream
    np.testing.assert_array_equal(s.peek(1), np.zeros(4))
    s.close(0)
    s.open(core=1)                      # reopenable after close (paper §4)
    with pytest.raises(IndexError):
        s.seek(1, 99)
    s.close(1)
    with pytest.raises(StreamClosedError):
        s.move_down(1)


def test_cyclic_distribution_matches_paper_figure2():
    ss = StreamSet()
    v = np.arange(24, dtype=np.float32)
    streams = ss.create_cyclic(v, p=3, token_size=2, name="v")
    # component i -> core i mod p (paper §3.1); stream 0 holds 0,3,6,...
    np.testing.assert_array_equal(np.asarray(streams[0].data), v[0::3])
    assert streams[0].num_tokens == 4  # |Σ_0| = 4 with C=2 (paper Fig. 2)


# -------------------------------------------------------------- hypersteps ----


def test_hyperstep_inner_product_and_records():
    ss = StreamSet()
    v = np.arange(1024, dtype=np.float32)
    u = np.full(1024, 2.0, np.float32)
    sv, su = ss.create(v, 128), ss.create(u, 128)
    runner = HyperstepRunner(
        lambda acc, toks: acc + jnp.vdot(jnp.asarray(toks[0]), jnp.asarray(toks[1])),
        [sv, su])
    out = runner.run(jnp.float32(0))
    assert float(out) == pytest.approx(float(v.sum() * 2))
    assert len(runner.records) == 8
    assert all(r.step_seconds > 0 for r in runner.records)


def test_hyperstep_prefetch_matches_serial_result():
    ss = StreamSet()
    data = np.random.default_rng(1).standard_normal(512).astype(np.float32)
    s1 = ss.create(data, 64)
    s2 = ss.create(data.copy(), 64)
    step = lambda acc, toks: acc + float(np.sum(np.asarray(toks[0])))
    r1 = HyperstepRunner(step, [s1], prefetch=True).run(0.0)
    r2 = HyperstepRunner(step, [s2], prefetch=False).run(0.0)
    assert r1 == pytest.approx(r2)


# ------------------------------------------------------------------- cost ----


def test_bsp_cost_formula():
    m = EPIPHANY_III
    ss = SuperstepCost(work=[100, 50], transmitted=[10, 0], received=[0, 10])
    assert ss.h_relation == 10
    assert bsp_cost([ss], m) == pytest.approx(100 + 10 * m.g + m.l)


def test_bsps_cost_is_max_of_compute_and_fetch():
    acc = dataclasses.replace(EPIPHANY_III, e=2.0)
    h_bw = HyperstepCost(bsp_flops=10.0, fetch_words=[100.0])     # fetch = 200
    h_cp = HyperstepCost(bsp_flops=1000.0, fetch_words=[100.0])   # compute wins
    assert h_bw.bandwidth_heavy(acc) and not h_cp.bandwidth_heavy(acc)
    assert bsps_cost([h_bw, h_cp], acc) == pytest.approx(200 + 1000)


def test_inner_product_cost_closed_form():
    acc = EPIPHANY_III
    n, c = 65536, 128
    hypersteps = n // (acc.p * c)
    want = hypersteps * max(2 * c, 2 * c * acc.e) + acc.p + (acc.p - 1) * acc.g + acc.l
    assert inner_product_cost(acc, n, c) == pytest.approx(want)
    # e > 1 on the Parallella ⇒ bandwidth heavy ⇒ the max picks 2Ce
    assert inner_product_cost(acc, n, c) > hypersteps * 2 * c


def test_cannon_k_equal_reproduces_paper():
    """Paper §6: k_equal ≈ 8 on the Epiphany-III (with optimised writes g ≲ 1)."""
    acc = dataclasses.replace(EPIPHANY_III, g=1.0)
    k = cannon_k_equal(acc)
    assert 6 <= k <= 11
    # with the pessimistic contested-read g the window closes (documented)
    assert cannon_k_equal(EPIPHANY_III) == 0.0


def test_cannon_cost_crossover_consistency():
    """Below k_equal hypersteps are bandwidth heavy, above compute heavy."""
    acc = dataclasses.replace(EPIPHANY_III, g=1.0)
    n_grid = 4
    k_eq = cannon_k_equal(acc)

    def sides(k):
        compute = n_grid * (2 * k**3 + 2 * k**2 * acc.g + acc.l)
        fetch = 2 * k**2 * acc.e
        return compute, fetch

    c_lo, f_lo = sides(int(k_eq) - 2)
    c_hi, f_hi = sides(int(k_eq) + 3)
    assert f_lo > c_lo and c_hi > f_hi


def test_cannon_bsps_cost_scales_with_m():
    """Fig. 5: smaller blocks (larger M) cost more — block size should be as
    large as local memory allows."""
    acc = dataclasses.replace(EPIPHANY_III, g=1.0)
    n = 512
    costs = [cannon_bsps_cost(acc, n, m) for m in (4, 8, 16)]
    assert costs[0] < costs[1] < costs[2]


# -------------------------------------------------------------------- hlo ----


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert parse_shape_bytes("bf16[8]") == 16
    assert parse_shape_bytes("pred[] token[]") == 1


def test_collective_bytes_on_real_hlo():
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    mesh = auto_mesh((1,), ("x",))
    from jax.sharding import PartitionSpec as P

    def f(a):
        return jax.lax.psum(a, "x")

    g = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    txt = jax.jit(g).lower(jnp.ones((8, 8))).compile().as_text()
    stats = collective_bytes(txt)
    # single-device: collective may be elided; parser must not crash and
    # returns a consistent structure
    assert stats.total_bytes >= 0
    assert isinstance(stats.by_kind, dict)


def test_collective_bytes_counts_start_not_done():
    txt = """
  %ar = f32[1024]{0} all-reduce-start(f32[1024]{0} %p), replica_groups={}
  %ard = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar)
  %ag = f32[512]{0} all-gather(f32[256]{0} %q), dimensions={0}
"""
    stats = collective_bytes(txt)
    assert stats.op_counts == {"all-reduce": 1, "all-gather": 1}
    assert stats.by_kind["all-reduce"] == 4096
    assert stats.by_kind["all-gather"] == 1024  # operand shard, not result

"""StreamPlan subsystem: cost algebra, fetch schedules, planner, lowering.

The plan layer's contract (DESIGN.md §3): one declarative object prices a
BSPS kernel with the paper's Eq. 1, budgets it against double-buffered local
memory, lowers it to Pallas, and drives the host-level runner. Also enforces
the architectural rule that no kernel module calls ``pl.pallas_call``
directly.
"""

import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as planlib
from repro.core.bsp import BSPAccelerator
from repro.core.hyperstep import HyperstepRunner
from repro.core.plan import ScratchSpec, StreamPlan, TokenSpec
from repro.core.stream import StreamSet
from repro.kernels.flash_attention import attention_plan
from repro.kernels.ssm_scan import ssm_plan
from repro.kernels.streamed_dot import dot_plan
from repro.kernels.streamed_matmul import matmul_plan, plan_candidates

ACC = BSPAccelerator(p=1, g=0.0, l=0.0, r=1e9, e=4.0,
                     L=1 << 20, E=1 << 30, word_bytes=4, name="test-acc")


# ------------------------------------------------------------ fetch model ----


def test_matmul_fetch_schedule_counts_reuse():
    # Single K block: grid (i, j, s=0) — A's (i, s) map ignores j, so each A
    # tile is fetched once per row of C and *reused* across j (the paper's
    # MOVE(Σ, -M) loop over groups of M blocks of A).
    plan = matmul_plan(256, 128, 256, block_m=128, block_n=128, block_k=128,
                       dtype=jnp.float32)
    sched = plan.fetch_schedule()
    assert len(sched) == plan.num_hypersteps == 4
    tok = 128 * 128
    # step order (i,j): (0,0) A+B; (0,1) A reused, B fetched; (1,0) both
    # change; (1,1) A reused, B fetched
    assert sched == [2 * tok, tok, 2 * tok, tok]


def test_constant_index_map_is_fetched_once():
    plan = ssm_plan(2, 64, 8, 4, chunk=16, dtype=jnp.float32)
    sched = plan.fetch_schedule()
    per_chunk = 2 * (16 * 8) + 2 * (16 * 4)   # x, dt, B, C tokens
    resident = 8 * 4 + 8                      # A + D: constant maps
    assert sched[0] == per_chunk + resident
    assert all(s == per_chunk for s in sched[1:])


def test_token_reuse_in_attention_gqa():
    # hq=4, hkv=1: K/V block index repeats across the 4 q-heads -> only the
    # first head pays the fetch when (b, i, j) stay put.
    plan = attention_plan(1, 4, 1, 32, 32, 8, block_q=32, block_kv=32,
                          causal=False, dtype=jnp.float32)
    sched = plan.fetch_schedule()
    q_tok, kv_tok = 32 * 8, 32 * 8
    assert sched[0] == q_tok + 2 * kv_tok
    # heads 1..3: new Q token, K/V reused (non-injective h // group map)
    assert all(s == q_tok for s in sched[1:])


def test_causal_skip_prices_zero_flops():
    plan = attention_plan(1, 1, 1, 64, 64, 8, block_q=32, block_kv=32,
                          causal=True, dtype=jnp.float32)
    # grid (1,1,2,2): step (i=0, j=1) is strictly above the diagonal
    flops = [plan._flops_at(c) for c in
             [(0, 0, 0, 0), (0, 0, 0, 1), (0, 0, 1, 0), (0, 0, 1, 1)]]
    assert flops[1] == 0.0
    assert flops[0] > 0 and flops[2] > 0 and flops[3] > 0
    assert plan.total_flops == pytest.approx(sum(flops))


def test_causal_kv_maps_fetch_only_computed_blocks():
    # grid (1,1,4,4) at 512 tiles: Q row i needs K/V blocks 0..i, 10 of 16
    plan = attention_plan(1, 1, 1, 2048, 2048, 64, block_q=512, block_kv=512,
                          causal=True)
    kv_tok = 512 * 64
    coords = [(0, 0, i, j) for i in range(4) for j in range(4)]
    computed = [c[3] <= c[2] for c in coords]
    assert sum(computed) == 10
    q_fetch = [plan.inputs[0].words * (c[3] == 0) for c in coords]
    kv_fetch = [f - qf for f, qf in zip(plan.fetch_schedule(), q_fetch)]
    assert set(kv_fetch) == {0, 2 * kv_tok}
    fetching = [f > 0 for f in kv_fetch]
    # a skipped step repeats the block its row left resident: no fetch, no
    # FLOPs; row 1 also starts on block 0, which row 0 left resident
    assert not any(f and not c for f, c in zip(fetching, computed))
    assert [c for c, f, k in zip(coords, fetching, computed) if k and not f] \
        == [(0, 0, 1, 0)]
    assert sum(fetching) == 9
    assert all(plan._flops_at(c) == 0.0 for c, k in zip(coords, computed) if not k)


@pytest.mark.parametrize("hq,hkv,sq,skv,d", [
    (36, 36, 2048, 2048, 64),     # minicpm-2b pretrain
    (48, 4, 2048, 2048, 128),     # starcoder2-15b, GQA group 12
    (48, 4, 1, 2176, 128),        # one decode row over a ragged cache
    (32, 8, 4096, 4096, 128),
    (4, 2, 96, 96, 32),
    (7, 7, 300, 300, 64),         # prime head count, short ragged sequence
])
def test_attention_tiles_fit_and_outgrow_128(hq, hkv, sq, skv, d):
    from repro.core import TPU_V5E_CHIP
    from repro.kernels.flash_attention import attention_tiles

    bq, bkv, heads = attention_tiles(hq, hkv, sq, skv, d)
    plan = attention_plan(1, hq, hkv, -(-sq // bq) * bq, -(-skv // bkv) * bkv,
                          d, block_q=bq, block_kv=bkv, heads=heads,
                          q_offset=skv - sq)
    assert plan.fits(TPU_V5E_CHIP)
    group = hq // hkv
    assert hq % heads == 0 and (heads % group == 0 or group % heads == 0)
    if sq >= 2048:
        # the pretrain family's length: tiles divide it, larger than 128
        assert sq % bq == 0 and skv % bkv == 0
        assert bq > 128 and bkv > 128
    if (sq, d) == (2048, 64):
        assert plan.num_hypersteps <= 1000


def test_cost_matches_manual_eq1():
    # dot product: n hypersteps, 2C words fetched, 2C flops each; paper §3.1
    c = 1024
    plan = dot_plan(8, c, dtype=jnp.float32)
    # Eq. 1 with the fetch shifted (h fetches h+1's tokens; last fetches none)
    expected = 7 * max(2.0 * c, ACC.e * 2.0 * c) + 2.0 * c
    assert plan.cost(ACC) == pytest.approx(expected)
    assert plan.bandwidth_heavy(ACC)  # e = 4 > 1
    lean = BSPAccelerator(p=1, g=0.0, l=0.0, r=1e9, e=0.5,
                          L=1 << 20, E=1 << 30)
    assert not plan.bandwidth_heavy(lean)


def test_closed_form_bounds_uniform_plans():
    # for uniform (constant-flops) plans the closed form over-counts fetch
    # and matches compute, so it upper-bounds the exact Eq. 1 sum; plans with
    # skipped hypersteps only get an estimate (see ENUMERATION_LIMIT note)
    plan = matmul_plan(512, 512, 512, block_m=128, block_n=128, block_k=128,
                       dtype=jnp.float32)
    exact = plan.cost(ACC, exact=True)
    bound = plan.cost(ACC, exact=False)
    assert bound >= exact > 0


# ------------------------------------------------------------ vmem budget ----


def test_vmem_accounting_double_buffers_tokens():
    plan = matmul_plan(128, 128, 128, block_m=128, block_n=128, block_k=128,
                       dtype=jnp.bfloat16)
    tok = 128 * 128
    assert plan.input_token_bytes == 2 * (2 * tok * 2)
    assert plan.output_token_bytes == 2 * tok * 2
    assert plan.scratch_bytes == tok * 4
    assert plan.vmem_bytes == plan.input_token_bytes + plan.output_token_bytes \
        + plan.scratch_bytes


def test_fits_budget():
    small = BSPAccelerator(p=1, g=0.0, l=0.0, r=1e9, e=4.0,
                           L=16 * 1024, E=1 << 30, word_bytes=4)
    tiny = dot_plan(4, 256, dtype=jnp.float32)
    huge = matmul_plan(512, 512, 512, block_m=512, block_n=512, block_k=512,
                       dtype=jnp.float32)
    assert tiny.fits(small)
    assert not huge.fits(small)


# --------------------------------------------------------------- planner ----


def test_autotune_prefers_cheapest_feasible():
    # dot product, bandwidth heavy (e=4): Eq. 1 says bigger tokens are
    # cheaper (one fewer overlapped fetch per doubling), so the planner
    # should pick the largest token that fits local memory — the paper's
    # "size tokens as large as local memory allows". The budget holds the
    # double-buffered tokens plus the kernel's one-vreg (4 KiB) accumulator.
    budget = BSPAccelerator(p=1, g=0.0, l=0.0, r=1e9, e=4.0,
                            L=2500, E=1 << 30, word_bytes=4)
    n = 4096

    def build(token_size):
        return dot_plan(n // token_size, token_size, dtype=jnp.float32)

    best, choices = planlib.autotune(
        build, [{"token_size": 128}, {"token_size": 256},
                {"token_size": 512}], budget)
    # token_size=512 would be cheapest but blows the double-buffered budget
    assert not build(512).fits(budget)
    assert best.params["token_size"] == 256
    assert sorted(c.feasible for c in choices) == [False, True, True]
    feas = [c for c in choices if c.feasible]
    assert feas[0].predicted_seconds <= feas[-1].predicted_seconds


def test_autotune_measures_top_candidates():
    calls = []

    def build(block_k):
        return matmul_plan(256, 256, 256, block_m=128, block_n=128,
                           block_k=block_k, dtype=jnp.float32)

    def measure(block_k):
        calls.append(block_k)

    best, choices = planlib.autotune(
        build, [{"block_k": 128}, {"block_k": 256}], ACC,
        measure=measure, measure_top=2, repeats=1)
    assert sorted(set(calls)) == [128, 256]
    assert best.measured_seconds is not None
    measured = [c for c in choices if c.measured_seconds is not None]
    assert len(measured) == 2
    assert all("pred_over_meas" in c.row() for c in measured)


def test_autotune_raises_when_nothing_fits():
    nano = BSPAccelerator(p=1, g=0.0, l=0.0, r=1e9, e=4.0, L=64, E=1 << 30)
    with pytest.raises(ValueError, match="fits"):
        planlib.autotune(
            lambda block_k: matmul_plan(128, 128, 128, block_m=128,
                                        block_n=128, block_k=block_k,
                                        dtype=jnp.float32),
            [{"block_k": 128}], nano)


def test_autotune_on_ragged_shapes():
    # the documented pairing: matmul_plan rounds ragged dims up to block
    # multiples, so plan_candidates can be fed straight into autotune
    best, choices = planlib.autotune(
        lambda **p: matmul_plan(192, 512, 512, dtype=jnp.float32, **p),
        plan_candidates(192, 512, 512), ACC)
    assert best.feasible
    assert best.plan.grid[0] * best.params["block_m"] >= 192


def test_plan_candidates_are_clipped_and_deduped():
    cands = plan_candidates(64, 128, 64)
    assert all(c["block_m"] <= 64 and c["block_n"] <= 64 and c["block_k"] <= 128
               for c in cands)
    keys = [tuple(sorted(c.items())) for c in cands]
    assert len(keys) == len(set(keys))


# ------------------------------------------------- host level + runner ----


def test_host_plan_drives_runner_prediction():
    n, c = 4096, 512
    rng = np.random.default_rng(0)
    v = rng.standard_normal(n).astype(np.float32)
    u = rng.standard_normal(n).astype(np.float32)
    ss = StreamSet()
    sv, su = ss.create(v, c), ss.create(u, c)
    plan = planlib.host_plan([sv, su], flops_per_hyperstep=2.0 * c)
    assert plan.num_hypersteps == n // c
    assert plan.inputs[0].words == c

    runner = HyperstepRunner(
        lambda acc, t: acc + float(np.dot(t[0], t[1])), [sv, su],
        plan=plan, machine=ACC)
    out = runner.run(0.0)
    assert out == pytest.approx(float(np.dot(v, u)), rel=1e-4)
    row = runner.predicted_vs_measured()
    assert row["predicted_seconds"] == pytest.approx(
        ACC.flops_to_seconds(plan.cost(ACC)))
    assert row["measured_seconds"] > 0
    assert len(runner.records) == plan.num_hypersteps


def test_runner_clamps_plan_to_stream_remainder():
    # a plan built before the cursors moved must not run the streams off the
    # end — the runner clamps to what the streams can still supply
    ss = StreamSet()
    s = ss.create(np.zeros(4 * 8, np.float32), 8)
    plan = planlib.host_plan([s], flops_per_hyperstep=1.0, num_hypersteps=9)
    runner = HyperstepRunner(lambda acc, t: acc + 1, [s], plan=plan, machine=ACC)
    assert runner.run(0) == 4  # 4 tokens available, not 9


# ------------------------------------------------------------- lowering ----


def test_lowered_plan_matches_jnp():
    """A hand-built StreamPlan lowers to a working Pallas pipeline."""
    from jax.experimental import pallas as pl

    from repro.kernels import pipeline

    def body(x_ref, o_ref, acc_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += x_ref[...]

        @pl.when(t == 3)
        def _():
            o_ref[...] = acc_ref[...]

    plan = StreamPlan(
        name="rowsum",
        grid=(4,),
        inputs=(TokenSpec("x", (1, 128), lambda t: (t, 0),
                          dtype=jnp.float32, full_shape=(4, 128)),),
        outputs=(TokenSpec("o", (1, 128), lambda t: (0, 0),
                           dtype=jnp.float32, full_shape=(1, 128),
                           direction="up", rate=0),),
        scratch=(ScratchSpec("acc", (1, 128), jnp.float32),),
        dimension_semantics=("arbitrary",),
        flops_per_hyperstep=128.0,
    )
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 128)),
                    jnp.float32)
    out = pipeline.lower(plan, body, interpret=True)(x)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-6)


def test_no_kernel_calls_pallas_call_directly():
    """Architectural rule: kernels/pipeline.py is the only pallas_call site."""
    kernels_dir = pathlib.Path(__file__).parent.parent / "src" / "repro" / "kernels"
    offenders = []
    for path in sorted(kernels_dir.rglob("*.py")):
        if path.name == "pipeline.py":
            continue
        # match the call site, not docstring mentions
        if "pallas_call(" in path.read_text():
            offenders.append(path.name)
    assert not offenders, f"kernels must lower through pipeline.lower: {offenders}"


def test_models_flash_lowers_through_pipeline():
    # the custom-vjp wrapper in models/ reuses the kernel entry points, so it
    # inherits the plan lowering; sanity-check it still works end to end
    from repro.models.flash import flash_attention_vjp
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.float32)
    out = flash_attention_vjp(q, k, v, True, 0, 16, 16)
    assert out.shape == q.shape

"""Distribution layer: sharding rules, mesh, cannon matmul, constraints.

Multi-device tests run in a subprocess with XLA_FLAGS device-count override so
the main test process keeps its single-device jax (the dry-run rule: never set
the flag globally).
"""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config
from repro.distributed import ctx
from repro.distributed import sharding as sh
from repro.models import model as M


def _run_sub(code: str, devices: int = 4) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src"}
    import os
    env = {**os.environ, **env}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ------------------------------------------------------------ specs ----


class _FakeMesh:
    """Shape-only stand-in so spec rules can be tested without 512 devices."""

    def __init__(self, shape: dict[str, int]):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        import numpy as np
        return int(np.prod(list(self.shape.values())))


PROD = _FakeMesh({"data": 16, "model": 16})
PROD_MP = _FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [PROD, PROD_MP], ids=["single", "multi"])
def test_param_specs_cover_every_leaf_and_divide(arch, mesh):
    cfg = get_config(arch)
    shapes = M.abstract_params(cfg)
    specs = sh.param_specs(cfg, mesh, shapes)  # raises if any leaf unmatched
    leaves_s = jax.tree_util.tree_leaves(shapes)
    leaves_p = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_s) == len(leaves_p)
    for arr, spec in zip(leaves_s, leaves_p):
        assert len(spec) <= len(arr.shape)
        for dim, entry in zip(arr.shape, tuple(spec)):
            if entry is None:
                continue
            assert dim % sh.axis_size(mesh, entry) == 0, (
                f"{arch}: {arr.shape} not divisible by {entry}")


def test_minicpm_uneven_vocab_stays_replicated():
    cfg = get_config("minicpm-2b")
    shapes = M.abstract_params(cfg)
    specs = sh.param_specs(cfg, PROD, shapes)
    assert tuple(specs["embed"]["tokens"])[0] is None  # 122753 % 16 != 0


def test_moe_expert_sharding_strategy():
    """64 experts -> EP over model; 60 experts -> per-expert TP fallback."""
    for arch, expect_ep in [("moonshot-v1-16b-a3b", True),
                            ("qwen2-moe-a2.7b", False)]:
        cfg = get_config(arch)
        shapes = M.abstract_params(cfg)
        specs = sh.param_specs(cfg, PROD, shapes)
        spec = tuple(specs["stack"][0]["mlp"]["w_up"])
        # leading axis is the scan stack
        assert (spec[1] == "model") == expect_ep


@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_batch_and_cache_specs_divide(shape_name):
    cfg = get_config("jamba-v0.1-52b")
    shape = SHAPES[shape_name]
    spec = sh.batch_spec(cfg, PROD, shape)
    if spec[0] is not None:
        assert shape.global_batch % sh.axis_size(PROD, spec[0]) == 0
    cache_shape = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, min(shape.seq_len, 4096)))
    specs = sh.cache_specs(cfg, PROD, shape, cache_shape)
    for arr, sp in zip(
        jax.tree_util.tree_leaves(cache_shape),
        jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        for dim, entry in zip(arr.shape, tuple(sp)):
            if entry is not None:
                assert dim % sh.axis_size(PROD, entry) == 0


# ---------------------------------------------------------------- ctx ----


def test_constrain_is_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, ctx.DP, None) is x


def test_constrain_filters_nondividing_axes():
    import jax.numpy as jnp
    with ctx.mesh_axes({"data": 16, "model": 16}):
        # dims of 5 are not divisible by any axis: must be a no-op
        x = jnp.ones((5, 5))
        y = ctx.constrain(x, ctx.DP, ctx.TP)
        assert y is x
    assert ctx.dp_size() == 1


def test_dp_size_registers():
    with ctx.mesh_axes({"pod": 2, "data": 16, "model": 16}):
        assert ctx.dp_size() == 32


# --------------------------------------------------------------- cannon ----


def test_cannon_matmul_matches_xla():
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from repro.distributed.cannon import cannon_matmul
        mesh = auto_mesh((2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        for (m, k, n) in [(64, 32, 48), (8, 8, 8), (128, 64, 64)]:
            a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
            b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
            c = cannon_matmul(a, b, mesh=mesh)
            err = float(jnp.abs(c - a @ b).max())
            assert err < 1e-4, (m, k, n, err)
        print("OK")
    """)


def test_cannon_collective_traffic_is_block_sized():
    """Cannon's per-step traffic = one block per neighbour (paper's zero
    redundancy), visible as collective-permutes of exactly block size."""
    out = _run_sub("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import auto_mesh
        from repro.distributed.cannon import cannon_matmul
        from repro.core.hlo import collective_bytes
        mesh = auto_mesh((2, 2), ("data", "model"))
        a = jnp.ones((64, 64), jnp.float32)
        b = jnp.ones((64, 64), jnp.float32)
        txt = jax.jit(lambda a, b: cannon_matmul(a, b, mesh=mesh)
                      ).lower(a, b).compile().as_text()
        s = collective_bytes(txt)
        assert s.op_counts.get("collective-permute", 0) >= 2, s
        print("BYTES", s.total_bytes)
    """)
    assert "BYTES" in out


def test_two_level_cannon_plan_driven_on_4_devices():
    """The flagship path: Algorithm 2 through the multi-core HyperstepRunner
    with the shard_map inner Cannon as the per-hyperstep BSP program, priced
    by the cannon_plan (Eq. 2) on a real 2×2 device grid."""
    _run_sub("""
        import dataclasses
        import jax, numpy as np
        from repro.core import EPIPHANY_III, cannon_bsps_cost
        from repro.distributed.cannon import two_level_cannon
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        n, m_blocks, n_grid = 64, 2, 2
        a = rng.standard_normal((n, n)).astype(np.float32)
        b = rng.standard_normal((n, n)).astype(np.float32)
        acc = dataclasses.replace(EPIPHANY_III, g=1.0, e=1.0)
        c, runner = two_level_cannon(a, b, m_blocks, n_grid=n_grid,
                                     mesh=mesh, machine=acc, compiled=False)
        err = float(np.abs(c - a @ b).max())
        assert err < 1e-3, err
        assert len(runner.core_records) == 4
        assert len(runner.records) == m_blocks**3
        # compute-heavy machine: the plan's Eq. 1 sum is exactly Eq. 2
        want = cannon_bsps_cost(acc, n, m_blocks, n_grid)
        got = runner.plan.cost(acc)
        assert abs(got - want) < 1e-6 * want, (got, want)
        row = runner.predicted_vs_measured()
        assert row["measured_seconds"] > 0
        assert row["fetch_words_measured"] == row["fetch_words_planned"]
        print("CANNON2 OK")
    """)


def test_make_host_mesh_validates_divisibility():
    """model must divide the device count — no silent device drop, and a
    clear error instead of an opaque make_mesh crash when model > n."""
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="exceeds"):
        make_host_mesh(n + 1)
    mesh = make_host_mesh(n)        # model == device count is fine
    assert mesh.shape["model"] == n
    _run_sub("""
        import pytest
        from repro.launch.mesh import make_host_mesh
        with pytest.raises(ValueError, match="drop"):
            make_host_mesh(3)       # 4 devices: would silently drop one
        mesh = make_host_mesh(2)
        assert dict(mesh.shape) == {"data": 2, "model": 2}
        print("MESH OK")
    """)


def test_gspmd_train_step_runs_on_4_devices():
    """End-to-end sharded train step on a real (2,2) mesh — the miniature of
    the production dry-run, actually executed."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.launch.mesh import auto_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as sh, ctx
        from repro.models import model as M
        from repro.optim.adamw import AdamW
        from repro.optim.schedule import constant
        from repro.train.steps import make_train_step

        cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True),
                                  scan_layers=True, remat="full")
        mesh = auto_mesh((2, 2), ("data", "model"))
        with mesh, ctx.mesh_axes(dict(mesh.shape)):
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            specs = sh.param_specs(cfg, mesh, params)
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, specs, is_leaf=lambda x: isinstance(x, P))
            opt = AdamW(schedule=constant(1e-3))
            state = opt.init(params)
            step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
            toks = jax.device_put(
                jnp.zeros((4, 16), jnp.int32),
                NamedSharding(mesh, P(("data",), None)))
            batch = {"tokens": toks, "labels": toks}
            params, state, metrics = step(params, state, batch)
            assert np.isfinite(float(metrics["loss"]))
        print("OK")
    """)


def test_pipeline_parallel_matches_sequential():
    """GPipe fill–drain over a 4-stage ring == sequential stage application."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from repro.distributed.pipeline import pipeline_apply
        mesh = auto_mesh((4,), ("model",))
        rng = np.random.default_rng(0)
        S, M, B, D = 4, 6, 2, 8
        ws = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
        bs = jnp.asarray(rng.standard_normal((S, D)) * 0.1, jnp.float32)
        xs = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

        def stage(p, x):
            w, b = p
            return jnp.tanh(x @ w + b)

        out = pipeline_apply(stage, (ws, bs), xs, mesh=mesh, axis="model")
        want = xs
        for i in range(S):
            want = jnp.tanh(want @ ws[i] + bs[i])
        err = float(jnp.abs(out - want).max())
        assert err < 1e-5, err
        print("PP OK")
    """)

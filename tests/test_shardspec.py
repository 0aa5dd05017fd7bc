"""Declarative sharding layer: spec validation + golden round-trip.

``tests/golden_shardings.json`` was dumped from the hand-written rule
functions the declarative tables replaced (ISSUE 7) — every arch × mesh
params tree plus cache/batch trees for three representative families × all
shapes. The round-trip tests assert the table-driven resolver reproduces
that output *exactly*, so the refactor is behaviour-preserving by
construction. A one-axis entry is compared as the bare axis name on both
sides: ``PartitionSpec(("data",))`` and ``PartitionSpec("data")`` are the same
sharding, and the installed jax writes both as ``"data"``.
"""

import json
import os

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config
from repro.distributed import sharding as sh
from repro.distributed import shardspec as ssp
from repro.models import model as M


class _FakeMesh:
    """Shape-only stand-in so spec rules resolve without 512 devices."""

    def __init__(self, shape: dict[str, int]):
        self.shape = shape
        self.axis_names = tuple(shape)


MESHES = {
    "prod": _FakeMesh({"data": 16, "model": 16}),
    "prod_mp": _FakeMesh({"pod": 2, "data": 16, "model": 16}),
}
HOST_MESH = _FakeMesh({"host": 2, "data": 2, "model": 2})

with open(os.path.join(os.path.dirname(__file__),
                       "golden_shardings.json")) as _f:
    GOLDEN = json.load(_f)


def _entry(e):
    """One spec entry as JSON: None, an axis name, or a list of axis names."""
    if isinstance(e, (tuple, list)):
        return e[0] if len(e) == 1 else list(e)
    return e


def _golden(tree: dict) -> dict:
    return {k: [_entry(e) for e in v] for k, v in tree.items()}


def _dump_tree(spec_tree) -> dict:
    out = {}
    for path, spec in jax.tree_util.tree_leaves_with_path(
            spec_tree, is_leaf=lambda x: isinstance(x, P)):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = [_entry(e) for e in tuple(spec)]
    return out


# ------------------------------------------------------ golden round-trip ----


@pytest.mark.parametrize("mname", list(MESHES))
@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_match_golden(arch, mname):
    cfg = get_config(arch)
    shapes = M.abstract_params(cfg)
    got = _dump_tree(sh.param_specs(cfg, MESHES[mname], shapes))
    assert got == _golden(GOLDEN["params"][f"{arch}::{mname}"])


@pytest.mark.parametrize("mname", list(MESHES))
@pytest.mark.parametrize("sname", list(SHAPES))
@pytest.mark.parametrize("arch",
                         ("jamba-v0.1-52b", "qwen2-moe-a2.7b", "xlstm-1.3b"))
def test_cache_and_batch_specs_match_golden(arch, sname, mname):
    cfg = get_config(arch)
    shape = SHAPES[sname]
    mesh = MESHES[mname]
    cache_shape = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, min(shape.seq_len, 4096)))
    got = _dump_tree(sh.cache_specs(cfg, mesh, shape, cache_shape))
    key = f"{arch}::{sname}::{mname}"
    assert got == _golden(GOLDEN["cache"][key])
    got_batch = [_entry(e) for e in tuple(sh.batch_spec(cfg, mesh, shape))]
    assert got_batch == [_entry(e) for e in GOLDEN["batch"][key]]


# ------------------------------------------------------------- validation ----


def test_unknown_logical_axis_names_the_rule_and_known_axes():
    rules = (ssp.Rule("w", (ssp.dim("bogus"),), rank=1),)
    ctx = ssp.build_context(MESHES["prod"])
    with pytest.raises(ValueError) as e:
        ssp.resolve_leaf(rules, ["w"], (64,), ctx, MESHES["prod"],
                         scanned=False)
    assert "bogus" in str(e.value) and "tp" in str(e.value)


def test_non_divisible_dim_replicates():
    # 18 % 16 != 0: the tp alternative is infeasible, the dim degrades to
    # replication instead of handing GSPMD an uneven sharding
    rules = (ssp.Rule("w", (ssp.dim("tp"),), rank=1),)
    ctx = ssp.build_context(MESHES["prod"])
    spec = ssp.resolve_leaf(rules, ["w"], (18,), ctx, MESHES["prod"],
                            scanned=False)
    assert tuple(spec) == (None,)


def test_non_divisible_required_dim_fails_to_next_rule():
    # the EP-else-TP pattern: required dim infeasible -> next matching rule
    rules = (
        ssp.Rule("w", (ssp.dim("ep", required=True), ssp.REPLICATED), rank=2),
        ssp.Rule("w", (ssp.REPLICATED, ssp.dim("tp")), rank=2),
    )
    ctx = ssp.build_context(MESHES["prod"])
    spec = ssp.resolve_leaf(rules, ["w"], (60, 64), ctx, MESHES["prod"],
                            scanned=False)          # 60 % 16 != 0
    assert tuple(spec) == (None, "model")
    spec = ssp.resolve_leaf(rules, ["w"], (64, 64), ctx, MESHES["prod"],
                            scanned=False)
    assert tuple(spec) == ("model", None)


def test_no_axis_reuse_within_a_leaf():
    # both dims want model; the second dim must not double-spend it
    rules = (ssp.Rule("w", (ssp.dim("tp"), ssp.dim("tp")), rank=2),)
    ctx = ssp.build_context(MESHES["prod"])
    spec = ssp.resolve_leaf(rules, ["w"], (64, 64), ctx, MESHES["prod"],
                            scanned=False)
    assert tuple(spec) == ("model", None)


def test_unmatched_leaf_raises_with_kind_and_path():
    ctx = ssp.build_context(MESHES["prod"])
    with pytest.raises(ValueError, match="no cache rule for a/b"):
        ssp.resolve_leaf((), ["a", "b"], (4,), ctx, MESHES["prod"],
                         scanned=False, kind="cache")


def test_dp_axes_include_host():
    assert ssp.dp_axes(HOST_MESH) == ("host", "data")
    assert ssp.dp_axes(MESHES["prod_mp"]) == ("pod", "data")


# ------------------------------------------------------- host h-relation ----


def test_host_h_relation_counts_gathered_and_reduced():
    specs = {"a": P(("host", "data"), "model"), "b": P(None, "model")}
    shapes = {"a": jax.ShapeDtypeStruct((8, 8), "float32"),
              "b": jax.ShapeDtypeStruct((4, 4), "float32")}
    rel = ssp.host_h_relation(HOST_MESH, specs, shapes)
    assert rel["hosts"] == 2
    assert rel["gathered_words"] == 64.0
    assert rel["reduced_words"] == 16.0
    # 3 transfers of the gathered half + 2 of the reduced half, frac = 1/2
    assert rel["h_words"] == pytest.approx(3 * 64 * 0.5 + 2 * 16 * 0.5)
    assert rel["supersteps"] == 3.0


def test_host_h_relation_zero_without_host_axis():
    rel = ssp.host_h_relation(MESHES["prod"], {"a": P()},
                              {"a": jax.ShapeDtypeStruct((8,), "float32")})
    assert rel["h_words"] == 0.0 and rel["hosts"] == 1

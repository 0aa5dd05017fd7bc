import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (assignment: MULTI-POD DRY-RUN §3 + ROOFLINE):

  1. the full-depth, layer-scanned step compiled for the production mesh —
     proves the sharding is coherent and reports ``memory_analysis()``
     (bytes/device) and the collective schedule;
  2. (``--roofline``) two *unrolled* reduced-depth lowerings (1 and 2 pattern
     periods, time-loops unrolled) whose cost/collective deltas give the exact
     per-layer cost; the cell's true HLO terms are the affine extrapolation
     ``f1 + (n_periods − 1)·(f2 − f1)`` — necessary because XLA's
     ``cost_analysis`` counts a ``lax.scan`` body once (verified; see
     EXPERIMENTS.md §Roofline methodology);
  3. the three BSPS roofline terms (compute / HBM / ICI) from those corrected
     counts, per :mod:`repro.core.roofline`.

Results append to a JSONL file consumed by ``benchmarks/`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
      --shape train_4k --mesh both --roofline --out results/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.core import plan as planlib
from repro.core import roofline as rf
from repro.core.bsp import TPU_V5E_CHIP, BSPAccelerator
from repro.core.calibstore import get_default_store
from repro.core.health import HealthMonitor
from repro.core.hlo import collective_bytes, fused_bytes
from repro.distributed import ctx
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim.adamw import AdamW
from repro.optim.schedule import constant
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "decode":
        if cfg.frontend != "none":
            return {"embeds": jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)}
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    batch: dict[str, Any] = {}
    if cfg.frontend != "none":
        batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
    if cfg.rope_type == "mrope":
        batch["positions"] = jax.ShapeDtypeStruct((3, b, s), i32)
    if shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct((b, s), i32)
    return batch


def batch_shardings(cfg: ModelConfig, mesh, shape: ShapeSpec, batch) -> Any:
    if shape.kind == "decode":
        # decode inputs are (B, 1) / (B, 1, d): batch over DP if divisible,
        # never sequence-sharded (the *cache* carries the SP sharding)
        dp = sh.dp_axes(mesh)
        ba = dp if shape.global_batch % sh.axis_size(mesh, dp) == 0 else None
        return jax.tree_util.tree_map(
            lambda leaf: NamedSharding(
                mesh, P(ba, *([None] * (len(leaf.shape) - 1)))),
            batch)
    spec = sh.batch_spec(cfg, mesh, shape)

    def one(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        name = names[-1]
        nd = len(leaf.shape)
        if name == "positions":          # (3, B, S)
            return NamedSharding(mesh, P(None, *spec))
        base = list(spec) + [None] * (nd - 2)
        return NamedSharding(mesh, P(*base[:nd]))

    return jax.tree_util.tree_map_with_path(one, batch)


def _lower_cell(cfg: ModelConfig, mesh, shape: ShapeSpec, *, unroll_time: bool):
    """Build abstract inputs + shardings, return (lowered, meta)."""
    params_shape = M.abstract_params(cfg)
    pspecs = sh.param_specs(cfg, mesh, params_shape)
    pshard = sh.named(mesh, pspecs)
    batch = input_specs(cfg, shape)
    bshard = batch_shardings(cfg, mesh, shape, batch)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt = AdamW(schedule=constant(1e-4))
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        oshard = sh.named(mesh, ospecs)
        step = make_train_step(cfg, opt, unroll_time=unroll_time)
        jitted = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_shape, opt_shape, batch)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, unroll_time=unroll_time)
        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        lowered = jitted.lower(params_shape, batch)
    else:  # decode
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = sh.cache_specs(cfg, mesh, shape, cache_shape)
        cshard = sh.named(mesh, cspecs)
        step = make_serve_step(cfg, unroll_time=unroll_time)
        jitted = jax.jit(
            step,
            in_shardings=(pshard, cshard, bshard),
            out_shardings=(None, cshard),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_shape, cache_shape, batch)
    return lowered


def _compile_stats(lowered) -> dict[str, float]:
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    coll = collective_bytes(text)
    ma = compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "bytes_fused": float(fused_bytes(text)),
        "coll_bytes": float(coll.total_bytes),
        "coll_by_kind": {k: float(v) for k, v in coll.by_kind.items()},
        "coll_ops": dict(coll.op_counts),
        "peak_bytes": float(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        ),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "arg_bytes": float(ma.argument_size_in_bytes),
    }


def _reduced(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    return dataclasses.replace(
        cfg, num_layers=n_periods * len(cfg.pattern), scan_layers=False,
    )


def analytic_extra_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """FLOPs hidden inside time-scans that cost_analysis counts once.

    Three recurrent bodies stay as ``lax.scan`` even in the roofline lowerings
    (unrolling them explodes compile time for <3% of model FLOPs — measured
    against the projection matmuls, which are hoisted out of every scan):

    * sLSTM per-step recurrence: 2·d·4dh matvec + ~30·d gates per token;
    * mLSTM chunk body (chunk=128): scores/pv ≈ 4·ck·di + state read/update
      ≈ 4·di·dh per token;
    * mamba chunk body: ≈ 10·di·ds per token (cum/exp/einsums).

    ×3 when training (fwd + ~2× bwd). Attention chunk scans ARE unrolled in
    the roofline lowerings (their quadratic term dominates), so no correction.
    """
    counts = {"slstm": 0, "mlstm": 0, "mamba": 0}
    for _, b in cfg.blocks():
        if b.mixer in counts:
            counts[b.mixer] += 1
    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    mult = 3.0 if shape.kind == "train" else 1.0
    d = cfg.d_model
    dh_s = d // cfg.num_heads
    extra = counts["slstm"] * (2 * d * 4 * dh_s + 30 * d)
    di_m = cfg.mlstm_expand * d
    dh_m = di_m // cfg.num_heads
    ck = 128
    extra += counts["mlstm"] * (4 * ck * di_m + 4 * di_m * dh_m)
    extra += counts["mamba"] * (10 * cfg.ssm_d_inner * cfg.ssm_d_state)
    return extra * tokens * mult


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def stream_plan_report(
    cfg: ModelConfig, shape: ShapeSpec, acc: BSPAccelerator = TPU_V5E_CHIP,
    *, chips: int = 1, health: Any = None,
) -> dict[str, Any]:
    """Chip-level StreamPlans for the cell's kernel hot-spots.

    For each hot-spot the planner (:func:`repro.core.plan.autotune`)
    enumerates MXU-aligned block sizes under the double-buffered VMEM budget,
    scores them with Eq. 1 on the v5e chip pack, and the chosen blocks +
    predicted seconds are recorded next to the cell's measured roofline
    terms — the cost-model side of the predicted-vs-measured table.

    ``chips`` divides the batch/token dimensions so the plan prices one
    chip's slice of the cell, in the same per-device units as the roofline
    terms it sits next to.
    """
    from repro.kernels.flash_attention import attention_plan, attention_tiles
    from repro.kernels.streamed_matmul import matmul_plan, plan_candidates

    def pick(build, candidates):
        # closed-form scoring: production-shaped grids make the exact fetch
        # enumeration cost seconds per candidate for no ranking benefit
        best, _ = planlib.autotune(build, candidates, acc, exact=False)
        if health is not None:
            # fold verifier findings into the shared BSPS rollup so the
            # dry-run record speaks the same code vocabulary as live stats
            health.ingest_diagnostics(best.diagnostics)
        return {
            **best.params,
            "predicted_seconds": best.predicted_seconds,
            "vmem_bytes": best.plan.vmem_bytes,
            "bandwidth_heavy": best.plan.bandwidth_heavy(acc, exact=False),
            # static verifier findings for the chosen plan (DESIGN.md §9) —
            # dryrun output doubles as a lint report for the cell's hot-spots
            "diagnostics": [d.format() for d in best.diagnostics],
        }

    report: dict[str, Any] = {}
    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    tokens = max(1, -(-tokens // chips))           # per-chip slice (batch DP)
    batch = max(1, -(-shape.global_batch // chips))
    d_ff = cfg.d_ff or cfg.moe_d_ff or 4 * cfg.d_model

    def build_mm(block_m, block_n, block_k):
        # matmul_plan rounds ragged dims up to block multiples itself
        return matmul_plan(
            tokens, cfg.d_model, d_ff,
            block_m=block_m, block_n=block_n, block_k=block_k,
            dtype=jnp.bfloat16,
        )

    report["ffn_matmul"] = pick(build_mm, plan_candidates(tokens, cfg.d_model, d_ff))

    sq = 1 if shape.kind == "decode" else shape.seq_len
    skv = shape.seq_len
    d_head = cfg.head_dim_

    hkv = max(cfg.num_kv_heads, 1)

    def build_attn(block_q, block_kv, heads):
        return attention_plan(
            batch, cfg.num_heads, hkv,
            _round_up(sq, block_q), _round_up(skv, block_kv), d_head,
            block_q=block_q, block_kv=block_kv, heads=heads,
            causal=True, q_offset=skv - sq, dtype=jnp.bfloat16,
        )

    # the kernel picks its own hyperstep from the shapes; record that one
    bq, bkv, heads = attention_tiles(cfg.num_heads, hkv, sq, skv, d_head)
    report["attention"] = pick(build_attn, [
        {"block_q": bq, "block_kv": bkv, "heads": heads}])
    return report


def _coerce(v: str):
    for t in (int, float):
        try:
            return t(v)
        except ValueError:
            continue
    if v in ("True", "False"):
        return v == "True"
    return v


def run_cell(
    arch: str, shape_name: str, *, multi_pod: bool, roofline: bool,
    tag: str = "baseline", overrides: dict[str, Any] | None = None,
) -> dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    health = HealthMonitor(name=f"dryrun_{arch}_{shape_name}")
    plans = stream_plan_report(cfg, shape, chips=chips, health=health)
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "kind": shape.kind, "tag": tag,
        "attn_impl": os.environ.get("REPRO_ATTN_IMPL", "blockwise"),
        "overrides": overrides or {},
        # cost-model side of the predicted-vs-measured table: planner-chosen
        # block sizes + Eq. 1 predictions for one chip's slice of the cell
        "stream_plans": plans,
        # flattened verifier findings across the cell's hot-spot plans —
        # empty means every chosen plan passed static verification
        "plan_diagnostics": sorted(
            {line for hs in plans.values() for line in hs.get("diagnostics", ())}),
        # static findings rolled up by BSPS code, same shape as
        # ServeEngine.stats()["health"] / train() result["health"]
        "health": health.rollup(),
        # what measured evidence this process has accumulated (DESIGN.md
        # §11): band coverage tells the reader which of the cell's Eq. 1
        # predictions a store refit could already cross-check
        "calibstore": get_default_store().summary(),
    }

    t0 = time.time()
    with mesh, ctx.mesh_axes(dict(mesh.shape)):
        lowered = _lower_cell(cfg, mesh, shape, unroll_time=False)
        full = _compile_stats(lowered)
    rec["full"] = full
    rec["compile_s"] = round(time.time() - t0, 1)

    if roofline:
        t1 = time.time()
        with mesh, ctx.mesh_axes(dict(mesh.shape)):
            f1 = _compile_stats(_lower_cell(_reduced(cfg, 1), mesh, shape,
                                            unroll_time=True))
            f2 = _compile_stats(_lower_cell(_reduced(cfg, 2), mesh, shape,
                                            unroll_time=True))
        n = cfg.n_periods
        corr = {k: f1[k] + (n - 1) * (f2[k] - f1[k])
                for k in ("flops", "bytes", "bytes_fused", "coll_bytes")}
        corr["flops"] += analytic_extra_flops(cfg, shape) / chips
        rec["f1"], rec["f2"], rec["corrected"] = f1, f2, corr
        rec["roofline_compile_s"] = round(time.time() - t1, 1)

        total, active = cfg.param_counts()
        tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
        mf = rf.model_flops(params=total, active_params=active, tokens=tokens,
                            training=shape.kind == "train")
        report = rf.RooflineReport(
            name=f"{arch}/{shape_name}", chips=chips,
            hlo_flops=corr["flops"], hlo_bytes=corr["bytes"],
            coll_bytes=corr["coll_bytes"], coll_stats=None,
            model_flops_global=mf, peak_device_bytes=full["peak_bytes"],
            hw=rf.TPU_V5E,  # the production mesh is a v5e pod
        )
        fused = rf.RooflineReport(
            name=f"{arch}/{shape_name}", chips=chips,
            hlo_flops=corr["flops"], hlo_bytes=corr["bytes_fused"],
            coll_bytes=corr["coll_bytes"], coll_stats=None,
            model_flops_global=mf, peak_device_bytes=full["peak_bytes"],
            hw=rf.TPU_V5E,  # the production mesh is a v5e pod
        )
        row = report.row()
        row["memory_fused_s"] = fused.memory_seconds
        row["dominant_fused"] = fused.dominant
        row["roofline_frac_fused"] = fused.roofline_fraction
        rec["roofline"] = row
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field overrides, e.g. --override vocab_pad_to=128")
    args = ap.parse_args()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = _coerce(v)

    cfg = get_config(args.arch)
    shapes = applicable_shapes(cfg) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for shape_name in shapes:
        for mp in meshes:
            # roofline terms are reported single-pod only (assignment §Roofline)
            do_roof = args.roofline and not mp
            rec = run_cell(args.arch, shape_name, multi_pod=mp,
                           roofline=do_roof, tag=args.tag, overrides=overrides)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            r = rec.get("roofline")
            extra = (f" | {r['dominant']}-bound mfu={r['roofline_frac']:.3f}"
                     if r else "")
            print(
                f"[dryrun] {args.arch} {shape_name} mesh={rec['mesh']} OK "
                f"peak={rec['full']['peak_bytes'] / 1e9:.2f}GB/dev "
                f"compile={rec['compile_s']}s{extra}",
                flush=True,
            )


if __name__ == "__main__":
    main()

"""Smoke run of the system's main path on a TPU, at minicpm-2b's published widths.

    python chip_smoke.py              # one chip: kernels, serve engine, train loop
    python chip_smoke.py --chips 4    # four chips: sharded train step, two-level Cannon

One process holds the chip(s) from start to end and starts no other. Phases:

1. device  — JAX must report a TPU, or the script exits non-zero and prints
   no result.
2. kernels — each Pallas kernel of the main path, compiled (its program must
   hold a ``tpu_custom_call``), against its ``kernels/ref.py`` oracle computed
   in float32 on the chip.
3. serve   — ``ServeEngine`` on the published minicpm-2b with seeded weights
   drains 8 requests; every request gets exactly its tokens, and the engine's
   cached prefill agrees with ``M.forward``.
4. train   — ``train()`` takes 3 steps of minicpm-2b cut to 4 layers; every
   loss is finite and the first is near the initial model's expected loss.

``--chips 4`` runs only the path that exists across chips: one ``train()``
step under a (data 2, model 2) mesh against the same step on one device, and
``two_level_cannon`` on the 2×2 grid against a plain matmul.

Timings printed here are smoke readings — one run, compilation included in
the first call of each shape — not benchmark numbers. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.distributed.cannon import two_level_cannon  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.engine import ServeEngine  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import make_prefill, prefill_block_size  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.layers import EMBED_INIT_STD  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.optim.schedule import constant  # noqa: E402
from repro.train.loop import TrainConfig, train  # noqa: E402

GB = 1e9


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Size:
    """Every shape the smoke runs. :func:`published` is the only size the
    script itself uses; tests build a small one to drive the phases on CPU."""

    serve_cfg: ModelConfig
    train_cfg: ModelConfig
    matmuls: tuple[tuple[int, int, int], ...]     # (M, K, N)
    attention: tuple[int, int, int, int]           # (B, H, S, D)
    lanes: int
    pool_seq: int
    segment_len: int
    requests: int
    prompt_lens: tuple[int, ...]
    new_tokens: tuple[int, int]                    # inclusive range
    train_batch: int
    train_seq: int
    train_steps: int
    cannon_n: int
    cannon_blocks: int


def published() -> Size:
    cfg = get_config("minicpm-2b")
    return Size(
        serve_cfg=cfg,
        # published widths, depth cut to 4 layers: 527 M params, ~8.4 GB
        # with AdamW state
        train_cfg=dataclasses.replace(cfg, num_layers=4),
        matmuls=((4, 2304, 5760), (2048, 2304, 5760)),   # decode / prefill M
        attention=(2, 36, 1024, 64),
        lanes=4, pool_seq=1024, segment_len=16,
        requests=8, prompt_lens=(128, 512), new_tokens=(64, 128),
        train_batch=2, train_seq=1024, train_steps=3,
        cannon_n=2048, cannon_blocks=2,
    )


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# -- 1. device ----------------------------------------------------------------


def device_phase(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    say("device", f"{info} jax {jax.__version__}")
    check(dev.platform == "tpu", f"JAX found no TPU (platform {dev.platform!r})")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    return info


# -- 2. kernels against their oracles -------------------------------------------


def _bf16_normal(key, shape, scale: float = 1.0) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def _oracle(fn, *args, **kwargs) -> jax.Array:
    """``fn`` on float32 copies of ``args``, every matmul at full precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(fn, **kwargs))(
            *(a.astype(jnp.float32) for a in args))


def _compare(name: str, got, want, *, rtol: float, atol: float) -> dict:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.abs(got - want)
    worst = float(jnp.max(err / (atol + rtol * jnp.abs(want))))
    row = {"kernel": name, "max_abs_err": float(jnp.max(err)),
           "atol": atol, "rtol": rtol, "worst_over_tol": worst,
           "finite": bool(jnp.all(jnp.isfinite(got)))}
    say("kernels", json.dumps(row))
    check(row["finite"] and worst <= 1.0, f"{name} disagrees with its oracle")
    return row


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def kernel_phase(size: Size, *, seed: int, on_chip: bool) -> list[dict]:
    """Each kernel of the path, compiled, against ``ref.py`` in float32.

    ``on_chip`` demands a ``tpu_custom_call`` in each compiled program, so
    an interpreted kernel cannot pass; tests on CPU pass False.
    """
    rows = []
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    for m, k, n in size.matmuls:
        a = _bf16_normal(next(keys), (m, k))
        b = _bf16_normal(next(keys), (k, n), k ** -0.5)
        if on_chip:
            check("tpu_custom_call" in _compiled_text(ops.matmul, a, b),
                  f"matmul {m}x{k}x{n} compiled without its Pallas kernel")
        # Inputs are bf16, exact in f32, and the kernel accumulates in f32:
        # what the oracle lacks is the bf16 rounding of the output (half an
        # ulp, 2^-8 of |C|) and the summation order, bounded by
        # k·2^-24·Σ|a||b| ≈ 6e-3 at k = 2304 for these scales.
        rows.append(_compare(f"streamed_matmul {m}x{k}x{n}",
                             jax.jit(ops.matmul)(a, b),
                             _oracle(ref.matmul_ref, a, b), rtol=2.0 ** -8,
                             atol=1e-2))
    bsz, h, s, d = size.attention
    q, k_, v = (_bf16_normal(next(keys), (bsz, h, s, d)) for _ in range(3))
    if on_chip:
        check("tpu_custom_call" in _compiled_text(ops.attention, q, k_, v),
              "flash_attention compiled without its Pallas kernel")
    # The output is a convex combination of v rows: rounding the softmax
    # weights to bf16 for the PV product (2^-8 each) and the output to bf16
    # (2^-8) move it by at most 2^-7·max|v|.
    rows.append(_compare(f"flash_attention {bsz}x{h}x{s}x{d}",
                         jax.jit(ops.attention)(q, k_, v),
                         _oracle(ref.attention_ref, q, k_, v, causal=True),
                         rtol=0.0,
                         atol=float(jnp.max(jnp.abs(v.astype(jnp.float32))))
                         / 128))
    return rows


# -- 3. serve -------------------------------------------------------------------


def init_loss(cfg: ModelConfig) -> float:
    """Expected cross-entropy of the freshly initialised model.

    The final norm leaves unit-RMS features, so the head's logits are about
    N(0, σ²) with σ² = d_model·std², std the head's init scale; for V such
    logits E[CE] = ln V + σ²/2 (ln V is the σ → 0 limit).
    """
    std = EMBED_INIT_STD if cfg.tie_embeddings else cfg.d_model ** -0.5
    return math.log(cfg.vocab_size) + cfg.d_model * std ** 2 / 2


def serve_phase(size: Size, *, seed: int) -> dict:
    cfg = size.serve_cfg
    t0 = time.perf_counter()
    # op by op, not under one jit: the layers share shapes, so each random
    # draw compiles once (one jit over the 40 unrolled layers takes minutes)
    params = jax.block_until_ready(M.init_params(cfg, jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, max_lanes=size.lanes,
                      pool_seq=size.pool_seq, segment_len=size.segment_len)
    build_s = time.perf_counter() - t0
    say("serve", f"params {M.count_params(cfg) / 1e9:.3f} B, init "
        f"{init_s:.1f}s; engine built in {build_s:.1f}s on machine pack "
        f"{eng.machine.name!r}")

    rng = np.random.default_rng(seed)
    submitted = {}
    for i in range(size.requests):
        plen = size.prompt_lens[i % len(size.prompt_lens)]
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        new = int(rng.integers(size.new_tokens[0], size.new_tokens[1] + 1))
        submitted[eng.submit(prompt, new, seed=i)] = (prompt, new)
    blocks = {p: prefill_block_size(cfg, 1, p, eng.machine)
              for p in size.prompt_lens}
    say("serve", f"prefill block per prompt length: {blocks}")

    t0 = time.perf_counter()
    out = eng.run_until_drained()
    drain_s = time.perf_counter() - t0
    for rid, (prompt, new) in submitted.items():
        toks = out[rid]
        check(len(toks) == len(prompt) + new,
              f"request {rid} returned {len(toks) - len(prompt)} of {new} tokens")
        check(np.array_equal(toks[: len(prompt)], prompt),
              f"request {rid} lost its prompt")
        gen = toks[len(prompt):]
        check(0 <= int(gen.min()) and int(gen.max()) < cfg.vocab_size,
              f"request {rid} emitted ids outside [0, {cfg.vocab_size})")
    check(bool(jnp.all(jnp.isfinite(eng.lane_logits))),
          "non-finite decode logits")

    # the cache path (engine prefill) against the full-sequence forward
    prompt = next(p for p, _ in submitted.values()
                  if len(p) == max(size.prompt_lens))
    prefill = make_prefill(cfg, blocks[len(prompt)])
    cached, _ = prefill(params, M.init_cache(cfg, 1, size.pool_seq),
                        jnp.asarray(prompt[None]))
    full, _ = jax.jit(functools.partial(M.forward, cfg))(
        params, jnp.asarray(prompt[None]))
    cached = cached[0, -1].astype(jnp.float32)
    full = full[0, -1, : cfg.vocab_size].astype(jnp.float32)
    rel = float(jnp.linalg.norm(cached - full) / jnp.linalg.norm(full))
    say("serve", f"prefill vs forward last-position logits: relative L2 "
        f"{rel:.3e} (tolerance 5e-2), finite "
        f"{bool(jnp.all(jnp.isfinite(cached)))}")
    # Both paths keep the residual stream in bf16 and round each layer's
    # attention output once to bf16 (2^-8), from different kernels (the
    # cache's dense attention against the Pallas flash); over 40 layers that
    # drift stays a few 2^-8, under 5e-2.
    check(bool(jnp.all(jnp.isfinite(cached))) and rel <= 5e-2,
          f"prefill and forward disagree (relative L2 {rel:.3e})")

    segs = eng.segment_log
    steady = segs[1:] or segs
    steady_tps = (sum(s["tokens"] for s in steady)
                  / max(sum(s["wall_seconds"] for s in steady), 1e-12))
    firsts = {}
    for r in sorted(eng.finished.values(), key=lambda r: r.rid):
        firsts.setdefault(r.prompt_len, r.prefill_seconds)
    mem = eng.segment_memory()
    stats = eng.stats()
    readings = {
        "drain_seconds": drain_s,
        "tokens": stats["tokens"],
        "segments": stats["segments"],
        "first_segment_seconds_incl_compile": segs[0]["wall_seconds"],
        "steady_decode_tokens_per_s": steady_tps,
        "first_prefill_seconds_incl_compile": firsts,
        "mean_occupancy": stats["mean_occupancy"],
        "segment_program_gb": {
            "arguments": mem.argument_size_in_bytes / GB,
            "outputs": mem.output_size_in_bytes / GB,
            "aliased": mem.alias_size_in_bytes / GB,
            "temporaries": mem.temp_size_in_bytes / GB,
        },
        "prefill_forward_rel_l2": rel,
    }
    say("serve", "smoke readings (one run, not a benchmark): "
        + json.dumps(readings))
    return readings


# -- 4. train -------------------------------------------------------------------


def _train(size: Size, steps: int, *, seed: int, mesh=None) -> dict:
    cfg = size.train_cfg
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=size.train_seq,
                      global_batch=size.train_batch, seed=seed)
    tcfg = TrainConfig(steps=steps, log_every=1, seed=seed)
    return train(cfg, tcfg, AdamW(schedule=constant(1e-4)), data_cfg=data,
                 mesh=mesh, log=lambda m: print(m, flush=True))


def train_phase(size: Size, *, seed: int) -> dict:
    t0 = time.perf_counter()
    out = _train(size, size.train_steps, seed=seed)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in out["history"]]
    check(len(losses) == size.train_steps,
          f"{len(losses)} of {size.train_steps} steps ran")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    expect = init_loss(size.train_cfg)
    check(abs(losses[0] - expect) <= 0.5,
          f"first loss {losses[0]:.4f} is not within 0.5 of the initial "
          f"model's {expect:.4f}")
    readings = {"losses": losses, "expected_first_loss": expect,
                "grad_norms": [h["grad_norm"] for h in out["history"]],
                "train_seconds_incl_compile": wall}
    say("train", "smoke readings (one run, not a benchmark): "
        + json.dumps(readings))
    return readings


# -- four chips ------------------------------------------------------------------


def four_chip_phase(size: Size, *, seed: int) -> dict:
    """One sharded train() step against the same step on one device, and the
    two-level Cannon on the 2×2 grid against a plain matmul."""
    single = _train(size, 1, seed=seed)["history"][0]
    gc.collect()
    mesh = make_host_mesh(model=2)
    say("four", f"mesh {dict(mesh.shape)}")
    sharded = _train(size, 1, seed=seed, mesh=mesh)["history"][0]
    row = {k: {"one_device": single[k], "mesh": sharded[k],
               "rel_diff": abs(sharded[k] - single[k]) / abs(single[k])}
           for k in ("loss", "grad_norm")}
    say("four", "train step: " + json.dumps(row))
    # Same seed, batch and f32 optimizer math; the partitioned program sums
    # bf16 matmul partials in another order (model-axis all-reduces), a few
    # 2^-8 at most in the loss and the gradient norm.
    for k, tol in (("loss", 1e-2), ("grad_norm", 5e-2)):
        check(row[k]["rel_diff"] <= tol,
              f"sharded {k} differs from one device by "
              f"{row[k]['rel_diff']:.3e} (tolerance {tol})")

    rng = np.random.default_rng(seed)
    n = size.cannon_n
    # bf16-representable f32 values: every product is exact whatever pass
    # count the MXU uses, so only the summation order differs
    a, b = (np.asarray(jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16),
                       np.float32) for _ in range(2))
    c, _ = two_level_cannon(a, b, size.cannon_blocks, n_grid=2, mesh=mesh)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.asarray(a) @ jnp.asarray(b))
    err = float(np.max(np.abs(np.asarray(c) - want)))
    # f32 sums of n exact products: |error| <= n·2^-24·Σ|a||b| ≈ 0.1 at
    # n = 2048 (Σ|a||b| ≈ 0.64·n); the bound is loose, typical errors ~1e-4
    tol = n * 2.0 ** -24 * 0.64 * n
    say("four", f"two_level_cannon {n}x{n}, {size.cannon_blocks} outer "
        f"blocks on 2x2: max |C - A@B| {err:.3e} (tolerance {tol:.3e})")
    check(err <= tol, f"two_level_cannon error {err:.3e} > {tol:.3e}")
    return {"train_step": row, "cannon_max_abs_err": err}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip path and its comparisons")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = device_phase(args.chips)
        say("device", f"compilation cache: {enable_compile_cache()}")
        size = published()
        if args.chips == 4:
            four_chip_phase(size, seed=args.seed)
        else:
            kernel_phase(size, seed=args.seed, on_chip=True)
            serve_phase(size, seed=args.seed)
            gc.collect()
            train_phase(size, seed=args.seed)
        dev = jax.devices()[0]
        say("device", f"peak_bytes_in_use "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use', 0) / GB:.3f}"
            " GB on device 0")
    except Exception:  # noqa: BLE001 — any failing phase fails the smoke
        traceback.print_exc()
        print("[smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: batched autoregressive decoding as a BSPS program.

``python -m repro.launch.serve --arch <id> --smoke --batch 4 --steps 32``

Prefill is one jitted chunked pass (a ``lax.scan`` of the decode step over
``block``-token chunks of the prompt, block size autotuned under the local
memory budget by :func:`prefill_block_size` — a single dispatch instead of
O(prompt_len) of them), then
decode runs through :class:`repro.core.hyperstep.HyperstepRunner`: each
generated token is one hyperstep whose jitted step samples from the resident
logits and advances the model, the KV/state cache is the persistent local
state (a :class:`~repro.core.plan.ScratchSpec` in the plan), and the sampled
token ids are written *up* into a backing :class:`~repro.core.stream.Stream`
— the serve path's write-back stream.

By default the whole decode is **one compiled dispatch**: the hyperstep loop
is lowered by :meth:`HyperstepRunner.compile` into a single jitted
``lax.scan`` over all generated tokens, killing the dispatch-per-token path
(the runner — and with it the traced program — is cached per
``(cfg, temperature, batch, prompt_len, steps)``, so repeated ``generate()``
calls, the serving hot path, reuse one program). ``compiled=False`` keeps the
instrumented one-dispatch-per-token loop with per-token timings. Either way
the run is priced by :func:`repro.core.plan.host_plan` and reports its
``predicted_vs_measured()`` row; prefill and decode timings are reported
separately.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.bsp import BSPAccelerator
from repro.core.calibrate import default_machine
from repro.core.hyperstep import HyperstepRecord, HyperstepRunner
from repro.core.plan import ScratchSpec, StreamPlan, autotune, host_plan, streamed_operand
from repro.core.stream import StreamSet
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.registry import Registry
from repro.models import model as M
from repro.train.steps import make_serve_step


@dataclasses.dataclass
class ServeStats:
    """Timings + cost-model row for one :func:`generate` call.

    ``decode_seconds`` is per generated token in measure mode
    (``compiled=False``); in compiled mode the whole decode is one dispatch,
    so it holds a single entry — the whole-run decode time.
    """

    prefill_seconds: float
    decode_seconds: list[float]
    records: list[HyperstepRecord]
    plan_row: dict[str, float] | None = None
    compiled: bool = False

    @property
    def decode_total_seconds(self) -> float:
        return float(sum(self.decode_seconds))


@functools.lru_cache(maxsize=32)
def make_prefill(cfg, block: int = 1):
    """One jitted chunked prefill: prompt -> (last-position logits, warm cache).

    Internally a ``lax.scan`` of the decode step over ``block``-token chunks
    of the prompt — identical cache contents to the per-token loop, one XLA
    dispatch, and ``ceil(S / block)`` scan iterations instead of ``S``. A
    prompt length that is not a multiple of ``block`` pays one leading partial
    chunk (``S mod block`` tokens) so the scanned chunks stay uniform.

    ``block=1`` (the default) is the original token-at-a-time scan and works
    for every mixer type; ``block > 1`` needs an attention-only stack (the
    recurrent mixers consume one token per step — see
    :func:`repro.models.model.decode_step`). Pick the block with
    :func:`prefill_block_size`, which autotunes it under the machine's
    local-memory budget.
    """
    serve_step = make_serve_step(cfg)
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    if block > 1 and any(b.mixer != "attn" for b in cfg.pattern):
        raise ValueError(
            f"chunked prefill needs an attention-only stack; {cfg.name} "
            "has recurrent mixers (use block=1)")

    def prefill(params, cache, prompt):          # prompt: (B, S) int32
        b, s = prompt.shape
        lead = s % block or block                # partial chunk goes first
        logits, cache = serve_step(params, cache, {"tokens": prompt[:, :lead]})
        logits = logits[:, -1:]
        num_chunks = (s - lead) // block
        if num_chunks:
            def body(carry, chunk):              # chunk: (block, B) int32
                cache, _ = carry
                lg, cache = serve_step(params, cache, {"tokens": chunk.T})
                return (cache, lg[:, -1:]), None

            chunks = prompt[:, lead:].T.reshape(num_chunks, block, b)
            (cache, logits), _ = jax.lax.scan(body, (cache, logits), chunks)
        return logits, cache

    return jax.jit(prefill, donate_argnums=(1,))


def _prefill_plan(cfg, batch: int, prompt_len: int, block: int) -> StreamPlan:
    """Eq. 1 plan for a chunked prefill: chunk down-stream + cache scratch."""
    cache_shapes = jax.eval_shape(lambda: M.init_cache(cfg, batch, prompt_len))
    cache_bytes = sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(cache_shapes) if hasattr(x, "shape"))
    return StreamPlan(
        name=f"prefill_{cfg.name}_b{block}",
        grid=(max(1, -(-prompt_len // block)),),
        inputs=(streamed_operand("chunk_embeds",
                                 batch * block * cfg.d_model),),
        outputs=(),
        scratch=(ScratchSpec("cache", (cache_bytes,), jnp.int8),),
        dimension_semantics=("arbitrary",),
        # one forward over `block` positions: ~2 FLOPs/param/position
        flops_per_hyperstep=2.0 * M.count_params(cfg) * batch * block,
        supersteps_per_hyperstep=1.0,  # the per-chunk dispatch barrier —
        # pricing it is what makes bigger chunks win under Eq. 1
    )


@functools.lru_cache(maxsize=64)
def prefill_block_size(cfg, batch: int, prompt_len: int,
                       machine: BSPAccelerator | None = None) -> int:
    """Autotuned prefill chunk size for a request shape.

    Enumerates power-of-two blocks (plus the whole prompt) and picks the
    predicted-fastest plan that fits the machine's local memory, double
    buffers included (:func:`repro.core.plan.autotune`): bigger blocks
    amortise the per-chunk barrier ``l``, the KV-cache scratch plus the
    chunk's double-buffered activations cap how big a block fits. Falls back
    to token-at-a-time when the stack has recurrent mixers or nothing fits.
    """
    if prompt_len <= 1 or any(b.mixer != "attn" for b in cfg.pattern):
        return 1
    machine = machine or default_machine()
    blocks = sorted({b for b in (1, 2, 4, 8, 16, 32, 64, 128, prompt_len)
                     if b <= prompt_len})
    try:
        best, _ = autotune(
            lambda block: _prefill_plan(cfg, batch, prompt_len, block),
            [{"block": b} for b in blocks], machine)
    except ValueError:       # not even block=1 fits L: stream token-at-a-time
        return 1
    return int(best.params["block"])


@functools.lru_cache(maxsize=8)
def compiled_serve_fns(cfg, temperature: float):
    """(prefill, decode_fn) for a config, built once per (cfg, temperature).

    The serving hot path calls :func:`generate` per request; rebuilding the
    jitted prefill/decode closures each time would retrace and recompile the
    whole model per request. ``ModelConfig`` is a frozen dataclass, so it
    keys an lru_cache directly; ``temperature`` is baked into the decode
    sampler's trace (0 = argmax branch), hence part of the key.
    """
    serve_step = make_serve_step(cfg)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode_fn(params, logits, cache, key):
        key, sub = jax.random.split(key)
        if temperature > 0:
            tok = jax.random.categorical(sub, logits[:, -1] / temperature)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)
        tok = tok.astype(jnp.int32)[:, None]
        logits, cache = serve_step(params, cache, {"tokens": tok})
        return tok, logits, cache, key

    return make_prefill(cfg), decode_fn


def _decode_plan(cfg, batch: int, max_len: int, generated):
    """Eq. 1 plan for a decode run: generated-id up-stream + cache scratch."""
    cache_shapes = jax.eval_shape(
        lambda: M.init_cache(cfg, batch, max_len))
    cache_bytes = sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(cache_shapes) if hasattr(x, "shape"))
    return host_plan(
        [], out_streams=[generated],
        # one forward pass per generated token: ~2 FLOPs/param/sequence
        flops_per_hyperstep=2.0 * M.count_params(cfg) * batch,
        scratch=(ScratchSpec("cache", (cache_bytes,), jnp.int8),),
        name=f"serve_{cfg.name}",
    )


#: Compiled decode runners keyed by request shape, with refcounted eviction.
#: A plain ``lru_cache(maxsize=8)`` would evict — and let a duplicate be
#: rebuilt for — a runner whose lock another thread still holds; the registry
#: only drops idle entries (see :mod:`repro.launch.registry`).
decode_runners = Registry(capacity=8)


def _build_decode_runner(cfg, temperature: float, batch: int, max_len: int,
                         steps: int):
    """One compiled decode runner per request shape (the serving hot path).

    The runner's compiled program scans all ``steps`` decode hypersteps in a
    single dispatch; caching the runner caches the traced program, so
    repeated ``generate()`` calls with the same shape re-dispatch without
    re-tracing. Params are the runner's read-only operands (a new jit
    argument each call — weight updates need no recompile), neither carried
    nor donated: the caller keeps owning them across requests, and the
    program holds one copy. The runner and its ``generated`` backing
    stream are shared mutable state; the registry entry's lock serialises
    concurrent same-shape requests.
    """
    _, decode_fn = compiled_serve_fns(cfg, temperature)
    streams = StreamSet()
    generated = streams.create(np.zeros((steps, batch), np.int32), 1,
                               name="generated")

    def hyperstep(state, _tokens, params):
        logits, cache, key = state
        tok, logits, cache, key = decode_fn(params, logits, cache, key)
        return (logits, cache, key), [tok[:, 0]]

    runner = HyperstepRunner(
        hyperstep, [], out_streams=[generated],
        plan=_decode_plan(cfg, batch, max_len, generated))
    runner.compile(steps)
    return runner, generated


def generate(
    cfg,
    params,
    prompt_tokens,
    *,
    steps: int,
    temperature: float = 0.0,
    seed: int = 0,
    machine: BSPAccelerator | None = None,
    compiled: bool = True,
    max_len: int | None = None,
    prefill_block: int | None = None,
) -> tuple[jax.Array, ServeStats]:
    """Generate ``steps`` tokens after ``prompt_tokens``; returns (tokens, stats).

    ``compiled=True`` (default) scans the whole decode in one device dispatch;
    ``compiled=False`` is the instrumented one-dispatch-per-token hyperstep
    loop with per-token records (calibration/measurement mode). ``max_len``
    overrides the cache length (default ``prompt_len + steps``) — e.g. to
    match the serve engine's pool geometry bit-for-bit. ``prefill_block``
    overrides the autotuned prefill chunk size (:func:`prefill_block_size`).
    """
    b, s = prompt_tokens.shape
    if s < 1:
        raise ValueError("need a non-empty prompt")
    if max_len is None:
        max_len = s + steps
    elif max_len < s + steps:
        raise ValueError(f"max_len={max_len} < prompt + steps = {s + steps}")
    cache = M.init_cache(cfg, b, max_len)

    machine = machine or default_machine()

    # compiled once per (cfg, temperature) / (cfg, block); repeated generate()
    # calls (the serving hot path) reuse the jitted prefill and decode step
    if prefill_block is None:
        prefill_block = prefill_block_size(cfg, b, s, machine)
    prefill = make_prefill(cfg, prefill_block)
    _, decode_fn = compiled_serve_fns(cfg, temperature)

    # -- prefill: one dispatch over the whole prompt -------------------------
    prompt_tokens = prompt_tokens.astype(jnp.int32)
    t0 = time.perf_counter()
    logits, cache = prefill(params, cache, prompt_tokens)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    key = jax.random.PRNGKey(seed)

    if compiled:
        # -- decode: all hypersteps in one compiled dispatch -----------------
        with decode_runners.acquire(
                (cfg, temperature, b, max_len, steps),
                lambda: _build_decode_runner(cfg, temperature, b, max_len,
                                             steps)) as entry:
            runner, generated = entry.value
            with entry.lock:            # cached runner + stream are shared
                runner.machine = machine
                runner.reset_records()  # per-request row, program stays cached
                runner.run((logits, cache, key), compiled=True,
                           operands=params)
                decode_seconds = [runner.records[-1].step_seconds]
                generated_ids = np.array(generated.data, np.int32)
                records = list(runner.records)
                plan_row = runner.predicted_vs_measured()
    else:
        # -- decode: one instrumented hyperstep per generated token ----------
        streams = StreamSet()
        generated = streams.create(np.zeros((steps, b), np.int32), 1,
                                   name="generated")

        def hyperstep(state, _tokens):
            logits, cache, key = state
            tok, logits, cache, key = decode_fn(params, logits, cache, key)
            # the sampled ids stream up; np.asarray on the DMA lane is the
            # device->external copy, off the compute path
            return (logits, cache, key), [tok[:, 0]]

        runner = HyperstepRunner(
            hyperstep, [], out_streams=[generated],
            plan=_decode_plan(cfg, b, max_len, generated), machine=machine)
        runner.run((logits, cache, key))
        decode_seconds = [r.compute_seconds for r in runner.records]
        generated_ids = np.array(generated.data, np.int32)
        records = list(runner.records)
        plan_row = runner.predicted_vs_measured()

    out = jnp.concatenate(
        [prompt_tokens, jnp.asarray(generated_ids).T.astype(jnp.int32)], axis=1)
    stats = ServeStats(
        prefill_seconds=prefill_s,
        decode_seconds=decode_seconds,
        records=records,
        plan_row=plan_row,
        compiled=compiled,
    )
    return out, stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--measure", action="store_true",
                    help="instrumented per-token decode loop instead of the "
                         "compiled single-dispatch scan")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab_size)
    tokens, stats = generate(cfg, params, prompt, steps=args.steps,
                             temperature=args.temperature,
                             compiled=not args.measure)
    if stats.compiled:
        total = stats.decode_total_seconds
        print(f"[serve] arch={args.arch} batch={args.batch} "
              f"prefill={stats.prefill_seconds * 1e3:.1f}ms "
              f"({args.prompt_len} tokens, 1 dispatch) | "
              f"decode={args.steps} tok in {total * 1e3:.1f}ms (1 dispatch) "
              f"throughput={args.steps * args.batch / total:.1f} tok/s")
    else:
        p50 = float(np.median(stats.decode_seconds))
        print(f"[serve] arch={args.arch} batch={args.batch} "
              f"prefill={stats.prefill_seconds * 1e3:.1f}ms "
              f"({args.prompt_len} tokens, 1 dispatch) | "
              f"decode={args.steps} tok/step p50={p50 * 1e3:.1f}ms "
              f"throughput={args.batch / p50:.1f} tok/s")
    row = stats.plan_row or {}
    if row:
        print(f"[predicted_vs_measured] pred={row['predicted_seconds']:.4g}s "
              f"meas={row['measured_seconds']:.4g}s "
              f"ratio={row['pred_over_meas']:.3g} "
              f"bw_heavy pred={row['bandwidth_heavy_predicted']:.0f} "
              f"meas={row['bandwidth_heavy_measured']:.0f}")
    print("sample row:", np.asarray(tokens[0])[: args.prompt_len + 8].tolist())


if __name__ == "__main__":
    main()

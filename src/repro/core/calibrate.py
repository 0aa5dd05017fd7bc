"""Calibrate a BSPAccelerator parameter pack for *this* host.

The paper (§5) measures (r, g, l, e) for the Epiphany-III; we do the same for
the running machine so the cost model's predictions can be validated against
measured hyperstep timings (§6 methodology). The "external memory" link of
this host is main RAM → jax device buffer (a memcpy), the compute rate r is a
jitted matmul.

Lives in ``core`` (not ``benchmarks``) because the launchers need a machine
pack to print their own predicted-vs-measured rows: ``calibrate(fast=True)``
is a ~100 ms variant with smaller probes, cheap enough to run at job start.
``benchmarks/calibrate.py`` re-exports everything for the benchmark harness.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bsp import BSPAccelerator

__all__ = [
    "calibrate",
    "calibrate_host_level",
    "default_machine",
    "measure_flops_rate",
    "measure_external_bandwidth",
    "measure_fetch_model",
    "measure_host_superstep",
    "measure_hyperstep_latency",
]


def _time(fn, repeats: int = 5, *, max_repeats: int = 17) -> float:
    """Probe timer: discard the first (jit-compiling) call, then median.

    Same protocol as :func:`repro.core.plan.median_seconds` plus two probe
    hardenings (DESIGN.md §11): the warmup call is discarded *explicitly*
    (the first dispatch pays compilation + first allocation and would poison
    a fast pack), and under high variance — interquartile range above 25% of
    the median, a contended CI host's signature — the repeat count escalates
    until the spread settles or ``max_repeats`` is hit.
    """
    fn()  # the discarded first repeat: compile + first-touch allocation
    repeats = max(int(repeats), 3)
    while True:
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        med = float(np.median(ts))
        q1, q3 = np.percentile(ts, (25, 75))
        if (q3 - q1) <= 0.25 * med or repeats >= max_repeats:
            return med
        repeats = min(2 * repeats + 1, max_repeats)


def measure_flops_rate(n: int = 768) -> float:
    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    dt = _time(lambda: jax.block_until_ready(f(a)))
    return 2 * n**3 / dt


def measure_external_bandwidth(nbytes: int = 1 << 26) -> float:
    """Host RAM -> device buffer words/s (the e-link of this machine)."""
    src = np.random.default_rng(0).standard_normal(nbytes // 4).astype(np.float32)
    dt = _time(lambda: jax.block_until_ready(jax.device_put(src)))
    return (nbytes / 4) / dt  # words (f32) per second


def measure_fetch_model() -> tuple[float, float]:
    """Two-point fit of the paper's Fig. 4 size effect: t(C) = t0 + C/BW.

    Returns (words_per_s_asymptotic, t0_seconds) — small tokens pay the fixed
    per-fetch overhead t0, which is why the paper sizes tokens as large as
    local memory allows.
    """
    times = {}
    for nbytes in (1 << 16, 1 << 26):
        src = np.random.default_rng(0).standard_normal(nbytes // 4).astype(np.float32)
        times[nbytes] = _time(lambda s=src: jax.block_until_ready(jax.device_put(s)),
                              repeats=9)
    c1, c2 = (1 << 16) / 4, (1 << 26) / 4
    t1, t2 = times[1 << 16], times[1 << 26]
    bw = (c2 - c1) / max(t2 - t1, 1e-12)          # words/s
    t0 = max(t1 - c1 / bw, 0.0)
    return bw, t0


def measure_hyperstep_latency() -> float:
    """Per-hyperstep fixed overhead (seconds) — the host's l.

    The paper's l is the barrier cost (136 FLOPs ≈ 0.3 µs on Epiphany); on
    this host the analogue is the python/jit dispatch + thread handoff per
    hyperstep, measured with near-empty tokens.
    """
    from repro.core.hyperstep import HyperstepRunner
    from repro.core.stream import StreamSet
    ss = StreamSet()
    data = np.zeros(16 * 64, np.float32)
    s1 = ss.create(data, 16)
    # a near-empty *jitted* step on a device token: captures the real
    # per-hyperstep overhead (dispatch + staging + thread handoff), which is
    # the host's barrier analogue
    tiny = jax.jit(lambda acc, t: acc + t.sum())
    runner = HyperstepRunner(lambda acc, t: tiny(acc, t[0]), [s1],
                             prefetch=False, device=jax.devices()[0])
    runner.run(jnp.float32(0.0))
    # record 0 pays jit compilation — the canonical probe outlier; a 16-step
    # run medianed *with* it could double the measured l on a cold backend
    recs = runner.records[1:] or runner.records
    return float(np.median([r.step_seconds for r in recs]))


def _local_words(dev) -> int:
    """Local memory L of ``dev`` in f32 words.

    The device's own memory where the runtime reports it (an accelerator's
    HBM: the KV pool and the weights live there), else 32 MiB, about a host
    CPU's last-level cache.
    """
    stats = dev.memory_stats() or {}
    return int(stats.get("bytes_limit", 1 << 25)) // 4


def calibrate(p: int = 1, *, fast: bool = False) -> BSPAccelerator:
    """Measure (r, e, l) and return the pack. ``fast=True`` shrinks the probes
    and skips the latency run — good enough for a launcher's predicted row.
    The pack is named after the device it measured."""
    if fast:
        r = measure_flops_rate(n=256)
        words_per_s = measure_external_bandwidth(nbytes=1 << 22)
        l = 200e-6 * r  # typical python-dispatch barrier; skip the measurement
    else:
        r = measure_flops_rate()
        words_per_s = measure_external_bandwidth()
        l = measure_hyperstep_latency() * r
    e = r / words_per_s  # FLOPs per word
    dev = jax.devices()[0]
    return BSPAccelerator(
        p=p, g=0.0, l=l, r=r, e=e,
        L=_local_words(dev), E=(1 << 34) // 4,  # RAM external
        word_bytes=4, name=f"calibrated:{dev.platform}:{dev.device_kind}",
    )


def measure_host_superstep(mesh, axis: str = "host") -> tuple[float, float]:
    """Two-point fit of the host-level superstep term over real collectives.

    Times an all-reduce (``psum``) across the mesh's ``axis`` at two payload
    sizes and fits ``t(h) = l_sec + h · g_sec_per_word`` — the same two-point
    protocol as :func:`measure_fetch_model`, one level up: the collective IS
    the host-level h-relation, so its slope is ``g_host`` (seconds/word,
    whatever ring/tree factor the runtime uses is absorbed into it) and its
    intercept the host barrier ``l_host``. Returns
    ``(g_host_seconds_per_word, l_host_seconds)``.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[axis])
    if n <= 1:
        return 0.0, 0.0
    w1, w2 = 1 << 12, 1 << 18  # words per host-shard

    def timed_psum(words: int) -> float:
        x = jnp.zeros((n * words,), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P((axis,))))
        f = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum(v, axis),
            mesh=mesh, in_specs=P((axis,)), out_specs=P(None),
            check_vma=False))
        return _time(lambda: jax.block_until_ready(f(x)), repeats=7)

    t1, t2 = timed_psum(w1), timed_psum(w2)
    g_sec = max(t2 - t1, 0.0) / (w2 - w1)
    l_sec = max(t1 - w1 * g_sec, 0.0)
    return g_sec, l_sec


def calibrate_host_level(acc: BSPAccelerator, mesh, axis: str = "host") -> BSPAccelerator:
    """Extend a calibrated device pack with the third pricing level.

    Measures ``(g_host, l_host)`` over real collectives on ``mesh``'s host
    axis (:func:`measure_host_superstep`) and returns the pack with
    ``hosts``/``g_host``/``l_host`` filled in — in FLOP units of the pack's
    own ``r``, like every other parameter, so
    ``HyperstepCost.cost = T_device + g_host·h_host + l_host·s_host``
    converts to wall time with the one ``flops_to_seconds``.
    """
    import dataclasses
    if axis not in mesh.axis_names:
        return dataclasses.replace(acc, hosts=1, g_host=0.0, l_host=0.0)
    g_sec, l_sec = measure_host_superstep(mesh, axis)
    return dataclasses.replace(
        acc,
        hosts=int(mesh.shape[axis]),
        g_host=g_sec * acc.r,
        l_host=l_sec * acc.r,
    )


_MACHINE_CACHE: dict[tuple, BSPAccelerator] = {}


def _machine_cache_key(p: int) -> tuple:
    return (int(p), jax.default_backend(),
            tuple((d.platform, str(getattr(d, "device_kind", "")), d.id)
                  for d in jax.devices()))


def default_machine(p: int = 1) -> BSPAccelerator:
    """The process-wide calibrated machine pack, measured once per device set.

    Hot paths that need a machine but were given none (``generate()``, the
    serve engine) must use this instead of calling :func:`calibrate` inline —
    even the ``fast=True`` probe costs ~100 ms of matmul + memcpy timing,
    which would otherwise be paid per request.

    The memo is keyed on ``(p, backend, device set)``, not just ``p``: a
    backend or device-count change mid-process (an ``XLA_FLAGS`` forced mesh
    in tests/CI, a fallback from an accelerator to CPU) re-measures instead
    of serving the stale pack the old device set produced.
    """
    key = _machine_cache_key(p)
    pack = _MACHINE_CACHE.get(key)
    if pack is None:
        pack = _MACHINE_CACHE[key] = calibrate(p, fast=True)
    return pack


default_machine.cache_clear = _MACHINE_CACHE.clear  # lru_cache-compatible hook

"""Continuous-batching serve engine: packed decode hypersteps on the BSPS runtime.

The serving tier above :mod:`repro.launch.serve`. Instead of one decode run
per request, a :class:`ServeEngine` packs up to ``max_lanes`` concurrent
requests of mixed prompt lengths into one batched decode program and runs it
in **segments**: each segment is ``segment_len`` packed hypersteps scanned in
a single compiled dispatch (one :class:`~repro.core.hyperstep.HyperstepRunner`
program, compiled once, replayed every segment), and requests join or retire
only at segment boundaries — the hot loop never recompiles on occupancy
changes because the batch axis stays ``max_lanes`` wide and an ``active``
mask in the scan carry turns lanes on and off.

Admission is priced, not guessed: before packing lane ``B+1`` the engine
builds Eq. 1 plans for ``B`` and ``B+1`` lanes
(:func:`repro.core.plan.packed_decode_plan`) and admits only while the packed
step is predicted to stay compute-bound
(:func:`repro.core.plan.admission_decision`) — the BSF scalability boundary
applied per request. Each segment then reports the runner's
``predicted_vs_measured()`` row, so every admission verdict can be checked
against the measured one.

The KV pool is paged, and it is *plan scratch*: one dense cache of
``max_lanes × pool_seq`` positions (declared to the cost model via
:func:`repro.core.plan.batched_scratch`) fronted by a :class:`BlockTable`
that accounts pages. Allocation and eviction never copy keys/values around —
retiring a request frees its pages and resets the lane's length cursor to 0
(cursor replay, the MOVE-style non-injective reuse of §4: the same physical
rows serve a different request id next join; the stale values are hidden by
the per-lane validity masks, exactly like a re-fetched token block).

Each lane's generated ids ride their own write-back stream
(:meth:`repro.core.stream.StreamSet.create_lanes`), scattered on-device by
the compiled program and harvested at the segment boundary.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bsp import BSPAccelerator
from repro.core.calibrate import default_machine
from repro.core.calibstore import get_default_store, plan_band
from repro.core.faults import FaultInjected
from repro.core.health import HealthMonitor
from repro.core.hyperstep import HyperstepRunner
from repro.core.plan import (
    AdmissionDecision,
    admission_decision,
    batched_scratch,
    packed_decode_plan,
)
from repro.core.stream import StreamSet
from repro.launch.serve import make_prefill, prefill_block_size
from repro.models import model as M
from repro.train.steps import make_serve_step

__all__ = ["BlockTable", "PagedKVPool", "Request", "ServeEngine"]


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One submitted generation request and its lifecycle state."""

    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int
    seed: int = 0
    deadline_s: float | None = None     # wall budget from submit; None = none

    lane: int | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    prefill_seconds: float = 0.0
    submit_time: float = 0.0
    # stamped as the join starts, before the prefill: join_time - submit_time
    # is the request's wait in the queue
    join_time: float | None = None
    done_time: float | None = None
    timed_out: bool = False
    cancelled: bool = False

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    def tokens(self) -> np.ndarray:
        """prompt ++ generated, the same layout :func:`serve.generate` returns."""
        return np.concatenate(
            [self.prompt.astype(np.int32),
             np.asarray(self.generated[: self.max_new_tokens], np.int32)])


# ---------------------------------------------------------------------------
# Paged KV accounting
# ---------------------------------------------------------------------------


class BlockTable:
    """Page accounting for the KV pool: which request owns which page.

    Pure bookkeeping — the physical rows live in :class:`PagedKVPool`'s dense
    cache; the table decides whether a request's working set *fits* and
    records the page → request map. The map is deliberately non-injective
    over time: :meth:`free` returns pages to the pool and the next
    :meth:`alloc` hands the same physical pages to a different request —
    ``history`` keeps the full (page, rid) assignment trail so tests can see
    one page serve several request ids with no copy in between.
    """

    def __init__(self, num_pages: int, page_tokens: int):
        if num_pages < 1 or page_tokens < 1:
            raise ValueError("need num_pages >= 1 and page_tokens >= 1")
        self.num_pages = int(num_pages)
        self.page_tokens = int(page_tokens)
        self._free: list[int] = list(range(num_pages))[::-1]
        self.owner: dict[int, int] = {}          # page -> rid
        self.history: list[tuple[int, int]] = []  # (page, rid) assignments

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_tokens)

    def can_alloc(self, tokens: int) -> bool:
        return self.pages_for(tokens) <= self.free_pages

    def alloc(self, rid: int, tokens: int) -> list[int] | None:
        """Claim pages for ``tokens`` positions, or None if the pool is full."""
        n = self.pages_for(tokens)
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.owner[p] = rid
            self.history.append((p, rid))
        return pages

    def free(self, rid: int) -> int:
        """Release every page owned by ``rid``; returns how many were freed."""
        pages = [p for p, r in self.owner.items() if r == rid]
        for p in pages:
            del self.owner[p]
            self._free.append(p)
        return len(pages)


class PagedKVPool:
    """The packed batch's KV state: a dense lane pool + page accounting.

    ``cache`` is one model cache of ``max_lanes`` lanes × ``pool_seq``
    positions with a *vector* ``len`` (one decode position per lane — the
    mixed-prompt-length support in
    :func:`repro.models.attention.attention_decode`). Joining a request
    scatters its prefilled batch-1 cache into a free lane (the only copy in
    a request's lifetime); retiring frees the lane and pages and resets the
    lane's ``len`` to 0 — eviction is cursor replay, not data movement.
    """

    def __init__(self, cfg, max_lanes: int, pool_seq: int, *,
                 page_tokens: int = 8, num_pages: int | None = None,
                 faults: Any | None = None):
        self.faults = faults
        self.cfg = cfg
        self.max_lanes = int(max_lanes)
        self.pool_seq = int(pool_seq)
        cache = M.init_cache(cfg, max_lanes, pool_seq)
        cache["len"] = jnp.zeros((max_lanes,), jnp.int32)
        self.cache = cache
        if num_pages is None:       # fully provisioned: pages never bind
            num_pages = max_lanes * (-(-pool_seq // page_tokens))
        self.table = BlockTable(num_pages, page_tokens)
        self._free_lanes = list(range(max_lanes))[::-1]

    @property
    def free_lanes(self) -> int:
        return len(self._free_lanes)

    def lane_lens(self) -> np.ndarray:
        return np.asarray(self.cache["len"], np.int32)

    def can_admit(self, tokens: int) -> bool:
        """Admission pre-check: a free lane, enough pages, and no injected
        exhaustion (an injected ``page_exhaust`` fault makes the pool report
        full for this one consultation — DESIGN.md §10)."""
        if self.faults is not None and self.faults.page_fault():
            return False
        return bool(self._free_lanes) and self.table.can_alloc(tokens)

    def try_admit(self, rid: int, tokens: int) -> tuple[int, list[int]] | None:
        """Claim a lane + pages for ``tokens`` positions, or None if full."""
        if not self._free_lanes:
            return None
        pages = self.table.alloc(rid, tokens)
        if pages is None:
            return None
        return self._free_lanes.pop(), pages

    def join(self, lane: int, req_cache: dict[str, Any]) -> None:
        """Scatter a prefilled batch-1 cache (``pool_seq`` positions) into a lane."""
        self.cache = _scatter_lane(self.cache, req_cache, jnp.int32(lane))

    def retire(self, rid: int, lane: int) -> None:
        """Free the request's pages + lane; reset the lane's length cursor."""
        self.table.free(rid)
        self.cache["len"] = self.cache["len"].at[lane].set(0)
        self._free_lanes.append(lane)

    def reset_inactive(self, active: np.ndarray) -> None:
        """Zero the length cursor of every inactive lane.

        Inactive lanes still step through the packed program (masked to token
        0), growing their ``len`` by ``segment_len`` per segment; resetting at
        the boundary keeps the junk bounded and the next join starts the lane
        from position 0 over the same physical rows.
        """
        self.cache["len"] = jnp.where(jnp.asarray(active),
                                      self.cache["len"], 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_lane(pool: dict[str, Any], req: dict[str, Any],
                  lane: jax.Array) -> dict[str, Any]:
    layers = jax.tree_util.tree_map(
        lambda p, r: jax.lax.dynamic_update_slice(
            p, r.astype(p.dtype),
            (lane,) + (jnp.int32(0),) * (p.ndim - 1)),
        pool["layers"], req["layers"])
    ln = pool["len"].at[lane].set(req["len"].astype(jnp.int32))
    return {"layers": layers, "len": ln}


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching decode over packed hypersteps with priced admission.

    Parameters
    ----------
    cfg, params:
        The model (attention-only stacks — the per-lane length vector rides
        the generalised :func:`repro.models.model.decode_step`).
    max_lanes:
        Packed batch width. The compiled program is traced once at this
        width; occupancy changes only flip the ``active`` mask.
    pool_seq:
        KV positions per lane. A request needs ``prompt_len`` plus its
        generation rounded up to whole segments.
    segment_len:
        Hypersteps per segment — the join/retire granularity. One segment =
        one device dispatch.
    page_tokens / num_pages:
        Paged-pool geometry (see :class:`PagedKVPool`). Passing fewer pages
        than ``max_lanes × pool_seq/page_tokens`` oversubscribes the pool, so
        admission can refuse on pages even with a free lane.
    temperature:
        0 = greedy (the packed-vs-sequential equivalence mode); > 0 samples
        per lane with a per-request PRNG key.
    faults:
        Optional :class:`~repro.core.faults.FaultInjector` threaded through
        the runner (dispatch failures, stalls, corruption) and the page pool
        (injected exhaustion) — DESIGN.md §10.
    slo_band / slo_warmup:
        The Eq. 1 SLO band the :class:`~repro.core.health.HealthMonitor`
        scores each segment against (relative to the warmup baseline ratio).
        The default is deliberately wide — occupancy changes move the
        prediction more than the wall time at toy scales; tighten it when
        chasing real regressions.
    degrade_after / recover_after:
        Degradation state machine (DESIGN.md §10): ``degrade_after``
        consecutive SLO-violating segments enter degraded mode (admissions
        shed while lanes are busy; admission re-priced against the measured
        slowdown), ``recover_after`` consecutive healthy segments exit it.
    dispatch_retries / retry_backoff_s:
        Bounded retry on a failed segment dispatch (simulated preemption):
        up to ``dispatch_retries`` retries with exponential backoff before
        the failure propagates out of :meth:`step_segment`.
    calibstore:
        Where measured segments land and where drift refits come from
        (DESIGN.md §11). ``None`` uses the process default store
        (:func:`repro.core.calibstore.get_default_store`), a
        :class:`~repro.core.calibstore.CalibrationStore` isolates this
        engine, ``False`` disables recording *and* recalibration.
    drift_band / drift_window:
        The BSPS220 drift detector (see :class:`HealthMonitor`): when the
        median predicted/measured ratio of the last ``drift_window``
        segments leaves ``drift_band`` × baseline, the engine refits
        (g, l, e) from the store for the current decode plan's band, adopts
        the refit pack for prediction *and* admission pricing (BSPS221),
        and re-prices the pending admission so the next segment's
        measurement confirms the verdict. No usable fit → BSPS222 and the
        degraded-mode derate remains the only protection.
    """

    def __init__(self, cfg, params, *, max_lanes: int = 4,
                 pool_seq: int = 128, segment_len: int = 8,
                 page_tokens: int = 8, num_pages: int | None = None,
                 temperature: float = 0.0,
                 machine: BSPAccelerator | None = None,
                 verify: bool = True,
                 faults: Any | None = None,
                 slo_band: tuple[float, float] = (0.05, 20.0),
                 slo_warmup: int = 2,
                 degrade_after: int = 2, recover_after: int = 2,
                 dispatch_retries: int = 3, retry_backoff_s: float = 0.01,
                 calibstore: Any | None = None,
                 drift_band: tuple[float, float] = (0.5, 2.0),
                 drift_window: int = 4):
        if any(b.mixer != "attn" for b in cfg.pattern):
            raise ValueError(
                f"ServeEngine needs an attention-only stack; {cfg.name} has "
                "recurrent mixers (serve them through generate())")
        if segment_len < 1 or max_lanes < 1:
            raise ValueError("need segment_len >= 1 and max_lanes >= 1")
        if pool_seq < segment_len:
            raise ValueError(f"pool_seq={pool_seq} < segment_len={segment_len}")
        self.cfg = cfg
        self.params = params
        self.max_lanes = int(max_lanes)
        self.pool_seq = int(pool_seq)
        self.segment_len = int(segment_len)
        self.temperature = float(temperature)
        self.machine = machine or default_machine()
        # the pack predictions and admissions are priced on *right now*:
        # self.machine until a drift refit is adopted (then BSPS221 swaps it)
        self.active_machine = self.machine
        if calibstore is None:
            calibstore = get_default_store()
        self.calibstore = calibstore if calibstore is not False else None
        self.faults = faults
        self.health = HealthMonitor(band=slo_band, warmup=slo_warmup,
                                    name=f"engine_{cfg.name}",
                                    drift_band=drift_band,
                                    drift_window=drift_window)
        self.degraded = False
        self._degrade_after = int(degrade_after)
        self._recover_after = int(recover_after)
        self._dispatch_retries = int(dispatch_retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._slo_scale = 1.0        # measured slowdown while degraded

        self.pool = PagedKVPool(cfg, max_lanes, pool_seq,
                                page_tokens=page_tokens, num_pages=num_pages,
                                faults=faults)
        self.queue: deque[Request] = deque()
        self.running: dict[int, Request] = {}     # rid -> request (has a lane)
        self.finished: dict[int, Request] = {}
        self.admission_log: list[dict[str, Any]] = []
        self.segment_log: list[dict[str, Any]] = []
        self._next_rid = 0
        self._segments_run = 0

        vocab = cfg.vocab_size
        self._logits = jnp.zeros((max_lanes, 1, vocab), jnp.float32)
        self._keys = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(max_lanes)])
        self._active = np.zeros((max_lanes,), bool)

        # per-lane generated-id up-streams + the one compiled segment program
        self._streams = StreamSet()
        self.lane_streams = self._streams.create_lanes(
            self.segment_len, max_lanes, name="lane")
        # verify=True statically checks each segment before dispatch
        # (DESIGN.md §9: lane-aliased up-streams, cursor overruns); results
        # are memoized per cursor state, so steady-state segments — which
        # rewind the same lane cursors — pay one set lookup, not a re-walk.
        # The weights are the runner's read-only operands: the segment
        # program donates and returns only (logits, cache, keys, active).
        self._runner = HyperstepRunner(
            self._make_step(), [], out_streams=self.lane_streams,
            machine=self.machine, verify=verify, faults=faults,
            health=self.health,
            calibstore=self.calibstore if self.calibstore is not None
            else False)
        self._runner.compile(self.segment_len)

        # Eq. 1 bookkeeping for the admission plans
        cache_bytes = sum(
            int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree_util.tree_leaves(
                jax.eval_shape(lambda: M.init_cache(cfg, max_lanes, pool_seq)))
            if hasattr(x, "shape"))
        self._bytes_per_lane = cache_bytes // max_lanes
        self._kv_words_per_pos = (cache_bytes / 4) / (max_lanes * pool_seq)
        self._param_words = M.count_params(cfg)

    # -- the packed hyperstep -------------------------------------------------

    def _make_step(self):
        serve_step = make_serve_step(self.cfg)
        temperature = self.temperature
        lanes = self.max_lanes

        def step(state, _tokens, params):
            logits, cache, keys, active = state
            if temperature > 0:
                split = jax.vmap(jax.random.split)(keys)   # (L, 2, 2)
                keys, subs = split[:, 0], split[:, 1]
                tok = jax.vmap(
                    lambda k, lg: jax.random.categorical(k, lg / temperature)
                )(subs, logits[:, -1])
            else:
                tok = jnp.argmax(logits[:, -1], axis=-1)
            # masked lanes decode token 0 — junk the boundary discards
            tok = jnp.where(active, tok, 0).astype(jnp.int32)
            logits, cache = serve_step(params, cache, {"tokens": tok[:, None]})
            # carry dtype is pinned to f32 (bf16 models would change the scan
            # carry structure mid-trace); argmax is unchanged by the upcast
            state = (logits.astype(jnp.float32), cache, keys, active)
            return state, [tok[i] for i in range(lanes)]

        return step

    @property
    def lane_logits(self) -> jax.Array:
        """``(max_lanes, 1, vocab)`` f32 logits each lane samples from next."""
        return self._logits

    def segment_memory(self) -> Any:
        """``memory_analysis()`` of the compiled segment program."""
        state = (self._logits, self.pool.cache, self._keys,
                 jnp.asarray(self._active))
        return self._runner.lower(state, self.segment_len,
                                  operands=self.params).compile(
                                  ).memory_analysis()

    # -- admission ------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, seed: int = 0,
               deadline_s: float | None = None) -> int:
        """Queue a request; returns its rid. Joins at a segment boundary.

        ``deadline_s`` is a wall-clock budget from submission: a request
        still unfinished when it expires is retired at the next segment
        boundary (``timed_out=True``, BSPS205) with whatever tokens it has.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("need a non-empty prompt")
        need = prompt.size + self._scheduled_steps(max_new_tokens)
        if need > self.pool_seq:
            raise ValueError(
                f"request needs {need} positions (prompt {prompt.size} + "
                f"{self._scheduled_steps(max_new_tokens)} scheduled steps) "
                f"> pool_seq={self.pool_seq}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
                      seed=seed, deadline_s=deadline_s,
                      submit_time=time.perf_counter())
        self.queue.append(req)
        return rid

    def _scheduled_steps(self, max_new_tokens: int) -> int:
        """Generation rounded up to whole segments (retire is boundary-only)."""
        segs = -(-int(max_new_tokens) // self.segment_len)
        return segs * self.segment_len

    def _occupancy(self) -> int:
        return len(self.running)

    def _decode_plan(self, lanes: int, extra_len: int = 0):
        """Eq. 1 plan for one segment at ``lanes`` occupancy.

        The KV working set per lane is the mean active position (plus the
        incoming request's prompt when pricing a candidate) advanced half a
        segment — the streamed-per-step traffic that grows with occupancy
        and length, against the shared params stream and barrier that
        batching amortises.
        """
        lens = self.pool.lane_lens()[self._active]
        total = float(lens.sum()) + float(extra_len)
        mean_len = total / max(lanes, 1)
        kv_pos = min(self.pool_seq, mean_len + self.segment_len / 2)
        return packed_decode_plan(
            lanes=lanes,
            steps=self.segment_len,
            flops_per_token=2.0 * self._param_words,
            params_words=self._param_words,
            kv_words_per_lane=self._kv_words_per_pos * kv_pos,
            scratch=(batched_scratch("kv_pool", self._bytes_per_lane,
                                     self.max_lanes),),
            name=f"engine_{self.cfg.name}_B{lanes}",
        )

    def _admission_machine(self) -> BSPAccelerator:
        """The machine admission prices against.

        Three packs, in order of preference: an adopted calibration-store
        refit (BSPS221 — measured (g, l, e), the drift priced where it
        actually lives), else the fixed degraded-mode derate (BSPS208 — the
        measured slowdown folded into the compute rate, a blunt instrument
        that moves the BSF boundary left), else the calibrated original.
        """
        if self.active_machine is not self.machine:
            return self.active_machine     # refit pack carries the drift
        if not self.degraded or self._slo_scale <= 1.0:
            return self.machine
        return dataclasses.replace(
            self.machine, r=self.machine.r / self._slo_scale)

    def _machine_pack_label(self) -> str:
        """Which pack :meth:`_admission_machine` is returning right now."""
        if self.active_machine is not self.machine:
            return "refit"
        if self.degraded and self._slo_scale > 1.0:
            return "derated"
        return "calibrated"

    def _try_join(self) -> None:
        """Admit queued requests while Eq. 1 says one more lane still pays.

        In degraded mode admissions are shed entirely while any lane is busy
        (an idle engine still serves — there is nothing left to protect).
        """
        while self.queue:
            req = self.queue[0]
            occupancy = self._occupancy()
            if self.degraded and occupancy > 0:
                break                      # shedding until the SLO recovers
            if self.pool.free_lanes == 0:
                break
            need = req.prompt_len + self._scheduled_steps(req.max_new_tokens)
            if not self.pool.can_admit(need):
                self.health.emit(
                    "BSPS207", f"page pool exhausted; request {req.rid} "
                    f"deferred (needs {need} positions)", index=req.rid)
                break                      # page pressure: defer (FCFS)
            with jax.profiler.TraceAnnotation("engine.admit", rid=req.rid):
                current = self._decode_plan(occupancy) if occupancy else None
                candidate = self._decode_plan(occupancy + 1,
                                              extra_len=req.prompt_len)
                dec = admission_decision(
                    current, candidate, self._admission_machine(),
                    tokens_per_hyperstep=occupancy + 1)
                self.admission_log.append({
                    "rid": req.rid, "segment": self._segments_run,
                    "occupancy_before": occupancy,
                    "measured_verdict": None,       # filled by the next segment
                    "machine_pack": self._machine_pack_label(),
                    "repriced": False,
                    **dec.row(),
                })
            if not dec.admit:
                break                      # bandwidth boundary: defer
            self.queue.popleft()
            self._join(req)

    def _join(self, req: Request) -> None:
        req.join_time = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "engine.join", rid=req.rid, prompt_len=req.prompt_len,
                queued_s=req.join_time - req.submit_time):
            claim = self.pool.try_admit(
                req.rid, req.prompt_len
                + self._scheduled_steps(req.max_new_tokens))
            assert claim is not None       # _try_join checked both resources
            lane, _pages = claim
            req.lane = lane

            # batch-1 chunked prefill at the pool's geometry, then one
            # scatter into the lane — the only copy in the request's lifetime
            block = prefill_block_size(self.cfg, 1, req.prompt_len,
                                       self.machine)
            prefill = make_prefill(self.cfg, block)
            cache = M.init_cache(self.cfg, 1, self.pool_seq)
            with jax.profiler.TraceAnnotation(
                    "engine.prefill", rid=req.rid, prompt_len=req.prompt_len,
                    block=block):
                t0 = time.perf_counter()
                logits, cache = prefill(
                    self.params, cache,
                    jnp.asarray(req.prompt[None, :], jnp.int32))
                jax.block_until_ready(logits)
                req.prefill_seconds = time.perf_counter() - t0

            with jax.profiler.TraceAnnotation("engine.scatter", rid=req.rid):
                self.pool.join(lane, cache)
                self._logits = self._logits.at[lane].set(
                    logits[0].astype(jnp.float32))
                self._keys = self._keys.at[lane].set(
                    jax.random.PRNGKey(req.seed))
            self._active[lane] = True
            self.running[req.rid] = req

    # -- request lifecycle (retire / cancel / deadlines) ----------------------

    def _retire(self, req: Request) -> None:
        """Free a running request's lane + pages and move it to finished."""
        self.pool.retire(req.rid, req.lane)
        self._active[req.lane] = False
        del self.running[req.rid]
        self.finished[req.rid] = req

    def cancel(self, rid: int) -> bool:
        """Cancel a request; returns True if it was queued or running.

        A running request's lane and pages are reclaimed *immediately* — the
        lane drops out of the active mask, so the next segment decodes
        nothing for it and a queued request can join in its place at the
        next boundary. The request lands in ``finished`` with
        ``cancelled=True`` and whatever tokens it had harvested.
        """
        for req in list(self.queue):
            if req.rid == rid:
                self.queue.remove(req)
                req.cancelled = True
                req.done_time = time.perf_counter()
                self.finished[rid] = req
                self.health.emit("BSPS206", f"request {rid} cancelled while "
                                 "queued", index=rid)
                return True
        req = self.running.get(rid)
        if req is not None:
            req.cancelled = True
            req.done_time = time.perf_counter()
            self._retire(req)
            self.health.emit("BSPS206", f"request {rid} cancelled; lane "
                             f"{req.lane} and pages reclaimed", index=rid)
            return True
        return False

    def _expire_deadlines(self) -> None:
        """Retire requests whose wall budget ran out (BSPS205).

        Runs at segment boundaries — the packed dispatch is never interrupted
        mid-segment, matching the bulk-synchronous contract.
        """
        now = time.perf_counter()

        def expired(req: Request) -> bool:
            return (req.deadline_s is not None
                    and now - req.submit_time > req.deadline_s)

        for req in list(self.queue):
            if expired(req):
                self.queue.remove(req)
                req.timed_out = True
                req.done_time = now
                self.finished[req.rid] = req
                self.health.emit(
                    "BSPS205", f"request {req.rid} expired in queue after "
                    f"{req.deadline_s}s", index=req.rid)
        for req in list(self.running.values()):
            if not req.done and expired(req):
                req.timed_out = True
                req.done_time = now
                self._retire(req)
                self.health.emit(
                    "BSPS205", f"request {req.rid} exceeded deadline "
                    f"{req.deadline_s}s with {len(req.generated)}/"
                    f"{req.max_new_tokens} tokens; retired", index=req.rid)

    # -- the segment loop -----------------------------------------------------

    def _dispatch_segment(self, state: Any) -> Any:
        """One segment dispatch under bounded retry-with-backoff.

        An injected dispatch failure (simulated preemption) raises from the
        runner *before* any state or cursor moves, so the retry re-runs the
        identical segment. Retries exhausted → BSPS211 and the failure
        propagates to the caller.
        """
        for attempt in range(self._dispatch_retries + 1):
            try:
                return self._runner.run(state, self.segment_len, compiled=True,
                                        operands=self.params)
            except FaultInjected as e:
                self.health.emit(
                    "BSPS204", f"segment {self._segments_run} dispatch failed "
                    f"(attempt {attempt + 1}): {e.record.kind}",
                    index=self._segments_run)
                if attempt >= self._dispatch_retries:
                    self.health.emit(
                        "BSPS211", f"segment {self._segments_run} dispatch "
                        f"retries exhausted after {attempt + 1} attempts",
                        index=self._segments_run)
                    raise
                time.sleep(self._retry_backoff_s * (2 ** attempt))

    def _update_degradation(self) -> None:
        """The BSPS208/209 state machine, stepped once per segment."""
        if (not self.degraded
                and self.health.consecutive_violations >= self._degrade_after):
            self.degraded = True
            self._slo_scale = max(
                self.health.last_ratio
                / max(self.health.baseline_ratio, 1e-12), 1.0)
            self.health.emit(
                "BSPS208", f"{self.health.consecutive_violations} consecutive "
                f"SLO violations (last {self._slo_scale:.3g}x baseline); "
                "shedding admissions and re-pricing the decode plan",
                index=self._segments_run - 1, value=self._slo_scale)
        elif (self.degraded
                and self.health.consecutive_healthy >= self._recover_after):
            self.degraded = False
            self._slo_scale = 1.0
            self.health.emit(
                "BSPS209", f"SLO recovered after "
                f"{self.health.consecutive_healthy} healthy segments; "
                "admissions resume", index=self._segments_run - 1)

    def _maybe_recalibrate(self) -> None:
        """Consume a pending drift event: refit, adopt, re-price (DESIGN.md §11).

        The HealthMonitor queues a :class:`RecalibrationEvent` when the
        median predicted/measured ratio of recent segments leaves the drift
        band (BSPS220). This closes the loop: refit (g, l, e) from the
        calibration store's most recent records for the current decode
        plan's band — a window of ``drift_window`` records, exactly the
        segments whose sustained shift fired the detector, so the fit
        follows the drift instead of averaging it away against the healthy
        history — adopt the refit pack for the runner's predictions and the
        admission pricing (BSPS221), rebaseline the SLO scorer on it, and
        re-price the pending admission so the next segment's measurement
        confirms the refit verdict. No store, or an under-evidenced /
        low-confidence fit, keeps the original pack (BSPS222) — the
        degraded-mode derate then remains the only protection.
        """
        event = self.health.pop_recalibration()
        if event is None:
            return
        with jax.profiler.TraceAnnotation("engine.recalibrate"):
            seg = self._segments_run - 1
            if self.calibstore is None:
                self.health.emit(
                    "BSPS222", "calibration drift detected but recording is "
                    "disabled; nothing to refit from (ratio "
                    f"{event.ratio:.3g}x baseline)", index=seg,
                    value=event.ratio)
                return
            band = plan_band(self._runner.plan)
            refit = self.calibstore.refit_machine(
                self.machine, band=band, window=self.health.drift_window)
            if refit is None:
                self.health.emit(
                    "BSPS222", f"calibration drift (ratio {event.ratio:.3g}x "
                    f"baseline) but band {band} is under-evidenced; keeping "
                    "the closed-form pack", index=seg, value=event.ratio)
                return
            self.active_machine = refit
            self._runner.machine = refit
            self.health.rebaseline()
            self.health.emit(
                "BSPS221", f"adopted calibration-store refit for band {band}: "
                f"g {self.machine.g:.3g}->{refit.g:.3g}, "
                f"l {self.machine.l:.3g}->{refit.l:.3g}, "
                f"e {self.machine.e:.3g}->{refit.e:.3g}; admission re-priced",
                index=seg, value=refit.e / max(self.machine.e, 1e-12))
            self._reprice_admission()

    def _reprice_admission(self) -> None:
        """Log a fresh admission verdict priced on the refit pack.

        The head-of-queue request (or, with an empty queue, the standing
        occupancy) is priced again through :func:`admission_decision` on
        :meth:`_admission_machine` and logged with ``repriced=True``; the
        next segment fills ``measured_verdict`` like any admission row, so
        the refit pack's verdicts get confirmed by the same
        predicted-vs-measured bookkeeping as the originals.
        """
        occupancy = self._occupancy()
        if occupancy == 0 and not self.queue:
            return
        if self.queue:
            req = self.queue[0]
            current = self._decode_plan(occupancy) if occupancy else None
            candidate = self._decode_plan(occupancy + 1,
                                          extra_len=req.prompt_len)
            rid, tokens = req.rid, occupancy + 1
        else:
            # no queue: re-price the standing batch itself (candidate-only
            # form — the verdict side of Eq. 1's max, no join policy)
            current, candidate = None, self._decode_plan(occupancy)
            rid, tokens = -1, occupancy
        dec = admission_decision(current, candidate,
                                 self._admission_machine(),
                                 tokens_per_hyperstep=tokens)
        self.admission_log.append({
            "rid": rid, "segment": self._segments_run,
            "occupancy_before": occupancy,
            "measured_verdict": None,       # filled by the next segment
            "machine_pack": self._machine_pack_label(),
            "repriced": True,
            **dec.row(),
        })

    def step_segment(self) -> int:
        """Run one packed segment; returns tokens harvested for real requests.

        Host spans (``jax.profiler.TraceAnnotation``) name each part of the
        boundary on a profiler trace: ``engine.segment`` around the call,
        inside it ``engine.admit`` / ``engine.join`` (its ``engine.prefill``
        and ``engine.scatter``) per queued head, ``engine.plan``, the
        runner's ``runtime.dispatch``, ``engine.harvest`` and
        ``engine.account``. They cost about a microsecond each untraced.
        """
        with jax.profiler.TraceAnnotation(
                "engine.segment", segment=self._segments_run) as span:
            self._expire_deadlines()
            self._try_join()
            occupancy = self._occupancy()
            span.set_metadata(occupancy=occupancy)
            if occupancy == 0:
                return 0

            with jax.profiler.TraceAnnotation("engine.plan"):
                self._runner.plan = self._decode_plan(occupancy)
                self._runner.reset_records()
                state = (self._logits, self.pool.cache, self._keys,
                         jnp.asarray(self._active))
            state = self._dispatch_segment(state)
            self._logits, cache, self._keys, _ = state
            self.pool.cache = dict(cache)
            wall = self._runner.records[-1].step_seconds
            self._segments_run += 1

            # harvest each lane's up-stream, retire satisfied requests
            harvested = 0
            with jax.profiler.TraceAnnotation("engine.harvest",
                                              lanes=occupancy):
                for req in list(self.running.values()):
                    data = np.asarray(self.lane_streams[req.lane].data,
                                      np.int32)
                    take = min(self.segment_len,
                               req.max_new_tokens - len(req.generated))
                    # corruption gate: a bit-flipped id is out of vocab range
                    self.health.check_output(
                        data[:take], lo=0, hi=self.cfg.vocab_size,
                        source=f"lane{req.lane}",
                        index=self._segments_run - 1)
                    req.generated.extend(int(t) for t in data[:take])
                    harvested += take
                    if req.done:
                        req.done_time = time.perf_counter()
                        self._retire(req)

            with jax.profiler.TraceAnnotation("engine.account"):
                row = self._runner.predicted_vs_measured()
                measured = ("bandwidth_heavy"
                            if row["bandwidth_heavy_measured"]
                            else "compute_bound")
                for entry in self.admission_log:
                    if entry["measured_verdict"] is None:
                        entry["measured_verdict"] = measured
                self.pool.reset_inactive(self._active)
                self._update_degradation()
                self._maybe_recalibrate()
                self._expire_deadlines()

                self.segment_log.append({
                    "segment": self._segments_run - 1,
                    "occupancy": occupancy,
                    "wall_seconds": wall,
                    "tokens": harvested,
                    "tokens_per_s": harvested / max(wall, 1e-12),
                    **row,
                })
            return harvested

    def run_until_drained(self, max_segments: int = 10_000) -> dict[int, np.ndarray]:
        """Run segments until queue + lanes are empty; returns rid -> tokens."""
        for _ in range(max_segments):
            if not self.queue and not self.running:
                break
            self.step_segment()
        else:
            raise RuntimeError(
                f"engine not drained after {max_segments} segments "
                f"({len(self.queue)} queued, {len(self.running)} running)")
        return {rid: r.tokens() for rid, r in sorted(self.finished.items())}

    # -- reporting ------------------------------------------------------------

    def stats(self) -> dict[str, Any]:
        """Counts over the engine's life. Per-segment walls and rates are in
        ``segment_log``; time a request or a token from its own stamps."""
        return {
            "requests": len(self.finished),
            "segments": self._segments_run,
            "tokens": sum(s["tokens"] for s in self.segment_log),
            "mean_occupancy": (
                float(np.mean([s["occupancy"] for s in self.segment_log]))
                if self.segment_log else 0.0),
            "admissions": len(self.admission_log),
            "admission_verdict_matches": sum(
                1 for a in self.admission_log
                if a["measured_verdict"] == a["verdict"]),
            "timed_out": sum(
                1 for r in self.finished.values() if r.timed_out),
            "cancelled": sum(
                1 for r in self.finished.values() if r.cancelled),
            "degraded": self.degraded,
            "machine_pack": self._machine_pack_label(),
            "repriced_admissions": sum(
                1 for a in self.admission_log if a.get("repriced")),
            "health": self.health.rollup(),
        }

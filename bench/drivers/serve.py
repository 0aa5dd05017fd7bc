"""The serve driver: the program's ``ServeEngine`` under a request mix.

Set-up draws the weights, builds the engine at the cell's lanes, pool and
segment length, and warms it with requests of every prompt length of the
mix. The window then drives ``submit`` and ``step_segment`` alone: a
backlog is topped up to ``queued_per_lane`` × lanes before each segment; an
open loop submits each request once it is due. Each request's first token
and last token are stamped with the end of the segment whose harvest held
them, and the count of tokens it had been served with the end of every
segment that served it some.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import time

import numpy as np

from bench import spec, weights
from bench.spec import say

clock = time.perf_counter


@dataclasses.dataclass
class Tracked:
    index: int
    prompt: np.ndarray
    new_tokens: int
    due: float                      # clock time it was due
    submitted: float
    rid: int = -1
    first: float | None = None      # end of the segment that harvested token 1
    done: float | None = None
    tokens: list | None = None      # what it was served, once finished
    served: int = 0                 # tokens harvested by the window's end
    prefill_s: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)   # (segment end, served)


def build(cell: spec.Cell, seed: int, tmp: str | None = None):
    """(engine, generator): weights from the seed, the engine built with the
    workload file's ``engine`` settings as its keyword arguments."""
    from repro.launch.engine import ServeEngine

    c, e, fam = cell.config, cell.workload["engine"], cell.family
    cfg = fam.program_config(c, cell.workload.get("program"))
    params = fam.to_program(c, weights.make(fam.layout(c), seed, c["torch_dtype"]),
                            cfg.scan_layers)
    eng = ServeEngine(cfg, params, **e)
    return eng, spec.generator(cell, seed)


def warm(sut, rounds: int = 2) -> None:
    """Every shape the window uses: one prefill per prompt length of the
    ladder, the segment program, the joins and the retirements. Then one
    full collection, and every object alive is frozen out of the garbage
    collector's later passes (as servers do once warm), so that no
    collection walks the traced programs inside the window; ``gc.unfreeze``
    undoes it."""
    from repro.launch.serve import prefill_block_size

    eng, gen = sut
    rng = np.random.default_rng([gen.seed, 3])
    for _ in range(rounds):
        for plen in gen.ladder:
            prompt = rng.integers(0, gen.vocab, plen).astype(np.int32)
            eng.submit(prompt, 2 * eng.segment_len)
        eng.run_until_drained()
    t = clock()
    gc.collect()
    gc.freeze()
    say(f"a full collection after warming took {clock() - t:.3f} s; "
        f"{gc.get_freeze_count()} objects frozen")
    m = eng.machine
    blocks = {p: prefill_block_size(eng.cfg, 1, p, m) for p in gen.ladder}
    say(f"machine pack {m.name!r} (r {m.r:.6g}, g {m.g:.6g}, l {m.l:.6g}, "
        f"e {m.e:.6g}); prefill blocks " + json.dumps(blocks))


def window(sut, seconds: float, annotate, tick=None) -> dict:
    """Drive the engine for ``seconds`` with the generator's requests; every
    submitted request's stamps. ``tick`` is called before each round of
    submits and a segment."""
    eng, gen = sut
    lanes = eng.max_lanes
    queued = gen.mix["arrivals"].get("queued_per_lane", 0) * lanes
    tracked: dict[int, Tracked] = {}
    open_: dict[int, Tracked] = {}
    segments, slow = [], []
    i, lateness = 0, []
    events, admissions, degraded = len(eng.health.events), len(eng.admission_log), 0
    t0 = clock()
    with annotate("bench.window"):
        while True:
            now = clock()
            if now - t0 >= seconds:
                break
            if tick:
                tick()
            with annotate("bench.submit"):
                while True:
                    if gen.backlog:
                        if len(eng.queue) >= queued:
                            break
                        due = now
                    else:
                        due = t0 + gen.due_s(i)
                        if due > now or due - t0 >= seconds:
                            break
                    r = gen.request(i)
                    t = Tracked(i, r.prompt, r.new_tokens, due, clock())
                    t.rid = eng.submit(r.prompt, r.new_tokens)
                    lateness.append(t.submitted - due)
                    tracked[t.rid] = open_[t.rid] = t
                    i += 1
            if not eng.queue and not eng.running:
                nxt = t0 + gen.due_s(i)
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t0 + seconds) - clock()))
                continue
            start, logged, joined = clock(), len(eng.segment_log), set(eng.running)
            with annotate("bench.step_segment"):
                n = eng.step_segment()
            end = clock()
            degraded += bool(eng.degraded)
            joins = [r for rid, r in eng.running.items() if rid not in joined]
            slow.append((end - start, len(joins),
                         sum(r.prefill_seconds or 0.0 for r in joins),
                         eng.segment_log[-1]["wall_seconds"]
                         if len(eng.segment_log) > logged else 0.0))
            with annotate("bench.stamp"):
                for rid, t in list(open_.items()):
                    req = eng.running.get(rid) or eng.finished.get(rid)
                    if req is None:
                        continue
                    if t.first is None and req.generated:
                        t.first = end
                        t.prefill_s = req.prefill_seconds
                    if len(req.generated) > t.served:
                        t.served = len(req.generated)
                        t.stamps.append((end, t.served))
                    if rid in eng.finished:
                        t.done, t.tokens = end, list(req.generated)
                        del open_[rid]
            if len(eng.segment_log) > logged:
                log = eng.segment_log[-1]
                segments.append({"start": start, "end": end, "tokens": n,
                                 "occupancy": log["occupancy"],
                                 "wall": log["wall_seconds"]})
    t1 = clock()
    for rid, t in open_.items():        # joined but unfinished: its prefill ran
        req = eng.running.get(rid)
        if req is not None:
            t.prefill_s = req.prefill_seconds
    codes: dict[str, int] = {}
    for ev in eng.health.events[events:]:
        codes[ev.code] = codes.get(ev.code, 0) + 1
    verdicts: dict[str, int] = {}
    for a in eng.admission_log[admissions:]:
        k = f"{'admit' if a.get('admit') else 'defer'}:{a.get('machine_pack')}"
        verdicts[k] = verdicts.get(k, 0) + 1
    program = {"health_events": codes, "admissions": verdicts,
               "degraded_segments": degraded,
               "slowest_steps": [[round(x, 4) for x in row]
                                 for row in sorted(slow, reverse=True)[:3]]}
    say(f"submit lag behind due time: max {max(lateness, default=0):.6f} s, mean "
        f"{sum(lateness) / max(len(lateness), 1):.6f} s over {len(lateness)} requests")
    say("engine in the window (the slowest steps: [seconds, joins, their "
        "prefill s, segment s]) " + json.dumps(program))
    return {"t0": t0, "t1": t1, "seconds": seconds, "requests": list(tracked.values()),
            "segments": segments, "lateness": lateness, "program": program}


def upto(rec: dict, cut: float) -> dict:
    """The record as it stood at ``cut``: the segments that had ended, the
    requests due by then with the stamps they had."""
    if cut >= rec["t1"]:
        return dict(rec)
    reqs = []
    for r in rec["requests"]:
        if r.due > cut:
            continue
        stamps = [s for s in r.stamps if s[0] <= cut]
        first = r.first if r.first is not None and r.first <= cut else None
        done = r.done if r.done is not None and r.done <= cut else None
        reqs.append(dataclasses.replace(
            r, stamps=stamps, served=stamps[-1][1] if stamps else 0, first=first,
            done=done, tokens=r.tokens if done is not None else None,
            prefill_s=r.prefill_s if first is not None else 0.0))
    return {**rec, "t1": cut, "seconds": cut - rec["t0"], "requests": reqs,
            "segments": [s for s in rec["segments"] if s["end"] <= cut]}


def e2e(rec: dict) -> dict:
    """The end-to-end numbers of a serve window."""
    t0, t1 = rec["t0"], rec["t1"]
    span = t1 - t0
    reqs = rec["requests"]
    out = {"out_tok_s": sum(s["tokens"] for s in rec["segments"]) / span}
    tpot = [(r.done - r.first) / (r.new_tokens - 1) for r in reqs
            if r.done is not None and r.new_tokens > 1]
    if tpot:
        out["tpot_p90_ms"] = 1e3 * percentile(tpot, 90)
    ttft = [(r.first if r.first is not None else t1) - r.due for r in reqs
            if r.due - t0 < rec["seconds"]]
    if ttft:
        out["ttft_p90_s"] = percentile(ttft, 90)
    out["counts"] = {"submitted": len(reqs), "finished": sum(r.done is not None for r in reqs),
                     "first_token": sum(r.first is not None for r in reqs),
                     "tpot_samples": len(tpot), "ttft_samples": len(ttft),
                     "segments": len(rec["segments"])}
    return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between closest ranks (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def tally(cell: spec.Cell, rec: dict, warmed=None) -> tuple[int, int]:
    """(requests submitted, finished requests served the wrong count of
    tokens or an id outside the vocabulary)."""
    vocab = cell.config["vocab_size"]
    failed = sum(1 for r in rec["requests"] if r.tokens is not None
                 and (len(r.tokens) != r.new_tokens
                      or any(not 0 <= t < vocab for t in r.tokens)))
    return len(rec["requests"]), failed


def for_readers(cell: spec.Cell, rec: dict) -> dict:
    """Each request's pending interval (due to done, else the record's
    end), the prefills' seconds and model FLOPs, and the model FLOPs of the
    prefills and the tokens served."""
    fam, c = cell.family, cell.config
    reqs = rec["requests"]
    prefill = sum(fam.prefill_flops(c, len(r.prompt)) for r in reqs if r.prefill_s > 0)
    return {"pending": [(r.due, r.done if r.done is not None else rec["t1"]) for r in reqs],
            "prefill_s": sum(r.prefill_s for r in reqs), "prefill_flops": prefill,
            "model_flops": prefill + sum(fam.decode_flops(c, len(r.prompt), r.served)
                                         for r in reqs)}


def check(cell: spec.Cell, seed: int, rec: dict, warmed=None,
          control: bool = False) -> dict:
    """Compare a sample of the window's finished requests with the reference.

    The sample, drawn from the seed, holds the request that was served most
    tokens and ``check.requests`` - 1 others. The reference runs once over
    each prompt with its served tokens, padded to the pool's length; each
    served token's gap is the reference's best logit minus its logit of
    that token, in standard deviations of its logits there. With
    ``control`` the same is read for the token the fp8 reference puts first
    at each of those positions.
    """
    import jax

    c, chk, fam = cell.config, cell.workload["check"], cell.family
    pool = cell.workload["engine"]["pool_seq"]
    done = [r for r in rec["requests"] if r.tokens is not None]
    missing = sum(abs(len(r.tokens) - r.new_tokens) for r in done)
    bad_ids = sum(int(np.sum((np.asarray(r.tokens) < 0)
                             | (np.asarray(r.tokens) >= c["vocab_size"]))) for r in done)
    out = {"finished": len(done), "missing_tokens": missing, "bad_ids": bad_ids}
    if not done:
        return out
    longest = max(range(len(done)), key=lambda j: len(done[j].tokens))
    rest = [j for j in range(len(done)) if j != longest]
    pick = [longest] + list(np.random.default_rng([seed, 4]).permutation(rest)[
        : chk["requests"] - 1])
    sample = [done[j] for j in pick]
    w = weights.make(fam.layout(c), seed, c["torch_dtype"])
    group = chk["group"]
    gaps, ctrl, served = [], [], 0
    for k in range(0, len(sample), group):
        part = sample[k:k + group]
        toks = np.zeros((group, pool), np.int32)
        mask = np.zeros((group, pool), bool)
        for j, r in enumerate(part):
            seq = np.concatenate([r.prompt, np.clip(r.tokens, 0, c["vocab_size"] - 1)])
            toks[j, :len(seq)] = seq
            mask[j, len(r.prompt) - 1:len(seq) - 1] = True
        g, g8 = (np.asarray(x) for x in jax.device_get(
            fam.token_gaps(c, w, toks, fp8=control)))
        gaps.append(g[mask])
        ctrl.append(g8[mask])
        served += int(mask.sum())
    del w
    gc.collect()
    out.update(sampled=len(sample), served_tokens=served,
               max_gap_sd=float(np.max(np.concatenate(gaps))))
    if control:
        out["control_max_gap_sd"] = float(np.max(np.concatenate(ctrl)))
    return out


def rehearse(cell: spec.Cell, place, report) -> None:
    """Compile the engine's segment program and the prefill at every prompt
    length of the ladder (one chunk of the whole prompt: the largest block
    the engine can choose). ``place`` gives a tree's shapes on the described
    device; ``report`` prints a compiled program's memory."""
    import jax

    from repro.launch.engine import ServeEngine
    from repro.launch.serve import make_prefill
    from repro.models import model as M

    c, e, fam = cell.config, cell.workload["engine"], cell.family
    cfg = fam.program_config(c, cell.workload.get("program"))
    params = place(jax.eval_shape(lambda: fam.to_program(
        c, weights.make(fam.layout(c), 0, c["torch_dtype"]), cfg.scan_layers)))
    eng = ServeEngine(cfg, params, max_lanes=e["max_lanes"], pool_seq=e["pool_seq"],
                      segment_len=e["segment_len"])
    runner = eng._runner
    prog = runner._compiled_cache[eng.segment_len]
    state = (eng._logits, eng.pool.cache, eng._keys, np.asarray(eng._active))
    stacked = [[s.as_stacked() for s in ss] for ss in runner._streams]
    out_bufs = [[s.as_stacked() for s in outs] for outs in runner._out_streams]
    report(f"{cell.name} segment ({e['max_lanes']} lanes x {e['pool_seq']}, "
           f"{eng.segment_len} steps)",
           prog._call.lower(place(state), place(out_bufs), place(stacked), params).compile())
    del eng
    cache = place(jax.eval_shape(lambda: M.init_cache(cfg, 1, e["pool_seq"])))
    for plen in cell.traffic["prompt_len"]["ladder"]:
        prompt = place(jax.ShapeDtypeStruct((1, plen), np.int32))
        report(f"{cell.name} prefill {plen} (block {plen})",
               make_prefill(cfg, plen).lower(params, cache, prompt).compile())

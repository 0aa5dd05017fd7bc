"""The train driver: the compiled train segment that ``train()`` runs.

``train()`` draws its parameters eagerly and calibrates inside the call, so
the harness builds the same segment program itself, as
``repro.train.loop._train_compiled`` does: ``make_train_step`` jitted with
donation, scanned by one ``HyperstepRunner`` over a ``BatchStream`` of the
program's ``TokenStream``, with each step's metrics streamed up. The token
stream reads the generator's batches from a token file. Set-up drives that
runner from the seed through its first three steps, reading the state the
comparison needs before the next step donates it; the window keeps calling
the same runner on the same state.
"""

from __future__ import annotations

import gc
import os
import tempfile
import time

import numpy as np

from bench import reference, spec, weights

clock = time.perf_counter
CHECK_STEPS = 3


class Trainer:
    """One compiled train segment with its state."""

    def __init__(self, cell: spec.Cell, seed: int, token_file: str,
                 abstract: bool = False):
        import jax
        import jax.numpy as jnp

        from repro.core.calibrate import default_machine
        from repro.core.hyperstep import HyperstepRunner
        from repro.core.plan import host_plan
        from repro.core.stream import Stream
        from repro.data.pipeline import BatchStream, DataConfig, TokenStream
        from repro.models import model as M
        from repro.optim.adamw import AdamW
        from repro.optim.schedule import constant
        from repro.train.steps import make_train_step

        c, o, fam = cell.config, cell.config["optimizer"], cell.family
        self.cell, self.seed = cell, seed
        self.steps = cell.workload["segment_steps"]
        if self.steps != 1:
            raise ValueError("the comparison reads the optimizer state after step 1: "
                             "segment_steps must be 1")
        gen = spec.generator(cell, seed)
        self.gen = gen
        gen.write(token_file)
        cfg = fam.program_config(c, cell.workload.get("program"))
        self.opt = AdamW(schedule=constant(o["lr"]), b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
        step_fn = jax.jit(make_train_step(cfg, self.opt), donate_argnums=(0, 1))
        data = DataConfig(vocab_size=c["vocab_size"], seq_len=gen.seq_len,
                          global_batch=gen.batch, source=token_file)
        spec_batch = {k: jax.ShapeDtypeStruct((gen.batch, gen.seq_len), jnp.int32)
                      for k in ("tokens", "labels")}
        if abstract:       # shapes only, for a compile without the chip
            params = jax.eval_shape(lambda: fam.to_program(
                c, weights.make(fam.layout(c), seed, c["torch_dtype"])))
            opt_state = jax.eval_shape(self.opt.init, params)
        else:
            params = fam.to_program(c, weights.make(fam.layout(c), seed, c["torch_dtype"]))
            opt_state = jax.jit(self.opt.init)(params)
        _, _, shapes = jax.eval_shape(step_fn, params, opt_state, spec_batch)
        self.mkeys = sorted(k for k, v in shapes.items() if v.size == 1)

        def hyperstep(state, tokens):
            p, s = state
            p, s, metrics = step_fn(p, s, tokens[0])
            return (p, s), [jnp.stack([metrics[k].astype(jnp.float32).reshape(())
                                       for k in self.mkeys])]

        batches = BatchStream(TokenStream(data), self.steps)
        self.metrics = Stream(data=np.zeros((self.steps, len(self.mkeys)), np.float32),
                              token_size=1, name="metrics")
        plan = host_plan([batches], out_streams=[self.metrics],
                         flops_per_hyperstep=6.0 * M.count_params(cfg) * gen.batch
                         * gen.seq_len, name=f"train_{cfg.name}")
        self.runner = HyperstepRunner(hyperstep, [batches], out_streams=[self.metrics],
                                      plan=plan, machine=default_machine())
        self.state = (params, opt_state)
        self.done = 0

    def segment(self) -> list[dict]:
        """One dispatch of ``segment_steps`` steps; each step's metrics."""
        self.state = self.runner.run(self.state, compiled=True)
        self.done += self.steps
        return [{k: float(self.metrics.data[i, j]) for j, k in enumerate(self.mkeys)}
                for i in range(self.steps)]


def build(cell: spec.Cell, seed: int, tmp: str) -> Trainer:
    """The compiled train segment with its state; its token file in ``tmp``."""
    return Trainer(cell, seed, os.path.join(tmp, "tokens.u32"))


def warm(tr: Trainer) -> dict:
    """Steps 1..3 through the window's own call: each loss, the first
    gradient as the optimizer got it (its first moment over 1 - b1, read
    after step 1), and the parameters' change after step 3."""
    import jax

    c, fam = tr.cell.config, tr.cell.family
    b1 = c["optimizer"]["b1"]
    stacked = fam.stacked(c)
    norms = jax.jit(lambda t: reference.leaf_norms(
        {k: v / (1 - b1) for k, v in fam.from_program(t).items()}, stacked))
    out = {"losses": []}
    while tr.done < CHECK_STEPS:
        out["losses"] += [m["loss"] for m in tr.segment()]
        if tr.done == 1:
            out["grad_norms"] = jax.device_get(norms(tr.state[1]["m"]))
    w0 = weights.make(fam.layout(c), tr.seed, c["torch_dtype"])
    out["change_norms"] = jax.device_get(jax.jit(lambda p, q: reference.leaf_norms(
        {k: v.astype("float32") - q[k].astype("float32")
         for k, v in fam.from_program(p).items()}, stacked))(tr.state[0], w0))
    del w0
    return out


def window(tr: Trainer, seconds: float, annotate, tick=None) -> dict:
    """Segments back to back until ``seconds`` have passed; ``tick`` is
    called before each."""
    segs = []
    t0 = clock()
    with annotate("bench.window"):
        while clock() - t0 < seconds:
            if tick:
                tick()
            start = clock()
            with annotate("bench.train_segment"):
                tr.segment()
            segs.append({"start": start, "end": clock(), "steps": tr.steps,
                         "wall": tr.runner.records[-1].step_seconds})
    return {"t0": t0, "t1": clock(), "seconds": seconds, "segments": segs,
            "tokens_per_step": tr.gen.batch * tr.gen.seq_len}


def upto(rec: dict, cut: float) -> dict:
    """The record as it stood at ``cut``: the segments that had ended."""
    if cut >= rec["t1"]:
        return dict(rec)
    return {**rec, "t1": cut, "seconds": cut - rec["t0"],
            "segments": [s for s in rec["segments"] if s["end"] <= cut]}


def e2e(rec: dict) -> dict:
    """The end-to-end numbers of a train window."""
    steps = sum(s["steps"] for s in rec["segments"])
    return {"train_tok_s": steps * rec["tokens_per_step"] / (rec["t1"] - rec["t0"]),
            "counts": {"steps": steps, "segments": len(rec["segments"])}}


def leaf_gap(cand: dict, base: dict, skip: set) -> tuple[float, str]:
    """The worst leaf: |candidate's norm - reference's norm| over the larger
    of the reference's norm of that leaf and of its median leaf."""
    rows = [(f"{k}[{i}]" if np.ndim(base[k]) else k, float(p), float(r))
            for k in base
            for i, (p, r) in enumerate(zip(np.ravel(cand[k]), np.ravel(base[k])))]
    med = float(np.median([r for _, _, r in rows]))
    return max(((abs(p - r) / max(r, med), name) for name, p, r in rows
                if name not in skip), default=(0.0, ""))


def tally(cell: spec.Cell, rec: dict, first: dict) -> tuple[int, int]:
    """(steps taken, set-up's checked steps included; those whose loss is
    not a number)."""
    return (sum(s["steps"] for s in rec["segments"]) + CHECK_STEPS,
            sum(1 for x in first["losses"] if x != x))


def for_readers(cell: spec.Cell, rec: dict) -> dict:
    """The model FLOPs of the steps taken."""
    return {"model_flops": sum(s["steps"] for s in rec["segments"]) * rec["tokens_per_step"]
            * cell.family.train_token_flops(cell.config, cell.traffic["seq_len"])}


def readings(cell: spec.Cell, seed: int, batches: list, fp8: bool = False) -> dict:
    """The reference's losses, first-gradient and change norms over ``batches``."""
    c, fam = cell.config, cell.family
    ref = fam.train(c, lambda: weights.make(fam.layout(c), seed, c["torch_dtype"]), batches,
                    fp8=fp8)
    ref["change_norms"] = reference.change_norms(
        ref.pop("w"), weights.make(fam.layout(c), seed, c["torch_dtype"]), fam.stacked(c))
    gc.collect()
    return ref


def compare(cand: dict, base: dict) -> dict:
    """Each compared number of ``cand`` against the reference ``base``. Leaves
    whose reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out."""
    g1 = base["grad_norms"]
    cutoff = 1e-3 * float(np.median([x for v in g1.values() for x in np.ravel(v)]))
    skip = {(f"{k}[{i}]" if np.ndim(v) else k) for k, v in g1.items()
            for i, x in enumerate(np.ravel(v)) if x < cutoff}
    grad, grad_leaf = leaf_gap(cand["grad_norms"], g1, skip)
    change, change_leaf = leaf_gap(cand["change_norms"], base["change_norms"], skip)
    return {"loss_gap": max(abs(a - b) for a, b in zip(cand["losses"], base["losses"])),
            "grad_gap": grad, "grad_leaf": grad_leaf,
            "change_gap": change, "change_leaf": change_leaf,
            "skipped_leaves": sorted(skip)}


def check(cell: spec.Cell, seed: int, rec: dict, prog: dict, control: bool = False) -> dict:
    """The reference follows the first three steps (``prog``, from ``warm``)
    from the same weights and rows; with ``control``, so does the fp8
    reference in the program's place."""
    gen = spec.generator(cell, seed)
    batches = [gen.batch_at(i) for i in range(CHECK_STEPS)]
    base = readings(cell, seed, batches)
    out = compare(prog, base)
    out["losses"], out["reference_losses"] = prog["losses"], base["losses"]
    if control:
        ctrl = compare(readings(cell, seed, batches, fp8=True), base)
        out.update({f"control_{k}": v for k, v in ctrl.items() if k != "skipped_leaves"})
    return out


def rehearse(cell: spec.Cell, place, report) -> None:
    """Compile the train segment. ``place`` gives a tree's shapes on the
    described device; ``report`` prints a compiled program's memory."""
    with tempfile.TemporaryDirectory() as tmp:
        tr = Trainer(cell, 0, os.path.join(tmp, "tokens.u32"), abstract=True)
        runner = tr.runner
        prog = runner.compile(tr.steps)
        stacked = [[s.as_stacked() for s in ss] for ss in runner._streams]
        out_bufs = [[s.as_stacked() for s in outs] for outs in runner._out_streams]
        report(f"{cell.name} train segment ({tr.steps} step, batch "
               f"{tr.gen.batch} x {tr.gen.seq_len})",
               prog._call.lower(place(tr.state), place(out_bufs), place(stacked),
                                None).compile())

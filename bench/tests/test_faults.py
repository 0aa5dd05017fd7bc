"""A run with its timed path broken underneath comes out not ``correct``.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, comparison) on the CPU at the smoke widths, with one fault
planted in the program, and the cell's own limits. The faults are those
these cells can have: a served token altered where it is produced; a train
step that returns its state unchanged; a train step that leaves out half of
its batch and takes the mean over the rest. (No cell spans chips, so there
is no exchange between chips to leave out.)"""

from __future__ import annotations

import jax.numpy as jnp

from bench.run import run_cell
from bench.tests import tiny

SEED = 2**32 + 99


def test_an_altered_token_is_caught(monkeypatch):
    from repro.launch.engine import ServeEngine

    make = ServeEngine._make_step

    def broken(self):
        step, vocab = make(self), self.cfg.vocab_size

        def altered(state, tokens, params):
            state, out = step(state, tokens, params)
            return state, [jnp.where(t % 7 == 0, (t + 97) % vocab, t) for t in out]
        return altered

    monkeypatch.setattr(ServeEngine, "_make_step", broken)
    out = run_cell(tiny.cell("minicpm-2b.batch-chat-unrolled"), SEED, 2.0, False)
    assert out["correct"] is False
    assert out["compared"]["max_gap_sd"]["value"] > out["compared"]["max_gap_sd"]["limit"]


def broken_train_step(monkeypatch, fault):
    from repro.train import steps

    make = steps.make_train_step

    def factory(cfg, opt, **kw):
        step = make(cfg, opt, **kw)

        def run(params, opt_state, batch):
            if fault == "half_batch":
                return step(params, opt_state,
                            {k: v[: v.shape[0] // 2] for k, v in batch.items()})
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return run

    monkeypatch.setattr(steps, "make_train_step", factory)


def test_a_step_that_keeps_its_state_is_caught(monkeypatch):
    broken_train_step(monkeypatch, "unchanged")
    out = run_cell(tiny.cell("minicpm-2b.pretrain"), SEED, 1.0, False)
    assert out["correct"] is False
    assert out["compared"]["change_gap"]["value"] > out["compared"]["change_gap"]["limit"]


def test_half_a_batch_is_caught(monkeypatch):
    broken_train_step(monkeypatch, "half_batch")
    out = run_cell(tiny.cell("minicpm-2b.pretrain"), SEED, 1.0, False)
    assert out["correct"] is False

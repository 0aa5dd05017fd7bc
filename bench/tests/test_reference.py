"""The dense decoder family's reference (``bench/families/dense_decoder.py``)
against the program, on the CPU at the smoke widths of both configurations'
block kinds: minicpm-2b (RMSNorm, SwiGLU, MHA, tied head)
and starcoder2-15b (LayerNorm, GELU, GQA, untied head).

The weights are float32 here, so the program and the reference do the same
arithmetic in another order: a few float32 roundings (2^-24 relative) per
operation, over two layers. Each tolerance below is that, with room."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.tests import tiny

CELLS = ["minicpm-2b.batch-chat-unrolled", "starcoder2-15b.code-complete-unrolled"]


def f32_cell(name: str, scanned: bool = True):
    cell = tiny.cell(name)
    cell.config["torch_dtype"] = "float32"
    cell.workload["program"] = {"dtype": "float32", "scan_layers": scanned}
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_forward_logits_match_the_program(name):
    from repro.models import model as M

    cell = f32_cell(name)
    c, fam = cell.config, cell.family
    cfg = fam.program_config(c, cell.workload["program"])
    w = weights.make(fam.layout(c), 3, "float32")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want, _ = M.forward(cfg, fam.to_program(c, w), toks)
    got = fam.head(c, w, fam.hidden(c, w, toks))
    # logits reach a few units; float32 reassociation over two blocks and
    # a 64-wide contraction stays under 1e-5 of that
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("name", CELLS)
def test_loss_and_gradients_match_the_program(name):
    from repro.models import model as M

    cell = f32_cell(name)
    c, fam = cell.config, cell.family
    cfg = fam.program_config(c, cell.workload["program"])
    w = weights.make(fam.layout(c), 4, "float32")
    rows = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 33))
    toks, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    with jax.default_matmul_precision("highest"):
        (want, _), g_prog = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, toks, labels), has_aux=True)(fam.to_program(c, w))
        got, g_ref = jax.value_and_grad(lambda w_: fam.loss(c, w_, toks, labels))(w)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    g_prog = fam.from_program(g_prog)
    for k, g in g_ref.items():
        # a gradient is a sum over 64 positions of products of the above
        scale = float(jnp.max(jnp.abs(g))) + 1e-12
        assert float(jnp.max(jnp.abs(g_prog[k] - g))) < 1e-4 * scale, k


def test_adamw_matches_the_program():
    from repro.optim.adamw import AdamW
    from repro.optim.schedule import constant

    cell = tiny.cell("minicpm-2b.pretrain")
    c, fam = cell.config, cell.family
    o = c["optimizer"]
    opt = AdamW(schedule=constant(o["lr"]), b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
    w = weights.make(fam.layout(c), 5, "float32")
    g = weights.make(fam.layout(c), 6, "float32")
    p, s = fam.to_program(c, w), opt.init(fam.to_program(c, w))
    m = {k: jnp.zeros_like(x) for k, x in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    for step in (1, 2):
        p, s, _ = opt.update(fam.to_program(c, g), s, p)
        w, m, v, _ = reference._adamw(tuple(sorted(o.items())), fam.stacked(c), w, m, v,
                                      dict(g), jnp.float32(step))
    got = fam.from_program(p)
    for k in w:
        # the same float32 update, computed in another order
        assert float(jnp.max(jnp.abs(got[k] - w[k]))) < 1e-6, k


@pytest.mark.parametrize("name", CELLS)
def test_the_engine_serves_the_reference_argmax(name):
    """Prefill through the cache and packed decode through the compiled
    segment, several requests at once, against the reference's first choice
    at every served position (the unrolled path the serve cells run)."""
    from bench.drivers import serve

    cell = f32_cell(name, scanned=False)
    c, fam = cell.config, cell.family
    eng, _ = serve.build(cell, 7)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, c["vocab_size"], n).astype(np.int32) for n in (8, 16, 8, 16)]
    rids = [eng.submit(p, 20) for p in prompts]
    out = eng.run_until_drained()
    w = weights.make(fam.layout(c), 7, "float32")
    pool = cell.workload["engine"]["pool_seq"]
    toks = np.zeros((len(prompts), pool), np.int32)
    for j, rid in enumerate(rids):
        toks[j, :len(out[rid])] = out[rid]
    gaps, _ = fam.token_gaps(c, w, toks)
    gaps = np.asarray(gaps)
    for j, p in enumerate(prompts):
        # a served token may lose to the reference's best only by a tie
        # broken the other way: float32 error, far under 1e-3
        assert gaps[j, len(p) - 1:len(p) + 19].max() < 1e-3

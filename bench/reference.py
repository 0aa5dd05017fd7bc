"""What every family's plain float32 reference shares.

A family module (``bench/families/<family>.py``) writes its architecture's
forward pass, ``hidden`` and ``head``, from the published description; this
module turns such a pair into the numbers the comparison reads: the gap of
each served token below the reference's best logit, the loss, and the
readings of AdamW's first steps.

Every operation runs in float32 and every matmul at ``HIGHEST`` precision;
the weights are the values the configuration serves, widened. ``fp8=True``
is the control: each linear layer's operands rounded to ``float8_e4m3fn``
with one scale per tensor. Nothing here imports the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LOGIT_CHUNK = 512   # positions per block of the head and the loss


def _q8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8_e4m3fn under one scale; its gradient passes
    straight through, as fp8 training's does."""
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...d,df->...f", x, w, precision=HIGHEST)


def _chunks(y, chunk):
    """(B, T, ...) -> (n, B, chunk, ...), zero-padded."""
    b, t = y.shape[:2]
    n = -(-t // chunk)
    y = jnp.pad(y, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (y.ndim - 2))
    return jnp.moveaxis(y.reshape(b, n, chunk, *y.shape[2:]), 1, 0)


def _unchunk(a, t):
    """(n, B, chunk) -> (B, t)."""
    return jnp.moveaxis(a, 0, 1).reshape(a.shape[1], -1)[:, :t]


def gaps(hidden, head, tokens, fp8: bool):
    """For (B, T) ``tokens``, at every position t: the reference's best logit
    minus its logit of token t+1, and (``fp8``) minus its logit of the token
    the control puts first there; both in standard deviations of the
    reference's logits at t. ``hidden(tokens, fp8)`` gives the final-normed
    hidden states, ``head(y, fp8)`` the logits of hidden states ``y``. To be
    traced inside the family's jitted call."""
    t = tokens.shape[1]
    chunk = min(LOGIT_CHUNK, t)
    ys = _chunks(hidden(tokens, False), chunk)
    y8s = _chunks(hidden(tokens, True), chunk) if fp8 else ys
    nxt = _chunks(jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1), chunk)

    def block(args):
        yc, y8c, nc = args
        lg = head(yc, False)
        best, sd = jnp.max(lg, -1), jnp.std(lg, -1)
        served = jnp.take_along_axis(lg, nc[..., None], -1)[..., 0]
        if not fp8:
            return (best - served) / sd, (best - served) / sd
        pick = jnp.argmax(head(y8c, True), -1)
        return ((best - served) / sd,
                (best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]) / sd)

    g, g8 = jax.lax.map(block, (ys, y8s, nxt))
    return _unchunk(g, t), _unchunk(g8, t)


def loss(hidden, head, tokens, labels, fp8: bool = False):
    """Mean next-token cross entropy over every position, in float32, of the
    forward pass ``hidden``/``head`` (see :func:`gaps`)."""
    t = tokens.shape[1]
    chunk = min(LOGIT_CHUNK, t)
    ys = _chunks(hidden(tokens, fp8), chunk)
    ls = _chunks(labels, chunk)

    @jax.checkpoint
    def block(args):
        i, yc, lc = args
        lg = head(yc, fp8)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(i * chunk + jnp.arange(chunk) < t, nll, 0.0))

    return jnp.sum(jax.lax.map(block, (jnp.arange(ys.shape[0]), ys, ls))) / labels.size


# -- training -------------------------------------------------------------------


def leaf_norms(tree: dict, stacked=()) -> dict:
    """The L2 norm of each leaf in float32; of each layer, for the leaves
    named in ``stacked`` (stacked over the layers on their first axis)."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=tuple(range(1, x.ndim)) if k in stacked else None))
            for k, x in tree.items()}


@functools.partial(jax.jit, static_argnames=("opt", "stacked"), donate_argnums=(2, 3, 4, 5))
def _adamw(opt, stacked, w, m, v, g, step):
    """One AdamW step (Loshchilov & Hutter) after clipping the global norm,
    with the hyperparameters ``opt`` (the configuration's ``optimizer`` as
    sorted items), computed in float32 and stored back in the weights' own
    type. Returns the new state and the per-leaf norms of the clipped
    gradient."""
    o = dict(opt)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    g = {k: x * jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9)) for k, x in g.items()}
    bc1 = 1 - o["b1"] ** step
    bc2 = 1 - o["b2"] ** step
    m = {k: o["b1"] * m[k] + (1 - o["b1"]) * g[k] for k in w}
    v = {k: o["b2"] * v[k] + (1 - o["b2"]) * g[k] * g[k] for k in w}

    def update(k):
        p = w[k].astype(jnp.float32)
        u = (m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + o["eps"]) + o["weight_decay"] * p
        return (p - o["lr"] * u).astype(w[k].dtype)

    return {k: update(k) for k in w}, m, v, leaf_norms(g, stacked)


@functools.partial(jax.jit, static_argnames=("stacked",), donate_argnums=(0,))
def _change_norms(w, w0, stacked):
    return leaf_norms({k: w[k].astype(jnp.float32) - w0[k].astype(jnp.float32) for k in w},
                      stacked)


def train(loss_and_grad, optimizer: dict, stacked, make, batches) -> dict:
    """AdamW from the weights ``make()`` returns, over ``batches`` of
    (tokens, labels), one per step; ``loss_and_grad(w, tokens, labels)``
    gives the loss and its float32 gradient. Returns each step's loss, the
    first clipped gradient's per-leaf norms, and the final weights. The
    weights stay in the type the configuration stores them in; the moments
    are float32."""
    opt = tuple(sorted(optimizer.items()))
    w = make()
    m = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    v = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    losses, g1 = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        lval, g = loss_and_grad(w, jnp.asarray(tokens, jnp.int32),
                                jnp.asarray(labels, jnp.int32))
        losses.append(float(lval))
        w, m, v, gn = _adamw(opt, stacked, w, m, v, g, jnp.float32(step))
        del g
        g1 = jax.device_get(gn) if g1 is None else g1
    return {"losses": losses, "grad_norms": g1, "w": w}


def change_norms(w: dict, w0: dict, stacked=()) -> dict:
    """Per-leaf norms of ``w - w0`` (see :func:`leaf_norms`); ``w`` is consumed."""
    return jax.device_get(_change_norms(w, w0, stacked))

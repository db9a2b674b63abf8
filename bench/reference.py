"""The plain reference: the configuration's model in float32, and the
stale-psum training it follows.

Straight ``jax.numpy``, imported from nothing of the program. The forward
pass is the family's own (``logits`` of ``bench/models/<model>.py``, found
by the configuration's ``"model"``); this module holds what every family
shares: the matmul, the RMS norm, next-token cross-entropy, the stale-psum
delays and Adam. Every matmul runs at ``Precision.HIGHEST``. Passing
``quant`` rounds both operands of every matmul to that dtype first, an
8-bit float tensor scaled so that its largest magnitude fills the type's
range, as a low-precision matmul path would: the control of the
correctness check computes the same mathematics one precision below the
configuration's. The rounding passes gradients straight through, so the
backward pass multiplies float32 cotangents by the rounded operands.

The stale-psum training reference follows the paper's delay model: worker p
at step k applies its gradient from step k - d, d drawn uniformly from
0..s-1 per worker and step from the engine key's per-step split and clamped
to k, the workers' delivered gradients are averaged, and Adam applies the
mean.
"""
from __future__ import annotations

import functools
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q(x, quant):
    if quant is None:
        return x
    top = float(jnp.finfo(quant).max)
    if top > 1e30:                    # bfloat16: float32's range, no scale
        low = x.astype(quant).astype(jnp.float32)
    else:
        scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        low = (x * scale).astype(quant).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(low - x)


def mm(spec, a, b, quant):
    """``jnp.einsum`` at ``HIGHEST``, both operands rounded to ``quant``
    first where it is given."""
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HI)


def rms(x, scale, eps):
    """RMS norm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def loss(model, params, tokens, cfg: dict, quant=None):
    """Mean next-token cross-entropy of tokens [B, S+1] under the family
    ``model``'s forward pass."""
    lg = model.logits(params, tokens[:, :-1], cfg, quant)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("model", "cfg_text", "quant"))
def _value_and_grad(params, tokens, model, cfg_text, quant):
    return jax.value_and_grad(loss, argnums=1)(
        model, params, tokens, json.loads(cfg_text), quant)


def value_and_grad(model, params, tokens, cfg: dict, quant=None):
    """(loss, gradient) of ``loss``. The whole configuration, nested groups
    included, reaches the family's forward pass."""
    return _value_and_grad(params, tokens, model,
                           json.dumps(cfg, sort_keys=True), quant)


def uniform_delays(key_seed: int, steps: int, workers: int, s: int):
    """d[k, p] of the first ``steps`` steps: the engine key is split once per
    step into (next key, delay key), and the delay key draws each worker's
    delay uniformly from 0..s-1; a delay never reaches before step 0."""
    key = jax.random.PRNGKey(key_seed)
    out = []
    for k in range(steps):
        key, kd = jax.random.split(key)
        d = (jax.random.randint(kd, (workers,), 0, s, dtype=jnp.int32)
             if s > 1 else jnp.zeros((workers,), jnp.int32))
        out.append(np.minimum(np.asarray(d), k))
    return np.stack(out)


@jax.jit
def _adam(params, m, v, g, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
    v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, mi, vi: p - lr * (mi / c1) / (jnp.sqrt(vi / c2) + eps),
        params, m, v)
    return params, m, v


@jax.jit
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_change_norms(tree, base) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))])


def stale_psum(model, params0, batches, delays, cfg: dict, opt: dict, *,
               quant=None, rows: Optional[slice] = None,
               own_worker: Optional[int] = None) -> dict:
    """Follow the first ``len(batches)`` stale-psum steps of the family
    ``model``.

    ``batches[k]`` is step k's global batch [B, S+1]; worker p takes rows
    [p B/P, (p+1) B/P). Returns per-step mean losses, the leaf norms of the
    first delivered gradient, and the leaf norms of the parameters' change
    after the last step, and the first delivered gradient itself, on the
    host (``first_tree``). A worker's gradient is the mean of its rows'
    gradients, taken one row at a time so that the reference fits beside
    nothing else on the chip. ``rows`` keeps only those rows of each
    worker's shard and ``own_worker`` delivers that worker's gradient alone:
    faults planted in the reference, for calibrating the limits."""
    steps, workers = delays.shape
    src = list(range(workers)) if own_worker is None else [own_worker]
    # uses[(j, p)] = the steps k that deliver worker p's step-j gradient
    uses = {}
    for k in range(steps):
        for p in src:
            uses.setdefault((k - int(delays[k, p]), p), []).append(k)
    params = params0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    acc, losses, first = {}, [], None
    for k in range(steps):
        b = batches[k]
        per = b.shape[0] // workers
        step_losses = []
        for p in range(workers):
            shard = b[p * per:(p + 1) * per]
            if rows is not None:
                shard = shard[rows]
            g, lv = None, 0.0
            for r in range(shard.shape[0]):
                lr_, gr = value_and_grad(model, params,
                                         jnp.asarray(shard[r:r + 1]), cfg,
                                         quant)
                lv += float(lr_) / shard.shape[0]
                g = gr if g is None else _add(g, gr)
            step_losses.append(lv)
            for later in uses.get((k, p), []):
                acc[later] = _axpy(acc.get(later), g,
                                   1.0 / (shard.shape[0] * len(src)))
            del g
        losses.append(float(np.mean(step_losses)))
        agg = acc.pop(k)
        if k == 0:
            first = np.asarray(leaf_norms(agg))
            first_tree = jax.device_get(agg)
        params, m, v = _adam(params, m, v, agg, jnp.float32(k + 1),
                             opt["lr"], opt["b1"], opt["b2"], opt["eps"])
        del agg
    change = np.asarray(leaf_change_norms(params, params0))
    return {"losses": losses, "first_grad": first, "change": change,
            "first_tree": first_tree}


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(g, c):
    return jax.tree.map(lambda x: x * c, g)


def _axpy(acc, g, c):
    """acc + c g (acc may be None)."""
    scaled = _scale(g, jnp.float32(c))
    return scaled if acc is None else _add(acc, scaled)


def worst_leaf_share(amounts: np.ndarray, ref: np.ndarray,
                     keep: Optional[np.ndarray] = None) -> float:
    """Largest per-leaf ``amounts`` over the larger of the reference's leaf
    norm ``ref`` and its median leaf norm, over the leaves ``keep``
    selects."""
    amounts, ref = np.asarray(amounts, np.float64), np.asarray(ref,
                                                                np.float64)
    shares = amounts / np.maximum(ref, np.median(ref))
    if keep is not None:
        shares = shares[keep]
    return float(shares.max())


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: Optional[np.ndarray] = None) -> float:
    """The gap between the program's and the reference's leaf norms, by
    the worst leaf (``worst_leaf_share``)."""
    return worst_leaf_share(np.abs(np.asarray(prog, np.float64)
                                   - np.asarray(ref, np.float64)), ref, keep)


def leaf_diff_norms(a, b) -> np.ndarray:
    """||a - b|| of each leaf of two host trees, in float64."""
    return np.array([np.sqrt(np.sum(np.square(
        np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def moving_leaves(first_grad_ref: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    g = np.asarray(first_grad_ref, np.float64)
    return g >= 1e-3 * np.median(g)

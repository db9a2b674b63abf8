"""Pytree-native optimizers matching Table 1 of the paper.

Self-contained (no optax): each optimizer is an ``(init, update)`` pair where
``update(grads, state, params) -> (delta, new_state)`` returns the *additive*
parameter delta. Additivity is what the staleness engine transports — a
worker's "update" u_p^t is exactly this delta, so worker-side adaptive state
(momentum, second moments) stays local to the worker while the delta travels
through the delayed network, mirroring the paper's setup.

Learning rates may be floats or callables of the (int32) step count, which is
carried inside the optimizer state; the Theorem-1 schedule plugs in here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

from repro.scopes import MODEL, OPTIMIZER

Pytree = Any
Schedule = Union[float, Callable[[jax.Array], jax.Array]]


class Optimizer(NamedTuple):
    init: Callable[[Pytree], Pytree]
    update: Callable[[Pytree, Pytree, Pytree], tuple]
    # Hyperparameter spec for optimizers whose update math can be driven by
    # the one-pass fused megakernel (``dispatch.fused_update``). ``None``
    # means the optimizer is opaque: engines must call ``update``.
    spec: Any = None


def _lr_at(lr: Schedule, step: jax.Array) -> jax.Array:
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


# Engines driving the fused megakernel resolve schedules outside the kernel.
lr_at = _lr_at


def sgd(lr: Schedule = 0.01) -> Optimizer:
    def init(params):
        return {"step": jnp.int32(0)}

    def update(grads, state, params):
        eta = _lr_at(lr, state["step"] + 1)
        delta = jax.tree.map(lambda g: (-eta * g.astype(jnp.float32)).astype(g.dtype), grads)
        return delta, {"step": state["step"] + 1}

    return Optimizer(init, update)


def momentum(lr: Schedule = 0.01, beta: float = 0.9, nesterov: bool = False) -> Optimizer:
    def init(params):
        return {"step": jnp.int32(0), "m": jax.tree.map(jnp.zeros_like, params)}

    def update(grads, state, params):
        eta = _lr_at(lr, state["step"] + 1)
        m = jax.tree.map(lambda mi, g: beta * mi + g, state["m"], grads)
        if nesterov:
            delta = jax.tree.map(lambda mi, g: -eta * (beta * mi + g), m, grads)
        else:
            delta = jax.tree.map(lambda mi: -eta * mi, m)
        return delta, {"step": state["step"] + 1, "m": m}

    return Optimizer(init, update)


def adagrad(lr: Schedule = 0.01, eps: float = 1e-7) -> Optimizer:
    def init(params):
        return {"step": jnp.int32(0), "v": jax.tree.map(jnp.zeros_like, params)}

    def update(grads, state, params):
        eta = _lr_at(lr, state["step"] + 1)
        v = jax.tree.map(lambda vi, g: vi + g * g, state["v"], grads)
        delta = jax.tree.map(lambda vi, g: -eta * g / (jnp.sqrt(vi) + eps), v, grads)
        return delta, {"step": state["step"] + 1, "v": v}

    return Optimizer(init, update)


def rmsprop(lr: Schedule = 0.01, decay: float = 0.9, eps: float = 1e-7,
            mom: float = 0.0) -> Optimizer:
    """Table 1: eta=0.01, decay=0.9, momentum=0 (Hinton 2012 formulation)."""
    def init(params):
        st = {"step": jnp.int32(0), "v": jax.tree.map(jnp.zeros_like, params)}
        if mom > 0:
            st["m"] = jax.tree.map(jnp.zeros_like, params)
        return st

    def update(grads, state, params):
        eta = _lr_at(lr, state["step"] + 1)
        v = jax.tree.map(lambda vi, g: decay * vi + (1 - decay) * g * g, state["v"], grads)
        scaled = jax.tree.map(lambda vi, g: g / (jnp.sqrt(vi) + eps), v, grads)
        new = {"step": state["step"] + 1, "v": v}
        if mom > 0:
            m = jax.tree.map(lambda mi, sg: mom * mi + sg, state["m"], scaled)
            new["m"] = m
            delta = jax.tree.map(lambda mi: -eta * mi, m)
        else:
            delta = jax.tree.map(lambda sg: -eta * sg, scaled)
        return delta, new

    return Optimizer(init, update)


def adam(lr: Schedule = 0.001, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0,
         kernel: bool = False) -> Optimizer:
    """Table 1 defaults. With weight_decay > 0 this is AdamW (decoupled).

    ``kernel=True`` runs the moment/update math as ONE fused pass over packed
    flat [D] views (``repro.kernels.dispatch.fused_adam``) instead of ~8
    per-leaf elementwise ops. The additive-delta contract is preserved by
    feeding the kernel a zero parameter vector: ``0 - update`` IS the delta,
    exactly the unfused formula (fp32; the default path stays bitwise).
    """
    def init(params):
        return {
            "step": jnp.int32(0),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
        }

    def update_fused(grads, state, params):
        from repro import treemath as tm
        from repro.kernels import dispatch
        spec = tm.pack_spec(params)
        pad = dispatch.PACK_ALIGN
        if not dispatch.fuses(4 * tm.padded_size(spec.total, pad)):
            # Packing exists to feed the fused kernel; when dispatch would
            # fall back to the jnp oracle anyway (interpret mode, oversized
            # operand), the per-leaf path IS the reference — skip the copies.
            dispatch.note("fused_adam", "tree",
                          "packed pass skipped: dispatcher would run ref")
            return update(grads, state, params)
        step = state["step"] + 1
        eta = _lr_at(lr, step)
        gv = tm.tree_pack(grads, pad_to=pad)
        dneg, m_new, v_new = dispatch.fused_adam(
            jnp.zeros_like(gv), tm.tree_pack(state["m"], pad_to=pad),
            tm.tree_pack(state["v"], pad_to=pad), gv, eta, b1, b2, eps, step)
        delta32 = tm.tree_unpack(dneg, spec, dtype=jnp.float32)

        def delta_leaf(d, p):
            if weight_decay:
                d = d - eta * weight_decay * p
            return d.astype(p.dtype)

        delta = jax.tree.map(delta_leaf, delta32, params)
        return delta, {"step": step, "m": tm.tree_unpack(m_new, spec),
                       "v": tm.tree_unpack(v_new, spec)}

    def update(grads, state, params):
        step = state["step"] + 1
        eta = _lr_at(lr, step)
        m = jax.tree.map(lambda mi, g: b1 * mi + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda vi, g: b2 * vi + (1 - b2) * g * g, state["v"], grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def delta_leaf(mi, vi, p):
            d = -eta * (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
            if weight_decay:
                d = d - eta * weight_decay * p
            return d.astype(p.dtype)

        delta = jax.tree.map(delta_leaf, m, v, params)
        return delta, {"step": step, "m": m, "v": v}

    return Optimizer(init, update_fused if kernel else update,
                     spec=dict(name="adam", lr=lr, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay))


_REGISTRY = {
    "sgd": sgd,
    "momentum": momentum,
    "adam": adam,
    "adagrad": adagrad,
    "rmsprop": rmsprop,
}


def get_optimizer(name: str, **kwargs) -> Optimizer:
    if name not in _REGISTRY:
        raise KeyError(f"unknown optimizer {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def paper_default(name: str, lr: Schedule = None) -> Optimizer:
    """Table 1 hyperparameters for the CNN/DNN/MLR experiments."""
    table1 = {
        "sgd": dict(lr=0.01),
        "momentum": dict(lr=0.01, beta=0.9),
        "adam": dict(lr=0.001, b1=0.9, b2=0.999),
        "adagrad": dict(lr=0.01),
        "rmsprop": dict(lr=0.01, decay=0.9, mom=0.0),
    }
    kw = dict(table1[name])
    if lr is not None:
        kw["lr"] = lr
    return _REGISTRY[name](**kw)


def make_sgd_update_fn(loss_fn, optimizer: Optimizer):
    """Adapt (loss_fn, optimizer) to the staleness engine's UpdateFn contract:
    (params, opt_state, batch, key) -> (delta, new_opt_state, metrics)."""
    def update_fn(params, opt_state, batch, key):
        with jax.named_scope(MODEL):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope(OPTIMIZER):
            delta, new_state = optimizer.update(grads, opt_state, params)
        return delta, new_state, {"loss": loss}

    return update_fn


def make_stochastic_update_fn(loss_fn, optimizer: Optimizer):
    """Same, for losses that consume a PRNG key (VAE blackbox VI)."""
    def update_fn(params, opt_state, batch, key):
        with jax.named_scope(MODEL):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        with jax.named_scope(OPTIMIZER):
            delta, new_state = optimizer.update(grads, opt_state, params)
        return delta, new_state, {"loss": loss}

    return update_fn

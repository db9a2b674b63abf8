"""Properties of the simulation engine (paper Section 3 semantics)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import treemath as tm
from repro.core import (ConstantDelay, StalenessConfig, UniformDelay, drain,
                        init_sim_state, make_sim_step)
from repro.optim import adam, make_sgd_update_fn, sgd


def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


def make_setup(P, s, seed=0, opt=None, delay=None):
    opt = opt or sgd(0.05)
    update_fn = make_sgd_update_fn(quad_loss, opt)
    cfg = StalenessConfig(num_workers=P, delay=delay or UniformDelay(s))
    params = {"w": jnp.zeros((4,))}
    state = init_sim_state(params, opt.init(params), cfg, jax.random.PRNGKey(seed))
    return update_fn, cfg, state


def gen_batches(key, P, n, w_true):
    for _ in range(n):
        key, kb = jax.random.split(key)
        x = jax.random.normal(kb, (P, 8, 4))
        yield (x, x @ w_true), key


W_TRUE = jnp.array([1.0, -2.0, 3.0, 0.5])


@given(P=st.integers(1, 6), s=st.integers(0, 7), seed=st.integers(0, 1000))
@settings(max_examples=12, deadline=None)
def test_update_conservation(P, s, seed):
    """After draining, every worker cache equals x0 + sum of ALL updates —
    no update is lost or duplicated by the delivery buffer."""
    opt = sgd(0.05)
    update_fn_raw = make_sgd_update_fn(quad_loss, opt)

    def logging_update(params, ustate, batch, key):
        # updates are returned THROUGH metrics (vmap-safe; appending from
        # inside the traced fn would capture tracers).
        delta, new_state, m = update_fn_raw(params, ustate, batch, key)
        return delta, new_state, dict(m, delta=delta)

    cfg = StalenessConfig(num_workers=P, delay=UniformDelay(s))
    params = {"w": jnp.zeros((4,))}
    state = init_sim_state(params, opt.init(params), cfg, jax.random.PRNGKey(seed))
    step = jax.jit(make_sim_step(logging_update, cfg))

    key = jax.random.PRNGKey(seed + 1)
    deltas_log = []
    for batch, key in gen_batches(key, P, 5, W_TRUE):
        state, metrics = step(state, batch)
        deltas_log.append(metrics["delta"])

    drained = drain(state)
    total = sum(np.asarray(d["w"]).sum(axis=0) for d in deltas_log)
    for p in range(P):
        np.testing.assert_allclose(
            np.asarray(drained.caches["w"][p]), total, rtol=1e-4, atol=1e-5)
    # all caches identical after drain
    spread = np.asarray(drained.caches["w"]).max(0) - np.asarray(drained.caches["w"]).min(0)
    assert np.abs(spread).max() < 1e-5


def test_s0_p1_equals_sequential():
    """s=0, one worker == sequential SGD exactly (paper Section 3)."""
    update_fn, cfg, state = make_setup(1, 0)
    step = jax.jit(make_sim_step(update_fn, cfg))
    key = jax.random.PRNGKey(7)
    batches = list(gen_batches(key, 1, 12, W_TRUE))

    for batch, _ in batches:
        state, _ = step(state, batch)
    engine_w = drain(state).caches["w"][0]

    opt = sgd(0.05)
    xs, ust = {"w": jnp.zeros((4,))}, opt.init({"w": jnp.zeros((4,))})
    ufn = make_sgd_update_fn(quad_loss, opt)
    for batch, _ in batches:
        u, ust, _ = ufn(xs, ust, (batch[0][0], batch[1][0]), jax.random.PRNGKey(0))
        xs = tm.tree_add(xs, u)
    np.testing.assert_allclose(np.asarray(engine_w), np.asarray(xs["w"]),
                               rtol=1e-6, atol=1e-7)


def test_staleness_bound_respected():
    """With ConstantDelay(d) every update lands exactly d+1 steps later:
    after t steps, a worker cache reflects exactly the first t-d-1 updates."""
    d = 3
    update_fn, cfg, state = make_setup(2, 0, delay=ConstantDelay(d))
    # use constant updates of 1.0 to count arrivals
    def unit_update(params, ustate, batch, key):
        return {"w": jnp.ones((4,))}, ustate, {}
    cfg = StalenessConfig(num_workers=2, delay=ConstantDelay(d))
    params = {"w": jnp.zeros((4,))}
    state = init_sim_state(params, (), cfg, jax.random.PRNGKey(0))
    step = make_sim_step(unit_update, cfg)
    t_steps = 10
    batch = jnp.zeros((2, 1))
    for t in range(t_steps):
        state, _ = step(state, batch)
    # updates generated at steps 0..9; update from step t arrives at t+1+d.
    # after 10 steps we have applied those with t+1+d <= 10 => t <= 6: 7 steps
    # x 2 workers x 1.0 each.
    expected = 2.0 * max(t_steps - d - 1 + 0, 0)
    np.testing.assert_allclose(np.asarray(state.caches["w"][0]),
                               np.full(4, expected))


def test_convergence_under_staleness():
    """C1 sanity: the engine still converges at moderate staleness."""
    update_fn, cfg, state = make_setup(4, 8)
    step = jax.jit(make_sim_step(update_fn, cfg))
    key = jax.random.PRNGKey(3)
    for batch, key in gen_batches(key, 4, 300, W_TRUE):
        state, m = step(state, batch)
    np.testing.assert_allclose(np.asarray(state.caches["w"][0]),
                               np.asarray(W_TRUE), atol=0.05)


def test_worker_adapt_adam_state_is_local():
    """Per-worker Adam moments stay worker-local (update_state leading dim P)."""
    update_fn, cfg, state = make_setup(3, 4, opt=adam(1e-3))
    step = jax.jit(make_sim_step(update_fn, cfg))
    key = jax.random.PRNGKey(5)
    for batch, key in gen_batches(key, 3, 5, W_TRUE):
        state, _ = step(state, batch)
    assert state.update_state["m"]["w"].shape == (3, 4)
    # different workers saw different data => different moments
    m = np.asarray(state.update_state["m"]["w"])
    assert not np.allclose(m[0], m[1])


def test_server_side_apply():
    """Server-side optimizer transform (ablation mode) runs and converges."""
    opt = sgd(1.0)  # worker emits raw (negative) gradients, server scales

    def grad_update(params, ustate, batch, key):
        g = jax.grad(quad_loss)(params, batch)
        return tm.tree_scale(g, -1.0), ustate, {}

    def server_apply(cache, srv_state, arrived):
        # server applies the learning rate at delivery
        return tm.tree_axpy(0.05, arrived, cache), srv_state

    cfg = StalenessConfig(num_workers=2, delay=UniformDelay(3), server_side=True)
    params = {"w": jnp.zeros((4,))}
    state = init_sim_state(params, (), cfg, jax.random.PRNGKey(0),
                           server_state={"dummy": jnp.zeros(())})
    step = jax.jit(make_sim_step(grad_update, cfg, server_apply=server_apply))
    key = jax.random.PRNGKey(9)
    for batch, key in gen_batches(key, 2, 250, W_TRUE):
        state, _ = step(state, batch)
    np.testing.assert_allclose(np.asarray(state.caches["w"][0]),
                               np.asarray(W_TRUE), atol=0.05)

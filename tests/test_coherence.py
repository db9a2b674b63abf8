"""Coherence monitor vs numpy oracle + Theorem-1 stepsize behavior."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import coherence as coh


def np_mu(history, g, count, head):
    """Oracle for Definition 1 over the valid window."""
    window = history.shape[0]
    vals = []
    for slot in range(window):
        lag = (head - 1 - slot) % window + 1
        if lag <= min(count, window):
            vals.append(history[slot] @ g / max(g @ g, 1e-30))
    return min(vals) if vals else 1.0


@given(seed=st.integers(0, 500), window=st.integers(1, 6), n=st.integers(1, 12))
@settings(max_examples=20, deadline=None)
def test_observe_matches_oracle(seed, window, n):
    rng = np.random.default_rng(seed)
    dim = 16
    state = coh.init_coherence(dim, window)
    gs = rng.standard_normal((n, dim)).astype(np.float32)
    for i in range(n):
        hist = np.asarray(state.history).copy()
        count, head = int(state.count), int(state.head)
        state, out = coh.observe(state, jnp.asarray(gs[i]))
        expect = np_mu(hist, gs[i], count, head)
        np.testing.assert_allclose(float(out["mu"]), expect, rtol=1e-4, atol=1e-5)


def test_identical_gradients_have_mu_one():
    state = coh.init_coherence(8, 4)
    g = jnp.ones((8,))
    for _ in range(6):
        state, out = coh.observe(state, g)
    np.testing.assert_allclose(float(out["mu"]), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["cos_by_lag"]), 1.0, rtol=1e-5)


def test_opposed_gradient_negative_mu():
    state = coh.init_coherence(8, 4)
    state, _ = coh.observe(state, jnp.ones((8,)))
    state, out = coh.observe(state, -jnp.ones((8,)))
    assert float(out["mu"]) < 0


def test_theorem1_stepsize_decays():
    s, L = 8, 2.0
    etas = [float(coh.theorem1_stepsize(jnp.float32(0.5), s, jnp.float32(L),
                                        jnp.float32(k))) for k in [1, 4, 16, 64]]
    assert etas == sorted(etas, reverse=True)
    np.testing.assert_allclose(etas[0] / etas[2], 4.0, rtol=1e-5)  # 1/sqrt(k)


def test_controller_shrinks_and_relaxes():
    ctl = coh.CoherenceController(s_max=16, lo=0.0, hi=0.25, patience=3)
    st_c = ctl.init()
    st_c = ctl.step(st_c, jnp.float32(-0.5))
    assert int(st_c["allowed_s"]) == 8
    st_c = ctl.step(st_c, jnp.float32(-0.5))
    assert int(st_c["allowed_s"]) == 4
    for _ in range(3):
        st_c = ctl.step(st_c, jnp.float32(0.9))
    assert int(st_c["allowed_s"]) == 5  # relaxed one notch after patience


def test_secant_lipschitz_quadratic():
    """For f = 0.5 c x^2, L = c exactly; the secant estimate finds it."""
    c = 3.0
    st_l = coh.init_secant(4)
    x = jnp.ones((4,))
    for i in range(5):
        g = c * x
        st_l = coh.update_secant(st_l, x, g)
        x = x - 0.1 * g
    np.testing.assert_allclose(float(st_l.l_hat), c, rtol=0.2)

"""Property tests for the delay models (paper Section 3 / Appendix A.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ssp as ssp_lib
from repro.core.delay import (ConstantDelay, GeometricDelay, UniformDelay,
                              matched_geometric)


@given(s=st.integers(0, 40), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_uniform_delay_bounds(s, seed):
    model = UniformDelay(s)
    draws = model.sample(jax.random.PRNGKey(seed), (16, 16))
    assert draws.dtype == jnp.int32
    assert int(draws.min()) >= 0
    assert int(draws.max()) <= model.bound
    assert model.bound == max(s - 1, 0)


def test_uniform_delay_is_uniform():
    model = UniformDelay(8)
    draws = np.asarray(model.sample(jax.random.PRNGKey(0), (4000,)))
    counts = np.bincount(draws, minlength=8)
    # each bin ~500; loose chi-square-ish bound
    assert counts.min() > 350 and counts.max() < 650


def test_uniform_mean_total_delay_matches_paper():
    # paper: average delay = s/2 + 1 (approximately, for the categorical model)
    model = UniformDelay(20)
    draws = np.asarray(model.sample(jax.random.PRNGKey(1), (100_000,)))
    assert abs((draws.mean() + 1) - (20 / 2 + 1)) < 0.6


@given(v=st.integers(0, 12))
@settings(max_examples=10, deadline=None)
def test_constant_delay(v):
    model = ConstantDelay(v)
    draws = model.sample(jax.random.PRNGKey(0), (8,))
    assert (np.asarray(draws) == v).all()


def test_geometric_truncated_and_straggler():
    model = GeometricDelay(p_normal=0.5, p_straggler=0.05, trunc=31)
    draws = np.asarray(model.sample(jax.random.PRNGKey(2), (8, 8)))
    assert draws.min() >= 0 and draws.max() <= 31
    # one source row (the straggler) should have a clearly larger mean
    row_means = draws.mean(axis=1)
    assert row_means.max() > 2 * np.median(row_means)


def test_matched_geometric_mean():
    s, p = 16, 8
    model = matched_geometric(s, p)
    keys = jax.random.split(jax.random.PRNGKey(3), 400)
    draws = np.asarray(jax.vmap(lambda k: model.sample(k, (p, p)))(keys))
    target = (s - 1) / 2
    assert abs(draws.mean() - target) < 1.0, (draws.mean(), target)


@given(trunc=st.integers(1, 48), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_geometric_never_exceeds_bound(trunc, seed):
    """The truncation bound IS the model's bound — the delivery ring is
    sized from it, so a single draw above it would corrupt a slot."""
    model = GeometricDelay(p_normal=0.3, p_straggler=0.05, trunc=trunc)
    draws = model.sample(jax.random.PRNGKey(seed), (6, 6))
    assert model.bound == trunc
    assert int(draws.min()) >= 0
    assert int(draws.max()) <= model.bound


@given(s=st.integers(2, 24), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_uniform_delay_distribution_stable_under_reseed(s, seed):
    """Same key -> bitwise-identical draws; a fresh key keeps the
    distribution (mean within sampling noise of (s-1)/2, full support)."""
    model = UniformDelay(s)
    key = jax.random.PRNGKey(seed)
    a = np.asarray(model.sample(key, (2048,)))
    b = np.asarray(model.sample(key, (2048,)))
    np.testing.assert_array_equal(a, b)

    c = np.asarray(model.sample(jax.random.PRNGKey(seed + 1), (2048,)))
    target = (s - 1) / 2.0
    # mean of 2048 uniform draws over width s: sd = s/sqrt(12*2048) < 0.21*s
    tol = 0.25 * s / np.sqrt(12) + 0.2
    assert abs(a.mean() - target) < tol, (s, seed, a.mean())
    assert abs(c.mean() - target) < tol, (s, seed, c.mean())
    assert set(np.unique(c)) <= set(range(s))


@given(bound=st.integers(0, 6), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_ssp_delay_schedule_respects_clock_semantics(bound, seed):
    """The SSP schedule is a clock discipline, not a sampler: staleness is
    (a) within [0, bound] — no worker reads state more than ``bound`` clocks
    behind; (b) bounded by the clock index — you cannot be staler than the
    history that exists; (c) identically zero at bound 0 (BSP)."""
    T, P = 24, 4
    speeds = ssp_lib.sample_worker_durations(
        jax.random.PRNGKey(seed), T, P, mean_dur=1.0, cv=0.8)
    sched = np.asarray(ssp_lib.ssp_delay_schedule(
        ssp_lib.SSPConfig(num_workers=P, bound=bound), speeds))
    assert sched.shape == (T, P)
    assert sched.dtype == np.int32
    assert sched.min() >= 0 and sched.max() <= bound
    clocks = np.arange(T)[:, None]
    assert (sched <= clocks).all(), "staleness exceeds available history"
    if bound == 0:
        assert (sched == 0).all()


def test_ssp_schedule_lockstep_workers_are_synchronous():
    """Identical constant speeds -> workers advance in lockstep, so the
    effective read staleness stays 0 regardless of the allowed bound."""
    T, P = 16, 4
    speeds = jnp.ones((T, P))
    sched = np.asarray(ssp_lib.ssp_delay_schedule(
        ssp_lib.SSPConfig(num_workers=P, bound=5), speeds))
    assert (sched == 0).all()

"""Property tests for the unified delay subsystem (``repro.delays``).

Every DelaySpec must respect its declared ``bound`` (the delivery ring is
sized from it — one draw above it corrupts a slot), be deterministic under a
fixed key, and ``Trace`` must round-trip record → replay exactly. The moved
sampler models must match the ``repro.core.delay`` legacy surface bitwise.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import delays
from repro.core import ssp as ssp_lib


def spec_zoo(s: int, p: int, seed: int):
    """One instance of every DelaySpec family, sized to bound <= some s."""
    rng = np.random.default_rng(seed)
    table = rng.integers(0, max(s, 1), size=(6, p))
    return [
        delays.Uniform(s),
        delays.Constant(max(s - 1, 0)),
        delays.Zero(),
        delays.matched_geometric(max(s, 2), p, trunc=max(s, 1)),
        delays.Schedule(table),
        delays.MultiPod(pod_of=delays.pods_of(p, 2),
                        intra=delays.Uniform(1),
                        inter=delays.Uniform(max(s, 1))),
    ]


SHAPES = ((), "p", "pp")  # aggregate, per-worker, simulate matrix


def _shape(tag, p):
    return {(): (), "p": (p,), "pp": (p, p)}[tag]


@given(s=st.integers(0, 12), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_every_spec_respects_declared_bound(s, seed):
    p = 4
    for spec in spec_zoo(s, p, seed):
        src = spec.realize(num_workers=p)
        assert src.bound == spec.bound, spec
        for tag in SHAPES:
            if tag == () and isinstance(spec, (delays.MultiPod,
                                               delays.Schedule)):
                continue  # no aggregate form (topology / [T, P] table)
            for step in (0, 3, 17):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                d = src.delays(key, jnp.int32(step), _shape(tag, p))
                d = np.asarray(d)
                assert d.shape == _shape(tag, p), (spec, tag)
                assert d.dtype == np.int32, (spec, tag)
                assert d.min() >= 0, (spec, tag, step)
                assert d.max() <= spec.bound, (spec, tag, step, d.max())


@given(s=st.integers(1, 10), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_every_spec_deterministic_under_fixed_key(s, seed):
    p = 4
    key = jax.random.PRNGKey(seed)
    for spec in spec_zoo(s, p, seed):
        a = np.asarray(spec.realize(num_workers=p).delays(key, 2, (p, p)))
        b = np.asarray(spec.realize(num_workers=p).delays(key, 2, (p, p)))
        np.testing.assert_array_equal(a, b, err_msg=repr(spec))


def test_sampler_source_matches_legacy_sample_bitwise():
    """spec.realize().delays(key, step, shape) == spec.sample(key, shape)
    for the stateless samplers — the engine hands the same per-step key
    either way, so spec-driven engines replay legacy trajectories."""
    p = 5
    key = jax.random.PRNGKey(3)
    for spec in (delays.Uniform(7), delays.Constant(3), delays.Zero(),
                 delays.matched_geometric(8, p)):
        src = spec.realize(num_workers=p)
        for shape in ((), (p,), (p, p)):
            np.testing.assert_array_equal(
                np.asarray(src.delays(key, 11, shape)),
                np.asarray(spec.sample(key, shape)))


def test_moved_models_are_the_legacy_classes():
    """repro.core.delay re-exports the SAME objects (not copies): sampling
    through either import path is bitwise-identical by construction."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core import delay as legacy
    assert legacy.UniformDelay is delays.Uniform
    assert legacy.ConstantDelay is delays.Constant
    assert legacy.GeometricDelay is delays.Geometric
    assert legacy.matched_geometric is delays.matched_geometric
    assert legacy.DelayModel is delays.DelayModel
    key = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(
        np.asarray(legacy.UniformDelay(9).sample(key, (8, 8))),
        np.asarray(delays.Uniform(9).sample(key, (8, 8))))


# -- Schedule ----------------------------------------------------------------

def test_schedule_shapes_and_wrap():
    table = np.array([[0, 1], [2, 0], [1, 1]], np.int32)   # [T=3, P=2]
    spec = delays.Schedule(table)
    assert spec.bound == 2
    src = spec.realize(num_workers=2)
    key = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(np.asarray(src.delays(key, 1, (2,))),
                                  table[1])
    # wraps at step T (mod semantics, like the legacy delay_table)
    np.testing.assert_array_equal(np.asarray(src.delays(key, 4, (2,))),
                                  table[1])
    # simulate matrix: source rows broadcast over destinations
    np.testing.assert_array_equal(np.asarray(src.delays(key, 0, (2, 2))),
                                  np.broadcast_to(table[0][:, None], (2, 2)))
    # [T] tables serve the aggregate form; [T, P] tables refuse it
    agg = delays.Schedule(np.array([3, 0, 1])).realize()
    assert int(agg.delays(key, 0, ())) == 3
    with pytest.raises(ValueError, match=r"\[T\]"):
        src.delays(key, 0, ())


def test_schedule_validates_workers_and_values():
    with pytest.raises(ValueError, match="workers"):
        delays.Schedule(np.zeros((4, 3), np.int32)).realize(num_workers=2)
    with pytest.raises(ValueError, match="negative"):
        delays.Schedule(np.array([[-1, 0]]))
    with pytest.raises(ValueError, match="non-empty"):
        delays.Schedule(np.zeros((0,), np.int32))


# -- Trace -------------------------------------------------------------------

def test_trace_roundtrips_record_replay_exactly(tmp_path):
    """record -> read recovers the durations exactly (JSON floats round-trip)
    and two independent replays realize bitwise-identical schedules."""
    path = str(tmp_path / "trace.jsonl")
    rng = np.random.default_rng(0)
    durations = rng.lognormal(0.0, 0.5, size=(12, 3))
    delays.record_trace(path, durations, meta={"src": "test"})
    back, header = delays.read_trace(path)
    np.testing.assert_array_equal(back, durations)
    assert header["num_workers"] == 3 and header["src"] == "test"

    t1 = np.asarray(delays.Trace(path, bound=4).schedule().table)
    t2 = np.asarray(delays.Trace(path, bound=4).schedule().table)
    np.testing.assert_array_equal(t1, t2)
    # ...and the replay IS the SSP clock discipline over the recording
    ref = np.asarray(ssp_lib.ssp_delay_schedule(
        ssp_lib.SSPConfig(num_workers=3, bound=4),
        jnp.asarray(durations, jnp.float32)))
    np.testing.assert_array_equal(t1, ref)


def test_trace_respects_bound_and_broadcast(tmp_path):
    path = str(tmp_path / "t1.jsonl")
    rng = np.random.default_rng(1)
    delays.record_trace(path, rng.lognormal(0.0, 0.8, size=(10,)))  # 1 worker
    spec = delays.Trace(path, bound=3)
    src = spec.realize(num_workers=4)     # single-worker trace broadcasts
    d = np.asarray(src.delays(jax.random.PRNGKey(0), 5, (4,)))
    assert d.shape == (4,)
    assert d.min() >= 0 and d.max() <= 3
    with pytest.raises(ValueError, match="bound"):
        delays.Trace(path).schedule()     # bound required outside ssp mode


def test_trace_recorder_hook_writes_replayable_trace(tmp_path):
    """A live Trainer run records a trace the Trace spec replays."""
    from repro.engine import (EngineConfig, TraceRecorderHook, Trainer,
                              build_engine)
    from repro.optim import sgd

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    path = str(tmp_path / "run.jsonl")
    eng = build_engine(loss, sgd(0.05),
                       EngineConfig(mode="sync", num_workers=2))
    st = eng.init(jax.random.PRNGKey(0), params={"w": jnp.zeros((4,))})
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    batches = [(x, x @ jnp.ones(4))] * 4
    Trainer(eng, hooks=[TraceRecorderHook(path)]).run(
        iter(batches), 4, state=st)
    durations, header = delays.read_trace(path)
    assert durations.shape == (4, 2)
    assert (durations > 0).all()
    sched = delays.Trace(path, bound=2).schedule(num_workers=2)
    assert sched.bound <= 2


# -- MultiPod ----------------------------------------------------------------

def test_multipod_composes_intra_plus_inter():
    """Cross-pod delays are intra + inter; same-pod pairs see intra alone;
    bound composes additively."""
    spec = delays.MultiPod(pod_of=(0, 0, 1, 1),
                           intra=delays.Constant(1),
                           inter=delays.Constant(3))
    assert spec.bound == 4
    src = spec.realize(num_workers=4)
    d = np.asarray(src.delays(jax.random.PRNGKey(0), 0, (4, 4)))
    pods = np.array([0, 0, 1, 1])
    cross = pods[:, None] != pods[None, :]
    np.testing.assert_array_equal(d, np.where(cross, 4, 1))
    # per-worker form: pods other than server_pod pay the inter hop
    dp = np.asarray(src.delays(jax.random.PRNGKey(0), 0, (4,)))
    np.testing.assert_array_equal(dp, np.where(pods != 0, 4, 1))


def test_multipod_rejects_aggregate_and_bad_worker_count():
    spec = delays.MultiPod(pod_of=(0, 1), intra=delays.Zero(),
                           inter=delays.Uniform(2))
    with pytest.raises(ValueError, match="aggregate"):
        spec.realize(num_workers=2).delays(jax.random.PRNGKey(0), 0, ())
    with pytest.raises(ValueError, match="workers"):
        spec.realize(num_workers=3)
    with pytest.raises(ValueError, match="evenly"):
        delays.pods_of(5, 2)


# -- trainer realized-vs-nominal ---------------------------------------------

def test_trainer_realized_delay_unbiased_vs_log_interval():
    """``mean_total_delay`` accumulates over EVERY step, not only logged
    rows: a schedule whose delays differ exactly on log-interval steps must
    not bias the realized-vs-nominal check (pre-PR 5 the accumulator only
    saw log rows and would report 4.0 here instead of 1.75)."""
    from repro.engine import EngineConfig, Trainer, build_engine
    from repro.optim import sgd

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    p = 2
    # Delay 3 exactly on the logged steps (t = 3, 7 at log_every=4), 0
    # elsewhere: mean over ALL 8 steps is 6/8 = 0.75.
    table = np.array([[0, 0], [0, 0], [0, 0], [3, 3]], np.int32)
    eng = build_engine(loss, sgd(0.05), EngineConfig(
        mode="stale-psum", num_workers=p, s=4,
        delay=delays.Schedule(table)))
    st = eng.init(jax.random.PRNGKey(0), params={"w": jnp.zeros((4,))})
    x = jax.random.normal(jax.random.PRNGKey(1), (p * 8, 4))
    res = Trainer(eng).run(iter([(x, x @ jnp.ones(4))] * 8), 8,
                           state=st, log_every=4)
    assert res.history[-1]["mean_total_delay"] == pytest.approx(1.75)
    # ...and the per-row mean_staleness still reflects THAT step's draw.
    assert res.history[-1]["mean_staleness"] == pytest.approx(3.0)


# -- CLI grammar -------------------------------------------------------------

def test_parse_spec_grammar():
    assert delays.parse_spec("uniform", s=6) == delays.Uniform(6)
    assert delays.parse_spec("uniform:3", s=6) == delays.Uniform(3)
    assert delays.parse_spec("zero") == delays.Zero()
    assert delays.parse_spec("constant:2") == delays.Constant(2)
    geo = delays.parse_spec("geometric", s=8, num_workers=4)
    assert isinstance(geo, delays.Geometric) and geo.bound == 7
    mp = delays.parse_spec("multipod:2", s=8, num_workers=4)
    assert isinstance(mp, delays.MultiPod)
    assert mp.pod_of == (0, 0, 1, 1) and mp.bound == 7
    tr = delays.parse_spec("trace:/tmp/x.jsonl:5")
    assert tr == delays.Trace("/tmp/x.jsonl", bound=5)
    with pytest.raises(ValueError, match="grammar"):
        delays.parse_spec("nonsense")
    with pytest.raises(ValueError, match="bad delay spec"):
        delays.parse_spec("constant:notanint")


def test_parse_spec_trace_paths_with_colons():
    """The bound splits off the RIGHT and only when the last segment is an
    integer — Windows drive letters and URLs stay part of the path
    (pre-PR 5 any colon in the path made the spec unparseable)."""
    assert (delays.parse_spec(r"trace:C:\runs\t.jsonl:8")
            == delays.Trace(r"C:\runs\t.jsonl", bound=8))
    assert (delays.parse_spec(r"trace:C:\runs\t.jsonl", s=4)
            == delays.Trace(r"C:\runs\t.jsonl", bound=4))
    assert (delays.parse_spec("trace:http://host:8080/t.jsonl", s=2)
            == delays.Trace("http://host:8080/t.jsonl", bound=2))
    assert (delays.parse_spec("trace:/tmp/x.jsonl")
            == delays.Trace("/tmp/x.jsonl", bound=None))
    with pytest.raises(ValueError, match="path"):
        delays.parse_spec("trace:")
    with pytest.raises(ValueError, match="path"):
        delays.parse_spec("trace::5")


def test_parse_spec_round_trip_matrix():
    """Every spec kind x edge args x s=0: any staleness parameter that
    resolves to 0 parses to the explicit Zero() spec (pre-PR 5 `geometric`
    at s=0 still emitted delays up to trunc=1, and multipod's inter_s=0
    became UniformDelay(0) while intra_s=0 became Zero())."""
    cases = [
        ("uniform", dict(s=6), delays.Uniform(6)),
        ("uniform:3", dict(s=0), delays.Uniform(3)),
        ("uniform:0", dict(s=6), delays.Zero()),
        ("uniform", dict(s=0), delays.Zero()),
        ("zero", dict(s=9), delays.Zero()),
        ("constant:0", {}, delays.Constant(0)),   # an explicit VALUE, kept
        ("constant:7", {}, delays.Constant(7)),
        ("geometric", dict(s=0, num_workers=4), delays.Zero()),
        ("geometric:5", dict(s=0, num_workers=4), delays.Zero()),
        ("trace:/tmp/x.jsonl:5", {}, delays.Trace("/tmp/x.jsonl", bound=5)),
    ]
    for text, kw, want in cases:
        assert delays.parse_spec(text, **kw) == want, text
    geo = delays.parse_spec("geometric:5", s=8, num_workers=4)
    assert isinstance(geo, delays.Geometric) and geo.bound == 5
    mp = delays.parse_spec("multipod:2:0:0", num_workers=4)
    assert mp.intra == delays.Zero() and mp.inter == delays.Zero()
    mp = delays.parse_spec("multipod:2:4", num_workers=4)
    assert mp.inter == delays.Uniform(4) and mp.intra == delays.Zero()
    mp = delays.parse_spec("multipod:2:4:2", num_workers=4)
    assert mp.inter == delays.Uniform(4) and mp.intra == delays.Uniform(2)
    mp = delays.parse_spec("multipod:2", s=0, num_workers=4)
    assert mp.inter == delays.Zero() and mp.bound == 0
    # every parsed sampler realizes and respects its declared bound
    for text, kw, _ in cases:
        if text.startswith("trace"):
            continue
        spec = delays.parse_spec(text, **kw)
        src = spec.realize(num_workers=kw.get("num_workers", 1))
        d = np.asarray(src.delays(jax.random.PRNGKey(0), 0,
                                  (kw.get("num_workers", 1),)))
        assert d.min() >= 0 and d.max() <= spec.bound, text

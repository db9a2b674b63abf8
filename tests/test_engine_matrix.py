"""Engine smoke matrix: modes x archs x meshes through the sharding planner.

Every combination of the four staleness regimes, three model families, and
{1-device, 2-device} CPU meshes must produce finite losses and replay
deterministically from a fixed seed through the engine-planned sharded step
(``repro/engine/plan.py``). One arch is additionally checked BITWISE against
the legacy ``launch/steps.py`` construction (hand-built on
``core/stale_sync``, as the pre-fold code did) — the planner is a surface
refactor, not a numerics change.

The 2-device leg runs in a subprocess: jax locks the host device count at
first init and the main pytest process must keep 1 device for the smoke
tests (same pattern as test_distributed_integration.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro import delays
from repro.configs.base import InputShape
from repro.core import stale_sync
from repro.engine import plan as planlib
from repro.launch import mesh as meshlib
from repro.optim import optimizers as optlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODES = ("sync", "stale-psum", "ssp", "simulate")
ARCHS = ("deepseek-7b", "mamba2-1.3b", "whisper-base")  # 3 model families
SHAPE = InputShape("matrix_train", seq_len=16, global_batch=4, kind="train")


def make_batch(spec, key):
    """Deterministic batch matching a plan's batch struct (tokens stay in
    [0, 16) — valid for every arch's vocabulary)."""
    out = {}
    for i, name in enumerate(sorted(spec)):
        s = spec[name]
        k = jax.random.fold_in(key, i)
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(k, s.shape, 0, 16)
        else:
            out[name] = jax.random.normal(k, s.shape, s.dtype)
    return out


def make_engine(arch_id, mode, mesh, kernels="off", **kw):
    return planlib.make_train_engine(
        arch_id, SHAPE, mesh, mode=mode, stale_s=2, num_workers=2,
        reduced=True, ssp_steps=8, kernels=kernels, **kw)


MULTIPOD = delays.MultiPod(pod_of=(0, 1), intra=delays.Zero(),
                           inter=delays.Uniform(2))


def run_combo(engine, steps=2, seed=0):
    state = engine.init(jax.random.PRNGKey(seed))
    spec = engine.plan().args[1]
    losses = []
    for t in range(steps):
        batch = make_batch(spec, jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), t))
        state, metrics = engine.step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def _rel_l2_close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    rel = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
    assert rel <= tol, f"relative L2 {rel:.3g} > {tol} over {b.shape}"


def check_legacy_equivalence(mesh, arch_id="deepseek-7b", steps=5):
    """Engine-planned step == the pre-fold launch/steps.py path: bitwise on
    one device. On a mesh of several the sharded gradient mean reduces in
    another order; Adam normalises each coordinate's step, so where a
    gradient coordinate is near zero that fp32 noise can move that one
    parameter by a few percent of a step (lr = 1e-3). There each leaf must
    agree to 1e-5 in relative L2 norm: the noise measures under 4e-6, while
    one parameter off by a whole step reads 5e-5 or more on every leaf."""
    P, s = 2, 3
    arch = cfglib.get(arch_id)
    api = arch.api(reduced=True)
    opt = optlib.get_optimizer(arch.train_optimizer)
    key = jax.random.PRNGKey(0)
    params = api.init(key)[0]

    scfg = stale_sync.StaleSyncConfig(
        num_workers=P, s=s,
        buffer_dtype=getattr(api.cfg, "param_dtype", jnp.float32))
    legacy_step = jax.jit(stale_sync.make_stale_train_step(api.loss, opt, scfg))
    legacy = stale_sync.init_state(params, opt, scfg, key)

    engine = planlib.make_train_engine(
        arch, SHAPE, mesh, mode="stale-psum", stale_s=s, num_workers=P,
        reduced=True)
    state = engine.init(key)
    spec = engine.plan().args[1]

    for t in range(steps):
        batch = make_batch(spec, jax.random.fold_in(jax.random.PRNGKey(1), t))
        legacy, lm = legacy_step(legacy, batch)
        state, em = engine.step(state, batch)
        np.testing.assert_array_equal(np.asarray(lm["mean_staleness"]),
                                      np.asarray(em["mean_staleness"]))
    same = (np.testing.assert_array_equal if mesh.devices.size == 1
            else _rel_l2_close)
    for a, b in zip(jax.tree.leaves(legacy.params),
                    jax.tree.leaves(state.inner.params)):
        same(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(legacy.gbuf),
                    jax.tree.leaves(state.inner.gbuf)):
        same(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch_id", ARCHS)
@pytest.mark.parametrize("mode", MODES)
def test_matrix_single_device(mode, arch_id):
    """Finite losses + bitwise-deterministic replay on the 1-device mesh."""
    mesh = meshlib.make_host_mesh(1, 1)
    engine = make_engine(arch_id, mode, mesh)
    state1, losses1 = run_combo(engine)
    assert all(np.isfinite(l) for l in losses1), (mode, arch_id, losses1)
    state2, losses2 = run_combo(engine)
    assert losses1 == losses2, (mode, arch_id)
    for a, b in zip(jax.tree.leaves(engine.params(state1)),
                    jax.tree.leaves(engine.params(state2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_plan_matches_legacy_steps_path():
    check_legacy_equivalence(meshlib.make_host_mesh(1, 1))


@pytest.mark.parametrize("legacy_kw", [
    {"delay": delays.UniformDelay(2)},
    {"delay": delays.GeometricDelay(p_normal=0.5, trunc=2)},
    {"delay_table": np.array([[0, 1], [2, 0], [1, 2], [0, 0]], np.int32)},
], ids=["delay=uniform", "delay=geometric", "delay_table"])
def test_engine_delay_spec_matches_legacy_stale_sync(legacy_kw):
    """EngineConfig(delay=spec) reproduces the legacy
    StaleSyncConfig(delay=/delay_table=) trajectories BITWISE under
    kernels="off" — the delays refactor is a surface move, not a numerics
    change."""
    from repro.engine.api import EngineConfig, build_engine
    from repro.optim import sgd

    P, s = 2, 3
    opt = sgd(0.05)

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.zeros((4,))}
    key = jax.random.PRNGKey(0)
    scfg = stale_sync.StaleSyncConfig(num_workers=P, s=s, **legacy_kw)
    legacy_step = jax.jit(stale_sync.make_stale_train_step(loss, opt, scfg))
    legacy = stale_sync.init_state(params, opt, scfg, key)

    spec = legacy_kw.get("delay")
    if spec is None:
        spec = delays.Schedule(legacy_kw["delay_table"])
    eng = build_engine(loss, opt, EngineConfig(
        mode="stale-psum", num_workers=P, s=s, delay=spec))
    st = eng.init(key, params=params)

    for t in range(6):
        kb = jax.random.fold_in(jax.random.PRNGKey(1), t)
        x = jax.random.normal(kb, (P * 8, 4))
        batch = (x, x @ jnp.arange(4.0))
        legacy, lm = legacy_step(legacy, batch)
        st, em = eng.step(st, batch)
        np.testing.assert_array_equal(np.asarray(lm["mean_staleness"]),
                                      np.asarray(em["mean_staleness"]))
    np.testing.assert_array_equal(np.asarray(legacy.params["w"]),
                                  np.asarray(st.inner.params["w"]))
    for a, b in zip(jax.tree.leaves(legacy.gbuf),
                    jax.tree.leaves(st.inner.gbuf)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_all_modes_accept_delay_spec():
    """EngineConfig(delay=...) is honored uniformly: MultiPod in the
    sampled modes, a Schedule table in ssp, Zero in sync."""
    mesh = meshlib.make_host_mesh(1, 1)
    table = np.array([[0, 1], [1, 0], [2, 2], [0, 1]], np.int32)
    spec_for = {"simulate": MULTIPOD, "stale-psum": MULTIPOD,
                "ssp": delays.Schedule(table), "sync": delays.Zero()}
    for mode in MODES:
        engine = make_engine("mamba2-1.3b", mode, mesh,
                             delay=spec_for[mode])
        state, losses = run_combo(engine)
        assert all(np.isfinite(l) for l in losses), (mode, losses)
        _, replay = run_combo(engine)
        assert losses == replay, mode
    # the schedule IS the ssp table: effective staleness matches it
    eng = make_engine("mamba2-1.3b", "ssp", mesh,
                      delay=delays.Schedule(table))
    np.testing.assert_array_equal(np.asarray(eng.meta["ssp_schedule"]), table)


@pytest.mark.parametrize("arch_id", ARCHS)
@pytest.mark.parametrize("mode", MODES)
def test_matrix_kernels_on_matches_off(mode, arch_id):
    """kernels="on" (packed ring + fused delivery/Adam + donated planned
    step) tracks the bitwise-legacy kernels="off" path within fp32 tolerance
    on every mode x arch combination — including the simulate-mode packed
    [P, slots, D] pending ring (PR 4)."""
    mesh = meshlib.make_host_mesh(1, 1)
    e_off = make_engine(arch_id, mode, mesh)
    e_on = make_engine(arch_id, mode, mesh, kernels="on")
    if mode in ("stale-psum", "ssp", "simulate"):
        assert e_on.meta["kernels"]["delivery"] == "packed"
        assert e_on.plan().donate_argnums == (0,)
    s_off, l_off = run_combo(e_off)
    s_on, l_on = run_combo(e_on)
    np.testing.assert_allclose(l_off, l_on, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(e_off.params(s_off)),
                    jax.tree.leaves(e_on.params(s_on))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_matrix_compensation_modes():
    """repro.compensate rows across all four modes: the explicit
    compress="none", lr_scale="none" engine is BITWISE-identical to the
    default (PR 4) construction — the compensation layer must be absent,
    not merely inert, when switched off — and every active knob combination
    stays finite and replays deterministically."""
    mesh = meshlib.make_host_mesh(1, 1)
    for mode in MODES:
        base = make_engine("mamba2-1.3b", mode, mesh)
        none = make_engine("mamba2-1.3b", mode, mesh,
                           compress="none", lr_scale="none")
        s_base, l_base = run_combo(base)
        s_none, l_none = run_combo(none)
        assert l_base == l_none, mode
        for a, b in zip(jax.tree.leaves(base.params(s_base)),
                        jax.tree.leaves(none.params(s_none))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert s_none.comp == ()   # no residual/signal leaves when off

        # One fully-active row per mode (both knobs at once); the per-knob
        # and per-policy coverage lives in test_compensate.py on cheap
        # engines.
        eng = make_engine("mamba2-1.3b", mode, mesh,
                          compress="topk:0.5", lr_scale="inverse")
        state, losses = run_combo(eng)
        assert all(np.isfinite(l) for l in losses), (mode, losses)
        _, replay = run_combo(eng)
        assert losses == replay, mode
        # Residuals live in SOURCE layout since the pre-transport compression
        # change (PR 7): sparsification runs per worker BEFORE the ring
        # write, so every mode with per-source gradients carries [P, D]
        # residuals; only sync (one aggregate stream) keeps the flat [D].
        assert state.comp["resid"].ndim == (1 if mode == "sync" else 2)


# ---------------------------------------------------------------------------
# One-pass fused-update megakernel (PR 7): the whole post-gradient tail
# (EF split -> weighted stale delivery -> Adam) as ONE dispatch.fused_update
# pass over the packed [D] view. The toy below packs to exactly one 2048
# block so the interpret-mode Pallas kernel actually executes on CPU.
# ---------------------------------------------------------------------------

def _toy_mega_engine(mode, megakernel, **kw):
    from repro.engine.api import EngineConfig, build_engine

    def loss(params, batch):
        pred = batch["x"] @ params["w"] + jnp.sum(params["b"])
        return jnp.mean(pred ** 2)

    cfg = EngineConfig(mode=mode, num_workers=2,
                       s=(0 if mode == "sync" else 2),
                       kernels="auto", megakernel=megakernel, **kw)
    eng = build_engine(loss, optlib.adam(lr=0.05, kernel=True), cfg)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (300,),
                                     jnp.float32),
              "b": jnp.full((5,), 0.1, jnp.float32)}
    return eng, params


def _run_toy(eng, params, mode, steps=5):
    state = eng.init(jax.random.PRNGKey(1), params=params)
    key, metrics = jax.random.PRNGKey(2), None
    for _ in range(steps):
        key, kb = jax.random.split(key)
        x = jax.random.normal(kb, (4, 300), jnp.float32)
        batch = ({"x": x.reshape(2, 2, 300)} if mode == "simulate"
                 else {"x": x})
        state, metrics = eng.step(state, batch)
    return state, metrics


@pytest.mark.parametrize("mode", MODES)
def test_megakernel_matches_three_dispatch(mode):
    """megakernel="on" tracks the three-dispatch kernel path it replaces
    within fp32 tolerance — dense AND with the EF compensator active (where
    the residual trajectories must agree too)."""
    for kw in ({}, dict(compress="topk:0.25", lr_scale="inverse")):
        e_off, params = _toy_mega_engine(mode, "off", **kw)
        e_on, _ = _toy_mega_engine(mode, "on", **kw)
        assert e_on.meta["kernels"]["megakernel"] == "fused"
        assert e_off.meta["kernels"]["megakernel"] == "off"
        s_off, m_off = _run_toy(e_off, params, mode)
        s_on, m_on = _run_toy(e_on, params, mode)
        np.testing.assert_allclose(float(m_off["loss"]),
                                   float(m_on["loss"]), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(e_off.params(s_off)),
                        jax.tree.leaves(e_on.params(s_on))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-4)
        for a, b in zip(jax.tree.leaves(s_off.comp),
                        jax.tree.leaves(s_on.comp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


def test_megakernel_off_compensation_none_is_bitwise_inert():
    """With megakernel="off" the kernel path is the pre-PR-7 three-dispatch
    step: an explicit compress="none"/lr_scale="none" engine is BITWISE
    identical to one built with no compensation knobs at all — the
    pre-transport compression plumbing must vanish, not merely no-op, when
    the compensator is off. (megakernel defaults to "auto", which resolves
    to "fused" on this kernel-eligible toy — pin it "off" for the PR 6
    baseline identity.)"""
    for mode in MODES:
        e_def, params = _toy_mega_engine(mode, "off")
        e_none, _ = _toy_mega_engine(mode, "off", compress="none",
                                     lr_scale="none")
        e_auto, _ = _toy_mega_engine(mode, "auto")
        assert e_auto.meta["kernels"]["megakernel"] == "fused", mode
        s_def, m_def = _run_toy(e_def, params, mode)
        s_none, m_none = _run_toy(e_none, params, mode)
        assert float(m_def["loss"]) == float(m_none["loss"]), mode
        assert s_none.comp == ()
        for a, b in zip(jax.tree.leaves(e_def.params(s_def)),
                        jax.tree.leaves(e_none.params(s_none))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_megakernel_momentum_ef_replay_deterministic(mode="stale-psum"):
    """The DGC-style momentum-corrected EF variant (ef_momentum > 0) carries
    masked momentum in EngineState.comp and replays bitwise from a fixed
    seed through the megakernel."""
    for mode in MODES:
        e1, params = _toy_mega_engine(mode, "on", compress="topk:0.25",
                                      ef_momentum=0.5)
        e2, _ = _toy_mega_engine(mode, "on", compress="topk:0.25",
                                 ef_momentum=0.5)
        s1, m1 = _run_toy(e1, params, mode)
        s2, m2 = _run_toy(e2, params, mode)
        assert "mom" in s1.comp and "resid" in s1.comp, mode
        assert float(m1["loss"]) == float(m2["loss"]), mode
        for a, b in zip(jax.tree.leaves(e1.params(s1)),
                        jax.tree.leaves(e2.params(s2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s1.comp), jax.tree.leaves(s2.comp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_update_ef_conservation_exact():
    """EF conservation holds BITWISE inside the megakernel: sent + resid'
    == acc on every coordinate, masked coordinates send exactly zero, and
    the DGC momentum is zeroed exactly on kept coordinates — on both the
    Pallas-interpret path (D = 4096) and the odd-width ref fallback
    (D = 4095)."""
    from repro.kernels import dispatch

    R = 3
    for d in (4096, 4095):
        ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(3), d), 6)
        p = jax.random.normal(ks[0], (d,))
        m = jax.random.normal(ks[1], (d,)) * 0.1
        v = jax.random.uniform(ks[2], (d,)) * 0.01
        stale = jax.random.normal(ks[3], (R, d))
        acc = jax.random.normal(ks[4], (R, d))
        mom = jax.random.normal(ks[5], (R, d))
        thr = jnp.full((R,), 0.8, jnp.float32)
        fresh = jnp.array([1.0, 0.0, 1.0], jnp.float32)
        w = jnp.full((R,), 1.0 / R, jnp.float32)
        keep = np.abs(np.asarray(acc)) >= 0.8

        outs = dispatch.fused_update(p, m, v, stale, w, 0.05, step=1,
                                     acc=acc, thr=thr, fresh=fresh)
        assert len(outs) == 6
        _, _, _, _, sent, resid = outs
        np.testing.assert_array_equal(np.asarray(sent) + np.asarray(resid),
                                      np.asarray(acc))
        assert (np.asarray(sent)[~keep] == 0).all()

        outs = dispatch.fused_update(p, m, v, stale, w, 0.05, step=1,
                                     acc=acc, thr=thr, fresh=fresh, mom=mom)
        assert len(outs) == 7
        _, _, _, _, sent, resid, mom_out = outs
        np.testing.assert_array_equal(np.asarray(sent) + np.asarray(resid),
                                      np.asarray(acc))
        assert (np.asarray(mom_out)[keep] == 0).all()
        np.testing.assert_array_equal(np.asarray(mom_out)[~keep],
                                      np.asarray(mom)[~keep])


def test_matrix_two_device_sharded():
    """The full matrix on a (data=2) mesh, the sharded legacy
    bitwise-equivalence check, and the MultiPod delay spec (one worker per
    pod, pods mapped onto the data axis), in a 2-device subprocess."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'tests')!r})
        import numpy as np
        import test_engine_matrix as M
        from repro.launch import mesh as meshlib

        mesh = meshlib.make_host_mesh(2, 1)
        for arch_id in M.ARCHS:
            for mode in M.MODES:
                engine = M.make_engine(arch_id, mode, mesh)
                state, losses = M.run_combo(engine)
                assert all(np.isfinite(l) for l in losses), \\
                    (arch_id, mode, losses)
                _, replay = M.run_combo(engine)
                assert losses == replay, (arch_id, mode)
        M.check_legacy_equivalence(mesh)
        # MultiPod: hierarchical intra/inter-pod delays on the sharded mesh
        # (both the gradient-ring and per-worker-cache substrates).
        for mode in ("stale-psum", "simulate"):
            engine = M.make_engine("mamba2-1.3b", mode, mesh,
                                   delay=M.MULTIPOD)
            state, losses = M.run_combo(engine)
            assert all(np.isfinite(l) for l in losses), (mode, losses)
            _, replay = M.run_combo(engine)
            assert losses == replay, mode
        # PR 7: compression runs per source worker BEFORE the ring write —
        # the packed gbuf slot holds the SPARSE sent payload (zeros where
        # the EF mask dropped coordinates), not the dense gradient.
        eng = M.make_engine("mamba2-1.3b", "stale-psum", mesh, kernels="on",
                            compress="topk:0.25")
        assert eng.meta["kernels"]["megakernel"] == "fused", eng.meta
        state, losses = M.run_combo(eng, steps=1)
        assert all(np.isfinite(l) for l in losses), losses
        ring = np.asarray(state.inner.gbuf)          # packed [slots, P, D]
        row = ring[np.abs(ring).sum(axis=(1, 2)).argmax()]
        frac_zero = float((row == 0).mean())
        assert frac_zero > 0.5, frac_zero
        print("MATRIX2_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert "MATRIX2_OK" in r.stdout, r.stdout + r.stderr

"""Kernel dispatch subsystem: packed flat views, backend routing, donation.

Covers the tentpole contracts of the kernel-backed engine hot path:
* ``treemath`` packed views round-trip exactly (hypothesis property
  test), including leading worker axes and block padding;
* the dispatchers agree with the ref oracles on divisible AND non-divisible
  D (the odd-shape path must fall back, not crash);
* the packed stale delivery / fused Adam reproduce the per-leaf tree math
  within fp32 tolerance;
* the planned engine step donates the EngineState exactly for the
  ring-buffer modes (input/output aliasing present in the lowering) and the
  escape hatch / simulate exemption hold.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import treemath as tm
from repro.core import stale_sync
from repro.kernels import dispatch, ref
from repro.optim import optimizers as optlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_tree(seed: int):
    """A mixed-shape/dtype pytree whose layout varies with the seed."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 40, size=4)
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (int(sizes[0]), int(sizes[1]))),
        "b": jax.random.normal(jax.random.fold_in(k, 1), (int(sizes[2]),)),
        "nested": {"h": jax.random.normal(
            jax.random.fold_in(k, 2),
            (int(sizes[3]),)).astype(jnp.bfloat16)},
    }


@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_pack_roundtrip_property(seed):
    """pack -> unpack restores every leaf exactly (fp32 packing widens
    bf16 losslessly), for any leaf layout."""
    tree = _random_tree(seed)
    spec = tm.pack_spec(tree)
    vec = tm.tree_pack(tree)
    assert vec.shape == (spec.total,)
    back = tm.tree_unpack(vec, spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_pack_roundtrip_padded_and_leading_axis(seed):
    """Block padding is inert (unpack ignores the zero tail) and a leading
    worker axis is preserved through pack/unpack."""
    tree = _random_tree(seed)
    spec = tm.pack_spec(tree)
    vec = tm.tree_pack(tree, pad_to=dispatch.PACK_ALIGN)
    assert vec.shape[-1] % dispatch.PACK_ALIGN == 0
    assert vec.shape[-1] >= spec.total
    np.testing.assert_array_equal(np.asarray(vec[spec.total:]), 0.0)
    back = tm.tree_unpack(vec, spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    stacked = jax.tree.map(lambda x: jnp.stack([x, 2 * x]), tree)
    v2 = tm.tree_pack(stacked, lead_ndim=1)
    assert v2.shape == (2, spec.total)
    back2 = tm.tree_unpack(v2, tm.pack_spec(stacked, lead_ndim=1))
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("d", [2048, 1000])  # divisible and non-divisible
def test_stale_accum_dispatch_matches_ref(d):
    p = jax.random.normal(jax.random.PRNGKey(0), (d,))
    buf = jax.random.normal(jax.random.PRNGKey(1), (5, d))
    w = jax.random.uniform(jax.random.PRNGKey(2), (5,))
    got = dispatch.stale_accum(p, buf, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.stale_accum(p, buf, w)),
                               rtol=1e-5, atol=1e-6)
    backend = dispatch.report()["stale_accum"]
    assert backend.startswith("ref" if d % 1024 else "pallas")


@pytest.mark.parametrize("d", [2048, 1000])
def test_fused_adam_dispatch_matches_ref(d):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    p, m, v, g = (jax.random.normal(k, (d,)) for k in ks)
    v = jnp.abs(v)
    got = dispatch.fused_adam(p, m, v, g, 1e-3, step=7)
    want = ref.fused_adam(p, m, v, g, 1e-3, 0.9, 0.999, 1e-8, 7)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("d", [4096, 1000])
def test_coherence_dispatch_matches_ref(d):
    hist = jax.random.normal(jax.random.PRNGKey(4), (6, d))
    g = jax.random.normal(jax.random.PRNGKey(5), (d,))
    for a, b in zip(dispatch.coherence_dots(hist, g),
                    ref.coherence_dots(hist, g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4)


def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


def _quad_setup():
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (6, 1)),
              "b": jnp.zeros((1,))}
    batches = []
    for t in range(8):
        x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(1), t),
                              (16, 6))
        batches.append((x, x.sum(axis=1, keepdims=True)))
    return params, batches


@pytest.mark.parametrize("per_worker", [True, False])
def test_packed_stale_step_matches_tree_step(per_worker):
    """StaleSyncConfig(kernels=True): packed ring + fused delivery tracks
    the per-leaf legacy step within fp32 tolerance, same sampled delays."""
    params, batches = _quad_setup()
    opt = optlib.sgd(0.05)
    key = jax.random.PRNGKey(9)
    cfgs = [stale_sync.StaleSyncConfig(num_workers=4, s=3,
                                       per_worker_delays=per_worker,
                                       kernels=k) for k in (False, True)]
    states = [stale_sync.init_state(params, opt, c, key) for c in cfgs]
    steps = [jax.jit(stale_sync.make_stale_train_step(quad_loss, opt, c))
             for c in cfgs]
    assert states[1].gbuf.ndim == (3 if per_worker else 2)  # packed array
    for b in batches:
        outs = [s(st, b) for s, st in zip(steps, states)]
        states = [o[0] for o in outs]
        np.testing.assert_array_equal(
            np.asarray(outs[0][1]["mean_staleness"]),
            np.asarray(outs[1][1]["mean_staleness"]))
    np.testing.assert_allclose(np.asarray(states[0].params["w"]),
                               np.asarray(states[1].params["w"]),
                               rtol=1e-5, atol=1e-6)


def test_kernel_adam_matches_tree_adam():
    """adam(kernel=True) (packed fused pass, zero-params delta trick) equals
    the per-leaf Adam, including moments, at a size the interpreter runs."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (40, 25)),
              "b": jnp.zeros((25,))}
    tree_opt = optlib.adam(1e-3)
    kern_opt = optlib.adam(1e-3, kernel=True)
    s0, s1 = tree_opt.init(params), kern_opt.init(params)
    for t in range(4):
        g = jax.tree.map(
            lambda p, i=t: jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(i), 7), p.shape), params)
        d0, s0 = tree_opt.update(g, s0, params)
        d1, s1 = kern_opt.update(g, s1, params)
        for a, b in zip(jax.tree.leaves(d0), jax.tree.leaves(d1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(s0["m"]), jax.tree.leaves(s1["m"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_engine_kernels_on_rejects_fsdp_archs():
    """The packed ring cannot keep the 'embed'->data FSDP placement: 'on'
    refuses, 'auto' silently falls back to tree math."""
    from repro.engine import EngineConfig, build_engine
    cfg = EngineConfig(mode="stale-psum", num_workers=2, s=2, kernels="on")
    with pytest.raises(ValueError, match="FSDP"):
        build_engine(quad_loss, optlib.sgd(0.1), cfg, arch="kimi-k2-1t-a32b")
    cfg_auto = EngineConfig(mode="stale-psum", num_workers=2, s=2,
                            kernels="auto")
    eng = build_engine(quad_loss, optlib.sgd(0.1), cfg_auto,
                       arch="kimi-k2-1t-a32b")
    assert eng.meta["kernels"]["delivery"] == "tree"


def test_compiled_kernels_vetoed_on_multi_device_mesh():
    """XLA cannot partition a compiled Mosaic kernel: with the compiled
    backend selected, a mesh of several devices gets no packed kernels;
    one device, or the interpreter (plain HLO), keeps them."""
    import dataclasses
    import types
    from repro.engine.api import kernel_placement_ok
    two = types.SimpleNamespace(devices=np.empty((2, 1)),
                                axis_names=("data", "model"))
    one = types.SimpleNamespace(devices=np.empty((1, 1)),
                                axis_names=("data", "model"))
    old = dispatch.CONFIG
    try:
        dispatch.CONFIG = dataclasses.replace(old, interpret=False)
        for kernels in ("auto", "on"):
            ok, why = kernel_placement_ok(kernels, "deepseek-7b", two)
            assert not ok and "partitioned" in why
            assert kernel_placement_ok(kernels, "deepseek-7b", one) == (
                True, "")
        dispatch.CONFIG = dataclasses.replace(old, interpret=True)
        assert kernel_placement_ok("auto", "deepseek-7b", two) == (True, "")
    finally:
        dispatch.CONFIG = old


# -- donation ---------------------------------------------------------------

def _planned_engine(mode, **kw):
    from repro.configs.base import InputShape
    from repro.engine import plan as planlib
    from repro.launch import mesh as meshlib
    shape = InputShape("donate_t", seq_len=16, global_batch=4, kind="train")
    return planlib.make_train_engine(
        "deepseek-7b", shape, meshlib.make_host_mesh(1, 1), mode=mode,
        stale_s=2, num_workers=2, reduced=True, ssp_steps=8, **kw)


def test_planned_step_donates_ring_buffer():
    """The lowered planned step aliases the EngineState (ring buffer, opt
    state, params) into its outputs; cfg.donate=False removes the aliasing
    and simulate mode (fully-rewritten state) never donates."""
    eng = _planned_engine("stale-psum", kernels="on")
    assert eng.plan().donate_argnums == (0,)
    assert "tf.aliasing_output" in eng.lowered_step().as_text()

    off = _planned_engine("stale-psum", donate=False)
    assert off.plan().donate_argnums == ()
    assert "tf.aliasing_output" not in off.lowered_step().as_text()

    sim = _planned_engine("simulate")
    assert sim.plan().donate_argnums == ()


def test_donated_step_replays_deterministically():
    """Donation must not change numerics or break linear state threading:
    two fresh runs through the donated step produce identical losses."""
    eng = _planned_engine("stale-psum", kernels="on")
    spec = eng.plan().args[1]

    def batch(t):
        out = {}
        for i, name in enumerate(sorted(spec)):
            s = spec[name]
            k = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(5), t), i)
            out[name] = (jax.random.randint(k, s.shape, 0, 16)
                         if s.dtype == jnp.int32
                         else jax.random.normal(k, s.shape, s.dtype))
        return out

    def run():
        st = eng.init(jax.random.PRNGKey(0))
        losses = []
        for t in range(3):
            st, m = eng.step(st, batch(t))
            losses.append(float(m["loss"]))
        return losses

    assert run() == run()


def test_interpret_cutoff_counts_total_touched_elements():
    """Regression (PR 7): ``fused_adam`` used to size the interpret-max
    guard on D alone while ``stale_accum`` counted s*d — a packed width
    whose delivery already fell back to the ref oracle could still push a
    4x-that-footprint Adam kernel through interpret mode. Every dispatcher
    now counts TOTAL touched elements, so the ref cutoff lands at the same
    footprint across ops."""
    import dataclasses

    old = dispatch.CONFIG
    d = 2048
    try:
        # Cap exactly at fused_adam's 4-operand footprint for width d.
        dispatch.CONFIG = dataclasses.replace(old,
                                              interpret_max_elements=4 * d)
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        p, m, v, g = (jax.random.normal(k, (d,)) for k in ks)
        v = jnp.abs(v)
        dispatch.reset_report()
        dispatch.fused_adam(p, m, v, g, 1e-3, step=1)
        # 4 [D] operands AT the cap -> the kernel still runs...
        assert dispatch.report()["fused_adam"].startswith("pallas")
        dispatch.fused_adam(*(jnp.tile(a, 2) for a in (p, m, v, g)),
                            1e-3, step=1)
        # ...and one block past it falls back, even though 2*d alone is
        # far under the cap (the pre-fix sizing).
        assert dispatch.report()["fused_adam"].startswith("ref")
        # Cutoff agreement: stale_accum's s*d footprint flips at the same
        # total — 4 buffer rows sit AT the cap, 5 fall back.
        dispatch.stale_accum(p, jnp.stack([g] * 4), jnp.ones((4,)) / 4)
        assert dispatch.report()["stale_accum"].startswith("pallas")
        dispatch.stale_accum(p, jnp.stack([g] * 5), jnp.ones((5,)) / 5)
        assert dispatch.report()["stale_accum"].startswith("ref")
    finally:
        dispatch.CONFIG = old
        dispatch.reset_report()


def test_interpret_env_config_read_once():
    """REPRO_KERNELS_INTERPRET is honored at import with no module-global
    mutation (and ops.INTERPRET is gone)."""
    code = (
        "from repro.kernels import dispatch, ops\n"
        "assert dispatch.CONFIG.interpret is False\n"
        "assert dispatch.interpret_mode() is False\n"
        "try:\n"
        "    ops.INTERPRET\n"
        "except AttributeError as e:\n"
        "    assert 'REPRO_KERNELS_INTERPRET' in str(e)\n"
        "else:\n"
        "    raise SystemExit('ops.INTERPRET read should be gone')\n"
        "try:\n"
        "    ops.INTERPRET = False\n"   # the old documented mutation
        "except AttributeError as e:\n"
        "    assert 'REPRO_KERNELS_INTERPRET' in str(e)\n"
        "else:\n"
        "    raise SystemExit('ops.INTERPRET write should be rejected')\n"
        "print('ENV_OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_KERNELS_INTERPRET="0", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert "ENV_OK" in r.stdout, r.stdout + r.stderr

"""The stale-psum ring read: P per-worker slices of the ring where its
worker axis sits on one device, one gather where it is split over devices
(or where the rows feed a packed kernel), and the same delivered gradient
either way."""
import os
import re
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.core import stale_sync
from repro.optim import optimizers as optlib
from repro.sharding import rules as rules_lib

from test_trace_scopes import reduced_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = 4


def read_slots(pattern, p):
    """Slot each of ``p`` workers reads, at a step whose write slot is 2."""
    if pattern == "fresh":        # delay 0: this step's row, just written
        return np.full((p,), 2)
    if pattern == "wrapped":      # delays 0, 1, 2, 3 reach back past slot 0
        return (2 - np.arange(p)) % SLOTS
    return np.full((p,), 3)       # every worker on the same older slot


@pytest.mark.parametrize("pattern", ["fresh", "wrapped", "same_slot"])
@pytest.mark.parametrize("p", [1, 2, 4])
def test_ring_rows_equal_the_gathered_rows(p, pattern):
    ring = jax.random.normal(jax.random.PRNGKey(p), (SLOTS, p, 3, 5)
                             ).astype(jnp.bfloat16)
    read = jnp.asarray(read_slots(pattern, p), jnp.int32)
    rows = jax.jit(stale_sync._ring_rows)(ring, read)
    gathered = jax.jit(stale_sync._ring_rows_gathered)(ring, read)
    assert rows.shape == gathered.shape == (p, 3, 5)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(gathered))
    want = np.stack([np.asarray(ring)[int(r), q] for q, r in enumerate(read)])
    np.testing.assert_array_equal(np.asarray(rows), want)


@pytest.mark.parametrize("p", [1, 2, 4])
def test_worker_mean_matches_the_reduction(p):
    rows = jax.random.normal(jax.random.PRNGKey(7), (p, 64, 33)
                             ).astype(jnp.bfloat16)
    got = np.asarray(jax.jit(stale_sync._worker_mean)(rows))
    want = np.asarray(jax.jit(
        lambda r: r.astype(jnp.float32).mean(axis=0))(rows))
    assert got.dtype == np.float32
    if p <= 2:
        np.testing.assert_array_equal(got, want)
    else:   # the sum's order differs from the reduction's
        np.testing.assert_allclose(got, want, rtol=1e-6)


def fake_mesh(data, model=1, pod=None):
    """What the predicate reads of a mesh: axis names and device shape."""
    names, shape = ("data", "model"), (data, model)
    if pod is not None:
        names, shape = ("pod",) + names, (pod,) + shape
    return types.SimpleNamespace(axis_names=names, devices=np.empty(shape))


@pytest.mark.parametrize("mesh,p,split", [
    (None, 2, False),
    (fake_mesh(1), 2, False),           # one data device splits nothing
    (fake_mesh(4), 4, True),
    (fake_mesh(4), 8, True),
    (fake_mesh(4), 6, False),           # uneven: the plan replicates
    (fake_mesh(2, pod=2), 4, True),     # pods x data
    (types.SimpleNamespace(axis_names=("model",),
                           devices=np.empty((4,))), 4, False),
])
def test_worker_axis_split(mesh, p, split):
    assert rules_lib.worker_axis_split(mesh, p) is split


@pytest.mark.parametrize("kernels,split,form", [
    (False, False, "rows"),
    (False, True, "gather"),
    (True, False, "gather"),
])
def test_ring_read_form_follows_placement(kernels, split, form):
    cfg = stale_sync.StaleSyncConfig(num_workers=2, s=4, kernels=kernels,
                                     worker_axis_split=split)
    assert cfg.ring_read[0] == form


def toy_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


@pytest.mark.parametrize("p", [2, 4])
def test_rows_and_gather_deliver_the_same_steps(p):
    """Six steps (every ring slot written, wrapped reads) with each read
    form: bitwise the same parameters at P=2, within float32 rounding of
    the mean's summation order at P=4."""
    key = jax.random.PRNGKey(11)
    kx, ky, kw = jax.random.split(key, 3)
    params = {"w": jax.random.normal(kw, (8, 3)), "b": jnp.zeros((3,))}
    batches = [{"x": jax.random.normal(jax.random.fold_in(kx, t), (4 * p, 8)),
                "y": jax.random.normal(jax.random.fold_in(ky, t), (4 * p, 3))}
               for t in range(6)]
    opt = optlib.get_optimizer("adam", lr=1e-2)
    finals = []
    for split in (False, True):
        cfg = stale_sync.StaleSyncConfig(num_workers=p, s=SLOTS,
                                         buffer_dtype=jnp.bfloat16,
                                         worker_axis_split=split)
        step = jax.jit(stale_sync.make_stale_train_step(toy_loss, opt, cfg))
        state = stale_sync.init_state(params, opt, cfg, key)
        for b in batches:
            state, _ = step(state, b)
        finals.append(jax.tree.map(np.asarray, state.params))
    for a, b in zip(jax.tree.leaves(finals[0]), jax.tree.leaves(finals[1])):
        if p == 2:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def ring_gathers(engine):
    """Instructions of the compiled step traced from a gather under the
    ``ring`` scope (after partitioning, a gather over a sharded axis may
    run as a local slice; its op name still says gather)."""
    text = engine.compiled_step_text(*engine.plan().args)
    return re.findall(rf'op_name="[^"]*/{scopes.RING}/[^"]*gather"', text)


@pytest.mark.parametrize("route,kw,form", [
    ("tree", dict(), "rows"),
    ("packed", dict(kernels="on", megakernel="off"), "gather"),
])
def test_one_device_step_read_form(route, kw, form):
    from repro.kernels import dispatch
    engine, _ = reduced_engine(**kw)
    assert engine.meta["kernels"]["ring_read"] == form
    gathers = ring_gathers(engine)   # compiles: the trace notes the form
    assert engine.dispatch_report()["decisions"]["ring_read"].startswith(
        form)
    assert dispatch.report()["ring_read"].startswith(form)
    if form == "rows":
        assert gathers == []
    else:
        assert gathers


def test_split_worker_axis_keeps_the_gather():
    """P=4 on a 4x1 data mesh of CPU devices, bf16 ring: the read stays
    one gather over the sharded worker axis."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'tests')!r})
        import jax.numpy as jnp
        from repro import configs as cfglib
        from repro.configs.base import InputShape
        from repro.launch import mesh as meshlib
        from repro.launch.train import build_train_engine
        import test_ring_read as T

        arch = cfglib.get("deepseek-7b")
        engine = build_train_engine(
            arch.api(reduced=True), arch, meshlib.parse_host_mesh("4x1"),
            InputShape("split", 16, 8, "train"), lr=1e-3, mode="stale-psum",
            num_workers=4, s=4, buffer_dtype=jnp.bfloat16)
        assert engine.meta["kernels"]["ring_read"] == "gather", engine.meta
        assert T.ring_gathers(engine)
        decided = engine.dispatch_report()["decisions"]["ring_read"]
        assert decided.startswith("gather"), decided
        print("SPLIT_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"),
                                         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert "SPLIT_OK" in r.stdout, r.stdout + r.stderr

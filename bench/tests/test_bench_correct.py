"""The correctness check fails where it should: the control (the
reference one precision below the configuration's) reads over a limit, and
a run with the timed path broken underneath comes out not correct, once for
each fault a cell can have."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest_support as sup
from bench import harness
from bench.drivers import train as train_drv

SEED = 2 ** 33 + 7


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return sup.tiny_checkout(tmp_path_factory.mktemp("checkout"))


def test_train_control_fails(root):
    cell = harness.load_cell("tiny-train", root)
    ref = train_drv.reference_readings(cell, SEED)
    ctl = train_drv.reference_readings(cell, SEED, quant=jnp.bfloat16)
    gaps = train_drv.compare(ctl, ref)
    assert any(gaps[k] > cell.limits[k] for k in train_drv.CHECKED), gaps


def _unchanged_state(monkeypatch):
    from repro.core import stale_sync
    make = stale_sync.make_stale_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)

        def same(state, batch, bound=None, comp=None):
            new, metrics = step(state, batch, bound=bound)
            return dataclasses.replace(new, params=state.params,
                                       opt_state=state.opt_state), metrics
        return same
    monkeypatch.setattr(stale_sync, "make_stale_train_step", broken)


def _half_batch(monkeypatch):
    from repro.models import transformer
    loss = transformer.loss_fn

    def half(params, batch, cfg):
        n = batch["tokens"].shape[0]
        return loss(params, {"tokens": batch["tokens"][: max(n // 2, 1)]},
                    cfg)
    monkeypatch.setattr(transformer, "loss_fn", half)


def _no_exchange(monkeypatch):
    # Every worker applies the first worker's delivered gradient: what a
    # chip sees when the mean over the worker axis is not exchanged.
    from repro.core import stale_sync
    rows = stale_sync._ring_rows

    def own(ring, read):
        r = rows(ring, read)
        return jnp.broadcast_to(r[:1], r.shape)
    monkeypatch.setattr(stale_sync, "_ring_rows", own)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _no_exchange])
def test_train_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()
    line = sup.run(root, "tiny-train", seconds=0.5)
    jax.clear_caches()
    assert line["correct"] is False, line["checks"]

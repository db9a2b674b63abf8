"""The trace reduction: busy and idle share, kernel time, exposed
collectives and idle gaps, on hand-made intervals and on a trace recorded
by the profiler."""
import jax
import jax.numpy as jnp
import pytest

import benchtest_support  # noqa: F401  (puts the repo on sys.path)
from bench import program, tracing

MS = 1_000_000


def summary():
    ops = [("fusion.1", 0, 2 * MS), ("all-reduce.3", 1 * MS, 4 * MS),
           ("paged_attention", 6 * MS, 7 * MS),
           ("fusion.1", 9 * MS, 9 * MS + MS // 2)]
    host = [("step", 0, 10 * MS), ("make_batch", 4 * MS, 6 * MS),
            ("sample", 7 * MS, 9 * MS), ("inner", 7 * MS, 8 * MS)]
    mods = [("jit_serve_step", 0, 5 * MS), ("jit_serve_step", 6 * MS, 10 * MS),
            ("jit_other", 5 * MS, 6 * MS)]
    dev = tracing.Device("/device:TPU:0", ops, mods)
    return tracing.Summary(devices=[dev], host=host, window_s=0.01)


def test_busy_and_idle_share():
    s = summary()
    assert s.busy_s == pytest.approx(0.0055)           # 0-4, 6-7, 9-9.5 ms
    assert s.idle_share() == pytest.approx(0.45)


def test_kernel_and_module_time():
    s = summary()
    assert s.op_seconds("paged_attention") == pytest.approx(0.001)
    assert s.op_seconds("fusion") == pytest.approx(0.0025)
    assert s.module_seconds("serve_step") == (pytest.approx(0.009), 2)


def test_exposed_collective_is_the_part_no_compute_covers():
    s = summary()
    # all-reduce 1-4 ms, compute covers 1-2 ms: 2 ms exposed
    assert s.exposed_collective_s() == pytest.approx(0.002)


def test_idle_gaps_are_named_by_innermost_host_span():
    s = summary()
    gaps = dict(s.idle_gaps())
    assert gaps == {"make_batch": pytest.approx(0.002),
                    "inner": pytest.approx(0.002)}
    ops = s.device_ops()
    assert ops[0] == ["all-reduce.3", pytest.approx(0.003)]
    assert len(s.breakdown()["device_ops"]) <= 10


def test_recorded_trace_reduces():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    box = {}
    with program.profiled(True, 1, box):
        for _ in range(5):
            f(x).block_until_ready()
    s = box["summary"]
    assert s.devices and s.devices[0].ops
    assert 0.0 < s.busy_s <= s.window_s == box["window_s"]
    assert 0.0 <= s.idle_share() < 1.0
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in b.values())
    assert all(isinstance(n, str) and sec >= 0 for n, sec in b["device_ops"])


def test_async_collective_counts_where_nothing_overlaps():
    s = summary()
    s.devices[0].async_collectives = [("all-reduce-start.1", 4 * MS,
                                       7 * MS)]
    # 1-4 ms sync (2 exposed) + 4-6 ms async with no op (6-7 ms overlaps
    # paged_attention): 4 ms exposed; busy is unchanged
    assert s.exposed_collective_s() == pytest.approx(0.004)
    assert s.busy_s == pytest.approx(0.0055)

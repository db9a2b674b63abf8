"""The training step by layer (``bench/layers.py``): nested operations
counted once under the outer one's layer, layers that add up to busy time,
a layer name the program adds counted under its own key, device idle
inside the Trainer's host spans, the six readers on a recorded CPU trace
of the tiny cell, and the accepted metrics unchanged."""
import warnings

import jax
import pytest

import benchtest_support as sup
from bench import harness, layers, tracing

MS = 1_000_000
# mfu.train of the tiny cell at 1 step, 4 x 16 tokens, in 0.02 s, against
# a 1e12 FLOP/s peak, as the accepted reader computed it.
MFU_TINY = 0.76333056
READERS = ("forward_ms.train", "backward_ms.train", "ring_ms.train",
           "optimizer_ms.train", "sync_idle_ms.train", "feed_idle_ms.train")
OP_LAYERS = {"while.1": "backward", "fusion.2": "ring", "fusion.3": "forward",
             "fusion.4": "optimizer", "all-reduce.5": "ring"}


def summary(chips=1):
    """The step program runs 0-10 ms, another program 12-14 ms."""
    ops = [("while.1", 0, 6 * MS), ("fusion.2", 1 * MS, 2 * MS),
           ("fusion.3", 3 * MS, 5 * MS),            # both inside while.1
           ("fusion.4", 6 * MS, 8 * MS), ("all-reduce.5", 7 * MS, 9 * MS),
           ("copy.6", 9 * MS, 10 * MS),             # in no scope
           ("fusion.2", 12 * MS, 13 * MS)]          # the other program's
    mods = [("jit__wrap(7)", 0, 10 * MS), ("jit_other(8)", 12 * MS, 14 * MS)]
    host = [("train", 0, 11 * MS), ("trainer.batch", 0, MS // 2),
            ("trainer.dispatch", MS // 2, MS), ("trainer.log", 10 * MS,
                                                 12 * MS + MS // 2),
            ("train", 11 * MS, 15 * MS), ("trainer.batch", 11 * MS, 12 * MS)]
    devs = [tracing.Device(f"/device:TPU:{i}", list(ops), list(mods))
            for i in range(chips)]
    return tracing.Summary(devices=devs, host=host, window_s=0.02)


def test_nested_operations_count_once_under_the_outer_layer():
    secs = layers.layer_seconds(summary(), OP_LAYERS, "jit__wrap")
    assert secs == {"forward": 0.0, "backward": pytest.approx(0.006),
                    "optimizer": pytest.approx(0.002),
                    # 7-8 ms is fusion.4's, which started first
                    "ring": pytest.approx(0.001),
                    # copy.6, and fusion.2 outside the step program
                    "other": pytest.approx(0.002)}


@pytest.mark.parametrize("chips", [1, 2])
def test_layers_add_up_to_busy(chips):
    s = summary(chips)
    s.devices[-1].ops.append(("fusion.3", 15 * MS, 16 * MS))
    secs = layers.layer_seconds(s, OP_LAYERS, "jit__wrap")
    assert sum(secs.values()) == pytest.approx(s.busy_s)


def test_a_layer_the_program_names_later_is_counted():
    # the program's op map names an ``experts`` layer: it is reported under
    # its own name, the five as before, and all still add up to busy
    s = summary()
    op_layers = dict(OP_LAYERS, **{"fusion.4": "experts"})
    secs = layers.layer_seconds(s, op_layers, "jit__wrap")
    assert secs == {"forward": 0.0, "backward": pytest.approx(0.006),
                    "optimizer": 0.0, "ring": pytest.approx(0.001),
                    "other": pytest.approx(0.002),
                    "experts": pytest.approx(0.002)}
    assert sum(secs.values()) == pytest.approx(s.busy_s)
    run = _run(None, s, 1)
    run.outcome.layer_readings = {"steps": 1, "idle": {}, "layers": secs}
    assert layers.layer_ms(run, "experts") == pytest.approx(2.0)
    assert layers.layer_ms(run, "router") is None


def test_idle_inside_a_named_span():
    s = summary()
    # trainer.log 10-12.5 ms: busy 12-12.5 ms
    assert layers.idle_in_span_seconds(s, "trainer.log") == pytest.approx(
        0.002)
    # trainer.batch 0-0.5 ms (busy) and 11-12 ms (idle)
    assert layers.idle_in_span_seconds(s, "trainer.batch") == pytest.approx(
        0.001)


def test_step_count_is_the_step_spans_that_dispatched():
    # the second span is the source running dry: no dispatch
    assert layers.step_count(summary()) == 1


def test_module_name():
    assert layers.module_name("HloModule jit__wrap, is_scheduled=true\n"
                              "ENTRY ...") == "jit__wrap"


def _run(cell, trace, steps):
    out = harness.Outcome(end_to_end={}, checks=[], attempted=steps,
                          failed=0, memory_peak_bytes=0,
                          counters={"steps": steps, "window_s": 0.02,
                                    "batch": 4, "seq": 16},
                          trace=trace)
    return harness.Run(cell, out, sup.CPU_PEAKS, 1)


def _cpu_modules(path):
    """Module events of a CPU trace, which has none: each run of a program
    spans its operations (``run_id``)."""
    runs = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                for e in line.events:
                    st = dict(e.stats)
                    if "hlo_module" not in st:
                        continue
                    key = (st["hlo_module"], st.get("run_id"))
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    lo, hi = runs.get(key, (s, t))
                    runs[key] = (min(lo, s), max(hi, t))
    return [(m, s, e) for (m, _), (s, e) in runs.items()]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny cell's engine driven by the Trainer under the profiler:
    (cell, reduced trace with module events, steps)."""
    from repro.engine import Trainer
    root = sup.tiny_checkout(tmp_path_factory.mktemp("checkout"))
    cell = harness.load_cell("tiny-train", root)
    driver = harness.load_driver(cell)
    engine, _ = driver.build(cell, jax.devices()[:1])
    state, _, feed = driver.first_steps(engine, cell, 2 ** 33 + 5)
    steps = 8
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        result = Trainer(engine).run(lambda: {"tokens": next(feed)}, steps,
                                     state=state, log_every=2)
        jax.block_until_ready(result.state)
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xplane(d)
    trace = tracing.reduce(path, 1.0, 1)
    trace.devices[0].modules = _cpu_modules(path)
    return cell, trace, steps


def test_readers_on_a_recorded_cpu_trace(recorded):
    cell, trace, steps = recorded
    run = _run(cell, trace, steps)
    values = {m: harness.load_reader(cell, m).read(run) for m in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    for m in ("forward_ms.train", "backward_ms.train", "ring_ms.train",
              "optimizer_ms.train"):
        assert values[m] > 0, m
    r = layers.readings(run)
    assert r["steps"] == steps
    per_step_busy = trace.busy_s / steps
    assert sum(r["layers"].values()) == pytest.approx(per_step_busy,
                                                      rel=0.005)


def test_readers_have_nothing_to_read_without_module_events(recorded):
    cell, trace, steps = recorded
    bare = tracing.Summary(
        devices=[tracing.Device(d.name, d.ops, []) for d in trace.devices],
        host=trace.host, window_s=trace.window_s)
    run = _run(cell, bare, steps)
    assert [harness.load_reader(cell, m).read(run) for m in READERS] == [
        None] * len(READERS)


def test_accepted_metrics_read_as_before(tmp_path):
    """``idle_share.train``, ``mfu.train``, ``collective_exposed_ms.train``
    and the breakdown on a fixed summary, at the values the accepted
    readers gave before the layer metrics were added."""
    cell = harness.load_cell("tiny-train", sup.tiny_checkout(tmp_path))
    run = _run(cell, summary(), 1)
    read = lambda m: harness.load_reader(cell, m).read(run)
    assert read("idle_share.train") == pytest.approx(45.0)
    assert read("mfu.train") == pytest.approx(MFU_TINY)
    # all-reduce 7-9 ms, fusion.4 covers 7-8 ms
    assert read("collective_exposed_ms.train") == pytest.approx(1.0)
    assert summary().breakdown() == {
        "device_ops": [["while.1", pytest.approx(0.006)],
                       ["fusion.2", pytest.approx(0.002)],
                       ["fusion.3", pytest.approx(0.002)],
                       ["fusion.4", pytest.approx(0.002)],
                       ["all-reduce.5", pytest.approx(0.002)],
                       ["copy.6", pytest.approx(0.001)]],
        # the 10-12 ms gap, by the innermost host span at its middle
        "idle_gaps": [["trainer.batch", pytest.approx(0.002)]]}

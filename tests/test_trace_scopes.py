"""The training step's layers named inside the program: the Trainer's host
spans in a profiler trace, runs that do not depend on whether a profiler
records them, and ``Engine.op_layers`` on every route of the step."""
import glob
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro import scopes
from repro.configs.base import InputShape
from repro.engine import Trainer
from repro.launch import mesh as meshlib
from repro.launch.train import build_train_engine

STEPS, LOG_EVERY, EVAL_EVERY = 6, 2, 3
CHILDREN = ("trainer.batch", "trainer.dispatch", "trainer.hooks",
            "trainer.log", "trainer.eval")


def reduced_engine(mode="stale-psum", kernels="off", megakernel="auto"):
    """The reduced deepseek-7b on a 1x1 mesh, 4 x 16 tokens a step."""
    arch = cfglib.get("deepseek-7b")
    api = arch.api(reduced=True)
    ring = mode != "sync"
    engine = build_train_engine(
        api, arch, meshlib.parse_host_mesh("1x1"),
        InputShape("trace_test", 16, 4, "train"), lr=1e-3, mode=mode,
        num_workers=2 if ring else 1, s=4 if ring else 0, kernels=kernels,
        megakernel=megakernel)
    return engine, api


def batches(vocab, n):
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    return [{"tokens": jax.random.randint(k, (4, 16), 0, vocab, jnp.int32)}
            for k in keys]


def run_trainer(engine, api, feed):
    state = engine.init(jax.random.PRNGKey(0))
    eval_fn = lambda params: api.loss(params, feed[0])
    return Trainer(engine).run(iter(feed), STEPS, state=state,
                               log_every=LOG_EVERY, eval_fn=eval_fn,
                               eval_every=EVAL_EVERY)


@pytest.fixture(scope="module")
def ring_engine():
    return reduced_engine()


@pytest.fixture(scope="module")
def profiled(ring_engine, tmp_path_factory):
    """(result, host events of the xplane) of a profiled Trainer.run."""
    engine, api = ring_engine
    feed = batches(api.cfg.vocab, STEPS)
    run_trainer(engine, api, feed)             # compile outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        result = run_trainer(engine, api, feed)
        jax.block_until_ready(result.state)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name == "train" or e.name in CHILDREN:
                        events.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats)))
    return result, events


def test_trainer_writes_one_step_span_per_step(profiled):
    _, events = profiled
    steps = sorted((e for e in events if e[0] == "train"),
                   key=lambda e: e[1])
    assert [int(e[3]["step_num"]) for e in steps] == list(range(STEPS))
    counts = {name: sum(e[0] == name for e in events) for name in CHILDREN}
    assert counts == {"trainer.batch": STEPS, "trainer.dispatch": STEPS,
                      "trainer.hooks": STEPS,
                      "trainer.log": STEPS // LOG_EVERY,
                      "trainer.eval": STEPS // EVAL_EVERY}
    for name, s, e, _ in events:
        if name != "train":
            assert any(ss <= s and e <= se for _, ss, se, _ in steps), name
    # each step's children, in the order the loop runs them
    for t, (_, ss, se, _) in enumerate(steps):
        inside = [n for n, s, e, _ in sorted(events, key=lambda e: e[1])
                  if n != "train" and ss <= s and e <= se]
        want = ["trainer.batch", "trainer.dispatch", "trainer.hooks"]
        want += ["trainer.log"] * ((t + 1) % LOG_EVERY == 0)
        want += ["trainer.eval"] * ((t + 1) % EVAL_EVERY == 0)
        assert inside == want


def test_runs_are_bitwise_the_same_with_and_without_profiler(ring_engine,
                                                             profiled):
    engine, api = ring_engine
    traced, _ = profiled
    plain = run_trainer(engine, api, batches(api.cfg.vocab, STEPS))
    strip = lambda rows: [{k: v for k, v in r.items() if k != "wall_s"}
                          for r in rows]
    assert strip(plain.history) == strip(traced.history)
    assert plain.curve == traced.curve
    for a, b in zip(jax.tree.leaves(jax.device_get(plain.state)),
                    jax.tree.leaves(jax.device_get(traced.state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("op_name,layer", [
    ("jit(_wrap)/vmap(model)/jvp()/while/body/dot_general", "forward"),
    ("jit(_wrap)/model/jvp()/reduce_sum", "forward"),
    ("jit(_wrap)/vmap(model)/transpose(jvp())/while/body/dot_general",
     "backward"),
    ("jit(_wrap)/vmap(model)/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/tanh", "backward"),
    ("jit(_wrap)/ring/vmap()/dynamic_slice", "ring"),
    ("jit(_wrap)/optimizer/mul;jit(_wrap)/ring/add", "optimizer"),
    ("jit(_wrap)/ring/vmap(optimizer)/add", "optimizer"),
    ("jit(_wrap)/vmap()/transpose", "other"),
    ("reduce_sum", "other"),
])
def test_layer_of(op_name, layer):
    assert scopes.layer_of(op_name) == layer


_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\s([a-z][\w\-]*)\((.*)$")
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def executed_instructions(text):
    """(name, opcode, operands) of the instructions a device runs as
    operations: those of computations that are not fused into, or applied
    by, another instruction."""
    comps, comp, inner = {}, None, set()
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = _HEADER.match(line)
            if h is not None:
                comp = comps.setdefault(h.group(1), [])
            continue
        if comp is not None:
            comp.append(m.groups())
            inner.update(re.findall(r"(?:calls|to_apply)=%?([^\s,]+)",
                                    m.group(3)))
    return [ins for name, body in comps.items() if name not in inner
            for ins in body]


ROUTES = {
    "tree": (dict(), ("forward", "backward", "ring", "optimizer")),
    "packed": (dict(kernels="on", megakernel="off"),
               ("forward", "backward", "ring", "optimizer")),
    "fused_update": (dict(kernels="on", megakernel="on"),
                     ("forward", "backward", "ring", "optimizer")),
    # sync has no ring
    "sync": (dict(mode="sync"), ("forward", "backward", "optimizer")),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_op_layers_name_every_operation(route):
    kw, want = ROUTES[route]
    engine, _ = reduced_engine(**kw)
    text = engine.compiled_step_text(*engine.plan().args)
    layers = engine.op_layers(*engine.plan().args)
    assert layers == scopes.op_layers(text)
    ops = executed_instructions(text)
    params = {name for name, op, _ in ops if op == "parameter"}
    found = {layers[name] for name, op, _ in ops if op not in PLUMBING}
    assert found - {"other"} == set(want)
    unnamed = [(name, op) for name, op, args in ops
               if op not in PLUMBING and layers[name] == "other"
               # a passed-through input copied to its output
               and not (op == "copy" and args.split(")")[0].lstrip("%")
                        in params)]
    assert unnamed == []

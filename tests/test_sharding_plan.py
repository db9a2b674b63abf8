"""Sharding-plan unit tests: FSDP vs replicated placement, eval_shape only.

Plans for the big ZeRO-class configs (kimi-k2-1t-a32b at ~1T params,
deepseek-67b) must build abstractly — ShapeDtypeStructs and NamedShardings,
never device arrays — with the "embed" -> data rule applied to every param
leaf, and round-trip through ``build_engine(mesh=...)`` / ``engine.plan()``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro import configs as cfglib
from repro.configs.base import SHAPES
from repro.engine import EngineConfig, build_engine
from repro.engine import plan as planlib
from repro.launch import mesh as meshlib
from repro.sharding import rules as rules_lib

FSDP = sorted(rules_lib.FSDP_ARCHS)          # kimi-k2-1t-a32b, deepseek-67b
REPLICATED = ["deepseek-7b", "qwen3-14b"]


def host_mesh():
    return meshlib.make_host_mesh(1, 1)


def spec_axes(sharding) -> set:
    """Flat set of mesh-axis names a NamedSharding's spec uses."""
    out = set()
    for part in sharding.spec:
        if isinstance(part, tuple):
            out.update(a for a in part if a)
        elif part is not None:
            out.add(part)
    return out


def train_plan(arch_id, stale_s=4):
    return planlib.make_train_engine(arch_id, "train_4k", host_mesh(),
                                     stale_s=stale_s).plan()


@pytest.mark.parametrize("arch_id", FSDP + REPLICATED)
def test_every_param_leaf_gets_a_partition_spec(arch_id):
    plan = train_plan(arch_id)
    params_sh = plan.in_shardings[0].inner.params
    arch = cfglib.get(arch_id)
    n_params = len(jax.tree.leaves(
        jax.eval_shape(lambda k: arch.api().init(k)[0], jax.random.PRNGKey(0))))
    leaves = jax.tree.leaves(params_sh)
    assert len(leaves) == n_params
    assert all(isinstance(l, NamedSharding) and isinstance(l.spec, PS)
               for l in leaves)


@pytest.mark.parametrize("arch_id", FSDP)
def test_fsdp_archs_shard_params_over_data(arch_id):
    """ZeRO rule: the "embed" dims of FSDP archs land on the data axis, and
    the planner selects the aggregate (Theorem-1) buffer form — the
    per-worker buffer axis cannot reuse 'data'."""
    plan = train_plan(arch_id)
    params_sh = jax.tree.leaves(plan.in_shardings[0].inner.params)
    assert any("data" in spec_axes(l) for l in params_sh)
    gbuf_sh = jax.tree.leaves(plan.in_shardings[0].inner.gbuf)
    for buf, param in zip(gbuf_sh, params_sh):
        assert len(buf.spec) >= 1 and buf.spec[0] is None  # slot axis
        assert buf.spec[1:] == param.spec                  # aggregate form


@pytest.mark.parametrize("arch_id", REPLICATED)
def test_replicated_archs_keep_params_off_data(arch_id):
    plan = train_plan(arch_id)
    params_sh = jax.tree.leaves(plan.in_shardings[0].inner.params)
    assert all("data" not in spec_axes(l) and "pod" not in spec_axes(l)
               for l in params_sh)
    # per-worker buffers spend the data axis on the worker dim instead
    gbuf_sh = jax.tree.leaves(plan.in_shardings[0].inner.gbuf)
    assert all(b.spec[0] is None and "data" in spec_axes(b) for b in gbuf_sh)


@pytest.mark.parametrize("arch_id", FSDP)
def test_plans_build_abstractly_without_device_memory(arch_id):
    """eval_shape only: every planned argument is a ShapeDtypeStruct —
    building a 1T-param plan must not allocate a single device array."""
    plan = train_plan(arch_id)
    leaves = jax.tree.leaves(plan.args)
    assert leaves, arch_id
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    total = sum(int(np.prod(l.shape)) for l in
                jax.tree.leaves(plan.args[0].inner.params))
    assert total > 1e10  # genuinely the full-scale config


def test_plan_round_trips_through_build_engine():
    """build_engine(mesh=..., arch=..., shape=...) attaches the identical
    plan the planner computes — one sharding-planning layer, two doors."""
    mesh = host_mesh()
    arch = cfglib.get("deepseek-67b")
    api = arch.api()
    from repro.optim import optimizers as optlib
    ecfg = EngineConfig(mode="stale-psum", s=4, num_workers=2,
                        per_worker_delays=False)
    engine = build_engine(api, optlib.get_optimizer(arch.train_optimizer),
                          ecfg, mesh=mesh, arch=arch, shape="train_4k")
    via_engine = engine.plan()
    direct = planlib.make_train_engine(
        arch, "train_4k", mesh, ecfg=dataclasses.replace(ecfg)).plan()
    a = jax.tree.leaves(via_engine.in_shardings)
    b = jax.tree.leaves(direct.in_shardings)
    assert len(a) == len(b)
    assert all(x.spec == y.spec for x, y in zip(a, b))
    sa = jax.tree.leaves(via_engine.args[0])
    sb = jax.tree.leaves(direct.args[0])
    assert all(x.shape == y.shape and x.dtype == y.dtype
               for x, y in zip(sa, sb))


def test_planned_engine_compiles_its_step_once():
    """engine.init places the state as the step's outputs are placed, so
    later steps reuse the first step's executable. An unplaced state is
    another input type to jit, and the step compiled twice."""
    from repro.configs.base import InputShape
    from repro.launch.train import build_train_engine, make_batch_fn
    arch = cfglib.get("deepseek-7b")
    api = arch.api(reduced=True)
    shape = InputShape("once_t", seq_len=16, global_batch=4, kind="train")
    engine = build_train_engine(api, arch, host_mesh(), shape,
                                mode="stale-psum", num_workers=2, s=1)
    next_batch = make_batch_fn(api, 4, 16, 0)
    state = engine.init(jax.random.PRNGKey(0))
    for _ in range(3):
        state, _ = engine.step(state, next_batch())
    assert engine._jit_step._cache_size() == 1


def test_batch_smaller_than_data_extent_replicates():
    """long_500k has global batch 1 < a multi-device data extent: the
    even-division fallback must drop the batch rule rather than emit an
    unpartitionable spec."""
    mesh = host_mesh()
    rules = rules_lib.rules_for_arch("deepseek-7b", shape=SHAPES["long_500k"],
                                    mesh=mesh)
    assert rules["batch"] == ("pod", "data")  # extent 1 divides everything
    fake_shape = dataclasses.replace(SHAPES["long_500k"], global_batch=3)

    class Wide:  # a mesh-alike with data extent 2 (planning needs axes only)
        axis_names = ("data", "model")
        devices = np.empty((2, 1))

    rules2 = rules_lib.rules_for_arch("deepseek-7b", shape=fake_shape,
                                     mesh=Wide())
    assert rules2["batch"] is None and rules2["cache_batch"] is None


def test_strip_data_keeps_model_axis_only():
    rules = rules_lib.rules_for(fsdp=True)
    stripped = rules_lib.strip_data(rules)
    assert stripped["embed"] is None
    assert stripped["batch"] is None
    assert stripped["heads"] == "model"


def test_prefill_and_decode_plans_are_abstract():
    mesh = host_mesh()
    for shape in ("prefill_32k", "decode_32k"):
        plan = planlib.build("deepseek-67b", shape, mesh)
        assert all(isinstance(l, jax.ShapeDtypeStruct)
                   for l in jax.tree.leaves(plan.args))
        assert plan.meta["kind"] == SHAPES[shape].kind

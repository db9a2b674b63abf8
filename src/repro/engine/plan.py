"""Mesh-aware sharding planner: (arch x input-shape x mesh) -> :class:`Plan`.

This module is what used to live in ``launch/steps.py``, folded into the
engine so every execution path — the dry-run, the trainer, the server, and
the benchmarks — lowers steps through ONE planning layer. A ``Plan`` bundles
a jit-able step function with its abstract arguments (ShapeDtypeStructs,
built via ``eval_shape`` — nothing here allocates device memory) and the
NamedShardings for inputs and outputs.

Train plans wrap a :class:`repro.engine.Engine`: the planned function is the
engine's own EngineState-level step, so the dynamic staleness bound and the
coherence-controller hook path work unchanged under sharded state. Placement
comes from ``sharding/rules.py`` — FSDP archs get the ZeRO-style
"embed" -> data rule; per-worker gradient ring buffers and simulate-mode
worker caches shard their leading worker axis over ("pod","data") with
model-axis-only rules on the parameter dims (a spec may not use a mesh axis
twice).

Entry points
------------
* ``build_engine(api, opt, cfg, mesh=mesh, arch=arch, shape=shape)``
  attaches a train plan to the returned engine (``engine.plan()`` /
  ``engine.lowered_step()``).
* ``make_train_engine(arch, shape, mesh, ...)`` — the one-call form the
  drivers use (legacy ``steps.build_train_step`` semantics).
* ``plan_prefill`` / ``plan_decode`` — inference step plans (no engine).
* ``build(arch_id, shape_name, mesh, ...)`` — kind dispatcher, the shape of
  the old ``steps.build``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro import configs as cfglib
from repro.configs.base import SHAPES, ArchDef, InputShape, ModelAPI
from repro.core import stale_sync, staleness
from repro.engine.api import Engine, EngineConfig, EngineState
from repro.optim import optimizers as optlib
from repro.sharding import rules as rules_lib
from repro.sharding.rules import FSDP_ARCHS  # noqa: F401  (re-exported)

ShapeLike = Union[str, InputShape]


@dataclasses.dataclass
class Plan:
    """Everything needed to lower one step (the old ``steps.Built``)."""
    fn: Callable
    args: tuple                 # ShapeDtypeStructs (positionally matching fn)
    in_shardings: tuple
    out_shardings: Any          # or None to let GSPMD choose outputs
    meta: dict
    # Positional args donated to the jitted step. Train plans donate the
    # EngineState (arg 0) unless EngineConfig(donate=False): XLA aliases the
    # ring buffer / opt state / params in-place instead of materialising a
    # full-state copy every step.
    donate_argnums: tuple = ()

    def jit(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self, mesh=None):
        with (mesh if mesh is not None else contextlib.nullcontext()):
            return self.jit().lower(*self.args)


def mode_label(kind: str, mode: Optional[str] = None,
               stale_s: Optional[int] = None) -> str:
    """The dry-run record key's mode component — shared by the planner and
    ``launch/dryrun.py`` so records stay idempotent across the refactor."""
    if kind != "train":
        return kind
    if mode in (None, "auto"):
        return f"stale_psum(s={stale_s})" if stale_s else "sync"
    if mode == "sync":
        return "sync"
    name = "stale_psum" if mode == "stale-psum" else mode
    return f"{name}(s={stale_s})"


# -- abstract state/axes helpers (moved from launch/steps.py) ---------------

def captured_axes(fn_returning_tree_and_axes):
    """eval_shape a ``key -> (tree, axes)`` initializer, returning both the
    ShapeDtypeStruct tree and the (static) logical-axes tree."""
    captured = {}

    def go(key):
        tree, axes = fn_returning_tree_and_axes(key)
        captured["axes"] = axes
        return tree

    shapes = jax.eval_shape(go, jax.random.PRNGKey(0))
    return shapes, captured["axes"]


def _is_axes_leaf(x):
    return (isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))


def _shardings(axes_tree, mesh, rules):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, rules_lib.spec_for(a, mesh, rules)),
        axes_tree, is_leaf=_is_axes_leaf)


def _replicated(mesh):
    return NamedSharding(mesh, PS())


def _opt_state_shardings(opt_state_shapes, params_shardings, mesh):
    """Moment trees mirror params; scalars replicate."""
    flat_params = jax.tree.leaves(params_shardings)

    def assign(subtree):
        leaves = jax.tree.leaves(subtree)
        if len(leaves) == len(flat_params):
            treedef = jax.tree.structure(subtree)
            return jax.tree.unflatten(treedef, flat_params)
        return jax.tree.map(lambda _: _replicated(mesh), subtree)

    return {k: assign(v) if isinstance(v, dict) or jax.tree.structure(v).num_leaves > 1
            else _replicated(mesh)
            for k, v in opt_state_shapes.items()}


def _batch_struct_and_shardings(api: ModelAPI, shape: InputShape, mesh, rules):
    spec = api.batch_spec(shape)
    axes = api.batch_axes(shape)
    shardings = {k: NamedSharding(mesh, rules_lib.spec_for(axes[k], mesh, rules))
                 for k in spec}
    return spec, shardings


def _lead(mesh, wax, *rest):
    """PS with an optional leading worker axis followed by ``rest`` parts."""
    return NamedSharding(mesh, PS(wax, *rest))


def place_delay_table(table, mesh):
    """Place a deterministic delay table for a mesh-aware engine: [T, P]
    tables shard their worker axis over ("pod","data") — each worker holds
    only its own delay column, like every other per-worker buffer. [T]
    tables (and worker counts that don't divide the data extent) replicate,
    mirroring the planner's even-division fallback."""
    arr = jnp.asarray(table, jnp.int32)
    wax = rules_lib.worker_axes(mesh)
    if (arr.ndim < 2 or wax is None
            or arr.shape[1] % rules_lib.data_extent(mesh)):
        return jax.device_put(arr, _replicated(mesh))
    return jax.device_put(arr, _lead(mesh, None, wax))


# -- the train plan ---------------------------------------------------------

def attach_train_plan(engine: Engine, api: ModelAPI, shape: ShapeLike, *,
                      arch_id: Optional[str] = None,
                      rules: Optional[dict] = None) -> Plan:
    """Compute the full sharding plan for a train engine and attach it.

    The planned fn is the engine's EngineState-level step; state and batch
    structures come from ``eval_shape`` over ``engine.init`` (no device
    memory). Called by ``build_engine`` when ``mesh`` and ``shape`` are
    given.
    """
    mesh = engine.mesh
    if mesh is None:
        raise ValueError("attach_train_plan needs an engine built with mesh=")
    if not (hasattr(api, "init") and hasattr(api, "batch_spec")):
        raise ValueError(
            "sharding plans need a ModelAPI (init/batch_spec/batch_axes) to "
            "derive state and batch structures; got a bare loss function — "
            "build the engine without shape=, or pass a ModelAPI")
    shape = SHAPES[shape] if isinstance(shape, str) else shape
    cfg = engine.cfg
    p = cfg.num_workers
    fsdp = arch_id in rules_lib.FSDP_ARCHS
    rules = rules or rules_lib.rules_for_arch(arch_id, shape=shape, mesh=mesh)
    wax = rules_lib.worker_axes(mesh)
    if (rules_lib.data_extent(mesh) > 1
            and not rules_lib.worker_axis_split(mesh, p)):
        wax = None  # jit args must divide evenly; replicate the worker axis

    params_shapes, params_axes = captured_axes(api.init)
    params_sh = _shardings(params_axes, mesh, rules)
    # Reuse the params structs so the (expensive, for 1T configs) abstract
    # trace of api.init is paid once, not again inside engine.init.
    state_struct = jax.eval_shape(lambda k, p: engine.init(k, params=p),
                                  jax.random.PRNGKey(0), params_shapes)
    inner = state_struct.inner
    opt_sh = _opt_state_shardings(inner.opt_state, params_sh, mesh) \
        if hasattr(inner, "opt_state") else None

    if cfg.mode == "sync":
        inner_sh = stale_sync.SyncTrainState(
            params=params_sh, opt_state=opt_sh, step=_replicated(mesh))
    elif cfg.mode in ("stale-psum", "ssp"):
        per_worker = cfg.mode == "ssp" or cfg.per_worker_delays
        if engine.meta.get("kernels", {}).get("delivery") == "packed":
            # Kernel-backed ring: ONE [slots(, P), D] array. The packed D
            # axis mixes leaves, so only the worker axis can shard; FSDP
            # archs never reach here (build_engine routes them to tree math).
            gbuf_sh = (_lead(mesh, None, wax, None) if per_worker
                       else _lead(mesh, None, None))
        else:
            # A per-worker buffer spends the data axis on its worker dim, so
            # its param dims must not reuse it (FSDP rules would).
            buf_rules = (rules_lib.strip_data(rules)
                         if (per_worker and fsdp) else rules)

            def buf_shard(a):
                base = rules_lib.spec_for(a, mesh, buf_rules)
                if per_worker:
                    return _lead(mesh, None, wax, *base)
                return _lead(mesh, None, *base)

            gbuf_sh = jax.tree.map(buf_shard, params_axes,
                                   is_leaf=_is_axes_leaf)
        inner_sh = stale_sync.StaleTrainState(
            params=params_sh, opt_state=opt_sh, gbuf=gbuf_sh,
            step=_replicated(mesh), key=_replicated(mesh))
    elif cfg.mode == "simulate":
        # [P, ...] worker caches: leading axis over data, model-only rules on
        # the param dims (the data axis is already spent on the worker dim).
        sim_rules = rules_lib.strip_data(rules)
        cache_sh = jax.tree.map(
            lambda a: _lead(mesh, wax, *rules_lib.spec_for(a, mesh, sim_rules)),
            params_axes, is_leaf=_is_axes_leaf)
        if engine.meta.get("kernels", {}).get("delivery") == "packed":
            # Packed pending: ring [P, slots, D] + the prefetched arrived
            # [P, D] row, both worker-sharded on their leading axis (the
            # packed D axis mixes leaves, so only the worker axis can shard
            # — the placement gate already vetoed model-sharded archs).
            pend_sh = {"ring": _lead(mesh, wax, None, None),
                       "arrived": _lead(mesh, wax, None)}
        else:
            pend_sh = jax.tree.map(
                lambda a: _lead(mesh, wax, None,
                                *rules_lib.spec_for(a, mesh, sim_rules)),
                params_axes, is_leaf=_is_axes_leaf)

        def lead_only(x):
            return _lead(mesh, wax, *([None] * (x.ndim - 1)))

        inner_sh = staleness.SimState(
            caches=cache_sh, pending=pend_sh,
            update_state=jax.tree.map(lead_only, inner.update_state),
            server_state=jax.tree.map(lead_only, inner.server_state),
            step=_replicated(mesh), key=_replicated(mesh))
    else:  # pragma: no cover — EngineConfig validates modes
        raise ValueError(f"no sharding plan for mode {cfg.mode!r}")

    if cfg.mode == "simulate":
        if shape.global_batch % p:
            raise ValueError(
                f"simulate mode needs global_batch divisible by num_workers "
                f"({shape.global_batch} % {p})")
        per = dataclasses.replace(shape, global_batch=shape.global_batch // p)
        flat_struct = api.batch_spec(per)
        batch_struct = {
            k: jax.ShapeDtypeStruct((p,) + s.shape, s.dtype)
            for k, s in flat_struct.items()}
        batch_sh = {k: _lead(mesh, wax, *([None] * s.ndim))
                    for k, s in flat_struct.items()}
    else:
        batch_struct, batch_sh = _batch_struct_and_shardings(
            api, shape, mesh, rules)

    # Compensation state (repro.compensate): sparsification runs per SOURCE
    # before transport, so every per-source mode (simulate and the
    # per-worker-delay ring modes) carries [P, D] error-feedback
    # residual/momentum rows — 2-D comp leaves — which shard their leading
    # worker axis like every other per-worker buffer (the packed D axis
    # mixes leaves, so only the worker axis can shard). Aggregate [D]
    # residuals and the scalar mu/L signals replicate. Donation below
    # covers it — the EF state is rewritten in place every step, exactly
    # like the gradient ring.
    def comp_shard(leaf):
        if getattr(leaf, "ndim", 0) == 2:
            return _lead(mesh, wax, None)
        return _replicated(mesh)

    comp_sh = jax.tree.map(comp_shard, state_struct.comp)
    state_sh = EngineState(inner=inner_sh, bound=_replicated(mesh),
                           comp=comp_sh)
    # Donate the state where aliasing actually elides work: the ring-buffer
    # modes carry a [slots(, P), ...] gbuf of which ONE slot changes per
    # step — undonated, XLA materialises the whole ring afresh every step.
    # sync rewrites params/moments wholesale and tree-mode simulate ROLLS
    # its pending ring (every element rewritten), so there donation elides
    # nothing and jax's per-call donated-buffer bookkeeping is pure overhead
    # — skipped. PACKED simulate addresses its [P, slots, D] ring with a
    # rotating cursor (one slot zeroed + scatter-add per step, no roll), so
    # it donates like the gradient-ring modes.
    packed = engine.meta.get("kernels", {}).get("delivery") == "packed"
    donate = cfg.donate and (cfg.mode in ("stale-psum", "ssp")
                             or (cfg.mode == "simulate" and packed))
    plan = Plan(
        fn=engine._wrap,
        args=(state_struct, batch_struct),
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,) if donate else (),
        meta={"arch": arch_id, "shape": shape.name, "kind": "train",
              "mode": mode_label("train", cfg.mode, cfg.s),
              "engine_mode": cfg.mode, "s": cfg.s, "workers": p,
              "kernels": engine.meta.get("kernels"),
              "compensate": engine.meta.get("compensate"),
              "donate": donate},
    )
    engine._attach_plan(plan)
    return plan


def make_train_engine(arch: Union[str, ArchDef], shape: ShapeLike, mesh, *,
                      ecfg: Optional[EngineConfig] = None,
                      mode: Optional[str] = None,
                      stale_s: Optional[int] = None,
                      num_workers: Optional[int] = None,
                      optimizer_name: Optional[str] = None,
                      remat_override: Optional[bool] = None,
                      overrides: Optional[dict] = None,
                      reduced: bool = False,
                      rules: Optional[dict] = None,
                      **engine_kw) -> Engine:
    """One call from (arch x shape x mesh) to a plan-carrying train engine.

    ``stale_s`` keeps the legacy ``steps.build_train_step`` semantics: None/0
    -> the synchronous baseline, >= 1 -> the paper's stale-psum step with
    that bound (unless ``mode`` selects another regime explicitly). Extra
    ``engine_kw`` (``ssp_steps``, ``delay=...``, ...) land on EngineConfig;
    pass a full ``ecfg`` to control everything.
    """
    from repro.engine.api import build_engine  # local: api lazily imports us

    arch = cfglib.get(arch) if isinstance(arch, str) else arch
    shape = SHAPES[shape] if isinstance(shape, str) else shape
    assert shape.kind == "train", shape.name
    overrides = dict(overrides or {})
    if remat_override is not None:
        overrides["remat"] = remat_override
    api = arch.api(reduced=reduced, overrides=overrides or None)
    opt_name = optimizer_name or arch.train_optimizer

    if ecfg is not None:
        clashing = {k: v for k, v in dict(
            mode=mode, stale_s=stale_s, num_workers=num_workers,
            **engine_kw).items() if v is not None}
        if clashing:
            raise ValueError(
                f"ecfg= fully specifies the engine; also passing "
                f"{sorted(clashing)} would be silently ignored")
    if ecfg is None:
        if mode in (None, "auto"):
            mode = "sync" if not stale_s else "stale-psum"
        s = 0 if mode == "sync" else (
            stale_s if stale_s is not None else arch.stale_s_default)
        kw = dict(engine_kw)
        if mode == "stale-psum":
            # FSDP archs shard params over 'data' already, so the per-worker
            # buffer axis cannot also use it; they get the aggregate-buffer
            # form (the Theorem-1 single-tau update — P-fold less memory).
            kw.setdefault("per_worker_delays",
                          arch.arch_id not in rules_lib.FSDP_ARCHS)
        ecfg = EngineConfig(
            mode=mode, s=s,
            num_workers=num_workers or rules_lib.data_extent(mesh),
            buffer_dtype=getattr(api.cfg, "param_dtype", jnp.float32), **kw)

    # The fused-Adam hot spot is an optimizer-construction opt-in, built
    # AFTER the engine config resolves the kernel mode and gated on the same
    # placement verdict as the delivery (a packed [D] view of FSDP/model-
    # sharded params would all-gather the full parameter set every step).
    from repro.engine.api import kernel_placement_ok
    fuse_adam = (opt_name == "adam"
                 and kernel_placement_ok(ecfg.kernels, arch, mesh)[0])
    opt = optlib.get_optimizer(opt_name, **({"kernel": True} if fuse_adam
                                            else {}))

    engine = build_engine(api, opt, ecfg, mesh=mesh, arch=arch, shape=shape,
                          rules=rules)
    engine.plan().meta["optimizer"] = opt_name
    return engine


# -- inference plans (no staleness, hence no engine) ------------------------

def _resolve(arch, shape, reduced, overrides, long_ctx=False):
    arch = cfglib.get(arch) if isinstance(arch, str) else arch
    shape = SHAPES[shape] if isinstance(shape, str) else shape
    api = arch.api(reduced=reduced, long_ctx=long_ctx, overrides=overrides)
    return arch, shape, api


def plan_prefill(arch: Union[str, ArchDef], shape: ShapeLike, mesh,
                 overrides: Optional[dict] = None,
                 reduced: bool = False) -> Plan:
    arch, shape, api = _resolve(arch, shape, reduced, overrides)
    assert shape.kind == "prefill", shape.name
    rules = rules_lib.rules_for_arch(arch.arch_id, shape=shape, mesh=mesh)

    params_shapes, params_axes = captured_axes(api.init)
    params_sh = _shardings(params_axes, mesh, rules)
    batch_struct, batch_sh = _batch_struct_and_shardings(api, shape, mesh, rules)

    _, cache_axes = captured_axes(
        lambda key: api.init_cache(shape.global_batch, shape.seq_len))
    cache_sh = _shardings(cache_axes, mesh, rules)

    def prefill(params, batch):
        return api.prefill(params, batch)

    return Plan(
        fn=prefill,
        args=(params_shapes, batch_struct),
        in_shardings=(params_sh, batch_sh),
        out_shardings=(
            NamedSharding(mesh, rules_lib.spec_for(("batch", None, None), mesh, rules)),
            cache_sh),
        meta={"arch": arch.arch_id, "shape": shape.name, "kind": "prefill",
              "seq_len": shape.seq_len, "batch": shape.global_batch},
    )


def plan_decode(arch: Union[str, ArchDef], shape: ShapeLike, mesh,
                overrides: Optional[dict] = None,
                reduced: bool = False) -> Plan:
    long_ctx = (shape if isinstance(shape, str)
                else shape.name) == "long_500k"
    arch, shape, api = _resolve(arch, shape, reduced, overrides,
                                long_ctx=long_ctx)
    assert shape.kind == "decode", shape.name
    rules = rules_lib.rules_for_arch(arch.arch_id, shape=shape, mesh=mesh)

    params_shapes, params_axes = captured_axes(api.init)
    params_sh = _shardings(params_axes, mesh, rules)

    cache_shapes, cache_axes = captured_axes(
        lambda key: api.init_cache(shape.global_batch, shape.seq_len))
    cache_sh = _shardings(cache_axes, mesh, rules)

    token_struct = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    token_sh = NamedSharding(mesh, rules_lib.spec_for(("batch", None), mesh, rules))
    pos_struct = jax.ShapeDtypeStruct((), jnp.int32)

    def decode(params, token, cache, pos):
        return api.decode(params, token, cache, pos)

    return Plan(
        fn=decode,
        args=(params_shapes, token_struct, cache_shapes, pos_struct),
        in_shardings=(params_sh, token_sh, cache_sh, _replicated(mesh)),
        out_shardings=(None, cache_sh),
        meta={"arch": arch.arch_id, "shape": shape.name, "kind": "decode",
              "seq_len": shape.seq_len, "batch": shape.global_batch,
              "long_ctx": long_ctx},
    )


def resolve_serve_paged(api: ModelAPI, layout, arch=None, mesh=None,
                        paged: str = "auto"):
    """Resolve the serve decode route -> ``(route, why)`` with route one of
    ``"paged"`` (in-place page-table attention kernel), ``"gather"`` (the
    gather -> decode -> scatter reference), or ``"resident"`` (no token-major
    leaves at all — SSM state rewrites wholesale; trivially "in place").

    ``paged`` follows the training kernels' tri-state: ``"off"`` forces the
    gather reference, ``"auto"`` takes the paged path only where the same
    ``kernel_placement_ok`` verdict would fuse a training kernel (and the
    model family implements ``decode_paged``), ``"on"`` overrides the
    model-axis veto and raises where the paged path cannot run at all."""
    if paged not in ("off", "auto", "on"):
        raise ValueError(f"paged={paged!r}: expected off/auto/on")
    if not layout.has_tokens:
        return "resident", "no token-major cache leaves"
    if paged == "off":
        return "gather", "config off"
    if api.decode_paged is None:
        if paged == "on":
            raise ValueError(
                f"paged='on' but family {api.family!r} has no decode_paged")
        return "gather", f"family {api.family!r} has no decode_paged"
    from repro.engine.api import kernel_placement_ok
    ok, why = kernel_placement_ok(paged, arch, mesh)
    if not ok:
        if paged == "on":
            raise ValueError(f"paged='on' vetoed by placement: {why}")
        return "gather", why
    return "paged", ""


def plan_serve_step(arch: Union[str, ArchDef], shape: ShapeLike, mesh, *,
                    layout, num_pages: int,
                    overrides: Optional[dict] = None,
                    reduced: bool = False, paged: str = "off") -> Plan:
    """Continuous-batching decode step for the serving plane.

    One jitted call advances every occupied slot by one token against the
    paged cache (``repro.serving.cache.PageLayout`` — passed duck-typed to
    keep the planner model-agnostic). Two routes, resolved by
    :func:`resolve_serve_paged` from ``paged="off"|"auto"|"on"``:

    * **gather** (the bitwise reference): page-table gather -> per-slot
      batch-1 ``api.decode`` under ``vmap`` (each slot carries its own
      position, which the shared-scalar-``pos`` decode contract can't
      express batch-wide) -> cursor-addressed whole-page scatter.
    * **paged**: resident leaves unpack, but the K/V ring stays put —
      ``api.decode_paged`` reads it in place through the page-table
      attention kernel (``kernels/paged_attention``) and the step scatters
      ONE [W] row per slot instead of a whole page. Null-page table entries
      are masked in-kernel, so slots may hold only the pages their request
      touches (lazy allocation) and ``max_seq`` is no longer bounded by what
      a slot's gathered contiguous ring can hold.

    Slots excluded by ``mask`` still occupy lanes but are inert: their
    sampled token is discarded and their cache write is routed to the null
    page, so membership changes between steps never retrace. The page and
    resident buffers are donated — the cache is updated in place like the
    engine's gradient ring.

    ``shape.global_batch`` is the slot count; ``temp`` <= 0 selects greedy
    argmax, > 0 temperature sampling (one fold-in key per slot).
    """
    from repro.kernels import dispatch
    arch, shape, api = _resolve(arch, shape, reduced, overrides)
    assert shape.kind == "decode", shape.name
    rules = rules_lib.rules_for_arch(arch.arch_id, shape=shape, mesh=mesh)
    slots = shape.global_batch
    route, route_why = resolve_serve_paged(api, layout, arch, mesh, paged)
    dispatch.note("serve_decode", route, route_why)

    params_shapes, params_axes = captured_axes(api.init)
    params_sh = _shardings(params_axes, mesh, rules)
    rep = _replicated(mesh)

    f32, i32 = jnp.float32, jnp.int32
    pages_struct = jax.ShapeDtypeStruct(
        (num_pages + 1, layout.page_tokens, layout.width), f32)
    res_struct = jax.ShapeDtypeStruct((slots, layout.res_width), f32)
    tables_struct = jax.ShapeDtypeStruct(
        (slots, max(layout.pages_per_slot, 1)), i32)
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
    temp_struct = jax.ShapeDtypeStruct((), f32)

    def serve_step(params, pages, resident, tables, tokens, pos, mask, key,
                   temp):
        cache = layout.gather(pages, resident, tables)   # [S, ...] leaves
        keys = jax.random.split(key, slots)

        def one(tok, slot_cache, p, k):
            logits, new_cache = api.decode(params, tok[None, None],
                                           slot_cache, p)
            logits = logits[0, -1].astype(jnp.float32)
            greedy = jnp.argmax(logits).astype(i32)
            sampled = jax.random.categorical(
                k, logits / jnp.maximum(temp, 1e-6)).astype(i32)
            return jnp.where(temp > 0.0, sampled, greedy), new_cache

        next_tok, new_caches = jax.vmap(one)(tokens, cache, pos, keys)
        pages, resident = layout.scatter_token(
            pages, resident, new_caches, tables, pos, mask)
        return jnp.where(mask, next_tok, tokens), pages, resident

    def serve_step_paged(params, pages, resident, tables, tokens, pos, mask,
                         key, temp):
        cache = layout.unpack_resident(resident)         # token leaves None
        kv = layout.paged_kv(pages, tables, pos)
        logits, new_cache = api.decode_paged(params, tokens[:, None],
                                             cache, pos, kv)
        logits = logits[:, -1].astype(jnp.float32)
        keys = jax.random.split(key, slots)

        def one(lg, k):                                   # mirrors the
            greedy = jnp.argmax(lg).astype(i32)           # gather route's
            sampled = jax.random.categorical(             # per-slot draws
                k, lg / jnp.maximum(temp, 1e-6)).astype(i32)
            return jnp.where(temp > 0.0, sampled, greedy)

        next_tok = jax.vmap(one)(logits, keys)
        pages, resident = layout.scatter_rows(
            pages, resident, new_cache, tables, pos, mask)
        return jnp.where(mask, next_tok, tokens), pages, resident

    return Plan(
        fn=serve_step_paged if route == "paged" else serve_step,
        args=(params_shapes, pages_struct, res_struct, tables_struct,
              vec(i32), vec(i32), vec(jnp.bool_), key_struct, temp_struct),
        in_shardings=(params_sh, rep, rep, rep, rep, rep, rep, rep, rep),
        out_shardings=(rep, rep, rep),
        donate_argnums=(1, 2),
        meta={"arch": arch.arch_id, "shape": shape.name, "kind": "serve",
              "slots": slots, "seq_len": shape.seq_len,
              "cache_tokens": layout.tokens,
              "page_tokens": layout.page_tokens,
              "pages": num_pages, "resident_width": layout.res_width,
              "paged": route, "paged_why": route_why},
    )


def build(arch_id: str, shape_name: str, mesh, *,
          stale_s: Optional[int] = None, mode: Optional[str] = None,
          optimizer_name: Optional[str] = None,
          remat_override: Optional[bool] = None,
          overrides: Optional[dict] = None,
          num_workers: Optional[int] = None, **engine_kw) -> Plan:
    """Kind dispatcher with the legacy ``steps.build`` call shape."""
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return make_train_engine(
            arch_id, shape_name, mesh, mode=mode, stale_s=stale_s,
            num_workers=num_workers, optimizer_name=optimizer_name,
            remat_override=remat_override, overrides=overrides,
            **engine_kw).plan()
    if kind == "prefill":
        return plan_prefill(arch_id, shape_name, mesh, overrides=overrides)
    return plan_decode(arch_id, shape_name, mesh, overrides=overrides)

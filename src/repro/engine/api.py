"""Unified execution surface over every staleness regime in the repo.

The paper treats synchronous, bounded-async, and SSP training as points on
one staleness axis; this module makes the code match: one
``EngineConfig(mode=...)`` + ``build_engine(...)`` pair replaces the four
incompatible per-regime APIs (``core/staleness.py``, ``core/stale_sync.py``,
``core/ssp.py``, and the hand-rolled loops that consumed them).

Modes
-----
* ``simulate``   — the paper's Section-3 per-worker-cache simulator
                   (``core/staleness.py``); batches carry a leading worker
                   axis ``[P, b, ...]``.
* ``stale-psum`` — Theorem-1 delayed-gradient data parallelism
                   (``core/stale_sync.py``); batches are flat global batches
                   reshaped to per-worker shards inside the step.
* ``ssp``        — Stale Synchronous Parallel as a *real* execution mode:
                   ``core/ssp.py`` clock semantics are converted into a
                   per-step delay schedule fed to the delayed-gradient step.
* ``sync``       — the buffer-free synchronous baseline (s = 0).

All modes share the same object surface: ``engine.init(key) -> state``,
``engine.step(state, batch) -> (state, metrics)``, ``engine.params(state)``
for the evaluation view, and ``engine.with_staleness(state, s)`` for dynamic
staleness control (the coherence controller clamps the delay bound at
runtime without rebuilding buffers).  ``Trainer`` (trainer.py) supplies the
loop + hooks that the benchmarks, the train driver, and the examples share.

Every mode delegates to the existing ``repro.core`` step builders, so legacy
trajectories are reproduced bit-for-bit (tested in test_engine_api.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compensate as compensate_lib
from repro import delays as delays_lib
from repro import scopes
from repro.core import ssp as ssp_lib
from repro.core import stale_sync, staleness
from repro.delays.models import DelaySpec, UniformDelay
from repro.optim import optimizers as optlib
from repro.sharding import rules as rules_lib

Pytree = Any

MODES = ("simulate", "stale-psum", "ssp", "sync")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One config for every staleness regime.

    ``s`` is the staleness bound: for ``simulate`` it parameterises
    ``UniformDelay(s)`` (delays r in [0, s-1]) unless ``delay`` overrides it;
    for ``stale-psum`` it sizes the gradient ring buffer; for ``ssp`` it is
    the SSP clock-drift bound; ``sync`` ignores it.
    """
    mode: str = "sync"
    num_workers: int = 1
    s: int = 0
    # Any repro.delays spec, honored uniformly by every mode: samplers
    # (Uniform/Geometric/Constant/Zero) and MultiPod for the sampled modes,
    # Schedule/Trace (deterministic tables, measured wall-time replays) for
    # any per-worker mode including ssp; a raw [T, P] / [T] array coerces to
    # a Schedule. None = UniformDelay(s) (sampled modes) / the lognormal
    # speed model (ssp). sync is delay-free and accepts only bound-0 specs.
    delay: Optional[DelaySpec] = None
    # Kernel-backed hot path (repro.kernels.dispatch): "off" keeps the
    # legacy per-leaf tree math (bitwise legacy trajectories); "auto" routes
    # the ring-buffer delivery through the packed fused kernels where the
    # sharding placement allows it (falls back to tree math otherwise, e.g.
    # FSDP archs whose buffer must shard param dims over 'data'); "on"
    # requires the packed path and raises where it is unsupported.
    kernels: str = "off"
    # Donate the EngineState to the PLANNED jitted step (ring buffer, opt
    # state, params reuse their buffers instead of a full-state copy each
    # step). Escape hatch for callers that re-step a held state.
    donate: bool = True
    # Staleness compensation (repro.compensate), honored by all four modes:
    # lr_scale scales each step's effective stepsize from the REALIZED delay
    # ("inverse" = Zhang-Gupta 1/tau) or the Theorem-1 formula on live mu/L
    # signals ("theorem1", fed via Engine.with_lr_signals / CoherenceHook);
    # compress EF-sparsifies the transported gradient/update ("topk:K" keeps
    # fraction K (0<K<1) or K elements, "thresh:V" keeps |g| >= V), with the
    # packed residual carried in EngineState.comp. Both "none" (default) are
    # bitwise-identical to the uncompensated engine.
    lr_scale: str = "none"
    compress: str = "none"
    # DGC-style masked momentum correcting the EF sparsifier (beta in
    # [0, 1); 0 = plain EF). Needs compress != "none"; the masked velocity
    # rides in EngineState.comp next to the residual.
    ef_momentum: float = 0.0
    # One-pass fused update megakernel (repro.kernels.dispatch.fused_update):
    # EF split, stale delivery, and the Adam moment/param update run as a
    # single pass over the packed [D] view with the Adam moments stored
    # packed in the optimizer state. "auto" engages wherever supported (an
    # Adam-spec optimizer on a packed delivery path — or sync mode under the
    # same placement gate as `kernels`) and falls back to the three-dispatch
    # path otherwise; "on" raises where unsupported; "off" never fuses.
    megakernel: str = "auto"
    # stale-psum extras (see StaleSyncConfig):
    per_worker_delays: bool = True
    buffer_dtype: Any = jnp.float32
    # simulate extras (see StalenessConfig):
    server_side: bool = False
    loss_takes_key: bool = False         # loss_fn(params, batch, key) losses
    # ssp extras: worker-speed model the clock schedule is derived from.
    ssp_speeds: Optional[Any] = None     # [T, P] durations; sampled if None
    ssp_steps: int = 512
    ssp_mean_dur: float = 1.0
    ssp_cv: float = 0.5
    ssp_seed: int = 0

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; have {MODES}")
        if self.num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if self.s < 0:
            raise ValueError(f"staleness bound s must be >= 0, got {self.s}")
        if self.kernels not in ("off", "auto", "on"):
            raise ValueError(f"kernels must be 'off'|'auto'|'on', "
                             f"got {self.kernels!r}")
        if self.megakernel not in ("off", "auto", "on"):
            raise ValueError(f"megakernel must be 'off'|'auto'|'on', "
                             f"got {self.megakernel!r}")
        # Validates lr_scale/compress/ef_momentum grammar (raises on bad
        # specs).
        compensate_lib.CompensateConfig(lr_scale=self.lr_scale,
                                        compress=self.compress, s=self.s,
                                        ef_momentum=self.ef_momentum)
        object.__setattr__(self, "delay", delays_lib.as_spec(self.delay))
        if self.delay is not None:
            if self.mode == "sync" and getattr(self.delay, "bound", None) != 0:
                raise ValueError(
                    "sync mode is delay-free: only a bound-0 spec "
                    "(delays.Zero()) is accepted — misconfiguration "
                    "rejected rather than silently ignored")
            if self.mode == "ssp" and not isinstance(
                    self.delay, (delays_lib.Schedule, delays_lib.Trace)):
                raise ValueError(
                    "ssp derives its delays from a clock schedule: pass "
                    "delays.Trace(...) (measured wall-times), "
                    "delays.Schedule(...) (explicit table), or delay=None "
                    "for the lognormal speed model")
            if (isinstance(self.delay, delays_lib.Trace)
                    and self.delay.bound is None and self.mode != "ssp"):
                raise ValueError(
                    "Trace needs an explicit bound= outside mode='ssp' "
                    "(it sizes the delivery ring)")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    """Mode-specific state plus the dynamic staleness bound.

    ``bound`` is the inclusive max *delay* currently allowed (clamps whatever
    the delay model / schedule produces); it starts at the config's static
    bound and is lowered/raised via ``Engine.with_staleness``.

    ``comp`` is the compensation layer's state (repro.compensate): the
    packed error-feedback residual plus the live mu/L signals of the
    theorem1 LR policy. ``()`` — no leaves, hence no compiled-step change —
    whenever ``lr_scale`` and ``compress`` are both ``"none"``.
    """
    inner: Pytree
    bound: jax.Array  # int32
    comp: Pytree = ()


@dataclasses.dataclass
class Engine:
    """Uniform handle returned by ``build_engine`` — see module docstring."""
    cfg: EngineConfig
    mesh: Any = None
    meta: dict = dataclasses.field(default_factory=dict)
    # wired by build_engine:
    _init_inner: Callable = None   # (params, update_state, key) -> inner
    _step_inner: Callable = None   # (inner, batch, bound, comp)
    #                                -> (inner, comp, metrics)
    _params_of: Callable = None    # inner -> params eval view
    _init_params: Callable = None  # key -> params (None when caller supplies)
    _max_bound: int = 0
    _plan: Any = None              # sharding plan (engine/plan.py), if any
    _init_comp: Callable = None    # params -> comp state (None = no comp)

    def __post_init__(self):
        self._jit_step = jax.jit(
            lambda state, batch: self._wrap(state, batch))

    def _attach_plan(self, plan) -> None:
        """Adopt a sharding plan: the jitted step gains explicit in/out
        NamedShardings so state and batches are placed on the mesh, and —
        unless ``cfg.donate=False`` — donates the EngineState argument so
        the ring buffer / optimizer state / params reuse their buffers
        instead of being copied whole every step."""
        self._plan = plan
        self._jit_step = jax.jit(self._wrap,
                                 in_shardings=plan.in_shardings,
                                 out_shardings=plan.out_shardings,
                                 donate_argnums=plan.donate_argnums)

    def _wrap(self, state: EngineState, batch):
        inner, comp, metrics = self._step_inner(state.inner, batch,
                                                state.bound, state.comp)
        return EngineState(inner=inner, bound=state.bound, comp=comp), metrics

    # -- lifecycle ---------------------------------------------------------
    def init(self, key: jax.Array, params: Pytree = None,
             update_state: Pytree = None) -> EngineState:
        """Initialise engine state. ``params`` overrides the model's own
        initialiser (required when the engine was built from a bare loss
        function); ``update_state`` overrides the per-worker algorithm state
        in ``simulate`` mode (defaults to ``optimizer.init(params)``).

        ``key`` seeds both the param init and the engine's delay/update
        stream, exactly as the legacy drivers did — given the same key the
        engine reproduces legacy trajectories bit-for-bit (tested)."""
        if params is None:
            if self._init_params is None:
                raise ValueError(
                    "engine built from a bare loss function: pass params= "
                    "(or build from a ModelAPI, which knows how to init)")
            params = self._init_params(key)
        inner = self._init_inner(params, update_state, key)
        comp = self._init_comp(params) if self._init_comp is not None else ()
        state = EngineState(inner=inner, bound=jnp.int32(self._max_bound),
                            comp=comp)
        if self._plan is not None:
            # Placed as the step's outputs are: an unplaced state is another
            # input type to jit, and the second step would compile again.
            state = jax.device_put(state, self._plan.in_shardings[0])
        return state

    def step(self, state: EngineState, batch) -> Tuple[EngineState, dict]:
        """One engine step (jit-compiled): ``(state, batch) -> (state, metrics)``."""
        return self._jit_step(state, batch)

    # -- sharding plan -----------------------------------------------------
    def plan(self):
        """The (arch x shape x mesh) sharding plan — abstract args plus
        NamedShardings for one step (see ``repro.engine.plan.Plan``)."""
        if self._plan is None:
            raise ValueError(
                "engine has no sharding plan: build it with "
                "build_engine(..., mesh=mesh, arch=arch, shape=shape) or "
                "repro.engine.plan.make_train_engine(...)")
        return self._plan

    def lowered_step(self):
        """Lower one sharded step on the engine's mesh (dry-run entry)."""
        return self.plan().lower(self.mesh)

    def compiled_step_text(self, state: EngineState, batch) -> str:
        """The compiled step's HLO text. ``state`` and ``batch`` may be
        arrays or ``jax.ShapeDtypeStruct``s (``engine.plan().args``).

        The persistent compilation cache keys a program without its
        metadata, so an executable loaded from it may carry the op names of
        another build of the same program; this compile keys on the
        metadata too, and its text names this build's scopes."""
        flag = "jax_compilation_cache_include_metadata_in_key"
        prev = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            return self._jit_step.lower(state, batch).compile().as_text()
        finally:
            jax.config.update(flag, prev)

    def op_layers(self, state: EngineState, batch) -> Dict[str, str]:
        """Every instruction of the compiled step, nested computations
        included, mapped to ``forward``, ``backward``, ``ring``,
        ``optimizer`` or ``other`` by the named scope it was traced under
        (``repro.scopes``). A device trace names each operation by its
        instruction, so this map reads a trace by layer."""
        return scopes.op_layers(self.compiled_step_text(state, batch))

    # -- views -------------------------------------------------------------
    def params(self, state: EngineState) -> Pytree:
        """The evaluation view of the model (worker 0's cache in ``simulate``
        mode, the global params otherwise)."""
        return self._params_of(state.inner)

    def step_count(self, state: EngineState) -> jax.Array:
        return state.inner.step

    @property
    def batches_per_step(self) -> int:
        """Worker batches consumed per engine step (the paper's accounting)."""
        return self.cfg.num_workers

    # -- kernel dispatch ----------------------------------------------------
    def dispatch_report(self) -> dict:
        """Which hot spots run fused vs ref: the engine-level routing verdict
        (``delivery``, engine-specific) plus the per-op backend decisions the
        dispatch layer recorded at trace time. Decisions are a PROCESS-WIDE
        trace log (one entry per op, last trace wins): a second engine whose
        step hits the jit cache records nothing new, and entries traced by
        other engines in the same process remain visible."""
        from repro.kernels import dispatch
        info = dict(self.meta.get("kernels", {"config": self.cfg.kernels}))
        info["decisions"] = dispatch.report()
        return info

    # -- dynamic staleness control ----------------------------------------
    def with_staleness(self, state: EngineState, s) -> EngineState:
        """Clamp the engine to an effective staleness bound ``s`` (0 =
        synchronous behavior) without rebuilding buffers. In ``simulate``
        mode a bound of s means delays r <= s-1 (UniformDelay semantics); in
        the gradient modes it means gradient age d <= s."""
        if self.cfg.mode == "simulate":
            b = jnp.maximum(jnp.asarray(s, jnp.int32) - 1, 0)
        else:
            b = jnp.asarray(s, jnp.int32)
        return dataclasses.replace(
            state, bound=jnp.minimum(b, jnp.int32(self._max_bound)))

    def with_lr_signals(self, state: EngineState, mu, lip=None) -> EngineState:
        """Refresh the theorem1 LR policy's live curvature signals without
        rebuilding the engine: ``mu`` is the Definition-1 coherence estimate,
        ``lip`` an (optional) Lipschitz estimate — both ride in
        ``EngineState.comp`` and trace into the jitted step, exactly like the
        dynamic staleness bound. The CoherenceHook pulls this lever from the
        probe-gradient dots every observation."""
        if not (isinstance(state.comp, dict) and "mu" in state.comp):
            raise ValueError(
                "engine carries no live LR signals: build it with "
                "EngineConfig(lr_scale='theorem1')")
        comp = {**state.comp, "mu": jnp.asarray(mu, jnp.float32)}
        if lip is not None:
            comp["lip"] = jnp.asarray(lip, jnp.float32)
        return dataclasses.replace(state, comp=comp)


def kernel_placement_ok(kernels: str, arch=None, mesh=None) -> Tuple[bool, str]:
    """Can packed flat [D] views keep this (arch, mesh) placement?

    Shared verdict for every packed hot spot (ring delivery AND the fused
    optimizer): FSDP archs shard param dims over 'data' and a mesh with a
    model axis > 1 shards them over 'model' — a packed view mixes leaves,
    so either placement would be silently replaced by per-step all-gathers.
    XLA cannot partition a compiled Mosaic kernel, so on a TPU no packed
    kernel runs on a mesh of several devices (the interpreter lowers to
    plain, partitionable HLO).
    Returns ``(ok, why_not)``; ``kernels="on"`` overrides the model-axis
    veto (an explicit, profiled choice) but never the FSDP or Mosaic ones.
    """
    if kernels == "off":
        return False, "config off"
    from repro.kernels import dispatch
    arch_id = getattr(arch, "arch_id", arch)
    if arch_id in rules_lib.FSDP_ARCHS:
        return False, "FSDP placement"
    if (mesh is not None and mesh.devices.size > 1
            and not dispatch.interpret_mode()):
        return False, (f"compiled Pallas kernels cannot be partitioned over "
                       f"{mesh.devices.size} devices")
    if kernels == "auto" and mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get("model", 1) > 1:
            return False, f"model axis extent {sizes['model']}"
    return True, ""


def _place_table(table, mesh):
    """Worker-shard a [T, P] delay table on mesh-aware engines (the table is
    closed over by the step, so it must be placed before tracing)."""
    if mesh is None:
        return table
    from repro.engine import plan as plan_lib  # lazy: plan imports us
    return plan_lib.place_delay_table(table, mesh)


def _mean_over_workers(metrics: dict) -> dict:
    """simulate-mode update_fns report per-worker metric rows [P, ...];
    reduce to scalars so all modes emit a uniform metrics dict."""
    return jax.tree.map(
        lambda v: v.mean(axis=0) if getattr(v, "ndim", 0) >= 1 else v, metrics)


def build_engine(api_or_loss, optimizer: Optional[optlib.Optimizer],
                 cfg: EngineConfig, mesh=None, *,
                 update_fn=None, server_apply=None,
                 arch=None, shape=None, rules=None) -> Engine:
    """Build a uniform :class:`Engine` for any mode.

    ``api_or_loss`` is either a ``ModelAPI`` (anything with ``.loss`` and
    ``.init``) or a bare ``loss_fn(params, batch)`` (pass
    ``cfg.loss_takes_key=True`` for ``loss_fn(params, batch, key)``).
    ``update_fn`` bypasses the loss/optimizer adaptation entirely for
    ``simulate`` mode (e.g. the LDA Gibbs sampler's count-delta updates).

    ``mesh`` makes the engine mesh-aware: together with ``shape`` (an
    ``InputShape`` or name) and optionally ``arch`` (ArchDef or arch_id, for
    FSDP placement) it computes the full sharding plan — ``engine.plan()``
    and ``engine.lowered_step()`` — and jits the step with explicit
    NamedShardings (see ``repro/engine/plan.py``). The step math itself is
    mesh-agnostic — GSPMD inserts collectives when state is sharded over the
    data axis.
    """
    loss, init_params = None, None
    if api_or_loss is not None and hasattr(api_or_loss, "loss"):
        loss = api_or_loss.loss
        init_params = lambda key: api_or_loss.init(key)[0]
    elif callable(api_or_loss):
        loss = api_or_loss
    elif api_or_loss is not None:
        raise TypeError(f"api_or_loss must be a ModelAPI or a loss fn, "
                        f"got {type(api_or_loss)!r}")

    mode = cfg.mode
    meta = {"mode": mode, "workers": cfg.num_workers, "s": cfg.s}

    # Kernel routing verdict for the ring-buffer delivery (the stale_accum
    # hot spot): the gradient ring (stale-psum / ssp) AND the simulate-mode
    # pending ring route through the same packed path under the same
    # placement gate. FSDP archs shard buffer param dims over 'data'; a
    # packed [.., D] buffer cannot keep that placement, so "auto" falls back
    # to tree math there and "on" refuses. simulate's server_side transform
    # consumes per-leaf arrivals, so it stays on tree math too.
    kernel_delivery, why = False, ""
    if cfg.kernels != "off" and mode in ("stale-psum", "ssp", "simulate"):
        if mode == "simulate" and cfg.server_side:
            why = "server_side transform"
            if cfg.kernels == "on":
                raise ValueError(
                    "kernels='on' is unsupported with server_side=True: the "
                    "server transform consumes per-leaf arrivals; use "
                    "kernels='auto' (falls back to tree math)")
        else:
            kernel_delivery, why = kernel_placement_ok(cfg.kernels, arch, mesh)
            if not kernel_delivery and cfg.kernels == "on":
                arch_id = getattr(arch, "arch_id", arch)
                raise ValueError(
                    f"kernels='on' is unsupported for arch {arch_id!r} on "
                    f"this placement ({why}): the packed ring buffer cannot "
                    "run there; use kernels='auto' (falls back to tree math)")
    if mode in ("stale-psum", "ssp", "simulate"):
        delivery = "packed" if kernel_delivery else "tree"
    else:
        delivery = "none"   # sync is buffer-free
    meta["kernels"] = {"config": cfg.kernels, "delivery": delivery}
    if why:
        meta["kernels"]["fallback"] = why
    if cfg.delay is not None:
        meta["delay_spec"] = repr(cfg.delay)

    # Compensation layer (repro.compensate): built only when a knob is set,
    # so the default path hands compensator=None to the core step builders —
    # the exact pre-compensation code, bitwise (tested in the engine matrix).
    ccfg = compensate_lib.CompensateConfig(
        lr_scale=cfg.lr_scale, compress=cfg.compress, s=cfg.s,
        ef_momentum=cfg.ef_momentum)
    compensator = compensate_lib.Compensator(ccfg) if ccfg.active else None
    init_comp = None
    if compensator is not None:
        meta["compensate"] = {"lr_scale": cfg.lr_scale,
                              "compress": cfg.compress}
        if cfg.ef_momentum:
            meta["compensate"]["ef_momentum"] = cfg.ef_momentum
        # Sparsification runs per SOURCE before transport, so the EF state
        # follows the source layout: [P, D] rows wherever each worker emits
        # its own payload (simulate, and the per-worker-delay ring modes),
        # one [D] row for the aggregate/sync forms.
        per_source = (mode == "simulate"
                      or (mode in ("stale-psum", "ssp")
                          and cfg.per_worker_delays))
        comp_workers = cfg.num_workers if per_source else None
        init_comp = lambda params: compensator.init(
            params, num_workers=comp_workers)

    def resolve_mega(supported: bool, why_not: str) -> bool:
        """Resolve the megakernel knob against this engine's placement.
        Records the verdict in meta; 'on' refuses unsupported placements."""
        if cfg.megakernel == "off":
            meta["kernels"]["megakernel"] = "off"
            return False
        sp = getattr(optimizer, "spec", None) if optimizer is not None else None
        if not (sp and sp.get("name") == "adam"):
            supported, why_not = False, "optimizer has no Adam spec"
        if not supported:
            if cfg.megakernel == "on":
                raise ValueError(
                    f"megakernel='on' is unsupported here: {why_not}; use "
                    "megakernel='auto' (falls back to the three-dispatch "
                    "path)")
            meta["kernels"]["megakernel"] = "off"
            meta["kernels"]["megakernel_fallback"] = why_not
            return False
        meta["kernels"]["megakernel"] = "fused"
        return True

    def _finish(engine: Engine) -> Engine:
        if mesh is not None and shape is not None:
            from repro.engine import plan as plan_lib  # lazy: plan imports us
            arch_id = getattr(arch, "arch_id", arch)
            plan_lib.attach_train_plan(engine, api_or_loss, shape,
                                       arch_id=arch_id, rules=rules)
        return engine

    if mode == "simulate":
        custom_update = update_fn is not None
        if update_fn is None:
            if loss is None or optimizer is None:
                raise ValueError("simulate mode needs (loss, optimizer) or "
                                 "an explicit update_fn")
            make = (optlib.make_stochastic_update_fn if cfg.loss_takes_key
                    else optlib.make_sgd_update_fn)
            update_fn = make(loss, optimizer)
        if custom_update:
            mega = resolve_mega(False, "custom update_fn (opaque update math)")
        elif cfg.server_side:
            mega = resolve_mega(False, "server_side transform")
        else:
            mega = resolve_mega(kernel_delivery, why or "tree delivery")
        sim_cfg = staleness.StalenessConfig(
            num_workers=cfg.num_workers,
            delay=cfg.delay or UniformDelay(cfg.s),
            server_side=cfg.server_side,
            kernels=kernel_delivery)
        fused_kw = None
        if mega:
            sp = optimizer.spec
            fused_kw = dict(loss=loss, takes_key=cfg.loss_takes_key,
                            lr=sp["lr"], b1=sp["b1"], b2=sp["b2"],
                            eps=sp["eps"], weight_decay=sp["weight_decay"])
        raw = staleness.make_sim_step(update_fn, sim_cfg,
                                      server_apply=server_apply,
                                      compensator=compensator,
                                      fused=fused_kw)

        def init_inner(params, update_state, key):
            if update_state is None:
                if mega:
                    # Megakernel layout: per-worker Adam moments live packed
                    # ([P, D] after the worker broadcast) — see make_sim_step.
                    width = staleness._packed_width(params)
                    update_state = {"m": jnp.zeros((width,), jnp.float32),
                                    "v": jnp.zeros((width,), jnp.float32)}
                else:
                    update_state = optimizer.init(params)
            return staleness.init_sim_state(params, update_state, sim_cfg, key)

        def sim_step_inner(inner, batch, bound, comp):
            if compensator is None:
                inner, m = raw(inner, batch, bound=bound)
            else:
                inner, comp, m = raw(inner, batch, bound=bound, comp=comp)
            return inner, comp, _mean_over_workers(m)

        return _finish(Engine(
            cfg=cfg, mesh=mesh, meta=meta,
            _init_inner=init_inner,
            _step_inner=sim_step_inner,
            _params_of=lambda inner: jax.tree.map(lambda x: x[0], inner.caches),
            _init_params=init_params,
            _max_bound=sim_cfg.delay.bound,
            _init_comp=init_comp,
        ))

    if mode == "sync":
        if loss is None or optimizer is None:
            raise ValueError("sync mode needs (loss, optimizer)")
        # sync has no ring, but the megakernel still wins the packed-Adam
        # fusion — gated by the same placement verdict as `kernels`.
        sync_ok, sync_why = kernel_placement_ok(cfg.kernels, arch, mesh)
        mega = resolve_mega(sync_ok, sync_why or "kernels='off'")
        raw = stale_sync.make_sync_train_step_lean(loss, optimizer,
                                                   compensator=compensator,
                                                   fused=mega)

        def sync_step_inner(inner, batch, _bound, comp):
            if compensator is None:
                inner, m = raw(inner, batch)
                return inner, comp, m
            return raw(inner, batch, comp=comp)

        return _finish(Engine(
            cfg=cfg, mesh=mesh, meta=meta,
            _init_inner=lambda params, _ust, _key:
                stale_sync.init_sync_state(params, optimizer, fused=mega),
            _step_inner=sync_step_inner,
            _params_of=lambda inner: inner.params,
            _init_params=init_params,
            _max_bound=0,
            _init_comp=init_comp,
        ))

    # gradient ring-buffer modes: stale-psum and ssp.
    if loss is None or optimizer is None:
        raise ValueError(f"{mode} mode needs (loss, optimizer)")
    mega = resolve_mega(kernel_delivery, why or "tree delivery")
    # The per-worker ring read takes its form from the ring's placement, by
    # the predicate the plan shards the ring with (StaleSyncConfig).
    split = rules_lib.worker_axis_split(mesh, cfg.num_workers)
    if mode == "ssp":
        if cfg.delay is not None:
            # Trace/Schedule specs replace the sampled lognormal speed model
            # (type-validated in EngineConfig.__post_init__): measured
            # wall-times run through the same clock discipline.
            spec = cfg.delay
            if isinstance(spec, delays_lib.Trace):
                spec = spec.schedule(
                    num_workers=cfg.num_workers,
                    bound=spec.bound if spec.bound is not None else cfg.s)
            else:
                spec.realize(num_workers=cfg.num_workers)  # width check
            if spec.bound > cfg.s:
                raise ValueError(
                    f"delay schedule bound {spec.bound} exceeds the ssp "
                    f"clock bound s={cfg.s}; raise s to at least {spec.bound}")
            table = jnp.asarray(spec.table, jnp.int32)
        else:
            speeds = cfg.ssp_speeds
            if speeds is None:
                speeds = ssp_lib.sample_worker_durations(
                    jax.random.PRNGKey(cfg.ssp_seed), cfg.ssp_steps,
                    cfg.num_workers, cfg.ssp_mean_dur, cfg.ssp_cv)
            table = ssp_lib.ssp_delay_schedule(
                ssp_lib.SSPConfig(num_workers=cfg.num_workers, bound=cfg.s),
                jnp.asarray(speeds))
        table = _place_table(table, mesh)
        # schedule delays reach cfg.s, so the ring needs s+1 slots.
        scfg = stale_sync.StaleSyncConfig(
            num_workers=cfg.num_workers, s=cfg.s + 1,
            buffer_dtype=cfg.buffer_dtype, delay_table=table,
            kernels=kernel_delivery, fused_update=mega,
            worker_axis_split=split)
        meta["ssp_schedule"] = table
        max_bound = cfg.s
    else:
        spec = cfg.delay
        if isinstance(spec, delays_lib.Trace):
            # bound is non-None here (EngineConfig validates it).
            spec = spec.schedule(num_workers=cfg.num_workers)
        if (isinstance(spec, delays_lib.MultiPod)
                and not cfg.per_worker_delays):
            raise ValueError(
                "MultiPod delays are per-worker; the Theorem-1 aggregate "
                "form (per_worker_delays=False) cannot express topology")
        table = None
        if isinstance(spec, delays_lib.Schedule) and cfg.per_worker_delays:
            # Deterministic tables ride the delay_table fast path so the
            # planner can pre-place [T, P] tables over the worker axis.
            spec.realize(num_workers=cfg.num_workers)  # width check
            table = _place_table(jnp.asarray(spec.table, jnp.int32), mesh)
        scfg = stale_sync.StaleSyncConfig(
            num_workers=cfg.num_workers, s=cfg.s,
            delay=None if table is not None else spec,
            delay_table=table,
            buffer_dtype=cfg.buffer_dtype,
            per_worker_delays=cfg.per_worker_delays,
            kernels=kernel_delivery, fused_update=mega,
            worker_axis_split=split)
        eff_bound = spec.bound if spec is not None else scfg.delay.bound
        if eff_bound > scfg.slots - 1:
            # A delay the ring can't hold would silently wrap onto a much
            # fresher slot while metrics report the large staleness.
            raise ValueError(
                f"delay bound {eff_bound} exceeds the gradient ring "
                f"({scfg.slots} slots from s={cfg.s}); raise s to at least "
                f"{eff_bound + 1}")
        max_bound = eff_bound
    if scfg.per_worker_delays:
        meta["kernels"]["ring_read"] = scfg.ring_read[0]
    raw = stale_sync.make_stale_train_step(loss, optimizer, scfg,
                                           compensator=compensator)

    def ring_step_inner(inner, batch, bound, comp):
        if compensator is None:
            inner, m = raw(inner, batch, bound=bound)
            return inner, comp, m
        return raw(inner, batch, bound=bound, comp=comp)

    return _finish(Engine(
        cfg=cfg, mesh=mesh, meta=meta,
        _init_inner=lambda params, _ust, key:
            stale_sync.init_state(params, optimizer, scfg, key),
        _step_inner=ring_step_inner,
        _params_of=lambda inner: inner.params,
        _init_params=init_params,
        _max_bound=max_bound,
        _init_comp=init_comp,
    ))

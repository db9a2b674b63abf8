"""The paper's staleness simulation model, as a composable JAX engine.

Semantics (Section 3 of the paper):
  * ``P`` workers each hold a full *model cache* ``x_p``.
  * At iteration ``t`` every worker computes an additive update ``u_p^t`` from
    its own cache (SGD-family step, Gibbs count delta, blackbox-VI step, ...).
  * The update is delivered to every worker ``p'`` (including ``p`` itself) at
    the start of iteration ``t + 1 + r_{p,p'}^t`` with ``r`` drawn from the
    configured delay spec (``repro.delays``).
  * Evaluation reads worker 0's cache (caches are symmetric).

Implementation: caches are stacked on a leading worker axis ``[P, ...]`` and
in-flight updates live in a delivery ring buffer ``pending``. Two layouts:

* tree (default, ``kernels=False``): leaves ``[P, B, ...]`` with
  ``B = delay.bound + 1``; slot ``d`` of worker ``p`` holds the sum of
  updates landing on ``p`` in ``d + 1`` iterations. Each step delivers slot
  0 and ROLLS the buffer left — every ring element is rewritten. Bitwise
  legacy trajectories.
* packed (``kernels=True``): ONE contiguous ``ring [P, B, D]`` array of
  packed flat rows (``treemath.tree_pack``) addressed by a rotating cursor
  (slot ``t mod B`` = step ``t``'s arrivals), plus a PREFETCHED
  ``arrived [P, D]`` row carried in the state. Each step delivers from the
  prefetched row (fused into the packed caches view through
  ``repro.kernels.dispatch.stale_accum``), zeroes the consumed slot,
  scatter-adds the P^2 new packed rows, and only THEN re-slices the next
  step's arrivals. Ordering matters: a slot read scheduled *before* ring
  writes is an anti-dependency XLA CPU resolves by copying the whole
  donated ring (measured: 2 full copies per step); the end-of-step
  prefetch is a true dependency, so the ring updates strictly in place —
  the packed step touches O(P^2 · D) bytes instead of the tree layout's
  O(P · B · D) roll. fp32-tolerance equivalent to the tree layout.

One engine step is:

  1. deliver   -- apply this iteration's arrivals to the caches.
  2. compute   -- ``vmap`` the user's ``update_fn`` over the worker axis.
  3. dispatch  -- draw the delay matrix ``r[src, dst]`` from the realized
                  delay source and scatter each update into the slot it
                  arrives in.

Because the whole engine is pure array math over the leading worker axis, the
*same* code is the single-host simulator (paper's setting) and the distributed
implementation: sharding ``[P, ...]`` over ``("pod", "data")`` makes GSPMD
insert the collectives, which is exactly what the roofline analysis measures.

The engine is generic over *additive updates*; adaptive optimizers can live
either worker-side (their state rides in ``update_state``, the paper's implied
setting) or server-side (``server_apply`` transforms the *arrived* aggregate;
see DESIGN.md §8.3 for the ablation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import treemath as tm
from repro.delays.models import DelayModel, DelaySpec, UniformDelay, as_spec
from repro.scopes import MODEL, OPTIMIZER, RING

Pytree = Any
# update_fn(params, update_state, batch, key) -> (update, new_update_state, metrics)
UpdateFn = Callable[[Pytree, Pytree, Pytree, jax.Array], Tuple[Pytree, Pytree, dict]]
# server_apply(cache, server_state, arrived) -> (new_cache, new_server_state)
ServerApply = Callable[[Pytree, Pytree, Pytree], Tuple[Pytree, Pytree]]


@dataclasses.dataclass(frozen=True)
class StalenessConfig:
    num_workers: int
    delay: DelaySpec           # any repro.delays spec (or legacy DelayModel)
    # Apply delivered aggregates through a server-side transform instead of
    # plain addition (ablation: where does Adam state live?).
    server_side: bool = False
    # Packed [P, B, D] pending ring + fused delivery via
    # repro.kernels.dispatch (see module docstring). False keeps the legacy
    # per-leaf [P, B, ...] layout (bitwise-identical trajectories).
    kernels: bool = False

    def __post_init__(self):
        object.__setattr__(self, "delay", as_spec(self.delay))
        if self.kernels and self.server_side:
            raise ValueError(
                "kernels=True is unsupported with server_side=True: the "
                "server transform consumes per-leaf arrivals")

    @property
    def buffer_slots(self) -> int:
        return self.delay.bound + 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    caches: Pytree        # [P, ...] per-worker model caches
    pending: Pytree       # [P, B, ...] ring (packed: {"hist", "arrival"})
    update_state: Pytree  # [P, ...] per-worker algorithm state (opt moments, z's, ...)
    server_state: Pytree  # [P, ...] per-worker server-side transform state (or ())
    step: jax.Array       # scalar int32 iteration counter
    key: jax.Array        # PRNG key threaded through delay + update sampling


def _packed_width(params: Pytree) -> int:
    from repro.kernels import dispatch
    return tm.padded_size(tm.pack_spec(params).total, dispatch.PACK_ALIGN)


def _worker_keys(key: jax.Array, caches: Pytree) -> jax.Array:
    """One update key per worker, placed like the caches' worker axis. Under
    a mesh with Explicit axes the worker axis's sharding is part of each
    array's type, and ``vmap`` refuses mapped inputs sharded differently."""
    leaf = jax.tree.leaves(caches)[0]
    keys = jax.random.split(key, leaf.shape[0])
    sharding = jax.typeof(leaf).sharding
    if len(sharding.spec) and sharding.spec[0] is not None:
        keys = jax.sharding.reshard(keys, jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec(sharding.spec[0])))
    return keys


def _is_packed(state: SimState) -> bool:
    """Packed states carry ONE pending array whose tree shape differs from
    the caches tree (a single [P, B, D] leaf)."""
    return (jax.tree.structure(state.pending)
            != jax.tree.structure(state.caches))


def init_sim_state(
    params: Pytree,
    update_state: Pytree,
    cfg: StalenessConfig,
    key: jax.Array,
    server_state: Pytree = (),
) -> SimState:
    """All workers start from identical ``params``; buffers start empty.

    ``update_state``/``server_state`` are given *per single worker* and are
    broadcast across the worker axis.
    """
    p = cfg.num_workers
    caches = tm.tree_broadcast_leading(params, p)
    if cfg.kernels:
        # ring[dst, b, :] = packed sum of updates arriving on dst at the
        # next step congruent to b (mod B); arrived = the prefetched slot
        # for the CURRENT step (see make_sim_step's packed_step).
        width = _packed_width(params)
        pending = {
            "ring": jnp.zeros((p, cfg.buffer_slots, width), jnp.float32),
            "arrived": jnp.zeros((p, width), jnp.float32),
        }
    else:
        pending = jax.tree.map(
            lambda x: jnp.zeros((p, cfg.buffer_slots) + x.shape, x.dtype),
            params)
    return SimState(
        caches=caches,
        pending=pending,
        update_state=tm.tree_broadcast_leading(update_state, p),
        server_state=tm.tree_broadcast_leading(server_state, p)
        if server_state != ()
        else (),
        step=jnp.int32(0),
        key=key,
    )


def draw_delay_matrix(key: jax.Array, delay: DelayModel, p: int) -> jax.Array:
    """``r[src, dst]`` — legacy helper (samplers only); the engine step now
    draws through ``delay.realize(...).delays(key, step, (p, p))``, which for
    samplers is this exact call (tested bitwise)."""
    return delay.sample(key, (p, p))


def _deliver(caches: Pytree, pending: Pytree) -> Tuple[Pytree, Pytree]:
    new_caches = jax.tree.map(lambda c, b: c + b[:, 0].astype(c.dtype), caches, pending)
    rolled = jax.tree.map(
        lambda b: jnp.concatenate([b[:, 1:], jnp.zeros_like(b[:, :1])], axis=1), pending
    )
    return new_caches, rolled


def _dispatch(pending: Pytree, updates: Pytree, delays: jax.Array, slots: int) -> Pytree:
    # onehot[src, dst, slot] routes update[src] into pending[dst, slot].
    onehot = jax.nn.one_hot(delays, slots, dtype=jnp.float32)  # [P, P, B]
    def scatter(buf, u):
        acc = jnp.tensordot(onehot, u.astype(jnp.float32), axes=([0], [0]))  # [P,B,...]
        return buf + acc.astype(buf.dtype)
    return jax.tree.map(scatter, pending, updates)


def make_sim_step(
    update_fn: UpdateFn,
    cfg: StalenessConfig,
    server_apply: Optional[ServerApply] = None,
    compensator=None,
    fused: Optional[dict] = None,
):
    """Build one jit-able engine step: ``step(state, batches) -> (state, metrics)``.

    ``batches`` must have a leading worker axis of size ``P`` on every leaf
    (each worker consumes its own data shard, as in the paper).

    ``compensator`` (``repro.compensate.Compensator``) compensates each
    worker's *outgoing* update before it enters the delivery ring: the
    update is scaled by the worker's realized mean total delay (the
    per-source form of the 1/tau rule — the delays are drawn in the same
    step, so the scale sees them) and then EF-sparsified against a
    per-worker [P, D] packed residual. The step then takes/returns the comp
    state (``(state, comp, metrics)``); ``compensator=None`` keeps the
    legacy signature and bitwise behavior.

    ``fused`` (requires ``kernels=True``) replaces the vmapped ``update_fn``
    with the megakernel compute stage: per-worker gradients come from
    ``jax.vmap(jax.value_and_grad(fused["loss"]))`` and ALL P workers' Adam
    moment/delta math runs as ONE ``dispatch.fused_adam`` pass over the
    flattened [P*D] packed view, with the moments stored PACKED in
    ``update_state = {"m": [P, D], "v": [P, D]}`` fp32 — no per-step
    pack/unpack of optimizer state, and the packed delta rows feed transport
    directly (pack∘elementwise == elementwise∘pack, so this is bitwise the
    packed-step trajectory for fp32 params). Keys of ``fused``: ``loss``,
    ``takes_key`` (loss consumes a PRNG key), ``lr``, ``b1``, ``b2``,
    ``eps``, ``weight_decay``.
    """
    if cfg.server_side and server_apply is None:
        raise ValueError("server_side=True requires a server_apply transform")
    if fused is not None and not cfg.kernels:
        raise ValueError("fused simulate step requires kernels=True "
                         "(the megakernel runs over the packed ring)")
    p = cfg.num_workers
    slots = cfg.buffer_slots
    source = cfg.delay.realize(num_workers=p)

    def compensate(comp, updates, delays, step, packed_true_size=None):
        """Scale-then-sparsify each source worker's update; ``updates`` is
        the pytree (tree layout) or the packed [P, D] view (packed layout,
        ``packed_true_size`` set)."""
        lr_metrics = {}
        if compensator.scales:
            out_delay = delays.astype(jnp.float32).mean(axis=1)    # [P]
            factor = jnp.broadcast_to(
                compensator.lr_factor(comp, out_delay, step), (p,))
            if packed_true_size is not None:
                updates = updates * factor[:, None]
            else:
                updates = compensator.scale_tree(updates, factor)
            lr_metrics["lr_scale"] = factor
        if packed_true_size is not None:
            updates, comp, cmetrics = compensator.sparsify_packed(
                comp, updates, packed_true_size)
        else:
            updates, comp, cmetrics = compensator.sparsify_tree(
                comp, updates, lead_ndim=1)
        return updates, comp, {**cmetrics, **lr_metrics}

    def packed_step(state: SimState, batches: Pytree,
                    bound: Optional[jax.Array] = None,
                    comp: Pytree = None) -> Tuple[SimState, dict]:
        from repro.kernels import dispatch
        pspec = tm.pack_spec(state.caches, lead_ndim=1)
        ring = state.pending["ring"]

        # 1. deliver from the PREFETCHED arrivals (no ring read here — see
        #    module docstring): one fused accumulate over the flattened
        #    packed caches view, the same stale_accum hot spot as the
        #    gradient ring.
        with jax.named_scope(RING):
            key, kdelay, kupd = jax.random.split(state.key, 3)
            arrived = state.pending["arrived"]                   # [P, D]
            cvec = tm.tree_pack(state.caches, lead_ndim=1,
                                pad_to=dispatch.PACK_ALIGN)      # [P, D] fp32
            flat = dispatch.stale_accum(cvec.reshape(-1),
                                        arrived.reshape(1, -1),
                                        jnp.ones((1,), jnp.float32))
            caches = tm.tree_unpack(flat.reshape(p, -1), pspec)
            worker_keys = _worker_keys(kupd, caches)

        # 2. compute (identical to the tree path).
        updates, update_state, metrics = jax.vmap(update_fn)(
            caches, state.update_state, batches, worker_keys)

        # 3. dispatch: zero the consumed slot, scatter-add each src's
        #    packed update row into (dst, (t + 1 + r) mod B), then prefetch
        #    the NEXT step's arrivals. The prefetch reads the ring after
        #    every write (a true dependency), so the donated ring mutates
        #    strictly in place.
        with jax.named_scope(RING):
            delays = source.delays(kdelay, state.step, (p, p))
            if bound is not None:
                delays = jnp.minimum(delays, jnp.asarray(bound, jnp.int32))
            uvec = tm.tree_pack(updates, lead_ndim=1,
                                pad_to=dispatch.PACK_ALIGN)      # [P, D]
        if compensator is not None:
            uvec, comp, cmetrics = compensate(
                comp, uvec, delays, state.step, packed_true_size=pspec.total)
            metrics = {**metrics, **cmetrics}
        with jax.named_scope(RING):
            cursor = jnp.mod(state.step, slots)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.zeros_like(arrived)[:, None], cursor, axis=1)
            slot = jnp.mod(state.step + 1 + delays, slots)       # [src, dst]
            dst = jnp.broadcast_to(jnp.arange(p)[None, :], (p, p))
            ring = ring.at[dst, slot].add(
                jnp.broadcast_to(uvec[:, None, :], (p, p) + uvec.shape[-1:])
                .astype(ring.dtype))
            arrived_next = jax.lax.dynamic_index_in_dim(
                ring, jnp.mod(state.step + 1, slots), axis=1, keepdims=False)

        new_state = SimState(
            caches=caches,
            pending={"ring": ring, "arrived": arrived_next},
            update_state=update_state, server_state=state.server_state,
            step=state.step + 1, key=key)
        if compensator is not None:
            return new_state, comp, metrics
        return new_state, metrics

    def packed_fused_step(state: SimState, batches: Pytree,
                          bound: Optional[jax.Array] = None,
                          comp: Pytree = None) -> Tuple[SimState, dict]:
        from repro.kernels import dispatch
        from repro.optim.optimizers import lr_at
        pspec = tm.pack_spec(state.caches, lead_ndim=1)
        ring = state.pending["ring"]

        # 1. deliver (identical to packed_step).
        with jax.named_scope(RING):
            key, kdelay, kupd = jax.random.split(state.key, 3)
            arrived = state.pending["arrived"]                   # [P, D]
            cvec = tm.tree_pack(state.caches, lead_ndim=1,
                                pad_to=dispatch.PACK_ALIGN)      # [P, D] fp32
            flat = dispatch.stale_accum(cvec.reshape(-1),
                                        arrived.reshape(1, -1),
                                        jnp.ones((1,), jnp.float32))
            cflat = flat.reshape(p, -1)                          # [P, D]
            caches = tm.tree_unpack(cflat, pspec)
            worker_keys = _worker_keys(kupd, caches)

        # 2. compute: per-worker gradients, then ALL P Adam updates in one
        #    fused pass over the flattened packed view. The moments stay
        #    packed in update_state ([P, D] fp32), read/written exactly
        #    once; the delta rows ARE the packed transport payload.
        def grad_one(cache, batch, wkey):
            args = (cache, batch, wkey) if fused["takes_key"] else (cache,
                                                                     batch)
            with jax.named_scope(MODEL):
                return jax.value_and_grad(fused["loss"])(*args)

        losses, grads = jax.vmap(grad_one)(caches, batches, worker_keys)
        with jax.named_scope(OPTIMIZER):
            gvec = tm.tree_pack(grads, lead_ndim=1,
                                pad_to=dispatch.PACK_ALIGN)      # [P, D]
            m, v = state.update_state["m"], state.update_state["v"]
            ostep = state.step + 1    # every worker steps once per iteration
            eta = lr_at(fused["lr"], ostep)
            dneg, m2, v2 = dispatch.fused_adam(
                jnp.zeros((m.size,), jnp.float32), m.reshape(-1),
                v.reshape(-1), gvec.reshape(-1), eta, fused["b1"],
                fused["b2"], fused["eps"], ostep)
            uvec = dneg.reshape(p, -1)                           # [P, D]
            wd = fused["weight_decay"]
            if wd:
                # Decoupled decay against the post-delivery cache each
                # gradient was computed at — the packed image of the
                # per-leaf AdamW rule.
                uvec = uvec - eta * wd * cflat
        update_state = {"m": m2.reshape(p, -1), "v": v2.reshape(p, -1)}
        metrics = {"loss": losses}

        # 3. dispatch (identical to packed_step).
        with jax.named_scope(RING):
            delays = source.delays(kdelay, state.step, (p, p))
            if bound is not None:
                delays = jnp.minimum(delays, jnp.asarray(bound, jnp.int32))
        if compensator is not None:
            uvec, comp, cmetrics = compensate(
                comp, uvec, delays, state.step, packed_true_size=pspec.total)
            metrics = {**metrics, **cmetrics}
        with jax.named_scope(RING):
            cursor = jnp.mod(state.step, slots)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.zeros_like(arrived)[:, None], cursor, axis=1)
            slot = jnp.mod(state.step + 1 + delays, slots)       # [src, dst]
            dst = jnp.broadcast_to(jnp.arange(p)[None, :], (p, p))
            ring = ring.at[dst, slot].add(
                jnp.broadcast_to(uvec[:, None, :], (p, p) + uvec.shape[-1:])
                .astype(ring.dtype))
            arrived_next = jax.lax.dynamic_index_in_dim(
                ring, jnp.mod(state.step + 1, slots), axis=1, keepdims=False)

        new_state = SimState(
            caches=caches,
            pending={"ring": ring, "arrived": arrived_next},
            update_state=update_state, server_state=state.server_state,
            step=state.step + 1, key=key)
        if compensator is not None:
            return new_state, comp, metrics
        return new_state, metrics

    def step(state: SimState, batches: Pytree,
             bound: Optional[jax.Array] = None,
             comp: Pytree = None) -> Tuple[SimState, dict]:
        # 1. deliver arrivals scheduled for this iteration.
        with jax.named_scope(RING):
            key, kdelay, kupd = jax.random.split(state.key, 3)
            if cfg.server_side:
                arrived = jax.tree.map(lambda b: b[:, 0], state.pending)
                with jax.named_scope(OPTIMIZER):
                    caches, server_state = jax.vmap(server_apply)(
                        state.caches, state.server_state, arrived
                    )
                pending = jax.tree.map(
                    lambda b: jnp.concatenate(
                        [b[:, 1:], jnp.zeros_like(b[:, :1])], axis=1),
                    state.pending,
                )
            else:
                caches, pending = _deliver(state.caches, state.pending)
                server_state = state.server_state
            worker_keys = _worker_keys(kupd, caches)

        # 2. every worker computes its update from its own (stale) cache.
        updates, update_state, metrics = jax.vmap(update_fn)(
            caches, state.update_state, batches, worker_keys
        )

        # 3. dispatch into the delivery buffer with the realized delays.
        with jax.named_scope(RING):
            delays = source.delays(kdelay, state.step, (p, p))
            if bound is not None:
                # Dynamic staleness control (repro.engine): clamp the
                # sampled delay to an (inclusive, possibly traced) bound.
                delays = jnp.minimum(delays, jnp.asarray(bound, jnp.int32))
        if compensator is not None:
            updates, comp, cmetrics = compensate(
                comp, updates, delays, state.step)
            metrics = {**metrics, **cmetrics}
        with jax.named_scope(RING):
            pending = _dispatch(pending, updates, delays, slots)

        new_state = SimState(
            caches=caches,
            pending=pending,
            update_state=update_state,
            server_state=server_state,
            step=state.step + 1,
            key=key,
        )
        if compensator is not None:
            return new_state, comp, metrics
        return new_state, metrics

    if fused is not None:
        return packed_fused_step
    return packed_step if cfg.kernels else step


def drain(state: SimState, server_apply: Optional[ServerApply] = None,
          server_side: bool = False) -> SimState:
    """Deliver every in-flight update without generating new ones.

    Used by the conservation property test: after draining, every cache equals
    ``x0 + sum of all generated updates`` (all caches identical). Handles both
    the tree and the packed pending layouts.
    """
    if _is_packed(state):
        ring = state.pending["ring"]
        slots = ring.shape[1]
        pspec = tm.pack_spec(state.caches, lead_ndim=1)
        caches = state.caches

        def add(caches, row):
            delivered = tm.tree_unpack(row, pspec)
            return jax.tree.map(lambda c, d: c + d.astype(c.dtype),
                                caches, delivered)

        # The prefetched row IS ring slot (step mod B); the remaining
        # in-flight updates sit at the following B-1 cursor positions.
        caches = add(caches, state.pending["arrived"])
        for i in range(1, slots):
            row = jax.lax.dynamic_index_in_dim(
                ring, jnp.mod(state.step + i, slots), axis=1, keepdims=False)
            caches = add(caches, row)
        return dataclasses.replace(
            state, caches=caches,
            pending={"ring": jnp.zeros_like(ring),
                     "arrived": jnp.zeros_like(state.pending["arrived"])})

    slots = jax.tree.leaves(state.pending)[0].shape[1]
    caches, pending, server_state = state.caches, state.pending, state.server_state
    for _ in range(slots):
        if server_side:
            arrived = jax.tree.map(lambda b: b[:, 0], pending)
            caches, server_state = jax.vmap(server_apply)(caches, server_state, arrived)
            pending = jax.tree.map(
                lambda b: jnp.concatenate([b[:, 1:], jnp.zeros_like(b[:, :1])], axis=1),
                pending,
            )
        else:
            caches, pending = _deliver(caches, pending)
    return dataclasses.replace(
        state, caches=caches, pending=pending, server_state=server_state
    )


def sequential_reference(
    update_fn: UpdateFn,
    params: Pytree,
    update_state: Pytree,
    batches_per_step,
    keys,
) -> Pytree:
    """Plain sequential execution (the s=0, P=1 limit) for exactness tests."""
    x, ust = params, update_state
    for batch, key in zip(batches_per_step, keys):
        u, ust, _ = update_fn(x, ust, batch, key)
        x = tm.tree_add(x, u)
    return x


def effective_staleness_histogram(delay: DelayModel, key: jax.Array,
                                  p: int, steps: int) -> jax.Array:
    """Empirical distribution of total delay (1 + r) — diagnostic used by the
    EXPERIMENTS.md §Repro delay-model calibration plot."""
    keys = jax.random.split(key, steps)
    draws = jax.vmap(lambda k: delay.sample(k, (p, p)))(keys)
    return jnp.bincount((draws + 1).reshape(-1), length=delay.bound + 2)

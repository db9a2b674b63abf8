"""Distributed staleness: the paper's delay model as a data-parallel
training-step transformation (SPMD-implicit — DESIGN.md §3).

Both modes express staleness as pure array math over a leading worker axis
``P`` (= the mesh's data-parallel extent, times pods). GSPMD inserts the
collectives; no hand-written shard_map is needed, so the same step composes
with arbitrary model parallelism on the ``model`` axis.

Modes
-----
* ``stale-psum`` — the Async-SGD of Theorem 1, production-scalable:
  params stay global/replicated-over-data; each worker's *gradient* enters a
  ring buffer of ``s`` slots, and the aggregation at step k sums, per worker,
  the gradient from step ``k - d_p`` (d_p sampled from the delay model).
  Buffer leaves are [s, P, ...param] (sharded over data on axis 1 and over
  model inside the param dims). Early steps clamp d_p <= k.

* ``sync`` — s = 0 baseline: standard data-parallel aggregation (the paper's
  s=0 reference points).

The *faithful* per-worker-cache mode lives in ``core/staleness.py``; running
it distributed is just sharding its [P, ...] state over the data axis (the
equivalence is tested). It is intentionally not used for the 1T-param config
(DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import treemath as tm
from repro.delays.models import DelaySpec, UniformDelay, as_spec
from repro.delays.schedule import Schedule
from repro.kernels import dispatch
from repro.optim.optimizers import Optimizer, lr_at
from repro.scopes import MODEL, OPTIMIZER, RING

Pytree = Any


@dataclasses.dataclass(frozen=True)
class StaleSyncConfig:
    num_workers: int                 # data-parallel extent (pods * data)
    s: int                           # staleness bound (0 = synchronous)
    # Any repro.delays spec (samplers, Schedule, Trace, MultiPod) or a
    # legacy DelayModel; defaults to UniformDelay(s).
    delay: Optional[DelaySpec] = None
    buffer_dtype: Any = jnp.float32
    # True: per-worker delays d_p with a [slots, P, ...] buffer (the paper's
    # simulation semantics). False: ONE sampled delay per step over the
    # aggregated gradient, buffer [slots, ...] — exactly Theorem 1's
    # x_{k+1} = x_k - eta * grad(x_{tau_k}) update, and the only form whose
    # buffer fits HBM for the 1T-param configs (P-fold smaller).
    per_worker_delays: bool = True
    # Deterministic per-step delays instead of sampling: int32 [T, P] table
    # indexed by step mod T. This is how repro.engine runs SSP — the clock
    # discipline's effective read staleness becomes the delay schedule.
    delay_table: Optional[Any] = None
    # Kernel-backed hot path: store the gradient ring buffer as ONE packed
    # [slots(, P), D] array and run the delayed-update delivery through
    # repro.kernels.dispatch.stale_accum over contiguous flat views, instead
    # of per-leaf tree math. False keeps the legacy per-leaf buffer
    # (bitwise-identical trajectories); True is fp32-tolerance equivalent.
    kernels: bool = False
    # One-pass megakernel step (dispatch.fused_update): EF split, weighted
    # stale delivery and the Adam update fuse into a single pass over the
    # packed [D] view, with the Adam moments stored PACKED in opt_state
    # ({"step", "m" [D], "v" [D]} fp32) so they are read/written exactly
    # once per step with no per-step pack/unpack. Requires kernels=True and
    # an optimizer carrying an Adam spec (optimizers.adam().spec).
    fused_update: bool = False
    # Derived from the placement (rules.worker_axis_split, which the
    # engine's plan shards the ring by), not a knob: True where the ring's
    # worker axis lies split over devices. It picks the per-worker read's
    # form (``ring_read``); either form is correct on any placement.
    worker_axis_split: bool = False

    def __post_init__(self):
        if self.delay is None:
            object.__setattr__(self, "delay", UniformDelay(self.s))
        else:
            object.__setattr__(self, "delay", as_spec(self.delay))
        if self.delay_table is not None and not self.per_worker_delays:
            raise ValueError("delay_table requires per_worker_delays=True")
        if self.fused_update and not self.kernels:
            raise ValueError("fused_update=True requires kernels=True "
                             "(the megakernel runs over the packed ring)")

    @property
    def slots(self) -> int:
        return max(self.s, 1)

    @property
    def ring_read(self) -> Tuple[str, str]:
        """(form, why) of the per-worker ring read: ``"rows"`` reads each
        worker's row by its own slice (``_ring_rows``), whose reads fuse
        into their consumers; ``"gather"`` is one batched index
        (``_ring_rows_gathered``) where nothing can fuse them: packed rows
        feed a Pallas kernel (a stack of slices costs the TPU two passes
        over them), or the worker axis lies split over devices."""
        if self.kernels:
            return "gather", "packed rows feed a kernel"
        if self.worker_axis_split:
            return "gather", "worker axis split over devices"
        return "rows", "worker axis on one device"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StaleTrainState:
    params: Pytree
    opt_state: Pytree
    gbuf: Pytree          # [slots, P, ...param] gradient ring buffer
    step: jax.Array
    key: jax.Array


def init_state(params: Pytree, optimizer: Optimizer, cfg: StaleSyncConfig,
               key: jax.Array) -> StaleTrainState:
    lead = ((cfg.slots, cfg.num_workers) if cfg.per_worker_delays
            else (cfg.slots,))
    if cfg.kernels:
        # One contiguous ring: [slots(, P), D] — the packed view the fused
        # delivery kernel consumes without per-step re-packing. D is padded
        # to the kernel block width so the fast path always applies.
        width = tm.padded_size(tm.pack_spec(params).total,
                               dispatch.PACK_ALIGN)
        gbuf = jnp.zeros(lead + (width,), cfg.buffer_dtype)
    else:
        gbuf = jax.tree.map(
            lambda x: jnp.zeros(lead + x.shape, cfg.buffer_dtype), params)
    if cfg.fused_update:
        # Megakernel: Adam moments live packed, aligned with the ring width,
        # so the fused pass reads/writes them in place (donation-aliased).
        opt_state = {"step": jnp.int32(0),
                     "m": jnp.zeros((width,), jnp.float32),
                     "v": jnp.zeros((width,), jnp.float32)}
    else:
        opt_state = optimizer.init(params)
    return StaleTrainState(
        params=params,
        opt_state=opt_state,
        gbuf=gbuf,
        step=jnp.int32(0),
        key=key,
    )


def _ring_rows(ring: jax.Array, read: jax.Array) -> jax.Array:
    """Worker q's row from slot ``read[q]`` of a [slots, P, ...] ring ->
    [P, ...], as P static per-worker dynamic slices (P is static).

    Each row is one dynamic slice of the ring itself (not of a static
    ``ring[:, q]``, which the TPU compiler copies whole, every slot of
    it). Where the worker axis sits on one device, a consumer that takes
    ``rows[q]`` (the worker mean, ``_worker_mean``) then reads the ring
    in its own fusion, so no [P, ...] copy of the rows is written. The
    batched form (``_ring_rows_gathered``) lowers to a gather, which the
    TPU compiler expands into one loop per leaf writing that copy. On a
    worker axis split over devices a slice of one worker would move whole
    shards between devices, so there the gather stays
    (``StaleSyncConfig.ring_read``)."""
    rest = ring.shape[2:]
    return jnp.stack([
        jax.lax.dynamic_slice(ring, (read[q], q) + (0,) * len(rest),
                              (1, 1) + rest).reshape(rest)
        for q in range(ring.shape[1])])


def _ring_rows_gathered(ring: jax.Array, read: jax.Array) -> jax.Array:
    """``_ring_rows`` as one dynamic index batched over the worker axis,
    where nothing fuses the row reads (``StaleSyncConfig.ring_read``).
    Not ``take_along_axis``: the TPU
    compiler splits a plain gather whose slice spans a packed row into
    pieces in proportion to its width, which took longer than 15 minutes
    to compile at deepseek-7b widths."""
    return jax.vmap(
        lambda col, r: jax.lax.dynamic_index_in_dim(col, r, 0, keepdims=False),
        in_axes=(1, 0))(ring, read)


def _worker_mean(rows: jax.Array) -> jax.Array:
    """fp32 mean of [P, ...] rows over the worker axis, summed row by row
    so that each row is read where the sum is consumed; bitwise
    ``rows.astype(float32).mean(axis=0)`` for P <= 2."""
    p = rows.shape[0]
    return sum(rows[q].astype(jnp.float32) for q in range(p)) / p


def make_stale_train_step(
    loss_fn: Callable[[Pytree, Pytree], jax.Array],
    optimizer: Optimizer,
    cfg: StaleSyncConfig,
    compensator=None,
):
    """Returns step(state, batch) -> (state, metrics).

    ``batch`` leaves have a leading global-batch axis; it is reshaped to
    [P, B/P, ...] so each worker computes its own gradient (a vmap, which
    under pjit shards over the data axis — per-device work is identical to
    a plain data-parallel step).

    ``compensator`` (a ``repro.compensate.Compensator``) slots the
    compensation layer around transport: each source's gradient is
    EF-sparsified BEFORE it enters the ring (the ring stores the sparse
    payload — see the ring-layout note below) and the optimizer's delta is
    scaled by the staleness-aware LR factor after delivery. The step then
    takes/returns the comp state: ``step(state, batch, bound=, comp=) ->
    (state, comp, metrics)``. With ``compensator=None`` (default) this code
    path is untouched and the legacy 2-tuple signature/behavior is
    preserved bitwise.

    Ring layout under compression: slot rows hold the post-split ``sent``
    payload — sparse VALUES at the dense packed width (zeros where masked),
    cast to ``buffer_dtype``. Keeping the dense width means delivery stays
    the same gather + weighted reduction; a later change can shrink rows to
    (indices, values) pairs without touching the step math, since only the
    write/gather sites interpret the row layout.

    With ``cfg.fused_update`` the whole post-gradient tail is ONE
    ``dispatch.fused_update`` pass: EF split, weighted delivery of the
    gathered ring rows, and the Adam update over packed moments. Freshness
    (delay 0) is resolved in-kernel via a per-row ``fresh`` flag selecting
    this step's ``sent`` over the gathered (pre-write) ring row — bitwise
    the same delivery as the write-then-read order, without scheduling a
    ring read before the ring write on the donated buffer."""
    p = cfg.num_workers
    if cfg.fused_update:
        spec_ = optimizer.spec if hasattr(optimizer, "spec") else None
        if not (spec_ and spec_.get("name") == "adam"):
            raise ValueError(
                "fused_update=True needs an optimizer with an Adam spec "
                "(optimizers.adam(...)); got an opaque optimizer")
    # One realized delay source for the whole step (repro.delays): the
    # legacy ``delay_table`` becomes a Schedule source; samplers draw from
    # the same per-step key as before (bitwise-identical trajectories,
    # tested). Schedules whose bound exceeds the ring would silently wrap
    # onto much fresher slots, so those are clamped — a no-op for specs the
    # engine validated against the ring size.
    if cfg.delay_table is not None:
        source = Schedule(cfg.delay_table).realize(num_workers=p)
    else:
        source = cfg.delay.realize(
            num_workers=p if cfg.per_worker_delays else None)
    clamp_slots = source.bound > cfg.slots - 1

    read_form, read_why = cfg.ring_read

    def ring_rows(ring, read):
        """Each worker's delayed row of a [slots, P, ...] ring in the form
        of ``cfg.ring_read``, recorded in the dispatch report."""
        dispatch.note("ring_read", read_form, read_why)
        if read_form == "rows":
            return _ring_rows(ring, read)
        return _ring_rows_gathered(ring, read)

    def per_worker_grads(params, batch):
        def one(b):
            with jax.named_scope(MODEL):
                return jax.value_and_grad(loss_fn)(params, b)
        shaped = jax.tree.map(
            lambda x: x.reshape((p, x.shape[0] // p) + x.shape[1:]), batch)
        return jax.vmap(one)(shaped)  # (losses [P], grads [P, ...])

    def realized_delays(kdelay, step, bound, shape):
        """Sampled per-step delays with every clamp applied (ring size,
        dynamic bound, no-history-before-step-0)."""
        d = source.delays(kdelay, step, shape)
        if clamp_slots:
            d = jnp.minimum(d, cfg.slots - 1)
        if bound is not None:
            d = jnp.minimum(d, jnp.asarray(bound, jnp.int32))
        return jnp.minimum(d, step)

    def pack_grads(tree, dtype=jnp.float32):
        """Gradients as packed [P, D] (or [D]) rows. Rows bound for the ring
        are packed in the ring's dtype: a cast between the packing
        concatenate and the ring write made the TPU compiler take minutes
        at deepseek-7b widths."""
        return tm.tree_pack(tree, lead_ndim=1 if cfg.per_worker_delays else 0,
                            dtype=dtype, pad_to=dispatch.PACK_ALIGN)

    def fused_tail(state, losses, gtree, kdelay, key, bound, comp):
        """Megakernel tail: everything after the backward pass is ONE
        ``dispatch.fused_update`` pass over the packed [D] view — EF split
        of the source rows, weighted delivery of the gathered ring rows
        (fresh rows take this step's in-kernel ``sent``), and the Adam
        moment/param update on the packed opt_state."""
        per = cfg.per_worker_delays
        slots = cfg.slots
        spec = tm.pack_spec(state.params)
        with jax.named_scope(RING):
            write = jnp.mod(state.step, slots)
            if cfg.s == 0:
                d = jnp.zeros((p,) if per else (), jnp.int32)
            else:
                d = realized_delays(kdelay, state.step, bound,
                                    (p,) if per else ())
            staleness = d if per else jnp.broadcast_to(d, (p,))
            mean_stale = staleness.astype(jnp.float32).mean()
            read = jnp.mod(state.step - d, slots)

        cmetrics = {}
        with jax.named_scope(OPTIMIZER):
            factor = jnp.float32(1.0)
            if compensator is not None and compensator.scales:
                factor = compensator.lr_factor(comp, mean_stale, state.step)
                cmetrics["lr_scale"] = factor
            osp = optimizer.spec
            ostep = state.opt_state["step"] + 1
            eta = lr_at(osp["lr"], ostep)
            m, v = state.opt_state["m"], state.opt_state["v"]
            pzero = jnp.zeros_like(m)
        adam_kw = dict(lr=eta, b1=osp["b1"], b2=osp["b2"], eps=osp["eps"],
                       step=ostep, scale=factor)

        if compensator is not None and compensator.sparsifies:
            # Gather the PRE-write ring rows; the kernel substitutes this
            # step's sent for fresh (delay 0) rows, so the sparse payload
            # only has to reach the ring after the kernel.
            with jax.named_scope(RING):
                acc, thr, mom_in = compensator.ef_inputs(
                    comp, pack_grads(gtree), spec.total)
                if per:
                    sel = ring_rows(state.gbuf, read)
                    weights = jnp.full((p,), 1.0 / p, jnp.float32)
                else:
                    sel = jax.lax.dynamic_index_in_dim(state.gbuf, read, 0,
                                                       keepdims=True)
                    acc, thr = acc[None], jnp.reshape(thr, (1,))
                    mom_in = None if mom_in is None else mom_in[None]
                    weights = jnp.ones((1,), jnp.float32)
                fresh = (d == 0).astype(jnp.float32).reshape(weights.shape)
            with jax.named_scope(OPTIMIZER):
                outs = dispatch.fused_update(pzero, m, v, sel, weights,
                                             acc=acc, thr=thr, fresh=fresh,
                                             mom=mom_in, **adam_kw)
            dneg, m2, v2, u, sent, resid = outs[:6]
            mom_out = outs[6] if mom_in is not None else None
            with jax.named_scope(RING):
                comp = compensator.ef_commit(
                    comp, resid if per else resid[0],
                    mom_out if (per or mom_out is None) else mom_out[0])
                cmetrics.update(compensator.ef_metrics(sent, spec.total))
                payload = sent if per else sent[0]
                gbuf = jax.lax.dynamic_update_index_in_dim(
                    state.gbuf, payload.astype(state.gbuf.dtype), write, 0)
        else:
            # Dense: the ring write happens first and the gather reads the
            # written ring (fresh rows come back verbatim) — the same
            # write-then-read order as the three-dispatch path.
            with jax.named_scope(RING):
                gbuf = jax.lax.dynamic_update_index_in_dim(
                    state.gbuf, pack_grads(gtree, state.gbuf.dtype), write,
                    0)
                if per:
                    sel = ring_rows(gbuf, read)
                    weights = jnp.full((p,), 1.0 / p, jnp.float32)
                else:
                    sel = jax.lax.dynamic_index_in_dim(gbuf, read, 0,
                                                       keepdims=True)
                    weights = jnp.ones((1,), jnp.float32)
            with jax.named_scope(OPTIMIZER):
                dneg, m2, v2, u = dispatch.fused_update(pzero, m, v, sel,
                                                        weights, **adam_kw)

        with jax.named_scope(OPTIMIZER):
            delta32 = tm.tree_unpack(dneg, spec, dtype=jnp.float32)
            wd = osp["weight_decay"]
            swd = factor * eta * wd if wd else None

            def delta_leaf(dl, pp):
                if swd is not None:
                    dl = dl - swd * pp
                return dl.astype(pp.dtype)

            delta = jax.tree.map(delta_leaf, delta32, state.params)
            params = tm.tree_add(state.params, delta)
            new_state = StaleTrainState(
                params=params, opt_state={"step": ostep, "m": m2, "v": v2},
                gbuf=gbuf, step=state.step + 1, key=key)
            metrics = {
                "loss": losses.mean(),
                "grad_norm": jnp.sqrt(jnp.sum(u * u)),
                "mean_staleness": mean_stale,
                **cmetrics,
            }
        if compensator is not None:
            return new_state, comp, metrics
        return new_state, metrics

    def deliver(state, grads, gmean, kdelay, bound, comp):
        """Ring write, delayed read and worker mean of this step's
        gradients (``grads`` per worker, or the aggregate ``gmean``):
        ``(gbuf, agg, mean_stale, comp, cmetrics)``."""
        slots = cfg.slots
        write = jnp.mod(state.step, slots)
        # Compression runs per SOURCE, before the ring write (pre-transport:
        # the ring stores the sparse sent payload, which is where sparsity
        # saves wire bytes). The residual/momentum state therefore follows
        # the source layout — [P, D] per-worker, [D] aggregate/sync. Each
        # trace-time box is written at most once per trace.
        comp_box, cmetrics = [comp], {}
        if cfg.kernels:
            # Packed hot path: gradients concatenate once into a contiguous
            # [P, D] (or [D]) view, the ring holds packed rows, and delivery
            # is ONE fused weighted reduction (dispatch.stale_accum) over the
            # selected rows instead of per-leaf gather + mean.
            spec = tm.pack_spec(state.params)
            sent_tree = grads if cfg.per_worker_delays else gmean
            gvec = pack_grads(sent_tree)
            if compensator is not None and compensator.sparsifies:
                gvec, comp_box[0], cm = compensator.sparsify_packed(
                    comp_box[0], gvec, spec.total)
                cmetrics.update(cm)
                row = gvec.astype(state.gbuf.dtype)
            else:
                row = pack_grads(sent_tree, state.gbuf.dtype)
            gbuf = jax.lax.dynamic_update_index_in_dim(state.gbuf, row,
                                                       write, 0)

            def kernel_agg(sel, weights):
                aggv = dispatch.stale_accum(
                    jnp.zeros((sel.shape[-1],), jnp.float32), sel, weights)
                return tm.tree_unpack(aggv, spec, dtype=jnp.float32)
        else:
            to_buffer = grads if cfg.per_worker_delays else gmean
            if compensator is not None and compensator.sparsifies:
                to_buffer, comp_box[0], cm = compensator.sparsify_tree(
                    comp_box[0], to_buffer,
                    lead_ndim=1 if cfg.per_worker_delays else 0)
                cmetrics.update(cm)
            gbuf = jax.tree.map(
                lambda buf, g: jax.lax.dynamic_update_index_in_dim(
                    buf, g.astype(buf.dtype), write, 0),
                state.gbuf, to_buffer)

        if cfg.s == 0:
            if cfg.kernels and cfg.per_worker_delays:
                agg = kernel_agg(gvec, jnp.full((p,), 1.0 / p, jnp.float32))
            elif cfg.per_worker_delays:
                agg = jax.tree.map(lambda g: g.mean(axis=0), to_buffer)
            elif (cfg.kernels and compensator is not None
                  and compensator.sparsifies):
                # The sparse payload is what transport delivers, even with
                # zero delay — unpack the split gvec rather than gmean.
                agg = tm.tree_unpack(gvec, spec, dtype=jnp.float32)
            else:
                agg = gmean if cfg.kernels else to_buffer
            staleness = jnp.zeros((p,), jnp.int32)
        elif cfg.per_worker_delays:
            d = source.delays(kdelay, state.step, (p,))
            if clamp_slots:
                d = jnp.minimum(d, slots - 1)
            if bound is not None:
                d = jnp.minimum(d, jnp.asarray(bound, jnp.int32))
            d = jnp.minimum(d, state.step)          # no history before step 0
            read = jnp.mod(state.step - d, slots)   # [P]

            if cfg.kernels:
                # [P, D]: each worker's delayed packed row, fused-averaged.
                sel = ring_rows(gbuf, read)
                agg = kernel_agg(sel, jnp.full((p,), 1.0 / p, jnp.float32))
            elif read_form == "rows":
                # Summed row by row, so the row reads fuse into the
                # optimizer's consumers of agg (_ring_rows).
                agg = jax.tree.map(
                    lambda buf: _worker_mean(ring_rows(buf, read)), gbuf)
            else:
                agg = jax.tree.map(
                    lambda buf: ring_rows(buf, read).astype(
                        jnp.float32).mean(axis=0), gbuf)
            staleness = d
        else:
            # Theorem-1 form: one delayed AGGREGATE gradient per step.
            d = source.delays(kdelay, state.step, ())
            if clamp_slots:
                d = jnp.minimum(d, slots - 1)
            if bound is not None:
                d = jnp.minimum(d, jnp.asarray(bound, jnp.int32))
            d = jnp.minimum(d, state.step)
            read = jnp.mod(state.step - d, slots)
            if cfg.kernels:
                sel = jax.lax.dynamic_index_in_dim(gbuf, read, 0,
                                                   keepdims=True)  # [1, D]
                agg = kernel_agg(sel, jnp.ones((1,), jnp.float32))
            else:
                agg = jax.tree.map(
                    lambda buf: jax.lax.dynamic_index_in_dim(
                        buf, read, 0, keepdims=False).astype(jnp.float32),
                    gbuf)
            staleness = jnp.broadcast_to(d, (p,))

        mean_stale = staleness.astype(jnp.float32).mean()
        return gbuf, agg, mean_stale, comp_box[0], cmetrics

    def step(state: StaleTrainState, batch,
             bound: Optional[jax.Array] = None,
             comp: Pytree = None) -> Tuple[StaleTrainState, dict]:
        with jax.named_scope(RING):
            key, kdelay = jax.random.split(state.key)
        if cfg.per_worker_delays:
            losses, grads = per_worker_grads(state.params, batch)
            gmean = None
        else:
            # Aggregate form needs only the global mean gradient — one
            # backward pass, not P vmapped ones (mathematically identical;
            # measured 14x less collective traffic on the FSDP 1T config,
            # whose per-worker backwards each re-gathered the params).
            with jax.named_scope(MODEL):
                loss, gmean = jax.value_and_grad(loss_fn)(state.params,
                                                          batch)
            losses = loss[None]
            grads = None
        if cfg.fused_update:
            return fused_tail(state, losses,
                              grads if cfg.per_worker_delays else gmean,
                              kdelay, key, bound, comp)

        with jax.named_scope(RING):
            gbuf, agg, mean_stale, comp, cmetrics = deliver(
                state, grads, gmean, kdelay, bound, comp)
        with jax.named_scope(OPTIMIZER):
            delta, opt_state = optimizer.update(agg, state.opt_state,
                                                state.params)
            if compensator is not None and compensator.scales:
                factor = compensator.lr_factor(comp, mean_stale, state.step)
                delta = compensator.scale_tree(delta, factor)
                cmetrics["lr_scale"] = factor
            params = tm.tree_add(state.params, delta)

            new_state = StaleTrainState(
                params=params, opt_state=opt_state, gbuf=gbuf,
                step=state.step + 1, key=key)
            metrics = {
                "loss": losses.mean(),
                "grad_norm": tm.tree_norm(agg),
                "mean_staleness": mean_stale,
                **cmetrics,
            }
        if compensator is not None:
            return new_state, comp, metrics
        return new_state, metrics

    return step


def make_sync_train_step(loss_fn, optimizer: Optimizer):
    """Plain synchronous data-parallel step (the 40-pair dry-run baseline)."""

    def step(state: StaleTrainState, batch) -> Tuple[StaleTrainState, dict]:
        with jax.named_scope(MODEL):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope(OPTIMIZER):
            delta, opt_state = optimizer.update(grads, state.opt_state,
                                                state.params)
            params = tm.tree_add(state.params, delta)
            new_state = StaleTrainState(
                params=params, opt_state=opt_state, gbuf=state.gbuf,
                step=state.step + 1, key=state.key)
            return new_state, {"loss": loss,
                               "grad_norm": tm.tree_norm(grads)}

    return step


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SyncTrainState:
    """Buffer-free state for the synchronous baseline (dry-run memory truth)."""
    params: Pytree
    opt_state: Pytree
    step: jax.Array


def _sync_fuses(params: Pytree) -> bool:
    """Sync has no ring delivery to fuse with, so the packed megakernel tail
    only pays when the packed pass reaches a real kernel — on oversized
    interpret-mode operands the pack/unpack copies are pure overhead over
    the per-leaf path (the ``update_fused`` convention; the ring modes keep
    the megakernel regardless because collapsing three passes into one wins
    even on the ref oracle)."""
    width = tm.padded_size(tm.pack_spec(params).total, dispatch.PACK_ALIGN)
    return dispatch.fuses(4 * width)


def init_sync_state(params: Pytree, optimizer: Optimizer,
                    fused: bool = False) -> SyncTrainState:
    if fused and _sync_fuses(params):
        # Megakernel layout: Adam moments packed at the ring width (see
        # init_state) so the fused pass aliases them in place.
        width = tm.padded_size(tm.pack_spec(params).total,
                               dispatch.PACK_ALIGN)
        opt_state = {"step": jnp.int32(0),
                     "m": jnp.zeros((width,), jnp.float32),
                     "v": jnp.zeros((width,), jnp.float32)}
    else:
        opt_state = optimizer.init(params)
    return SyncTrainState(params=params, opt_state=opt_state,
                          step=jnp.int32(0))


def make_sync_train_step_lean(loss_fn, optimizer: Optimizer,
                              compensator=None, fused: bool = False):
    """Buffer-free synchronous step. ``fused=True`` runs the post-gradient
    tail as ONE pass over the packed [D] view: the EF split (when
    compressing) happens in-kernel via ``dispatch.fused_update`` (the
    gradient plays a single fresh row of weight 1.0 — delivery is exact),
    the dense case routes straight to ``dispatch.fused_adam``, and the Adam
    moments live packed in opt_state — requires an optimizer with an Adam
    spec (``optimizers.adam().spec``). Where the packed pass would run the
    jnp ref oracle anyway (``_sync_fuses`` false: oversized interpret-mode
    operands), the step keeps the per-leaf tail — packing with nothing to
    fuse against is pure copy overhead."""
    if fused:
        spec_ = optimizer.spec if hasattr(optimizer, "spec") else None
        if not (spec_ and spec_.get("name") == "adam"):
            raise ValueError(
                "fused=True needs an optimizer with an Adam spec "
                "(optimizers.adam(...)); got an opaque optimizer")

    def fused_tail(state, loss, grads, comp):
        spec = tm.pack_spec(state.params)
        gvec = tm.tree_pack(grads, pad_to=dispatch.PACK_ALIGN)
        cmetrics = {}
        factor = jnp.float32(1.0)
        if compensator is not None and compensator.scales:
            # Staleness is identically 0 here, so "inverse" is a no-op and
            # "theorem1" reduces to its pure schedule factor — sync stays
            # the s=0 reference point of the compensated sweeps.
            factor = compensator.lr_factor(comp, jnp.float32(0.0), state.step)
            cmetrics["lr_scale"] = factor
        osp = optimizer.spec
        ostep = state.opt_state["step"] + 1
        eta = lr_at(osp["lr"], ostep)
        m, v = state.opt_state["m"], state.opt_state["v"]
        pzero = jnp.zeros_like(m)
        adam_kw = dict(lr=eta, b1=osp["b1"], b2=osp["b2"], eps=osp["eps"],
                       step=ostep, scale=factor)
        if compensator is not None and compensator.sparsifies:
            acc, thr, mom_in = compensator.ef_inputs(comp, gvec, spec.total)
            outs = dispatch.fused_update(
                pzero, m, v, jnp.zeros((1, gvec.shape[-1]), jnp.float32),
                jnp.ones((1,), jnp.float32), acc=acc[None],
                thr=jnp.reshape(thr, (1,)),
                fresh=jnp.ones((1,), jnp.float32),
                mom=None if mom_in is None else mom_in[None], **adam_kw)
            dneg, m2, v2, u, sent, resid = outs[:6]
            mom_out = outs[6][0] if mom_in is not None else None
            comp = compensator.ef_commit(comp, resid[0], mom_out)
            cmetrics.update(compensator.ef_metrics(sent, spec.total))
        else:
            # No ring and no EF split: delivery would be the identity (one
            # fresh row at weight 1.0), so skip the delivery pass and run
            # the packed Adam kernel alone, folding the LR factor into eta
            # (``scale`` only ever multiplies the delta).
            dneg, m2, v2 = dispatch.fused_adam(
                pzero, m, v, gvec, factor * eta, osp["b1"], osp["b2"],
                osp["eps"], ostep)
        delta32 = tm.tree_unpack(dneg, spec, dtype=jnp.float32)
        wd = osp["weight_decay"]
        swd = factor * eta * wd if wd else None

        def delta_leaf(dl, pp):
            if swd is not None:
                dl = dl - swd * pp
            return dl.astype(pp.dtype)

        delta = jax.tree.map(delta_leaf, delta32, state.params)
        params = tm.tree_add(state.params, delta)
        new_state = SyncTrainState(
            params=params, opt_state={"step": ostep, "m": m2, "v": v2},
            step=state.step + 1)
        if compensator is not None:
            return new_state, comp, {"loss": loss, **cmetrics}
        return new_state, {"loss": loss}

    def step(state: SyncTrainState, batch, comp: Pytree = None):
        with jax.named_scope(MODEL):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        # _sync_fuses is trace-time static (width + dispatch config), and
        # init_sync_state applies the same predicate — layouts agree.
        if fused and _sync_fuses(state.params):
            with jax.named_scope(OPTIMIZER):
                return fused_tail(state, loss, grads, comp)
        cmetrics = {}
        if compensator is not None:
            # See the fused tail's note: sync is the s=0 reference point.
            grads, comp, cmetrics = compensator.sparsify_tree(comp, grads)
        with jax.named_scope(OPTIMIZER):
            delta, opt_state = optimizer.update(grads, state.opt_state,
                                                state.params)
            if compensator is not None and compensator.scales:
                factor = compensator.lr_factor(comp, jnp.float32(0.0),
                                               state.step)
                delta = compensator.scale_tree(delta, factor)
                cmetrics = {**cmetrics, "lr_scale": factor}
            params = tm.tree_add(state.params, delta)
            new_state = SyncTrainState(params=params, opt_state=opt_state,
                                       step=state.step + 1)
        if compensator is not None:
            return new_state, comp, {"loss": loss, **cmetrics}
        return new_state, {"loss": loss}
    return step

"""Pallas megakernel: EF split + weighted stale delivery + Adam in ONE pass.

The kernel path used to make three dispatches over the same packed flat [D]
view per step (``sparsify_topk`` -> ``stale_accum`` -> ``fused_adam``), each
re-reading its operands from HBM. This kernel does the whole update in a
single grid sweep: for every ``block_d`` lane block it

1. splits the R source-row accumulators against their thresholds
   (``sent = where(|acc| >= thr, acc, 0)``, ``resid = acc - sent``), with an
   optional DGC-style momentum correction (``mom`` rows are zeroed where the
   mask kept the value, so masked coordinates keep accumulating velocity);
2. forms the delivered aggregate ``u = sum_r w[r] * delivered[r]`` where
   ``delivered[r]`` is this step's ``sent[r]`` for fresh rows (delay 0) and
   the ring row ``stale[r]`` otherwise — the caller gathers ring rows
   *before* writing, so freshness is resolved in-register instead of via a
   write-then-read round trip through the donated ring;
3. applies the bias-corrected Adam moment/param update with the compensator's
   LR factor folded in as a 7th scalar (``p' = p - scale * update``).

Params, moments, accumulators and the residual/momentum state are each read
and written exactly once per step. Three variants share the math: ``plain``
(dense delivery + Adam), ``ef`` (adds the split), ``ef_mom`` (adds the masked
momentum). Scalars ride in one stacked [7] vector like ``fused_adam``'s [6].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stack_scalars(lr, b1, b2, eps, step, scale):
    step_f = jnp.asarray(step, jnp.float32)
    b1f = jnp.asarray(b1, jnp.float32)
    b2f = jnp.asarray(b2, jnp.float32)
    return jnp.stack([
        jnp.asarray(lr, jnp.float32), b1f, b2f,
        jnp.asarray(eps, jnp.float32),
        1 - b1f ** step_f, 1 - b2f ** step_f,
        jnp.asarray(scale, jnp.float32),
    ])


def _adam(p_ref, m_ref, v_ref, u, sc, p_out, m_out, v_out):
    lr, b1, b2, eps, bc1, bc2, scale = (sc[i] for i in range(7))
    m = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * u
    v = b2 * v_ref[...].astype(jnp.float32) + (1 - b2) * u * u
    update = scale * (lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps))
    p_out[...] = (p_ref[...].astype(jnp.float32) - update).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def _kernel_plain(p_ref, m_ref, v_ref, stale_ref, w_ref, sc_ref,
                  p_out, m_out, v_out, u_out):
    w = w_ref[...].astype(jnp.float32)                     # [R]
    st = stale_ref[...].astype(jnp.float32)                # [R, block_d]
    u = jnp.sum(st * w[:, None], axis=0)                   # [block_d]
    u_out[...] = u.astype(u_out.dtype)
    _adam(p_ref, m_ref, v_ref, u, sc_ref, p_out, m_out, v_out)


def _split(acc_ref, thr_ref):
    a = acc_ref[...].astype(jnp.float32)                   # [R, block_d]
    t = thr_ref[...].astype(jnp.float32)                   # [R]
    keep = jnp.abs(a) >= t[:, None]
    sent = jnp.where(keep, a, 0.0)
    return keep, sent, a - sent


def _deliver(sent, stale_ref, fresh_ref, w_ref):
    st = stale_ref[...].astype(jnp.float32)
    fresh = fresh_ref[...].astype(jnp.float32)
    delivered = jnp.where(fresh[:, None] > 0, sent, st)
    w = w_ref[...].astype(jnp.float32)
    return jnp.sum(delivered * w[:, None], axis=0)


def _kernel_ef(p_ref, m_ref, v_ref, stale_ref, w_ref, acc_ref, thr_ref,
               fresh_ref, sc_ref, p_out, m_out, v_out, u_out,
               sent_out, resid_out):
    _, sent, resid = _split(acc_ref, thr_ref)
    sent_out[...] = sent.astype(sent_out.dtype)
    resid_out[...] = resid.astype(resid_out.dtype)
    u = _deliver(sent, stale_ref, fresh_ref, w_ref)
    u_out[...] = u.astype(u_out.dtype)
    _adam(p_ref, m_ref, v_ref, u, sc_ref, p_out, m_out, v_out)


def _kernel_ef_mom(p_ref, m_ref, v_ref, stale_ref, w_ref, acc_ref, thr_ref,
                   fresh_ref, mom_ref, sc_ref, p_out, m_out, v_out, u_out,
                   sent_out, resid_out, mom_out):
    keep, sent, resid = _split(acc_ref, thr_ref)
    sent_out[...] = sent.astype(sent_out.dtype)
    resid_out[...] = resid.astype(resid_out.dtype)
    # DGC masked momentum: coordinates that shipped restart their velocity.
    mom = mom_ref[...].astype(jnp.float32)
    mom_out[...] = jnp.where(keep, 0.0, mom).astype(mom_out.dtype)
    u = _deliver(sent, stale_ref, fresh_ref, w_ref)
    u_out[...] = u.astype(u_out.dtype)
    _adam(p_ref, m_ref, v_ref, u, sc_ref, p_out, m_out, v_out)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_update(p, m, v, stale, weights, scalars, acc=None, thr=None,
                 fresh=None, mom=None, block_d: int = 2048,
                 *, interpret: bool):
    """p/m/v [D]; stale [R, D]; weights [R]; scalars [7] stacked
    ``[lr, b1, b2, eps, bc1, bc2, scale]``. Optional EF rows acc [R, D] /
    thr [R] / fresh [R] (and mom [R, D]) switch in the split variants.
    Returns ``(p', m', v', u)`` (+ ``sent, resid`` with EF, + ``mom'``).
    D % block_d == 0."""
    (d,) = p.shape
    r = stale.shape[0]
    assert stale.shape == (r, d) and weights.shape == (r,)
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    vec = lambda: pl.BlockSpec((block_d,), lambda i: (i,))
    rows = lambda: pl.BlockSpec((r, block_d), lambda i: (0, i))
    flat = lambda n: pl.BlockSpec((n,), lambda i: (0,))
    vec_out = lambda dt: jax.ShapeDtypeStruct((d,), dt)
    rows_out = lambda dt: jax.ShapeDtypeStruct((r, d), dt)
    in_specs = [vec(), vec(), vec(), rows(), flat(r)]
    operands = [p, m, v, stale, weights]
    out_specs = [vec(), vec(), vec(), vec()]
    out_shape = [vec_out(p.dtype), vec_out(m.dtype), vec_out(v.dtype),
                 vec_out(jnp.float32)]
    if acc is None:
        kernel = _kernel_plain
    else:
        assert acc.shape == (r, d) and thr.shape == (r,) and fresh.shape == (r,)
        in_specs += [rows(), flat(r), flat(r)]
        operands += [acc, thr, fresh]
        out_specs += [rows(), rows()]
        out_shape += [rows_out(acc.dtype), rows_out(acc.dtype)]
        kernel = _kernel_ef
        if mom is not None:
            assert mom.shape == (r, d)
            in_specs.append(rows())
            operands.append(mom)
            out_specs.append(rows())
            out_shape.append(rows_out(mom.dtype))
            kernel = _kernel_ef_mom
    in_specs.append(flat(7))
    operands.append(scalars)
    return pl.pallas_call(
        kernel,
        grid=(d // block_d,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)

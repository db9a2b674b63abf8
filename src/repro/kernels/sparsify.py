"""Pallas kernel: fused threshold sparsification with error-feedback split.

``sent = where(|acc| >= thr, acc, 0)``; ``resid = acc - sent`` — the
compensation layer's hot spot (repro.compensate). The top-k *selection*
(finding the per-row k-th largest magnitude) stays outside the kernel —
it is a global reduction jnp already does well — but the masked SPLIT is a
single fused pass producing both outputs, instead of three elementwise ops
each re-reading the [R, D] accumulator from HBM (traffic: 4·R·D·bytes vs
the unfused 6·R·D).

Tiling: 1-D grid over D // block_d; each program loads every row's lane
block ([R, block_d], as ``fused_update`` does) plus the [R] thresholds, and
writes the kept and residual blocks once. A one-row block would break
Mosaic's tiling rule (the second-minor block dim must be a multiple of 8 or
span the whole array) whenever R > 1. block_d is a multiple of 128 to match
the VPU lane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(acc_ref, thr_ref, sent_ref, resid_ref):
    a = acc_ref[...].astype(jnp.float32)               # [R, block_d]
    t = thr_ref[...].astype(jnp.float32)               # [R]
    keep = jnp.abs(a) >= t[:, None]
    sent = jnp.where(keep, a, 0.0)
    sent_ref[...] = sent.astype(sent_ref.dtype)
    resid_ref[...] = (a - sent).astype(resid_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def sparsify_topk(acc: jax.Array, thr: jax.Array, block_d: int = 1024,
                  *, interpret: bool):
    """acc [R, D], thr [R] -> (sent [R, D], resid [R, D]). D % block_d == 0."""
    r, d = acc.shape
    assert thr.shape == (r,), thr.shape
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    rows = lambda: pl.BlockSpec((r, block_d), lambda j: (0, j))
    return pl.pallas_call(
        _kernel,
        grid=(d // block_d,),
        in_specs=[rows(), pl.BlockSpec((r,), lambda j: (0,))],
        out_specs=[rows(), rows()],
        out_shape=[jax.ShapeDtypeStruct((r, d), acc.dtype),
                   jax.ShapeDtypeStruct((r, d), acc.dtype)],
        interpret=interpret,
    )(acc, thr)

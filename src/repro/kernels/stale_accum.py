"""Pallas kernel: fused delayed-update delivery (the staleness-engine hotspot).

``out = params + sum_s weights[s] * buffer[s, :]`` over a parameter chunk —
one pass over the [S, D] delivery buffer instead of S separate axpy's, which
on TPU keeps the buffer slabs resident in VMEM for the whole reduction
(HBM traffic: (S+2)·D·bytes vs the unfused 3·S·D).

Tiling: grid over D in ``block_d`` lanes; each program loads the whole slot
axis (S is small: the staleness bound) for its lane block, reduces in fp32
on the VPU, adds the params block, writes once. block_d is a multiple of 128
to match the VPU lane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(params_ref, buffer_ref, weights_ref, out_ref):
    w = weights_ref[...].astype(jnp.float32)           # [S]
    buf = buffer_ref[...].astype(jnp.float32)          # [S, block_d]
    acc = jnp.sum(buf * w[:, None], axis=0)            # [block_d]
    out_ref[...] = (params_ref[...].astype(jnp.float32) + acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def stale_accum(params: jax.Array, buffer: jax.Array, weights: jax.Array,
                block_d: int = 1024, *, interpret: bool) -> jax.Array:
    """params [D], buffer [S, D], weights [S] -> [D]. D % block_d == 0."""
    (d,) = params.shape
    s = buffer.shape[0]
    assert buffer.shape == (s, d) and weights.shape == (s,)
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    grid = (d // block_d,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d,), lambda i: (i,)),
            pl.BlockSpec((s, block_d), lambda i: (0, i)),
            pl.BlockSpec((s,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((d,), params.dtype),
        interpret=interpret,
    )(params, buffer, weights)

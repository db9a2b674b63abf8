"""Pallas kernel: fused gradient-coherence reduction (Definition 1).

Computes, in ONE pass over the gradient-history matrix,
    dots[w]    = <history[w], g>
    hist_sq[w] = <history[w], history[w]>
    g_sq       = <g, g>
The unfused version reads ``history`` twice (dot + norm) and ``g`` W+1
times; fused it is exactly one read of each — at parameter-scale D (the
probe gradient is the full flattened model) this is HBM-bound, so the fused
pass halves the coherence monitor's overhead.

Tiling: 1-D grid over D; every program reduces its [W, block_d] slab and
accumulates into the [W]-shaped outputs (grid-carried accumulation: Pallas
revisits the same output block each step, init on program 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(hist_ref, g_ref, dots_ref, hsq_ref, gsq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        hsq_ref[...] = jnp.zeros_like(hsq_ref)
        gsq_ref[...] = jnp.zeros_like(gsq_ref)

    h = hist_ref[...].astype(jnp.float32)      # [W, block_d]
    g = g_ref[...].astype(jnp.float32)         # [block_d]
    dots_ref[...] += h @ g
    hsq_ref[...] += jnp.sum(h * h, axis=-1)
    gsq_ref[...] += jnp.sum(g * g)[None]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def coherence_dots(history: jax.Array, g: jax.Array, block_d: int = 2048,
                   *, interpret: bool):
    """history [W, D], g [D] -> (dots [W], hist_sq [W], g_sq scalar)."""
    w, d = history.shape
    assert g.shape == (d,)
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    grid = (d // block_d,)
    dots, hsq, gsq = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, block_d), lambda i: (0, i)),
            pl.BlockSpec((block_d,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((w,), lambda i: (0,)),
            pl.BlockSpec((w,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w,), jnp.float32),
            jax.ShapeDtypeStruct((w,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        interpret=interpret,
    )(history, g)
    return dots, hsq, gsq[0]

"""Pallas kernel: fused Adam step.

The unfused Adam update launches ~8 elementwise HLO ops, each re-reading
[D]-sized tensors from HBM (6 reads + 3 writes of D floats -> ~9·D·4 bytes).
Fused: one pass reading (p, m, v, g) and writing (p, m, v) = 7·D·4 bytes with
all intermediate math in VREGs — and on real TPUs it avoids the inter-op
HBM round-trips XLA sometimes fails to fuse across the rsqrt.

Tiling: flat 1-D grid over D, fp32 math regardless of storage dtype.
Bias-correction scalars are computed on the host side of the call (they are
step-dependent scalars, not worth a VMEM slot each).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p_ref, m_ref, v_ref, g_ref, sc_ref, p_out, m_out, v_out):
    lr, b1, b2, eps, bc1, bc2 = (sc_ref[i] for i in range(6))
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * g
    v = b2 * v_ref[...].astype(jnp.float32) + (1 - b2) * g * g
    update = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    p_out[...] = (p_ref[...].astype(jnp.float32) - update).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_adam(p, m, v, g, lr, b1, b2, eps, step, block_d: int = 2048,
               *, interpret: bool):
    """All of p, m, v, g are [D]; returns (p', m', v'). step >= 1."""
    (d,) = p.shape
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    step_f = jnp.asarray(step, jnp.float32)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        1 - jnp.asarray(b1, jnp.float32) ** step_f,
        1 - jnp.asarray(b2, jnp.float32) ** step_f,
    ])
    grid = (d // block_d,)
    blk = lambda: pl.BlockSpec((block_d,), lambda i: (i,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[blk(), blk(), blk(), blk(),
                  pl.BlockSpec((6,), lambda i: (0,))],
        out_specs=[blk(), blk(), blk()],
        out_shape=[
            jax.ShapeDtypeStruct((d,), p.dtype),
            jax.ShapeDtypeStruct((d,), m.dtype),
            jax.ShapeDtypeStruct((d,), v.dtype),
        ],
        interpret=interpret,
    )(p, m, v, g, scalars)

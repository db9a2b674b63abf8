"""Pallas kernel: blockwise online-softmax attention forward (FlashAttention
adapted to TPU), with causal and sliding-window masking and GQA head groups.

TPU adaptation (vs the CUDA original): the SRAM tiling becomes VMEM
BlockSpecs — the grid is (batch*heads, q_blocks, kv_blocks) with the kv axis
innermost, so the (m, l, acc) running-softmax state lives in VMEM scratch
that persists across the kv sweep while q/k/v blocks stream HBM->VMEM.
Block sizes default to 128 (MXU tile edge); scores hit the MXU as
[block_q, head_dim] @ [head_dim, block_k].

Forward only: the framework uses it on the serving path (prefill); training
uses the jnp attention (differentiable) — recorded in DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, window, sq, sk, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # [bq, hd]
    k = k_ref[0].astype(jnp.float32)            # [bk, hd]
    v = v_ref[0].astype(jnp.float32)            # [bk, hd]

    scores = (q @ k.T) * scale                  # [bq, bk]

    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q)[:, None] + (sk - sq)
    k_pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
    mask = k_pos < sk  # guards kv padding
    if causal or window:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    *, interpret: bool):
    """q [B,Sq,H,hd], k/v [B,Sk,Hkv,hd] -> [B,Sq,H,hd].

    GQA: q head h reads kv head h // (H//Hkv). Sq/Sk need not be multiples of
    the block sizes (padded; masked out). q is assumed right-aligned with the
    kv sequence (q offset = Sk - Sq), matching prefill/decode use."""
    b, sq, h, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    scale = 1.0 / (hd ** 0.5)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qf = jnp.moveaxis(qp, 2, 1).reshape(b * h, sq + pad_q, hd)
    kf = jnp.moveaxis(kp, 2, 1).reshape(b * hkv, sk + pad_k, hd)
    vf = jnp.moveaxis(vp, 2, 1).reshape(b * hkv, sk + pad_k, hd)

    nq = (sq + pad_q) // block_q
    nk = (sk + pad_k) // block_k
    grid = (b * h, nq, nk)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, sq=sq, sk=sk,
        block_q=block_q, block_k=block_k)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, iq, ik, g=g, hkv=hkv, h=h:
                         ((bh // h) * hkv + (bh % h) // g, ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, iq, ik, g=g, hkv=hkv, h=h:
                         ((bh // h) * hkv + (bh % h) // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(b, h, sq + pad_q, hd)[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)

"""Pallas kernel: decode attention straight out of the packed page pool.

The serving plane keeps every slot's decode cache in ONE flat
``[num_pages + 1, page_tokens, width]`` array (``serving/cache.py``); the
baseline serve step gathers each slot's pages into a contiguous ring, runs
``api.decode``, and scatters the touched page back. This kernel removes the
round-trip for the attention K/V reads: the grid walks (slot, page-slot),
the host page table rides in as scalar prefetch so each page's K and V
column blocks stream HBM->VMEM *in place* (BlockSpec index maps resolve
``table[slot, j]`` and the per-layer column offset), and an online-softmax
(m, l, acc) scratch accumulates across the page sweep exactly like
``flash_attention.py``.

Ring semantics are reproduced arithmetically instead of reading the cache's
``slot_pos`` columns: with the ring invariant (position p lives in row
``p % tokens``), row ``r`` of a slot at decode position ``pos`` holds

    spos(r) = pos - 1 - ((pos - 1 - r) % tokens)

which is negative for never-written rows AND for the cursor row about to be
overwritten (``spos = pos - tokens``, masked by ``spos >= 0`` full-causal
and by the strict window check under sliding-window) — so the stale row
drops out without any update to the pool. The just-projected token's K/V
enters as a separate operand folded in at the final grid step
(``j == pages_per_slot``), and rows whose page table entry is the null page
(lazily allocated slots) are masked, which is what decouples ``max_seq``
from the pool size.

Forward only, single query token per slot — this is the serve decode step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, pos_ref, meta_ref, q_ref, kn_ref, vn_ref,
            kp_ref, vp_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, hkv, g, hd, tokens, page_tokens, pps, window, null_page):
    si = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    posv = pos_ref[si]
    q = q_ref[0].astype(jnp.float32) * scale            # [H, hd]

    @pl.when(j < pps)
    def _page():
        pid = tables_ref[si, jnp.minimum(j, pps - 1)]
        kpg = kp_ref[0].astype(jnp.float32).reshape(page_tokens, hkv, hd)
        vpg = vp_ref[0].astype(jnp.float32).reshape(page_tokens, hkv, hd)
        r = j * page_tokens + jax.lax.iota(jnp.int32, page_tokens)
        spos = posv - 1 - ((posv - 1 - r) % tokens)
        ok = (r < tokens) & (spos >= 0) & (pid != null_page)
        if window:
            ok = ok & (spos > posv - window)
        for n in range(hkv):                             # static GQA groups
            sl = slice(n * g, (n + 1) * g)
            sc = q[sl] @ kpg[:, n].T                     # [g, T]
            sc = jnp.where(ok[None, :], sc, NEG_INF)
            m_prev = m_scr[sl]
            m_new = jnp.maximum(m_prev, sc.max(axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new[:, None])
            l_scr[sl] = l_scr[sl] * alpha + p.sum(axis=-1)
            acc_scr[sl] = acc_scr[sl] * alpha[:, None] + p @ vpg[:, n]
            m_scr[sl] = m_new

    @pl.when(j == pps)
    def _new_token():
        # Fold in the just-projected token (always valid: it attends to
        # itself under both full-causal and sliding-window), then finish.
        kn = kn_ref[0, 0].astype(jnp.float32).reshape(hkv, hd)
        vn = vn_ref[0, 0].astype(jnp.float32).reshape(hkv, hd)
        for n in range(hkv):
            sl = slice(n * g, (n + 1) * g)
            sc = (q[sl] @ kn[n][:, None])[:, 0]          # [g]
            m_prev = m_scr[sl]
            m_new = jnp.maximum(m_prev, sc)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_scr[sl] = l_scr[sl] * alpha + p
            acc_scr[sl] = (acc_scr[sl] * alpha[:, None]
                           + p[:, None] * vn[n][None, :])
            m_scr[sl] = m_new
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("k_off", "v_off", "kv_heads", "head_dim", "tokens",
                     "page_tokens", "window", "interpret"))
def paged_attention(q, k_new, v_new, pages, tables, pos, layer, *,
                    k_off: int, v_off: int, kv_heads: int, head_dim: int,
                    tokens: int, page_tokens: int, window: int = 0,
                    interpret: bool):
    """q [S,H,hd]; k_new/v_new [S,Hkv,hd]; pages [P+1,T,W] (packed pool);
    tables [S,PPS] page ids (null = P); pos [S] absolute decode positions;
    ``layer`` a traced scalar selecting the per-layer K/V column block at
    ``k_off + layer * Hkv*hd`` inside each row. Returns [S,H,hd].

    Contract (checked by the dispatcher): ``Hkv*hd`` divides 128-aligned and
    both offsets are ``Hkv*hd``-aligned, so the per-layer column block is a
    whole BlockSpec block on the packed row axis.
    """
    s, h, hd = q.shape
    hkv = kv_heads
    g = h // hkv
    kvsz = hkv * hd
    pps = tables.shape[1]
    null_page = pages.shape[0] - 1
    scale = 1.0 / (hd ** 0.5)
    kcol = k_off // kvsz
    vcol = v_off // kvsz

    meta = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    tables = tables.astype(jnp.int32)
    posv = pos.astype(jnp.int32)
    # [S, 1, kvsz] with a (1, 1, kvsz) block: the last two block dims then
    # span the array's own (1, kvsz), which Mosaic's (8, 128) tiling rule
    # accepts for any slot count (a (1, kvsz) block over [S, kvsz] does not).
    knf = k_new.reshape(s, 1, kvsz)
    vnf = v_new.reshape(s, 1, kvsz)

    def page_map(col0):
        def index_map(si, j, tables_ref, pos_ref, meta_ref):
            pid = jnp.where(j == pps, null_page,
                            tables_ref[si, jnp.minimum(j, pps - 1)])
            return (pid, 0, col0 + meta_ref[0])
        return index_map

    kernel = functools.partial(
        _kernel, scale=scale, hkv=hkv, g=g, hd=hd, tokens=tokens,
        page_tokens=page_tokens, pps=pps, window=window, null_page=null_page)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, pps + 1),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda si, j, *_: (si, 0, 0)),
            pl.BlockSpec((1, 1, kvsz), lambda si, j, *_: (si, 0, 0)),
            pl.BlockSpec((1, 1, kvsz), lambda si, j, *_: (si, 0, 0)),
            pl.BlockSpec((1, page_tokens, kvsz), page_map(kcol)),
            pl.BlockSpec((1, page_tokens, kvsz), page_map(vcol)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda si, j, *_: (si, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, hd), v_new.dtype),
        interpret=interpret,
    )(tables, posv, meta, q, knf, vnf, pages, pages)

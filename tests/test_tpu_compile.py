"""Every main-path Pallas kernel compiles for a TPU v5e at real widths.

Interpret-mode tests cannot see Mosaic's lowering rules (block tiling, VMEM
limits), so each kernel is compiled here with ``interpret=False`` against a
*described* ``v5e:2x2`` topology — the TPU compiler runs, no chip is needed
— and the compiled HLO must hold the kernel as a ``tpu_custom_call``.

Widths: a packed parameter view of D = 2^24 (a deepseek-7b layer slice),
deepseek-7b attention (32 heads x head_dim 128, kv 32), P = 2..4 worker
rows, and 8 serving slots.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
each import every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import coherence as _co
from repro.kernels import flash_attention as _fl
from repro.kernels import fused_adam as _fa
from repro.kernels import fused_update as _fu
from repro.kernels import paged_attention as _pa
from repro.kernels import sparsify as _sp
from repro.kernels import stale_accum as _sa

D = 1 << 24
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

# deepseek-7b serving at 2 layers, 8 slots, 160-token
# rings in 8-token pages; each packed row holds every layer's K, V and the
# ring positions (serving/cache.py's layout: k at 0, v at L*kvsz).
LAYERS, HEADS, KV_HEADS, HEAD_DIM = 2, 32, 32, 128
KVSZ = KV_HEADS * HEAD_DIM
SLOTS, TOKENS, PAGE_TOKENS = 8, 160, 8
PPS = TOKENS // PAGE_TOKENS
NUM_PAGES = SLOTS * PPS
WIDTH = 2 * LAYERS * KVSZ + LAYERS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache but
    can never be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, text[:2000]


def _fused_update(ef: bool):
    def fn(p, m, v, stale, w, sc, *ef_ops):
        acc, thr, fresh = ef_ops if ef else (None, None, None)
        return _fu.fused_update(p, m, v, stale, w, sc, acc=acc, thr=thr,
                                fresh=fresh, block_d=2048, interpret=False)
    shapes = [((D,), F32)] * 3 + [((2, D), BF16), ((2,), F32), ((7,), F32)]
    if ef:
        shapes += [((2, D), F32), ((2,), F32), ((2,), F32)]
    return fn, shapes


def _paged_attention(p_q, kn, vn, pages, tables, pos, layer):
    return _pa.paged_attention(
        p_q, kn, vn, pages, tables, pos, layer, k_off=0, v_off=LAYERS * KVSZ,
        kv_heads=KV_HEADS, head_dim=HEAD_DIM, tokens=TOKENS,
        page_tokens=PAGE_TOKENS, interpret=False)


KERNELS = {
    "stale_accum": (
        functools.partial(_sa.stale_accum, block_d=1024, interpret=False),
        [((D,), F32), ((4, D), F32), ((4,), F32)]),
    "fused_update": _fused_update(ef=False),
    "fused_update_ef": _fused_update(ef=True),
    "fused_adam": (
        lambda p, m, v, g: _fa.fused_adam(p, m, v, g, 1e-3, 0.9, 0.999, 1e-8,
                                          3, block_d=2048, interpret=False),
        [((D,), F32)] * 4),
    "coherence_dots": (
        functools.partial(_co.coherence_dots, block_d=2048, interpret=False),
        [((8, D), F32), ((D,), F32)]),
    "sparsify_topk": (
        functools.partial(_sp.sparsify_topk, block_d=1024, interpret=False),
        [((4, D), F32), ((4,), F32)]),
    "paged_attention": (
        _paged_attention,
        [((SLOTS, HEADS, HEAD_DIM), BF16), ((SLOTS, KV_HEADS, HEAD_DIM), BF16),
         ((SLOTS, KV_HEADS, HEAD_DIM), BF16),
         ((NUM_PAGES + 1, PAGE_TOKENS, WIDTH), F32), ((SLOTS, PPS), I32),
         ((SLOTS,), I32), ((), I32)]),
    "flash_attention": (
        functools.partial(_fl.flash_attention, causal=True, interpret=False),
        [((1, 2048, HEADS, HEAD_DIM), BF16)]
        + [((1, 2048, KV_HEADS, HEAD_DIM), BF16)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, one_chip, *shapes)

"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import dispatch, ops, ref
from repro.kernels import flash_attention as _fl


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,s,block", [(1024, 1, 256), (4096, 8, 1024),
                                       (2048, 17, 512)])
def test_stale_accum_sweep(dtype, d, s, block):
    k = jax.random.PRNGKey(d + s)
    p = jax.random.normal(k, (d,), dtype)
    buf = jax.random.normal(jax.random.PRNGKey(1), (s, d), dtype)
    w = (jax.random.uniform(jax.random.PRNGKey(2), (s,)) > 0.5).astype(jnp.float32)
    got = ops.stale_accum(p, buf, w, block_d=block)
    want = ref.stale_accum(p, buf, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@given(seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_stale_accum_property_zero_weights(seed):
    """All-zero weights must return params exactly."""
    k = jax.random.PRNGKey(seed)
    p = jax.random.normal(k, (2048,))
    buf = jax.random.normal(jax.random.PRNGKey(seed + 1), (4, 2048))
    got = ops.stale_accum(p, buf, jnp.zeros((4,)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(p), atol=1e-7)


@pytest.mark.parametrize("w,d", [(1, 2048), (8, 4096), (16, 8192)])
def test_coherence_sweep(w, d):
    hist = jax.random.normal(jax.random.PRNGKey(0), (w, d))
    g = jax.random.normal(jax.random.PRNGKey(1), (d,))
    got = ops.coherence_dots(hist, g)
    want = ref.coherence_dots(hist, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4)


@pytest.mark.parametrize("d,step", [(2048, 1), (4096, 100)])
def test_fused_adam_sweep(d, step):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p, m, v, g = (jax.random.normal(k, (d,)) for k in ks)
    v = jnp.abs(v)
    got = ops.fused_adam(p, m, v, g, 1e-3, step=step)
    want = ref.fused_adam(p, m, v, g, 1e-3, 0.9, 0.999, 1e-8, step)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fused_adam_agrees_with_optimizer_module():
    """The kernel and the pytree Adam implement the same update."""
    from repro.optim import adam
    d = 2048
    p = jax.random.normal(jax.random.PRNGKey(0), (d,))
    g = jax.random.normal(jax.random.PRNGKey(1), (d,))
    opt = adam(1e-3)
    state = opt.init({"w": p})
    delta, state2 = opt.update({"w": g}, state, {"w": p})
    p_opt = p + delta["w"]
    p_kern, _, _ = ops.fused_adam(p, jnp.zeros(d), jnp.zeros(d), g, 1e-3, step=1)
    np.testing.assert_allclose(np.asarray(p_opt), np.asarray(p_kern),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sq,sk,h,hkv,hd,win,dtype", [
    (128, 128, 4, 2, 64, 0, jnp.float32),
    (100, 260, 8, 8, 32, 0, jnp.float32),
    (64, 192, 4, 1, 128, 48, jnp.float32),
    (1, 300, 4, 2, 64, 0, jnp.float32),
    (96, 96, 2, 2, 64, 0, jnp.bfloat16),
    (33, 77, 6, 3, 16, 20, jnp.float32),
])
def test_flash_attention_sweep(sq, sk, h, hkv, hd, win, dtype):
    """Raw kernel (padding path included) vs the oracle — the dispatcher
    would route non-divisible seq lens to ref, so call the kernel directly
    to keep its padding/masking under test."""
    q = jax.random.normal(jax.random.PRNGKey(6), (2, sq, h, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(7), (2, sk, hkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(8), (2, sk, hkv, hd), dtype)
    got = _fl.flash_attention(q, k, v, causal=True, window=win,
                              block_q=32, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=win)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("sq,sk,expect_ref", [
    (128, 128, False),   # divisible: the kernel runs
    (100, 260, True),    # odd seq lens: dispatcher falls back to ref
])
def test_flash_attention_dispatch_guard(sq, sk, expect_ref):
    """ops/dispatch guard (same contract as the other three dispatchers):
    seq lens that don't divide the blocks fall back to ref instead of
    relying on in-kernel padding."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, sq, 4, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, sk, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, sk, 2, 32))
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=64)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    backend = dispatch.report()["flash_attention"]
    assert backend.startswith("ref") == expect_ref, backend


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the transformer's training attention path."""
    from repro.models import transformer as tr
    cfg = tr.TransformerConfig(
        name="t", num_layers=1, d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=8, d_ff=64, vocab=64, vocab_real=64, tp=1,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    b, s = 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, 2, 8))
    mask = tr.L.causal_mask(s, s, 0)
    want = tr._attend(q, k, v, mask[None], cfg)
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)

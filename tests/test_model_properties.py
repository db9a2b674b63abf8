"""Property tests on model invariants (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import ssm as ssm_lib
from repro.models import transformer as tr


def tiny_cfg(**kw):
    base = dict(name="t", num_layers=2, d_model=32, num_heads=4,
                num_kv_heads=2, head_dim=8, d_ff=64, vocab=64, vocab_real=60,
                tp=1, dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    base.update(kw)
    return tr.TransformerConfig(**base)


@given(seed=st.integers(0, 100), pos=st.integers(1, 8))
@settings(max_examples=8, deadline=None)
def test_causality(seed, pos):
    """Changing token t+1.. must not change logits at positions <= t."""
    cfg = tiny_cfg()
    params, _ = tr.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, 10), 0, 60)
    toks2 = toks.at[0, pos + 1:].set((toks[0, pos + 1:] + 7) % 60)
    l1, _ = tr.forward(params, toks, cfg)
    l2, _ = tr.forward(params, toks2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :pos + 1]),
                               np.asarray(l2[:, :pos + 1]), atol=1e-5)


@given(seed=st.integers(0, 100))
@settings(max_examples=6, deadline=None)
def test_chunked_equals_naive_property(seed):
    cfg_n = tiny_cfg()
    cfg_c = tiny_cfg(attn_impl="chunked", attn_chunk=3)
    params, _ = tr.init(jax.random.PRNGKey(seed % 5), cfg_n)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (2, 11), 0, 60)
    ln, _ = tr.forward(params, toks, cfg_n)
    lc, _ = tr.forward(params, toks, cfg_c)
    np.testing.assert_allclose(np.asarray(ln), np.asarray(lc),
                               rtol=1e-4, atol=1e-4)


def test_swa_equals_full_when_window_covers():
    """swa_window >= seq_len must equal full attention exactly."""
    cfg_f = tiny_cfg()
    cfg_w = tiny_cfg(swa_window=64)
    params, _ = tr.init(jax.random.PRNGKey(1), cfg_f)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 60)
    lf, _ = tr.forward(params, toks, cfg_f)
    lw, _ = tr.forward(params, toks, cfg_w)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lw), atol=1e-5)


def test_padded_vocab_never_predicted():
    cfg = tiny_cfg()  # vocab 64, real 60
    params, _ = tr.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 60)
    logits, _ = tr.forward(params, toks, cfg)
    assert float(logits[..., 60:].max()) < -1e8


@given(seed=st.integers(0, 50), t=st.integers(4, 16))
@settings(max_examples=6, deadline=None)
def test_ssd_streaming_equals_batch(seed, t):
    """Processing a sequence in two segments through the cache must equal
    one full pass (the SSD state is a sufficient statistic)."""
    cfg = ssm_lib.SSMSettings(d_model=16, d_state=8, head_dim=8, expand=2,
                              chunk=5, conv_width=4)
    p = ssm_lib.init_mamba_block(jax.random.PRNGKey(0), cfg)
    from repro.models.layers import unzip
    pv, _ = unzip(p)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, t, 16))
    y_full, _ = ssm_lib.mamba_forward(pv, x, cfg)
    cut = t // 2
    y1, cache = ssm_lib.mamba_forward(pv, x[:, :cut], cfg)
    y2, _ = ssm_lib.mamba_forward(pv, x[:, cut:], cfg, cache=cache)
    got = jnp.concatenate([y1, y2], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y_full),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_monotone():
    """Higher capacity factor can only decrease routing drops (more tokens
    processed => output closer to the dropless result)."""
    from repro.models.transformer import MoESettings
    cfg_lo = tiny_cfg(num_kv_heads=4, moe=MoESettings(
        num_experts=8, num_experts_real=8, top_k=2, d_ff=32,
        capacity_factor=0.5))
    cfg_hi = tiny_cfg(num_kv_heads=4, moe=MoESettings(
        num_experts=8, num_experts_real=8, top_k=2, d_ff=32,
        capacity_factor=16.0))
    params, _ = tr.init(jax.random.PRNGKey(3), cfg_hi)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 60)
    l_hi, _ = tr.forward(params, toks, cfg_hi)      # ~dropless reference
    l_lo, _ = tr.forward(params, toks, cfg_lo)
    # low capacity must still be finite and (weakly) different
    assert bool(jnp.isfinite(l_lo).all())
    assert not np.allclose(np.asarray(l_lo), np.asarray(l_hi))

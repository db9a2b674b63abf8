"""The dense decoder family: a stack of one kind of layer, rotary causal
attention with grouped K/V heads and an optional sliding window, a
SiLU-gated MLP, RMS norms and an untied output head (deepseek-llm-7b,
h2o-danube).

Everything of the benchmark that depends on the family, found by the
configuration file's ``"model": "dense"``:

* ``overrides`` and ``check``: the program's config fields set from the
  file, and every width of the program checked against it;
* ``shapes`` and ``fan_in``: the layout of the weights ``bench/weights.py``
  makes;
* ``logits``: the plain float32 reference forward;
* ``train_step_flops`` and the counts it needs, which ``mfu.train`` reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference import mm, rms


# -- the program ------------------------------------------------------------

def overrides(cfg: dict) -> dict:
    """The program's config fields set from the file: depth, vocabulary and
    the norm's epsilon."""
    return {"num_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "vocab_real": cfg["vocab_size"],
            "norm_eps": cfg["rms_norm_eps"]}


def check(cfg: dict, c) -> None:
    """Raise where a width of the program's config ``c`` differs from the
    file, or where the program runs something the family has not (experts,
    a q/k norm, a logit soft-cap)."""
    have = {"hidden_size": c.d_model, "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim,
            "intermediate_size": c.d_ff, "sliding_window": c.swa_window,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps}
    wrong = {k: (v, cfg[k]) for k, v in have.items() if v != cfg[k]}
    if wrong or c.moe is not None or c.qk_norm or c.logit_softcap:
        raise ValueError(f"program config differs from {cfg['name']}: "
                         f"(program, file) {wrong}")


# -- the weights ------------------------------------------------------------

def shapes(cfg: dict) -> dict:
    """Leaf shapes of the program transformer's parameter tree (``embed``,
    ``head``, ``final_ln`` and the layer stack with its leading layer
    axis)."""
    d, h, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd, f, nl, v = (cfg["head_dim"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"], cfg["vocab_size"])
    return {
        "embed": (v, d), "head": (d, v), "final_ln": (d,),
        "layers": {
            "ln1": (nl, d), "ln2": (nl, d),
            "attn": {"wq": (nl, d, h, hd), "wk": (nl, d, hkv, hd),
                     "wv": (nl, d, hkv, hd), "wo": (nl, h, hd, d)},
            "mlp": {"w_gate": (nl, d, f), "w_up": (nl, d, f),
                    "w_down": (nl, f, d)},
        },
    }


def fan_in(name: str, shape) -> int:
    """Inputs that each output of the matrix ``name`` sums over."""
    if name.endswith("wo"):
        return shape[-3] * shape[-2]          # heads x head_dim
    return shape[1] if len(shape) > 2 else shape[0]


# -- the reference ----------------------------------------------------------

def _rope(x, pos, theta):
    """Rotary positions, rotate-half layout, inverse frequencies
    theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, cfg: dict, quant=None):
    """tokens [B, S] -> logits [B, S, V], float32: RMS norm, rotary causal
    attention with an optional sliding window and grouped K/V heads, a
    SiLU-gated MLP, a final norm and an untied output head."""
    b, s = tokens.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta, window = (cfg["rms_norm_eps"], cfg["rope_theta"],
                          cfg.get("sliding_window"))
    pos = jnp.arange(s)
    allowed = pos[None, :] <= pos[:, None]
    if window:
        allowed &= pos[None, :] > pos[:, None] - window
    x = params["embed"][tokens]
    lay = params["layers"]
    for i in range(cfg["num_hidden_layers"]):
        a = rms(x, lay["ln1"][i], eps)
        q = _rope(mm("bsd,dhk->bshk", a, lay["attn"]["wq"][i], quant),
                  pos, theta)
        k = _rope(mm("bsd,dhk->bshk", a, lay["attn"]["wk"][i], quant),
                  pos, theta)
        v = mm("bsd,dhk->bshk", a, lay["attn"]["wv"][i], quant)
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        sc = mm("bqhk,bthk->bhqt", q, k, quant) / np.sqrt(hd)
        sc = jnp.where(allowed[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = mm("bhqt,bthk->bqhk", p, v, quant)
        x = x + mm("bqhk,hkd->bqd", o, lay["attn"]["wo"][i], quant)
        m = rms(x, lay["ln2"][i], eps)
        g = mm("bsd,df->bsf", m, lay["mlp"]["w_gate"][i], quant)
        u = mm("bsd,df->bsf", m, lay["mlp"]["w_up"][i], quant)
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                   lay["mlp"]["w_down"][i], quant)
    x = rms(x, params["final_ln"], eps)
    return mm("bsd,dv->bsv", x, params["head"], quant)


# -- operation counts (``bench/flops.py`` has the conventions) ---------------

def matmul_params(cfg: dict) -> int:
    """Weights that multiply activations: attention projections, the gated
    MLP and the output head. The embedding table and norm scales are not."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * h * hd * 2 + d * hkv * hd * 2          # wq, wo; wk, wv
    mlp = 3 * d * f                                   # gate, up, down
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def attention_flops_fwd(cfg: dict, seq: int) -> int:
    """Forward attention operations of one sequence over all layers: the
    scores and the weighted sum each cost 2 H hd operations per (query, key)
    pair a query attends to."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = flops.attended_pairs(seq, cfg.get("sliding_window"))
    return cfg["num_hidden_layers"] * 4 * h * hd * pairs


def train_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model operations of one training step over ``batch`` sequences of
    ``seq`` tokens: forward plus backward, 3 x forward."""
    tokens = batch * seq
    return (6 * matmul_params(cfg) * tokens
            + 3 * batch * attention_flops_fwd(cfg, seq))


def decode_step_flops(cfg: dict, contexts) -> int:
    """Operations of one decode step that advances one token in each active
    slot. ``contexts`` lists, per active slot, the keys its query attends
    to (the cached positions plus the new token), before the window."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    window = cfg.get("sliding_window")
    attn = 0
    for c in contexts:
        attn += 4 * h * hd * (min(c, window) if window else c)
    return (2 * matmul_params(cfg) * len(contexts)
            + cfg["num_hidden_layers"] * attn)


def paged_attention_cost(cfg: dict, cached, kv_bytes: int = 4):
    """(operations, bytes) that one layer's paged decode attention needs:
    each active slot's query against its ``cached`` keys read from the page
    pool plus the new token, K and V read once at ``kv_bytes`` per element
    (the pool stores float32 rows). The query and output (H x hd each) and
    the new token's K/V are counted too."""
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    window = cfg.get("sliding_window")
    total_flops = nbytes = 0
    for c in cached:
        keys = min(c + 1, window) if window else c + 1
        total_flops += 4 * h * hd * keys
        nbytes += 2 * (keys - 1) * hkv * hd * kv_bytes   # pooled K, V
        nbytes += 2 * h * hd * 2 + 2 * hkv * hd * 2      # q, out, new k, v
    return total_flops, nbytes

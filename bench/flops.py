"""Operation and byte counts the per-layer metrics divide by.

Kept with the benchmark so that a program change cannot move its own
yardstick: a change to a kernel changes the program's time, and only a
benchmark change changes these counts. Every count is from the shapes of a
configuration file (``bench/configs/<name>.json``), never from the program.

Conventions:

* A matmul with K x N weights costs 2 K N operations per token forward, and
  twice that backward (input and weight gradients): 6 per parameter per
  token for a training step.
* The embedding lookup is a gather, not a matmul: not counted. The output
  head is a matmul: counted.
* Causal attention at the published head size: the scores and the weighted
  sum each cost 2 H hd operations per (query, key) pair a query attends to.
  A sliding window caps the keys a query sees. Recomputation (remat) is not
  counted.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``. A kind that is not in
    the table is an error, not a default."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"{path} has {sorted(table)}")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights that multiply activations: attention projections, the gated
    MLP and the output head. The embedding table and norm scales are not."""
    d, h, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * h * hd * 2 + d * hkv * hd * 2          # wq, wo; wk, wv
    mlp = 3 * d * f                                   # gate, up, down
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def attended_pairs(seq: int, window) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens, with
    each query seeing at most ``window`` keys (itself included)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq - w) * w


def attention_flops_fwd(cfg: dict, seq: int) -> int:
    """Forward attention operations of one sequence over all layers."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = attended_pairs(seq, cfg.get("sliding_window"))
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
    flops = nbytes = 0
    for c in cached:
        keys = min(c + 1, window) if window else c + 1
        flops += 4 * h * hd * keys
        nbytes += 2 * (keys - 1) * hkv * hd * kv_bytes   # pooled K, V
        nbytes += 2 * h * hd * 2 + 2 * hkv * hd * 2      # q, out, new k, v
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]):
    """Least time over measured time, as a percentage, and which bound sets
    the least time (``"flops"`` or ``"bytes"``)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound

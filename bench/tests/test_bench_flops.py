"""The operation counts (``bench/flops.py`` and the dense family's
``bench/models/dense.py``) against counts worked out by hand for both
configurations, and the peaks table."""
import json

import pytest

import benchtest_support as sup
from bench import flops, harness

CONFIGS = sup.REPO / "bench" / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


dense = harness.load_model(sup.REPO / "bench", {"model": "dense"})


def test_deepseek_7b_1l_by_hand():
    cfg = load("deepseek-7b-1L")
    attn = 4 * 4096 * 4096              # wq, wk, wv, wo: 32 x 128 = 4096
    mlp = 3 * 4096 * 11008
    head = 4096 * 8192
    assert dense.matmul_params(cfg) == attn + mlp + head == 235_929_600
    # 4 sequences of 128: 6 N T, plus causal attention 3 x 4 H hd pairs
    pairs = 128 * 129 // 2
    want = 6 * 235_929_600 * 512 + 3 * 4 * (4 * 32 * 128 * pairs)
    assert dense.train_step_flops(cfg, 4, 128) == want
    assert abs(want / 1e12 - 0.7264) < 1e-4


def test_danube_2l_by_hand():
    cfg = load("h2o-danube-1.8b-2L")
    attn = 2 * 2560 * 32 * 80 + 2 * 2560 * 8 * 80     # wq, wo; wk, wv (GQA)
    mlp = 3 * 2560 * 6912
    head = 2560 * 32000
    assert dense.matmul_params(cfg) == 2 * (attn + mlp) + head == 220_856_320
    # 2048 tokens stay inside the 4096 window: plain causal pairs
    pairs = 2048 * 2049 // 2
    want = 6 * 220_856_320 * 8192 + 3 * 4 * 2 * (4 * 32 * 80 * pairs)
    assert dense.train_step_flops(cfg, 4, 2048) == want
    assert abs(want / 1e12 - 11.37) < 0.01


def test_window_caps_attended_pairs():
    assert flops.attended_pairs(6, None) == 21
    assert flops.attended_pairs(6, 3) == 1 + 2 + 3 + 3 + 3 + 3
    assert flops.attended_pairs(6, 10) == 21


def test_decode_and_paged_attention_counts():
    cfg = load("deepseek-7b-1L")
    n = dense.matmul_params(cfg)
    # two slots attending 10 and 20 keys
    assert dense.decode_step_flops(cfg, [10, 20]) == (
        2 * n * 2 + 4 * 32 * 128 * 30)
    f, b = dense.paged_attention_cost(cfg, [9, 19])
    assert f == 4 * 32 * 128 * 30
    kv_row = 32 * 128 * 4                     # float32 pool, per K or V
    small = 2 * 32 * 128 * 2 + 2 * 32 * 128 * 2
    assert b == 2 * (9 + 19) * kv_row + 2 * small


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = flops.roofline_share(100.0, 50.0, 10.0, peak)
    assert (share, bound) == (50.0, "bytes")
    share, bound = flops.roofline_share(1000.0, 5.0, 20.0, peak)
    assert (share, bound) == (50.0, "flops")


def test_peaks_table_refuses_unknown_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")

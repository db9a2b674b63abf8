"""Shared by the benchmark's tests: a copy of the benchmark in a temporary
checkout, with small CPU cells of the program's reduced deepseek-7b."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny", "model": "dense", "program_arch": "deepseek-7b",
    "program_reduced": True,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 32, "intermediate_size": 256, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "sliding_window": None, "compute_dtype": "float32",
    "param_dtype": "float32",
    "optimizer": {"name": "adam", "lr": 0.001, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-08}}
TRAIN = {"driver": "train", "mode": "stale-psum", "workers": 2,
         "staleness": 4, "ring_dtype": "float32", "mesh": "1x1", "batch": 4,
         "seq": 16, "data": {"kind": "markov", "fan_out": 8},
         "log_every": 10, "check_steps": 6}
# The tiny cells compute in float32 on the CPU: sound runs read 1e-6 or
# less against the reference, the bfloat16 control 1e-3 or more.
LIMITS = {"tiny-train": {"grad_gap": 1e-4, "change_gap": 1e-4,
                         "grad_diff": 1e-4}}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 1e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
             "ici_bits_per_s": 1e9}


def tiny_checkout(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout at ``tmp`` holding a copy of ``bench/`` and a
    ``BENCHMARK.json`` whose cells are the tiny ones (metric entries kept,
    their cell lists pointed at the tiny cells)."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "bench tests",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU test size"}]
    spec["workloads"] = [
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "CPU test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-train"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    b = tmp / "bench"
    (b / "limits").mkdir(exist_ok=True)
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (b / "traffic" / "tiny-train.json").write_text(json.dumps(TRAIN))
    for cell, lim in LIMITS.items():
        (b / "limits" / f"{cell}.json").write_text(json.dumps(lim))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["devices"]["cpu"] = CPU_PEAKS
    (b / "peaks.json").write_text(json.dumps(peaks))
    return tmp


def run(root: pathlib.Path, cell: str, trace: bool = False,
        seed: int = 2 ** 33 + 7, seconds: float = 1.0) -> dict:
    from bench import harness
    return harness.run_cell(cell, seed, seconds, trace, root=root,
                            require_tpu=False, log=lambda msg: None)

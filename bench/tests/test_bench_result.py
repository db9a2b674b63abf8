"""The result line a run prints, and the refusal of a machine without a
TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchtest_support as sup

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return sup.tiny_checkout(tmp_path_factory.mktemp("checkout"))


def test_train_line_without_trace(root):
    line = sup.run(root, "tiny-train")
    assert list(line) == KEYS                    # checks come last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    dev = line["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["checks"]) == {"grad_gap", "change_gap", "grad_diff"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(line)


def test_train_line_with_trace(root):
    line = sup.run(root, "tiny-train", trace=True)
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"idle_share.train", "mfu.train"}
    assert line["metrics"]["mfu.train"]["unit"] == "%"
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())


def test_loss_gap_is_held_where_the_cell_limits_it(tmp_path):
    root = sup.tiny_checkout(tmp_path)
    limits = dict(sup.LIMITS["tiny-train"], loss_gap=1e-4)
    (root / "bench" / "limits" / "tiny-train.json").write_text(
        json.dumps(limits))
    line = sup.run(root, "tiny-train")
    assert list(line["checks"]) == ["loss_gap", "grad_gap", "change_gap",
                                    "grad_diff"]
    assert line["correct"] is True


def _bench(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    p = _bench(["--workload", "ds7b-train-ring", "--seed", str(2 ** 33),
                "--seconds", "1", "--trace", "0"], sup.REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(sup.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(sup.REPO / "BENCHMARK.json", tmp_path)
    p = _bench(["--workload", "ds7b-train-ring", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

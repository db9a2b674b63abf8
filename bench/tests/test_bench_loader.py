"""A configuration, a cell and a per-layer metric added as new files and
new entries in BENCHMARK.json are found with no code edited."""
import json

import pytest

import benchtest_support as sup
from bench import harness


def test_new_files_are_found_by_name(tmp_path):
    root = sup.tiny_checkout(tmp_path)
    b = root / "bench"
    cfg = dict(sup.TINY, name="tiny-wide", intermediate_size=512)
    (b / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    traffic = dict(sup.TRAIN, batch=8, seq=32)
    (b / "traffic" / "tiny-train-8x32.json").write_text(json.dumps(traffic))
    (b / "metrics" / "tokens_per_step.train.py").write_text(
        "def read(run):\n"
        "    return run.counters['batch'] * run.counters['seq']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "bench/configs/tiny-wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-wide-train", "config": "tiny-wide",
                              "traffic": "tiny-train-8x32", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tokens_per_step.train",
                              "unit": "tokens", "better": "higher",
                              "source": "program_counter",
                              "layer": "engine step",
                              "moves": "train_tokens_per_s"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-wide-train")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny-wide-train", root)
    assert cell.config["intermediate_size"] == 512
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (8, 32)
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "tokens_per_step.train" in names          # no workloads key
    assert "idle_share.train" not in names           # listed cells only
    assert harness.load_driver(cell).__name__.endswith("train")
    outcome = harness.Outcome(end_to_end={}, checks=[], attempted=0,
                              failed=0, memory_peak_bytes=0,
                              counters={"batch": 8, "seq": 32})
    run = harness.Run(cell, outcome, {}, 1)
    got = harness.per_layer_metrics(cell, run, lambda m: None)
    assert got == {"tokens_per_step.train": {"value": 256.0,
                                             "unit": "tokens"}}


def test_committed_cells_load():
    spec = json.loads((sup.REPO / "BENCHMARK.json").read_text())
    for name in [w["name"] for w in spec["workloads"]]:
        cell = harness.load_cell(name)
        assert cell.traffic["driver"] == "train"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        assert cell.limits
        harness.load_driver(cell)
        for m in cell.per_layer:
            assert callable(harness.load_reader(cell, m["name"]).read)


def _committed(kind: str) -> list:
    spec = json.loads((sup.REPO / "BENCHMARK.json").read_text())
    return [e["name"] for e in spec[kind]]


@pytest.mark.parametrize("name", _committed("configs"))
def test_program_runs_the_file_as_stated(name):
    # depth, vocabulary and the norm's epsilon are set from the file; every
    # width is checked against it
    from bench import program
    cell_cfg = json.loads((sup.REPO / "bench" / "configs" /
                           f"{name}.json").read_text())
    model = harness.load_model(sup.REPO / "bench", cell_cfg)
    _, api = program.model_api(model, cell_cfg)
    assert api.cfg.norm_eps == cell_cfg["rms_norm_eps"]
    assert api.cfg.num_layers == cell_cfg["num_hidden_layers"]
    wrong = dict(cell_cfg, hidden_size=cell_cfg["hidden_size"] + 1)
    with pytest.raises(ValueError):
        program.model_api(model, wrong)


@pytest.mark.parametrize("name", _committed("workloads"))
def test_checked_steps_reach_the_ring_wrap(name):
    # the checked steps outlast the ring (s slots), so a slot written
    # twice is read back, and the seeds draw the largest delay, s - 1
    from bench import program, reference
    tr = harness.load_cell(name).traffic
    s, steps = tr["staleness"], tr["check_steps"]
    assert steps >= s + 2
    most = max(int(reference.uniform_delays(program.key_seed(2 ** 33 + i),
                                            steps, tr["workers"], s).max())
               for i in range(6))
    assert most == s - 1

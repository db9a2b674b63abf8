"""The benchmark harness: finds a cell by name, refuses a machine without
the chips it asks for, runs the cell's driver, and prints the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one cell is data found by name:

* ``BENCHMARK.json`` (the checkout root): the cell's configuration, traffic
  mix and chip count, and the metrics it reports;
* ``bench/configs/<config>.json``: the model as it is run, its source and
  cut, and its family under ``"model"``;
* ``bench/models/<model>.py``: the family's module, one per family, which
  owns everything that depends on it:
  ``overrides(cfg) -> dict``, the program's config fields the file sets;
  ``check(cfg, program_cfg)``, which raises where a width of the program
  differs from the file; ``shapes(cfg)`` (the weights' tree of leaf
  shapes) and ``fan_in(name, shape)``, asked of every leaf, the layout
  ``bench/weights.py`` makes; ``logits(params, tokens, cfg, quant)``, the
  plain float32 reference forward built on ``bench/reference.py``'s
  ``mm``; ``train_step_flops(cfg, batch, seq)``, the model operations of
  one training step (``bench/flops.py`` has the conventions);
* ``bench/traffic/<traffic>.json``: the driver kind (``train``)
  and the parameters that kind's generator reads;
* ``bench/limits/<cell>.json``: the limit of each number the correctness
  check compares;
* ``bench/drivers/<kind>.py``: one driver per kind (``run(ctx) -> Outcome``);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric
  (``read(run) -> float | None``); a layer the program names in its step
  is read by ``bench/layers.py``'s ``layer_ms(run, <layer>)``.

A later cell, configuration or per-layer metric is new files and new
entries in ``BENCHMARK.json``; no code here changes. A configuration of
another family is its config file, its ``bench/models/<model>.py``, its
traffic and limits files, any reader files, and its cell's name appended
to the ``workloads`` lists of the metrics it reports.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(RuntimeError):
    """The machine cannot run the cell: no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: pathlib.Path
    model: Any                          # the module bench/models/<model>.py


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end values, the numbers compared
    with their limits, counts for the metric readers, and the window."""
    end_to_end: Dict[str, float]
    checks: List[tuple]                 # (name, value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None                   # tracing.Summary of the traced window


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    started: float                      # time.monotonic() at process start
    devices: list
    log: Callable[[str], None]


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / spec["paths"][0]
    applies = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    limits_path = bench / "limits" / f"{name}.json"
    config = _json(root / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config,
        traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_json(limits_path) if limits_path.exists() else {},
        end_to_end=e2e, per_layer=per_layer, bench=bench,
        model=load_model(bench, config))


def _module(path: pathlib.Path):
    if not path.exists():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(bench: pathlib.Path, config: dict):
    """The family module ``bench/models/<model>.py`` that ``config`` names
    under ``"model"``."""
    if "model" not in config:
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f'model family: give it "model": "<name>" for '
                         f"bench/models/<name>.py")
    return _module(bench / "models" / f"{config['model']}.py")


def load_driver(cell: Cell):
    return _module(cell.bench / "drivers" / f"{cell.traffic['driver']}.py")


def load_reader(cell: Cell, metric: str):
    return _module(cell.bench / "metrics" / f"{metric}.py")


def check_devices(chips: int, require_tpu: bool = True) -> list:
    """The devices a cell runs on: the first ``chips`` accelerators JAX
    finds. No TPU, or fewer chips than asked, refuses the run."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0].platform = "
                      f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX sees "
                      f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``<root>/.jax_cache``, the program's own choice), or where
    ``JAX_COMPILATION_CACHE_DIR`` says. Every program is cached, however
    short its compile, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader sees."""
    cell: Cell
    outcome: Outcome
    peaks: Dict[str, float]
    chips: int

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def counters(self) -> dict:
        return self.outcome.counters

    @property
    def trace(self):
        return self.outcome.trace


def per_layer_metrics(cell: Cell, run: Run, log) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell, m["name"]).read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, devices, trace: bool,
                log) -> dict:
    """The last line of standard output."""
    from bench import flops
    dev = devices[0]
    checks = {name: {"value": float(value), "limit": float(limit)}
              for name, value, limit in outcome.checks}
    correct = bool(outcome.checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    line = {
        "correct": correct,
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": int(outcome.memory_peak_bytes)},
    }
    if trace:
        run = Run(cell, outcome,
                  flops.peaks(dev.device_kind, cell.bench / "peaks.json"),
                  len(devices))
        line["metrics"] = per_layer_metrics(cell, run, log)
        if outcome.trace is not None:
            line["device"]["busy_s"] = outcome.trace.busy_s
            line["device"]["window_s"] = outcome.trace.window_s
            line["breakdown"] = outcome.trace.breakdown()
    else:
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {
                "value": float(outcome.end_to_end[m["name"]]),
                "unit": m["unit"]}
    line["checks"] = checks
    return line


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, require_tpu: bool = True,
             started: Optional[float] = None, log=None) -> dict:
    """Run one cell and return its result line (tests call this with
    ``require_tpu=False``)."""
    started = time.monotonic() if started is None else started
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(name, root)
    devices = check_devices(cell.chips, require_tpu)
    if require_tpu:
        log(f"compile cache: {enable_compile_cache(root)}")
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  started=started, devices=devices, log=log)
    outcome = load_driver(cell).run(ctx)
    return result_line(cell, outcome, devices, trace, log)


def main(argv=None, started: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), started=started)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0

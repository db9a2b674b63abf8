"""Readings the correctness limits are set from, on the chip at a cell's
own size: the program's numbers on many seeds (the lower readings), the
control's (the reference one precision below the configuration's) and the
faults' planted in the reference (the upper readings).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out readings.jsonl] \
        [--faults-only] [--memory]

One process, one build: the program's seeds and the control's are read
side by side. ``--faults-only`` reads the control and the faults against
the reference alone, with no program, on one chip whatever the cell asks
for (the reference runs on one chip). ``--memory`` logs each chip's peak
bytes after each stage of the first seed's set-up. Not part of a
benchmark run; ``PERF.md`` records what it read and the limits set from
it. Prints one JSON object per seed and kind.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness, program  # noqa: E402


def control_dtype(cfg: dict):
    """One precision below the configuration's compute type."""
    import jax.numpy as jnp
    return {"float32": jnp.bfloat16,
            "bfloat16": jnp.float8_e4m3fn}[cfg["compute_dtype"]]


def memory(devices, key: str) -> list:
    return [int((d.memory_stats() or {}).get(key, 0)) for d in devices]


def faults(cell, seed: int, ref: dict, emit) -> None:
    """The control's and each planted fault's readings against ``ref``."""
    from bench.drivers import train as drv
    kinds = [("control", {"quant": control_dtype(cell.config)}),
             ("half_batch", {"fault": "half_batch"})]
    if cell.chips > 1:
        kinds.append(("own_worker", {"fault": "own_worker"}))
    for kind, kw in kinds:
        got = drv.reference_readings(cell, seed, **kw)
        emit({"seed": seed, "kind": kind, **drv.compare(got, ref)})


def train_faults(cell, seeds, emit):
    from bench.drivers import train as drv
    for seed in seeds:
        ref = drv.reference_readings(cell, seed)
        emit({"seed": seed, "kind": "reference", "delays": ref["delays"]})
        faults(cell, seed, ref, emit)
        gc.collect()


def train_memory(cell, devices, engine, seed, emit):
    """Each chip's peak after each stage of ``first_steps``."""
    import jax
    from bench import weights
    from bench.drivers import train as drv
    row = {"seed": seed, "kind": "memory",
           "start": memory(devices, "peak_bytes_in_use")}
    ks = program.key_seed(seed)
    params = weights.make(cell.model, cell.config, jax.random.PRNGKey(ks))
    jax.block_until_ready(params)
    row["weights"] = memory(devices, "peak_bytes_in_use")
    state = engine.init(jax.random.PRNGKey(ks), params=params)
    jax.block_until_ready(state)
    row["init"] = memory(devices, "peak_bytes_in_use")
    row["in_use_after_init"] = memory(devices, "bytes_in_use")
    del params, state
    gc.collect()
    state, rec, _ = drv.first_steps(engine, cell, seed)
    row["first_steps"] = memory(devices, "peak_bytes_in_use")
    row["in_use_after_first_steps"] = memory(devices, "bytes_in_use")
    del state, rec
    gc.collect()
    emit(row)


def train(cell, devices, seeds, control_seeds, emit, memory_log=False):
    from bench.drivers import train as drv
    engine, _ = drv.build(cell, devices)
    if memory_log:
        train_memory(cell, devices, engine, seeds[0], emit)
    for seed in seeds:
        t0 = time.monotonic()
        state, rec, _ = drv.first_steps(engine, cell, seed)
        prog = rec.readings()
        del state, rec
        gc.collect()
        ref = drv.reference_readings(cell, seed)
        emit({"seed": seed, "kind": "program", **drv.compare(prog, ref),
              "losses": prog["losses"], "ref_losses": ref["losses"],
              "delays": ref["delays"], "s": time.monotonic() - t0})
        del prog
        if seed in control_seeds:
            faults(cell, seed, ref, emit)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--faults-only", action="store_true")
    ap.add_argument("--memory", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(1 if args.faults_only else cell.chips)
    harness.enable_compile_cache(ROOT)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        row = {"workload": cell.name, **row}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    if args.faults_only:
        train_faults(cell, seeds, emit)
    else:
        train(cell, devices, seeds, control, emit, args.memory)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Exits nonzero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for. See ``bench/harness.py``.
"""
import time

STARTED = time.monotonic()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(started=STARTED))

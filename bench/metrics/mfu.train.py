"""Model FLOP/s utilisation of the training step: the model operations of
one step (``bench/flops.py``: 6 per matmul parameter per token, attention
at the published head size, no recompute) times the steps the traced
window completed, over its wall time, the chips and the chip's bf16 peak."""
from bench import flops


def read(run):
    c = run.counters
    if not c.get("steps") or not c.get("window_s"):
        return None
    per_step = flops.train_step_flops(run.config, c["batch"], c["seq"])
    rate = per_step * c["steps"] / c["window_s"]
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])

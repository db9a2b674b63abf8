"""Model FLOP/s utilisation of the training step: the model operations of
one step (``train_step_flops`` of the configuration's family,
``bench/models/<model>.py``, by ``bench/flops.py``'s conventions: 6 per
matmul parameter per token, attention at the published head sizes, no
recompute) times the steps the traced window completed, over its wall
time, the chips and the chip's bf16 peak."""


def read(run):
    c = run.counters
    if not c.get("steps") or not c.get("window_s"):
        return None
    per_step = run.cell.model.train_step_flops(run.config, c["batch"],
                                               c["seq"])
    rate = per_step * c["steps"] / c["window_s"]
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])

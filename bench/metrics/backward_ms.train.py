"""Device milliseconds per training step under the step program's
``model`` scope and inside ``transpose(...)``: the backward pass, remat's
recomputation included, averaged over the chips (profiler trace;
``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.layer_ms(run, "backward")

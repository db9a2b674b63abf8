"""Device milliseconds per training step under the step program's
``model`` scope and outside any ``transpose(...)``: the forward pass,
averaged over the chips (profiler trace; ``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.layer_ms(run, "forward")

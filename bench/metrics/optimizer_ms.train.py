"""Device milliseconds per training step under the step program's
``optimizer`` scope: the optimizer update, the LR scale, the parameter add
and the step's metrics, averaged over the chips (profiler trace;
``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.layer_ms(run, "optimizer")

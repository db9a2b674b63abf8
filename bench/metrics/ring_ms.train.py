"""Device milliseconds per training step under the step program's
``ring`` scope: the delay draw, the ring write and read and the worker mean
(on a data mesh, the worker mean's all-reduce), averaged over the chips
(profiler trace; ``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.layer_ms(run, "ring")

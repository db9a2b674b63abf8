"""Device milliseconds per training step with no operation running
while the Trainer's ``trainer.batch`` span was open (the batch source),
averaged over the chips (profiler trace; ``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.idle_ms(run, "feed")

"""Device milliseconds per training step with no operation running
while the Trainer's ``trainer.log`` span was open (the log row, where the
host syncs on the step's metrics), averaged over the chips (profiler trace;
``bench/layers.py``)."""
from bench import layers


def read(run):
    return layers.idle_ms(run, "sync")

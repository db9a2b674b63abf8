"""Device milliseconds per training step in which a collective (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) ran and no other
operation did on that chip, averaged over the chips (profiler trace).
Collectives in flight asynchronously count where no operation overlaps
them. A run with no collective has nothing to read."""
from bench import tracing


def read(run):
    c = run.counters
    if run.trace is None or not run.trace.devices or not c.get("steps"):
        return None
    if not any(tracing.COLLECTIVE.search(n)
               for d in run.trace.devices
               for n, _, _ in d.ops + d.async_collectives):
        return None
    return 1e3 * run.trace.exposed_collective_s() / c["steps"]

"""Share of the traced training window in which no operation ran on the
device, averaged over the cell's chips (profiler trace)."""


def read(run):
    share = run.trace.idle_share() if run.trace is not None else None
    return None if share is None else 100.0 * share

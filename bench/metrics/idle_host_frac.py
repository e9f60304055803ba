"""idle_host_frac: of the device's idle time in the traced window, the
share that overlaps a host phase of the drain thread (collect, stack,
dispatch, fanout, publish), by interval intersection on the profiler's
clock (`bench/host_phases.py`)."""
from bench import host_phases


def read(ctx):
    if ctx.trace is None:
        return None
    split = host_phases.read_split()
    return None if split is None else split["host_frac"]

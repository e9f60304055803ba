"""drain_host_ms: the drain thread's host milliseconds a serve cycle,
outside waiting for work and waiting on the device: the window's increase
of `serving.phase.<p>_s.sum` over the host phases (collect, stack,
dispatch, fanout, publish), over that of `serving.drain_cycles`."""
from bench import stats
from bench.host_phases import HOST_PHASES


def read(ctx):
    cycles = stats.counter_delta(ctx.snap0, ctx.snap1, "serving.drain_cycles")
    if not cycles:
        return None
    host = sum(stats.counter_delta(ctx.snap0, ctx.snap1,
                                   f"serving.phase.{p}_s.sum")
               for p in HOST_PHASES)
    return 1e3 * host / cycles

"""Device idle time inside the replay span before the replay scan starts
(host prepare: stream batching, transfers, the scheduler's launch), per
client op (us/op); the mean over the traced devices."""

from bench import tracing
from bench.metrics import programs


def read(ctx):
    lo, _ = ctx.replay
    match = programs.module_match(programs.SCAN_MODULES)
    idle = []
    for d in ctx.devices:
        starts = [s for n, s, _ in d["modules"] if match(n) and s >= lo]
        if not starts:
            return None
        first = min(starts)
        busy = tracing.union_ns(d["busy"], lo, first)
        idle.append(first - lo - busy)
    ops_per_device = ctx.ops / len(ctx.devices)
    return sum(idle) / len(idle) / 1e3 / ops_per_device

"""Device idle time inside the replay span while no ``engine.*`` span
of the program is open, per client op (us/op); the mean over the traced
devices.  Idle time the program's spans do not name is host work outside
them (the benchmark's own, or code between two spans).  Where the trace
stops before the scan ends, the device's timeline after that is unknown:
the span is then read up to the start of the last traced round, and
divided by the ops of the whole rounds before it
(``scopes.window``).  The run's log also gives, per device, the start
of the first scan program less the start of ``engine.launch``: a
negative or large value says that the host and device timelines of the
trace disagree."""

from bench import tracing
from bench.metrics import programs, scopes, spans


def read(ctx):
    ours = spans.within(ctx)
    if not ours:
        return None
    lo, hi = ctx.replay
    whole = [((lo, hi), ctx.ops / len(ctx.devices))] * len(ctx.devices)
    windows = scopes.window(ctx) or whole
    if not all(ops for _, ops in windows):
        return None                     # the trace holds no whole round
    opened = tracing.merged([(s, e) for _, s, e, _ in ours])
    launch = [s for n, s, _, _ in ours if n == "engine.launch"]
    scan = programs.module_match(programs.SCAN_MODULES)
    per_op = []
    for d, ((_, end), ops) in zip(ctx.devices, windows):
        gaps = tracing.gaps(d["busy"], lo, end)
        covered = sum(tracing.union_ns(opened, s, e) for s, e in gaps)
        per_op.append((sum(e - s for s, e in gaps) - covered) / ops)
        starts = [s for n, s, _ in d["modules"] if scan(n) and s >= lo]
        if launch and starts:
            spans.log(f"{d['name']}: scan program starts "
                      f"{(min(starts) - launch[0]) / 1e3:.3f} us after "
                      f"engine.launch")
    return sum(per_op) / len(per_op) / 1e3

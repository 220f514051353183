"""Device time of the cadence scheduler (``core/replicated_store.py``
``_stream_scheduler``, the ``jit_schedule`` program that host prepare
launches before the replay scan), per client op (us/op).  Also logs the
scheduler's counters from the ``engine.assemble.schedule`` spans, and
how many of the traced replay's ops the trace holds whole rounds of."""

from bench.metrics import programs
from bench.metrics import scopes
from bench.metrics import spans

SCHEDULE_MODULES = ("jit_schedule",)


def read(ctx):
    for _, _, _, args in spans.within(ctx, "engine.assemble.schedule"):
        spans.log("schedule counters " + ", ".join(
            f"{k} {args[k]}" for k in ("shard", "dep_applied",
                                       "delta_applied", "pending_peak",
                                       "overflow") if k in args))
    for (_, ops), d in zip(scopes.window(ctx) or (), ctx.devices):
        spans.log(f"{d['name']}: the trace holds whole rounds of {ops:g} "
                  f"of {ctx.ops / len(ctx.devices):g} ops")
    return programs.per_device_us_per_op(
        ctx, programs.modules(SCHEDULE_MODULES))

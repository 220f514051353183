"""Device time of the replay scan program, per client op (us/op)."""

from bench.metrics import programs


def read(ctx):
    return programs.per_device_us_per_op(
        ctx, programs.modules(programs.SCAN_MODULES))

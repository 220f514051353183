"""Device time of the op-ingest kernel's operations, per client op
(us/op)."""

from bench.metrics import programs


def read(ctx):
    return programs.per_device_us_per_op(ctx, programs.ingest_ops)

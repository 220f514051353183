"""Share of the HBM roofline the op-ingest kernel reaches (%): the bytes
every call must move (``bench/cost.py``) over the chip's HBM bandwidth,
over the kernel's summed time.  Integer prefix work has no published
peak, so the bound is the memory's."""

from bench import cost
from bench.metrics import programs


def read(ctx):
    us_per_op = programs.per_device_us_per_op(ctx, programs.ingest_ops)
    if us_per_op is None:
        return None
    ops_per_device = ctx.ops / len(ctx.devices)
    kernel_s = us_per_op * ops_per_device / 1e6
    calls = ctx.rounds
    least_s = (cost.op_ingest_bytes(ctx.batch, ctx.ring) * calls
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s

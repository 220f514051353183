"""Share of the HBM roofline the cadence scheduler's kernel reaches (%):
the bytes a scheduling of the traced replay's ops must move
(``bench/schedule_cost.py``) over the chip's HBM bandwidth, over the
kernel's summed time.  The kernel is its Pallas call inside the
``jit_schedule`` program (named ``cadence_schedule``)."""

from bench import schedule_cost
from bench.metrics import programs

KERNEL = "cadence_schedule"


def kernel_ops(device):
    """The scheduler kernel's events on one device: by its name where an
    event's name or stats carry it, else the Pallas call inside the
    ``jit_schedule`` program."""
    stats = device["op_stats"]
    named = {n for n, st in stats.items()
             if KERNEL in n + " " + " ".join(st.values())}
    if not named:
        named = {n for n, st in stats.items()
                 if any(p in n + " " + " ".join(st.values())
                        for p in programs.PALLAS_OPS)}
    match = programs.module_match(("jit_schedule",))
    spans = [(s, e) for n, s, e in device["modules"] if match(n)]
    return [(n, s, e) for n, s, e in device["ops"]
            if n in named and any(lo <= s and e <= hi for lo, hi in spans)]


def read(ctx):
    us_per_op = programs.per_device_us_per_op(ctx, kernel_ops)
    if us_per_op is None:
        return None
    ops_per_device = ctx.ops / len(ctx.devices)
    kernel_s = us_per_op * ops_per_device / 1e6
    least_s = (schedule_cost.schedule_bytes(int(ops_per_device))
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s

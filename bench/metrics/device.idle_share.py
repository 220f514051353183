"""Share of the replay span in which no operation ran on the device
(%), the mean over the traced devices."""

from bench.tracing import busy_window


def read(ctx):
    if not ctx.devices:
        return None
    busy, window = busy_window(ctx)
    return 100.0 * (1.0 - sum(busy) / len(busy) / window)

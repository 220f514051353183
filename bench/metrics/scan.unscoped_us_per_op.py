"""Device time of the replay scan program under no stage name (carry
copies, the scan loop's own ops, ops whose names the compiler dropped),
per client op (us/op): what the names cannot place.  With the scoped
metrics and ``op_ingest.device_us_per_op`` it makes up
``scan.device_us_per_op``."""

from bench.metrics import scopes


def read(ctx):
    return scopes.unscoped_us_per_op(ctx)

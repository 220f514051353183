"""Device time of the vector-clock scan (the ``vclock_scan`` scope: the
sequential clock chain of each batch), per client op (us/op)."""

from bench.metrics import scopes


def read(ctx):
    return scopes.us_per_op(ctx, "vclock_scan")

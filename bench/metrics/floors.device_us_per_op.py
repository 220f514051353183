"""Device time of the ops named ``floors``: the gathers from and the
scatters into the (sessions, rows) read and write floors and the
replica and global versions, per client op (us/op)."""

from bench.metrics import scopes


def read(ctx):
    return scopes.us_per_op(ctx, "floors")

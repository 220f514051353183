"""Device time of the boundary merge (the ``boundary_merge`` scope: the
pending ring's fixpoint at each round's end), per client op (us/op)."""

from bench.metrics import scopes


def read(ctx):
    return scopes.us_per_op(ctx, "boundary_merge")

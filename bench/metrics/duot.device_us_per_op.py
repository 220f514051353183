"""Device time of the DUOT record (the ``duot_record`` scope: each
batch's ops logged for the audit), per client op (us/op)."""

from bench.metrics import scopes


def read(ctx):
    return scopes.us_per_op(ctx, "duot_record")

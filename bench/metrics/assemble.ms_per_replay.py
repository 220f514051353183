"""Host time of result assembly and the DUOT audit for one replay (ms):
the benchmark's span around ``results.assemble``."""


def read(ctx):
    if ctx.assemble is None:
        return None
    s, e = ctx.assemble
    return (e - s) / 1e6

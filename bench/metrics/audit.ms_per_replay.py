"""Host time of the DUOT audit in result assembly, per replay (ms): the
summed ``engine.assemble.audit`` spans (one per tenant) inside the
replay span, its per-call compile included."""

from bench.metrics import spans


def read(ctx):
    audits = spans.within(ctx, "engine.assemble.audit")
    if not audits:
        return None
    return sum(e - s for _, s, e, _ in audits) / 1e6

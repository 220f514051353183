"""Names by which the program's compiled programs and kernels show in a
device trace: one place for every reader."""

from __future__ import annotations

# The replay scan: ``engine/replay.py`` ``unified_runner``'s jitted
# ``run``, or the ``shard_map`` around it when tenants span chips.
SCAN_MODULES = ("jit_run", "jit_local")
# The op-ingest kernel (``kernels/op_ingest.py``): an op whose name or
# stats name it (a ``named_scope("op_ingest")`` or a named kernel), or
# else the Pallas call inside the scan program, the only one there.
INGEST_OPS = ("op_ingest",)
PALLAS_OPS = ("pallas_call", "tpu_custom_call")


def modules(prefixes):
    """A selector of one device's program events named by ``prefixes``."""
    match = module_match(prefixes)
    return lambda d: [ev for ev in d["modules"] if match(ev[0])]


def module_match(prefixes):
    def match(name: str) -> bool:
        base = name.split("(")[0]
        return any(base == p or base.startswith(p + ".") for p in prefixes)
    return match


def ingest_ops(device) -> list[tuple[str, int, int]]:
    """The op-ingest kernel's events on one device."""
    stats = device["op_stats"]

    def says(needles):
        return {name for name, st in stats.items()
                if any(n in name + " " + " ".join(st.values())
                       for n in needles)}

    named = says(INGEST_OPS)
    if named:
        return [ev for ev in device["ops"] if ev[0] in named]
    pallas = says(PALLAS_OPS)
    scan = module_match(SCAN_MODULES)
    spans = [(s, e) for n, s, e in device["modules"] if scan(n)]
    return [(n, s, e) for n, s, e in device["ops"]
            if n in pallas and any(lo <= s and e <= hi for lo, hi in spans)]


def per_device_us_per_op(ctx, select) -> float | None:
    """Mean over the traced devices of the time of the events
    ``select(device)`` picks inside the replay span, per client op of
    that device, in microseconds."""
    lo, hi = ctx.replay
    per = []
    for d in ctx.devices:
        t = sum(min(e, hi) - max(s, lo) for _, s, e in select(d)
                if s < hi and e > lo)
        per.append(t)
    if not any(per):
        return None
    ops_per_device = ctx.ops / len(ctx.devices)
    return sum(per) / len(per) / 1e3 / ops_per_device

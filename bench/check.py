"""Whether a replay of the window is correct: the program's readings of
one replay against the plain reference on the same stream.

Every number compared is exact, limit 0: a count of disagreements, or
the gap between the severities, a float32 ratio that both sides compute
from the same two sums.
"""

from __future__ import annotations

import numpy as np

from bench.reference import audit as ref_audit
from bench.reference import store as ref_store

AUDIT_KEYS = ref_audit.COUNTS


def program_readings(cell, prep, result: dict, shard: int) -> dict:
    """What the timed path produced for tenant ``shard`` of a replay
    (its final state and log, and ``result``, what result assembly
    returned), fetched to the host; the program's state is left to be
    freed."""
    import jax
    import jax.numpy as jnp

    from repro.core import audit as audit_lib

    out = prep["out"]
    store = prep["store"]
    st = out["st"]
    if cell.tenants > 1:
        dev = jax.devices()[0]
        st = jax.device_put(jax.tree.map(lambda x: x[shard], st), dev)
        counts = {k: int(np.asarray(out[k])[shard])
                  for k in ("reads", "stale", "viol")}
        ps = result["per_shard"]
        reads = int(ps["reads"][shard])
        shown = {"reads": reads,
                 "stale_rate": int(ps["stale"][shard]) / max(1, reads),
                 "viol_rate": int(ps["viol"][shard]) / max(1, reads)}
    else:
        counts = {k: int(out[k]) for k in ("reads", "stale", "viol")}
        shown = {"reads": int(result["n_reads"]),
                 "stale_rate": float(result["staleness_rate"]),
                 "viol_rate": float(result["violation_rate"])}
    cl = st.cluster
    duot = jax.device_get(st.duot)
    valid = np.asarray(duot.valid)
    log = {k: np.asarray(getattr(duot, k))[valid]
           for k in ("client", "kind", "resource", "version", "seq", "vc")}
    res = store.audit(st, delta=store.delta or 0)
    audit = {k: int(v) for k, v in
             audit_lib.session_guarantee_report(res).items()}
    audit["audited"] = int(res.n_audited)
    batched = prep["batched"][shard]
    apply_idx = (np.asarray(batched["apply_idx"]).reshape(-1)
                 if "apply_idx" in batched else None)
    global_version = np.asarray(cl.global_version)
    dropped = int(cl.pend_dropped)

    # Drain the pending ring with the program's own merge, then read
    # every replica's version of every row.
    merge = jax.jit(lambda s: store.merge(s)[0])
    merges = 0
    while bool(jnp.any(st.cluster.pend_live)) and merges < 256:
        st = merge(st)
        merges += 1
    drained = not bool(jnp.any(st.cluster.pend_live))
    replica_version = np.asarray(st.cluster.replica_version)
    return {**counts, "dropped": dropped, "log": log, "audit": audit,
            "apply_idx": apply_idx, "global_version": global_version,
            "session_vc": np.asarray(cl.session_vc),
            "replica_vc": np.asarray(cl.replica_vc),
            "replica_version": replica_version, "drained": drained,
            "drain_merges": merges, "shown": shown,
            # A sharded result gives the mean over tenants: take this
            # tenant's own from the audit of its log.
            "severity": float(result["severity"] if cell.tenants == 1
                              else res.severity),
            "result_dropped": int(result["dropped_writes"])}


def reference_readings(cell, stream: dict[str, np.ndarray]) -> dict:
    c = cell.config
    ref = ref_store.replay(
        stream, level=c["level"], n_sessions=int(c["sessions_per_tenant"]),
        n_replicas=int(c["replicas"]), merge_every=int(c["merge_every"]),
        delta=int(c["delta"]), log_cap=int(c["duot_cap"]),
        batch=int(c["batch"]),
    )
    _, d = ref_store.cadence(c["level"], int(c["merge_every"]),
                             int(c["delta"]))
    ref["audit"] = ref_audit.counts(ref["log"], d)
    return ref


def compare(prog: dict, ref: dict, n_rows: int) -> list[tuple]:
    """``(name, disagreements, limit)`` for every number compared."""
    gv = np.zeros(n_rows, np.int64)
    if ref["global_version"]:
        rows = np.fromiter(ref["global_version"].keys(), np.int64)
        gv[rows] = np.fromiter(ref["global_version"].values(), np.int64)
    log, rlog = prog["log"], ref["log"]
    m = len(rlog["version"])
    if len(log["version"]) == m:
        bad = np.zeros(m, bool)
        for k in ("client", "kind", "resource", "version"):
            bad |= log[k] != rlog[k]
        bad |= log["seq"] != np.arange(m)
        log_gap = int(bad.sum())
        clock_gap = int(np.any(log["vc"] != rlog["vc"], axis=1).sum())
    else:
        log_gap = clock_gap = m + abs(len(log["version"]) - m)
    shown = prog["shown"]
    checks = [
        ("reads", abs(prog["reads"] - ref["reads"])),
        ("stale_reads", abs(prog["stale"] - ref["stale"])),
        ("violations", abs(prog["viol"] - ref["viol"])),
        ("dropped_writes", prog["dropped"]),
        ("row_versions", int(np.sum(prog["global_version"] != gv))),
        ("logged_ops", log_gap),
        ("op_clocks", clock_gap),
        ("session_clocks", int(np.sum(prog["session_vc"]
                                      != ref["session_vc"]))),
        ("replica_clocks", int(np.sum(prog["replica_vc"]
                                      != ref["replica_vc"]))),
        ("audit_counts", sum(abs(prog["audit"][k] - ref["audit"][k])
                             for k in AUDIT_KEYS)),
        # What result assembly returned: reads, the stale and violation
        # rates, and dropped writes.
        ("result_fields", (shown["reads"] != ref["reads"])
         + (shown["stale_rate"] != ref["stale"] / max(1, ref["reads"]))
         + (shown["viol_rate"] != ref["viol"] / max(1, ref["reads"]))
         + (prog["result_dropped"] != 0)),
        ("unconverged_rows", int(np.sum(np.any(
            prog["replica_version"] != gv[None, :], axis=0)))
         + (0 if prog["drained"] else n_rows)),
    ]
    if prog["apply_idx"] is not None:
        a = prog["apply_idx"]
        ap, w = ref["apply_point"], ref["is_write"]
        applied = ap < ref_store.NEVER
        n = len(a)
        bad = w & ((applied & (a != ap)) | (~applied & (a <= n)))
        checks.append(("apply_points", int(bad.sum())))
    out = [(name, int(v), 0) for name, v in checks]
    out.append(("severity",
                abs(prog["severity"] - ref["audit"]["severity"]), 0))
    return out

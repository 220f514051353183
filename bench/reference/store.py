"""The replicated store, one operation at a time, in plain Python.

Semantics of the paper's protocol engine (arXiv 2009.02355 section 3.4)
as a sequential loop: P replicas, C sessions, R rows.

* A write at replica ``p`` by session ``c`` takes the clock
  ``tick_c(max(session_vc[c], replica_vc[p]))``, creates version
  ``global[r] + 1``, is applied at once at ``p`` and raises the
  session's floor on ``r``; it waits in the pending set for the others.
* A read at ``p`` returns ``replica_version[p][r]``; a session-guarded
  level (X-STCC) returns ``max(that, floor)`` instead, and an unguarded
  one counts a violation when it falls below the floor.  A read is stale
  when it returns less than ``global[r]``.  It takes the clock
  ``tick_c(max(session_vc[c], replica_vc[p]))`` and raises the floor.
* Every op and every merge advance the logical clock by one.  After op
  ``i`` with ``i % sync_every == sync_every - 1`` a merge walks the
  pending writes in the total order ``(sum of clock, session)`` and
  applies a write at every replica when it is Δ-overdue (``clock -
  commit_time >= Δ``) or when its causal dependencies (its clock less its
  own tick) are at or below every replica's clock.  A write applied in
  that merge becomes visible from op ``i + 1``: its apply point.
* The clock an op is stamped with, and the replica clocks it reads,
  follow the engine's rounds of ``batch`` ops (a level that emulates its
  cadence inside a round: the synchronous and the timed ones; the
  others' rounds are their merge period).  Inside a round a replica
  clock learns only the writes it coordinates; after the round's last
  op a merge applies, all at once and until nothing changes, every
  pending write that is overdue (``clock - commit_time >= Δ``, where the
  clock counts one per op and one per such merge) or whose dependencies
  are at or below every replica's clock, and each replica clock takes
  the clocks of the writes applied.  Versions are served as above; the
  sequential merge's own clocks decide only its dependency test.
* The first ``log_cap`` ops are logged with their versions and stamped
  clocks (the DUOT), for the audit in ``audit.py``.

Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

NEVER = 2 ** 30


def cadence(level: str, merge_every: int, delta: int) -> tuple[int, int]:
    """(sync_every, effective Δ) of a consistency level."""
    if level in ("ALL", "TWO", "QUORUM"):
        return 1, 0
    if level == "ONE":
        return 2 * merge_every, 4 * delta
    if level == "CAUSAL":
        return merge_every, 4 * delta
    if level in ("TCC", "X_STCC"):
        return merge_every, max(1, delta // 3)
    raise ValueError(f"unknown level {level!r}")


def round_width(level: str, batch: int, merge_every: int, delta: int,
                n_ops: int) -> int:
    """Ops per round of the engine: a batch where the level emulates its
    merge cadence inside one, else the merge period."""
    sync_every, _ = cadence(level, merge_every, delta)
    emulate = sync_every == 1 or level in ("TCC", "X_STCC")
    return max(1, min(batch if emulate else sync_every, n_ops))


def _vmax(a: list[int], b: list[int]) -> list[int]:
    return [x if x > y else y for x, y in zip(a, b)]


class RoundClocks:
    """Session and replica clocks as the engine's rounds advance them."""

    def __init__(self, n_sessions: int, n_replicas: int, delta: int):
        C, P = n_sessions, n_replicas
        self.delta = delta
        self.session = [[0] * C for _ in range(C)]
        self.replica = [[0] * C for _ in range(P)]
        self.pending: list[tuple[list[int], int, int]] = []  # vc, c, time
        self.clock = 0

    def op(self, c: int, p: int, write: bool) -> list[int]:
        """Stamp one op of session ``c`` at replica ``p``."""
        vc = _vmax(self.session[c], self.replica[p])
        vc[c] += 1
        self.session[c] = vc
        if write:
            self.replica[p] = _vmax(self.replica[p], vc)
            self.pending.append((vc, c, self.clock))
        self.clock += 1
        return vc

    def merge(self) -> None:
        """The round's closing merge, as a fixpoint."""
        while self.pending:
            ready, kept = [], []
            for vc, c, t in self.pending:
                ok = self.clock - t >= self.delta
                if not ok:
                    dep = list(vc)
                    dep[c] -= 1
                    ok = all(all(a <= b for a, b in zip(dep, rvc))
                             for rvc in self.replica)
                (ready if ok else kept).append((vc, c, t))
            if not ready:
                break
            learned = ready[0][0]
            for vc, _, _ in ready[1:]:
                learned = _vmax(learned, vc)
            self.replica = [_vmax(rvc, learned) for rvc in self.replica]
            self.pending = kept
        self.clock += 1


def replay(
    stream: dict[str, np.ndarray], *, level: str, n_sessions: int,
    n_replicas: int, merge_every: int, delta: int, log_cap: int,
    batch: int,
) -> dict:
    """Replay ``stream`` op by op; every number the benchmark compares."""
    sync_every, d = cadence(level, merge_every, delta)
    guarded = level == "X_STCC"
    C, P = n_sessions, n_replicas
    clients = stream["client"].tolist()
    kinds = stream["kind"].tolist()
    rows = stream["resource"].tolist()
    homes = stream["home"].tolist()
    n = len(clients)
    width = round_width(level, batch, merge_every, delta, n)
    stamped = RoundClocks(C, P, d)

    replica_version = [dict() for _ in range(P)]
    replica_vc = [[0] * C for _ in range(P)]
    session_vc = [[0] * C for _ in range(C)]
    floor = [dict() for _ in range(C)]
    global_version: dict[int, int] = {}
    pending: list[tuple] = []     # (order key, client, row, version, vc, time)
    apply_point = np.full(n, NEVER, np.int64)
    n_log = min(n, log_cap)
    log_version = np.zeros(n_log, np.int64)
    log_vc = np.zeros((n_log, C), np.int64)
    stale = viol = reads = 0
    clock = 0
    # The sequential merge's own clocks decide its dependency test and
    # the order it applies writes in (applying is a max, so the order
    # changes nothing else).  With Δ = 0 every pending write is overdue
    # at every merge, nothing reads them, and they are not kept.
    causal = d > 0
    svc = None

    for i in range(n):
        c, p, r = clients[i], homes[i], rows[i]
        if causal:
            svc = [a if a > b else b
                   for a, b in zip(session_vc[c], replica_vc[p])]
            svc[c] += 1
            session_vc[c] = svc
        vc = stamped.op(c, p, kinds[i] == 1)
        if kinds[i]:
            ver = global_version.get(r, 0) + 1
            global_version[r] = ver
            rv = replica_version[p]
            if rv.get(r, 0) < ver:
                rv[r] = ver
            if causal:
                replica_vc[p] = [a if a > b else b
                                 for a, b in zip(replica_vc[p], svc)]
            fc = floor[c]
            if fc.get(r, 0) < ver:
                fc[r] = ver
            key = sum(svc) * (C + 1) + c if causal else i
            pending.append((key, c, r, ver, svc, clock, i))
            out = ver
        else:
            reads += 1
            raw = replica_version[p].get(r, 0)
            fl = floor[c].get(r, 0)
            if guarded:
                served = raw if raw > fl else fl
            else:
                served = raw
                viol += raw < fl
            stale += served < global_version.get(r, 0)
            if fl < served:
                floor[c][r] = served
            out = served
        if i < n_log:
            log_version[i] = out
            log_vc[i] = vc
        clock += 1
        if i % sync_every == sync_every - 1 and pending:
            pending.sort()
            kept = []
            for entry in pending:
                _, wc, wr, wver, wvc, wtime, wi = entry
                ok = clock - wtime >= d
                if not ok:
                    own = wvc[wc]
                    wvc[wc] = own - 1
                    ok = all(all(a <= b for a, b in zip(wvc, rvc))
                             for rvc in replica_vc)
                    wvc[wc] = own
                if ok:
                    for p2 in range(P):
                        rv = replica_version[p2]
                        if rv.get(wr, 0) < wver:
                            rv[wr] = wver
                        if causal:
                            replica_vc[p2] = [a if a > b else b for a, b
                                              in zip(replica_vc[p2], wvc)]
                    apply_point[wi] = i + 1
                else:
                    kept.append(entry)
            pending = kept
            clock += 1
        elif i % sync_every == sync_every - 1:
            clock += 1
        if i % width == width - 1 or i == n - 1:
            stamped.merge()

    kind = np.asarray(stream["kind"])
    return {
        "reads": reads, "stale": stale, "viol": viol,
        "global_version": global_version,
        "apply_point": apply_point, "is_write": kind == 1,
        "session_vc": np.asarray(stamped.session, np.int64),
        "replica_vc": np.asarray(stamped.replica, np.int64),
        "log": {
            "client": np.asarray(stream["client"][:n_log], np.int64),
            "kind": np.asarray(kind[:n_log], np.int64),
            "resource": np.asarray(stream["resource"][:n_log], np.int64),
            "version": log_version, "vc": log_vc,
        },
    }

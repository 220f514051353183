"""The DUOT audit (paper section 3.3), pair by pair over a short log.

For every ordered pair ``(i, j)``, ``i`` logged before ``j``, on one
row: a same-session pair whose clocks are ordered falls under a session
guarantee, checked on versions (MR: a read went backwards; MW: a write
not above the session's earlier write; RYW: a read below the session's
own write; WFR: a write not above a version the session read).  A pair
of different sessions whose clocks are ordered is timed causal: a read
that returned a version older than a write ordered before it breaks it.
The timed bound is broken when a read more than Δ log positions after a
write on its row returned an older version.

Severity (paper section 3.4.1) weighs the audited pairs: a (write,
later read) pair is a data edge (weight 3), another ordered pair a
causal edge (2), any other pair a timed edge (1); it is the weight of
the violated edges over the weight of all, in float32 as the paper's
ratio of two sums that float32 holds exactly.

Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

READ, WRITE = 0, 1
COUNTS = ("audited", "monotonic_read", "monotonic_write",
          "read_your_write", "write_follows_read", "timed_causal",
          "timed_bound")


def counts(log: dict[str, np.ndarray], delta: int) -> dict:
    """Pairs audited, violations of each session guarantee, of timed
    causality and of the timed bound, and the severity, over ``log``
    (columns ``client``, ``kind``, ``resource``, ``version``, ``vc``;
    ``seq`` is the position)."""
    client = np.asarray(log["client"])
    kind = np.asarray(log["kind"])
    row = np.asarray(log["resource"])
    ver = np.asarray(log["version"])
    vc = np.asarray(log["vc"])
    m = len(client)
    seq = np.arange(m)
    base = (row[:, None] == row[None, :]) & (seq[:, None] < seq[None, :])
    # i happens before j: vc_i <= vc_j everywhere and somewhere below.
    le = np.ones((m, m), bool)
    lt = np.zeros((m, m), bool)
    for k in range(vc.shape[1]):
        col = vc[:, k]
        le &= col[:, None] <= col[None, :]
        lt |= col[:, None] < col[None, :]
    ordered = base & le & lt
    same_session = client[:, None] == client[None, :]
    same = ordered & same_session
    ki, kj = kind[:, None], kind[None, :]
    vi, vj = ver[:, None], ver[None, :]
    data = base & (ki == WRITE) & (kj == READ)
    viol = ((same & (ki == READ) & (kj == READ) & (vj < vi))
            | (same & (ki == WRITE) & (kj == WRITE) & (vj <= vi))
            | (same & data & (vj < vi))
            | (same & (ki == READ) & (kj == WRITE) & (vj <= vi))
            | (ordered & ~same_session & data & (vj < vi)))
    timed = np.zeros((m, m), bool)
    if delta > 0:
        gap = seq[None, :] - seq[:, None]
        timed = data & (gap > delta) & (vj < vi)
    causal = ordered & ~data
    loose = base & ~ordered & ~data
    weight = 3 * int((viol & data).sum()) + 2 * int((viol & causal).sum()) \
        + int(((viol | timed) & loose).sum())
    total = 3 * int(data.sum()) + 2 * int(causal.sum()) + int(loose.sum())
    out = {
        "audited": int(base.sum()),
        "monotonic_read": int((same & (ki == READ) & (kj == READ)
                               & (vj < vi)).sum()),
        "monotonic_write": int((same & (ki == WRITE) & (kj == WRITE)
                                & (vj <= vi)).sum()),
        "read_your_write": int((same & (ki == WRITE) & (kj == READ)
                                & (vj < vi)).sum()),
        "write_follows_read": int((same & (ki == READ) & (kj == WRITE)
                                   & (vj <= vi)).sum()),
        "timed_causal": int((ordered & ~same_session & data
                             & (vj < vi)).sum()),
        "timed_bound": int(timed.sum()),
        "severity": float(np.float32(weight) / np.float32(max(total, 1))),
    }
    return out

"""The flat (C*R,) session floors hold the MR/RYW floor rule exactly.

``ClusterState`` keeps the read and write floors flat, cell ``(c, r)``
at ``c * R + r``.  A random mixed batch with repeated
``(client, resource)`` trains goes through ``apply_op_batch``; the
floors, reshaped to ``(C, R)``, must equal a plain numpy replay of the
floor rule, one op at a time:

  * a write of version ``v`` to ``r`` by ``c`` raises ``read[c, r]``
    and ``write[c, r]`` to ``v``;
  * a read serves ``max(replica, read[c, r], write[c, r])`` where
    sessions are enforced, the replica's version where not, and raises
    ``read[c, r]`` to what it served.

QUORUM does not enforce the floors, but it keeps them all the same.
"""

import numpy as np
import pytest

from repro.core import xstcc
from repro.core.consistency import ConsistencyLevel

C, P, R, B = 16, 3, 300, 256


def _numpy_floors(ops, rv, gv, rf, wf, enforce):
    """Replay ``ops`` one at a time on (C, R) floors; returns the
    version each op created or served."""
    out = []
    for c, p, r, k in ops:
        if k == xstcc.WRITE:
            v = gv[r] + 1
            gv[r] = v
            rv[p, r] = max(rv[p, r], v)
            wf[c, r] = max(wf[c, r], v)
            rf[c, r] = max(rf[c, r], v)
        else:
            floor = max(rf[c, r], wf[c, r])
            v = max(rv[p, r], floor) if enforce else rv[p, r]
            rf[c, r] = max(rf[c, r], v)
        out.append(v)
    return np.asarray(out, np.int32)


@pytest.mark.parametrize(
    "level", [ConsistencyLevel.QUORUM, ConsistencyLevel.X_STCC],
    ids=lambda lv: lv.name,
)
def test_flat_floors_match_sequential_floor_rule(level):
    enforce = level.is_session_guarded
    rng = np.random.default_rng(14)
    state = xstcc.make_cluster(P, C, R, pending_cap=4 * B)
    assert state.read_floor.shape == state.write_floor.shape == (C * R,)

    rv = np.zeros((P, R), np.int64)
    gv = np.zeros((R,), np.int64)
    rf = np.zeros((C, R), np.int64)
    wf = np.zeros((C, R), np.int64)
    violations = 0
    for _ in range(3):
        # Half the ops hit 4 hot (client, resource) pairs: trains.
        hot = rng.integers(0, 4, B)
        c = np.where(hot < 2, hot, rng.integers(0, C, B)).astype(np.int32)
        r = np.where(hot < 2, 7 * hot + R - 9,
                     rng.integers(0, R, B)).astype(np.int32)
        p = rng.integers(0, P, B).astype(np.int32)
        k = (rng.random(B) < 0.5).astype(np.int32)

        res = xstcc.apply_op_batch(
            state, client=c, replica=p, resource=r, kind=k,
            enforce_sessions=enforce,
        )
        want = _numpy_floors(zip(c, p, r, k), rv, gv, rf, wf, enforce)
        np.testing.assert_array_equal(np.asarray(res.version), want)
        np.testing.assert_array_equal(
            np.asarray(res.state.read_floor).reshape(C, R), rf)
        np.testing.assert_array_equal(
            np.asarray(res.state.write_floor).reshape(C, R), wf)
        violations += int(np.sum(np.asarray(res.violation)))

        # The merge moves replica versions and leaves the floors alone.
        state, _ = xstcc.server_merge(res.state, delta=0)
        np.testing.assert_array_equal(state.read_floor, res.state.read_floor)
        np.testing.assert_array_equal(
            state.write_floor, res.state.write_floor)
        rv = np.asarray(state.replica_version).astype(np.int64)

    # The floors rose for every train, whether or not they are enforced.
    assert (wf[:2, [R - 9, R - 2]].diagonal() > 0).all()
    assert (rf >= wf).all() and rf.max() > 0
    assert (violations > 0) != enforce

"""The cadence scheduler follows the sequential protocol's clocks.

``ReplicatedStore.schedule_stream`` must give every write the apply
point that a sequential replay of the protocol gives it: the plain
reference (``bench/reference/store.py`` ``replay``, its
``apply_point``), on YCSB-A streams of the benchmark's own generator and
on a hand-made stream where knowledge travels through other sessions'
clocks.  The Pallas kernel (``repro.kernels.cadence_schedule``, run
interpreted here) must equal the scan, and a pending window too small
for the cadence must count its overflow, never pass in silence.
"""

import numpy as np
import pytest

from bench.reference import store as ref_store
from bench.traffic import ycsb
from repro.core import replicated_store as rs
from repro.core.consistency import ConsistencyLevel
from repro.engine import results

MIX = ycsb.load_mix("ycsb-a")


def _stream(seed, n_ops=16384, n_rows=20000):
    return ycsb.stream(MIX, n_ops=n_ops, n_sessions=16, n_rows=n_rows,
                       n_replicas=3, seed=(seed, 0, 0))


def _reference(stream, level):
    return ref_store.replay(stream, level=level, n_sessions=16,
                            n_replicas=3, merge_every=8, delta=24,
                            log_cap=16, batch=4096)


@pytest.mark.parametrize("level", ["TCC", "X_STCC"])
@pytest.mark.parametrize("seed", [0, 1, 3])
def test_schedule_equals_the_sequential_merge(level, seed):
    s = _stream(seed)
    store = rs.ReplicatedStore(3, 16, 20000, level=ConsistencyLevel[level])
    sched = store.schedule_stream(s["client"], s["home"], s["kind"],
                                  counters=True)
    ref = _reference(s, level)
    np.testing.assert_array_equal(np.asarray(sched.apply),
                                  ref["apply_point"])
    applied = int((ref["apply_point"] < ref_store.NEVER).sum())
    assert int(sched.dep_applied) + int(sched.delta_applied) == applied
    assert int(sched.dep_applied) > 0 and int(sched.overflow) == 0
    assert 0 < int(sched.pending_peak) <= 2 * 8


@pytest.mark.parametrize("level", ["CAUSAL", "ONE"])
@pytest.mark.parametrize("n_ops", [40, 16384])
def test_slow_cadences_and_short_streams(level, n_ops):
    """Δ 96 keeps a write pending for up to 12 (CAUSAL) or 7 (ONE)
    merges; a 40-op stream ends before the window ever fills."""
    s = _stream(4, n_ops=n_ops)
    store = rs.ReplicatedStore(3, 16, 20000, level=ConsistencyLevel[level])
    sched = store.schedule_stream(s["client"], s["home"], s["kind"],
                                  counters=True)
    np.testing.assert_array_equal(np.asarray(sched.apply),
                                  _reference(s, level)["apply_point"])
    assert int(sched.overflow) == 0


def test_a_write_after_a_read_applies_a_merge_early():
    """Session 0 reads, then writes at replica 0; session 1 carries that
    clock from replica 0 to replicas 1 and 2 with its own writes, so at
    the period's merge every replica clock covers the read's tick and
    the write (position 1, not yet overdue) applies there: at op 8, not
    at its overdue merge after op 15."""
    client = np.array([0, 0, 1, 1, 1, 2, 2, 2] + [2] * 8, np.int32)
    home = np.array([0, 0, 0, 1, 2, 0, 0, 0] + [0] * 8, np.int32)
    kind = np.array([0, 1, 0, 1, 1, 0, 0, 0] + [0] * 8, np.int32)
    store = rs.ReplicatedStore(3, 16, 8, level=ConsistencyLevel.X_STCC)
    apply = np.asarray(store.schedule_stream(client, home, kind))
    ref = _reference({"client": client, "home": home, "kind": kind,
                      "resource": np.zeros(16, np.int32)}, "X_STCC")
    np.testing.assert_array_equal(apply, ref["apply_point"])
    assert apply[1] == 8
    # Session 1's writes depend on its read at replica 0, which no
    # replica learns before the overdue merge.
    assert apply[3] == apply[4] == 16


@pytest.mark.parametrize("cadence", [(8, 8), (4, 4)])
def test_kernel_equals_the_scan(cadence):
    """5,003 ops: the last period is partial and has no merge."""
    s = _stream(5, n_ops=5003, n_rows=2000)
    args = [np.asarray(s[k], np.int32) for k in ("client", "home", "kind")]
    scan = rs._stream_scheduler(*cadence, 16, 3)(*args)
    kernel = rs._stream_scheduler(*cadence, 16, 3, impl="pallas")(*args)
    for a, b in zip(scan, kernel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(scan.apply)[-3:].tolist() == [rs.NEVER] * 3


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_a_small_window_counts_its_overflow(impl):
    s = _stream(2, n_ops=4096, n_rows=2000)
    args = [np.asarray(s[k], np.int32) for k in ("client", "home", "kind")]
    assert rs.pending_periods(8, 8) == 2
    full = rs._stream_scheduler(8, 8, 16, 3, impl=impl)(*args)
    small = rs._stream_scheduler(8, 8, 16, 3, periods=1, impl=impl)(*args)
    assert int(full.overflow) == 0 and int(small.overflow) > 0


def test_assembly_refuses_an_overflowed_schedule():
    counts = {"dep_applied": 1, "delta_applied": 2, "pending_peak": 3,
              "overflow": 1}
    with pytest.raises(RuntimeError, match="overflowed"):
        results._schedule_counts({"schedule_counts": [None, counts]})
    results._schedule_counts({"schedule_counts": [dict(counts, overflow=0)]})

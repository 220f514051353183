"""A run with the timed path broken underneath comes out not correct.

Each test plants one fault in the program, runs the rest of a run of
the cell at a tiny size on the CPU (the harness's look for a chip is
skipped), and expects ``correct`` false.  No cell exchanges anything
between chips (the four tenants of the four-chip cell share nothing),
so that fault has none to plant.  A severity zeroed
where it is produced is the right answer at QUORUM, where no pair is
violated; ``test_reference_audit_reads_the_programs_log_alike`` shows
that the reference's severity is the program's to the last bit where it
is not zero.
"""

import pytest

from bench import run as bench_run
from bench.cell import ROOT

TINY = dict(rows_per_tenant=3000, ops_per_tenant=4096, batch=512)
ARGS = ["--seed", str(2 ** 31 + 77), "--seconds", "0.1", "--trace", "0"]


def _workloads():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"] if w["chips"] == 1]


@pytest.fixture
def fresh_runners():
    from repro.engine import replay

    replay.unified_runner.cache_clear()
    yield
    replay.unified_runner.cache_clear()


def _run(workload):
    rc, out = bench_run.run(["--workload", workload, *ARGS], allow_cpu=True,
                            overrides=TINY)
    assert rc == 0
    return out


def _state_unchanged(monkeypatch):
    from repro.core.replicated_store import ReplicatedStore

    orig = ReplicatedStore.apply_batch

    def apply_batch(self, state, **kw):
        _, res = orig(self, state, **kw)
        return state, res

    monkeypatch.setattr(ReplicatedStore, "apply_batch", apply_batch)


def _half_batch(monkeypatch):
    from repro.core.replicated_store import ReplicatedStore

    orig = ReplicatedStore.apply_batch

    def apply_batch(self, state, *, client, replica, resource, kind,
                    apply_index=None, **kw):
        h = client.shape[0] // 2
        return orig(self, state, client=client[:h], replica=replica[:h],
                    resource=resource[:h], kind=kind[:h],
                    apply_index=None if apply_index is None
                    else apply_index[:h], **kw)

    monkeypatch.setattr(ReplicatedStore, "apply_batch", apply_batch)


def _answer_altered(monkeypatch):
    from repro.core import xstcc

    orig = xstcc.apply_op_batch

    def apply_op_batch(state, **kw):
        res = orig(state, **kw)
        # The first op of every batch is served one version too new.
        return res._replace(version=res.version.at[0].add(1))

    monkeypatch.setattr(xstcc, "apply_op_batch", apply_op_batch)


def _clock_scan_skipped(monkeypatch):
    from repro.core import xstcc

    orig = xstcc.apply_op_batch

    def apply_op_batch(state, **kw):
        # The per-op vector-clock scan is left out: the clocks come back
        # as they went in, and the ops are stamped with zeros.
        return orig(state, **{**kw, "with_clocks": False})

    monkeypatch.setattr(xstcc, "apply_op_batch", apply_op_batch)


def _severity_altered(monkeypatch):
    from repro.engine import results

    orig = results._severity
    monkeypatch.setattr(results, "_severity",
                        lambda *a: orig(*a) + 0.25)


@pytest.mark.parametrize("workload", _workloads())
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _clock_scan_skipped,
                                   _severity_altered])
def test_planted_fault_is_not_correct(workload, fault, monkeypatch,
                                      fresh_runners):
    fault(monkeypatch)
    out = _run(workload)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_reference_audit_reads_the_programs_log_alike(fresh_runners):
    """The program's audit of its own log, severity included, against
    the reference's audit of that log, at the control's level, where
    pairs are violated and the severity is not zero."""
    from bench import cell as cell_lib
    from bench import check as check_lib
    from bench.reference import audit as ref_audit
    from bench.reference import store as ref_store
    from bench.run import replay_once
    from bench.seam import StreamPool

    cell = cell_lib.load(_workloads()[0], overrides=dict(TINY, level="TCC"))
    pool = StreamPool(cell_lib.pool_streams(cell, 2 ** 31 + 9))
    prep, result, _ = replay_once(cell, pool, 0)
    prog = check_lib.program_readings(cell, prep, result, 0)
    c = cell.config
    _, d = ref_store.cadence("TCC", int(c["merge_every"]), int(c["delta"]))
    ref = ref_audit.counts(prog["log"], d)
    assert ref["severity"] > 0
    assert sum(v for k, v in prog["audit"].items() if k != "audited") > 0
    assert {k: ref[k] for k in check_lib.AUDIT_KEYS} == prog["audit"]
    assert ref["severity"] == prog["severity"]

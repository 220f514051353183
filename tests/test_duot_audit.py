"""DUOT + audit: paper Table 1, injected violations, GC safety."""

import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep: property tests
from hypothesis import given, settings, strategies as st

from repro.core import audit, duot, odg
from repro.core import vector_clock as vclock


def table1():
    """The paper's Table 1 DUOT (versions: a=1, b=2, d=3, c=4)."""
    t = duot.make(capacity=16, n_clients=3)
    rows = [
        (0, duot.WRITE, 0, 1, 0, [1, 0, 0]),
        (0, duot.WRITE, 0, 2, 0, [2, 0, 0]),
        (1, duot.READ, 0, 1, 1, [2, 1, 0]),
        (1, duot.READ, 0, 2, 1, [2, 2, 0]),
        (1, duot.WRITE, 0, 3, 1, [2, 3, 0]),
        (2, duot.READ, 0, 1, 2, [2, 3, 1]),
        (2, duot.READ, 0, 2, 2, [2, 3, 2]),
        (2, duot.READ, 0, 3, 2, [2, 3, 3]),
        (1, duot.READ, 0, 3, 1, [2, 4, 3]),
        (1, duot.WRITE, 0, 4, 1, [2, 5, 3]),
        (0, duot.READ, 0, 2, 0, [3, 5, 3]),
    ]
    for c, k, r, v, rep, clock in rows:
        t = duot.append(t, client=c, kind=k, resource=r, version=v,
                        replica=rep, vc=jnp.array(clock))
    return t


def test_table1_structure():
    t = table1()
    assert int(t.size) == 11
    res = audit.audit(t)
    assert int(res.n_audited) > 0
    g = odg.build(t)
    counts = odg.edge_counts(g)
    # Table 1 has causal chains and read-from (data) edges.
    assert int(counts["causal"]) > 0
    assert int(counts["data"]) > 0
    assert int(counts["timed"]) == 10  # adjacent same-resource pairs


def test_clean_session_no_violations():
    """A single client reading its own monotone writes: no violations."""
    t = duot.make(8, 2)
    vc = vclock.zeros(2)
    for ver in range(1, 4):
        vc = vclock.tick(vc, 0)
        t = duot.append(t, client=0, kind=duot.WRITE, resource=0,
                        version=ver, replica=0, vc=vc)
        vc = vclock.tick(vc, 0)
        t = duot.append(t, client=0, kind=duot.READ, resource=0,
                        version=ver, replica=0, vc=vc)
    res = audit.audit(t)
    assert int(res.n_violations) == 0


@pytest.mark.parametrize(
    "first_kind,second_kind,expected_phase",
    [
        (duot.READ, duot.READ, audit.PHASE_A1_MR),    # read went backwards
        (duot.WRITE, duot.WRITE, audit.PHASE_A2_MW),  # non-monotone write
        (duot.WRITE, duot.READ, audit.PHASE_A3_RYW),  # own write invisible
    ],
)
def test_injected_violation_detected(first_kind, second_kind,
                                     expected_phase):
    t = duot.make(8, 2)
    vc = vclock.zeros(2)
    vc = vclock.tick(vc, 0)
    t = duot.append(t, client=0, kind=first_kind, resource=0, version=2,
                    replica=0, vc=vc)
    vc2 = vclock.tick(vc, 0)
    t = duot.append(t, client=0, kind=second_kind, resource=0,
                    version=1, replica=1, vc=vc2)
    res = audit.audit(t)
    assert int(res.n_violations) >= 1
    assert bool(jnp.any(res.vio_kind == expected_phase))


def test_ryw_violation():
    """W(x)v then R(x)v' with v' < v in the same session -> RYW."""
    t = duot.make(8, 2)
    vc = vclock.tick(vclock.zeros(2), 0)
    t = duot.append(t, client=0, kind=duot.WRITE, resource=0, version=5,
                    replica=0, vc=vc)
    vc = vclock.tick(vc, 0)
    t = duot.append(t, client=0, kind=duot.READ, resource=0, version=3,
                    replica=1, vc=vc)
    res = audit.audit(t)
    assert bool(jnp.any(res.vio_kind == audit.PHASE_A3_RYW))


def test_timed_bound_violation():
    """A write invisible after more than delta timestamps -> timed."""
    t = duot.make(16, 2)
    vc = vclock.tick(vclock.zeros(2), 0)
    t = duot.append(t, client=0, kind=duot.WRITE, resource=0, version=9,
                    replica=0, vc=vc)
    # Pad the clock forward with unrelated resource ops.
    for i in range(6):
        vc = vclock.tick(vc, 1)
        t = duot.append(t, client=1, kind=duot.WRITE, resource=1,
                        version=i + 1, replica=1, vc=vc)
    # Late stale read (different client, no causal link -> not b1).
    t = duot.append(t, client=1, kind=duot.READ, resource=0, version=2,
                    replica=1, vc=jnp.array([0, 7], jnp.int32))
    res = audit.audit(t, delta=3)
    assert int(jnp.sum(res.timed_vio)) >= 1


def test_gc_drops_only_covered():
    t = table1()
    frontier = jnp.array([2, 3, 1], jnp.int32)
    g = duot.gc(t, frontier)
    # Entries with vc <= frontier are gone; all others retained in order.
    kept_versions = np.asarray(g.version[: int(g.size)])
    assert int(g.size) < int(t.size)
    for i in range(int(g.size)):
        assert not bool(vclock.leq(g.vc[i], frontier))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_audit_no_false_positives_on_serial_history(seed):
    """A serial (fully synchronous) history audits clean."""
    rng = np.random.default_rng(seed)
    t = duot.make(32, 3)
    vc = vclock.zeros(3)
    version = {0: 0, 1: 0}
    for _ in range(16):
        c = int(rng.integers(0, 3))
        r = int(rng.integers(0, 2))
        k = int(rng.integers(0, 2))
        vc = vclock.tick(vc, c)
        if k == duot.WRITE:
            version[r] += 1
        t = duot.append(t, client=c, kind=k, resource=r,
                        version=version[r], replica=0, vc=vc)
    res = audit.audit(t, delta=4)
    assert int(res.n_violations) == 0


def test_severity_ratio_is_the_ieee_quotient():
    """The audit's severity divides on the device by an int32 long
    division: the float32 quotient numpy's IEEE division gives, on any
    backend (a TPU's float32 division can be one unit off)."""
    import jax

    rng = np.random.default_rng(15)
    num = np.concatenate([[0, 1, 7, 5, 3, 2 ** 24 - 1, 6],
                          rng.integers(0, 2 ** 24, 4000)])
    den = np.concatenate([[1, 1, 7, 3, 1, 2 ** 24 - 1, 12_582_912],
                          rng.integers(1, 2 ** 24, 4000)])
    got = np.asarray(jax.vmap(audit.ratio_f32)(jnp.asarray(num, jnp.int32),
                                         jnp.asarray(den, jnp.int32)))
    want = num.astype(np.float32) / den.astype(np.float32)
    np.testing.assert_array_equal(got, want)

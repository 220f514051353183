"""Tiled/Pallas op-ingestion == dense oracle == scalar loop, bit for bit.

The tentpole contract: ``repro.kernels.ops.op_ingest`` computes the
batched engine's three prefix reductions in O(B·tile) memory, and every
implementation (dense masks, jnp tile walk, Pallas kernel in interpret
mode) agrees exactly — across consistency levels, all three merge
cadences (scalar / merge-every-op / op-index & timed-Δ schedules),
pending-ring overflow, and the sharded scale-out paths.
"""

import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import xstcc
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import ReplicatedStore
from repro.kernels import ops as kernel_ops

from test_batch_equivalence import (
    assert_states_equal,
    random_ops,
    scalar_apply,
)

IMPLS = ("tiled", "pallas")


def _rand_ingest_inputs(seed, b, q, cadence, pending):
    rng = np.random.default_rng(seed)
    a = lambda x: jnp.asarray(x, jnp.int32)               # noqa: E731
    kw = dict(
        client=a(rng.integers(0, 6, b)),
        replica=a(rng.integers(0, 3, b)),
        resource=a(rng.integers(0, 5, b)),
        is_write=jnp.asarray(rng.integers(0, 2, b), bool),
        g0=a(rng.integers(0, 40, b)),
        raw0=a(rng.integers(0, 40, b)),
        floor0=a(rng.integers(0, 40, b)),
    )
    if cadence:
        kw["op_index"] = a(np.arange(b))
        kw["apply_index"] = a(rng.integers(0, 2 * b, b))
    if pending:
        kw.update(
            op_index=a(np.arange(b)),
            pend_version=a(rng.integers(0, 60, q)),
            pend_resource=a(rng.integers(0, 5, q)),
            pend_live=jnp.asarray(rng.integers(0, 2, q), bool),
            pend_apply=a(rng.integers(0, 2 * b, q)),
        )
    return kw


@pytest.mark.parametrize("cadence,pending", [
    (False, False), (True, False), (False, True), (True, True),
])
@pytest.mark.parametrize("seed", range(3))
def test_op_ingest_impls_match_oracle(seed, cadence, pending):
    """dense == tiled == pallas on random inputs, odd sizes included."""
    b = int(np.random.default_rng(seed).integers(33, 180))
    kw = _rand_ingest_inputs(seed, b, q=24, cadence=cadence, pending=pending)
    want = kernel_ops.op_ingest(**kw, impl="dense")
    for impl in IMPLS:
        for block in (32, 64):
            got = kernel_ops.op_ingest(**kw, impl=impl, block=block)
            for name, w, g in zip(("occ", "raw", "floor"), want, got):
                np.testing.assert_array_equal(
                    np.asarray(w), np.asarray(g),
                    err_msg=f"{impl} block={block} {name} "
                            f"(cadence={cadence} pending={pending})",
                )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seed", range(3))
def test_apply_op_batch_tiled_matches_scalar(seed, impl):
    """The full batch op with tiled/Pallas ingest reproduces the scalar
    loop exactly, including intra-batch trains and ring overflow."""
    c, p, r, k = random_ops(seed, 48, 4, 3, 3)
    state0 = xstcc.make_cluster(3, 4, 3, pending_cap=12)
    want_state, vers, *_ = scalar_apply(state0, c, p, r, k, True)
    got = xstcc.apply_op_batch(
        state0,
        client=jnp.asarray(c, jnp.int32), replica=jnp.asarray(p, jnp.int32),
        resource=jnp.asarray(r, jnp.int32), kind=jnp.asarray(k, jnp.int32),
        enforce_sessions=True, ingest=impl)
    assert_states_equal(want_state, got.state, f"{impl} seed={seed}")
    np.testing.assert_array_equal(np.asarray(got.version), vers)


def _store_trace(level, ingest, seed, rounds=3, b=48, pending_cap=16):
    """Run a few cadence-emulated batches + merges through one store."""
    store = ReplicatedStore(
        3, 5, 4, level=level, pending_cap=pending_cap, duot_cap=256,
        ingest=ingest,
    )
    st = store.init()
    results = []
    for rd in range(rounds):
        rng = np.random.default_rng(seed * 100 + rd)
        ops = {
            "client": jnp.asarray(rng.integers(0, 5, b), jnp.int32),
            "replica": jnp.asarray(rng.integers(0, 3, b), jnp.int32),
            "resource": jnp.asarray(rng.integers(0, 4, b), jnp.int32),
            "kind": jnp.asarray(rng.integers(0, 2, b), jnp.int32),
        }
        st, res = store.apply_batch(st, **ops, op_step0=rd * b)
        st, _ = store.merge(st)
        results.append(res)
    return st, results


# One level per cadence family: merge-every-op (ALL), op-index/timed
# schedule (X_STCC), real-merge batches (CAUSAL uses no emulation in the
# simulator but the store still schedules apply points here).
@pytest.mark.parametrize("level", [
    ConsistencyLevel.ALL, ConsistencyLevel.X_STCC, ConsistencyLevel.CAUSAL,
])
@pytest.mark.parametrize("impl", IMPLS)
def test_store_cadence_paths_bit_exact(level, impl):
    """Store-level multi-batch traces (cadence predicates + pending ring
    carry-over + ring overflow at pending_cap=16 < writes) are identical
    across ingest implementations."""
    st_d, res_d = _store_trace(level, "dense", seed=7)
    st_i, res_i = _store_trace(level, impl, seed=7)
    assert_states_equal(st_d.cluster, st_i.cluster, f"{level} {impl}")
    np.testing.assert_array_equal(
        np.asarray(st_d.pend_apply), np.asarray(st_i.pend_apply))
    for rd, (a, b_) in enumerate(zip(res_d, res_i)):
        for f in ("version", "admissible", "stale", "violation", "dropped"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b_, f)),
                err_msg=f"{level} {impl} round={rd} {f}",
            )


def test_run_protocol_ingest_paths_agree():
    from repro.storage.simulator import run_protocol
    from repro.storage.ycsb import WORKLOAD_A

    for level in (ConsistencyLevel.X_STCC, ConsistencyLevel.ONE):
        want = run_protocol(level, WORKLOAD_A, n_ops=600, audit=False,
                            ingest="dense")
        got = run_protocol(level, WORKLOAD_A, n_ops=600, audit=False,
                           ingest="tiled")
        assert want == got, (level, want, got)


# ---------------------------------------------------------------------------
# Pending-ring slot assignment (cumsum rank regression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_cumsum_slot_rank_matches_argsort(seed):
    """The O(Q) cumsum/scatter k-th-free-slot map equals the former
    argsort(~free) assignment, including overflow accounting."""
    rng = np.random.default_rng(seed)
    q = 24
    state = xstcc.make_cluster(2, 3, 4, pending_cap=q)
    live = jnp.asarray(rng.integers(0, 2, q), bool)
    state = state._replace(pend_live=live)
    b = 20
    kw = dict(
        client=jnp.asarray(rng.integers(0, 3, b), jnp.int32),
        replica=jnp.asarray(rng.integers(0, 2, b), jnp.int32),
        resource=jnp.asarray(rng.integers(0, 4, b), jnp.int32),
        kind=jnp.asarray(rng.integers(0, 2, b), jnp.int32),
    )
    res = xstcc.apply_op_batch(state, **kw)

    free = ~np.asarray(live)
    order = np.argsort(~free, kind="stable")
    is_w = np.asarray(kw["kind"]) == xstcc.WRITE
    wrank = np.cumsum(is_w) - 1
    n_free = int(free.sum())
    want_slot = np.where(
        is_w & (wrank < n_free),
        order[np.clip(wrank, 0, q - 1)],
        q,
    )
    np.testing.assert_array_equal(np.asarray(res.slot), want_slot)
    want_dropped = int((is_w & (wrank >= n_free)).sum())
    np.testing.assert_array_equal(np.asarray(res.dropped).sum(), want_dropped)
    assert int(res.state.pend_dropped) == want_dropped


def test_dropped_write_accounting_unchanged():
    """Overflow drops the tail writes, never clobbers live slots."""
    state0 = xstcc.make_cluster(2, 2, 4, pending_cap=2)
    res = xstcc.client_write_batch(
        state0,
        client=jnp.zeros(4, jnp.int32),
        replica=jnp.zeros(4, jnp.int32),
        resource=jnp.arange(4, dtype=jnp.int32))
    assert int(res.state.pend_dropped) == 2
    assert np.asarray(res.dropped).tolist() == [False, False, True, True]
    assert np.asarray(res.state.pend_resource).tolist() == [0, 1]


# ---------------------------------------------------------------------------
# Audit: Pallas kernel routing
# ---------------------------------------------------------------------------


def test_audit_kernel_path_matches_dense():
    from repro.core import audit as audit_lib
    from repro.core import duot as duot_lib

    rng = np.random.default_rng(3)
    m, n = 192, 6
    fill = 150
    d = duot_lib.make(m, n)
    d = d._replace(
        client=d.client.at[:fill].set(
            jnp.asarray(rng.integers(0, n, fill), jnp.int32)),
        kind=d.kind.at[:fill].set(
            jnp.asarray(rng.integers(0, 2, fill), jnp.int32)),
        resource=d.resource.at[:fill].set(
            jnp.asarray(rng.integers(0, 4, fill), jnp.int32)),
        version=d.version.at[:fill].set(
            jnp.asarray(rng.integers(0, 30, fill), jnp.int32)),
        seq=d.seq.at[:fill].set(jnp.arange(fill, dtype=jnp.int32)),
        vc=d.vc.at[:fill].set(jnp.asarray(
            np.cumsum(rng.integers(0, 2, (fill, n)), axis=0), jnp.int32)),
        valid=d.valid.at[:fill].set(True),
    )
    for delta in (0, 7):
        want = audit_lib.audit(d, delta=delta, use_kernel=False)
        got = audit_lib.audit(d, delta=delta, use_kernel=True)
        for f in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)), np.asarray(getattr(got, f)),
                err_msg=f"delta={delta} {f}",
            )


# ---------------------------------------------------------------------------
# Sharded scale-out paths
# ---------------------------------------------------------------------------


def test_run_protocol_sharded_matches_per_shard_sum():
    """A 2-shard split of a disjoint-client workload reproduces the
    unsharded per-shard metrics exactly (shards share nothing)."""
    from repro.storage.simulator import run_protocol, run_protocol_sharded
    from repro.storage.ycsb import WORKLOAD_A

    sh = run_protocol_sharded(
        ConsistencyLevel.X_STCC, WORKLOAD_A, n_shards=2, n_ops=800,
        n_clients=16, n_resources=24, audit=False,
    )
    singles = [
        run_protocol(
            ConsistencyLevel.X_STCC, WORKLOAD_A, n_ops=400, n_clients=8,
            n_resources=12, seed=s, audit=False,
        )
        for s in range(2)
    ]
    for s in range(2):
        stale = round(singles[s]["staleness_rate"] * singles[s]["n_reads"])
        assert sh["per_shard"]["stale"][s] == stale
        assert sh["per_shard"]["reads"][s] == singles[s]["n_reads"]
    assert sh["n_reads"] == sum(s["n_reads"] for s in singles)


def test_sharded_serving_router_matches_engine():
    """Routing an (S, B) shard-aligned batch equals routing the
    concatenated sessions through one unsharded ServingEngine."""
    from repro.serve.engine import (
        ServeSession, ServingEngine, ShardedServingRouter,
    )

    class _M:
        def prefill(self, params, batch):
            raise NotImplementedError

        def decode_step(self, params, cache, tokens):
            raise NotImplementedError

    eng = ServingEngine(_M(), ConsistencyLevel.X_STCC, jit=False,
                        max_replicas=4, max_sessions=8)
    eng.publish(params=None, version=1)
    eng.publish(params=None, version=3)
    sessions = [ServeSession(i) for i in range(8)]

    router = ShardedServingRouter(2, 4, max_replicas=4)
    router.install(0, 1)
    router.install(1, 3)
    sid = jnp.arange(8, dtype=jnp.int32).reshape(2, 4) % 4

    for pref in (1, 0):
        rep_u, srv_u = eng.route_batch(
            sessions, preferred=jnp.full((8,), pref, jnp.int32))
        rep_s, srv_s = router.route(
            sid, preferred=jnp.full((2, 4), pref, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(rep_u), np.asarray(rep_s).reshape(-1))
        np.testing.assert_array_equal(
            np.asarray(srv_u), np.asarray(srv_s).reshape(-1))
    assert router.reroutes == eng.reroutes
    assert router.staleness_rate() == eng.staleness_rate()


@pytest.mark.slow
def test_sharded_runner_uses_device_mesh():
    """With 2 host devices the shard axis lands on the mesh and the
    metrics stay identical to the single-device vmap path."""
    code = (
        "import os; os.environ['XLA_FLAGS']="
        "'--xla_force_host_platform_device_count=2';"
        "import jax; assert len(jax.devices()) == 2;"
        "from repro.core.consistency import ConsistencyLevel;"
        "from repro.storage.simulator import run_protocol_sharded;"
        "from repro.storage.ycsb import WORKLOAD_A;"
        "kw = dict(n_shards=2, n_ops=400, n_clients=16, n_resources=24,"
        "          audit=False);"
        "a = run_protocol_sharded(ConsistencyLevel.X_STCC, WORKLOAD_A,"
        "                         use_devices=True, **kw);"
        "b = run_protocol_sharded(ConsistencyLevel.X_STCC, WORKLOAD_A,"
        "                         use_devices=False, **kw);"
        "assert a == b, (a, b);"
        "from repro.engine import EngineConfig, EpochEngine;"
        "lay = EpochEngine(EngineConfig(level=ConsistencyLevel.X_STCC,"
        "    **kw)).replay(WORKLOAD_A)['layout'];"
        "assert lay == {'mode': 'shard_map', 'devices': 2}, lay;"
        "print('mesh OK')"
    )
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # The child must never take an accelerator from the test process.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "mesh OK" in out.stdout



"""Observability plane: kernel trio exactness, bit-inertness, traces.

Four contracts:

* the histogram kernel trio (Pallas / tiled jnp twin / dense oracle)
  is **bit-exact** across bin counts, batch shapes, masking, and the
  saturating edge bins — integer counts, no tolerance;
* histogram percentiles reproduce ``jnp.percentile(method="lower")``
  exactly for in-range integer streams;
* ``obs=ObsConfig()`` is **bit-inert**: the golden-wrapper traces
  replay unchanged (the sanitized result minus the ``obs`` block equals
  the pinned pre-obs golden), and an obs-on replay still takes exactly
  one jit entry;
* the span tracer's Chrome export round-trips through JSON against the
  event schema, and a traced replay splits compile from execute.
"""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

import golden_bridge
from repro.engine import EngineConfig, EpochEngine
from repro.engine import replay as replay_mod
from repro.kernels import ops
from repro.kernels.histogram import (
    hist_percentile,
    histogram_pallas,
    histogram_tiled,
    metric_params,
    pack_observations,
)
from repro.kernels.ref import histogram_ref
from repro.obs import trace as trace_lib
from repro.obs.metrics import HostHistogram, ObsConfig, host_percentile
from repro.storage import simulator as sim
from repro.storage.ycsb import WORKLOAD_A

# -- kernel trio ----------------------------------------------------------


def _trio(vals, mask, params, n_bins, block=128):
    pv, pm = pack_observations(vals, mask, block=block)
    dense = histogram_ref(vals, mask, params, n_bins=n_bins)
    tiled = histogram_tiled(pv, pm, params, n_bins=n_bins, block=block)
    pallas = histogram_pallas(
        pv, pm, params, n_bins=n_bins, block=block, interpret=True
    )
    return dense, tiled, pallas


@pytest.mark.parametrize("n_bins", [4, 16, 64])
@pytest.mark.parametrize("batch", [64, 4096])
def test_histogram_trio_bit_exact(n_bins, batch):
    rng = np.random.default_rng(n_bins * 10007 + batch)
    m = 3
    vals = jnp.asarray(
        rng.uniform(-20.0, 120.0, size=(m, batch)), jnp.float32
    )
    mask = jnp.asarray(rng.integers(0, 2, size=(m, batch)), jnp.int32)
    params = metric_params(
        jnp.asarray([0.0, -8.0, 10.0]), jnp.asarray([100.0, 8.0, 11.0]),
        n_bins,
    )
    dense, tiled, pallas = _trio(vals, mask, params, n_bins)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(tiled))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(pallas))
    # Masked histograms count exactly the masked-in observations.
    np.testing.assert_array_equal(
        np.asarray(dense).sum(axis=1), np.asarray(mask.sum(axis=1))
    )


@pytest.mark.parametrize("n_bins", [4, 64])
def test_histogram_empty_and_saturated_bins(n_bins):
    # All mass below lo -> bin 0; all above hi -> top bin; a masked-out
    # row stays empty.  The trio must agree bit-exactly on all three.
    vals = jnp.stack([
        jnp.full((256,), -5.0), jnp.full((256,), 99.0),
        jnp.linspace(0.0, 9.0, 256),
    ]).astype(jnp.float32)
    mask = jnp.stack([
        jnp.ones((256,), jnp.int32), jnp.ones((256,), jnp.int32),
        jnp.zeros((256,), jnp.int32),
    ])
    params = metric_params(
        jnp.zeros(3), jnp.full((3,), 10.0), n_bins
    )
    dense, tiled, pallas = _trio(vals, mask, params, n_bins)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(tiled))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(pallas))
    out = np.asarray(dense)
    assert out[0, 0] == 256 and out[0, 1:].sum() == 0
    assert out[1, -1] == 256 and out[1, :-1].sum() == 0
    assert out[2].sum() == 0


def test_ops_histogram_wrapper_dispatch():
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.uniform(0, 50, size=(130,)), jnp.float32)
    kw = dict(lo=0.0, hi=50.0, n_bins=16)
    dense = ops.histogram(v, impl="dense", **kw)
    tiled = ops.histogram(v, impl="tiled", **kw)
    pallas = ops.histogram(v, impl="pallas", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(tiled))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(pallas))
    assert dense.shape == (16,)
    with pytest.raises(ValueError):
        ops.histogram(v, impl="nope", **kw)


# -- percentile exactness -------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 37, 500])
def test_percentiles_match_jnp_lower(n):
    # Integer-valued streams binned at width 1: the histogram loses
    # nothing, so its percentile must equal jnp.percentile exactly.
    rng = np.random.default_rng(n)
    x = rng.integers(0, 64, size=n).astype(np.float32)
    hist = ops.histogram(
        jnp.asarray(x), lo=0.0, hi=64.0, n_bins=64, impl="dense"
    )
    for q in (50.0, 90.0, 99.0):
        want = float(jnp.percentile(jnp.asarray(x), q, method="lower"))
        got = float(hist_percentile(hist, 0.0, 1.0, q))
        assert got == want, (n, q, got, want)
        assert host_percentile(np.asarray(hist), 0.0, 1.0, q) == want


def test_percentile_of_empty_histogram_is_lo():
    hist = jnp.zeros(16, jnp.int32)
    assert float(hist_percentile(hist, 3.0, 2.0, 99.0)) == 3.0
    assert host_percentile(np.zeros(16, np.int64), 3.0, 2.0, 99.0) == 3.0


def test_host_histogram_mirrors_device_bins():
    rng = np.random.default_rng(11)
    x = rng.uniform(-10, 600, size=2048).astype(np.float32)
    h = HostHistogram(0.0, 512.0, 64)
    h.observe(x)
    dev = ops.histogram(
        jnp.asarray(x), lo=0.0, hi=512.0, n_bins=64, impl="dense"
    )
    np.testing.assert_array_equal(h.counts, np.asarray(dev))
    assert h.count == 2048


# -- bit-inertness vs the golden wrappers ---------------------------------

GOLDEN = golden_bridge.load_golden()
OBS_CASES = [
    "protocol/X_STCC",
    "geo/TCC",
    "sharded/ONE",
    "faulty/X_STCC/outage",   # gossip + handoff + recovery: all rows
]


@pytest.mark.slow
@pytest.mark.parametrize("name", OBS_CASES)
def test_obs_on_matches_golden_wrapper_traces(name):
    if name not in GOLDEN:
        pytest.skip("golden trace not captured")
    fn, kwargs = golden_bridge._cases()[name]
    kwargs = dict(kwargs)
    level, w = kwargs.pop("level"), kwargs.pop("w")
    got = golden_bridge.sanitize(fn(level, w, obs=ObsConfig(), **kwargs))
    obs = got.pop("obs")
    assert golden_bridge.match(got, GOLDEN[name]) == []
    m = obs["metrics"]
    assert m["staleness_age"]["count"] == obs["counters"]["reads"]
    if name.startswith("faulty/X_STCC/outage"):
        assert "hint_depth" in m
    if name.startswith("geo/"):
        assert "read_latency_ms" in m


@pytest.mark.slow
def test_obs_on_replay_takes_one_jit_entry():
    config = EngineConfig(
        golden_bridge.ConsistencyLevel.X_STCC, n_ops=512, batch_size=128,
        obs=ObsConfig(),
    )
    j0 = replay_mod.jit_entries()
    EpochEngine(config).run(WORKLOAD_A)
    assert replay_mod.jit_entries() - j0 == 1


def test_obs_summary_shape():
    res = sim.run_protocol(
        golden_bridge.ConsistencyLevel.ONE, WORKLOAD_A, n_ops=512,
        batch_size=128, obs=ObsConfig(n_bins=16),
    )
    ob = res["obs"]
    assert ob["n_bins"] == 16
    for entry in ob["metrics"].values():
        assert len(entry["hist"]) == 16
        assert entry["count"] == sum(entry["hist"])
        assert all(entry[f"p{q:g}"] is not None for q in (50, 90, 99))
    assert set(ob["cost_attribution"]) == {
        "merge", "gossip", "wal", "egress"
    }
    # One entry per scanned merge epoch (the tail round, if any, is
    # folded into the counters but not the series).
    epochs = ob["counters"]["epochs"]
    assert len(ob["per_round"]["viol"]) in (epochs, epochs - 1)
    # ONE is unguarded: violations exist, and the first violating epoch
    # points at the earliest nonzero per-round count.
    fve = ob["first_violation_epoch"]
    if fve is not None:
        assert ob["per_round"]["viol"][fve] > 0
        assert not any(ob["per_round"]["viol"][:fve])


# -- trace export ---------------------------------------------------------


def test_trace_chrome_round_trip(tmp_path):
    tr = trace_lib.Tracer(run_id="t")
    with tr.recording():
        with trace_lib.span("outer", k=1):
            tr.instant("mark", note="x")
    path = tmp_path / "trace.json"
    tr.write_chrome(path)
    events = trace_lib.load_chrome(path)
    assert [e["name"] for e in events] == ["mark", "outer"]
    for ev in events:
        assert set(trace_lib.EVENT_KEYS) <= set(ev)
    outer = events[-1]
    assert outer["ph"] == "X" and outer["dur"] >= 0
    assert outer["args"] == {"k": 1}
    obj = json.loads(path.read_text())
    assert obj["otherData"]["schema"] == trace_lib.TRACE_SCHEMA


def test_trace_validation_rejects_malformed_events():
    with pytest.raises(ValueError):
        trace_lib.validate_chrome({"no": "events"})
    with pytest.raises(ValueError):
        trace_lib.validate_chrome(
            {"traceEvents": [{"name": "a", "ph": "i"}]}
        )
    with pytest.raises(ValueError):  # complete event without dur
        trace_lib.validate_chrome(
            {"traceEvents": [
                {"name": "a", "ph": "X", "ts": 0, "pid": 1, "tid": 1}
            ]}
        )


@pytest.mark.slow
def test_traced_run_splits_compile_from_execute():
    """The compile of the replay scan lands in its own event inside the
    launch span, which counts it; prepare, launch and assembly are
    spans of their own, and the replay takes one jit entry."""
    # A batch no other test compiles: the launch compiles the scan.
    config = EngineConfig(
        golden_bridge.ConsistencyLevel.X_STCC, n_ops=640, batch_size=160,
        obs=ObsConfig(),
    )
    result, tr = trace_lib.traced_run(config, WORKLOAD_A)
    assert "obs" in result
    names = [e["name"] for e in tr.events]
    for required in ("config", "stages", "engine.replay", "engine.prepare",
                     "engine.launch", "engine.assemble",
                     "engine.assemble.obs"):
        assert required in names, required
    (launch,) = tr.spans("engine.launch")
    assert launch["args"]["jit_entries"] == 1
    compiles = [e for e in tr.spans("compile")
                if launch["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= launch["ts"] + launch["dur"]]
    assert [e["args"]["fun_name"] for e in compiles] == ["jit(run)"]
    assert launch["args"]["compiles"] + launch["args"]["cache_loads"] == 1
    (execute,) = [e for e in tr.spans("engine.assemble.fetch")]
    assert execute["ts"] >= launch["ts"] + launch["dur"]
    (stages,) = [
        e["args"] for e in tr.events if e["name"] == "stages"
    ]
    assert stages["obs"] and not stages["geo"]


# -- serving percentiles (regression: failover spikes p99, not p50) -------


def test_sharded_router_failover_spikes_p99_not_p50():
    from repro.serve.engine import ShardedServingRouter

    r = ShardedServingRouter(2, 8, max_replicas=4, age_hi=64)
    for i in range(4):
        r.install(i, version=3)
    sid = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    r.route(sid)
    st = r.age_stats()
    assert st == {"serves": 16, "p50_age": 0.0, "p99_age": 0.0}
    # Replica 0 dies; replica 1 publishes v10.  Failed-over sessions
    # serve fresh (v10), but sessions pinned to replicas 2/3 now lag by
    # 7 versions: a minority-tail event — p99 spikes, p50 holds.
    r.install(1, version=10)
    r.set_replica_health([False, True, True, True])
    r.route(sid)
    st = r.age_stats()
    assert st["p50_age"] == 0.0
    assert st["p99_age"] == 7.0


def test_region_stats_percentiles():
    from repro.geo.topology import uniform_topology
    from repro.serve.engine import ServeSession, ServingEngine

    class _M:
        def prefill(self, params, batch):
            raise NotImplementedError

        def decode_step(self, params, cache, tokens):
            return "logits", "cache"

    topo = uniform_topology(
        (0, 0, 1, 1, 2, 2), intra_rtt_ms=2.0, inter_rtt_ms=40.0
    )
    eng = ServingEngine(
        _M(), jit=False, max_replicas=6, max_sessions=12
    )
    for _ in range(6):
        eng.publish(None, version=1)
    eng.set_topology(topo)
    sessions = [ServeSession(i) for i in range(12)]
    eng.route_batch(sessions)
    stats = eng.region_stats()
    assert len(stats["p50_latency_ms"]) == topo.n_regions
    # All serves are intra-region (nearest replica): every percentile
    # sits in the first bin, strictly below the WAN RTT.
    assert all(p < 40.0 for p in stats["p99_latency_ms"])
    # Scalar path feeds the same histograms.
    eng._observe(sessions[0], eng.route(sessions[0]))
    assert sum(h.count for h in eng._region_hist) == 13

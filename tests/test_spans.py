"""The engine's host spans, on the profiler's clock.

A replay opens ``engine.*`` spans (``repro.obs.trace.span``): TraceMe
events in a profile, laid on one clock with the device, and complete
events in a recording ``Tracer``.  Checked here on the CPU: a profile
of ``EpochEngine.run`` holds the spans nested as documented, with the
launch's compile count; a recording tracer sees the same spans and
changes no result; with nothing recording a span does nothing.
"""

import contextlib

import jax
import pytest

from repro.core.consistency import ConsistencyLevel
from repro.engine import EngineConfig, EpochEngine
from repro.engine import replay as replay_mod
from repro.obs import trace as trace_lib
from repro.storage.ycsb import WORKLOAD_A

# Shapes no other test compiles, so the first launch here compiles.
CONFIG = EngineConfig(ConsistencyLevel.QUORUM, n_ops=336, batch_size=112,
                      n_resources=40)

# (child, parent): each span lies inside its parent.
NESTING = [
    ("engine.prepare", "engine.replay"),
    ("engine.prepare.stream", "engine.prepare"),
    ("engine.prepare.batch", "engine.prepare"),
    ("engine.place", "engine.replay"),
    ("engine.launch", "engine.replay"),
    ("engine.assemble.fetch", "engine.assemble"),
    ("engine.assemble.audit", "engine.assemble"),
]


@contextlib.contextmanager
def no_persistent_cache():
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


def profile_runs(logdir, n: int):
    """``n`` runs of ``CONFIG`` under the profiler; (results, spans by
    name: lists of (start, end, args))."""
    with no_persistent_cache():
        jax.profiler.start_trace(str(logdir))
        try:
            results = [EpochEngine(CONFIG).run(WORKLOAD_A)
                       for _ in range(n)]
        finally:
            jax.profiler.stop_trace()
    (path,) = list(logdir.rglob("*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(str(path))
    spans: dict[str, list] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    for v in spans.values():
        v.sort(key=lambda s: s[0])
    return results, spans


def test_a_profile_holds_the_engine_spans_nested(tmp_path):
    results, spans = profile_runs(tmp_path, 2)
    assert results[0] == results[1]
    for name in ("engine.replay", "engine.assemble"):
        assert len(spans[name]) == 2, name
    for child, parent in NESTING:
        for s, e, _ in spans[child]:
            assert any(ps <= s and e <= pe for ps, pe, _ in spans[parent]), (
                child, parent)
    # Assembly follows the replay it assembles, which carries the id.
    for (_, r_end, r), (a_start, _, a) in zip(spans["engine.replay"],
                                             spans["engine.assemble"]):
        assert r_end <= a_start
        assert a["replay"] == r["replay"]
        assert r["level"] == "QUORUM" and r["ops"] == 336
        assert r["rounds"] == 3 and r["tenants"] == 1
    first, second = (a for _, _, a in spans["engine.launch"])
    assert first["compiles"] >= 1 and first["jit_entries"] == 1
    assert second["compiles"] == 0 and second["cache_loads"] == 0
    (place, _) = (a for _, _, a in spans["engine.place"])
    assert place["bytes"] > 0


def test_a_recording_tracer_changes_no_result():
    plain = EpochEngine(CONFIG).run(WORKLOAD_A)
    j0 = replay_mod.jit_entries()
    result, tracer = trace_lib.traced_run(CONFIG, WORKLOAD_A)
    assert replay_mod.jit_entries() - j0 == 1
    assert result == plain
    (launch,) = tracer.spans("engine.launch")
    assert launch["args"]["jit_entries"] == 1
    (replay,) = tracer.spans("engine.replay")
    (assemble,) = tracer.spans("engine.assemble")
    assert replay["ts"] + replay["dur"] <= assemble["ts"]
    assert assemble["args"]["replay"] == replay["args"]["replay"]
    trace_lib.validate_chrome(tracer.chrome())


def test_the_launch_span_counts_the_jit_entries_it_saw(monkeypatch):
    """A replay that entered the jitted scan twice inside one launch
    shows 2: the count is measured, not assumed."""
    runner = EpochEngine.runner

    def twice(self, w):
        store, run = runner(self, w)

        def run_twice(b, t):
            run(b, t)
            return run(b, t)

        run_twice.jitted = run.jitted
        return store, run_twice

    monkeypatch.setattr(EpochEngine, "runner", twice)
    tracer = trace_lib.Tracer()
    with tracer.recording():
        EpochEngine(CONFIG).replay(WORKLOAD_A)
    (launch,) = tracer.spans("engine.launch")
    assert launch["args"]["jit_entries"] == 2


@pytest.mark.parametrize("config,impl", [
    (CONFIG, "scan"),
    (EngineConfig(ConsistencyLevel.X_STCC, n_ops=336, batch_size=112,
                  n_resources=40, audit=False, lean=True), "off"),
], ids=["clocks", "lean"])
def test_the_launch_span_names_the_clock_chain(config, impl):
    """``clock_impl`` says which clock chain the round step ran: the
    scan on a CPU, none on the lean cadence path."""
    tracer = trace_lib.Tracer()
    with tracer.recording():
        EpochEngine(config).replay(WORKLOAD_A)
    (launch,) = tracer.spans("engine.launch")
    assert launch["args"]["clock_impl"] == impl


def test_spans_are_inert_when_nothing_records():
    assert not trace_lib.recording()
    with trace_lib.span("engine.test", n=1) as sp:
        assert not sp
        sp.set(n=2)
    tracer = trace_lib.Tracer()
    with tracer.recording():
        with trace_lib.span("outer", shard=3) as outer:
            assert outer
            with trace_lib.span("inner", n=1, skipped=None) as inner:
                inner.set(k=2)
    inner_ev, outer_ev = tracer.events
    assert inner_ev["args"] == {"shard": 3, "n": 1, "k": 2}
    assert outer_ev["args"] == {"shard": 3}
    assert outer_ev["ts"] <= inner_ev["ts"]
    assert inner_ev["ts"] + inner_ev["dur"] <= outer_ev["ts"] + outer_ev["dur"]


def test_the_tracer_clock_is_the_profilers(tmp_path):
    """A span's start in the Chrome export and in the profile agree
    once the profile's own start time is added back."""
    tracer = trace_lib.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.recording():
            with trace_lib.span("engine.clock"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(str(path))
    start = None
    for plane in profile.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
    assert start is not None
    (ev,) = [e.start_ns for p in profile.planes for line in p.lines
             for e in line.events if e.name == "engine.clock"]
    (span,) = tracer.spans("engine.clock")
    assert abs((start + ev) / 1e3 - span["ts"]) < 5e3     # within 5 ms


def test_assembly_carries_the_schedulers_counters():
    """An X-STCC replay schedules its stream in prepare; assembly fetches
    the scheduler's counters with the rest and sets them on a span."""
    config = EngineConfig(ConsistencyLevel.X_STCC, n_ops=512, batch_size=128,
                          n_resources=40)
    _, tracer = trace_lib.traced_run(config, WORKLOAD_A)
    (sched,) = tracer.spans("engine.assemble.schedule")
    args = sched["args"]
    assert args["shard"] == 0 and args["overflow"] == 0
    assert args["dep_applied"] + args["delta_applied"] > 0
    assert 0 < args["pending_peak"] <= 16
    (prep,) = tracer.spans("engine.prepare.schedule")
    (assemble,) = tracer.spans("engine.assemble")
    assert prep["ts"] < assemble["ts"] <= sched["ts"]

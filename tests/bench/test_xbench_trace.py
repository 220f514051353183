"""The reduction from a profiler trace to per-layer numbers."""

import pytest

from bench import tracing
from bench.metrics import programs


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 40)]
    assert tracing.merged(iv) == [(0, 15), (20, 31)]
    assert tracing.union_ns(iv) == 15 + 11
    assert tracing.union_ns(tracing.merged(iv), lo=8, hi=25) == 7 + 5
    assert tracing.union_ns(iv, lo=8, hi=25) == 7 + 5
    assert tracing.union_ns([]) == 0


def test_gaps_are_the_complement_of_the_union():
    iv = [(2, 4), (3, 6), (8, 9)]
    assert tracing.gaps(iv, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    g = tracing.gaps(iv, 0, 10)
    assert sum(e - s for s, e in g) + tracing.union_ns(iv, 0, 10) == 10


def test_top_sums_time_by_name():
    evs = [("a", 0, 5), ("b", 1, 2), ("a", 10, 12)]
    assert tracing.top(evs, 1) == [("a", 7e-9)]
    assert tracing.top(evs) == [("a", 7e-9), ("b", 1e-9)]


def test_module_matching():
    m = programs.module_match(programs.SCAN_MODULES)
    assert m("jit_run") and m("jit_run(123)") and m("jit_local")
    assert not m("jit_runner") and not m("jit_sched")


def test_ingest_ops_by_name_else_the_scans_pallas_call():
    pallas = {"tf_op": "jit(run)/while/body/closed_call/pallas_call"}
    dev = {"modules": [("jit_run", 100, 200)],
           "ops": [("closed_call.3", 120, 130), ("closed_call.3", 250, 260),
                   ("fusion.1", 130, 140)],
           "op_stats": {"closed_call.3": pallas, "fusion.1": {}}}
    assert programs.ingest_ops(dev) == [("closed_call.3", 120, 130)]
    dev["ops"].append(("op_ingest_kernel", 150, 160))
    dev["op_stats"]["op_ingest_kernel"] = {}
    assert programs.ingest_ops(dev) == [("op_ingest_kernel", 150, 160)]


def _ctx(ops_events, module_events, replay=(0, 1000), n_ops=10):
    return tracing.Context(
        ops=n_ops, rounds=2, batch=4, ring=8,
        peaks={"hbm_bytes_per_s": 1e13}, replay=replay,
        assemble=(900, 1000),
        devices=[{"name": "/device:TPU:0", "ops": ops_events,
                  "op_stats": {n: {} for n, _, _ in ops_events},
                  "modules": module_events,
                  "busy": tracing.merged([(s, e) for _, s, e in ops_events])}])


def test_readers_on_a_synthetic_replay():
    ops = [("copy", 50, 100), ("_op_ingest_kernel", 300, 400),
           ("_op_ingest_kernel", 500, 600), ("fusion", 600, 700)]
    mods = [("jit_run(7)", 200, 800)]
    ctx = _ctx(ops, mods)
    read = lambda name: tracing.reader(name)(ctx)       # noqa: E731
    # Busy: 50 + 100 + 100 + 100 = 350 of a 1000 ns span.
    assert read("device.idle_share") == pytest.approx(65.0)
    assert read("scan.device_us_per_op") == pytest.approx(600 / 1e3 / 10)
    assert read("op_ingest.device_us_per_op") == pytest.approx(
        200 / 1e3 / 10)
    # Idle before the scan starts: 200 ns less 50 ns busy.
    assert read("prepare.idle_us_per_op") == pytest.approx(150 / 1e3 / 10)
    assert read("assemble.ms_per_replay") == pytest.approx(100 / 1e6)
    # Two calls of 4 * (12 * 4 + 4 * 8) bytes at 10 TB/s over 200 ns.
    least = 2 * 4 * (12 * 4 + 4 * 8) / 1e13
    assert read("op_ingest.hbm_roofline") == pytest.approx(
        100 * least / 200e-9)


def test_readers_find_nothing_in_an_empty_trace():
    ctx = _ctx([], [])
    for name in ("scan.device_us_per_op", "op_ingest.device_us_per_op",
                 "op_ingest.hbm_roofline", "prepare.idle_us_per_op"):
        assert tracing.reader(name)(ctx) is None


DATA = __import__("pathlib").Path(__file__).resolve().parent / "data"


def test_reduction_of_a_small_trace_in_profiler_format():
    import jax

    profile = jax.profiler.ProfileData.from_text_proto(
        (DATA / "replay_trace.textproto").read_text())
    ctx = tracing.context(profile, ops=100, rounds=1, batch=4, ring=8,
                          peaks={"hbm_bytes_per_s": 1e13}, n_devices=1)
    assert ctx.replay == (1000, 2000) and ctx.assemble == (1850, 2000)
    assert [d["name"] for d in ctx.devices] == ["/device:TPU:0"]
    busy, window = tracing.busy_window(ctx)
    assert busy == [pytest.approx(450e-9)] and window == pytest.approx(1e-6)
    read = lambda name: tracing.reader(name)(ctx)       # noqa: E731
    assert read("device.idle_share") == pytest.approx(55.0)
    assert read("scan.device_us_per_op") == pytest.approx(600 / 1e3 / 100)
    assert read("op_ingest.device_us_per_op") == pytest.approx(
        300 / 1e3 / 100)
    # 200 ns before the scan, 50 of them busy.
    assert read("prepare.idle_us_per_op") == pytest.approx(150 / 1e3 / 100)
    assert read("assemble.ms_per_replay") == pytest.approx(150 / 1e6)
    bd = tracing.breakdown(ctx, profile)
    assert bd["device_ops"][0] == ["closed_call.23", pytest.approx(300e-9)]
    assert len(bd["idle_gaps"]) == 5
    assert bd["idle_gaps"][0] == ["bench.replay", pytest.approx(200e-9)]
    assert ["bench.assemble", pytest.approx(50e-9)] in bd["idle_gaps"]
    assert sum(g for _, g in bd["idle_gaps"]) == pytest.approx(550e-9)

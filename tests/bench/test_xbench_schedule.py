"""The X-STCC cell's program against the plain reference at the witness
study's size, and the reader of the cadence scheduler's device time."""

import pytest

from bench import cell as cell_lib
from bench import check as check_lib
from bench import tracing
from bench.run import replay_once
from bench.seam import StreamPool

STUDY = dict(rows_per_tenant=20000, ops_per_tenant=65536, batch=4096)


@pytest.mark.parametrize("seed,stale", [(0, 204), (3, 177)])
def test_x_stcc_replay_equals_the_reference(seed, stale):
    """65,536 YCSB-A ops over 20,000 rows: every number compared is 0,
    apply points included, and the stale reads are the reference's."""
    cell = cell_lib.load("xstcc-5m.ycsb-a", overrides=STUDY)
    pool = StreamPool(cell_lib.pool_streams(cell, seed))
    prep, result, _ = replay_once(cell, pool, 0)
    prog = check_lib.program_readings(cell, prep, result, 0)
    ref = check_lib.reference_readings(cell, pool.streams[0])
    checks = {n: v for n, v, _ in check_lib.compare(prog, ref, 20000)}
    assert checks["apply_points"] == 0
    assert all(v == 0 for v in checks.values()), checks
    assert prog["stale"] == ref["stale"] == stale


def test_schedule_readers_on_a_synthetic_replay():
    ops = [("fusion.1", 100, 110), ("custom-call.1", 120, 160),
           ("custom-call.1", 300, 340), ("fusion.2", 300, 700)]
    mods = [("jit_schedule(3)", 100, 200), ("jit_run(7)", 250, 800)]
    stats = {n: {} for n, _, _ in ops}
    stats["custom-call.1"] = {"long_name": "custom-call.1 = custom-call("
                              "...), custom_call_target=\"tpu_custom_call\""
                              ", backend_config=cadence_schedule"}
    ctx = tracing.Context(
        ops=10, rounds=2, batch=4, ring=8, peaks={"hbm_bytes_per_s": 1e13},
        replay=(0, 1000), assemble=(900, 1000),
        devices=[{"name": "/device:TPU:0", "ops": ops, "op_stats": stats,
                  "modules": mods,
                  "busy": tracing.merged([(s, e) for _, s, e in ops])}])
    time = tracing.reader("schedule.device_us_per_op")
    roofline = tracing.reader("schedule.hbm_roofline")
    assert time(ctx) == pytest.approx(100 / 1e3 / 10)
    # 10 ops, 8 bytes each, at 10 TB/s, over the kernel's 40 ns inside
    # the scheduler program (the same kernel in the scan does not count).
    assert roofline(ctx) == pytest.approx(100 * 80 / 1e13 / 40e-9)
    ctx.devices[0]["modules"] = mods[1:]
    assert time(ctx) is None and roofline(ctx) is None

"""The readers of the program's stage names and spans, on a small trace
in the profiler's format (``data/scoped_trace.textproto``, whose header
gives every interval the numbers below are made of), on the round step
of the scan as compiled for a TPU v5e (``data/scan_round_hlo.json``),
and on a trace that stops before the scan ends."""

import json
import pathlib
import types

import pytest

from bench import tracing
from bench.metrics import scopes, spans

DATA = pathlib.Path(__file__).resolve().parent / "data"
OPS = 100                                   # client ops of the replay
STACK = "jit(run)/while/body/closed_call/"
Inst = scopes.Inst

# The HLO of the fixture's scan program, as the profile would carry it.
SCAN = {
    "main": [Inst("while.1", "jit(run)/while", ("tuple.0",),
                  ("body", "cond"))],
    "cond": [Inst("lt.1", "jit(run)/while/lt")],
    "body": [
        Inst("param.1"),
        Inst("copy.1", "", ("param.1",)),
        Inst("op_ingest.15", STACK + "apply/op_ingest/pallas_call",
             ("param.1",)),
        Inst("while.156", STACK + "apply/vclock_scan/while",
             ("op_ingest.15",), ("vclock_body",)),
        Inst("fusion.186", STACK + "apply/floors/gather", ("param.1",)),
        Inst("while.160", "", ("param.1",), ("loop_body",)),
        Inst("fusion.203", STACK + "apply/floors/scatter-max",
             ("fusion.186", "while.156")),
        Inst("while.158", STACK + "boundary_merge/while", ("fusion.203",),
             ("merge_body",)),
        Inst("fusion.208", STACK + "apply/duot_record/scatter",
             ("while.156",)),
        Inst("fusion.7", STACK + "apply/pending_ring/scatter",
             ("op_ingest.15",)),
        Inst("fusion.8", STACK + "telemetry/reduce_sum", ("while.158",)),
        Inst("tuple.2", "", ("copy.1", "while.160", "fusion.203",
                             "while.158", "fusion.208", "fusion.7",
                             "fusion.8")),
    ],
    "vclock_body": [Inst("param.2"), Inst("fusion.9", "", ("param.2",))],
    "loop_body": [Inst("param.3"), Inst("add.3", "", ("param.3",))],
    "merge_body": [Inst("param.4"),
                   Inst("max.4", STACK + "boundary_merge/max",
                        ("param.4",))],
}


@pytest.fixture
def scoped():
    import jax

    profile = jax.profiler.ProfileData.from_text_proto(
        (DATA / "scoped_trace.textproto").read_text())
    spans.use(profile)
    scopes.use(SCAN)
    ctx = tracing.context(profile, ops=OPS, rounds=1, batch=4, ring=8,
                          peaks={"hbm_bytes_per_s": 1e13}, n_devices=1)
    yield ctx, profile
    spans.use(None)
    scopes.use(None)


def _us(ns):
    return ns / 1e3 / OPS


def read(ctx, name):
    return tracing.reader(name)(ctx)


def test_scopes_are_path_components_of_the_name_stack():
    of = scopes.scopes_of
    assert of(["jit(run)/while/body/closed_call/apply/floors/gather"]) == {
        "apply", "floors"}
    assert of(["jit(run)/while"]) == set()
    # A file or kernel named alike is not a scope.
    assert of(["kernels/op_ingest.py:12"]) == set()
    assert of(["a/op_ingest:custom-call"]) == {"op_ingest"}


def test_an_op_event_takes_its_instructions_name_stack():
    """A TPU trace names an op by its instruction text; its stages are
    those of the instruction in the HLO the profile carries."""
    smap = scopes.stage_map(SCAN)
    text = "%while.156 = (s32[], s32[16,16]) while(%tuple.9), body=%b"
    assert smap.stages[scopes.instruction(text)] == {"apply", "vclock_scan"}
    # An unnamed op of a loop's body takes the loop's stages.
    assert smap.stages["fusion.9"] == {"apply", "vclock_scan"}
    # An unnamed op no named instruction reads or feeds takes none, nor
    # does the round loop, whose body holds every stage.
    assert smap.stages[scopes.instruction("%copy.1 = s32[] copy(%x)")] \
        == frozenset()
    assert smap.stages["while.160"] == frozenset()
    assert smap.stages["while.1"] == frozenset()
    assert "copy.1" in smap.round_body and "while.1" not in smap.round_body


def test_the_profile_carries_the_scans_name_stacks(tmp_path):
    """The HLO on a profile's metadata plane, read from the raw file:
    the instructions of a program named like the scan, with their name
    stacks, operands and called computations; another program's are
    left out."""
    import jax
    import jax.numpy as jnp

    def run(x):
        with jax.named_scope("floors"):
            y = x * 2
        with jax.named_scope("vclock_scan"):
            return y.sum()

    def other(x):
        with jax.named_scope("floors"):
            return x - 1

    run_j, other_j = jax.jit(run), jax.jit(other)
    x = jnp.arange(8.0)
    run_j(x).block_until_ready()            # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_j(x).block_until_ready()
        other_j(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    comps = scopes.hlo_computations(path.read_bytes())
    insts = [i for c in comps.values() for i in c]
    stacks = {i.op_name for i in insts}
    assert any("/floors/" in v for v in stacks)
    assert any("/vclock_scan/" in v for v in stacks)
    # The profile holds every live program's HLO; only the scan's count.
    assert not any(v.startswith("jit(other)") for v in stacks)
    names = {i.name for i in insts}
    assert any(i.operands for i in insts)
    assert all(o in names for i in insts for o in i.operands)
    assert all(c in comps for i in insts for c in i.called)
    assert scopes.hlo_computations(path.read_bytes(), ("jit_nothing",)) \
        == {}


@pytest.mark.parametrize("name,ns", [
    # The clock loop's while spans its body's fusion: the union, 200 ns,
    # not the sum, 290.
    ("vclock.device_us_per_op", 200),
    ("floors.device_us_per_op", 50 + 50),
    ("merge.device_us_per_op", 100),
    ("duot.device_us_per_op", 30),
])
def test_scoped_device_time_is_the_union_of_the_scopes_ops(scoped, name,
                                                           ns):
    ctx, _ = scoped
    assert read(ctx, name) == pytest.approx(_us(ns))


def test_unscoped_is_the_scan_time_no_named_op_covers(scoped):
    ctx, _ = scoped
    # The carry copy (50), the unnamed while (100) and the idle tail of
    # the program (40); the scan loop's own while covers everything but
    # names no stage.
    assert read(ctx, "scan.unscoped_us_per_op") == pytest.approx(_us(190))
    # The parts make up the scan program, less the stages that no metric
    # reads (pending ring 20, telemetry 10).
    parts = ["vclock.device_us_per_op", "floors.device_us_per_op",
             "merge.device_us_per_op", "duot.device_us_per_op",
             "op_ingest.device_us_per_op", "scan.unscoped_us_per_op"]
    total = sum(read(ctx, n) for n in parts)
    assert total == pytest.approx(read(ctx, "scan.device_us_per_op")
                                  - _us(30))


def test_the_ingest_kernel_is_found_by_its_name(scoped):
    ctx, _ = scoped
    assert read(ctx, "op_ingest.device_us_per_op") == pytest.approx(_us(50))


def test_audit_time_is_the_summed_audit_spans(scoped):
    ctx, _ = scoped
    assert read(ctx, "audit.ms_per_replay") == pytest.approx(200 / 1e6)


def test_unattributed_idle_lies_outside_every_engine_span(scoped):
    ctx, _ = scoped
    # Device idle 1000-1200, 1900-2700 and 2750-3000; the engine's spans
    # cover 1010-1150 and 2510-2990.
    assert read(ctx, "unattributed.idle_us_per_op") == pytest.approx(
        _us(10 + 50 + 610 + 10))


def test_spans_carry_their_arguments_and_name_idle_gaps(scoped):
    ctx, profile = scoped
    launch = spans.within(ctx, "engine.launch")
    assert [(s, e, a) for _, s, e, a in launch] == [
        (1120, 1140, {"jit_entries": 1, "compiles": 0})]
    gaps = dict((n, g) for n, g in tracing.breakdown(ctx, profile)
                ["idle_gaps"])
    assert gaps["engine.prepare"] == pytest.approx(200e-9)
    assert gaps["engine.assemble"] == pytest.approx(250e-9)


def test_a_trace_without_names_or_spans_gives_nothing():
    import jax

    profile = jax.profiler.ProfileData.from_text_proto(
        (DATA / "replay_trace.textproto").read_text())
    spans.use(profile)
    scopes.use({})
    try:
        ctx = tracing.context(profile, ops=OPS, rounds=1, batch=4, ring=8,
                              peaks={"hbm_bytes_per_s": 1e13}, n_devices=1)
        for name in ("vclock.device_us_per_op", "floors.device_us_per_op",
                     "merge.device_us_per_op", "duot.device_us_per_op",
                     "scan.unscoped_us_per_op", "audit.ms_per_replay",
                     "unattributed.idle_us_per_op"):
            assert read(ctx, name) is None, name
    finally:
        spans.use(None)
        scopes.use(None)


# -- the scan as compiled for a TPU v5e -----------------------------------


@pytest.fixture(scope="module")
def chip_round():
    data = json.loads((DATA / "scan_round_hlo.json").read_text())
    return scopes.stage_map({c: [Inst(n, op, tuple(a), tuple(k))
                                 for n, op, a, k in insts]
                             for c, insts in data["computations"].items()})


FLOORS = {"apply", "floors"}


@pytest.mark.parametrize("name,stages", [
    # The loops the compiler puts around the scatters into the (16, 5M)
    # read and write floors and the (3, 5M) replica versions carry no
    # name; each leads, through the unnamed scatter, to the named
    # reshape of the floors.
    ("while.159", FLOORS), ("while.160", FLOORS),
    ("while.161", FLOORS), ("while.162", FLOORS),
    ("while.154", FLOORS), ("while.155", FLOORS),
    # Their bodies' ops take their loop's stages.
    ("dynamic-update-slice.125", FLOORS),
    ("dynamic-slice_reduce_fusion.7", FLOORS),
    # Named instructions keep their own.
    ("while.156", {"apply", "vclock_scan"}),
    ("while.158", {"boundary_merge"}),
    ("op_ingest.15", {"apply", "op_ingest"}),
    # The round loop holds every stage and takes none.
    ("while.153", set()),
])
def test_unnamed_ops_of_the_chips_scan_take_their_neighbours_stages(
        chip_round, name, stages):
    assert chip_round.stages[name] == stages


def test_the_round_body_is_the_round_loops_body(chip_round):
    body = chip_round.round_body
    assert {"while.156", "while.159", "op_ingest.15"} <= body
    assert "while.153" not in body
    assert "dynamic-update-slice.125" not in body   # a loop body's op


# -- a trace that stops before the scan ends --------------------------------


def _cut_context(rounds: int):
    """Three rounds of a scan traced, the last of them cut 30 ns in:
    each round a copy (10 ns) then the clock loop (90 ns) from 100 on;
    the host's engine.launch span covers 0-90 of the replay span
    0-1000."""
    ev = []
    for r in range(3):
        s = 100 + 100 * r
        ev.append(("%copy.1 = s32[] copy(%param.1)", s, s + 10))
        ev.append(("%while.156 = (s32[]) while(%op_ingest.15)", s + 10,
                   min(s + 100, 330)))
    device = {"name": "/device:TPU:0", "ops": ev,
              "op_stats": {n: {} for n, _, _ in ev},
              "modules": [("jit_run(1)", 100, 330)],
              "busy": tracing.merged([(s, e) for _, s, e in ev])}
    ctx = tracing.Context(ops=rounds * 10, rounds=rounds, batch=10, ring=8,
                          peaks={}, replay=(0, 1000), assemble=None,
                          devices=[device])
    host = types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            types.SimpleNamespace(name="engine.launch", start_ns=0,
                                  end_ns=90, stats=[])])])
    return ctx, types.SimpleNamespace(planes=[host])


@pytest.mark.parametrize("rounds,vclock_ns,unscoped_ns,idle_ns,ops", [
    # The scan has 4 rounds: the trace holds 2 whole ones, 100-300.
    (4, 90 + 90, 10 + 10, 10, 20),
    # The scan has 3 rounds: the trace holds them all.
    (3, 90 + 90 + 20, 10 + 10 + 10, 10 + 670, 30),
])
def test_a_cut_trace_is_read_over_its_whole_rounds(rounds, vclock_ns,
                                                   unscoped_ns, idle_ns,
                                                   ops):
    ctx, host = _cut_context(rounds)
    spans.use(host)
    scopes.use({"main": SCAN["main"], "cond": SCAN["cond"],
                "body": SCAN["body"], "vclock_body": SCAN["vclock_body"]})
    try:
        per_op = lambda ns: ns / 1e3 / ops                  # noqa: E731
        assert read(ctx, "vclock.device_us_per_op") == pytest.approx(
            per_op(vclock_ns))
        assert read(ctx, "scan.unscoped_us_per_op") == pytest.approx(
            per_op(unscoped_ns))
        assert read(ctx, "unattributed.idle_us_per_op") == pytest.approx(
            per_op(idle_ns))
    finally:
        spans.use(None)
        scopes.use(None)

"""The stage names of the replay scan, read from the HLO the profile carries.

The program names each stage of its round step with a
``jax.named_scope`` (``engine/replay.py``, ``core/xstcc.py``,
``core/replicated_store.py``); the compiler keeps the name stack in each
HLO instruction's metadata (``op_name="jit(run)/while/body/closed_call/
apply/floors/gather"``).  A TPU trace names an op event by its
instruction text alone (``%while.156 = (...) while(...)``), without the
metadata, but the profile carries the HLO of every program it saw on its
``/host:metadata`` plane: the instructions of the scan programs, with
their name stacks, operands and called computations, are read from
there (:func:`hlo_computations`), and an op event takes the stages of
its instruction (:func:`stage_map`).

An instruction belongs to a stage when one component of its name stack
is the stage's name.  The compiler leaves some instructions without a
name: the layout loops it puts around a scatter into the ``(16, 5M)``
floors, the scatter's own fusion, copies.  Such an instruction takes the
stages that all its nearest named users share (reached through other
unnamed instructions of its computation), or failing that those that
all its nearest named producers share; an instruction inside a called
computation (a loop body, a fusion) that has none takes its caller's.
An unnamed loop whose body holds named instructions (the scan's own
round loop) takes none.

Only ops inside the scan programs count, so that an instruction of
another program with the same name is not taken for one of the scan's.
Ops nest (a ``while`` op's event spans the ops of its body), so a
stage's time is the union of its ops' intervals, not their sum.

The device profiler keeps a bounded number of op events, so the trace
of a long replay can stop before the scan ends (section 5 of PERF.md).
The round body's instructions run once per round: where the first of
them has fewer events than the scan has rounds, every device time here
covers the whole rounds the trace holds (from the first round's start
to the last traced round's start) and is divided by those rounds' ops.

A trace of a program without the names gives no stage, and every reader
here then returns ``None``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import sys

from bench import tracing
from bench.metrics import programs

# The round step's stages, and the scopes inside ``apply``.
STAGES = ("crash", "bootstrap", "hint_drain", "anti_entropy", "apply",
          "boundary_merge", "gossip", "durability", "telemetry", "obs")
APPLY_SCOPES = ("op_ingest", "vclock_scan", "pending_ring", "floors",
                "duot_record")
ALL = STAGES + APPLY_SCOPES
HLO_STAT = ("Hlo Proto", "hlo_proto")
NONE = frozenset()


@dataclasses.dataclass(frozen=True)
class Inst:
    """One HLO instruction: its name, its name stack (``""`` where the
    compiler left none), the instructions whose results it reads, and
    the computations it calls."""
    name: str
    op_name: str = ""
    operands: tuple[str, ...] = ()
    called: tuple[str, ...] = ()


_GIVEN: list = []                    # computations given by use()
_HLO: dict = {}                      # (path, mtime, size) -> stage map
_CACHE: dict[int, tuple] = {}


def use(computations) -> None:
    """Read the stages from ``computations`` (computation name -> its
    :class:`Inst` s) from now on; ``None`` goes back to the HLO of the
    traced run's file."""
    _GIVEN[:] = [] if computations is None else [stage_map(computations)]
    _CACHE.clear()


# -- the HLO the profile carries ------------------------------------------
#
# The ``.xplane.pb`` is an ``XSpace`` protobuf; the field numbers below
# are those of ``xplane.proto`` (XSpace.planes 1; XPlane.name 2,
# .event_metadata 4, .stat_metadata 5; a map entry's key 1 and value 2;
# XEventMetadata.stats 5; XStatMetadata.name 2; XStat.metadata_id 1,
# .bytes_value 6) and ``hlo.proto`` (HloProto.hlo_module 1;
# HloModuleProto.name 1, .computations 3; HloComputationProto.name 1,
# .instructions 2, .id 5; HloInstructionProto.name 1, .metadata 7,
# .id 35, .operand_ids 36, .called_computation_ids 38; OpMetadata.op_name
# 2).  Only the metadata plane is walked; the device planes are skipped
# whole.


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf, span=None):
    """(field number, value) of a message's fields: an integer, or the
    (start, end) slice of a length-delimited value."""
    i, end = span if span is not None else (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unexpected protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _first(buf, span, number: int):
    return next((v for f, v in _fields(buf, span) if f == number), None)


def _ints(buf, values) -> list[int]:
    """A repeated integer field: packed slices or single values."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i, end = v
        while i < end:
            x, i = _varint(buf, i)
            out.append(x)
    return out


def _module(buf, module, out: dict) -> None:
    """Add the computations of one ``HloModuleProto`` to ``out``."""
    comps, ids, comp_ids = [], {}, {}
    for h, comp in _fields(buf, module):
        if h != 3:
            continue
        cname, cid, insts = None, None, []
        for k, v in _fields(buf, comp):
            if k == 1:
                cname = _text(buf, v)
            elif k == 5:
                cid = v
            elif k == 2:
                f = collections.defaultdict(list)
                for g, w in _fields(buf, v):
                    f[g].append(w)
                meta = f[7][0] if f[7] else None
                op = _first(buf, meta, 2) if meta else None
                name = _text(buf, f[1][0])
                ids[f[35][0] if f[35] else name] = name
                insts.append((name, _text(buf, op) if op else "",
                              _ints(buf, f[36]), _ints(buf, f[38])))
        if cid is not None:
            comp_ids[cid] = cname
        comps.append((cname, insts))
    for cname, insts in comps:
        out[cname] = [Inst(n, op, tuple(ids[i] for i in a if i in ids),
                           tuple(comp_ids[i] for i in c if i in comp_ids))
                      for n, op, a, c in insts]


def hlo_computations(raw: bytes, prefixes=programs.SCAN_MODULES
                     ) -> dict[str, list[Inst]]:
    """Computation name -> instructions of the HLO modules named by
    ``prefixes`` that a profile (``raw``: the bytes of an
    ``.xplane.pb``) carries."""
    buf = memoryview(raw)
    match = programs.module_match(prefixes)
    out: dict[str, list[Inst]] = {}
    for number, plane in _fields(buf):
        name = _first(buf, plane, 2) if number == 1 else None
        if name is None or _text(buf, name) != "/host:metadata":
            continue
        stat_ids = set()
        for f, entry in _fields(buf, plane):
            md = _first(buf, entry, 2) if f == 5 else None
            stat_name = _first(buf, md, 2) if md else None
            if stat_name and _text(buf, stat_name) in HLO_STAT:
                stat_ids.add(_first(buf, entry, 1))
        for f, entry in _fields(buf, plane):
            md = _first(buf, entry, 2) if f == 4 else None
            if md is None:
                continue
            for g, stat in _fields(buf, md):
                if g != 5 or _first(buf, stat, 1) not in stat_ids:
                    continue
                blob = _first(buf, stat, 6)
                module = _first(buf, blob, 1) if blob else None
                mod_name = _first(buf, module, 1) if module else None
                if mod_name is not None and match(_text(buf, mod_name)):
                    _module(buf, module, out)
    return out


# -- stages ---------------------------------------------------------------


def scopes_of(stacks) -> frozenset:
    """The stage names in some name stacks."""
    found = set()
    for stack in stacks:
        for part in stack.split("/"):
            part = part.split(":")[0]
            if part in ALL:
                found.add(part)
    return frozenset(found)


@dataclasses.dataclass(frozen=True)
class StageMap:
    stages: dict          # instruction name -> its stages
    round_body: frozenset  # instructions run once per round


def stage_map(comps: dict[str, list[Inst]]) -> StageMap:
    """Each instruction's stages, named or taken from its neighbours
    (module docstring), and the instructions of the round body: the body
    of the loop whose named instructions share no stage."""
    own = {i.name: scopes_of([i.op_name]) for c in comps.values()
           for i in c}
    inside_memo: dict[str, tuple[bool, frozenset]] = {}

    def inside(c: str) -> tuple[bool, frozenset]:
        """Whether ``c`` (or what it calls) holds a named instruction,
        and the stages all of them share."""
        if c not in inside_memo:
            inside_memo[c] = (False, NONE)     # a cycle names nothing
            sets = [own[i.name] for i in comps[c] if own[i.name]]
            sets += [s for i in comps[c] for x in i.called if x in comps
                     for held, s in [inside(x)] if held]
            inside_memo[c] = (bool(sets), frozenset.intersection(*sets)
                              if sets else NONE)
        return inside_memo[c]

    # An unnamed instruction whose called named instructions share no
    # stage (the round loop) takes none, and no stage passes through it.
    shared_inside: dict[str, frozenset] = {}
    barrier = set()
    for insts in comps.values():
        for i in insts:
            sets = [s for x in i.called if x in comps
                    for held, s in [inside(x)] if held]
            if own[i.name] or not sets:
                continue
            shared_inside[i.name] = frozenset.intersection(*sets)
            if not shared_inside[i.name]:
                barrier.add(i.name)

    inferred: dict[str, frozenset] = {}
    for insts in comps.values():
        by = {i.name: i for i in insts}
        users = collections.defaultdict(list)
        for i in insts:
            for o in i.operands:
                users[o].append(i.name)

        def nearest(start, step):
            found, seen, front = [], {start}, [start]
            while front:
                nxt = []
                for x in front:
                    for y in step(x):
                        if y in seen or y not in by or y in barrier:
                            continue
                        seen.add(y)
                        (found if own[y] else nxt).append(y)
                front = nxt
            return frozenset.intersection(*(own[y] for y in found)) \
                if found else NONE

        for i in insts:
            if own[i.name] or i.name in barrier:
                continue
            inferred[i.name] = (nearest(i.name, users.__getitem__)
                                or nearest(i.name,
                                           lambda x: by[x].operands))

    callers = collections.defaultdict(list)
    comp_of = {}
    for c, insts in comps.items():
        for i in insts:
            comp_of[i.name] = c
            for x in i.called:
                callers[x].append(i.name)
    from_caller: dict[str, frozenset] = {}

    def final(name: str) -> frozenset:
        if own[name] or name in barrier:
            return own[name]
        return (inferred[name] or inherited(comp_of[name])
                or shared_inside.get(name, NONE))

    def inherited(c: str) -> frozenset:
        if c not in from_caller:
            from_caller[c] = NONE              # a cycle passes nothing
            sets = [final(x) for x in callers.get(c, ())]
            from_caller[c] = frozenset.intersection(*sets) if sets else NONE
        return from_caller[c]

    stages = {name: final(name) for name in own}
    body = frozenset(
        i.name for c, insts in comps.items()
        if any(own[i.name] for i in insts)
        and any(x in barrier for x in callers.get(c, ()))
        for i in insts)
    return StageMap(stages, body)


def _scan_stages() -> StageMap | None:
    """The scan programs' stages from the traced run's file (read once
    per file), those given to :func:`use`, or none."""
    if _GIVEN:
        return _GIVEN[0]
    try:
        path = tracing.latest_xplane(tracing.TRACE_DIR)
    except FileNotFoundError:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _HLO:
        _HLO.clear()
        try:
            _HLO[key] = stage_map(hlo_computations(path.read_bytes()))
        except (ValueError, IndexError, KeyError) as e:
            # A file this walk cannot read names no stage: the readers
            # then return nothing rather than fail the run.
            print(f"[scopes] no stages read from {path.name}: {e!r}",
                  file=sys.stderr, flush=True)
            _HLO[key] = None
    return _HLO[key]


def instruction(event_name: str) -> str:
    """The HLO instruction an op event of a TPU trace names."""
    return tracing.short(event_name).lstrip("%")


def _classify(ctx) -> list[dict] | None:
    """Per device: the window of whole traced rounds (``window``), the
    client ops it holds (``ops``), the scan programs' intervals in it
    (``scan``) and each stage's merged op intervals in it (key ``"*"``:
    the ops under any stage).  Computed once per context: one pass over
    the op events serves every reader.  ``None`` where no op of the scan
    has a stage."""
    hit = _CACHE.get(id(ctx))
    if hit is not None and hit[0] is ctx:
        return hit[1]
    smap = _scan_stages()
    out = None if smap is None else [_device(ctx, d, smap)
                                     for d in ctx.devices]
    if out is not None and not any(d["stages"]["*"] for d in out):
        out = None
    _CACHE.clear()
    _CACHE[id(ctx)] = (ctx, out)
    return out


def _device(ctx, d, smap: StageMap) -> dict:
    lo, hi = ctx.replay
    scan = programs.modules(programs.SCAN_MODULES)
    mods = tracing.merged([(max(s, lo), min(e, hi)) for _, s, e in
                           scan(d) if s < hi and e > lo])
    starts = [s for s, _ in mods]

    def in_scan(s, e) -> bool:
        j = bisect.bisect_right(starts, s) - 1
        return j >= 0 and e <= mods[j][1]

    events = [(n, s, e) for n, s, e in d["ops"] if in_scan(s, e)]
    by_name = {n: smap.stages.get(instruction(n), NONE)
               for n in d["op_stats"]}
    # The round body's first instruction starts each round.
    body = [(s, n) for n, s, _ in events
            if instruction(n) in smap.round_body]
    window, ops = (lo, hi), ctx.ops / len(ctx.devices)
    if body:
        first = min(body)[1]
        marks = sorted(s for s, n in body if n == first)
        if len(marks) < ctx.rounds:
            window = (marks[0], marks[-1])
            ops = (len(marks) - 1) * ctx.batch
            print(f"[scopes] {d['name']}: the trace holds {len(marks)} of "
                  f"{ctx.rounds} rounds; device times over the first "
                  f"{len(marks) - 1} ({ops} ops)", file=sys.stderr,
                  flush=True)
    a, b = window
    acc: dict = {s: [] for s in ALL}
    acc["*"] = []
    for n, s, e in events:
        found = by_name.get(n)
        if not found or e <= a or s >= b:
            continue
        iv = (max(s, a), min(e, b))
        acc["*"].append(iv)
        for stage in found:
            acc[stage].append(iv)
    return {"window": window, "ops": ops,
            "scan": [(max(s, a), min(e, b)) for s, e in mods
                     if e > a and s < b],
            "stages": {k: tracing.merged(v) for k, v in acc.items()}}


def window(ctx) -> list[tuple[tuple[int, int], float]] | None:
    """Per device: the part of the replay span whose device time the
    trace holds whole, and the client ops of that device in it."""
    classes = _classify(ctx)
    return None if classes is None else [(c["window"], c["ops"])
                                         for c in classes]


def _mean_per_op(classes, per) -> float | None:
    if not all(c["ops"] for c in classes):
        return None
    return sum(t / c["ops"] for t, c in zip(per, classes)) \
        / len(classes) / 1e3


def us_per_op(ctx, scope: str) -> float | None:
    """Device time of the ops under ``scope`` inside the scan programs of
    the whole traced rounds, per client op of a device, the mean over
    the traced devices (us)."""
    classes = _classify(ctx)
    if classes is None:
        return None
    per = [tracing.union_ns(c["stages"][scope]) for c in classes]
    return _mean_per_op(classes, per) if any(per) else None


def unscoped_us_per_op(ctx) -> float | None:
    """Device time of the replay scan program that no staged op covers
    (the scan loop's own work, and ops that take no stage from their
    neighbours), per client op of a device (us)."""
    classes = _classify(ctx)
    if classes is None:
        return None
    return _mean_per_op(classes, [
        tracing.union_ns(c["scan"]) - tracing.union_ns(c["stages"]["*"])
        for c in classes])

"""Spans of the engine's host work, on the profiler's clock.

The device-resident plane (``repro.obs.metrics``) covers *what the
protocol did*; this module covers *what the host did to run it*.  There
is one way the program opens a span: :func:`span`.  A span is a
``jax.profiler.TraceAnnotation`` — a TraceMe on the profiler's host
timeline, which the profiler lays beside the device planes — so a
profile of a replay shows, on one clock, where the host prepared,
placed, launched and assembled, and what the device did meanwhile.
Inside the compiled replay the round step's stages carry
``jax.named_scope`` names (``engine/replay.py``), which reach the device
trace as the ops' name stacks.

The spans of a replay (``engine/replay.py``, ``engine/stream.py``,
``engine/results.py``), each inside the one above it::

    engine.replay            level, tenants, ops, rounds, replay
      engine.prepare         ops
        engine.prepare.masks     fault and gossip masks
        engine.prepare.stream    shard, ops
        engine.prepare.batch     shard, ops
        engine.prepare.schedule  shard (emulated cadences)
      engine.place           bytes
      engine.launch          jit_entries, compiles, cache_loads
    engine.assemble          compiles, cache_loads, replay
      engine.assemble.fetch  the first host read of the carry, where a
                             caller that has not blocked waits for the scan
      engine.assemble.schedule  shard, dep_applied, delta_applied,
                             pending_peak, overflow (the cadence
                             scheduler's counters, emulated cadences)
      engine.assemble.audit  shard, compiles, cache_loads
      engine.assemble.obs

Every span of one replay carries its ``replay`` id; spans of one tenant
carry ``shard``.  A span computes its arguments and attaches them only
while the profiler is tracing or a :class:`Tracer` is recording; with
both off it is one check and nothing else.

``compiles`` and ``cache_loads`` come from one ``jax.monitoring``
duration listener, registered when this module is imported: it counts
backend compiles and persistent-cache loads by function name
(:data:`COMPILES`), so a trace shows which step compiled.

A :class:`Tracer` made active with ``with tracer.recording():`` appends
the same spans, each compile, and its own instants (the chaos harness's
nemesis actions and verdicts) to a Chrome trace-event export.  Its
timestamps are the profiler's host clock: wall-clock microseconds since
the epoch.  :func:`traced_run` is ``EpochEngine.run`` under a recording
tracer.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import itertools
import json
import time
from typing import Any

import jax

# Required keys of every exported trace event (the JSON schema the
# round-trip tests and the CI smoke validate).
EVENT_KEYS = ("name", "ph", "ts", "pid", "tid")
TRACE_SCHEMA = "repro-obs-trace/v2"

# Span arguments that every span opened inside carries too.
_INHERITED = ("replay", "shard")


def config_hash(config) -> str:
    """Content hash of an ``EngineConfig``'s static identity.

    Hashes the same ``_key()`` tuple that keys the compiled-replay
    cache, so equal hashes ⇒ the same jitted program (topology and
    fault-mask bytes included)."""
    return hashlib.sha256(repr(config._key()).encode()).hexdigest()[:16]


def stage_flags(config) -> dict[str, bool]:
    """The static feature gates of one configuration's jaxpr.

    Mirrors the Python-level gating in
    ``repro.engine.replay.unified_runner`` — a disabled stage does not
    exist in the compiled trace at all."""
    gossip, faults = config.gossip, config.faults
    faults_on = faults is not None
    d_on = (
        config.durability is not None and config.durability.enabled
        and faults_on
    )
    return {
        "faults": faults_on,
        "crashes": faults_on and faults.has_crashes,
        "geo": config.topology is not None,
        "gossip": gossip is not None and gossip.enabled,
        "handoff": gossip is not None and gossip.handoff and faults_on,
        "durability": d_on,
        "wal": d_on and config.durability.wal,
        "snapshot": d_on and config.durability.snapshot_every > 0,
        "sharded": config.n_shards > 1,
        "lean": config.lean,
        "obs": config.obs is not None and config.obs.enabled,
    }


def _now_us() -> float:
    """The profiler's host clock (wall-clock ns since the epoch), in us."""
    return time.time_ns() / 1e3


class Tracer:
    """Chrome-trace-event collector (complete events + instants).

    Records the :func:`span` s opened, and the compiles run, while it is
    active (``with tracer.recording():``), plus the instants written to
    it.  Spans are ``ph="X"`` complete events, instants ``ph="i"``;
    timestamps are the profiler's host clock.  One process, one thread
    lane — the engine lifecycle is sequential by construction.
    """

    def __init__(self, run_id: str = "replay"):
        self.run_id = run_id
        self.events: list[dict[str, Any]] = []

    def _event(self, name: str, ph: str, ts: float, **fields) -> dict:
        ev = {"name": name, "ph": ph, "ts": ts, "pid": 1, "tid": 1}
        ev.update(fields)
        self.events.append(ev)
        return ev

    @contextlib.contextmanager
    def recording(self):
        """Record every span opened (and compile run) inside."""
        _RECORDING.append(self)
        try:
            yield self
        finally:
            _RECORDING.remove(self)

    def instant(self, name: str, **args) -> None:
        self._event(name, "i", _now_us(), s="g", args=args)

    def spans(self, name: str) -> list[dict[str, Any]]:
        """The recorded complete events named ``name``, in order."""
        return [e for e in self.events
                if e["ph"] == "X" and e["name"] == name]

    # -- export -----------------------------------------------------------

    def chrome(self) -> dict[str, Any]:
        """The Chrome trace-event JSON object."""
        return {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": {"schema": TRACE_SCHEMA, "run_id": self.run_id},
        }

    def write_chrome(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome(), f, indent=1)


_RECORDING: list[Tracer] = []


# -- compile counter ------------------------------------------------------


class CompileCounter:
    """Backend compiles and persistent-cache loads, by function name.

    JAX reports a load from the persistent cache as a backend compile
    too, with the cache retrieval reported just before it; the counter
    files such a compile under ``loads``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.compiles: collections.Counter[str] = collections.Counter()
        self.loads: collections.Counter[str] = collections.Counter()
        self._loading = False

    def totals(self) -> tuple[int, int]:
        return sum(self.compiles.values()), sum(self.loads.values())

    def on_duration(self, event: str, secs: float, fun_name=None,
                    **_kw) -> None:
        if event == self.LOAD:
            self._loading = True
            return
        if event != self.COMPILE:
            return
        name = str(fun_name)
        loaded, self._loading = self._loading, False
        (self.loads if loaded else self.compiles)[name] += 1
        if _RECORDING:
            end = _now_us()
            for tracer in _RECORDING:
                tracer._event("compile", "X", end - secs * 1e6,
                              dur=secs * 1e6,
                              args={"fun_name": name, "cache_load": loaded})


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES.on_duration)


# -- spans ----------------------------------------------------------------


class _Off:
    """The span handle while nothing records: falsy, ignores arguments."""

    def __bool__(self) -> bool:
        return False

    def set(self, **args) -> None:
        pass


_OFF = _Off()
_REPLAY_IDS = itertools.count()
_OPEN: list[dict[str, Any]] = []          # arguments of the open spans


class _Span:
    """The handle of a recorded span: ``set`` adds arguments known only
    at its end (counts)."""

    def __init__(self, args: dict[str, Any]):
        self.args = args
        self.late: dict[str, Any] = {}

    def __bool__(self) -> bool:
        return True

    def set(self, **args) -> None:
        self.late.update(args)


def recording() -> bool:
    """True while the profiler traces or a :class:`Tracer` records."""
    return bool(_RECORDING) or jax.profiler.TraceAnnotation.is_enabled()


def next_replay_id() -> int:
    """A new ``replay`` id: every span of one replay carries it."""
    return next(_REPLAY_IDS)


@contextlib.contextmanager
def span(name: str, *, count_compiles: bool = False, **args):
    """Open the span ``name`` around the block; yields its handle.

    ``args`` (and what the block adds with ``handle.set``) become the
    span's metadata, less those that are ``None``; the handle is falsy when nothing records, so a
    caller computes a costly argument only ``if handle``.  With
    ``count_compiles`` the span also carries the backend compiles and
    persistent-cache loads run inside it."""
    if not recording():
        yield _OFF
        return
    args = {k: v for k, v in args.items() if v is not None}
    if _OPEN:
        parent = _OPEN[-1]
        args = {**{k: parent[k] for k in _INHERITED if k in parent}, **args}
    sp = _Span(args)
    c0 = COMPILES.totals() if count_compiles else None
    tracers = list(_RECORDING)
    annotation = None
    if jax.profiler.TraceAnnotation.is_enabled():
        annotation = jax.profiler.TraceAnnotation(name, **args)
        annotation.__enter__()
    _OPEN.append(args)
    t0 = _now_us()
    try:
        yield sp
    finally:
        t1 = _now_us()
        _OPEN.pop()
        if c0 is not None:
            c1 = COMPILES.totals()
            sp.set(compiles=c1[0] - c0[0], cache_loads=c1[1] - c0[1])
        if annotation is not None:
            if sp.late:
                annotation.set_metadata(**sp.late)
            annotation.__exit__(None, None, None)
        for tracer in tracers:
            tracer._event(name, "X", t0, dur=t1 - t0,
                          args={**args, **sp.late})


# -- validation -----------------------------------------------------------


def validate_chrome(obj: dict[str, Any]) -> list[dict[str, Any]]:
    """Check an exported trace against the event schema; returns the
    events.  Raises ``ValueError`` on the first malformed event — the
    CI smoke and the round-trip tests call this on re-loaded JSON."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("not a Chrome trace-event object")
    events = obj["traceEvents"]
    for i, ev in enumerate(events):
        missing = [k for k in EVENT_KEYS if k not in ev]
        if missing:
            raise ValueError(f"event {i} missing keys {missing}: {ev}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"complete event {i} missing dur: {ev}")
    return events


def load_chrome(path) -> list[dict[str, Any]]:
    """Load + validate a written Chrome trace; returns its events."""
    with open(path) as f:
        return validate_chrome(json.load(f))


def traced_run(engine, w, tracer: Tracer | None = None):
    """``EpochEngine.run`` under a recording :class:`Tracer`; ``(result,
    tracer)``.

    Accepts an ``EpochEngine`` or a bare ``EngineConfig``.  Besides the
    engine's spans and compiles, the trace holds a ``config`` instant
    (the content hash of the engine config's static key: two runs with
    the same hash compiled the same replay) and a ``stages`` instant
    (the static feature flags the jaxpr was gated on).
    """
    from repro.engine import EpochEngine

    if not isinstance(engine, EpochEngine):
        engine = EpochEngine(engine)
    c = engine.config
    tracer = tracer or Tracer()
    tracer.instant(
        "config", hash=config_hash(c), level=c.level.name,
        n_ops=c.n_ops, batch_size=c.batch_size, n_shards=c.n_shards,
    )
    tracer.instant("stages", **stage_flags(c))
    with tracer.recording():
        result = engine.run(w)
    return result, tracer

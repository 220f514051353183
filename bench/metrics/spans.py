"""The program's own host spans (``engine.*``), read from the trace.

The engine opens its spans with ``repro.obs.trace.span``: TraceMe
events on the profiler's host timeline, whose arguments (counts, the
replay id, the shard) are the events' stats.  The benchmark's
``Context`` holds only its own two spans, so the ``engine.*`` spans are
read here, once per run, from the traced run's ``.xplane.pb`` (or from
the profile given to :func:`use`) and kept.  A trace of a program
without these spans holds none, and the readers then return ``None``.
"""

from __future__ import annotations

import sys

from bench import tracing

PREFIX = "engine."

_GIVEN: list = []                    # a profile given by use()
_LOADED: dict = {}                   # (path, mtime, size) -> spans


def use(profile) -> None:
    """Read the spans of ``profile`` (a ``ProfileData``) from now on;
    ``None`` goes back to the traced run's file."""
    _GIVEN[:] = [] if profile is None else [profile]
    _LOADED.clear()


def _read(profile) -> list[tuple[str, int, int, dict]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    out.append((name, int(e.start_ns), int(e.end_ns),
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: s[1])


def engine_spans() -> list[tuple[str, int, int, dict]]:
    """(name, start_ns, end_ns, args) of every ``engine.*`` span."""
    if _GIVEN:
        key = id(_GIVEN[0])
        if key not in _LOADED:
            _LOADED.clear()
            _LOADED[key] = _read(_GIVEN[0])
        return _LOADED[key]
    try:
        path = tracing.latest_xplane(tracing.TRACE_DIR)
    except FileNotFoundError:
        return []
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _LOADED:
        import jax

        _LOADED.clear()
        _LOADED[key] = _read(jax.profiler.ProfileData.from_file(str(path)))
    return _LOADED[key]


def within(ctx, name: str | None = None):
    """The ``engine.*`` spans (named ``name``, if given) that lie inside
    the replay span."""
    lo, hi = ctx.replay
    return [s for s in engine_spans()
            if s[1] >= lo and s[2] <= hi and (name is None or s[0] == name)]


def log(msg: str) -> None:
    print(f"[spans] {msg}", file=sys.stderr, flush=True)

"""The traced replay of a ``--trace 1`` run: the trace's reduction to
per-layer numbers, and the metrics read from it.

One replay runs under the JAX profiler, inside a host span
``bench.replay`` (with ``bench.assemble`` around result assembly).  The
trace is the ``.xplane.pb`` the profiler writes.  Device planes are
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation run and the ``XLA Modules`` line one event per program
run.  Host spans lie on the host planes.  All times are nanoseconds on
one clock.  Each per-layer metric of the cell is read by its own reader,
``metrics/<name>.py``, whose ``read(ctx)`` returns a number or ``None``
when the trace holds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import shutil
import sys

TRACE_DIR = pathlib.Path(__file__).resolve().parent / ".trace"
METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"
REPLAY_SPAN = "bench.replay"
ASSEMBLE_SPAN = "bench.assemble"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def latest_xplane(logdir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def events(plane, line_name: str
           ) -> tuple[list[tuple[str, int, int]], dict[str, dict]]:
    """(name, start_ns, end_ns) of every event on one line of a plane,
    and the stats of the first event of each name (an op's name is its
    HLO instruction; its stats say what it runs)."""
    out, stats = [], {}
    for line in plane.lines:
        if line.name != line_name:
            continue
        for e in line.events:
            name = e.name
            if name not in stats:
                stats[name] = {k: str(v) for k, v in e.stats}
            out.append((name, int(e.start_ns), int(e.end_ns)))
    return out, stats


def device_planes(profile) -> list:
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "CPU" not in p.name
            and p.name.split(":")[-1].isdigit()]


def host_spans(profile, name: str) -> list[tuple[int, int]]:
    """(start_ns, end_ns) of every host event named ``name``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    out.append((int(e.start_ns), int(e.end_ns)))
    return sorted(out)


def merged(intervals) -> list[tuple[int, int]]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals, lo: int | None = None, hi: int | None = None) -> int:
    """Length of the union of ``intervals``, clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def short(name: str) -> str:
    """An op's HLO instruction name: a TPU trace names an op by its
    whole instruction text, ``%while.156 = (...) while(...)``."""
    return name.split(" = ", 1)[0]


def top(evs, k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` names with the most summed time, in seconds."""
    acc: dict[str, int] = {}
    for n, s, e in evs:
        n = short(n)
        acc[n] = acc.get(n, 0) + (e - s)
    return [(n, t / 1e9) for n, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Maximal sub-intervals of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    ops: int                       # client ops of the traced replay
    rounds: int                    # scan rounds per tenant
    batch: int                     # ops per round
    ring: int                      # pending-ring slots
    peaks: dict                    # the device's row of peaks.json
    replay: tuple[int, int]        # host span of the replay, ns
    assemble: tuple[int, int] | None
    devices: list[dict]            # per device: "ops", "modules" events


def reader(name: str):
    """The ``read`` function of metric ``name`` (``metrics/<name>.py``)."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def context(profile, *, ops, rounds, batch, ring, peaks, n_devices) -> Context:
    replay = host_spans(profile, REPLAY_SPAN)
    assemble = host_spans(profile, ASSEMBLE_SPAN)
    if not replay:
        raise RuntimeError(f"the trace holds no {REPLAY_SPAN!r} span")
    devices = []
    for p in device_planes(profile)[:n_devices]:
        op_events, op_stats = events(p, OPS_LINE)
        modules, _ = events(p, MODULES_LINE)
        # The union of op intervals, sorted once for every reader.
        busy = merged([(s, e) for _, s, e in op_events])
        devices.append({"name": p.name, "ops": op_events,
                        "op_stats": op_stats, "modules": modules,
                        "busy": busy})
    return Context(ops=ops, rounds=rounds, batch=batch, ring=ring,
                   peaks=peaks, replay=replay[-1],
                   assemble=assemble[-1] if assemble else None,
                   devices=devices)


def busy_window(ctx: Context) -> tuple[list[float], float]:
    """Per-device busy seconds inside the replay span, and its length."""
    lo, hi = ctx.replay
    busy = [union_ns(d["busy"], lo, hi) / 1e9 for d in ctx.devices]
    return busy, (hi - lo) / 1e9


def breakdown(ctx: Context, profile) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of device 0 named by the host event that covers their middle."""
    lo, hi = ctx.replay
    d0 = ctx.devices[0]
    ops = [(n, s, e) for n, s, e in d0["ops"] if s < hi and e > lo]
    idle = sorted(gaps(d0["busy"], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    host = [(e.name, int(e.start_ns), int(e.end_ns))
            for p in profile.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    named = []
    for s, e in idle:
        mid = (s + e) // 2
        cover = [h for h in host if h[1] <= mid <= h[2]]
        # The innermost covering span says what the host was doing.
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "none"
        named.append([name, (e - s) / 1e9])
    return {"device_ops": [[n, t] for n, t in top(ops, 10)],
            "idle_gaps": named}


def traced(run_replay, per_layer: list[dict], *, peaks: dict,
           n_devices: int):
    """Trace one replay (``run_replay() -> (prep, ops)``); (prep,
    metrics, device fields, breakdown).  A trace with no device plane
    (the CPU) gives no metrics."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(REPLAY_SPAN):
            prep, ops = run_replay()
    finally:
        jax.profiler.stop_trace()
    st = prep["out"]["st"]
    geometry = dict(rounds=int(prep["n_rounds"]), batch=int(prep["sub"]),
                    ring=int(st.cluster.pend_live.shape[-1]))
    try:
        xplane = latest_xplane(TRACE_DIR)
        print(f"[trace] {xplane.name}: {xplane.stat().st_size} bytes",
              file=sys.stderr, flush=True)
        profile = jax.profiler.ProfileData.from_file(str(xplane))
        ctx = context(profile, ops=ops, peaks=peaks, n_devices=n_devices,
                      **geometry)
        if not ctx.devices:
            return prep, {}, {}, None
        metrics = {}
        for m in per_layer:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for d in ctx.devices:
            print(f"[trace] {d['name']}: {len(d['ops'])} op events, top "
                  f"modules {top(d['modules'], 8)}, top ops "
                  f"{top(d['ops'], 8)}", file=sys.stderr, flush=True)
        busy, window = busy_window(ctx)
        device = {"busy_s": sum(busy) / len(busy), "window_s": window,
                  "busy_s_per_device": busy}
        return prep, metrics, device, breakdown(ctx, profile)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

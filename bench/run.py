#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a deployment from
``bench/configs/`` under a traffic mix from ``bench/traffic/``.  Set-up
generates the cell's pool of op streams from ``--seed``, turns on the
persistent compile cache in ``<checkout>/.jax_cache`` and compiles and
warms the cell's programs with one replay.  The window then replays
streams of the pool back to back, each through ``EpochEngine(cfg).replay``
and ``results.assemble`` (what ``EpochEngine.run`` does), for
``--seconds``, and compiles nothing beyond what result assembly is shown
in set-up to compile on every call.  Afterwards the last replay is
compared with the plain reference (``bench/reference/``), and each
number compared is printed beside its limit.

``--trace 0`` reports the end-to-end metrics.  ``--trace 1`` runs one
replay under the JAX profiler in place of the window (of the first
``traced_ops_per_tenant`` ops of each stream, where the configuration
gives that many, with set-up warming that length), reports the
per-layer metrics read from its trace (``bench/tracing.py``), and checks
that replay.  Without a TPU, or with fewer chips than the cell asks for,
nothing is run and the exit code is 2.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse                                             # noqa: E402
import collections                                          # noqa: E402
import gc                                                   # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import pathlib                                              # noqa: E402
import sys                                                  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations by listening to JAX's monitoring events:
    every compile (``n``), loads from the persistent cache (``loads``,
    which JAX also reports as compiles), and the names compiled with
    their seconds."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.n = 0
        self.loads = 0
        self.names: list[str] = []
        self.seconds: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, fun_name=None, **_kw):
        if name == self.COMPILE:
            self.n += 1
            self.names.append(str(fun_name))
            self.seconds.append(float(secs))
        elif name == self.LOAD:
            self.loads += 1


def replay_once(cell, pool, j: int):
    """One replay of pool entry ``j``: ``EpochEngine(cfg).replay`` then
    ``results.assemble``; (prep, result, seconds)."""
    import jax

    from bench import cell as cell_lib
    from repro.engine import EpochEngine, results

    cfg = cell_lib.engine_config(cell, j * cell.tenants)
    w = cell_lib.workload(cell)
    before = len(pool.calls)
    t0 = time.perf_counter()
    with pool.installed():
        eng = EpochEngine(cfg)
        prep = eng.replay(w)
        jax.block_until_ready(prep["out"])
        with jax.profiler.TraceAnnotation("bench.assemble"):
            result = results.assemble(eng, prep, w)
    seconds = time.perf_counter() - t0
    drawn = pool.calls[before:]
    want = [j * cell.tenants + s for s in range(cell.tenants)]
    if drawn != want:
        raise RuntimeError(f"replay {j} drew streams {drawn} from the pool, "
                           f"expected {want}: the engine bypassed the seam")
    return prep, result, seconds


def device_info(devices, chips: int) -> dict:
    """The devices as JAX reports them; the memory peak is the fullest
    of the ``chips`` the cell uses."""
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:chips])
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(argv=None, *, allow_cpu: bool = False, overrides: dict | None = None
        ) -> tuple[int, dict | None]:
    """One run of one cell; (exit code, result).  ``allow_cpu`` and
    ``overrides`` serve the CPU rehearsals in the tests: a run that
    finds no TPU returns no metrics."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program under test is missing: no {ROOT / 'src' / 'repro'}")
        return 2, None
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # JAX writes no cache entry into a directory that does not exist.
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    import jax
    import numpy as np

    from bench import cell as cell_lib
    from bench import check as check_lib
    from bench.seam import StreamPool
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    cell = cell_lib.load(args.workload, overrides=overrides)
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not allow_cpu:
        log(f"no TPU found (platform {devices[0].platform}); nothing run")
        return 2, None
    if len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}; nothing run")
        return 2, None
    log(f"[cell] {cell.name}: {cell.config['level']} x{cell.tenants} "
        f"tenant(s), {cell.config['rows_per_tenant']:,} rows, "
        f"{cell.ops_per_replay:,} ops per replay, {cell.traffic_name}; "
        f"devices {devices}")

    # -- set-up: the stream pool, compilation, one warm replay ----------
    counter = CompileCounter()
    full = cell
    if args.trace:
        cell = cell_lib.traced(cell)
    pool = StreamPool(cell_lib.pool_streams(
        full, args.seed, keep=int(cell.config["ops_per_tenant"])))
    n_pool = int(cell.mix["stream_pool"])
    warm, _, warm_s = replay_once(cell, pool, n_pool - 1)
    # Result assembly once more on the warm replay: what it compiles
    # again here, the program compiles on every call (it lowers the
    # Pallas audit anew each time), and the window may compile as often.
    from repro.engine import results as results_lib

    n0 = len(counter.names)
    results_lib.assemble(
        cell_lib.engine_config(cell, (n_pool - 1) * cell.tenants), warm,
        cell_lib.workload(cell))
    per_call = collections.Counter(counter.names[n0:])
    warm = None
    gc.collect()
    setup_compiles, setup_loads = counter.n, counter.loads
    setup_s = time.perf_counter() - PROCESS_START
    log(f"[setup] {setup_s:.3f} s (warm replay {warm_s:.3f} s, "
        f"{setup_compiles} compilations, {setup_loads} cache loads; "
        f"compiled on every assembly: {dict(per_call)})")

    # -- the measured window, or one traced replay ----------------------
    replays = []              # (pool index, result, seconds)
    layer, busy, breakdown = {}, {}, None
    if args.trace:
        from bench import cost
        from bench import tracing

        def traced_replay():
            p, r, secs = replay_once(cell, pool, 0)
            replays.append((0, r, secs))
            return p, cell.ops_per_replay

        kind = devices[0].device_kind
        prep, layer, busy, breakdown = tracing.traced(
            traced_replay, cell.per_layer(),
            peaks=cost.peaks(kind) if on_tpu else {}, n_devices=cell.chips)
        window_s = replays[0][2]
        log(f"[trace] {busy}")
    else:
        prep = None
        start = time.perf_counter()
        last_s = 0.0
        j = 0
        while not replays or (time.perf_counter() - start + last_s
                              <= args.seconds):
            prep = None
            prep, result, last_s = replay_once(cell, pool, j % n_pool)
            replays.append((j % n_pool, result, last_s))
            j += 1
        window_s = time.perf_counter() - start
    # The window may compile only what assembly compiles on every call,
    # as often as it assembles; anything else is warm-up that leaked in.
    in_window = collections.Counter(counter.names[setup_compiles:])
    window_compiles = sum(max(0, k - per_call[n] * len(replays))
                          for n, k in in_window.items())
    window_compile_s = sum(counter.seconds[setup_compiles:])
    window_loads = counter.loads - setup_loads
    info = device_info(devices, cell.chips)
    if busy:
        info.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
    ops = cell.ops_per_replay * len(replays)
    log(f"[window] {len(replays)} replays in {window_s:.3f} s: "
        + ", ".join(f"{s:.3f}" for _, _, s in replays)
        + f" s; compiled inside: {dict(in_window)} in "
        f"{window_compile_s:.3f} s, {window_loads} from the cache; beyond "
        f"what every assembly compiles: {window_compiles}; layout "
        f"{prep['layout']}")

    # -- correctness: the last replay against the plain reference --------
    j_last = replays[-1][0]
    shard = int(np.random.default_rng(args.seed).integers(cell.tenants))
    prog = check_lib.program_readings(cell, prep, replays[-1][1], shard)
    prep = None
    gc.collect()
    t_ref = time.perf_counter()
    stream = pool.streams[j_last * cell.tenants + shard]
    ref = check_lib.reference_readings(cell, stream)
    checks = check_lib.compare(prog, ref,
                               int(cell.config["rows_per_tenant"]))
    same = [r for jj, r, _ in replays if jj == j_last]
    checks.append(("replay_repeats",
                   sum(r != same[0] for r in same[1:]), 0))
    checks.append(("window_compilations", window_compiles, 0))
    ref_s = time.perf_counter() - t_ref
    correct = all(v <= lim for _, v, lim in checks)
    log(f"[check] replay {j_last} tenant {shard}: reference "
        f"{ref_s:.3f} s; program reads {prog['reads']} stale "
        f"{prog['stale']} violations {prog['viol']}; reference reads "
        f"{ref['reads']} stale {ref['stale']} violations {ref['viol']}; "
        f"drain merges {prog['drain_merges']}")

    dropped = sum(r["dropped_writes"] for _, r, _ in replays)
    if args.trace:
        metrics = layer
    else:
        metrics = {
            "replay_ops_s": {"value": ops / window_s, "unit": "ops/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    out = {
        "correct": correct, "attempted": ops, "failed": dropped,
        "metrics": metrics if on_tpu else {}, "device": info,
        "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks},
    }
    if breakdown is not None and on_tpu:
        out["breakdown"] = breakdown
    for n, v, lim in checks:
        log(f"check {n} {v} limit {lim}")
    return 0, out


def main(argv=None) -> int:
    rc, out = run(argv)
    if out is not None:
        breakdown = out.pop("breakdown", None)
        checks = out.pop("checks")
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = checks
        print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Smoke run of the X-STCC epoch engine on a TPU.

Drives ``EpochEngine(EngineConfig(...)).run(workload)`` once at the
paper's evaluation shape — X-STCC on YCSB workload A (50% reads, Zipf
0.99), 3 DCs, 16 sessions, the 5,000,000-row key space, a
1,048,576-op stream generated from ``--seed`` — and checks the results
by the repo's own means.  Each phase prints one informational line; the
last line of standard output is the JSON verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip, the default):
  devices     versions and ``jax.devices()``; no TPU -> exit 2
  replay      the full-size replay through the Pallas ``op_ingest``
              kernel (the compiled replay must contain a
              ``tpu_custom_call``), with the DUOT audit on
  agreement   the same replay with ``ingest="tiled"``: every integer
              output and audit count identical
  scalar      batched engine vs the scalar reference engine
              (900 ops, 24 rows): identical stale/violation counts
  guarantees  no dropped writes, no X-STCC session violations, and all
              3 replicas at the global version on every row once the
              pending ring has drained

``--chips 4`` runs only the sharded phase: 4 tenant shards (4 sessions
and 1,250,000 rows each) on a 4-device mesh against the same replay
vmapped on one device; results identical, output spread over 4 devices.

``--rehearse`` runs the same phases at a tiny size on any backend (the
CPU interprets the kernels); it never prints ``"ok": true``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # four chips, sharded phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_OPS = 1_048_576       # cut from the paper's 8M ops per experiment
N_RESOURCES = 5_000_000  # the paper's 5M-row dataset: nothing folds
BATCH = 4096


def info(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def versions() -> str:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}"


def ints_equal(a, b) -> bool:
    """Every leaf of two pytrees equal: on the device when both sides
    live on the same devices, else on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def equal(x, y) -> bool:
        if x.sharding.device_set == y.sharding.device_set:
            return bool(jnp.array_equal(x, y))
        return np.array_equal(np.asarray(x), np.asarray(y))

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and equal(x, y) for x, y in zip(la, lb)
    )


def audit_counts(store, st) -> dict[str, int]:
    from repro.core import audit as audit_lib

    res = store.audit(st, delta=store.delta or 0)
    counts = {k: int(v) for k, v in
              audit_lib.session_guarantee_report(res).items()}
    counts["audited"] = int(res.n_audited)
    counts["violations"] = int(res.n_violations)
    return counts


def replay(cfg, w):
    """``EpochEngine(cfg).run(w)``, keeping the replay's raw output:
    the same replay and result assembly, in two calls."""
    import jax

    from repro.engine import EpochEngine, results

    eng = EpochEngine(cfg)
    t0 = time.perf_counter()
    prep = eng.replay(w)
    jax.block_until_ready(prep["out"])
    t1 = time.perf_counter()
    result = results.assemble(eng, prep, w)
    return prep, result, t1 - t0, time.perf_counter() - t1


def compile_replay(cfg, w):
    """AOT-compile the replay ``cfg`` runs; (seconds, HLO text)."""
    import jax.numpy as jnp

    from repro.engine import EpochEngine

    prep = EpochEngine(cfg).prepare(w)
    b = {k: jnp.asarray(v) for k, v in prep["batched"][0].items()}
    t = {k: jnp.asarray(v) for k, v in prep["tails"][0].items()}
    t0 = time.perf_counter()
    compiled = prep["run"].jitted.lower(b, t).compile()
    return time.perf_counter() - t0, compiled.as_text()


def drain(store, st, max_merges: int = 256):
    """Merge until the pending ring is empty; (state, merges)."""
    import jax

    merge = jax.jit(lambda s: store.merge(s)[0])
    for i in range(max_merges):
        if not bool(st.cluster.pend_live.any()):
            return st, i
        st = merge(st)
    raise AssertionError(f"pending ring not drained after {max_merges} merges")


def one_chip(args, w, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.consistency import ConsistencyLevel
    from repro.engine import EngineConfig
    from repro.storage.simulator import run_protocol, run_protocol_scalar

    n_ops, n_res, batch = args.n_ops, args.n_resources, args.batch
    cfg = EngineConfig(
        level=ConsistencyLevel.X_STCC, n_ops=n_ops, n_resources=n_res,
        batch_size=batch, seed=args.seed, audit=True, lean=False,
    )

    # -- full-size replay, Pallas ingest ----------------------------------
    compile_s, hlo = compile_replay(cfg, w)
    kernel_in = "tpu_custom_call" in hlo
    prep, result, replay_s, assemble_s = replay(cfg, w)
    out, store = prep["out"], prep["store"]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    info("replay", (
        f"X_STCC workload-A {n_res:,} rows {n_ops:,} ops B={batch} "
        f"sessions={cfg.n_clients} audit=on: compile {compile_s:.2f} s, "
        f"replay {replay_s:.2f} s (host prepare included), assemble+audit "
        f"{assemble_s:.2f} s, peak_bytes_in_use {peak}, "
        f"tpu_custom_call in compiled replay: {kernel_in}, "
        f"reads {result['n_reads']}, stale {int(out['stale'])}, "
        f"violations {int(out['viol'])}, severity {result['severity']}"
    ))
    if not args.rehearse:
        assert kernel_in, "the compiled replay holds no Pallas kernel"

    # -- agreement: Pallas kernel vs its tiled twin, on the device --------
    counts = audit_counts(store, out["st"])
    tprep, tresult, tiled_s, _ = replay(
        dataclasses.replace(cfg, ingest="tiled"), w
    )
    tcounts = audit_counts(tprep["store"], tprep["out"]["st"])
    same = ints_equal(out, tprep["out"])
    info("agreement", (
        f"pallas vs tiled: all {len(jax.tree.leaves(out))} integer output "
        f"arrays identical: {same}; audit counts {counts} vs {tcounts}; "
        f"tiled replay {tiled_s:.2f} s"
    ))
    assert same and counts == tcounts, "pallas and tiled ingest disagree"
    assert result["dropped_writes"] == tresult["dropped_writes"]
    del tprep

    # -- agreement: batched engine vs the scalar reference ----------------
    kw = dict(n_ops=900, n_resources=24, audit=False, seed=args.seed)
    rows = []
    for level in ConsistencyLevel:
        bat = run_protocol(level, w, **kw)
        ref = run_protocol_scalar(level, w, **kw)
        counts_b = [round(bat[k] * bat["n_reads"]) for k in
                    ("staleness_rate", "violation_rate")] + [bat["n_reads"]]
        counts_r = [round(ref[k] * ref["n_reads"]) for k in
                    ("staleness_rate", "violation_rate")] + [ref["n_reads"]]
        rows.append((level.name, counts_b, counts_r))
    info("scalar", "engine vs scalar (stale, violations, reads): " + "; ".join(
        f"{n} {b} vs {r}" for n, b, r in rows))
    assert all(b == r for _, b, r in rows), "engine and scalar disagree"

    # -- guarantees --------------------------------------------------------
    st, n_merges = drain(store, out["st"])
    gv = st.cluster.global_version
    converged = bool(jnp.all(st.cluster.replica_version == gv[None, :]))
    written = int(jnp.sum(gv > 0))
    session = {k: counts[k] for k in (
        "monotonic_read", "monotonic_write", "read_your_write",
        "write_follows_read")}
    info("guarantees", (
        f"dropped_writes {result['dropped_writes']}, engine violations "
        f"{int(out['viol'])}, audited session-guarantee violations "
        f"{session}, replicas converged on all {written:,} written rows "
        f"after {n_merges} drain merges: {converged}"
    ))
    assert result["dropped_writes"] == 0, "writes were dropped"
    assert int(out["viol"]) == 0 and not any(session.values()), (
        "X-STCC let a session guarantee be violated")
    assert converged, "replicas diverge after the pending ring drained"


def four_chips(args, w) -> None:
    import jax

    from repro.core.consistency import ConsistencyLevel
    from repro.engine import EngineConfig

    cfg = EngineConfig(
        level=ConsistencyLevel.X_STCC, n_ops=args.n_ops,
        n_resources=args.n_resources, batch_size=args.batch, seed=args.seed,
        audit=True, n_shards=4,
    )
    prep, result, s_sharded, _ = replay(cfg, w)
    vprep, vresult, s_vmap, _ = replay(
        dataclasses.replace(cfg, use_devices=False), w
    )
    spread = {len(x.sharding.device_set)
              for x in jax.tree.leaves(prep["out"])}
    same = ints_equal(prep["out"], vprep["out"]) and result == vresult
    info("sharded", (
        f"4 shards x ({cfg.shard_clients} sessions, {cfg.shard_resources:,} "
        f"rows, {cfg.shard_ops:,} ops): layout {prep['layout']} "
        f"{s_sharded:.2f} s vs {vprep['layout']} {s_vmap:.2f} s; outputs "
        f"span {sorted(spread)} devices; identical: {same}; per-shard "
        f"stale {result['per_shard']['stale']}, dropped "
        f"{result['dropped_writes']}"
    ))
    if not args.rehearse:
        assert prep["layout"] == {"mode": "shard_map", "devices": 4}
        assert spread == {4}, "the output is not spread over 4 devices"
    assert same, "sharded and single-device replays disagree"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never reports ok")
    args = ap.parse_args(argv)
    args.n_ops, args.n_resources, args.batch = (
        (8192, 40_000, 1024) if args.rehearse
        else (N_OPS, N_RESOURCES, BATCH)
    )

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    from repro.storage.ycsb import WORKLOAD_A

    devices = jax.devices()
    dev = devices[0]
    info("devices", f"{versions()}; compile cache {cache}; {devices}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU found (platform {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    if args.chips == 4:
        four_chips(args, WORKLOAD_A)
    else:
        one_chip(args, WORKLOAD_A, dev)

    if args.rehearse:
        print("rehearsal passed; not a chip run, no verdict")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

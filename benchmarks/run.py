"""Benchmark driver: one section per paper table/figure.

  storage   — Figs 8-15 (throughput/staleness/violations/monetary) on
              the 24-node 3-DC cluster simulation.
  protocol  — batched vs scalar X-STCC engine throughput (ops/s) and
              metric agreement at the evaluation's n_ops=6000.
  faults    — failure scenarios (outage rate × partition duration ×
              level): staleness/violations/anti-entropy cost surface.
  geo       — region-aware topology (region skew × placement plan ×
              level): WAN traffic matrix, per-pair egress bill, and the
              placement planner vs the paper's static 4-per-DC plan.
  gossip    — continuous anti-entropy (cadence × outage × level):
              repair traffic, staleness reduction, digest bill.
  recovery  — crash recovery (snapshot cadence × crash rate × level):
              durability bill, replay/bootstrap traffic, and the seeded
              chaos-suite verdicts.
  policy    — adaptive consistency control plane vs every static level
              on phase-shifting workloads (cost/SLA frontier).
  sync_cost — the technique applied to multi-pod training (traffic +
              violations + bill per consistency level).
  kernels   — Pallas kernel agreement + oracle timing.
  roofline  — aggregates results/dryrun into the §Roofline table.

Each prints ``name,us_per_call,derived`` CSV rows.

``--suite NAME[,NAME...]`` (repeatable) restricts the run to the named
suites; ``--check`` runs each selected suite's CI smoke gate instead of
its plain benchmark (the unified-engine smoke matrix in ci.yml is
``--suite <X> --check`` per variant — every engine-backed suite gates
bit-identity with its baseline and the protocol suite gates staleness
deviation ≤ 0.5%).
"""

from __future__ import annotations

import argparse
import sys

from benchmarks.common import emit, write_json

SUITE_NAMES = (
    "storage", "protocol", "faults", "geo", "gossip", "recovery",
    "policy", "sync_cost", "kernels", "roofline",
)


def _suites() -> dict[str, object]:
    from benchmarks import (
        bench_faults,
        bench_geo,
        bench_gossip,
        bench_kernels,
        bench_policy,
        bench_protocol,
        bench_recovery,
        bench_roofline,
        bench_storage,
        bench_sync_cost,
    )

    return {
        "storage": bench_storage,
        "protocol": bench_protocol,
        "faults": bench_faults,
        "geo": bench_geo,
        "gossip": bench_gossip,
        "recovery": bench_recovery,
        "policy": bench_policy,
        "sync_cost": bench_sync_cost,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
    }


def main(argv: list[str] | None = None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--suite", action="append", default=None, metavar="NAME",
        help="suite(s) to run, comma-separated or repeated "
        f"(default: all of {', '.join(SUITE_NAMES)})",
    )
    parser.add_argument(
        "--check", action="store_true",
        help="run each selected suite's CI smoke gate (its check()) "
        "and exit non-zero on any gate failure",
    )
    args = parser.parse_args(argv)

    selected = list(SUITE_NAMES)
    if args.suite:
        selected = [s for part in args.suite for s in part.split(",") if s]
        unknown = [s for s in selected if s not in SUITE_NAMES]
        if unknown:
            parser.error(
                f"unknown suite(s) {unknown}; choose from {SUITE_NAMES}"
            )

    suites = _suites()
    if args.check:
        rc = 0
        for name in selected:
            mod = suites[name]
            if not hasattr(mod, "check"):
                print(f"suite {name} has no --check gate", file=sys.stderr)
                rc = max(rc, 2)
                continue
            rc = max(rc, int(mod.check()))
        sys.exit(rc)

    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        try:
            suites[name].run()
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((name, e))
            emit(name, 0.0, f"ERROR:{type(e).__name__}:{e}")
    # Machine-readable trajectory: every emitted row, including the
    # ERROR markers above, lands in BENCH_PROTOCOL.json at the repo
    # root so perf is diffable across PRs.
    path = write_json()
    print(f"wrote {path}", file=sys.stderr)
    if failures:
        for name, e in failures:
            print(f"benchmark {name} failed: {e}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

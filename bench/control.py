#!/usr/bin/env python3
"""Readings of the correctness comparison for a cell's control.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--sound]
        [--level X_STCC]

The control is the program run at the configuration's
``control_level``, a level that breaks one guarantee the configuration
states (QUORUM without synchronous propagation: TCC), on the cell's own
streams and sizes; the reference stays at the configuration's level.
``--sound`` runs the configuration's own level instead, for the lower
readings.  ``--level`` puts program and reference at another level on
the cell's store and traffic (the X-STCC study of PERF.md).  One JSON
line per seed: every number compared.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1]),
                str(pathlib.Path(__file__).resolve().parents[1] / "src")]


def readings(workload: str, seed: int, *, sound: bool = False,
             overrides: dict | None = None) -> dict[str, float]:
    """Every number compared, for the first pool stream of ``seed``."""
    from bench import cell as cell_lib
    from bench import check as check_lib
    from bench.run import replay_once
    from bench.seam import StreamPool

    cell = cell_lib.load(workload, overrides=overrides)
    if not sound:
        control = dict(cell.config, level=cell.config["control_level"])
        run_cell = dataclasses.replace(cell, config=control)
    else:
        run_cell = cell
    pool = StreamPool(cell_lib.pool_streams(cell, seed))
    prep, result, _ = replay_once(run_cell, pool, 0)
    prog = check_lib.program_readings(run_cell, prep, result, 0)
    prep = None
    gc.collect()
    ref = check_lib.reference_readings(cell, pool.streams[0])
    checks = check_lib.compare(prog, ref, int(cell.config["rows_per_tenant"]))
    return {name: v for name, v, _ in checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--level", default=None)
    args = ap.parse_args(argv)
    overrides = {"level": args.level} if args.level else None
    import os

    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"))
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(args.workload, seed, sound=args.sound,
                     overrides=overrides)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "level": args.level, "control": not args.sound,
                          "readings": r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Three replays of one stream: the program, the plain reference, and
the program's own scalar engine (``storage/simulator.py``
``_scalar_runner``, one op per scan step), as a second witness where the
program and the reference disagree.

    python bench/witness.py --workload quorum-5m.ycsb-a --level X_STCC \\
        --seeds 0,3,6 [--ops 65536 --rows 20000 --batch 4096]

Prints one JSON line per seed with each side's reads, stale reads and
violations, and the writes whose apply point the program's cadence
scheduler puts elsewhere than the reference.  The scalar engine copies
its state on every op, so keep ``--ops`` and ``--rows`` small.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1]),
                str(pathlib.Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="quorum-5m.ycsb-a")
    ap.add_argument("--level", default="X_STCC")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ops", type=int, default=65536)
    ap.add_argument("--rows", type=int, default=20000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--no-scalar", action="store_true")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from bench import cell as cell_lib
    from bench import check as check_lib
    from bench.run import replay_once
    from bench.seam import StreamPool
    from repro.core.consistency import ConsistencyLevel
    from repro.storage.simulator import _scalar_runner

    cell = cell_lib.load(args.workload, overrides=dict(
        level=args.level, rows_per_tenant=args.rows, ops_per_tenant=args.ops,
        batch=args.batch))
    c = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = StreamPool(cell_lib.pool_streams(cell, seed))
        s = pool.streams[0]
        prep, result, _ = replay_once(cell, pool, 0)
        prog = check_lib.program_readings(cell, prep, result, 0)
        prep = None
        ref = check_lib.reference_readings(cell, s)
        checks = dict((n, v) for n, v, _ in check_lib.compare(
            prog, ref, args.rows))
        line = {"seed": seed,
                "program": [prog["reads"], prog["stale"], prog["viol"]],
                "reference": [ref["reads"], ref["stale"], ref["viol"]],
                "apply_points": checks.get("apply_points")}
        if not args.no_scalar:
            run = _scalar_runner(ConsistencyLevel[c["level"]],
                                 int(c["sessions_per_tenant"]), args.rows,
                                 int(c["merge_every"]), int(c["delta"]),
                                 int(c["duot_cap"]))
            _, _, st, vi, rd = run(*(jnp.asarray(s[k]) for k in
                                     ("client", "kind", "resource", "home")))
            line["scalar_engine"] = [int(rd), int(st), int(vi)]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

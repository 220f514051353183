"""Golden-trace registry for the legacy ``run_protocol_*`` wrappers.

The unified epoch engine (``repro.engine``) replaced the four batched
``run_protocol`` twins; the legacy entry points survive as thin config
shims.  This module pins their *pre-refactor* outputs: every case below
was captured on the last commit where each wrapper still had its own
hand-rolled loop, and ``tests/test_engine_bridge.py`` replays the cases
through the engine and asserts the sanitized result dictionaries
:func:`match` (ints, bools and strings exact; floats to :data:`RTOL`).

Regenerate (only when a *deliberate* metrics change lands) with::

    PYTHONPATH=src python -m tests.golden_bridge

which rewrites ``tests/data/golden_wrappers.json``.
"""

from __future__ import annotations

import json
import math
import pathlib
from typing import Any, Callable

import jax
import numpy as np

from repro.core import availability as av
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import DurabilityConfig
from repro.gossip.scheduler import GossipConfig
from repro.policy.sla import SLA_RELAXED
from repro.storage import simulator as sim
from repro.storage.ycsb import PHASED_RW, WORKLOAD_A

GOLDEN_PATH = pathlib.Path(__file__).parent / "data" / "golden_wrappers.json"

# Float fields are float32 accumulations (per-region RTT sums, the
# adaptive bill) whose summation order changes between XLA builds and
# between backends: the goldens drift by up to ~1e-6 relative on a
# newer XLA, far below any change a metric could show.  Integer fields
# carry every count and stay exact.
RTOL = 1e-5

LEVELS = (
    ConsistencyLevel.X_STCC,
    ConsistencyLevel.TCC,
    ConsistencyLevel.CAUSAL,
    ConsistencyLevel.ONE,
    ConsistencyLevel.QUORUM,
    ConsistencyLevel.ALL,
)


def _outage_schedule() -> av.FaultSchedule:
    # 600 ops / batch 128 -> 5 rounds; replica 1 out for epochs 1..2,
    # healed before the end so the backlog drains.
    return av.replica_outage(5, 3, 1, 1, 3)


def _cases() -> dict[str, tuple[Callable[..., dict], dict[str, Any]]]:
    cases: dict[str, tuple[Callable[..., dict], dict[str, Any]]] = {}
    for lvl in LEVELS:
        cases[f"protocol/{lvl.name}"] = (
            sim.run_protocol,
            dict(level=lvl, w=WORKLOAD_A, n_ops=600),
        )
        cases[f"geo/{lvl.name}"] = (
            sim.run_protocol_geo,
            dict(level=lvl, w=WORKLOAD_A, n_ops=600),
        )
        cases[f"sharded/{lvl.name}"] = (
            sim.run_protocol_sharded,
            dict(level=lvl, w=WORKLOAD_A, n_ops=600, n_shards=2),
        )
        cases[f"faulty_allup/{lvl.name}"] = (
            sim.run_protocol_faulty,
            dict(level=lvl, w=WORKLOAD_A, n_ops=600),
        )
    # Non-default kwargs: cadence overrides, outages, gossip, recovery.
    cases["protocol/X_STCC/alt"] = (
        sim.run_protocol,
        dict(level=ConsistencyLevel.X_STCC, w=WORKLOAD_A, n_ops=640,
             batch_size=64, merge_every=4, delta=12, seed=3, audit=False),
    )
    cases["geo/X_STCC/gossip_recovery"] = (
        sim.run_protocol_geo,
        dict(level=ConsistencyLevel.X_STCC, w=WORKLOAD_A, n_ops=600,
             gossip=GossipConfig(cadence=2, hint_cap=32),
             recovery=DurabilityConfig(snapshot_every=2, wal=True)),
    )
    cases["faulty/X_STCC/outage"] = (
        sim.run_protocol_faulty,
        dict(level=ConsistencyLevel.X_STCC, w=WORKLOAD_A, n_ops=600,
             schedule=_outage_schedule(), schedule_unit=128,
             gossip=GossipConfig(cadence=2, hint_cap=32),
             recovery=DurabilityConfig(snapshot_every=2, wal=True)),
    )
    cases["faulty/CAUSAL/outage"] = (
        sim.run_protocol_faulty,
        dict(level=ConsistencyLevel.CAUSAL, w=WORKLOAD_A, n_ops=600,
             schedule=_outage_schedule(), schedule_unit=128, audit=False),
    )
    cases["faulty/X_STCC/sharded"] = (
        sim.run_protocol_faulty,
        dict(level=ConsistencyLevel.X_STCC, w=WORKLOAD_A, n_ops=600,
             n_shards=2, schedule=_outage_schedule(), schedule_unit=128,
             audit=False),
    )
    cases["adaptive/PHASED_RW"] = (
        sim.run_protocol_adaptive,
        dict(w=PHASED_RW, sla=SLA_RELAXED, n_ops=1280, epoch_size=64,
             levels=(ConsistencyLevel.ONE, ConsistencyLevel.X_STCC)),
    )
    return cases


def sanitize(obj: Any) -> Any:
    """Result dict -> pure JSON (drop private keys, widen numpy types)."""
    if isinstance(obj, dict):
        return {
            str(k): sanitize(v)
            for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))
            if not str(k).startswith("_")
        }
    if isinstance(obj, (list, tuple)):
        return [sanitize(v) for v in obj]
    if isinstance(obj, (bool, np.bool_)):
        return bool(obj)
    if isinstance(obj, (int, np.integer)):
        return int(obj)
    if isinstance(obj, (float, np.floating)):
        return float(obj)
    if isinstance(obj, np.ndarray) or type(obj).__name__ == "ArrayImpl":
        return sanitize(np.asarray(obj).tolist())
    return obj


def match(got: Any, want: Any, path: str = "") -> list[str]:
    """Paths where ``got`` differs from ``want`` (empty: they match).

    Exact everywhere except floats, which agree to :data:`RTOL`."""
    if isinstance(want, dict) and isinstance(got, dict):
        if set(got) != set(want):
            return [f"{path}: keys {sorted(set(got) ^ set(want))}"]
        return [d for k in want for d in match(got[k], want[k], f"{path}/{k}")]
    if isinstance(want, list) and isinstance(got, list):
        if len(got) != len(want):
            return [f"{path}: length {len(got)} != {len(want)}"]
        return [d for i, (g, w) in enumerate(zip(got, want))
                for d in match(g, w, f"{path}[{i}]")]
    if isinstance(want, float) and isinstance(got, float):
        if math.isclose(got, want, rel_tol=RTOL, abs_tol=0.0):
            return []
    elif type(got) is type(want) and got == want:
        return []
    return [f"{path}: {got!r} != {want!r}"]


def run_case(name: str) -> Any:
    fn, kwargs = _cases()[name]
    kwargs = dict(kwargs)
    if fn is sim.run_protocol_adaptive:
        w = kwargs.pop("w")
        sla = kwargs.pop("sla")
        # Captured when ``jax_threefry_partitionable`` defaulted to
        # False.  The new default draws different bits from the same
        # key, which moves the controller's epsilon-exploration draws
        # (session 0 explores at epoch 11).  Replay with the PRNG the
        # golden was captured under.
        with jax.threefry_partitionable(False):
            return sanitize(fn(w, sla, **kwargs))
    level = kwargs.pop("level")
    w = kwargs.pop("w")
    return sanitize(fn(level, w, **kwargs))


def case_names() -> list[str]:
    return list(_cases())


def load_golden() -> dict[str, Any]:
    with open(GOLDEN_PATH) as f:
        return json.load(f)


def main() -> None:
    golden = {}
    for name in case_names():
        golden[name] = run_case(name)
        print(f"captured {name}")
    GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    print(f"wrote {GOLDEN_PATH} ({len(golden)} cases)")


if __name__ == "__main__":
    main()

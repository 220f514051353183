"""A benchmark cell: a deployment (``configs/<config>.json``) under a
traffic mix (``traffic/<traffic>.json``), found by its name in
``BENCHMARK.json``."""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from bench.traffic import ycsb

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    spec: dict            # the whole BENCHMARK.json

    @property
    def tenants(self) -> int:
        return int(self.config["tenants"])

    @property
    def ops_per_replay(self) -> int:
        """Client ops one replay drives, over all tenants."""
        return int(self.config["ops_per_tenant"]) * self.tenants

    def per_layer(self) -> list[dict]:
        """The per-layer metrics this cell reports."""
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name])]


def load(workload: str, spec_path: pathlib.Path = ROOT / "BENCHMARK.json",
         overrides: dict | None = None) -> Cell:
    """The cell ``workload``; ``overrides`` replaces configuration keys
    (the CPU rehearsals run the same cells at a tiny scale, and the
    studies of ``control.py`` and ``witness.py`` at another level)."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; cells: {sorted(cells)}")
    w = cells[workload]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    config = json.loads((ROOT / files[w["config"]]).read_text())
    config.update(overrides or {})
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], mix=ycsb.load_mix(w["traffic"]),
                spec=spec)


def traced(cell: Cell) -> Cell:
    """The cell as its ``--trace 1`` run replays it: where the
    configuration gives ``traced_ops_per_tenant``, the first that many
    ops of each stream (a trace of the whole replay on every chip holds
    more op events than a run can reduce in its time), else the whole."""
    n = cell.config.get("traced_ops_per_tenant")
    if n is None:
        return cell
    return dataclasses.replace(
        cell, config={**cell.config, "ops_per_tenant": int(n)})


def engine_config(cell: Cell, engine_seed: int):
    """The ``EngineConfig`` one replay of ``cell`` runs with."""
    from repro.core.consistency import ConsistencyLevel
    from repro.engine import EngineConfig

    c = cell.config
    t = cell.tenants
    return EngineConfig(
        level=ConsistencyLevel[c["level"]],
        n_ops=int(c["ops_per_tenant"]) * t,
        n_clients=int(c["sessions_per_tenant"]) * t,
        n_resources=int(c["rows_per_tenant"]) * t,
        merge_every=int(c["merge_every"]), delta=int(c["delta"]),
        duot_cap=int(c["duot_cap"]), batch_size=int(c["batch"]),
        seed=engine_seed, audit=bool(c["audit"]), ingest=c["ingest"],
        lean=bool(c["lean"]), n_shards=t,
    )


def workload(cell: Cell):
    """The engine's workload record: only its read share is consulted
    once the stream comes from the pool."""
    from repro.storage.ycsb import Workload

    return Workload(cell.traffic_name,
                    read_fraction=float(cell.mix["read_proportion"]))


def pool_streams(cell: Cell, run_seed: int, keep: int | None = None
                 ) -> dict[int, dict[str, np.ndarray]]:
    """Every stream of the run, keyed by the engine seed that draws it:
    replay ``j`` of the pool asks for seeds ``j * tenants + shard``.
    ``keep`` cuts each stream to its first ``keep`` ops."""
    c = cell.config
    out = {}
    for j in range(int(cell.mix["stream_pool"])):
        for s in range(cell.tenants):
            stream = ycsb.stream(
                cell.mix, n_ops=int(c["ops_per_tenant"]),
                n_sessions=int(c["sessions_per_tenant"]),
                n_rows=int(c["rows_per_tenant"]),
                n_replicas=int(c["replicas"]), seed=(run_seed, j, s))
            out[j * cell.tenants + s] = {k: v[:keep]
                                         for k, v in stream.items()}
    return out

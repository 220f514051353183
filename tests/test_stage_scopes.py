"""The replay scan names its stages, and every Pallas kernel has a name.

Each stage of the round step that a configuration compiles runs under a
``jax.named_scope`` (``engine/replay.py``), and inside ``apply`` the
op-ingest kernel, the clock scan, the pending ring, the floors and the
DUOT record run under theirs.  The names are metadata: the compiled
program carries them in its ops' ``op_name``, which is what a device
trace shows.  Checked on the compiled scan of a flat QUORUM replay, a
flat X-STCC replay and a replay with crashes, gossip, hinted handoff,
durability and the observability plane.
"""

import pathlib
import re

import jax.numpy as jnp
import pytest

from repro.core import availability as av
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import DurabilityConfig
from repro.engine import EngineConfig, EpochEngine
from repro.gossip import GossipConfig
from repro.obs import ObsConfig
from repro.storage.ycsb import WORKLOAD_A

N_OPS, BATCH = 256, 64
T = N_OPS // BATCH
APPLY = {"op_ingest", "vclock_scan", "pending_ring", "floors", "duot_record"}
FLAT = {"apply", "boundary_merge", "telemetry"} | APPLY
FAULTY = FLAT | {"crash", "bootstrap", "hint_drain", "anti_entropy",
                 "gossip", "durability", "obs"}

CONFIGS = {
    "quorum": (EngineConfig(ConsistencyLevel.QUORUM, n_ops=N_OPS,
                            batch_size=BATCH), FLAT),
    "xstcc": (EngineConfig(ConsistencyLevel.X_STCC, n_ops=N_OPS,
                           batch_size=BATCH), FLAT),
    "faults_gossip": (EngineConfig(
        ConsistencyLevel.X_STCC, n_ops=N_OPS, batch_size=BATCH,
        faults=av.replica_crash(T, 3, 1, 1, down_for=1),
        schedule_unit=BATCH,
        gossip=GossipConfig(cadence=2, hint_cap=32),
        durability=DurabilityConfig(snapshot_every=2, wal=True),
        obs=ObsConfig(),
    ), FAULTY),
}


def scan_text(config) -> str:
    prep = EpochEngine(config).prepare(WORKLOAD_A)
    b = {k: jnp.asarray(v) for k, v in prep["batched"][0].items()}
    t = {k: jnp.asarray(v) for k, v in prep["tails"][0].items()}
    return prep["run"].jitted.lower(b, t).compile().as_text()


def scopes_in(text: str) -> set[str]:
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        found.update(name.split("/"))
    return found


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_compiled_stage_carries_its_scope(name):
    config, expected = CONFIGS[name]
    found = scopes_in(scan_text(config)) & (FAULTY | APPLY)
    assert found == expected


def test_every_pallas_call_is_named():
    kernels = pathlib.Path(__file__).resolve().parents[1] / "src/repro/kernels"
    calls = 0
    for path in sorted(kernels.glob("*.py")):
        text = path.read_text()
        for m in re.finditer(r"pl\.pallas_call\(", text):
            calls += 1
            depth, i = 1, m.end()
            while depth:
                depth += {"(": 1, ")": -1}.get(text[i], 0)
                i += 1
            assert re.search(r"\bname=\"\w+\"", text[m.end():i]), (
                f"{path.name}: a pallas_call without a name")
    assert calls >= 8

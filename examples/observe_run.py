"""Watch one engine replay: device histograms, spans, a rendered report.

The observability plane measures without re-introducing host→device
round trips: the staleness/severity/latency/queue-depth distributions
accumulate *inside* the engine's scan carry (one jit entry, same as an
unobserved run), and the engine's ``engine.*`` spans mark its host work
around it.  This example runs the crash-recovery geometry — faults +
gossip + hinted handoff on a geo topology — with ``obs=ObsConfig()``
under a recording ``Tracer``, exports the Chrome trace (open it in
Perfetto, ui.perfetto.dev, or ``chrome://tracing``), and renders the
percentile/cost report two consistency levels side by side.

The same spans land in a JAX profile, beside what the device ran, with
the round step's stage names on the device ops::

    jax.profiler.start_trace("/tmp/replay-profile")
    EpochEngine(config).run(WORKLOAD_A)
    jax.profiler.stop_trace()

Run:  PYTHONPATH=src python examples/observe_run.py
"""

import pathlib
import tempfile

from repro.core import availability as av
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import DurabilityConfig
from repro.engine import EngineConfig
from repro.geo.topology import PAPER_TOPOLOGY
from repro.gossip import GossipConfig
from repro.obs import ObsConfig
from repro.obs import report as report_lib
from repro.obs import trace as trace_lib
from repro.storage.ycsb import WORKLOAD_A

N_OPS, BATCH = 2048, 64
T = N_OPS // BATCH
SCHEDULE = av.replica_outage(T, 3, 1, T // 6, T // 2)
GOSSIP = GossipConfig(cadence=2, hint_cap=32)


def traced_level(level: ConsistencyLevel) -> tuple[dict, trace_lib.Tracer]:
    config = EngineConfig(
        level,
        n_ops=N_OPS,
        batch_size=BATCH,
        topology=PAPER_TOPOLOGY,
        faults=SCHEDULE,
        schedule_unit=BATCH,
        gossip=GOSSIP,
        durability=DurabilityConfig(snapshot_every=4, wal=True),
        obs=ObsConfig(),                 # histograms ride the scan carry
    )
    tracer = trace_lib.Tracer(run_id=f"observe-{level.value}")
    return trace_lib.traced_run(config, WORKLOAD_A, tracer)


def main() -> None:
    out = pathlib.Path(tempfile.mkdtemp(prefix="observe-run-"))
    runs = {}
    for level in (ConsistencyLevel.X_STCC, ConsistencyLevel.ONE):
        result, tracer = traced_level(level)
        runs[level.value] = result
        trace_path = out / f"trace_{level.value}.json"
        tracer.write_chrome(trace_path)
        (launch,) = tracer.spans("engine.launch")
        print(f"--- {level.value}: jit entries = "
              f"{launch['args']['jit_entries']}, compiles at launch = "
              f"{launch['args']['compiles']}")
        for name in ("engine.prepare", "engine.launch", "engine.assemble"):
            (span,) = tracer.spans(name)
            print(f"    {name:<16} {span['dur'] / 1e3:9.1f} ms")
        print(f"    trace -> {trace_path}")

    artifact = out / "runs.json"
    report_lib.write_artifact(artifact, runs)
    print()
    print(report_lib.render(report_lib.load_artifact(artifact)))
    print(f"\nartifact -> {artifact}")


if __name__ == "__main__":
    main()

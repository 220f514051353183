"""Seeded chaos runs: nemesis schedule -> faulty driver -> invariants.

One :func:`run_chaos` call is a full experiment: draw a randomized
nemesis schedule (crashes x outages x partitions x gossip cadence) from
the seed, run the crash-enabled faulty driver under it, run the
**never-crashed twin** (same schedule with the crash events stripped,
same everything else), then

* check the causal invariants (:mod:`repro.chaos.invariants`) — zero
  X-STCC violations, recovery traffic iff a crash fired;
* drive both final states through a quiescent all-up anti-entropy
  fixpoint and require the rebuilt fleet to match the never-crashed
  fleet **bit-exactly** (replica versions, replica vector clocks, and
  the global version frontier).

:func:`run_chaos_suite` aggregates N seeds into one verdict — the CI
gate runs it with >= 5 seeds.

Pass ``obs=ObsConfig()`` to record the run's staleness/severity
distributions device-side, and ``tracer=Tracer()`` to get the
experiment as a timeline: every nemesis action (crash/outage/partition
epochs), each invariant's verdict, and — with obs on — the per-epoch
violation counts, including the **first violating epoch**, land as
trace instants beside the spans of the run, its twin and the quiesce
(each holding the engine's own ``engine.*`` spans), so a failed chaos
run pinpoints *when* it went wrong instead of reporting one pass/fail
bit.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.chaos.invariants import check_invariants
from repro.chaos.nemesis import random_gossip, random_schedule
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import DurabilityConfig
from repro.gossip import GossipConfig
from repro.obs.metrics import ObsConfig
from repro.obs.trace import span
from repro.storage.simulator import run_protocol_faulty
from repro.storage.ycsb import WORKLOAD_A, Workload

__all__ = ["run_chaos", "run_chaos_suite"]

# Snapshot + WAL: a crash restores the exact pre-crash applied state,
# so bit-exact convergence to the never-crashed twin is a *guarantee*
# under the default config, not a fixture of lucky timing.
DEFAULT_RECOVERY = DurabilityConfig(snapshot_every=4, wal=True)

_QUIESCE_PASSES = 2


def _quiesce(store, state):
    """All-up anti-entropy fixpoint: flush every live pending write."""
    p = store.n_replicas
    up = jnp.ones((p,), bool)
    link = jnp.ones((p, p), bool)
    for _ in range(_QUIESCE_PASSES):
        state, _ = store.anti_entropy(state, up=up, link=link)
    return state


def _fleet_signature(state) -> dict[str, np.ndarray]:
    cl = state.cluster
    return {
        "replica_version": np.asarray(cl.replica_version),
        "replica_vc": np.asarray(cl.replica_vc),
        "global_version": np.asarray(cl.global_version),
    }


def _trace_nemesis(tracer, schedule) -> None:
    """The drawn schedule's actions, as trace instants on the epoch axis."""
    crashes = np.asarray(schedule.crashes())
    up = np.asarray(schedule.up)
    link = np.asarray(schedule.link)
    for t in range(schedule.n_epochs):
        for r in np.flatnonzero(crashes[t]):
            tracer.instant("nemesis.crash", epoch=t, replica=int(r))
        down = np.flatnonzero(~up[t])
        if down.size:
            tracer.instant(
                "nemesis.outage", epoch=t, replicas=down.tolist()
            )
        if not link[t].all():
            cut = int((~link[t]).sum() - (~link[t].diagonal()).sum())
            tracer.instant("nemesis.partition", epoch=t, cut_links=cut)


def run_chaos(
    seed: int,
    *,
    level: ConsistencyLevel = ConsistencyLevel.X_STCC,
    w: Workload = WORKLOAD_A,
    n_ops: int = 1024,
    batch_size: int = 128,
    n_replicas: int = 3,
    recovery: DurabilityConfig | None = DEFAULT_RECOVERY,
    gossip: GossipConfig | str | None = "random",
    p_crash: float = 0.08,
    p_outage: float = 0.10,
    p_partition: float = 0.08,
    quiet_tail: int = 3,
    obs: ObsConfig | None = None,
    tracer=None,
) -> dict[str, Any]:
    """One seeded chaos experiment; returns a verdict dict.

    ``gossip="random"`` lets the nemesis draw the cadence; pass a
    :class:`~repro.gossip.GossipConfig` or ``None`` to pin it.  The
    verdict's ``ok`` is True iff the invariants held *and* the rebuilt
    fleet converged bit-exactly to the never-crashed twin.

    ``obs`` threads the device-resident observability plane through the
    crashed run (the twin stays obs-free — obs is bit-inert, so the
    convergence check is unaffected) and adds ``first_violation_epoch``
    to the verdict; ``tracer`` (a :class:`repro.obs.trace.Tracer`)
    records nemesis actions, per-epoch violation counts, and each
    invariant's outcome as trace events.
    """
    n_epochs = n_ops // batch_size + (1 if n_ops % batch_size else 0)
    schedule = random_schedule(
        n_epochs, n_replicas, seed=seed, p_crash=p_crash,
        p_outage=p_outage, p_partition=p_partition,
        quiet_tail=min(quiet_tail, max(1, n_epochs - 1)),
    )
    if gossip == "random":
        gossip = random_gossip(seed)
    if tracer is not None:
        tracer.instant(
            "chaos.schedule", seed=seed, level=level.value,
            n_epochs=n_epochs, n_replicas=n_replicas,
            cadence=gossip.cadence if gossip is not None else 0,
        )
        _trace_nemesis(tracer, schedule)
    kw = dict(
        n_ops=n_ops, batch_size=batch_size, schedule=schedule,
        recovery=recovery, gossip=gossip, audit=True, obs=obs,
        _return_state=True,
    )
    recording = tracer.recording() if tracer is not None else nullcontext()
    with recording:
        with span("chaos.run", seed=seed):
            res = run_protocol_faulty(level, w, **kw)
        twin_kw = dict(kw, schedule=schedule.strip_crashes(), obs=None)
        with span("chaos.twin", seed=seed):
            twin = run_protocol_faulty(level, w, **twin_kw)

        crashed = schedule.has_crashes
        breaches = check_invariants(res, level, crashed=crashed)

        first_violation = None
        if obs is not None and obs.enabled:
            ob = res["obs"]
            first_violation = ob.get("first_violation_epoch")
            if tracer is not None:
                for t, v in enumerate(ob["per_round"]["viol"]):
                    if v:
                        tracer.instant(
                            "invariant.violations", epoch=t, count=int(v)
                        )

        store = res["_store"]
        with span("chaos.quiesce"):
            sig = _fleet_signature(_quiesce(store, res["_state"]))
            twin_sig = _fleet_signature(
                _quiesce(twin["_store"], twin["_state"])
            )
    diverged = [
        k for k in sig if not np.array_equal(sig[k], twin_sig[k])
    ]
    converged = not diverged

    if tracer is not None:
        for name, ok in (
            ("invariants", not breaches), ("convergence", converged),
        ):
            tracer.instant(
                f"verdict.{name}", ok=ok, seed=seed,
                **({"breaches": breaches} if name == "invariants"
                   and breaches else {}),
                **({"diverged": diverged} if name == "convergence"
                   and diverged else {}),
            )

    return {
        "seed": seed,
        "level": level.value,
        "crashes": int(schedule.crashes().sum()),
        "outage_epochs": int((~schedule.up).sum()),
        "partitions": int(
            sum(1 for t in range(schedule.n_epochs)
                if not schedule.link[t].all())
        ),
        "gossip_cadence": gossip.cadence if gossip is not None else 0,
        "breaches": breaches,
        "converged": converged,
        "diverged_fields": diverged,
        "first_violation_epoch": first_violation,
        "metrics": {
            k: res[k]
            for k in ("staleness_rate", "violation_rate", "severity",
                      "n_reads", "dropped_writes")
        },
        "recovery": res.get("recovery"),
        "ok": converged and not breaches,
    }


def run_chaos_suite(
    seeds=range(5), **kwargs: Any
) -> dict[str, Any]:
    """Run :func:`run_chaos` across seeds; aggregate one verdict.

    ``ok`` is True iff every seed passed.  The per-seed verdicts ride
    along under ``"runs"`` for diagnosis and the bench JSON.
    """
    runs = [run_chaos(int(s), **kwargs) for s in seeds]
    return {
        "n_seeds": len(runs),
        "n_crashes": sum(r["crashes"] for r in runs),
        "n_breaches": sum(len(r["breaches"]) for r in runs),
        "n_diverged": sum(0 if r["converged"] else 1 for r in runs),
        "ok": all(r["ok"] for r in runs),
        "runs": runs,
    }

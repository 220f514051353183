"""The unified epoch engine: one device-resident replay loop.

Every batched protocol driver — flat, geo-replicated, sharded, faulty,
and the adaptive control plane's telemetry precompute — is the *same*
``lax.scan`` over merge epochs.  One round step chains the op-ingest
kernel, the merge fixpoint, and the per-round telemetry device-side;
every orthogonal feature (fault masks, two-tier geo merge, gossip
anti-entropy, hinted handoff, durability journaling, crash recovery,
per-client telemetry, lean fidelity) is a *statically gated section* of
that one function.  A disabled feature does not exist in the jaxpr, so
a config with everything off compiles the exact pre-unification flat
trace — the property the bridge suite (``tests/test_engine_bridge.py``)
pins bit-for-bit against golden pre-refactor outputs.

Replays are cached per static configuration signature
(:func:`unified_runner` is ``lru_cache``'d), so a whole replay is a
single jit re-entry: host → device once per run, not per epoch.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import availability as avail_lib
from repro.core import duot as duot_lib
from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import DurabilityConfig, ReplicatedStore
from repro.engine.config import EngineConfig
from repro.engine import stream as stream_lib
from repro.gossip.scheduler import GossipConfig, gossip_pairs
from repro.kernels import ops as kernel_ops
from repro.kernels import vclock_chain
from repro.obs import metrics as obs_lib
from repro.obs import trace as trace_lib

# Monotone counter of jit re-entries into compiled replays — the
# "host hops per replay" the protocol bench reports.  One replay = one
# entry (plus one per vmapped shard stack), however many epochs it scans.
_JIT_ENTRIES = [0]


def jit_entries() -> int:
    return _JIT_ENTRIES[0]


@functools.lru_cache(maxsize=None)
def unified_runner(
    level: ConsistencyLevel,
    n_clients: int,
    n_resources: int,
    merge_every: int,
    delta: int,
    duot_cap: int,
    sub: int,
    rem: int,
    emulate: bool,
    pending_cap: int,
    ingest: str,
    lean: bool,
    topology,
    gossip: GossipConfig | None,
    recovery: DurabilityConfig | None,
    crashes: bool,
    faults_on: bool,
    telemetry: bool,
    obs: obs_lib.ObsConfig | None = None,
) -> tuple[ReplicatedStore, Any]:
    """(store, jitted replay) for one engine configuration.

    The returned ``run(batched, tail)`` scans the unified round step
    over the per-round input pytree and returns the final carry dict
    (plus per-round gossip telemetry when the gossip subsystem is
    compiled in).  Every feature below is gated on a *Python* flag, so
    the jaxpr of a given configuration contains exactly its features:

      ``faults_on``   crash/bootstrap conds, heal-time hint drain and
                      anti-entropy, failover reroute, emulation clamp,
                      masked merges, event metering;
      ``topology``    two-tier region merge with (G, G) delivery
                      attribution and per-region read telemetry;
      ``gossip``      scheduled digest exchange (+ hinted handoff);
      ``recovery``    WAL journaling and snapshot markers;
      ``telemetry``   per-client count vectors per round (the adaptive
                      control plane's feed) instead of scalar sums;
      ``obs``         the observability plane: the metric registry's
                      histogram/counter state rides the scan carry
                      (binned device-side via the ``ops.histogram``
                      kernel) and per-epoch stale/violation counts ride
                      the ys — still one jit entry per replay;
      ``lean``        skip the vector-clock scan, the DUOT record, and
                      the causal-dependency merge gate — the emulated
                      cadence's closed-form predicates already carry
                      visibility (flat throughput path only).
    """
    g_on = gossip is not None and gossip.enabled
    # Hinted handoff is a fault-path feature: the all-up geo driver
    # compiles (and allocates) none of it even when hint_cap > 0.
    h_on = gossip is not None and gossip.handoff and faults_on
    d_on = recovery is not None and recovery.enabled
    w_on = d_on and recovery.wal
    s_on = d_on and recovery.snapshot_every > 0
    rx_on = d_on or crashes
    geo_on = topology is not None
    gx_on = gossip is not None and faults_on
    ggx_on = g_on and geo_on and not faults_on
    lean_merge = lean and emulate
    boot_ranges = recovery.bootstrap_ranges if recovery is not None else 8
    boot_impl = recovery.impl if recovery is not None else None
    P = topology.n_replicas if geo_on else 3
    G = topology.n_regions if geo_on else 0
    o_on = obs is not None and obs.enabled
    if o_on:
        specs = obs_lib.build_metrics(obs, geo_on=geo_on, h_on=h_on)
        ob_lo, ob_hi, n_op_metrics = obs_lib.batch_bounds(specs)
        n_metrics = len(specs)

    store = ReplicatedStore(
        P, n_clients, n_resources, level=level, merge_every=merge_every,
        delta=delta, pending_cap=pending_cap, duot_cap=duot_cap,
        ingest=ingest,
        hint_cap=gossip.hint_cap if (gossip and faults_on) else 0,
        durability=recovery if d_on else None,
    )
    if geo_on:
        client_reg = jnp.asarray(
            topology.client_region_of(np.arange(n_clients)), jnp.int32
        )
        replica_reg = jnp.asarray(topology.regions(), jnp.int32)
        rtt = jnp.asarray(topology.rtt(), jnp.float32)
        all_up = jnp.ones((P,), bool)
        all_conn = jnp.ones((P, P), bool)

    def round_step(carry, ops, step0, width):
        st = carry["st"]
        if faults_on:
            up, conn = ops["up"], ops["conn"]
        elif geo_on:
            up, conn = all_up, all_conn
        if crashes:
            # Crash epoch: the replica's volatile state dies *before*
            # anything else happens this epoch; what survives is the
            # store's durability layer (snapshot + WAL).
            with jax.named_scope("crash"):
                def do_crash(s):
                    return store.crash(s, ops["crash"])

                def no_crash(s):
                    z = jnp.int32(0)
                    return s, {"wal_replayed": z, "snap_read": z,
                               "rows_lost": z}

                st, cinfo = jax.lax.cond(
                    ops["crash"].any(), do_crash, no_crash, st
                )
                rx = carry["rx"]
                rx = {
                    **rx,
                    "crashes": rx["crashes"]
                    + jnp.sum(ops["crash"].astype(jnp.int32)),
                    "wal_replayed": rx["wal_replayed"] + cinfo["wal_replayed"],
                    "rows_lost": rx["rows_lost"] + cinfo["rows_lost"],
                    "snap_read": rx["snap_read"] + cinfo["snap_read"],
                }

            with jax.named_scope("bootstrap"):
                # Rejoin epoch: pull stale ranges from the nearest live
                # holder before the replica serves anything.
                def do_boot(s):
                    s2, tel = store.bootstrap(
                        s, targets=ops["rejoin"], up=up, link=conn,
                        n_ranges=boot_ranges, impl=boot_impl,
                    )
                    return s2, (
                        jnp.sum(tel["cells"]), jnp.sum(tel["pend"]),
                        jnp.sum(tel["valid"].astype(jnp.int32)),
                    )

                def no_boot(s):
                    z = jnp.int32(0)
                    return s, (z, z, z)

                st, (bc, bp, be) = jax.lax.cond(
                    ops["rejoin"].any(), do_boot, no_boot, st
                )
                rx = {
                    **rx,
                    "boot_cells": rx["boot_cells"] + bc,
                    "boot_pend": rx["boot_pend"] + bp,
                    "boot_events": rx["boot_events"] + be,
                }
                carry = {**carry, "rx": rx}
        if w_on:
            with jax.named_scope("durability"):
                # Applied copies at the start of the epoch (post-recovery):
                # the epoch's growth is what each replica journals.
                applied0 = jnp.sum(
                    st.cluster.pend_applied.astype(jnp.int32), axis=0
                )
        if h_on:
            with jax.named_scope("hint_drain"):
                # Heal epoch: targeted hint deliveries front-run the full
                # anti-entropy pass — drained hints shrink its backlog.
                st, hd = jax.lax.cond(
                    ops["heal"],
                    lambda s: store.drain_hints(s, up=up, link=conn),
                    lambda s: (s, jnp.zeros((P,), jnp.int32)),
                    st,
                )
        if faults_on:
            with jax.named_scope("anti_entropy"):
                # Heal epoch: reconcile the backlog along the newly-available
                # links (Δ=0 full catch-up) before serving this epoch's ops.
                st, ev = jax.lax.cond(
                    ops["heal"],
                    lambda s: store.anti_entropy(s, up=up, link=conn),
                    lambda s: (s, jnp.int32(0)),
                    st,
                )
        # -- op ingest (the fused kernel chain, device-side) ------------
        with jax.named_scope("apply"):
            if faults_on:
                # Ops whose home replica is down fail over to the next
                # live replica in ring order (the serving router's
                # failover).
                home = avail_lib.reroute_ops(ops["home"], up)
                carry = {
                    **carry,
                    "ae": carry["ae"] + ev,
                    "fail": carry["fail"]
                    + jnp.sum((home != ops["home"]).astype(jnp.int32)),
                }
                # While a fault is active, the closed-form cadence's
                # "applied everywhere at the apply index" assumption is
                # wrong — defer pending-ring visibility to the real
                # masked merges.
                end = step0 + width
                st = st._replace(pend_apply=jnp.where(
                    ops["faulty"], jnp.maximum(st.pend_apply, end),
                    st.pend_apply,
                ))
            else:
                home = ops["home"]
            if w_on:
                # Ring slots claimed by this batch's writes overwrite
                # their old applied bits; snapshot them so the epoch's
                # journal growth counts every applied copy, not the net
                # of the sum.
                pre_bits = st.cluster.pend_applied
            st, res = store.apply_batch(
                st, client=ops["client"], replica=home,
                resource=ops["resource"], kind=ops["kind"],
                op_step0=step0 if emulate else None,
                apply_index=ops.get("apply_idx"),
                record=not (lean or telemetry),
                with_clocks=not lean_merge,
            )
            if h_on:
                # Writes served during a fault leave hints for the
                # replicas the coordinator could not reach this epoch.
                def enq(s):
                    return store.enqueue_hints(
                        s, slot=res.slot, version=res.version,
                        kind=ops["kind"], home=home, conn=conn,
                    )

                z = jnp.int32(0)
                st, ne, nd = jax.lax.cond(
                    ops["faulty"], enq, lambda s: (s, z, z), st
                )
        # -- boundary merge (fixpoint / two-tier / schedule-faithful) ---
        with jax.named_scope("boundary_merge"):
            if lean_merge:
                st, _ = store.merge(
                    st, timed_only=True, boundary=step0 + width
                )
            elif geo_on and faults_on:
                before = jnp.sum(st.cluster.pend_applied.astype(jnp.int32))
                st, _, tr = store.merge_geo(st, topology, up=up, link=conn)
                ev = (jnp.sum(st.cluster.pend_applied.astype(jnp.int32))
                      - before)
                carry = {**carry, "prop": carry["prop"] + ev,
                         "traffic": carry["traffic"] + tr}
            elif geo_on:
                st, _, tr = store.merge_geo(st, topology)
                carry = {**carry, "traffic": carry["traffic"] + tr}
            elif faults_on:
                st, _, ev = store.merge_faulty(st, up=up, link=conn)
                carry = {**carry, "prop": carry["prop"] + ev}
            else:
                st, _ = store.merge(st)
        # -- gossip anti-entropy ----------------------------------------
        ys = {}
        with jax.named_scope("gossip"):
            if gx_on:
                # Scheduled digest exchange: diff range digests with the
                # epoch's peers, repair only the stale ranges.
                def do_gossip(s):
                    s2, tel = store.gossip_round(
                        s, pairs=ops["pairs"], up=up, link=conn,
                        n_ranges=gossip.n_ranges, impl=gossip.impl,
                    )
                    return s2, (
                        jnp.sum(tel["growth"]),
                        jnp.sum(tel["ranges"]),
                        jnp.sum(tel["valid"].astype(jnp.int32)),
                        tel["gap_repaired"],
                    )

                def no_gossip(s):
                    z = jnp.int32(0)
                    return s, (z, z, z, z)

                if g_on:
                    st, (gd, gr, gp, gg) = jax.lax.cond(
                        ops["gossip"], do_gossip, no_gossip, st
                    )
                else:
                    gd = gr = gp = gg = jnp.int32(0)
                gx = carry["gx"]
                gx = {
                    **gx,
                    "deliv": gx["deliv"] + gd,
                    "ranges": gx["ranges"] + gr,
                    "pairs": gx["pairs"] + gp,
                    "gap": gx["gap"] + gg,
                }
                if h_on:
                    gx = {**gx, "h_enq": gx["h_enq"] + ne,
                          "h_drop": gx["h_drop"] + nd,
                          "h_deliv": gx["h_deliv"] + hd}
                carry = {**carry, "gx": gx}
                ys["gossip"] = (gd, gr, gg)
            elif ggx_on:
                # Geo flavor: repair deliveries and digest payloads are
                # attributed to the exchanging replicas' *region pair*.
                def do_gossip_geo(s):
                    s2, tel = store.gossip_round(
                        s, pairs=ops["pairs"], up=all_up, link=all_conn,
                        n_ranges=gossip.n_ranges, impl=gossip.impl,
                    )
                    a, b = ops["pairs"][:, 0], ops["pairs"][:, 1]
                    ra, rb = replica_reg[a], replica_reg[b]
                    mi = jnp.arange(a.shape[0])
                    growth = tel["growth"]
                    v = tel["valid"].astype(jnp.int32)
                    zgg = jnp.zeros((G, G), jnp.int32)
                    gt = zgg.at[ra, rb].add(growth[mi, b])
                    gt = gt.at[rb, ra].add(growth[mi, a])
                    dg = zgg.at[ra, rb].add(v).at[rb, ra].add(v)
                    return s2, (gt, dg, jnp.sum(tel["ranges"]),
                                tel["gap_repaired"])

                def no_gossip_geo(s):
                    zgg = jnp.zeros((G, G), jnp.int32)
                    return s, (zgg, zgg, jnp.int32(0), jnp.int32(0))

                st, (gt, dg, gr, gg) = jax.lax.cond(
                    ops["gossip"], do_gossip_geo, no_gossip_geo, st
                )
                ggx = carry["ggx"]
                carry = {**carry, "ggx": {
                    "traffic": ggx["traffic"] + gt,
                    "digest": ggx["digest"] + dg,
                    "ranges": ggx["ranges"] + gr,
                    "gap": ggx["gap"] + gg,
                }}
        # -- durability epilogue ----------------------------------------
        with jax.named_scope("durability"):
            if w_on:
                # Journal each replica's applied deltas for this epoch (new
                # coordinator copies + merge/gossip deliveries).  Recycled
                # slots destroyed their applied bits mid-epoch; add those
                # back so the journal measures gross applies, not the net
                # movement of the column sums.
                is_w = ops["kind"] == duot_lib.WRITE
                lost = jnp.sum(
                    pre_bits[res.slot].astype(jnp.int32)
                    * is_w[:, None].astype(jnp.int32),
                    axis=0,
                )
                growth = jnp.maximum(
                    jnp.sum(st.cluster.pend_applied.astype(jnp.int32), axis=0)
                    - applied0 + lost, 0,
                )
                st = store.wal_append(st, growth)
            if s_on:
                # Periodic snapshot marker: persist applied state, truncate
                # the journals (cells billed via DuraState.snap_rows).
                st = jax.lax.cond(
                    ops["snap"],
                    lambda s: store.snapshot(s)[0],
                    lambda s: s,
                    st,
                )
        # -- telemetry --------------------------------------------------
        with jax.named_scope("telemetry"):
            is_read = ops["kind"] == duot_lib.READ
            if telemetry:
                c = ops["client"]
                z = jnp.zeros((n_clients,), jnp.int32)
                ys["tel"] = (
                    z.at[c].add(res.stale.astype(jnp.int32)),
                    z.at[c].add(res.violation.astype(jnp.int32)),
                    z.at[c].add(is_read.astype(jnp.int32)),
                    z.at[c].add(jnp.logical_not(is_read).astype(jnp.int32)),
                )
            carry = {
                **carry,
                "st": st,
                "stale": carry["stale"] + jnp.sum(res.stale.astype(jnp.int32)),
                "viol": carry["viol"]
                + jnp.sum(res.violation.astype(jnp.int32)),
                "reads": carry["reads"] + jnp.sum(is_read.astype(jnp.int32)),
            }
            if geo_on:
                creg = client_reg[ops["client"]]
                hreg = replica_reg[home]
                zi = jnp.zeros((G,), jnp.int32)
                zf = jnp.zeros((G,), jnp.float32)
                reg = carry["reg"]
                carry = {**carry, "reg": (
                    reg[0] + zi.at[creg].add(res.stale.astype(jnp.int32)),
                    reg[1] + zi.at[creg].add(is_read.astype(jnp.int32)),
                    reg[2] + zf.at[creg].add(rtt[creg, hreg]),
                    reg[3] + zi.at[creg].add(1),
                )}
        # -- observability plane ----------------------------------------
        if o_on:
            with jax.named_scope("obs"):
                # Staleness age = the resource's post-merge write frontier
                # minus the version actually served — the distribution
                # whose upper tail the timed levels bound with Δ.  The same
                # ages masked to audit-flagged reads are the violation
                # severities.
                ob = carry["obs"]
                age = jnp.maximum(
                    st.cluster.global_version[ops["resource"]] - res.version,
                    0,
                ).astype(jnp.float32)
                rows = [age, age]
                row_mask = [is_read, res.violation]
                if geo_on:
                    rows.append(rtt[creg, hreg])
                    row_mask.append(is_read)
                part = kernel_ops.histogram(
                    jnp.stack(rows),
                    lo=ob_lo, hi=ob_hi, n_bins=obs.n_bins,
                    mask=jnp.stack(
                        [m.astype(jnp.int32) for m in row_mask]
                    ),
                    impl=obs.impl,
                )
                hist = ob["hist"].at[:n_op_metrics].add(part)
                if h_on:
                    hist = hist.at[n_op_metrics].add(kernel_ops.histogram(
                        st.hints.count.astype(jnp.float32),
                        lo=0.0, hi=obs.depth_hi, n_bins=obs.n_bins,
                        impl=obs.impl,
                    ))
                e_stale = jnp.sum(res.stale.astype(jnp.int32))
                e_viol = jnp.sum(res.violation.astype(jnp.int32))
                c0 = ob["counters"]
                n_reads = jnp.sum(is_read.astype(jnp.int32))
                carry = {**carry, "obs": {"hist": hist, "counters": {
                    "ops": c0["ops"] + jnp.int32(width),
                    "reads": c0["reads"] + n_reads,
                    "writes": c0["writes"] + jnp.int32(width) - n_reads,
                    "stale": c0["stale"] + e_stale,
                    "viol": c0["viol"] + e_viol,
                    "epochs": c0["epochs"] + 1,
                }}}
                ys["obs"] = (e_stale, e_viol)
        return carry, (ys or None)

    has_ys = gx_on or telemetry or o_on

    @jax.jit
    def run(batched, tail):
        z = jnp.int32(0)
        carry = {"st": store.init(), "stale": z, "viol": z, "reads": z}
        if faults_on:
            carry.update(ae=z, prop=z, fail=z)
        if geo_on:
            zg = lambda dt: jnp.zeros((G,), dt)               # noqa: E731
            carry["traffic"] = jnp.zeros((G, G), jnp.int32)
            carry["reg"] = (
                zg(jnp.int32), zg(jnp.int32), zg(jnp.float32),
                zg(jnp.int32),
            )
        if gx_on:
            carry["gx"] = {"deliv": z, "ranges": z, "pairs": z, "gap": z}
            if h_on:
                carry["gx"].update(
                    h_enq=z, h_drop=z, h_deliv=jnp.zeros((P,), jnp.int32)
                )
        if ggx_on:
            zgg = jnp.zeros((G, G), jnp.int32)
            carry["ggx"] = {"traffic": zgg, "digest": zgg,
                            "ranges": z, "gap": z}
        if rx_on:
            carry["rx"] = {
                "crashes": z, "wal_replayed": z, "rows_lost": z,
                "snap_read": z, "boot_cells": z, "boot_pend": z,
                "boot_events": z,
            }
        if o_on:
            carry["obs"] = {
                "hist": jnp.zeros((n_metrics, obs.n_bins), jnp.int32),
                "counters": {k: z for k in obs_lib.COUNTERS},
            }
        n_rounds = batched["client"].shape[0]

        def step(carry, ops):
            return round_step(carry, ops, ops["step0"], sub)

        carry, per_round = jax.lax.scan(step, carry, batched)
        if rem:
            carry, _ = round_step(
                carry, tail, jnp.int32(n_rounds * sub), rem
            )
        return (carry, per_round) if has_ys else carry

    def counted_run(batched, tail):
        _JIT_ENTRIES[0] += 1
        return run(batched, tail)

    counted_run.jitted = run
    return store, counted_run


@functools.lru_cache(maxsize=None)
def shard_runner(run, devices: tuple):
    """(jitted fn, input sharding): ``run`` once per device over a
    leading shard axis of size ``len(devices)``.

    A ``shard_map`` rather than a GSPMD-partitioned ``vmap``: the
    compiler never has to partition the Pallas calls inside ``run``,
    and each device holds only its own shard's state.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("shard",))
    spec = PartitionSpec("shard")

    def local(batched, tail):
        out = run(*jax.tree.map(lambda x: x[0], (batched, tail)))
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False,
    ))
    return fn, NamedSharding(mesh, spec)


def _nbytes(*trees) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(trees))


class EpochEngine:
    """One workload replay, device-resident end to end.

    ``EpochEngine(config).replay(w)`` prepares the op stream, the
    cadence plan, and the per-round mask inputs on the host once, then
    hands the whole run to the cached jitted scan — a single host→device
    round trip per replay (per shard stack when ``n_shards > 1``).
    Result assembly into the legacy dictionaries lives in
    :mod:`repro.engine.results`.
    """

    def __init__(self, config: EngineConfig):
        self.config = config

    # -- host-side preparation -------------------------------------------

    def plan(self):
        c = self.config
        return stream_lib.cadence_plan(
            c.level, c.shard_ops, c.batch_size, c.merge_every, c.delta
        )

    def _anchored_schedule(self, n_rounds: int, rem: int, sub: int):
        """The fault schedule re-anchored onto this level's rounds."""
        c = self.config
        schedule = c.faults
        if schedule is None:
            return None
        if c.schedule_unit:
            # Crash *events* fire once: only the first round mapped to a
            # schedule epoch inherits its crash flags (coarser levels
            # can map several rounds to one epoch).
            starts = np.arange(n_rounds + (1 if rem else 0)) * sub
            idx = np.minimum(
                starts // c.schedule_unit, schedule.n_epochs - 1
            )
            first = np.zeros(idx.shape, bool)
            first[0] = True
            first[1:] = idx[1:] != idx[:-1]
            schedule = avail_lib.FaultSchedule(
                schedule.up[idx], schedule.link[idx],
                crash=schedule.crashes()[idx] & first[:, None],
            )
        return schedule

    def runner(self, w) -> tuple[ReplicatedStore, Any]:
        c = self.config
        sub, rem, _, emulate = self.plan()
        # The all-up drivers (flat/geo/sharded) model durability
        # host-side; only the fault path journals device-side.
        d_on = (
            c.durability is not None and c.durability.enabled
            and c.faults is not None
        )
        crashes = c.faults is not None and c.faults.has_crashes
        return unified_runner(
            c.level, c.shard_clients, c.shard_resources, c.merge_every,
            c.delta, c.duot_cap, sub, rem, emulate,
            c.resolved_pending_cap(w.read_fraction), c.ingest, c.lean,
            c.topology, c.gossip, c.durability if d_on else None,
            crashes, c.faults is not None, False, c.obs,
        )

    def _masks(self, store, n_rounds: int, rem: int, sub: int):
        """(schedule, per-round masks, tail masks, faulty epochs) of the
        fault schedule and the gossip cadence."""
        c = self.config
        n_epochs_total = n_rounds + (1 if rem else 0)
        crashes = c.faults is not None and c.faults.has_crashes
        schedule = faulty_full = None
        if c.faults is not None:
            schedule = self._anchored_schedule(n_rounds, rem, sub)
            schedule, masks, tail_masks = stream_lib.fault_epoch_inputs(
                schedule, n_rounds, rem, crashes
            )
            faulty_full = np.concatenate([
                masks["faulty"],
                np.asarray([tail_masks["faulty"]]) if rem
                else np.zeros(0, bool),
            ])
            if c.gossip is not None:
                g_active, g_pairs = gossip_pairs(3, n_epochs_total, c.gossip)
                masks["gossip"] = g_active[:n_rounds]
                masks["pairs"] = g_pairs[:n_rounds]
                tail_masks["gossip"] = g_active[n_epochs_total - 1]
                tail_masks["pairs"] = g_pairs[n_epochs_total - 1]
            if c.durability is not None and c.durability.snapshot_every > 0:
                se = c.durability.snapshot_every
                snap = (np.arange(n_epochs_total) + 1) % se == 0
                masks["snap"] = snap[:n_rounds]
                tail_masks["snap"] = snap[n_epochs_total - 1]
        elif c.gossip is not None and c.gossip.enabled:
            # Geo flavor: scheduled pairs only, no fault masks.
            masks, tail_masks = {}, {}
            g_active, g_pairs = gossip_pairs(
                store.n_replicas, n_epochs_total, c.gossip,
                c.topology if c.gossip.peer == "nearest" else None,
            )
            masks["gossip"] = np.asarray(g_active[:n_rounds])
            masks["pairs"] = np.asarray(g_pairs[:n_rounds])
            tail_masks["gossip"] = np.asarray(g_active[n_epochs_total - 1])
            tail_masks["pairs"] = np.asarray(g_pairs[n_epochs_total - 1])
        return schedule, masks, tail_masks, faulty_full

    def prepare(self, w) -> dict[str, Any]:
        """Host-side inputs of one replay: streams, masks, schedule."""
        c = self.config
        with trace_lib.span("engine.prepare", ops=c.n_ops):
            return self._prepare(w)

    def _prepare(self, w) -> dict[str, Any]:
        c = self.config
        sub, rem, n_rounds, emulate = self.plan()
        store, run = self.runner(w)

        schedule = masks = tail_masks = faulty_full = None
        if c.faults is not None or (c.gossip is not None
                                    and c.gossip.enabled):
            with trace_lib.span("engine.prepare.masks"):
                schedule, masks, tail_masks, faulty_full = self._masks(
                    store, n_rounds, rem, sub
                )

        streams, batched_shards, tail_shards = [], [], []
        schedule_counts = []
        for s in range(c.n_shards):
            with trace_lib.span(
                "engine.prepare.stream", shard=s, ops=c.shard_ops
            ):
                stream = stream_lib.op_stream(
                    w, c.shard_ops, c.shard_clients, c.shard_resources,
                    c.seed + s, store.n_replicas,
                )
            streams.append(stream)
            if c.faults is not None and emulate:
                # The faulty flavor builds its apply schedule by hand:
                # synchronous levels defer to the masked merge under
                # faults, and every level clamps faulty epochs.
                with trace_lib.span(
                    "engine.prepare.batch", shard=s, ops=c.shard_ops
                ):
                    batched = {
                        k: stream[k][: n_rounds * sub].reshape(n_rounds, sub)
                        for k in stream_lib.OP_COLS
                    }
                    batched["step0"] = (
                        np.arange(n_rounds, dtype=np.int32) * sub
                    )
                    tail = {
                        k: stream[k][-max(rem, 1):]
                        for k in stream_lib.OP_COLS
                    }
                counts = None
                with trace_lib.span("engine.prepare.schedule", shard=s):
                    if store.sync_every > 1:
                        sched = store.schedule_stream(
                            stream["client"], stream["home"], stream["kind"],
                            counters=True,
                        )
                        apply_idx = np.asarray(sched.apply)
                        counts = sched.counts()
                    else:
                        apply_idx = np.zeros(c.shard_ops, np.int32)
                    apply_idx = stream_lib.clamp_apply_idx(
                        apply_idx, faulty_full, sub, c.shard_ops
                    )
                batched["apply_idx"] = apply_idx[
                    : n_rounds * sub
                ].reshape(n_rounds, sub)
                tail["apply_idx"] = apply_idx[-max(rem, 1):]
            else:
                batched, tail, counts = stream_lib.batch_inputs(
                    stream, store, sub, n_rounds, rem, emulate, shard=s
                )
            schedule_counts.append(counts)
            if masks is not None:
                batched = {**batched, **masks}
                tail = {**tail, **tail_masks}
            batched_shards.append(batched)
            tail_shards.append(tail)

        return {
            "store": store, "run": run, "schedule": schedule,
            "masks": masks, "tail_masks": tail_masks,
            "streams": streams, "batched": batched_shards,
            "tails": tail_shards, "schedule_counts": schedule_counts,
            "sub": sub, "rem": rem,
            "n_rounds": n_rounds, "emulate": emulate,
        }

    # -- replay -----------------------------------------------------------

    def _clock_impl(self, prep) -> str:
        """The clock chain the round step runs: the ``vclock_chain``
        kernel (``"pallas"``), the ``lax.scan`` (``"scan"``), or none
        (``"off"``: the lean cadence path skips it)."""
        c = self.config
        if c.lean and prep["emulate"]:
            return "off"
        return vclock_chain.resolve_impl(c.shard_clients,
                                         prep["store"].n_replicas)

    def replay(self, w) -> dict[str, Any]:
        """Run the whole workload through the device-resident scan.

        Returns the :meth:`prepare` dict extended with ``out`` — the
        final carry (stacked along a leading shard axis when
        ``n_shards > 1``) — ``per_round`` telemetry when the compiled
        configuration emits it, and ``layout``: how the shards were
        placed (``"shard_map"`` over one device each when the host has
        ``n_shards`` devices and ``use_devices`` is on, else ``"vmap"``
        on one device, or ``"single"``) and on how many devices.

        The launch is asynchronous: ``out`` is ready once the device has
        run the scan, which the first host read of it waits for.
        """
        c = self.config
        replay_id = trace_lib.next_replay_id()
        with trace_lib.span(
            "engine.replay", replay=replay_id, level=c.level.name,
            tenants=c.n_shards, ops=c.n_ops,
        ) as sp:
            prep = self.prepare(w)
            sp.set(rounds=prep["n_rounds"] + (1 if prep["rem"] else 0))
            run = prep["run"]
            stack = lambda dicts: {                           # noqa: E731
                k: jnp.asarray(np.stack([np.asarray(d[k]) for d in dicts]))
                for k in dicts[0]
            }
            per_round = None
            if c.n_shards > 1:
                devices = jax.devices()
                shard_map = c.use_devices and len(devices) >= c.n_shards
                with trace_lib.span("engine.place") as place:
                    batched = stack(prep["batched"])
                    tail = stack(prep["tails"])
                    if shard_map:
                        # One tenant shard per device.  The shards share
                        # no state, so each device runs the unbatched
                        # replay on its own slice: no cross-device
                        # traffic in the scan.
                        devs = tuple(devices[: c.n_shards])
                        fn, sharding = shard_runner(run.jitted, devs)
                        put = functools.partial(
                            jax.device_put, device=sharding
                        )
                        batched = jax.tree.map(put, batched)
                        tail = jax.tree.map(put, tail)
                    if place:
                        place.set(bytes=_nbytes(batched, tail))
                j0 = jit_entries()
                with trace_lib.span(
                    "engine.launch", count_compiles=True
                ) as launch:
                    _JIT_ENTRIES[0] += 1
                    if shard_map:
                        out = fn(batched, tail)
                        layout = {"mode": "shard_map", "devices": len(devs)}
                    else:
                        out = jax.vmap(run.jitted)(batched, tail)
                        layout = {"mode": "vmap", "devices": 1}
                    launch.set(jit_entries=jit_entries() - j0)
                    if launch:
                        launch.set(clock_impl=self._clock_impl(prep))
            else:
                with trace_lib.span("engine.place") as place:
                    b = {k: jnp.asarray(v)
                         for k, v in prep["batched"][0].items()}
                    t = {k: jnp.asarray(v)
                         for k, v in prep["tails"][0].items()}
                    if place:
                        place.set(bytes=_nbytes(b, t))
                j0 = jit_entries()
                with trace_lib.span(
                    "engine.launch", count_compiles=True
                ) as launch:
                    out = run(b, t)
                    launch.set(jit_entries=jit_entries() - j0)
                    if launch:
                        launch.set(clock_impl=self._clock_impl(prep))
                layout = {"mode": "single", "devices": 1}
        if isinstance(out, tuple):
            out, per_round = out
        prep["out"] = out
        prep["per_round"] = per_round
        prep["layout"] = layout
        prep["replay_id"] = replay_id
        return prep

    def run(self, w) -> dict[str, Any]:
        """Replay + legacy result assembly (see ``repro.engine.results``)."""
        from repro.engine import results

        return results.assemble(self, self.replay(w), w)


def session_telemetry_runner(
    level: ConsistencyLevel,
    n_clients: int,
    n_resources: int,
    merge_every: int,
    delta: int,
    sub: int,
    emulate: bool,
) -> tuple[ReplicatedStore, Any]:
    """(store, jitted engine) emitting per-client counts per sub-batch.

    The adaptive control plane's telemetry feed: the same unified round
    step in ``telemetry`` mode — per-client segment sums ride the scan's
    ys, the DUOT is skipped, and the policy controller's scoring scan
    consumes the output device-side.  Requires the stream to tile
    exactly (no tail round).
    """
    store, run = unified_runner(
        level, n_clients, n_resources, merge_every, delta, 64, sub, 0,
        emulate, max(128, 2 * sub), "auto", False, None, None, None,
        False, False, True,
    )

    def run_telemetry(batched):
        _, ys = run.jitted(
            batched,
            {k: v[0] for k, v in batched.items()},  # unused dummy tail
        )
        return ys["tel"]

    return store, run_telemetry

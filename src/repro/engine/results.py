"""Legacy result-dictionary assembly for the unified epoch engine.

Each ``assemble_*`` function turns one :meth:`EpochEngine.replay` output
into the exact dictionary its pre-unification driver returned — same
keys, same float arithmetic, same ordering of the billing terms — so
the legacy ``run_protocol_*`` wrappers stay bit-identical through the
refactor (gated by ``tests/test_engine_bridge.py`` against captured
golden traces).  :func:`assemble` dispatches on the config: faults ⇒
the failure-path dict (plus a ``"geo"`` block when a topology is
composed in — a combination no legacy driver offered), topology ⇒ the
region-aware dict, shards ⇒ the multi-tenant dict, else the flat
metrics dict.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model
from repro.engine.config import EngineConfig
from repro.gossip import DIGEST_BYTES
from repro.obs import trace as trace_lib
from repro.storage.cluster import PAPER_CLUSTER, ClusterConfig
from repro.storage.ycsb import Workload


def _severity(config: EngineConfig, store, st) -> float:
    if not config.audit:
        return 0.0
    if config.n_shards > 1:
        # The stacked logs may span devices (the shard_map layout), and
        # a Pallas audit cannot be partitioned: fetch the small logs
        # once and audit each shard's on the default device.
        logs = jax.device_get(st.duot)
        sev = []
        for s in range(config.n_shards):
            shard_st = st._replace(
                duot=jax.tree.map(lambda x, i=s: jnp.asarray(x[i]), logs)
            )
            with trace_lib.span(
                "engine.assemble.audit", shard=s, count_compiles=True
            ):
                sev.append(float(
                    store.audit(shard_st, delta=store.delta or 0).severity
                ))
        return float(np.mean(sev))
    with trace_lib.span(
        "engine.assemble.audit", shard=0, count_compiles=True
    ):
        return float(store.audit(st, delta=store.delta or 0).severity)


def assemble_flat(config: EngineConfig, prep: dict) -> dict[str, float]:
    out = prep["out"]
    st = out["st"]
    with trace_lib.span("engine.assemble.fetch"):
        n_reads_f = max(1, int(out["reads"]))
    return {
        "staleness_rate": float(out["stale"]) / n_reads_f,
        "violation_rate": float(out["viol"]) / n_reads_f,
        "severity": _severity(config, prep["store"], st),
        "n_reads": int(out["reads"]),
        "dropped_writes": int(st.cluster.pend_dropped),
    }


def assemble_sharded(config: EngineConfig, prep: dict) -> dict[str, float]:
    out = prep["out"]
    st = out["st"]
    with trace_lib.span("engine.assemble.fetch"):
        n_reads_total = int(jnp.sum(out["reads"]))
    return {
        "staleness_rate": float(jnp.sum(out["stale"]))
        / max(1, n_reads_total),
        "violation_rate": float(jnp.sum(out["viol"]))
        / max(1, n_reads_total),
        "severity": _severity(config, prep["store"], st),
        "n_reads": n_reads_total,
        "dropped_writes": int(jnp.sum(st.cluster.pend_dropped)),
        "n_shards": config.n_shards,
        "per_shard": {
            "stale": np.asarray(out["stale"]).reshape(-1).tolist(),
            "viol": np.asarray(out["viol"]).reshape(-1).tolist(),
            "reads": np.asarray(out["reads"]).reshape(-1).tolist(),
        },
    }


def assemble_geo(
    config: EngineConfig,
    prep: dict,
    w: Workload,
    cfg: ClusterConfig = PAPER_CLUSTER,
    pricing: cost_model.PricingScheme = cost_model.PAPER_PRICING,
) -> dict[str, Any]:
    from repro.storage.simulator import throughput_model

    out = prep["out"]
    topology = config.topology
    gossip = config.gossip
    recovery = config.durability
    g_on = gossip is not None and gossip.enabled
    st = out["st"]
    with trace_lib.span("engine.assemble.fetch"):
        n_reads = int(out["reads"])
    n_reads_f = max(1, n_reads)
    severity = _severity(config, prep["store"], st)
    stale_rate = float(out["stale"]) / n_reads_f
    n_ops = config.n_ops

    # -- region-pair billing (eq. 8 over the measured traffic matrix) ----
    events = np.asarray(out["traffic"], np.int64)
    prop_gb = events * cfg.row_bytes / 1e9
    off = ~np.eye(topology.n_regions, dtype=bool)
    inter_gb = float(prop_gb[off].sum())
    intra_gb = float(np.diag(prop_gb).sum())
    # One pricebook per run: a topology that pins a custom egress
    # matrix wins, but the default paper-derived matrix follows a
    # ``pricing`` override so the geo and scalar bills (and the
    # instance/storage terms) never mix providers.
    egress = topology.egress
    if egress == cost_model.EgressMatrix.from_pricing(
        topology.n_regions, cost_model.PAPER_PRICING
    ):
        egress = cost_model.EgressMatrix.from_pricing(
            topology.n_regions, pricing
        )
    network_geo = cost_model.cost_network_matrix(
        traffic_gb=prop_gb, egress=egress
    )
    network_scalar = cost_model.cost_network(
        inter_dc_gb=inter_gb, intra_dc_gb=intra_gb, pricing=pricing
    )
    thr, _ = throughput_model(config.level, w, 64, cfg, stale_rate)
    runtime_s = n_ops / thr
    bill = cost_model.cost_all(
        nb_instances=cfg.n_nodes,
        runtime_hours=runtime_s / 3600.0,
        hosted_gb=cfg.total_data_gb_after_replication,
        months=runtime_s / (30 * 24 * 3600.0),
        io_requests=float(n_ops)
        * config.level.write_acks(cfg.replication_factor),
        inter_dc_gb=inter_gb,
        intra_dc_gb=intra_gb,
        pricing=pricing,
    )
    cost = bill.as_dict()
    cost["network_geo"] = network_geo
    cost["network_scalar"] = network_scalar
    cost["total_geo"] = cost["instances"] + cost["storage"] + network_geo

    gossip_info = None
    if g_on:
        ggx = out["ggx"]
        g_traffic = np.asarray(ggx["traffic"])
        g_digest = np.asarray(ggx["digest"])
        k_eff = max(1, min(gossip.n_ranges, config.n_resources))
        repair_mat_gb = g_traffic.astype(np.float64) * cfg.row_bytes / 1e9
        digest_mat_gb = (
            g_digest.astype(np.float64) * k_eff * DIGEST_BYTES / 1e9
        )
        gossip_network_geo = cost_model.cost_network_matrix(
            traffic_gb=repair_mat_gb + digest_mat_gb, egress=egress
        )
        cost["gossip_network_geo"] = gossip_network_geo
        cost["total_geo"] += gossip_network_geo
        gossip_info = {
            "cadence": gossip.cadence,
            "repair_events": g_traffic.tolist(),
            "repair_gb": float(repair_mat_gb.sum()),
            "digest_gb": float(digest_mat_gb.sum()),
            "ranges_diffed": int(ggx["ranges"]),
            "gap_repaired": int(ggx["gap"]),
            "peer": gossip.peer,
        }

    durability_info = None
    if recovery is not None and recovery.enabled:
        # Steady-state durable-I/O model (all-up driver, host-side
        # only): every write applies at all P replicas, snapshots
        # persist the inter-marker working set capped at the key count.
        n_epochs_total = prep["n_rounds"] + (1 if prep["rem"] else 0)
        se = recovery.snapshot_every
        n_snaps = n_epochs_total // se if se > 0 else 0
        n_writes = int((prep["streams"][0]["kind"] == 1).sum())
        wal_records_pp = n_writes if recovery.wal else 0
        per_snap = (
            min(config.n_resources, -(-n_writes // n_snaps))
            if n_snaps else 0
        )
        snap_cells_pp = per_snap * n_snaps
        per_region = np.bincount(
            topology.regions(), minlength=topology.n_regions
        )
        dur_mat_gb = np.diag(
            (snap_cells_pp + wal_records_pp) * per_region
            * cfg.row_bytes / 1e9
        )
        durability_network_geo = cost_model.cost_network_matrix(
            traffic_gb=dur_mat_gb, egress=egress
        )
        cost["durability_network_geo"] = durability_network_geo
        cost["total_geo"] += durability_network_geo
        cost["durability_storage"] = cost_model.cost_storage(
            hosted_gb=3 * config.n_resources * cfg.row_bytes / 1e9,
            months=runtime_s / (30 * 24 * 3600.0),
            io_requests=float(
                (snap_cells_pp + wal_records_pp) * topology.n_replicas
            ),
            pricing=pricing,
        )
        durability_info = {
            "snapshot_every": se,
            "wal": recovery.wal,
            "snapshots": n_snaps,
            "snapshot_cells": snap_cells_pp * topology.n_replicas,
            "wal_records": wal_records_pp * topology.n_replicas,
            "durable_gb": float(dur_mat_gb.sum()),
            "durable_gb_by_region": np.diag(dur_mat_gb).tolist(),
        }

    reg_stale, reg_reads, reg_lat, reg_ops = (
        np.asarray(x) for x in out["reg"]
    )
    result = {
        "staleness_rate": stale_rate,
        "violation_rate": float(out["viol"]) / n_reads_f,
        "severity": severity,
        "n_reads": n_reads,
        "dropped_writes": int(st.cluster.pend_dropped),
        "n_regions": topology.n_regions,
        "traffic_events": events.tolist(),
        "propagation_gb": prop_gb.tolist(),
        "mean_latency_ms": float(reg_lat.sum() / max(1, reg_ops.sum())),
        "per_region": {
            "reads": reg_reads.tolist(),
            "stale": reg_stale.tolist(),
            "ops": reg_ops.tolist(),
            "staleness_rate": (
                reg_stale / np.maximum(1, reg_reads)
            ).tolist(),
            "mean_latency_ms": (
                reg_lat / np.maximum(1, reg_ops)
            ).tolist(),
        },
        "cost": cost,
    }
    if gossip_info is not None:
        result["gossip"] = gossip_info
    if durability_info is not None:
        result["durability"] = durability_info
    return result


def _geo_block(
    config: EngineConfig, out: dict, cfg: ClusterConfig,
    sharded: bool,
) -> dict[str, Any]:
    """Region attribution of a composed geo+faults run (engine-only)."""
    topology = config.topology
    traffic = out["traffic"]
    reg = out["reg"]
    if sharded:
        traffic = jnp.sum(traffic, axis=0)
        reg = tuple(jnp.sum(x, axis=0) for x in reg)
    events = np.asarray(traffic, np.int64)
    prop_gb = events * cfg.row_bytes / 1e9
    reg_stale, reg_reads, reg_lat, reg_ops = (np.asarray(x) for x in reg)
    return {
        "n_regions": topology.n_regions,
        "traffic_events": events.tolist(),
        "propagation_gb": prop_gb.tolist(),
        "network_geo": cost_model.cost_network_matrix(
            traffic_gb=prop_gb, egress=topology.egress
        ),
        "mean_latency_ms": float(reg_lat.sum() / max(1, reg_ops.sum())),
        "per_region": {
            "reads": reg_reads.tolist(),
            "stale": reg_stale.tolist(),
            "ops": reg_ops.tolist(),
            "staleness_rate": (
                reg_stale / np.maximum(1, reg_reads)
            ).tolist(),
            "mean_latency_ms": (
                reg_lat / np.maximum(1, reg_ops)
            ).tolist(),
        },
    }


def assemble_faulty(
    config: EngineConfig,
    prep: dict,
    w: Workload,
    cfg: ClusterConfig = PAPER_CLUSTER,
    pricing: cost_model.PricingScheme = cost_model.PAPER_PRICING,
    _return_state: bool = False,
) -> dict[str, Any]:
    from repro.storage.simulator import throughput_model, traffic_gb

    out = prep["out"]
    store = prep["store"]
    schedule = prep["schedule"]
    gossip = config.gossip
    recovery = config.durability
    n_shards = config.n_shards
    sharded = n_shards > 1
    d_on = recovery is not None and recovery.enabled
    crashes = config.faults.has_crashes
    rx_on = d_on or crashes
    n_ops = config.n_ops
    s_resources = config.shard_resources
    rem = prep["rem"]

    def total(x) -> int:
        return int(jnp.sum(x)) if sharded else int(x)

    st = out["st"]
    with trace_lib.span("engine.assemble.fetch"):
        n_stale, n_viol, n_reads = (
            total(out["stale"]), total(out["viol"]), total(out["reads"])
        )
    ae_ev, prop_ev, n_fail = (
        total(out["ae"]), total(out["prop"]), total(out["fail"])
    )
    dropped = (
        int(jnp.sum(st.cluster.pend_dropped)) if sharded
        else int(st.cluster.pend_dropped)
    )
    gx = rx = per_round = None
    if gossip is not None:
        gd = out["gx"]
        z3 = np.zeros((3,), np.int64)
        h_deliv_vec = gd.get("h_deliv")
        if h_deliv_vec is None:
            h_deliv_vec = z3
        elif sharded:
            h_deliv_vec = np.asarray(jnp.sum(h_deliv_vec, axis=0))
        else:
            h_deliv_vec = np.asarray(h_deliv_vec)
        gx = (
            total(gd["deliv"]), total(gd["ranges"]), total(gd["pairs"]),
            total(gd["gap"]),
            total(gd["h_enq"]) if "h_enq" in gd else 0,
            total(gd["h_drop"]) if "h_drop" in gd else 0,
            h_deliv_vec,
        )
        pr = prep["per_round"]["gossip"]
        if sharded:
            per_round = tuple(np.asarray(jnp.sum(x, axis=0)) for x in pr)
        else:
            per_round = tuple(np.asarray(x) for x in pr)
    if rx_on:
        rxd = out["rx"]
        rx = tuple(total(rxd[k]) for k in (
            "crashes", "wal_replayed", "rows_lost", "snap_read",
            "boot_cells", "boot_pend", "boot_events",
        ))

    severity = _severity(config, store, st)
    stale_rate = n_stale / max(1, n_reads)
    viol_rate = n_viol / max(1, n_reads)

    # -- eq. 8: the measured failure-path traffic joins the bill ---------
    row = cfg.row_bytes
    anti_entropy_gb = ae_ev * row / 1e9
    propagation_gb = prop_ev * row / 1e9
    gossip_gb = 0.0
    if gossip is not None:
        (g_deliv, g_ranges, g_pair_n, g_gap, h_enq, h_drop,
         h_deliv_vec) = gx
        h_deliv = int(h_deliv_vec.sum())
        k_eff = max(1, min(gossip.n_ranges, s_resources))
        digest_gb = g_pair_n * 2 * k_eff * DIGEST_BYTES / 1e9
        repair_gb = (g_deliv + h_deliv) * row / 1e9
        gossip_gb = digest_gb + repair_gb
    # -- durability + crash recovery (eq. 8's storage/network split) -----
    snapshot_gb = wal_gb = replay_gb = bootstrap_gb = 0.0
    recovery_info = None
    if rx_on:
        (crash_n, wal_rep, rows_lost, snap_read,
         boot_cells, boot_pend, boot_events) = rx
        snap_rows = int(jnp.sum(st.dura.snap_rows)) if d_on else 0
        wal_total = int(jnp.sum(st.dura.wal_total)) if d_on else 0
        bk = max(1, min(
            recovery.bootstrap_ranges if recovery is not None else 8,
            s_resources,
        ))
        snapshot_gb = snap_rows * row / 1e9
        wal_gb = wal_total * row / 1e9
        replay_gb = (wal_rep + snap_read) * row / 1e9
        bootstrap_gb = (
            (boot_cells + boot_pend) * row
            + boot_events * 2 * bk * DIGEST_BYTES
        ) / 1e9
        recovery_info = {
            "crashes": crash_n,
            "rejoins": boot_events,
            "rows_lost": rows_lost,
            "wal_replayed": wal_rep,
            "snapshot_cells_read": snap_read,
            "snapshot_cells": snap_rows,
            "wal_records": wal_total,
            "bootstrap_cells": boot_cells,
            "bootstrap_pending": boot_pend,
            "snapshot_gb": snapshot_gb,
            "wal_gb": wal_gb,
            "replay_gb": replay_gb,
            "bootstrap_gb": bootstrap_gb,
            # Crash-triggered traffic only (zero unless a crash fired).
            "recovery_gb": bootstrap_gb + replay_gb,
        }
    thr, _ = throughput_model(config.level, w, 64, cfg, stale_rate)
    runtime_s = n_ops / thr
    inter_gb, intra_gb = traffic_gb(config.level, w, n_ops, cfg, stale_rate)
    bill = cost_model.cost_all(
        nb_instances=cfg.n_nodes,
        runtime_hours=runtime_s / 3600.0,
        hosted_gb=cfg.total_data_gb_after_replication,
        months=runtime_s / (30 * 24 * 3600.0),
        io_requests=float(n_ops)
        * config.level.write_acks(cfg.replication_factor),
        inter_dc_gb=inter_gb + anti_entropy_gb + gossip_gb + bootstrap_gb,
        intra_dc_gb=intra_gb + snapshot_gb + wal_gb + replay_gb,
        pricing=pricing,
    )
    cost = bill.as_dict()
    cost["anti_entropy_network"] = cost_model.cost_network(
        inter_dc_gb=anti_entropy_gb, intra_dc_gb=0.0, pricing=pricing
    )
    if rx_on:
        # The durable-media side of eq. 8: snapshot copies hosted for
        # the run plus every marker/journal/restore I/O event.
        cost["durability_storage"] = cost_model.cost_storage(
            hosted_gb=(
                (3 * s_resources * row / 1e9) * n_shards if d_on else 0.0
            ),
            months=runtime_s / (30 * 24 * 3600.0),
            io_requests=float(
                snap_rows + wal_total + wal_rep + snap_read
            ) if d_on else float(0),
            pricing=pricing,
        )
        cost["durability_network"] = cost_model.cost_network(
            inter_dc_gb=bootstrap_gb,
            intra_dc_gb=snapshot_gb + wal_gb + replay_gb,
            pricing=pricing,
        )
    result: dict[str, Any] = {
        "staleness_rate": stale_rate,
        "violation_rate": viol_rate,
        "severity": severity,
        "n_reads": n_reads,
        "dropped_writes": dropped,
        "failovers": n_fail,
        "anti_entropy_events": ae_ev,
        "propagation_events": prop_ev,
        "anti_entropy_gb": anti_entropy_gb,
        "propagation_gb": propagation_gb,
        "n_epochs": schedule.n_epochs,
        "faulty_epochs": int(schedule.faulty().sum()),
        "heal_epochs": int(schedule.heals().sum()),
        "n_shards": n_shards,
        "cost": cost,
    }
    if gossip is not None:
        cost["gossip_network"] = cost_model.cost_network(
            inter_dc_gb=gossip_gb, intra_dc_gb=0.0, pricing=pricing
        )
        pr_deliv, pr_ranges, pr_gap = per_round
        result["gossip"] = {
            "cadence": gossip.cadence,
            "rounds": int(np.asarray(prep["masks"]["gossip"]).sum())
            + (int(bool(prep["tail_masks"]["gossip"])) if rem else 0),
            "pairs_exchanged": g_pair_n,
            "ranges_diffed": g_ranges,
            "repair_events": g_deliv + h_deliv,
            "gap_repaired": g_gap,
            "digest_gb": digest_gb,
            "repair_gb": repair_gb,
            "hints": {
                "enqueued": h_enq,
                "dropped": h_drop,
                "delivered": h_deliv,
                "delivered_by_replica": h_deliv_vec.tolist(),
            },
            "per_round": {
                "deliveries": pr_deliv.tolist(),
                "ranges_diffed": pr_ranges.tolist(),
                "gap_repaired": pr_gap.tolist(),
            },
        }
    if recovery_info is not None:
        result["crash_epochs"] = np.flatnonzero(
            schedule.crashes().any(axis=1)
        ).tolist()
        result["recovery"] = recovery_info
    if config.topology is not None:
        result["geo"] = _geo_block(config, out, cfg, sharded)
    if _return_state:
        # Final engine state for convergence checks (chaos harness);
        # underscore keys so dict-equality gates never see them.
        result["_state"] = st
        result["_store"] = store
    return result


def _obs_block(config: EngineConfig, prep: dict) -> dict[str, Any]:
    """Summarize the final obs carry into the result's ``"obs"`` block.

    Rebuilds the same static metric registry the compiled replay
    recorded into (row order is a pure function of the config), then
    renders histograms + percentile tables host-side.  Sharded runs sum
    the per-shard histogram/counter stacks — integer counts, so the
    fold is exact.  The per-round stale/violation series covers the
    full scan rounds (the tail round's ys are discarded, matching the
    gossip per-round telemetry).
    """
    from repro.obs import metrics as obs_lib

    obs = config.obs
    out = prep["out"]
    sharded = config.n_shards > 1
    hist = np.asarray(out["obs"]["hist"])
    counters = {
        k: int(jnp.sum(v)) if sharded else int(v)
        for k, v in out["obs"]["counters"].items()
    }
    if sharded:
        hist = hist.sum(axis=0)
    h_on = (
        config.gossip is not None and config.gossip.handoff
        and config.faults is not None
    )
    specs = obs_lib.build_metrics(
        obs, geo_on=config.topology is not None, h_on=h_on
    )
    block = obs_lib.summarize(obs, specs, hist, counters)
    pr = prep.get("per_round")
    if pr is not None and "obs" in pr:
        e_stale, e_viol = pr["obs"]
        es, ev = np.asarray(e_stale), np.asarray(e_viol)
        if sharded:
            es, ev = es.sum(axis=0), ev.sum(axis=0)
        viol_rounds = np.flatnonzero(ev)
        block["per_round"] = {
            "stale": es.tolist(),
            "viol": ev.tolist(),
        }
        block["first_violation_epoch"] = (
            int(viol_rounds[0]) if viol_rounds.size else None
        )
    return block


def _cost_attribution(result: dict[str, Any]) -> dict[str, float]:
    """Re-key the assembled bill's eq. 8 terms by subsystem.

    Every dollar here is already in ``result["cost"]`` — this is an
    attribution view (merge propagation + anti-entropy vs gossip vs
    WAL/snapshot durability vs base egress), not a new bill.  Configs
    without a cost block (flat/sharded) attribute zeros.
    """
    cost = result.get("cost") or {}

    def total(*keys: str) -> float:
        return float(sum(cost.get(k, 0.0) for k in keys))

    return {
        "merge": total("anti_entropy_network"),
        "gossip": total("gossip_network", "gossip_network_geo"),
        "wal": total(
            "durability_storage", "durability_network",
            "durability_network_geo",
        ),
        "egress": total("network", "network_geo"),
    }


def assemble(
    engine,
    prep: dict,
    w: Workload,
    cfg: ClusterConfig = PAPER_CLUSTER,
    pricing: cost_model.PricingScheme = cost_model.PAPER_PRICING,
    _return_state: bool = False,
) -> dict[str, Any]:
    """Dispatch the replay output to its config's result shape."""
    config = engine.config if hasattr(engine, "config") else engine
    with trace_lib.span(
        "engine.assemble", replay=prep.get("replay_id"), count_compiles=True,
    ):
        if config.faults is not None:
            result = assemble_faulty(
                config, prep, w, cfg, pricing, _return_state
            )
        elif config.topology is not None:
            result = assemble_geo(config, prep, w, cfg, pricing)
        elif config.n_shards > 1:
            result = assemble_sharded(config, prep)
        else:
            result = assemble_flat(config, prep)
        if config.obs is not None and config.obs.enabled:
            with trace_lib.span("engine.assemble.obs"):
                result["obs"] = _obs_block(config, prep)
                result["obs"]["cost_attribution"] = _cost_attribution(
                    result
                )
    return result

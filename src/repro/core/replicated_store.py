"""ReplicatedStore — the one replicated-state facade of the framework.

Every consumer of the X-STCC protocol (``repro.storage.simulator``,
``repro.sync.engine``, ``repro.serve.engine``) used to hand-roll the same
bookkeeping: build a :class:`~repro.core.xstcc.ClusterState`, derive the
level's merge cadence, thread session floors, append to the DUOT, run
``server_merge`` at the right moments.  This module centralizes all of it
behind a single object so that session-floor and clock logic lives only
in ``repro.core``:

  * **state**     — :class:`StoreState` bundles the protocol cluster and
    the DUOT op log; it is a pytree, safe inside jit/scan.
  * **batch ops** — :meth:`ReplicatedStore.write_batch` /
    :meth:`~ReplicatedStore.read_batch` / :meth:`~ReplicatedStore.apply_batch`
    ingest ``(B,)`` op arrays through the vectorized engine
    (:func:`repro.core.xstcc.apply_op_batch`) and register them in the
    DUOT in one bulk append.
  * **merge cadence** — :func:`merge_cadence` maps a consistency level to
    its (sync period, Δ) pair; :meth:`ReplicatedStore.merge` runs the
    timed-causal propagation step.
  * **DUOT hook**  — :meth:`ReplicatedStore.audit` /
    :meth:`~ReplicatedStore.gc` expose the auditing layer.

Sessions = clients, replicas = DCs/pods/snapshot servers, resources =
key buckets / the parameter vector / model snapshots — exactly the three
instantiations listed in the ``xstcc`` module docstring.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import audit as audit_lib
from repro.core import duot as duot_lib
from repro.core import xstcc
from repro.core.consistency import ConsistencyLevel
from repro.kernels import ops as kernel_ops

Array = jax.Array


def merge_cadence(
    level: ConsistencyLevel, merge_every: int, delta: int
) -> tuple[int, int]:
    """(sync_every, effective Δ) for a level.

    Synchronous levels (ALL/TWO/QUORUM) propagate on every op with no
    timed slack; ONE gossips on a slow cadence with an unbounded (large)
    Δ; CAUSAL merges on the normal cadence but is not timed; the timed
    levels (TCC/X-STCC) are forced prompt by the Δ bound.
    """
    if level in (
        ConsistencyLevel.ALL,
        ConsistencyLevel.TWO,
        ConsistencyLevel.QUORUM,
    ):
        return 1, 0
    if level is ConsistencyLevel.ONE:
        return 2 * merge_every, 4 * delta
    if level is ConsistencyLevel.CAUSAL:
        return merge_every, 4 * delta
    return merge_every, max(1, delta // 3)


_BIG = 2 ** 30  # "never" sentinel for the cadence emulator


def _timed_index(op_step: Array, s: int, d: int) -> Array:
    """Op index at which a write issued at ``op_step`` is Δ-overdue.

    Replays the sequential schedule in op-index space: merges run after
    ops ``k*s - 1``, the logical clock at op ``g`` is ``g + g//s`` (one
    tick per op, one per merge), and the timed bound unconditionally
    applies a write at the first merge whose clock exceeds the write's
    commit clock by Δ."""
    cs = op_step + op_step // s
    k_timed = (d + cs + 1 + s) // (s + 1)     # ceil((d+cs+1)/(s+1))
    k_after = (op_step + s) // s              # ceil((g+1)/s)
    return jnp.maximum(k_timed, k_after) * s


@functools.lru_cache(maxsize=None)
def _stream_scheduler(sync_every: int, delta: int, n_clients: int,
                      n_replicas: int):
    """Jitted apply-point scheduler for one cadence configuration."""

    @jax.jit
    def sched(client: Array, replica: Array, kind: Array) -> Array:
        n = client.shape[0]
        g = jnp.arange(n, dtype=jnp.int32)
        base = (g // sync_every + 1) * sync_every
        timed = _timed_index(g, sync_every, delta)
        is_w = kind == xstcc.WRITE

        def step(carry, op):
            last_a, rep_a = carry
            ci, pi, wi, ti, bi = op
            a_w = jnp.minimum(
                ti, jnp.maximum(bi, jnp.maximum(last_a[ci], rep_a[pi]))
            )
            last_a = last_a.at[ci].set(jnp.where(wi, a_w, jnp.int32(_BIG)))
            rep_a = jnp.where(wi, rep_a.at[pi].max(a_w), rep_a)
            return (last_a, rep_a), jnp.where(wi, a_w, jnp.int32(_BIG))

        carry = (jnp.zeros((n_clients,), jnp.int32),
                 jnp.zeros((n_replicas,), jnp.int32))
        _, a = jax.lax.scan(step, carry, (client, replica, is_w, timed, base))
        return a

    return sched


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Static durability knobs (hashable — keys jitted runner caches).

    ``snapshot_every`` merge epochs between snapshot markers (0 = no
    snapshots); ``wal`` additionally journals every applied delta
    between snapshots, so a crashed replica restores its exact
    pre-crash state (snapshot load + WAL replay) instead of the
    state as-of the last marker.  ``bootstrap_ranges`` is the digest
    granularity of the peer-bootstrap pass; ``impl`` selects the
    digest-compare kernel (None = auto).  Disabled ⇒ a crash is fully
    amnesiac and the replica rebuilds from peers alone.
    """

    snapshot_every: int = 4
    wal: bool = False
    bootstrap_ranges: int = 8
    impl: str | None = None

    @property
    def enabled(self) -> bool:
        return self.snapshot_every > 0 or self.wal


class DuraState(NamedTuple):
    """Durable-media shadow of the applied state, as pure arrays.

    ``snap_version``/``snap_vc`` mirror ``replica_version`` /
    ``replica_vc`` as of each replica's last snapshot marker;
    ``wal_len`` counts deltas journaled since that marker (the replay
    cost of a crash); ``wal_total``/``snap_rows`` accumulate lifetime
    I/O events for the eq. 8 durability bill."""

    snap_version: Array  # (P, R) int32 — applied versions at last marker
    snap_vc: Array       # (P, C) int32 — applied clock at last marker
    wal_len: Array       # (P,) int32 — deltas journaled since marker
    wal_total: Array     # () int32 — lifetime WAL append events
    snap_rows: Array     # () int32 — lifetime snapshot cells written


def make_dura(
    n_replicas: int, n_clients: int, n_resources: int
) -> DuraState:
    return DuraState(
        snap_version=jnp.zeros((n_replicas, n_resources), jnp.int32),
        snap_vc=jnp.zeros((n_replicas, n_clients), jnp.int32),
        wal_len=jnp.zeros((n_replicas,), jnp.int32),
        wal_total=jnp.zeros((), jnp.int32),
        snap_rows=jnp.zeros((), jnp.int32),
    )


class HintState(NamedTuple):
    """Bounded per-replica hinted-handoff queues, as pure arrays.

    Queue ``d`` holds hints for writes that could not reach replica
    ``d`` when they committed (down, or partitioned from the
    coordinator): the pending-ring slot plus the committed version —
    the version guards against slot recycling, so a stale hint whose
    slot was reused by a newer write validates to nothing instead of
    delivering the wrong payload.  ``count[d]`` entries are live (queue
    order = enqueue order); past-capacity hints bump ``dropped`` and
    fall back to digest repair / anti-entropy."""

    slot: Array      # (P, H) int32 — pending-ring slot per hint
    version: Array   # (P, H) int32 — version committed for that slot
    count: Array     # (P,) int32 — live hints per destination queue
    dropped: Array   # () int32 — overflowed hints (handled by gossip)


def make_hints(n_replicas: int, hint_cap: int) -> HintState:
    return HintState(
        slot=jnp.zeros((n_replicas, hint_cap), jnp.int32),
        version=jnp.zeros((n_replicas, hint_cap), jnp.int32),
        count=jnp.zeros((n_replicas,), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


class StoreState(NamedTuple):
    """Protocol state + op log, as one pytree.

    ``pend_apply`` shadows the pending ring with each in-flight write's
    emulated sequential apply op-index (see
    ``ReplicatedStore.apply_batch``), carrying the merge-cadence
    emulation across batch boundaries.  ``hints`` holds the
    hinted-handoff queues when the store was built with a nonzero
    ``hint_cap`` — ``None`` otherwise, which keeps the pytree (and
    every jitted trace over it) identical to a handoff-free store.
    ``dura`` follows the same pattern for the durability layer
    (``None`` unless the store was built with a ``DurabilityConfig``)."""

    cluster: xstcc.ClusterState
    duot: duot_lib.Duot
    pend_apply: Array     # (Q,) int32
    hints: HintState | None = None
    dura: DuraState | None = None


class ReplicatedStore:
    """Facade over the batched X-STCC engine for one replicated store.

    Static configuration (sizes, level, cadence) lives on the object;
    all dynamic state lives in the :class:`StoreState` pytree that every
    method threads functionally, so methods can be called from inside
    jit/scan.
    """

    def __init__(
        self,
        n_replicas: int,
        n_clients: int,
        n_resources: int,
        *,
        level: ConsistencyLevel = ConsistencyLevel.X_STCC,
        merge_every: int = 8,
        delta: int = 24,
        pending_cap: int = 128,
        duot_cap: int = 1024,
        ingest: str = "auto",
        hint_cap: int = 0,
        durability: DurabilityConfig | None = None,
    ):
        self.n_replicas = n_replicas
        self.n_clients = n_clients
        self.n_resources = n_resources
        self.level = level
        self.pending_cap = pending_cap
        self.duot_cap = duot_cap
        self.hint_cap = hint_cap
        self.durability = (
            durability if durability is not None and durability.enabled
            else None
        )
        self.sync_every, self.delta = merge_cadence(level, merge_every, delta)
        self.enforce_sessions = level.is_session_guarded
        # Op-ingestion implementation (repro.kernels.ops.op_ingest):
        # None = auto (tiled block walk on CPU, Pallas kernel on TPU;
        # O(B·tile) memory either way); "dense" forces the O(B²)-mask
        # baseline.  All choices are bit-identical.
        self.ingest = ingest

    # -- state ----------------------------------------------------------------

    def init(self) -> StoreState:
        return self.wrap(
            xstcc.make_cluster(
                self.n_replicas, self.n_clients, self.n_resources,
                pending_cap=self.pending_cap,
            ),
            duot_lib.make(self.duot_cap, self.n_clients),
        )

    def wrap(
        self, cluster: xstcc.ClusterState, duot: duot_lib.Duot
    ) -> StoreState:
        """Adopt an existing (cluster, duot) pair as store state."""
        q = cluster.pend_live.shape[0]
        return StoreState(
            cluster=cluster, duot=duot,
            pend_apply=jnp.zeros((q,), jnp.int32),
            hints=(
                make_hints(self.n_replicas, self.hint_cap)
                if self.hint_cap > 0 else None
            ),
            dura=(
                make_dura(self.n_replicas, self.n_clients, self.n_resources)
                if self.durability is not None else None
            ),
        )

    # -- merge-cadence emulation -------------------------------------------------

    def schedule_stream(
        self, client: Array, replica: Array, kind: Array
    ) -> Array:
        """Emulated sequential apply op-index for each write of a stream.

        The sequential merge applies a write at the first merge point
        where its causal dependencies are applied everywhere, and at its
        Δ-overdue point unconditionally.  In op-index space that is

          ``A(w) = min(timed(w), max(boundary_after(w), A(prev same-client
          write), max A over earlier same-coordinator writes))``

        with the causal fast path broken (pure timed) when the session's
        previous op was a *read*: the read ticks a clock component no
        replica ever learns, so the write's dependency vector can only be
        satisfied by its own application.  Reads get a "never" sentinel.
        The schedule depends only on the op sequence and the cadence, so
        callers precompute it for a whole run and slice it per batch.
        """
        sched = _stream_scheduler(
            self.sync_every, self.delta, self.n_clients, self.n_replicas
        )
        return sched(
            jnp.asarray(client, jnp.int32), jnp.asarray(replica, jnp.int32),
            jnp.asarray(kind, jnp.int32),
        )

    # -- batch ops --------------------------------------------------------------

    def apply_batch(
        self,
        state: StoreState,
        *,
        client: Array,
        replica: Array,
        resource: Array,
        kind: Array,
        op_step0: Array | int | None = None,
        apply_index: Array | None = None,
        record: bool = True,
        enforce: Array | bool | None = None,
        with_clocks: bool = True,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        """Ingest a mixed read/write batch and register it in the DUOT.

        ``enforce`` overrides the level's session enforcement, per batch
        or per op (a ``(B,)`` bool array) — the adaptive control plane
        serves sessions at different levels out of one store.

        With ``op_step0`` (the global op index of the batch's first op)
        the level's merge cadence is emulated *inside* the batch, so the
        caller only needs a real :meth:`merge` on batch boundaries.  The
        cadence reaches the engine as the closed-form predicate
        ``op_index(i) >= apply_index(j)`` over two ``(B,)`` vectors (plus
        the ``(Q,)`` ``pend_apply`` shadow of the pending ring) — never
        as a dense visibility matrix:

          * synchronous levels (``sync_every == 1``): ``apply_index = 0``
            — every write is visible to every later op at any replica,
            exactly what a merge-after-every-op (Δ=0) schedule serves;
          * causal-family levels: each write carries an emulated
            sequential apply point in ``apply_index`` (the batch's slice
            of :meth:`schedule_stream`) and becomes visible at remote
            replicas from that op index on — both for writes inside the
            batch and for writes still pending from earlier batches.

        Without ``op_step0`` the batch has plain scalar-loop semantics
        (writes visible at their coordinator only) — the bit-exact mode
        the equivalence tests check.
        """
        c = jnp.asarray(client, jnp.int32)
        p = jnp.asarray(replica, jnp.int32)
        r = jnp.asarray(resource, jnp.int32)
        k = jnp.asarray(kind, jnp.int32)
        b = c.shape[0]
        op_index = None
        pend_apply = None
        new_pend_apply = None
        # Every store-layer batch has affine op indices (step0 + i), so
        # the closed-form fused ingest is always eligible on CPU.  Every
        # path folds the pending ring's cadence visibility itself.
        impl = kernel_ops.resolve_op_ingest_impl(
            self.ingest, batch=b,
            n_clients=self.n_clients, n_replicas=self.n_replicas,
            n_resources=self.n_resources, affine_op_index=True,
        )
        if op_step0 is not None:
            step0 = jnp.asarray(op_step0, jnp.int32)
            op_index = step0 + jnp.arange(b, dtype=jnp.int32)
            if self.sync_every == 1 and apply_index is None:
                apply_index = jnp.zeros((b,), jnp.int32)
                pend_apply = jnp.zeros_like(state.pend_apply)
                new_pend_apply = jnp.zeros((b,), jnp.int32)
            else:
                if apply_index is None:
                    apply_index = self.schedule_stream(c, p, k) + step0
                pend_apply = state.pend_apply
                new_pend_apply = apply_index
        elif self.sync_every == 1:
            # Legacy batch entry points (no op index): intra-batch
            # merge-every-op visibility, pending ring untouched.
            op_index = jnp.arange(b, dtype=jnp.int32)
            apply_index = jnp.zeros((b,), jnp.int32)
        res = xstcc.apply_op_batch(
            state.cluster, client=c, replica=p, resource=r, kind=k,
            enforce_sessions=(
                self.enforce_sessions if enforce is None else enforce
            ),
            op_index=op_index, apply_index=apply_index,
            pend_apply=pend_apply,
            ingest=impl, with_clocks=with_clocks,
        )
        pend_apply = state.pend_apply
        if new_pend_apply is not None:
            with jax.named_scope("pending_ring"):
                pend_apply = pend_apply.at[res.slot].set(
                    new_pend_apply, mode="drop"
                )
        duot = state.duot
        if record:
            with jax.named_scope("duot_record"):
                duot = duot_lib.record(
                    duot,
                    {
                        "client": c,
                        "kind": k,
                        "resource": r,
                        "version": res.version,
                        "replica": p,
                        "vc": res.vc,
                    },
                )
        return (
            StoreState(cluster=res.state, duot=duot,
                       pend_apply=pend_apply, hints=state.hints,
                       dura=state.dura),
            res,
        )

    def write_batch(
        self,
        state: StoreState,
        *,
        client: Array,
        replica: Array,
        resource: Array,
        record: bool = True,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        c = jnp.asarray(client, jnp.int32)
        return self.apply_batch(
            state, client=c, replica=replica, resource=resource,
            kind=jnp.full(c.shape, xstcc.WRITE, jnp.int32), record=record,
        )

    def read_batch(
        self,
        state: StoreState,
        *,
        client: Array,
        replica: Array,
        resource: Array,
        record: bool = True,
        enforce: Array | bool | None = None,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        c = jnp.asarray(client, jnp.int32)
        return self.apply_batch(
            state, client=c, replica=replica, resource=resource,
            kind=jnp.full(c.shape, xstcc.READ, jnp.int32), record=record,
            enforce=enforce,
        )

    # -- server side ------------------------------------------------------------

    def merge(
        self,
        state: StoreState,
        *,
        delta: Array | int | None = None,
        up: Array | None = None,
        link: Array | None = None,
        timed_only: bool = False,
        boundary: Array | int | None = None,
    ) -> tuple[StoreState, Array]:
        """Timed-causal propagation (Δ defaults to the level's cadence).

        ``up``/``link`` mask the propagation to live, connected replica
        pairs (see :func:`repro.core.xstcc.server_merge`); omitted they
        reproduce the fully-connected merge bit-exactly.  ``timed_only``
        drops the causal-dependency gate (lean replay — see
        :func:`repro.core.xstcc.server_merge`); with ``boundary`` (the
        global op index reached so far) it applies exactly the slots
        whose emulated apply point has passed — the schedule-faithful
        boundary merge of the lean engine.
        """
        d = self.delta if delta is None else delta
        ready = None
        if boundary is not None:
            assert timed_only, "boundary requires timed_only"
            ready = state.pend_apply <= jnp.asarray(boundary, jnp.int32)
        cluster, n = xstcc.server_merge(
            state.cluster, delta=d, level=self.level, up=up, link=link,
            timed_only=timed_only, ready=ready,
        )
        return state._replace(cluster=cluster), n

    def merge_geo(
        self,
        state: StoreState,
        topology,
        *,
        delta: Array | int | None = None,
        up: Array | None = None,
        link: Array | None = None,
    ) -> tuple[StoreState, Array, Array]:
        """Two-tier region-grouped merge (see ``xstcc.server_merge_geo``).

        ``topology`` is a :class:`repro.geo.topology.RegionTopology`
        whose ``n_replicas`` matches this store.  The returned state is
        bit-identical to :meth:`merge` — only the accounting changes:
        the third return value is the ``(G, G)`` delivery-event matrix
        (intra-region fan-out on the diagonal, one WAN hop per (write,
        newly-reached region) off it) that the egress matrix bills per
        pair.  ``up``/``link`` masks compose exactly as in
        :meth:`merge`, so region-severing partitions stop the
        inter-region tier naturally.
        """
        if topology.n_replicas != self.n_replicas:
            raise ValueError(
                f"topology places {topology.n_replicas} replicas, store "
                f"has {self.n_replicas}"
            )
        d = self.delta if delta is None else delta
        cluster, n, traffic = xstcc.server_merge_geo(
            state.cluster, delta=d,
            region=topology.regions(), n_regions=topology.n_regions,
            rtt_ms=topology.rtt(), level=self.level, up=up, link=link,
        )
        return state._replace(cluster=cluster), n, traffic

    def merge_faulty(
        self,
        state: StoreState,
        *,
        up: Array,
        link: Array,
        delta: Array | int | None = None,
    ) -> tuple[StoreState, Array, Array]:
        """Masked merge that also meters propagation traffic.

        Returns ``(state, n_applied, events)`` where ``events`` counts
        the (write, replica) deliveries this merge performed — each is
        one replica-propagation payload for the cost model (eq. 8).
        The count is the growth of ``pend_applied`` (coordinator copies
        were stamped at commit time, so only real transfers count).
        """
        before = jnp.sum(state.cluster.pend_applied.astype(jnp.int32))
        new, n = self.merge(state, delta=delta, up=up, link=link)
        events = (
            jnp.sum(new.cluster.pend_applied.astype(jnp.int32)) - before
        )
        return new, n, events

    def anti_entropy(
        self, state: StoreState, *, up: Array, link: Array
    ) -> tuple[StoreState, Array]:
        """Full reconciliation along the currently-live links.

        The heal-time catch-up pass: with Δ=0 every live pending write
        is overdue, so one masked fixpoint pushes the whole backlog to
        every replica its holders can now reach — a healed replica (or
        a re-joined partition side) converges in one pass.  Returns
        ``(state, events)`` with ``events`` the deliveries performed,
        charged as anti-entropy traffic by the failure drivers.

        **Idempotent**: reconciliation is a background pass, not a
        protocol step, so the logical clock is restored afterwards —
        the merge's per-call clock tick otherwise advanced Δ-overdue
        points purely by *re-invoking* anti-entropy, making repeated
        passes at the same epoch observable (and double-billable: a
        later pass could ship writes the clock drift newly aged past
        Δ).  With the clock restored a second call at the same masks
        is a fixpoint: identical state, zero deliveries
        (``tests/test_faults.py::test_anti_entropy_idempotent``).
        """
        new, _, events = self.merge_faulty(state, up=up, link=link, delta=0)
        new = new._replace(
            cluster=new.cluster._replace(clock=state.cluster.clock)
        )
        return new, events

    # -- gossip anti-entropy / hinted handoff -------------------------------------

    def gossip_round(
        self,
        state: StoreState,
        *,
        pairs: Array,        # (M, 2) int32 — ordered (replica, peer) pairs
        up: Array,           # (P,) bool
        link: Array,         # (P, P) bool — closed connectivity
        n_ranges: int,
        impl: str | None = None,
    ) -> tuple[StoreState, dict[str, Array]]:
        """One digest-exchange pass: diff, then repair stale ranges.

        Each scheduled pair ``(a, b)`` (see
        ``repro.gossip.scheduler.gossip_pairs``) exchanges per-range
        version digests (``repro.gossip.digest.range_digests``), diffs
        them through ``repro.kernels.ops.digest_compare``, and repairs
        the ranges that differ with a *range-restricted* Δ=0 pair
        merge: the pending ring is temporarily masked to live writes
        whose resource falls in a stale range, and the link mask to the
        ``a``–``b`` edge — so only targeted deliveries between the two
        peers happen, metered exactly like ``merge_faulty``.  Pairs
        that are down, disconnected, or self-loops are invalid and
        repair nothing.  Like :meth:`anti_entropy` the pass is
        clock-neutral (idempotent: a second identical round finds no
        differing live ranges to ship and delivers zero).

        Returns ``(state, telemetry)`` with ``telemetry`` a dict of
        arrays: ``valid`` (M,) bool, ``ranges`` (M,) int32 stale
        ranges per pair, ``growth`` (M, P) int32 deliveries per pair
        by receiving replica, and ``gap_repaired`` () int32 — the
        drop in total version staleness ``Σ max(0, global − replica)``
        achieved by the round.
        """
        from repro.gossip import digest as digest_lib
        from repro.kernels import ops as kernel_ops

        cl = state.cluster
        p = self.n_replicas
        r = self.n_resources
        pairs = jnp.asarray(pairs, jnp.int32)
        u = jnp.asarray(up, bool)
        ln = jnp.asarray(link, bool)
        a_idx, b_idx = pairs[:, 0], pairs[:, 1]
        valid = (
            u[a_idx] & u[b_idx] & ln[a_idx, b_idx] & (a_idx != b_idx)
        )
        dig = digest_lib.range_digests(cl.replica_version, n_ranges)
        differ, _, _ = kernel_ops.digest_compare(
            dig[a_idx], dig[b_idx], impl=impl
        )                                                   # (M, K)
        stale = differ & valid[:, None]
        rid = digest_lib.range_of_resource(r, n_ranges)     # (R,)
        gap = lambda c: jnp.sum(jnp.maximum(                # noqa: E731
            c.global_version[None, :] - c.replica_version, 0
        ))
        gap_before = gap(cl)
        eye = jnp.eye(p, dtype=bool)
        rows = jnp.arange(p, dtype=jnp.int32)

        def step(cluster, inp):
            a, b, stale_k, v = inp
            res_rid = rid[jnp.clip(cluster.pend_resource, 0, r - 1)]
            in_stale = stale_k[res_rid] & v                 # (Q,)
            saved_live = cluster.pend_live
            saved_clock = cluster.clock
            ia, ib = rows == a, rows == b
            pair_ln = (
                eye | (ia[:, None] & ib[None, :]) | (ib[:, None] & ia[None, :])
            )
            masked = cluster._replace(
                pend_live=saved_live & in_stale
            )
            before = masked.pend_applied.astype(jnp.int32)
            merged, _ = xstcc.server_merge(
                masked, delta=0, level=self.level, up=u, link=pair_ln
            )
            growth = jnp.sum(
                merged.pend_applied.astype(jnp.int32) - before, axis=0
            )                                               # (P,)
            cluster = merged._replace(
                pend_live=saved_live & ~jnp.all(merged.pend_applied, axis=1),
                clock=saved_clock,
            )
            return cluster, growth

        cluster, growth = jax.lax.scan(
            step, cl, (a_idx, b_idx, stale, valid)
        )
        telemetry = {
            "valid": valid,
            "ranges": jnp.sum(stale.astype(jnp.int32), axis=1),
            "growth": growth,
            "gap_repaired": gap_before - gap(cluster),
        }
        return state._replace(cluster=cluster), telemetry

    def enqueue_hints(
        self,
        state: StoreState,
        *,
        slot: Array,      # (B,) int32 — pending-ring slot per op
        version: Array,   # (B,) int32 — committed version per op
        kind: Array,      # (B,) int32
        home: Array,      # (B,) int32 — coordinator replica per op
        conn: Array,      # (P, P) bool — closed connectivity this epoch
    ) -> tuple[StoreState, Array, Array]:
        """Queue hints for the replicas a batch's writes could not reach.

        A write whose coordinator cannot reach replica ``d`` this epoch
        (``~conn[home, d]`` — down or partitioned) enqueues ``(slot,
        version)`` on ``d``'s bounded hint queue; on heal,
        :meth:`drain_hints` re-validates and delivers them ahead of the
        full anti-entropy pass.  Overflow beyond ``hint_cap`` is
        counted in ``hints.dropped`` and left to digest repair.
        Returns ``(state, n_enqueued, n_dropped)``.
        """
        hints = state.hints
        h = self.hint_cap
        is_w = jnp.asarray(kind, jnp.int32) == xstcc.WRITE
        miss = is_w[None, :] & ~jnp.asarray(conn, bool)[
            jnp.asarray(home, jnp.int32)
        ].T                                                 # (P, B)
        rank = jnp.cumsum(miss.astype(jnp.int32), axis=1) - 1
        pos = hints.count[:, None] + rank                   # (P, B)
        ok = miss & (pos < h)
        posc = jnp.where(ok, pos, h)        # h = out-of-bounds → dropped
        d_grid = jnp.broadcast_to(
            jnp.arange(self.n_replicas, dtype=jnp.int32)[:, None], posc.shape
        )
        slot_b = jnp.broadcast_to(
            jnp.asarray(slot, jnp.int32)[None, :], posc.shape
        )
        ver_b = jnp.broadcast_to(
            jnp.asarray(version, jnp.int32)[None, :], posc.shape
        )
        n_enq = jnp.sum(ok.astype(jnp.int32))
        n_drop = jnp.sum((miss & ~ok).astype(jnp.int32))
        new_hints = HintState(
            slot=hints.slot.at[d_grid, posc].set(slot_b, mode="drop"),
            version=hints.version.at[d_grid, posc].set(ver_b, mode="drop"),
            count=hints.count + jnp.sum(ok.astype(jnp.int32), axis=1),
            dropped=hints.dropped + n_drop,
        )
        return state._replace(hints=new_hints), n_enq, n_drop

    def drain_hints(
        self, state: StoreState, *, up: Array, link: Array
    ) -> tuple[StoreState, Array]:
        """Deliver queued hints along the now-live links (heal path).

        For every destination replica the queue is re-validated against
        the pending ring — a hint whose slot was recycled (version
        mismatch) or whose write already retired is discarded — and the
        surviving hinted writes are pushed by a Δ=0 merge restricted to
        links touching the destination, the targeted front-run of the
        full anti-entropy pass.  Hints that delivered (or invalidated)
        leave the queue; hints whose holders are still unreachable stay
        queued.  Clock-neutral like :meth:`anti_entropy`.  Returns
        ``(state, deliveries)`` with ``deliveries`` a ``(P,)`` vector of
        applied-copy growth *by receiving replica* — destination ``d``'s
        sub-pass may relay hinted writes through ``d`` to other replicas
        it can reach, so when several destinations heal in the same
        epoch a scalar count would misattribute those deliveries to
        whichever queue drained first.
        """
        hints = state.hints
        h = self.hint_cap
        p = self.n_replicas
        q = state.cluster.pend_live.shape[0]
        u = jnp.asarray(up, bool)
        ln = jnp.asarray(link, bool)
        eye = jnp.eye(p, dtype=bool)
        rows = jnp.arange(p, dtype=jnp.int32)
        hpos = jnp.arange(h, dtype=jnp.int32)

        def step(carry, d):
            cluster, hints, delivered = carry
            qslots = jnp.clip(hints.slot[d], 0, q - 1)
            in_q = hpos < hints.count[d]
            hint_ok = (
                in_q
                & cluster.pend_live[qslots]
                & (cluster.pend_version[qslots] == hints.version[d])
            )
            marked = (
                jnp.zeros((q,), bool).at[qslots].max(hint_ok, mode="drop")
            )
            saved_live = cluster.pend_live
            saved_clock = cluster.clock
            touch_d = (rows == d)[:, None] | (rows == d)[None, :]
            masked = cluster._replace(pend_live=saved_live & marked)
            before = masked.pend_applied.astype(jnp.int32)
            merged, _ = xstcc.server_merge(
                masked, delta=0, level=self.level,
                up=u, link=(eye | touch_d) & ln,
            )
            ev = jnp.sum(
                merged.pend_applied.astype(jnp.int32) - before, axis=0
            )                                               # (P,)
            cluster = merged._replace(
                pend_live=saved_live & ~jnp.all(merged.pend_applied, axis=1),
                clock=saved_clock,
            )
            # Compact: keep valid hints still undelivered at d.
            keep = hint_ok & ~merged.pend_applied[qslots, d]
            kpos = jnp.where(
                keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, h
            )
            hints = HintState(
                slot=hints.slot.at[d].set(
                    jnp.zeros((h,), jnp.int32)
                    .at[kpos].set(hints.slot[d], mode="drop")
                ),
                version=hints.version.at[d].set(
                    jnp.zeros((h,), jnp.int32)
                    .at[kpos].set(hints.version[d], mode="drop")
                ),
                count=hints.count.at[d].set(
                    jnp.sum(keep.astype(jnp.int32))
                ),
                dropped=hints.dropped,
            )
            return (cluster, hints, delivered + ev), None

        (cluster, hints, delivered), _ = jax.lax.scan(
            step, (state.cluster, hints, jnp.zeros((p,), jnp.int32)), rows
        )
        return state._replace(cluster=cluster, hints=hints), delivered

    # -- durability / crash recovery ----------------------------------------------

    def snapshot(self, state: StoreState) -> tuple[StoreState, Array]:
        """Persist a snapshot marker at every replica; truncate WALs.

        The marker copies each replica's applied state
        (``replica_version``/``replica_vc``) onto durable media;
        snapshots are incremental, so the I/O charged is the number of
        ``(replica, resource)`` cells whose version moved since the
        previous marker.  Returns ``(state, cells_written)``.
        """
        cl, du = state.cluster, state.dura
        cells = jnp.sum(
            (du.snap_version != cl.replica_version).astype(jnp.int32)
        )
        dura = DuraState(
            snap_version=cl.replica_version,
            snap_vc=cl.replica_vc,
            wal_len=jnp.zeros_like(du.wal_len),
            wal_total=du.wal_total,
            snap_rows=du.snap_rows + cells,
        )
        return state._replace(dura=dura), cells

    def wal_append(self, state: StoreState, records: Array) -> StoreState:
        """Journal ``records`` (P,) applied deltas since the last marker."""
        du = state.dura
        rec = jnp.asarray(records, jnp.int32)
        dura = du._replace(
            wal_len=du.wal_len + rec,
            wal_total=du.wal_total + jnp.sum(rec),
        )
        return state._replace(dura=dura)

    def crash(
        self, state: StoreState, crashed: Array
    ) -> tuple[StoreState, dict[str, Array]]:
        """Destroy the volatile state of ``crashed`` (P,) bool replicas.

        What survives depends on the store's :class:`DurabilityConfig`:

          * **WAL on** — snapshot load + full replay reconstruct the
            exact pre-crash applied state; only the replay I/O is paid.
          * **snapshots only** — applied state rolls back to the last
            marker: version/clock rows restore to the snapshot, and
            pending-ring applied bits at the crashed replica survive
            only for writes the marker already covered.
          * **disabled** — full amnesia: the replica's column of the
            cluster state zeroes and every applied bit at it clears.

        The commit log itself (the pending ring, ``global_version``,
        session floors) is coordinator-durable — a crash never un-acks a
        committed write; it only forgets *applied* state, which peer
        :meth:`bootstrap` and the merge fixpoint re-deliver.  Returns
        ``(state, info)`` with ``info`` scalars: ``wal_replayed``
        (journal records re-applied), ``snap_read`` (snapshot cells
        loaded), ``rows_lost`` (version cells rolled back — 0 with WAL).
        """
        cl = state.cluster
        du = state.dura
        cfg = self.durability
        crashed = jnp.asarray(crashed, bool)
        zero = jnp.zeros((), jnp.int32)
        if cfg is not None and cfg.wal:
            # Redo log: restore is exact; bill marker load + replay.
            snap_read = jnp.sum(
                jnp.where(crashed[:, None], (du.snap_version > 0), False)
                .astype(jnp.int32)
            )
            replayed = jnp.sum(jnp.where(crashed, du.wal_len, 0))
            return state, {
                "wal_replayed": replayed,
                "snap_read": snap_read,
                "rows_lost": zero,
            }
        if du is not None and cfg is not None:
            base_v, base_c = du.snap_version, du.snap_vc
            snap_read = jnp.sum(
                jnp.where(crashed[:, None], (base_v > 0), False)
                .astype(jnp.int32)
            )
        else:
            base_v = jnp.zeros_like(cl.replica_version)
            base_c = jnp.zeros_like(cl.replica_vc)
            snap_read = zero
        new_rv = jnp.where(crashed[:, None], base_v, cl.replica_version)
        new_vc = jnp.where(crashed[:, None], base_c, cl.replica_vc)
        rows_lost = jnp.sum(
            (cl.replica_version > new_rv).astype(jnp.int32)
        )
        r = self.n_resources
        res = jnp.clip(cl.pend_resource, 0, r - 1)
        covered = cl.pend_version[:, None] <= base_v[:, res].T  # (Q, P)
        touch = crashed[None, :] & cl.pend_live[:, None]
        applied = jnp.where(
            touch, cl.pend_applied & covered, cl.pend_applied
        )
        cluster = cl._replace(
            replica_version=new_rv, replica_vc=new_vc, pend_applied=applied
        )
        new = state._replace(cluster=cluster)
        if du is not None:
            new = new._replace(
                dura=du._replace(
                    wal_len=jnp.where(crashed, 0, du.wal_len)
                )
            )
        return new, {
            "wal_replayed": zero,
            "snap_read": snap_read,
            "rows_lost": rows_lost,
        }

    def bootstrap(
        self,
        state: StoreState,
        *,
        targets: Array,      # (P,) bool — replicas rebuilding this epoch
        up: Array,           # (P,) bool
        link: Array,         # (P, P) bool — closed connectivity
        n_ranges: int,
        impl: str | None = None,
    ) -> tuple[StoreState, dict[str, Array]]:
        """Rebuild each target replica from its nearest live holder.

        For every target ``d`` the first live, linked, non-rebuilding
        peer in ring order after ``d`` is chosen as the source; the two
        exchange per-range version digests
        (``repro.gossip.digest.range_digests`` diffed through
        ``repro.kernels.ops.digest_compare`` — the same path a gossip
        round uses), and every differing range is pulled:

          * retired history — ``replica_version`` cells in stale ranges
            max-join the source's row, and the target's applied clock
            max-joins the source's (retired writes live at every
            replica, so any live source is complete);
          * in-flight writes — live pending-ring entries in stale
            ranges applied at the source are marked applied at the
            target, then the normal retire check runs.

        Clock-neutral and idempotent (a second pass finds no differing
        ranges).  Returns ``(state, telemetry)`` with ``(P,)`` arrays:
        ``valid`` (a source was reachable), ``source``, ``cells``
        (version cells raised), ``pend`` (pending copies delivered),
        ``ranges`` (stale ranges pulled).
        """
        from repro.gossip import digest as digest_lib
        from repro.kernels import ops as kernel_ops

        cl = state.cluster
        p = self.n_replicas
        r = self.n_resources
        t_all = jnp.asarray(targets, bool)
        u = jnp.asarray(up, bool)
        ln = jnp.asarray(link, bool)
        rid = digest_lib.range_of_resource(r, n_ranges)     # (R,)
        res = jnp.clip(cl.pend_resource, 0, r - 1)
        rows = jnp.arange(p, dtype=jnp.int32)
        saved_clock = cl.clock

        def step(cluster, d):
            offs = (d + 1 + jnp.arange(p - 1, dtype=jnp.int32)) % p
            cand = u[offs] & ln[d, offs] & ~t_all[offs]
            src = offs[jnp.argmax(cand)]
            valid = t_all[d] & u[d] & cand.any()
            dig = digest_lib.range_digests(cluster.replica_version, n_ranges)
            differ, _, _ = kernel_ops.digest_compare(
                dig[None, d], dig[None, src], impl=impl
            )                                               # (1, K)
            stale = differ[0] & valid                       # (K,)
            in_stale = stale[rid]                           # (R,)
            pull = jnp.maximum(
                cluster.replica_version[d],
                jnp.where(in_stale, cluster.replica_version[src], 0),
            )
            cells = jnp.sum(
                (pull > cluster.replica_version[d]).astype(jnp.int32)
            )
            new_rv = cluster.replica_version.at[d].set(pull)
            new_vc = jnp.where(
                valid,
                jnp.maximum(cluster.replica_vc[d], cluster.replica_vc[src]),
                cluster.replica_vc[d],
            )
            relay = (
                cluster.pend_live
                & stale[rid[res]]
                & cluster.pend_applied[:, src]
            )
            pend = jnp.sum(
                (relay & ~cluster.pend_applied[:, d]).astype(jnp.int32)
            )
            applied = cluster.pend_applied.at[:, d].max(relay)
            live = cluster.pend_live & ~jnp.all(applied, axis=1)
            cluster = cluster._replace(
                replica_version=new_rv,
                replica_vc=cluster.replica_vc.at[d].set(new_vc),
                pend_applied=applied,
                pend_live=live,
            )
            out = {
                "valid": valid,
                "source": jnp.where(valid, src, -1),
                "cells": cells,
                "pend": pend,
                "ranges": jnp.sum(stale.astype(jnp.int32)),
            }
            return cluster, out

        cluster, telemetry = jax.lax.scan(step, cl, rows)
        cluster = cluster._replace(clock=saved_clock)
        return state._replace(cluster=cluster), telemetry

    def install(
        self,
        state: StoreState,
        *,
        replica: Array | int,
        resource: Array | int,
        version: Array | int,
    ) -> StoreState:
        """Server-side snapshot install (the serving layer's ``publish``).

        Unlike a client write, an install carries an externally-assigned
        version (e.g. a checkpoint step) and no session: it just raises
        the replica's applied version and the global frontier.
        """
        p = jnp.asarray(replica, jnp.int32)
        r = jnp.asarray(resource, jnp.int32)
        v = jnp.asarray(version, jnp.int32)
        cluster = state.cluster._replace(
            replica_version=state.cluster.replica_version.at[p, r].max(v),
            global_version=state.cluster.global_version.at[r].max(v),
        )
        return state._replace(cluster=cluster)

    # -- session floors -----------------------------------------------------------

    def session_floor(
        self, state: StoreState, client: Array | int, resource: Array | int
    ) -> Array:
        """The MR/RYW floor: min version admissible for this session."""
        c = jnp.asarray(client, jnp.int32)
        r = jnp.asarray(resource, jnp.int32)
        return jnp.maximum(
            state.cluster.read_floor[c, r], state.cluster.write_floor[c, r]
        )

    def admit_batch(
        self,
        state: StoreState,
        *,
        client: Array,
        replica: Array,
        resource: Array,
        use_kernel: bool = False,
    ) -> tuple[StoreState, Array, Array]:
        """Batched admission check + floor update (the serving hot loop).

        Checks ``replica_version[p, r] >= max(read_floor, write_floor)``
        for each op against the *pre-batch* floors (router semantics: the
        batch was admitted concurrently), serves
        ``max(replica_version, floor)`` under session enforcement, and
        raises the read floors.  With ``use_kernel=True`` the check runs
        through the Pallas kernel (``repro.kernels.session_floor``).

        Returns ``(state, served, admissible)``.
        """
        c = jnp.asarray(client, jnp.int32)
        p = jnp.asarray(replica, jnp.int32)
        r = jnp.asarray(resource, jnp.int32)
        cl = state.cluster
        if use_kernel:
            from repro.kernels import ops as kernel_ops

            served, adm, _, new_rf = kernel_ops.session_admit(
                cl.replica_version, cl.read_floor, cl.write_floor,
                c, p, r, enforce=self.enforce_sessions,
            )
        else:
            from repro.kernels import ref as kernel_ref

            served, adm, _, new_rf = kernel_ref.session_admit_ref(
                cl.replica_version, cl.read_floor, cl.write_floor,
                c, p, r, enforce=self.enforce_sessions,
            )
        cluster = cl._replace(read_floor=new_rf)
        return state._replace(cluster=cluster), served, adm

    # -- audit / GC ---------------------------------------------------------------

    def audit(
        self, state: StoreState, *, delta: Array | int | None = None
    ) -> audit_lib.AuditResult:
        d = self.delta if delta is None else delta
        return audit_lib.audit(state.duot, delta=d)

    def gc(self, state: StoreState) -> StoreState:
        """Drop DUOT entries covered by the global stability frontier."""
        frontier = xstcc.stability_frontier(state.cluster)
        return state._replace(duot=duot_lib.gc(state.duot, frontier))

    def stability_frontier(self, state: StoreState) -> Array:
        return xstcc.stability_frontier(state.cluster)


class ShardedStore:
    """Disjoint-shard scale-out: S independent replica fleets, one axis.

    Multi-tenant ingestion partitions sessions and resources into S
    disjoint shards (tenant groups); each shard is a full
    :class:`ReplicatedStore` of its own (clients/resources renumbered
    shard-locally) whose :class:`StoreState` is stacked along a leading
    ``(S, ...)`` axis.  Every batch op maps over that axis with
    ``jax.vmap`` — and because the shards share no state, the mapped
    axis can be laid out across a device mesh (``jax.shard_map`` in
    :func:`repro.storage.simulator.run_protocol_sharded` does exactly
    that when the host has enough devices), with per-shard telemetry
    summed afterwards.  Sharded metrics are exactly the sum of the
    per-shard unsharded runs (``tests/test_op_ingest.py`` checks this).
    """

    def __init__(self, store: ReplicatedStore, n_shards: int):
        self.store = store
        self.n_shards = n_shards

    def init(self) -> StoreState:
        """Stacked fresh state, one store per shard."""
        return jax.vmap(lambda _: self.store.init())(
            jnp.arange(self.n_shards)
        )

    def apply_batch(
        self,
        state: StoreState,
        *,
        client: Array,     # (S, B) int32 — shard-local client ids
        replica: Array,    # (S, B) int32
        resource: Array,   # (S, B) int32 — shard-local resource ids
        kind: Array,       # (S, B) int32
        op_step0: Array | None = None,     # (S,) int32
        apply_index: Array | None = None,  # (S, B) int32
        record: bool = True,
        enforce: Array | bool | None = None,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        """One batch per shard, vmapped over the shard axis."""
        ops = {
            "client": jnp.asarray(client, jnp.int32),
            "replica": jnp.asarray(replica, jnp.int32),
            "resource": jnp.asarray(resource, jnp.int32),
            "kind": jnp.asarray(kind, jnp.int32),
        }
        if op_step0 is not None:
            ops["op_step0"] = jnp.asarray(op_step0, jnp.int32)
        if apply_index is not None:
            ops["apply_index"] = jnp.asarray(apply_index, jnp.int32)

        def one(st, o):
            return self.store.apply_batch(
                st, client=o["client"], replica=o["replica"],
                resource=o["resource"], kind=o["kind"],
                op_step0=o.get("op_step0"), apply_index=o.get("apply_index"),
                record=record, enforce=enforce,
            )

        return jax.vmap(one)(state, ops)

    def read_batch(
        self, state: StoreState, *, client: Array, replica: Array,
        resource: Array, record: bool = True,
        enforce: Array | bool | None = None,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        c = jnp.asarray(client, jnp.int32)
        return self.apply_batch(
            state, client=c, replica=replica, resource=resource,
            kind=jnp.full(c.shape, xstcc.READ, jnp.int32), record=record,
            enforce=enforce,
        )

    def write_batch(
        self, state: StoreState, *, client: Array, replica: Array,
        resource: Array, record: bool = True,
    ) -> tuple[StoreState, xstcc.BatchResult]:
        c = jnp.asarray(client, jnp.int32)
        return self.apply_batch(
            state, client=c, replica=replica, resource=resource,
            kind=jnp.full(c.shape, xstcc.WRITE, jnp.int32), record=record,
        )

    def merge(
        self,
        state: StoreState,
        *,
        delta: Array | int | None = None,
        up: Array | None = None,
        link: Array | None = None,
    ) -> tuple[StoreState, Array]:
        """Merge every shard (one availability mask shared by all)."""
        return jax.vmap(
            lambda st: self.store.merge(st, delta=delta, up=up, link=link)
        )(state)

    def anti_entropy(
        self, state: StoreState, *, up: Array, link: Array
    ) -> tuple[StoreState, Array]:
        """Heal-time reconciliation on every shard; events summed."""
        st, ev = jax.vmap(
            lambda s: self.store.anti_entropy(s, up=up, link=link)
        )(state)
        return st, jnp.sum(ev)

    def install(
        self, state: StoreState, *, replica: Array | int,
        resource: Array | int, version: Array | int,
    ) -> StoreState:
        """Install a snapshot on every shard (server-side publish)."""
        return jax.vmap(
            lambda st: self.store.install(
                st, replica=replica, resource=resource, version=version
            )
        )(state)

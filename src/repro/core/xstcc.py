"""X-STCC protocol engine — paper §3.4 (the proposed method).

A functional state machine over ``(clients × replicas × resources)``:

  * **server side** — every replica applies writes in the deterministic
    causal linear extension derived from DUOT vector clocks (timed causal:
    propagation bounded by Δ); all replicas share one view of the order.
  * **client side** — per-session floors enforce the four guarantees:
      MR  : a session's reads never return a version below its read floor;
      RYW : ... nor below its own-write floor;
      MW  : a session's writes are applied everywhere in issue order
            (guaranteed by the causal extension: same-client writes are
            totally ordered by the session's own clock component);
      WFR : a session's write is ordered after every write whose value
            the session has read (its clock dominates those writes').

The same engine backs three layers of the framework:
``repro.storage.simulator`` (keys = user table rows — the paper's own
evaluation), ``repro.sync.engine`` (single resource = the parameter
vector; replicas = pods), and ``repro.serve.engine`` (resources = model
snapshots; sessions = request streams) — all three consume it through
the ``repro.core.replicated_store.ReplicatedStore`` facade.

Ops come in two equivalent granularities: scalar (``client_write`` /
``client_read``, one op at a time) and batched (``apply_op_batch`` and
the ``client_*_batch`` wrappers), which ingest ``(B,)`` op arrays via
segment/scatter ops with bit-identical results — the serving-scale hot
path.  Everything is fixed-shape jnp so it can run under jit/vmap in
property tests and inside the training step.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import vector_clock as vclock
from repro.core.consistency import ConsistencyLevel
from repro.kernels import vclock_chain

Array = jax.Array

WRITE = 1
READ = 0


class ClusterState(NamedTuple):
    """Replicated-store state.

    P replicas, C clients/sessions, R resources."""

    replica_version: Array   # (P, R) int32 — applied version per resource
    replica_vc: Array        # (P, C) int32 — applied vector clock
    session_vc: Array        # (C, C) int32 — each session's clock
    # The session floors are flat row-major: cell (c, r) sits at
    # ``c * R + r`` (:func:`floor_index`), so a batch's scatter into them
    # acts in place, with no (C, R) layout for the compiler to convert.
    read_floor: Array        # (C*R,) int32 — MR floor
    write_floor: Array       # (C*R,) int32 — RYW floor
    global_version: Array    # (R,) int32 — latest committed version
    # Pending writes ring (bounded): writes committed but not yet applied
    # everywhere. Slots cycle; capacity bounds in-flight writes.
    pend_client: Array       # (Q,) int32
    pend_resource: Array     # (Q,) int32
    pend_version: Array      # (Q,) int32
    pend_vc: Array           # (Q, C) int32
    pend_coord: Array        # (Q,) int32  — coordinator replica
    pend_time: Array         # (Q,) int32  — commit step
    pend_live: Array         # (Q,) bool
    pend_applied: Array      # (Q, P) bool — applied at replica p?
    pend_dropped: Array      # () int32 — writes that found no free slot
    clock: Array             # () int32 — logical step counter


def make_cluster(
    n_replicas: int, n_clients: int, n_resources: int, pending_cap: int = 128
) -> ClusterState:
    P, C, R, Q = n_replicas, n_clients, n_resources, pending_cap
    assert C * R < 2**31, f"{C} x {R} floor cells overflow an int32 index"
    return ClusterState(
        replica_version=jnp.zeros((P, R), jnp.int32),
        replica_vc=jnp.zeros((P, C), jnp.int32),
        session_vc=jnp.zeros((C, C), jnp.int32),
        read_floor=jnp.zeros((C * R,), jnp.int32),
        write_floor=jnp.zeros((C * R,), jnp.int32),
        global_version=jnp.zeros((R,), jnp.int32),
        pend_client=jnp.full((Q,), -1, jnp.int32),
        pend_resource=jnp.full((Q,), -1, jnp.int32),
        pend_version=jnp.zeros((Q,), jnp.int32),
        pend_vc=jnp.zeros((Q, C), jnp.int32),
        pend_coord=jnp.full((Q,), -1, jnp.int32),
        pend_time=jnp.zeros((Q,), jnp.int32),
        pend_live=jnp.zeros((Q,), bool),
        pend_applied=jnp.zeros((Q, P), bool),
        pend_dropped=jnp.zeros((), jnp.int32),
        clock=jnp.zeros((), jnp.int32),
    )


def floor_index(state: ClusterState, client: Array, resource: Array) -> Array:
    """Index of the ``(client, resource)`` cell in the flat floors."""
    return client * state.global_version.shape[0] + resource


def _saturating_add(counter: Array, n: Array) -> Array:
    """int32 add that clamps at INT32_MAX instead of wrapping."""
    headroom = jnp.iinfo(jnp.int32).max - counter
    return counter + jnp.minimum(n.astype(jnp.int32), headroom)


class WriteResult(NamedTuple):
    state: ClusterState
    version: Array  # version created
    vc: Array       # clock stamped on the op


def client_write(
    state: ClusterState,
    *,
    client: Array | int,
    replica: Array | int,
    resource: Array | int,
) -> WriteResult:
    """Commit a write at its coordinator replica; enqueue propagation.

    The write's clock is ``tick(merge(session, replica_view), client)`` —
    it therefore dominates every write the session has read (WFR) and the
    session's own previous writes (MW).
    """
    c = jnp.asarray(client, jnp.int32)
    p = jnp.asarray(replica, jnp.int32)
    r = jnp.asarray(resource, jnp.int32)
    cr = floor_index(state, c, r)

    svc = vclock.receive(state.session_vc[c], state.replica_vc[p], c)
    ver = state.global_version[r] + 1

    # Apply at coordinator immediately (local write, T ≈ 0).
    replica_version = state.replica_version.at[p, r].max(ver)
    replica_vc = state.replica_vc.at[p].set(
        vclock.merge(state.replica_vc[p], svc)
    )

    # Enqueue for propagation in the first free pending slot.  When the
    # ring is full the write still commits at its coordinator but the
    # propagation record is DROPPED — observably: ``pend_dropped`` counts
    # every lost record (the old behaviour silently recycled slot 0,
    # clobbering a live unapplied write).
    Q = state.pend_live.shape[0]
    free = jnp.logical_not(state.pend_live)
    has_free = jnp.any(free)
    slot = jnp.argmax(free).astype(jnp.int32)  # first free slot
    q = jnp.where(has_free, slot, jnp.int32(Q))  # Q = out of bounds -> drop
    applied0 = jnp.zeros((state.pend_applied.shape[1],), bool).at[p].set(True)

    new = state._replace(
        replica_version=replica_version,
        replica_vc=replica_vc,
        session_vc=state.session_vc.at[c].set(svc),
        write_floor=state.write_floor.at[cr].max(ver),
        read_floor=state.read_floor.at[cr].max(ver),
        global_version=state.global_version.at[r].set(ver),
        pend_client=state.pend_client.at[q].set(c, mode="drop"),
        pend_resource=state.pend_resource.at[q].set(r, mode="drop"),
        pend_version=state.pend_version.at[q].set(ver, mode="drop"),
        pend_vc=state.pend_vc.at[q].set(svc, mode="drop"),
        pend_coord=state.pend_coord.at[q].set(p, mode="drop"),
        pend_time=state.pend_time.at[q].set(state.clock, mode="drop"),
        pend_live=state.pend_live.at[q].set(True, mode="drop"),
        pend_applied=state.pend_applied.at[q].set(applied0, mode="drop"),
        pend_dropped=_saturating_add(
            state.pend_dropped, 1 - has_free.astype(jnp.int32)
        ),
        clock=state.clock + 1,
    )
    return WriteResult(state=new, version=ver, vc=svc)


class ReadResult(NamedTuple):
    state: ClusterState
    version: Array      # version returned
    admissible: Array   # bool — replica satisfied the session floors
    stale: Array        # bool — returned < globally-latest version
    violation: Array    # bool — a session guarantee was actually violated


def client_read(
    state: ClusterState,
    *,
    client: Array | int,
    replica: Array | int,
    resource: Array | int,
    enforce_sessions: bool | Array = True,
) -> ReadResult:
    """Serve a read at ``replica`` for ``client``.

    Under X-STCC (``enforce_sessions=True``) an inadmissible replica
    (below the session floors) is *repaired before serving*: the engine
    waits for / fetches the missing version — modeled as serving
    ``max(replica_version, floors)``, which is exactly what rerouting to
    an admissible replica returns.  Weaker levels serve the raw replica
    value and may violate MR/RYW.
    """
    c = jnp.asarray(client, jnp.int32)
    p = jnp.asarray(replica, jnp.int32)
    r = jnp.asarray(resource, jnp.int32)
    cr = floor_index(state, c, r)

    raw = state.replica_version[p, r]
    floor = jnp.maximum(state.read_floor[cr], state.write_floor[cr])
    admissible = raw >= floor
    enforce = jnp.asarray(enforce_sessions, bool)
    served = jnp.where(enforce, jnp.maximum(raw, floor), raw)
    violation = jnp.logical_and(jnp.logical_not(enforce),
                                jnp.logical_not(admissible))
    stale = served < state.global_version[r]

    svc = vclock.receive(state.session_vc[c], state.replica_vc[p], c)
    new = state._replace(
        session_vc=state.session_vc.at[c].set(svc),
        read_floor=state.read_floor.at[cr].max(served),
        clock=state.clock + 1,
    )
    return ReadResult(
        state=new, version=served, admissible=admissible, stale=stale,
        violation=violation,
    )


class BatchResult(NamedTuple):
    """Per-op outputs of :func:`apply_op_batch` (B = batch size).

    ``version`` is the version created (writes) or served (reads); fields
    below it are meaningful for reads only (writes report ``admissible``
    True, ``stale``/``violation`` False).  ``dropped`` marks writes whose
    propagation record found no free pending slot; ``slot`` is the pending
    slot used (Q — out of range — when none)."""

    state: ClusterState
    version: Array      # (B,) int32
    vc: Array           # (B, C) int32 — op clock (receive rule)
    admissible: Array   # (B,) bool
    stale: Array        # (B,) bool
    violation: Array    # (B,) bool
    dropped: Array      # (B,) bool
    slot: Array         # (B,) int32


def clock_chain(
    session_vc: Array, replica_vc: Array, client: Array, replica: Array,
    is_write: Array,
) -> tuple[Array, Array, Array]:
    """The receive rule over a batch, in op order, as a ``lax.scan``:
    ``(session_vc, replica_vc, vcs)`` after the ops, ``vcs`` each op's
    clock.  The CPU path of :func:`apply_op_batch` and the oracle of the
    TPU kernel (``repro.kernels.vclock_chain``)."""
    def clock_step(carry, op):
        svcs, rvcs = carry
        ci, pi, wi = op
        svc = jnp.maximum(svcs[ci], rvcs[pi]).at[ci].add(1)
        svcs = svcs.at[ci].set(svc)
        rvcs = jnp.where(wi, rvcs.at[pi].max(svc), rvcs)
        return (svcs, rvcs), svc

    # Unrolling amortizes the scan's per-step loop overhead — the body
    # is ~tens of scalar ops on two small rows, far below the iteration
    # cost of an un-unrolled lax.scan on CPU.
    (session_vc, replica_vc), vcs = jax.lax.scan(
        clock_step, (session_vc, replica_vc), (client, replica, is_write),
        unroll=8,
    )
    return session_vc, replica_vc, vcs


def apply_op_batch(
    state: ClusterState,
    *,
    client: Array,
    replica: Array,
    resource: Array,
    kind: Array,
    enforce_sessions: bool | Array = True,
    op_index: Array | None = None,
    apply_index: Array | None = None,
    pend_apply: Array | None = None,
    ingest: str | None = None,
    with_clocks: bool = True,
) -> BatchResult:
    """Ingest a batch of ``B`` ops — bit-identical to the scalar loop.

    Applies ``B`` operations (``kind``: READ=0 / WRITE=1) with *exactly*
    the semantics of calling :func:`client_write` / :func:`client_read`
    one op at a time, but vectorized:

      * versions: a per-resource prefix count over the batch assigns each
        write the version the sequential loop would (``global + rank``);
      * floors / served versions: per-(client, resource) prefix maxima
        reproduce the sequential RYW/MR floor evolution, including
        intra-batch same-(client, resource) trains;
      * replica visibility: a write is visible within the batch at its
        coordinator only (per-(replica, resource) prefix max), exactly as
        in the sequential loop between merges;
      * vector clocks: the session/replica clock chaining is inherently
        sequential (each op's clock merges state its predecessors wrote),
        so it runs as a length-B chain over two small rows
        (:func:`clock_chain`; on a TPU the ``vclock_chain`` kernel) —
        every other state component is a closed-form segment/scatter op.

    Merge cadences finer than the batch are injected through the
    closed-form visibility predicate ``op_index(i) >= apply_index(j)``:
    ``apply_index`` (``(B,)`` int32, the store layer's emulated
    sequential apply point per batch write, ``NEVER`` for reads) makes a
    write visible at *every* replica to batch ops from that op index on,
    and ``pend_apply`` (``(Q,)`` int32) does the same for writes still
    in the pending ring from earlier batches.  With ``apply_index=None``
    the batch has plain scalar-loop semantics (coordinator-only
    visibility).  No ``(B, B)`` or ``(B, Q)`` mask crosses this API.

    ``ingest`` picks the prefix-reduction implementation
    (``repro.kernels.ops.op_ingest``): ``"dense"`` (default — the exact
    O(B²)-mask oracle), ``"tiled"`` (jnp block walk, O(B·tile) memory),
    or ``"pallas"`` (the TPU kernel).  All are bit-identical.

    The pending ring matches the sequential loop too: the k-th write of
    the batch takes the k-th free slot (ascending), and writes beyond the
    free capacity are dropped and counted in ``pend_dropped``.

    ``with_clocks=False`` skips the sequential vector-clock scan — the
    one O(B) serial chain of the batch — and leaves ``session_vc``,
    ``replica_vc``, and ``pend_vc`` untouched (``vc`` in the result is
    zeros).  Only valid when nothing downstream consumes the clocks: no
    DUOT registration, no audit, and merges gated on the timed bound
    alone (``server_merge(timed_only=True)``).  Under an emulated
    cadence the served/stale outcomes never read the clocks, so the
    lean batch is metric-identical to the full one.
    """
    from repro.kernels import ops as kernel_ops

    c = jnp.asarray(client, jnp.int32)
    p = jnp.asarray(replica, jnp.int32)
    r = jnp.asarray(resource, jnp.int32)
    k = jnp.asarray(kind, jnp.int32)
    B = c.shape[0]
    Q, P = state.pend_applied.shape

    is_w = k == WRITE
    idx = jnp.arange(B, dtype=jnp.int32)
    pend_kwargs = {}
    if pend_apply is not None:
        pend_kwargs = dict(
            pend_version=state.pend_version,
            pend_resource=state.pend_resource,
            pend_live=state.pend_live,
            pend_apply=jnp.asarray(pend_apply, jnp.int32),
        )
    # The stages below carry ``jax.named_scope`` names, which reach the
    # device trace as the ops' name stacks: ``floors`` (the reads and
    # writes of the flat (C*R,) floors and the (P, R) / (R,) versions),
    # ``op_ingest`` (the prefix kernel), ``vclock_scan`` (the clock
    # chain) and ``pending_ring`` (slot assignment).
    with jax.named_scope("floors"):
        cr = floor_index(state, c, r)
        g0 = state.global_version[r]
        raw0 = state.replica_version[p, r]
        floor0 = jnp.maximum(state.read_floor[cr], state.write_floor[cr])
    with jax.named_scope("op_ingest"):
        occ, raw, floor = kernel_ops.op_ingest(
            c, p, r, is_w, g0, raw0, floor0,
            op_index=op_index,
            apply_index=apply_index,
            impl="dense" if ingest is None else ingest,
            n_clients=state.session_vc.shape[0],
            n_replicas=state.replica_version.shape[0],
            n_resources=state.replica_version.shape[1],
            **pend_kwargs,
        )
    gcur = g0 + occ                              # global version seen by op i
    ver_w = gcur + 1                             # version created IF a write
    verw_masked = jnp.where(is_w, ver_w, 0)

    enforce = jnp.asarray(enforce_sessions, bool)
    adm = raw >= floor
    served = jnp.where(enforce, jnp.maximum(raw, floor), raw)
    violation = (~is_w) & (~enforce) & (~adm)
    stale = (~is_w) & (served < gcur)
    version_out = jnp.where(is_w, ver_w, served)
    admissible = jnp.where(is_w, True, adm)

    # -- vector clocks (exact sequential chaining) ---------------------------
    if with_clocks:
        impl = vclock_chain.resolve_impl(state.session_vc.shape[0], P)
        with jax.named_scope("vclock_scan"):
            chain = (vclock_chain.vclock_chain if impl == "pallas"
                     else clock_chain)
            session_vc, replica_vc, vcs = chain(
                state.session_vc, state.replica_vc, c, p, is_w
            )
    else:
        session_vc = state.session_vc
        replica_vc = state.replica_vc
        vcs = jnp.zeros((B, state.session_vc.shape[1]), jnp.int32)

    # -- pending ring: k-th batch write -> k-th free slot --------------------
    # The k-th-free-slot map is a cumsum rank + scatter (O(Q)), not an
    # argsort (O(Q log Q)): free slot q has rank cumsum(free)[q] - 1
    # among the free slots, so scattering q to its rank inverts the map.
    with jax.named_scope("pending_ring"):
        free = jnp.logical_not(state.pend_live)
        n_free = jnp.sum(free.astype(jnp.int32))
        wrank = jnp.cumsum(is_w.astype(jnp.int32)) - 1
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        kth_free = (
            jnp.zeros((Q,), jnp.int32)
            .at[jnp.where(free, free_rank, Q)]
            .set(jnp.arange(Q, dtype=jnp.int32), mode="drop")
        )
        enq = is_w & (wrank < n_free)
        slot = jnp.where(
            enq, kth_free[jnp.clip(wrank, 0, Q - 1)], jnp.int32(Q)
        )
        dropped = is_w & jnp.logical_not(enq)
        applied0 = jnp.arange(P, dtype=jnp.int32)[None, :] == p[:, None]
        pend_time = state.clock + idx
        ring = dict(
            pend_client=state.pend_client.at[slot].set(c, mode="drop"),
            pend_resource=state.pend_resource.at[slot].set(r, mode="drop"),
            pend_version=state.pend_version.at[slot].set(ver_w, mode="drop"),
            pend_vc=(state.pend_vc.at[slot].set(vcs, mode="drop")
                     if with_clocks else state.pend_vc),
            pend_coord=state.pend_coord.at[slot].set(p, mode="drop"),
            pend_time=state.pend_time.at[slot].set(pend_time, mode="drop"),
            pend_live=state.pend_live.at[slot].set(True, mode="drop"),
            pend_applied=state.pend_applied.at[slot].set(
                applied0, mode="drop"
            ),
            pend_dropped=_saturating_add(
                state.pend_dropped, jnp.sum(dropped.astype(jnp.int32))
            ),
        )

    with jax.named_scope("floors"):
        floors = dict(
            replica_version=state.replica_version.at[p, r].max(verw_masked),
            read_floor=state.read_floor.at[cr].max(
                jnp.where(is_w, ver_w, served)
            ),
            write_floor=state.write_floor.at[cr].max(verw_masked),
            global_version=state.global_version.at[r].max(verw_masked),
        )

    new = state._replace(
        replica_vc=replica_vc,
        session_vc=session_vc,
        clock=state.clock + B,
        **floors,
        **ring,
    )
    return BatchResult(
        state=new, version=version_out, vc=vcs, admissible=admissible,
        stale=stale, violation=violation, dropped=dropped, slot=slot,
    )


def client_write_batch(
    state: ClusterState,
    *,
    client: Array,
    replica: Array,
    resource: Array,
) -> BatchResult:
    """Commit a batch of writes — sequential-equivalent (see
    :func:`apply_op_batch`)."""
    c = jnp.asarray(client, jnp.int32)
    return apply_op_batch(
        state, client=c, replica=replica, resource=resource,
        kind=jnp.full(c.shape, WRITE, jnp.int32),
    )


def client_read_batch(
    state: ClusterState,
    *,
    client: Array,
    replica: Array,
    resource: Array,
    enforce_sessions: bool | Array = True,
) -> BatchResult:
    """Serve a batch of reads — sequential-equivalent (see
    :func:`apply_op_batch`)."""
    c = jnp.asarray(client, jnp.int32)
    return apply_op_batch(
        state, client=c, replica=replica, resource=resource,
        kind=jnp.full(c.shape, READ, jnp.int32),
        enforce_sessions=enforce_sessions,
    )


def server_merge(
    state: ClusterState,
    *,
    delta: Array | int,
    level: ConsistencyLevel = ConsistencyLevel.X_STCC,
    up: Array | None = None,
    link: Array | None = None,
    timed_only: bool = False,
    ready: Array | None = None,
) -> tuple[ClusterState, Array]:
    """Timed-causal propagation step (server side).

    Applies, at every replica, all pending writes that (a) are older than
    Δ, or (b) whose causal predecessors are already applied.  Because
    application is in causal order at every replica, all servers share
    one view (paper: "all servers have the same view of the causality
    relations").

    Implemented as a vectorized fixpoint: every round applies, over all
    Q slots at once, the writes whose gate (overdue OR deps applied) is
    open, then re-evaluates the gates with the updated replica clocks —
    chain-depth rounds instead of Q sequential steps.  The fixpoint is
    the closure of the gate relation; it matches
    :func:`server_merge_sequential` except that a write whose
    dependencies are satisfied by another slot applied in the *same*
    pass always lands in this merge (the one-at-a-time scan picks it up
    this merge only when the enabler sorts first, else next merge).

    ``up`` (``(P,)`` bool) and ``link`` (``(P, P)`` bool, the *closed*
    connectivity of :meth:`repro.core.availability.FaultSchedule.closure`)
    mask the propagation: a pending write reaches replica ``p`` only if
    ``p`` is live and connected to a replica already holding it, and the
    causal gate is evaluated over the write's reachable component
    instead of the whole fleet.  Writes therefore apply *partially*
    under a partition (their slot stays live until every replica has
    them), and a later merge with healed masks catches the stragglers
    up — the anti-entropy pass.  With all-True masks (or ``None``) the
    masked fixpoint is bit-identical to the unmasked one: the reachable
    component is the whole fleet, so gates, rounds, and updates
    coincide.

    ``timed_only=True`` drops the causal-dependency gate: one pass, no
    ``(Q, P, C)`` clock comparison and no fixpoint iteration.  The
    application criterion is ``ready`` (a ``(Q,)`` bool of slots whose
    *emulated* apply point has been reached — the lean engine passes
    ``pend_apply <= ops_done``), falling back to Δ-overdue age when
    ``ready`` is None.  Slots not yet ready stay live — under an
    emulated cadence their visibility is already carried by the
    closed-form apply-index predicates, so the *served* reads are
    unchanged; only the replica clocks lag, which nothing in the lean
    path reads.  Incompatible with ``up``/``link`` masks (the fault
    path always needs the causal gate).

    Returns (state, n_applied) — writes that reached at least one new
    replica this merge.
    """
    del level  # the order is identical; levels differ in *when* merge runs
    assert ready is None or timed_only, "ready requires timed_only"
    d = jnp.asarray(delta, jnp.int32)
    Q, P = state.pend_applied.shape
    C = state.replica_vc.shape[1]
    R = state.global_version.shape[0]
    masked = up is not None or link is not None
    if masked:
        u = (jnp.ones((P,), bool) if up is None
             else jnp.asarray(up, bool))
        ln = (jnp.ones((P, P), bool) if link is None
              else jnp.asarray(link, bool))
        # Holders can only hand a write to live, reachable replicas.
        conn = ln & u[None, :] & u[:, None]

    live = state.pend_live
    overdue = jnp.logical_and(live, (state.clock - state.pend_time) >= d)
    res_safe = jnp.where(live, state.pend_resource, jnp.int32(R))

    if timed_only:
        assert not masked, "timed_only merge cannot take fault masks"
        elig = overdue if ready is None else jnp.logical_and(live, ready)
        elig_at = elig[:, None] & ~state.pend_applied          # (Q, P)
        ver_at = jnp.where(elig_at, state.pend_version[:, None], 0)
        upd = (
            jnp.zeros((R, P), jnp.int32)
            .at[res_safe]
            .max(ver_at, mode="drop")
        )
        applied = state.pend_applied | elig_at
        fully = jnp.all(applied, axis=1)
        new = state._replace(
            replica_version=jnp.maximum(state.replica_version, upd.T),
            pend_applied=applied,
            pend_live=jnp.logical_and(live, jnp.logical_not(fully)),
            clock=state.clock + 1,
        )
        n_applied = jnp.sum(jnp.any(elig_at, axis=1).astype(jnp.int32))
        return new, n_applied

    # A write is applicable at all replicas once its causal deps are
    # stable: its vc (minus its own tick) ≤ every replica's vc.
    own = jnp.arange(C, dtype=jnp.int32)[None, :] == state.pend_client[:, None]
    dep_vc = state.pend_vc - own.astype(jnp.int32)

    def cond_fn(carry):
        return carry[4]

    def body_fn(carry):
        rv, rvc, applied, n, _ = carry
        deps_ok = jnp.all(
            dep_vc[:, None, :] <= rvc[None, :, :], axis=-1
        )                                                   # (Q, P)
        if masked:
            # reach[w, p]: some holder of w can ship it to p this epoch.
            reach = jnp.any(
                applied[:, :, None] & conn[None, :, :], axis=1
            )                                               # (Q, P)
            # The causal gate spans the write's reachable component
            # (deps at already-applied holders hold trivially); with
            # full connectivity this is the all-replica gate.
            gate = jnp.all(jnp.where(reach, deps_ok, True), axis=1)
            elig_at = (
                live[:, None] & ~applied & reach
                & (overdue | gate)[:, None]
            )                                               # (Q, P)
        else:
            done = jnp.all(applied, axis=1)
            elig = live & ~done & (overdue | jnp.all(deps_ok, axis=-1))
            elig_at = elig[:, None] & ~applied
        ver_at = jnp.where(elig_at, state.pend_version[:, None], 0)
        upd = (
            jnp.zeros((R, P), jnp.int32)
            .at[res_safe]
            .max(ver_at, mode="drop")
        )
        rv = jnp.maximum(rv, upd.T)
        vc_new = jnp.max(
            jnp.where(elig_at[:, :, None], state.pend_vc[:, None, :], 0),
            axis=0,
        )                                                   # (P, C)
        rvc = jnp.maximum(rvc, vc_new)
        applied = applied | elig_at
        n = n + jnp.sum(jnp.any(elig_at, axis=1).astype(jnp.int32))
        return (rv, rvc, applied, n, jnp.any(elig_at))

    rv, rvc, applied, n_applied, _ = jax.lax.while_loop(
        cond_fn,
        body_fn,
        (state.replica_version, state.replica_vc, state.pend_applied,
         jnp.zeros((), jnp.int32), jnp.any(live)),
    )
    fully = jnp.all(applied, axis=1)
    new = state._replace(
        replica_version=rv,
        replica_vc=rvc,
        pend_applied=applied,
        pend_live=jnp.logical_and(state.pend_live, jnp.logical_not(fully)),
        clock=state.clock + 1,
    )
    return new, n_applied


def server_merge_geo(
    state: ClusterState,
    *,
    delta: Array | int,
    region: Array,
    n_regions: int,
    rtt_ms: Array,
    level: ConsistencyLevel = ConsistencyLevel.X_STCC,
    up: Array | None = None,
    link: Array | None = None,
) -> tuple[ClusterState, Array, Array]:
    """Two-tier (region-grouped) propagation step.

    Geo-replicated propagation is two-tier: a write crosses the WAN
    *once* per destination region, then fans out over the region's LAN
    — intra-region exchange first, one inter-region hop per (write,
    region) per epoch.  The resulting state is **bit-identical** to
    :func:`server_merge` (the flat fixpoint IS the closure both tiers
    converge to; grouping changes which link carries each delivery, not
    which deliveries happen), so this wrapper runs the flat fixpoint
    for the state and re-derives the per-tier accounting from the
    ``pend_applied`` delta:

      * a (write, replica) delivery lands in region ``h``; if some
        replica of ``h`` already held the write before this merge, the
        copy travels the LAN — an ``(h, h)`` event;
      * otherwise the *first* copy into ``h`` ships across the WAN from
        the nearest region (by ``rtt_ms``, ties → lowest region id)
        that held the write pre-merge — a ``(src, h)`` event — and the
        remaining copies fan out on the LAN.

    ``up``/``link`` masks pass through to the flat fixpoint, so a
    region-severing partition stops the inter-region tier exactly like
    it stops the flat merge, and the attribution meters only the
    deliveries that actually happened.

    Returns ``(state, n_applied, traffic)`` with ``traffic`` a
    ``(G, G)`` int32 matrix of delivery events (one event = one row
    payload shipped from a region-g holder to a region-h replica) —
    the quantity the egress matrix bills per pair (eq. 8, tiered).
    """
    reg = jnp.asarray(region, jnp.int32)
    rtt = jnp.asarray(rtt_ms, jnp.float32)
    G = n_regions
    before = state.pend_applied                           # (Q, P)
    new, n_applied = server_merge(
        state, delta=delta, level=level, up=up, link=link
    )
    newly = jnp.logical_and(new.pend_applied, jnp.logical_not(before))
    onehot = (
        reg[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
    )                                                     # (P, G)
    held = jnp.any(before[:, :, None] & onehot[None], axis=1)      # (Q, G)
    new_in = jnp.sum(
        (newly[:, :, None] & onehot[None]).astype(jnp.int32), axis=1
    )                                                     # (Q, G)
    # First copy into a previously-empty region crosses the WAN from
    # the nearest pre-merge holder region.
    inter = (new_in > 0) & jnp.logical_not(held)          # (Q, G)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    src_cost = jnp.where(held[:, :, None], rtt[None], big)  # (Q, Gsrc, Gdst)
    src = jnp.argmin(src_cost, axis=1).astype(jnp.int32)    # (Q, Gdst)
    dst = jnp.broadcast_to(
        jnp.arange(G, dtype=jnp.int32)[None, :], src.shape
    )
    traffic = (
        jnp.zeros((G, G), jnp.int32)
        .at[src, dst]
        .add(inter.astype(jnp.int32))
    )
    intra = jnp.sum(new_in - inter.astype(jnp.int32), axis=0)      # (G,)
    gi = jnp.arange(G, dtype=jnp.int32)
    traffic = traffic.at[gi, gi].add(intra)
    return new, n_applied, traffic


def server_merge_sequential(
    state: ClusterState,
    *,
    delta: Array | int,
    level: ConsistencyLevel = ConsistencyLevel.X_STCC,
) -> tuple[ClusterState, Array]:
    """Pre-batching merge: one pending slot per ``lax.scan`` step.

    The original engine's propagation pass, kept as the benchmark /
    differential baseline for :func:`server_merge`.  Applies slots one
    at a time in the deterministic causal-extension order, so a write
    whose dependencies are satisfied *by a later-sorted slot in the same
    pass* (the cross-client carrier case) waits one extra merge compared
    to the fixpoint — otherwise the two are identical.
    """
    del level
    d = jnp.asarray(delta, jnp.int32)
    Q, P = state.pend_applied.shape

    due = jnp.logical_and(
        state.pend_live, (state.clock - state.pend_time) >= 0
    )
    overdue = jnp.logical_and(
        state.pend_live, (state.clock - state.pend_time) >= d
    )
    key = vclock.total_order_key(state.pend_vc, state.pend_client)
    key = jnp.where(due, key, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key)

    def apply_one(carry, qi):
        rv, rvc, applied, n = carry
        live = state.pend_live[qi]
        must = overdue[qi]
        dep_vc = state.pend_vc[qi].at[state.pend_client[qi]].add(-1)
        deps_ok = jnp.all(dep_vc[None, :] <= rvc, axis=1)  # (P,)
        do = jnp.logical_and(live, jnp.logical_or(must, jnp.all(deps_ok)))
        r = state.pend_resource[qi]
        ver = state.pend_version[qi]
        rv2 = jnp.where(do, rv.at[:, r].max(ver), rv)
        rvc2 = jnp.where(
            do, jnp.maximum(rvc, state.pend_vc[qi][None, :]), rvc
        )
        applied2 = applied.at[qi].set(
            jnp.where(do, jnp.ones((P,), bool), applied[qi])
        )
        return (rv2, rvc2, applied2, n + do.astype(jnp.int32)), None

    (rv, rvc, applied, n_applied), _ = jax.lax.scan(
        apply_one,
        (state.replica_version, state.replica_vc, state.pend_applied,
         jnp.zeros((), jnp.int32)),
        order,
    )
    fully = jnp.all(applied, axis=1)
    new = state._replace(
        replica_version=rv,
        replica_vc=rvc,
        pend_applied=applied,
        pend_live=jnp.logical_and(state.pend_live, jnp.logical_not(fully)),
        clock=state.clock + 1,
    )
    return new, n_applied


def stability_frontier(state: ClusterState) -> Array:
    """Component-wise min of replica clocks — DUOT GC frontier."""
    return jnp.min(state.replica_vc, axis=0)

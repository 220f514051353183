"""Batched serving engine with session-guarantee-aware replica routing.

The paper's Fig. 2 scenario for model serving: several serving replicas
(pods) each hold a parameter snapshot at some version; request *sessions*
must see monotonically-fresh models (MR) and their own effects (RYW —
e.g. a session that triggered an adapter/weights refresh must see it).
The router implements exactly the X-STCC client-side check: a replica is
admissible for a session iff its version >= the session floor; weaker
levels skip the check and stale serving becomes observable.

All floor/version bookkeeping lives in a
:class:`repro.core.replicated_store.ReplicatedStore` (replicas = snapshot
servers, clients = sessions, the single resource = the model): publishes
are server-side ``install``\\ s, serves are batched session reads, and the
batched router (:meth:`ServingEngine.route_batch`) runs the admission
check through the Pallas session-floor kernel at serving scale.

Consistency is **per session**, not per engine: the engine-level
``level`` is only the default, and :meth:`ServingEngine.set_session_level`
(or an attached :class:`repro.policy.AdaptiveController`, via
:meth:`~ServingEngine.attach_controller` / :meth:`~ServingEngine.adapt_sessions`)
moves individual sessions between consistency levels while they share
the one replicated store — the serving half of the adaptive consistency
control plane.

The compute path (prefill/decode) is the model substrate; this module
owns the jit'd step functions and the routing/bookkeeping.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.consistency import ConsistencyLevel
from repro.core.replicated_store import ReplicatedStore, ShardedStore
from repro.models.model_zoo import Model
from repro.obs.metrics import HostHistogram

Array = jax.Array


@dataclasses.dataclass
class ServeSession:
    session_id: int
    read_floor: int = 0  # min model version this session may observe


@dataclasses.dataclass
class ReplicaSnapshot:
    params: Any
    version: int


class ServeTimeout(RuntimeError):
    """A request exhausted its retry/backoff budget without a serve."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Client-side retry/timeout/backoff contract for routed serves.

    A request that cannot be admitted (no live replica, or no replica
    fresh enough for the session's floor — e.g. its home replica is
    mid-rebuild after a crash) waits out a **jittered exponential
    backoff** and retries, up to ``max_retries`` attempts or until the
    cumulative simulated wait would exceed ``timeout_ms``.  When the
    budget runs out, ``degrade=True`` admits the request once in
    **degraded mode** — the freshest live replica with floor
    enforcement off, i.e. a temporary fallback to an unguarded level —
    and ``degrade=False`` raises :class:`ServeTimeout`.

    Waits are *simulated* (accumulated in the engine's
    ``retry_wait_ms`` telemetry, never slept), so retry behavior is
    deterministic per ``seed`` and free to test.
    """

    max_retries: int = 3
    base_backoff_ms: float = 5.0
    backoff_mult: float = 2.0
    jitter: float = 0.5
    timeout_ms: float = 1000.0
    degrade: bool = True
    seed: int = 0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.base_backoff_ms <= 0 or self.backoff_mult < 1.0:
            raise ValueError(
                "base_backoff_ms must be > 0 and backoff_mult >= 1"
            )
        if not 0.0 <= self.jitter < 1.0:
            raise ValueError("jitter must be in [0, 1)")

    def backoff_ms(self, attempt: int, rng: np.random.Generator) -> float:
        """The jittered wait before retry ``attempt`` (0-indexed)."""
        base = self.base_backoff_ms * self.backoff_mult ** attempt
        if self.jitter:
            base *= 1.0 + self.jitter * float(rng.uniform(-1.0, 1.0))
        return base


class ServingEngine:
    def __init__(
        self,
        model: Model,
        level: ConsistencyLevel = ConsistencyLevel.X_STCC,
        jit: bool = True,
        max_replicas: int = 8,
        max_sessions: int = 64,
    ):
        self.model = model
        self.level = level
        self.replicas: list[ReplicaSnapshot] = []
        self.max_replicas = max_replicas
        self.max_sessions = max_sessions
        self.stale_serves = 0
        self.total_serves = 0
        self.reroutes = 0
        self.failovers = 0
        # Retry/backoff telemetry (serve_with_retry).
        self.retries = 0
        self.timeouts = 0
        self.downgrades = 0
        self.retry_wait_ms = 0.0
        # Replica liveness (NodeHealth-driven): down replicas are
        # inadmissible for every session and requests fail over.
        self.replica_up = np.ones(max_replicas, bool)
        # Crash-recovery: a replica that is restoring/bootstrapping is
        # reachable but serves nothing until finish_rebuilding().
        self.replica_rebuilding = np.zeros(max_replicas, bool)
        # Region-aware routing (set_topology): replica→region map, RTT
        # matrix, per-session region assignment, per-region telemetry.
        self._topology = None
        self._session_region: np.ndarray | None = None
        self._rtt_np: np.ndarray | None = None
        self._replica_region_np: np.ndarray | None = None
        self._region_stale: np.ndarray | None = None
        self._region_serves: np.ndarray | None = None
        self._region_lat_ms: np.ndarray | None = None
        self._region_hist: list[HostHistogram] | None = None
        # Per-session overrides of the engine default, plus per-session
        # serve telemetry (stale/violation/serve counts since the last
        # controller consultation) feeding `adapt_sessions`.
        self.session_levels: dict[int, ConsistencyLevel] = {}
        self._sess_stale = np.zeros(max_sessions, np.int64)
        self._sess_viol = np.zeros(max_sessions, np.int64)
        self._sess_serves = np.zeros(max_sessions, np.int64)
        self._controller = None
        self._ctl_state = None
        self._ctl_key = None
        self._store = ReplicatedStore(
            max_replicas, max_sessions, 1, level=level,
            pending_cap=max_sessions,
        )
        self._st = self._store.init()
        if jit:
            self._prefill = jax.jit(model.prefill)
            self._decode = jax.jit(model.decode_step)
        else:
            self._prefill = model.prefill
            self._decode = model.decode_step

    def _sid(self, session: ServeSession) -> int:
        if session.session_id >= self.max_sessions:
            # Silent modular aliasing would make colliding sessions
            # share one floor, breaking per-session MR/RYW.
            raise RuntimeError(
                f"session_id {session.session_id} >= max_sessions "
                f"{self.max_sessions}; raise max_sessions"
            )
        return session.session_id

    # -- replica management -----------------------------------------------------

    def publish(self, params, version: int, replica: int | None = None):
        """Install a parameter snapshot on one replica (or append new)."""
        snap = ReplicaSnapshot(params=params, version=version)
        if replica is None or replica >= len(self.replicas):
            if len(self.replicas) >= self.max_replicas:
                raise RuntimeError(
                    f"more than max_replicas={self.max_replicas} replicas"
                )
            self.replicas.append(snap)
            replica = len(self.replicas) - 1
        else:
            self.replicas[replica] = snap
        self._st = self._store.install(
            self._st, replica=replica, resource=0, version=version
        )

    def publish_everywhere(self, params, version: int):
        for r in range(len(self.replicas)):
            self.replicas[r] = ReplicaSnapshot(params, version)
            self._st = self._store.install(
                self._st, replica=r, resource=0, version=version
            )

    @property
    def latest_version(self) -> int:
        return max((r.version for r in self.replicas), default=0)

    # -- replica health -----------------------------------------------------------

    def set_replica_health(self, health) -> None:
        """Drive the liveness mask from a health source.

        ``health`` is either a ``repro.runtime.NodeHealth`` (its
        ``alive()`` vector is consumed) or a boolean sequence/array of
        per-replica liveness.  Down replicas become inadmissible in
        :meth:`route` / :meth:`route_batch` and requests fail over.
        """
        if hasattr(health, "alive"):
            health = health.alive()
        up = np.asarray(health, bool)
        if up.shape[0] > self.max_replicas:
            raise ValueError(
                f"health covers {up.shape[0]} replicas, engine has "
                f"max_replicas={self.max_replicas}"
            )
        self.replica_up[: up.shape[0]] = up

    def fail_replica(self, replica: int) -> None:
        self.replica_up[replica] = False

    def heal_replica(self, replica: int) -> None:
        self.replica_up[replica] = True

    def mark_rebuilding(self, replica: int) -> None:
        """Take a replica out of serving while it restores/bootstraps.

        The crash-recovery client: a replica that crashed is *up*
        (reachable for gossip/bootstrap) but must not serve until its
        state is rebuilt — requests targeting it fail over exactly like
        a down replica's would.
        """
        self.replica_rebuilding[replica] = True

    def finish_rebuilding(self, replica: int) -> None:
        """Re-admit a rebuilt replica into serving."""
        self.replica_rebuilding[replica] = False

    def _up(self) -> np.ndarray:
        """Serving-admissible mask: live and not mid-rebuild."""
        n = len(self.replicas)
        up = self.replica_up[:n] & ~self.replica_rebuilding[:n]
        if not up.any():
            raise RuntimeError("no live replica to serve from")
        return up

    # -- region-aware routing -------------------------------------------------------

    def set_topology(self, topology, session_region=None) -> None:
        """Make routing region-aware.

        ``topology`` is a :class:`repro.geo.topology.RegionTopology`
        whose replica map covers this engine's replica slots; sessions
        are pinned to regions by ``session_region`` (any sequence,
        defaulting to the topology's client-population assignment).
        From then on a session's default target is the **nearest live
        replica by RTT** from its region — replacing the
        ``session_id % n`` spread — reroutes prefer the nearest
        admissible replica, and per-region latency/staleness telemetry
        accumulates (:meth:`region_stats`).
        """
        if topology.n_replicas < self.max_replicas:
            raise ValueError(
                f"topology places {topology.n_replicas} replicas, engine "
                f"has max_replicas={self.max_replicas}"
            )
        if session_region is None:
            reg = topology.client_region_of(np.arange(self.max_sessions))
        else:
            reg = np.asarray(session_region, np.int32)
            if reg.shape[0] != self.max_sessions:
                raise ValueError(
                    f"session_region covers {reg.shape[0]} sessions, "
                    f"engine has {self.max_sessions}"
                )
        self._topology = topology
        self._session_region = reg.astype(np.int32)
        # Dense views of the topology tuples, converted once: the geo
        # routing paths argmin over these on every request.
        self._rtt_np = np.asarray(topology.rtt_ms, np.float64)
        self._replica_region_np = topology.regions()
        g = topology.n_regions
        self._region_stale = np.zeros(g, np.int64)
        self._region_serves = np.zeros(g, np.int64)
        self._region_lat_ms = np.zeros(g, np.float64)
        # Per-region serve-latency distributions on the shared obs
        # histogram primitive; RTTs are bounded by the matrix, so the
        # top bin saturates only if the topology is later mutated.
        lat_hi = max(1.0, float(self._rtt_np.max()) * 1.5)
        self._region_hist = [HostHistogram(0.0, lat_hi) for _ in range(g)]

    def _geo_rtts(self, session_ids, n: int) -> np.ndarray:
        """(B, n) RTT from each session's region to replicas ``0..n-1``.

        One matrix gather for the whole batch — the geo routing paths
        below are all argmins over rows of this.
        """
        sregs = self._session_region[np.asarray(session_ids, np.int64)]
        return self._rtt_np[sregs][:, self._replica_region_np[:n]]

    def _geo_preferred(self, session_id: int, n: int) -> int:
        """Nearest replica by RTT from the session's region.

        Deliberately liveness-*ignorant*: this is the session's natural
        target, so a down nearest replica registers as a failover (the
        PR-4 counting contract) before routing falls over to the
        nearest live replica.
        """
        return int(np.argmin(self._geo_rtts([session_id], n)[0]))

    def _geo_failover(self, session_id: int, up: np.ndarray) -> int:
        """Nearest *live* replica by RTT from the session's region."""
        rtts = self._geo_rtts([session_id], up.shape[0])[0]
        return int(np.argmin(np.where(up, rtts, np.inf)))

    def _geo_reroute(
        self, session_id: int, floor: int, up: np.ndarray
    ) -> int:
        """Nearest live *admissible* replica; freshest live fallback."""
        versions = np.asarray([r.version for r in self.replicas])
        adm = up & (versions >= floor)
        if not adm.any():
            return _freshest_replica(self.replicas, up)
        rtts = self._geo_rtts([session_id], up.shape[0])[0]
        return int(np.argmin(np.where(adm, rtts, np.inf)))

    def _note_serve(self, session_id: int, replica: int, stale: int) -> None:
        """Per-region serve telemetry (no-op without a topology)."""
        if self._topology is None:
            return
        sreg = int(self._session_region[session_id])
        rreg = int(self._replica_region_np[replica])
        self._region_serves[sreg] += 1
        self._region_stale[sreg] += stale
        lat = float(self._rtt_np[sreg, rreg])
        self._region_lat_ms[sreg] += lat
        self._region_hist[sreg].observe([lat])

    def region_stats(self) -> dict[str, list[float]]:
        """Per-region serving telemetry (requires :meth:`set_topology`).

        Latency is the RTT-matrix distance between the session's region
        and the replica that served it — the serving-side replacement
        of the two-value ``ack_latency_ms`` step function.  Percentiles
        come from per-region fixed-bin histograms (the shared obs
        primitive), so a failover burst that reroutes the slowest few
        percent of serves moves ``p99_latency_ms`` while
        ``p50_latency_ms`` holds — the mean alone can't show that.
        """
        if self._topology is None:
            raise RuntimeError("no topology set (call set_topology)")
        serves = np.maximum(1, self._region_serves)
        return {
            "serves": self._region_serves.tolist(),
            "stale": self._region_stale.tolist(),
            "staleness_rate": (self._region_stale / serves).tolist(),
            "mean_latency_ms": (self._region_lat_ms / serves).tolist(),
            "p50_latency_ms": [h.percentile(50) for h in self._region_hist],
            "p99_latency_ms": [h.percentile(99) for h in self._region_hist],
        }

    # -- per-session consistency ---------------------------------------------------

    def level_for(self, session_id: int) -> ConsistencyLevel:
        """The session's effective consistency level (default: engine's)."""
        return self.session_levels.get(session_id, self.level)

    def set_session_level(self, session_id: int, level: ConsistencyLevel):
        """Move one session to a different consistency level online."""
        if session_id >= self.max_sessions:
            raise RuntimeError(
                f"session_id {session_id} >= max_sessions {self.max_sessions}"
            )
        self.session_levels[session_id] = level

    def attach_controller(self, controller, key: Array | None = None):
        """Hand per-session level selection to an adaptive controller.

        ``controller`` is a :class:`repro.policy.AdaptiveController`
        sized to this engine's ``max_sessions``; call
        :meth:`adapt_sessions` once per serving epoch to fold the
        accumulated telemetry and re-select levels.
        """
        if controller.n_sessions != self.max_sessions:
            raise ValueError(
                f"controller sized for {controller.n_sessions} sessions, "
                f"engine has {self.max_sessions}"
            )
        if self.level not in controller.levels:
            raise ValueError(
                f"engine default level {self.level} not among controller "
                f"levels {controller.levels}"
            )
        self._controller = controller
        self._ctl_state = controller.init()
        self._ctl_key = jax.random.PRNGKey(0) if key is None else key

    def adapt_sessions(self) -> dict[int, ConsistencyLevel]:
        """One control-plane epoch: observe serve telemetry, re-select.

        Serving is a read-only workload, so ``read_frac`` is 1 and the
        violation telemetry comes from unguarded sessions observing
        reads below their floor.  Returns the new assignment.
        """
        if self._controller is None:
            raise RuntimeError("no controller attached")
        ctl = self._controller
        idx_list = []
        for s in range(self.max_sessions):
            lv = self.level_for(s)
            if lv not in ctl.levels:
                raise RuntimeError(
                    f"session {s} is at level {lv.value}, which is not "
                    f"among the controller's levels "
                    f"{[l.value for l in ctl.levels]}; use "
                    "set_session_level with a controller level (or a "
                    "controller whose level set covers it)"
                )
            idx_list.append(ctl.levels.index(lv))
        idx = jnp.asarray(idx_list, jnp.int32)
        self._ctl_state = ctl.observe(
            self._ctl_state,
            level_idx=idx,
            stale=jnp.asarray(self._sess_stale, jnp.float32),
            viol=jnp.asarray(self._sess_viol, jnp.float32),
            reads=jnp.asarray(self._sess_serves, jnp.float32),
        )
        self._ctl_key, sub = jax.random.split(self._ctl_key)
        choice = np.asarray(ctl.select(self._ctl_state, sub, read_frac=1.0))
        self._sess_stale[:] = 0
        self._sess_viol[:] = 0
        self._sess_serves[:] = 0
        for sid in range(self.max_sessions):
            self.session_levels[sid] = ctl.levels[int(choice[sid])]
        return dict(self.session_levels)

    # -- routing ------------------------------------------------------------------

    def session_floor(self, session: ServeSession) -> int:
        """MR/RYW floor: store-tracked, joined with any external floor."""
        floor = int(self._store.session_floor(self._st, self._sid(session), 0))
        return max(floor, session.read_floor)

    def route(self, session: ServeSession, preferred: int | None = None) -> int:
        """Pick a replica for this session per *its* consistency level.

        A down replica is inadmissible for every session regardless of
        level: the request fails over to the freshest live replica —
        the same target :meth:`route_batch` picks, so the scalar and
        batched paths route identical traffic identically — counted in
        ``failovers`` and ``reroutes``; the session floors are then
        checked against the failover target.
        """
        n = len(self.replicas)
        if n == 0:
            raise RuntimeError("no replicas published")
        up = self._up()
        if preferred is not None:
            idx = preferred % n
        elif self._topology is not None:
            # Region-aware default: nearest replica by RTT.  Liveness
            # is checked below, so a down nearest replica still counts
            # as a failover.
            idx = self._geo_preferred(session.session_id, n)
        else:
            idx = session.session_id % n
        failed_over = not up[idx]
        if failed_over:
            idx = (
                self._geo_failover(session.session_id, up)
                if self._topology is not None
                else _freshest_replica(self.replicas, up)
            )
            self.failovers += 1
            self.reroutes += 1
        if self.level_for(session.session_id).is_session_guarded:
            floor = self.session_floor(session)
            if self.replicas[idx].version < floor:
                best = (
                    self._geo_reroute(session.session_id, floor, up)
                    if self._topology is not None
                    else _freshest_replica(self.replicas, up)
                )
                if self.replicas[best].version < floor:
                    raise RuntimeError("no admissible replica for session")
                # Reroute to the freshest live admissible replica
                # (MR/RYW); a down+inadmissible serve still counts one
                # reroute, like the batched path's single ~ok.
                if not failed_over:
                    self.reroutes += 1
                idx = best
        return idx

    def serve_with_retry(
        self,
        session: ServeSession,
        preferred: int | None = None,
        policy: RetryPolicy | None = None,
    ) -> int:
        """Route-and-observe one serve under a retry/backoff policy.

        Attempts :meth:`route` + the observe read; an inadmissible
        request (no live replica, or no replica fresh enough for the
        session's floor — e.g. the home replica is mid-rebuild after a
        crash) backs off per ``policy`` and retries.  When the retry
        budget or ``timeout_ms`` runs out: ``policy.degrade`` admits
        the request once on the freshest live replica with floor
        enforcement off (counted in ``downgrades``, and the serve's
        staleness lands in the normal telemetry); otherwise the request
        fails with :class:`ServeTimeout` (counted in ``timeouts``).
        Waits are simulated — accumulated in ``retry_wait_ms`` — so
        the path is deterministic per ``policy.seed`` and session.
        Returns the replica that served.
        """
        if policy is None:
            policy = RetryPolicy()
        rng = np.random.default_rng(
            policy.seed + self._sid(session)
        )
        waited = 0.0
        last_err: RuntimeError | None = None
        for attempt in range(policy.max_retries + 1):
            try:
                r = self.route(session, preferred)
                self._observe(session, r)
                return r
            except RuntimeError as e:
                last_err = e
            if attempt >= policy.max_retries:
                break
            wait = policy.backoff_ms(attempt, rng)
            if waited + wait > policy.timeout_ms:
                break
            waited += wait
            self.retries += 1
            self.retry_wait_ms += wait
        if policy.degrade:
            n = len(self.replicas)
            live = self.replica_up[:n] & ~self.replica_rebuilding[:n]
            if n and live.any():
                r = _freshest_replica(self.replicas, live)
                self.downgrades += 1
                self._observe(session, r, enforce=False)
                return r
        self.timeouts += 1
        raise ServeTimeout(
            f"session {session.session_id}: no admissible replica after "
            f"{policy.max_retries} retries ({waited:.1f} ms backoff)"
        ) from last_err

    def route_batch(
        self, sessions: list[ServeSession], preferred: Array | None = None,
        use_kernel: bool = True,
    ) -> tuple[Array, Array]:
        """Vectorized admission check for a batch of sessions.

        Routes every session to its preferred replica, runs the batched
        session-floor admission check (the Pallas kernel when
        ``use_kernel``), reroutes inadmissible *session-guarded*
        sessions to the freshest live replica (unguarded sessions take
        the stale serve, which is counted as their violation telemetry),
        fails sessions whose preferred replica is down over to the
        freshest live replica, and registers the serves in the store.
        Returns ``(replica_indices, served_versions)``.
        """
        n = len(self.replicas)
        if n == 0:
            raise RuntimeError("no replicas published")
        up = self._up()
        sid = jnp.asarray([self._sid(s) for s in sessions], jnp.int32)
        geo_rtts = (
            self._geo_rtts(np.asarray(sid), n)
            if self._topology is not None else None
        )
        if preferred is None:
            if geo_rtts is not None:
                # Nearest replica by RTT, liveness-ignorant — a down
                # nearest replica counts as a failover below.
                preferred = jnp.asarray(
                    np.argmin(geo_rtts, axis=1), jnp.int32
                )
            else:
                preferred = jnp.asarray(
                    [s.session_id % n for s in sessions], jnp.int32
                )
        preferred = jnp.asarray(preferred, jnp.int32) % n
        guarded = jnp.asarray(
            [self.level_for(s.session_id).is_session_guarded
             for s in sessions],
            bool,
        )
        alive = jnp.asarray(up)[preferred]
        best = _freshest_replica(self.replicas, up)
        if bool(jnp.any(guarded)):
            # Admission against the store-tracked floors (the Pallas
            # kernel path); the returned state is discarded on purpose —
            # floors are only committed by the observe step below, after
            # rerouting decides where each session actually reads.
            _, _, adm = self._store.admit_batch(
                self._st, client=sid, replica=preferred,
                resource=jnp.zeros(sid.shape, jnp.int32),
                use_kernel=use_kernel,
            )
            # Join with any externally-set session floor (route() parity).
            ext = jnp.asarray(
                [s.read_floor for s in sessions], jnp.int32
            )
            versions = jnp.asarray(
                [r.version for r in self.replicas], jnp.int32
            )
            adm = jnp.logical_and(adm, versions[preferred] >= ext)
            adm = jnp.logical_or(adm, ~guarded)
            ok = adm & alive
            floor = jnp.maximum(
                self._store.session_floor(self._st, sid, 0), ext
            )
            if geo_rtts is not None:
                # Per-session reroute target: nearest live admissible
                # replica (freshest live when none admits) — one
                # masked argmin over the precomputed (B, n) RTT rows.
                # Unguarded sessions ignore floors: their only reroute
                # cause is a dead replica, and the target is the
                # nearest live replica — exactly what route() picks,
                # keeping the scalar/batch routing parity.
                adm_at = np.asarray(up)[None, :] & (
                    np.asarray(versions)[None, :]
                    >= np.asarray(floor)[:, None]
                )
                adm_at = np.where(
                    np.asarray(guarded)[:, None], adm_at,
                    np.asarray(up)[None, :],
                )
                target = np.where(
                    adm_at.any(axis=1),
                    np.argmin(np.where(adm_at, geo_rtts, np.inf), axis=1),
                    best,
                )
                best = jnp.asarray(target, jnp.int32)
            if bool(jnp.any(guarded & ~ok & (versions[best] < floor))):
                raise RuntimeError("no admissible replica for session")
        else:
            ok = alive
            if geo_rtts is not None:
                best = jnp.asarray(
                    np.argmin(
                        np.where(np.asarray(up)[None, :n], geo_rtts, np.inf),
                        axis=1,
                    ),
                    jnp.int32,
                )
        replica = jnp.where(ok, preferred, best)
        self.reroutes += int(jnp.sum(~ok))
        self.failovers += int(jnp.sum(~alive))
        served = self._observe_batch(sessions, replica, guarded)
        return replica, served

    def _observe_batch(
        self, sessions: list[ServeSession], replica: Array,
        guarded: Array | None = None,
    ):
        sid = jnp.asarray([self._sid(s) for s in sessions], jnp.int32)
        if guarded is None:
            guarded = jnp.asarray(
                [self.level_for(s.session_id).is_session_guarded
                 for s in sessions],
                bool,
            )
        self._st, res = self._store.read_batch(
            self._st, client=sid, replica=jnp.asarray(replica, jnp.int32),
            resource=jnp.zeros(sid.shape, jnp.int32), record=False,
            enforce=guarded,
        )
        self.total_serves += len(sessions)
        self.stale_serves += int(jnp.sum(res.stale))
        sid_np = np.asarray(sid)
        np.add.at(self._sess_stale, sid_np, np.asarray(res.stale))
        np.add.at(self._sess_viol, sid_np, np.asarray(res.violation))
        np.add.at(self._sess_serves, sid_np, 1)
        if self._topology is not None:
            sregs = self._session_region[sid_np]
            rregs = self._replica_region_np[np.asarray(replica)]
            lat = self._rtt_np[sregs, rregs]
            np.add.at(self._region_serves, sregs, 1)
            np.add.at(
                self._region_stale, sregs,
                np.asarray(res.stale).astype(np.int64),
            )
            np.add.at(self._region_lat_ms, sregs, lat)
            for g in np.unique(sregs):
                self._region_hist[g].observe(lat[sregs == g])
        for s, v in zip(sessions, list(res.version)):
            s.read_floor = max(s.read_floor, int(v))
        return res.version

    def _observe(
        self, session: ServeSession, replica: int,
        enforce: bool | None = None,
    ):
        # Telemetry comes from the store's read result — the same
        # source `_observe_batch` uses, so the scalar and batched
        # routing paths can never disagree about one serve (the old
        # python-side `version < latest_version` check diverged from
        # the store under enforcement and snapshot overwrites).
        # ``enforce`` overrides the session level's guard — the
        # degraded-admission path serves guarded sessions unguarded.
        if enforce is None:
            enforce = self.level_for(session.session_id).is_session_guarded
        self._st, res = self._store.read_batch(
            self._st,
            client=jnp.asarray([self._sid(session)], jnp.int32),
            replica=jnp.asarray([replica], jnp.int32),
            resource=jnp.zeros((1,), jnp.int32),
            record=False,
            enforce=enforce,
        )
        self.total_serves += 1
        self.stale_serves += int(res.stale[0])
        sid = self._sid(session)
        self._sess_stale[sid] += int(res.stale[0])
        self._sess_viol[sid] += int(res.violation[0])
        self._sess_serves[sid] += 1
        self._note_serve(sid, replica, int(res.stale[0]))
        session.read_floor = max(session.read_floor, int(res.version[0]))

    # -- compute ---------------------------------------------------------------

    def prefill(self, session: ServeSession, batch: dict,
                preferred: int | None = None):
        r = self.route(session, preferred)
        self._observe(session, r)
        logits, cache = self._prefill(self.replicas[r].params, batch)
        return logits, cache, r

    def decode(self, session: ServeSession, cache, tokens,
               replica: int):
        """Decode continues on the session's bound replica (KV cache
        affinity); version floors were checked at prefill.  A decode
        step is not a routed serve: it never counts toward
        ``total_serves`` (a serve is counted once per routed request,
        so the engine-level ``staleness_rate`` and the per-session
        telemetry share one denominator)."""
        return self._decode(self.replicas[replica].params, cache, tokens)

    def generate(self, session: ServeSession, batch: dict, n_tokens: int,
                 preferred: int | None = None):
        """Greedy generation helper for examples/tests."""
        logits, cache, r = self.prefill(session, batch, preferred)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out = [tok]
        for _ in range(n_tokens - 1):
            logits, cache = self.decode(session, cache, tok, r)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            out.append(tok)
        return jnp.concatenate(out, axis=1), r

    # -- metrics -----------------------------------------------------------------

    def staleness_rate(self) -> float:
        return self.stale_serves / max(1, self.total_serves)


def _freshest_replica(
    replicas: list[ReplicaSnapshot], up: np.ndarray | None = None
) -> int:
    """Freshest replica, restricted to live ones when ``up`` is given."""
    live = range(len(replicas)) if up is None else [
        r for r in range(len(replicas)) if up[r]
    ]
    return max(live, key=lambda r: replicas[r].version)


class ShardedServingRouter:
    """Device-sharded admission front door for multi-tenant serving.

    Partitions the session space into ``n_shards`` disjoint tenant
    groups of ``sessions_per_shard`` sessions; each shard owns a full
    replicated store (snapshot replicas × shard sessions × the one
    model resource) stacked along a leading axis
    (:class:`repro.core.replicated_store.ShardedStore`), so the
    admission check, reroute, and floor bookkeeping of a whole
    ``(S, B)`` shard-aligned request batch run as one vmapped program —
    on a multi-device host the shard axis lays out across the device
    mesh exactly like :func:`repro.storage.simulator.run_protocol_sharded`.

    Serving batches are read-only, so disjoint session shards share no
    floor state: routing an ``(S, B)`` batch here is bit-identical to
    routing the concatenated ``S·B`` sessions through one unsharded
    :class:`ServingEngine` (``tests/test_op_ingest.py`` asserts it).
    """

    def __init__(
        self,
        n_shards: int,
        sessions_per_shard: int,
        max_replicas: int = 8,
        level: ConsistencyLevel = ConsistencyLevel.X_STCC,
        age_hi: float = 1024.0,
    ):
        self.n_shards = n_shards
        self.sessions_per_shard = sessions_per_shard
        self.max_replicas = max_replicas
        self.level = level
        self._sharded = ShardedStore(
            ReplicatedStore(
                max_replicas, sessions_per_shard, 1, level=level,
                pending_cap=max(8, sessions_per_shard),
            ),
            n_shards,
        )
        self._st = self._sharded.init()
        self._versions = np.zeros(max_replicas, np.int64)
        self.replica_up = np.ones(max_replicas, bool)
        self.n_replicas = 0
        self.total_serves = 0
        self.stale_serves = 0
        self.reroutes = 0
        self.failovers = 0
        # Staleness-age distribution of every routed serve (latest
        # published version minus served version, in versions).
        self._age_hist = HostHistogram(0.0, float(age_hi))

    def set_replica_health(self, health) -> None:
        """Drive the liveness mask (``NodeHealth`` or a bool vector)."""
        if hasattr(health, "alive"):
            health = health.alive()
        up = np.asarray(health, bool)
        if up.shape[0] > self.max_replicas:
            raise ValueError(
                f"health covers {up.shape[0]} replicas, router has "
                f"max_replicas={self.max_replicas}"
            )
        self.replica_up[: up.shape[0]] = up

    def install(self, replica: int, version: int):
        """Publish a snapshot version on one replica — to every shard.

        Replica ids must be dense (install ``0..n`` in order, or
        overwrite an existing one) — the routing modulus spans
        ``n_replicas``, and a gap would let sessions land on a replica
        that never published (the unsharded engine appends snapshots,
        so it cannot have gaps either).
        """
        if replica >= self.max_replicas:
            raise RuntimeError(
                f"replica {replica} >= max_replicas {self.max_replicas}"
            )
        if replica > self.n_replicas:
            raise RuntimeError(
                f"replica ids must be dense: install replica "
                f"{self.n_replicas} before {replica}"
            )
        self._st = self._sharded.install(
            self._st, replica=replica, resource=0, version=version
        )
        self._versions[replica] = max(self._versions[replica], version)
        self.n_replicas = max(self.n_replicas, replica + 1)

    def route(
        self, session: Array, preferred: Array | None = None
    ) -> tuple[Array, Array]:
        """Route one ``(S, B)`` batch of shard-local session ids.

        Admission against each shard's store floors, reroute of
        inadmissible sessions to the freshest replica (the engine-level
        ``route_batch`` semantics), then the batched observe read that
        raises the floors.  Returns ``(replica, served)`` as ``(S, B)``
        arrays.
        """
        if self.n_replicas == 0:
            raise RuntimeError("no replicas published")
        up = self.replica_up[: self.n_replicas]
        if not up.any():
            raise RuntimeError("no live replica to serve from")
        sid = jnp.asarray(session, jnp.int32)
        if preferred is None:
            preferred = sid % self.n_replicas
        preferred = jnp.asarray(preferred, jnp.int32) % self.n_replicas
        alive = jnp.asarray(up)[preferred]
        # Freshest *live* replica is the failover / reroute target.
        best = int(np.argmax(np.where(up, self._versions[: self.n_replicas],
                                      -1)))

        guarded = self.level.is_session_guarded
        if guarded:
            def admit(st, s, pref):
                floor = self._sharded.store.session_floor(st, s, 0)
                return st.cluster.replica_version[pref, 0] >= floor, floor

            adm, floor = jax.vmap(admit)(self._st, sid, preferred)
            ok = adm & alive
            if bool(jnp.any(~ok & (self._versions[best] < floor))):
                raise RuntimeError("no admissible replica for session")
            replica = jnp.where(ok, preferred, best)
            self.reroutes += int(jnp.sum(~ok))
        else:
            # A failover is a reroute too — same counting as the
            # unsharded engine for identical traffic.
            replica = jnp.where(alive, preferred, best)
            self.reroutes += int(jnp.sum(~alive))
        self.failovers += int(jnp.sum(~alive))
        self._st, res = self._sharded.read_batch(
            self._st, client=sid, replica=replica,
            resource=jnp.zeros(sid.shape, jnp.int32), record=False,
            enforce=guarded,
        )
        self.total_serves += int(sid.size)
        self.stale_serves += int(jnp.sum(res.stale))
        ages = self._versions[: self.n_replicas].max() - np.asarray(
            res.version, np.int64
        )
        self._age_hist.observe(np.maximum(ages, 0).ravel())
        return replica, res.version

    def age_stats(self) -> dict[str, float]:
        """Staleness-age distribution of every serve routed so far.

        Age is how many published versions the served snapshot lagged
        the freshest replica at serve time; percentiles come from the
        shared obs histogram primitive, so a failover that pins a
        tenant group on a stale snapshot shows up as a p99 spike while
        the p50 (the healthy majority) holds.
        """
        return {
            "serves": int(self._age_hist.count),
            "p50_age": self._age_hist.percentile(50),
            "p99_age": self._age_hist.percentile(99),
        }

    def staleness_rate(self) -> float:
        return self.stale_serves / max(1, self.total_serves)

"""Auditing strategy — paper §3.3 and the five-phase flowchart (§3.4).

Given the DUOT, every ordered pair of live operations ``(o1, o2)`` with
``T(o1) < T(o2)`` on the same resource is classified (paper eq. 1a–1d and
Fig. 4) and checked for the consistency guarantee the pair falls under:

  same client, o1 -> o2:
    a1  R,R  monotonic read       (MR)
    a2  W,W  monotonic write      (MW)
    a3  W,R  read-your-write      (RYW)
    a4  R,W  write-follows-read   (WFR)
  different clients, o1 -> o2:
    b1       timed causal         (TCC, server side)
  no happens-before (same or different clients):
    b2       concurrent — conflict-resolved by the deterministic linear
             extension (LWW on (clock-sum, client)); never a violation by
             itself.

Violation semantics on versions (monotone per resource; a read's
``version`` is the version it returned, a write's the version it created):

  MR  violated  iff version(o2) <  version(o1)   (read went backwards)
  MW  violated  iff version(o2) <= version(o1)   (writes applied out of order)
  RYW violated  iff version(o2) <  version(o1)   (own write not visible)
  WFR violated  iff version(o2) <= version(o1)   (write not ordered after read)
  TCC violated  iff o1 is a write, o1 -> o2, and o2 (a read) returned an
                 older version — a causally-preceding write was invisible.
  TIMED violated iff seq(o2) - seq(o1) > delta and o2 still missed o1's
                 write — the propagation exceeded the timed bound Δ
                 (Torres-Rojas timed consistency; the "T" in X-STCC).

The dense pairwise pass is the O(m^2·n) hot-spot; a tiled Pallas TPU
kernel with an identical contract lives in ``repro.kernels.vclock_audit``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import vector_clock as vclock
from repro.core.duot import Duot, READ, WRITE

Array = jax.Array

# Phase codes (paper Fig. 4).
PHASE_NONE = 0
PHASE_A1_MR = 1
PHASE_A2_MW = 2
PHASE_A3_RYW = 3
PHASE_A4_WFR = 4
PHASE_B1_TCC = 5
PHASE_B2_CONCURRENT = 6

PHASE_NAMES = {
    PHASE_NONE: "none",
    PHASE_A1_MR: "a1:monotonic-read",
    PHASE_A2_MW: "a2:monotonic-write",
    PHASE_A3_RYW: "a3:read-your-write",
    PHASE_A4_WFR: "a4:write-follows-read",
    PHASE_B1_TCC: "b1:timed-causal",
    PHASE_B2_CONCURRENT: "b2:concurrent",
}

# ODG edge-kind weights for severity (paper §3.4.1: Timed, Causal, Data).
WEIGHT_TIMED = 1
WEIGHT_CAUSAL = 2
WEIGHT_DATA = 3


@jax.jit
def ratio_f32(num: Array, den: Array) -> Array:
    """``num / den`` rounded to the nearest float32, ties to even, for
    int32 ``0 <= num < 2**30`` and ``1 <= den < 2**30``.

    A TPU divides float32 by a refined reciprocal, which can land one
    unit in the last place off the IEEE quotient; this long division in
    int32 gives the IEEE quotient on every backend.  Jitted: the audit
    assembles its result eagerly, and the long division's ~120 small
    ops would each be a dispatch."""
    num = jnp.asarray(num, jnp.int32)
    den = jnp.asarray(den, jnp.int32)
    safe = jnp.maximum(num, 1)
    # 2**e <= num/den < 2**(e+1); scale both to the same bit length.
    e = jax.lax.clz(den) - jax.lax.clz(safe)
    a = jnp.left_shift(safe, jnp.maximum(-e, 0))
    b = jnp.left_shift(den, jnp.maximum(e, 0))
    low = a < b
    e = e - low
    a = jnp.where(low, a << 1, a)
    # a / b in [1, 2): 24 significant bits, then a guard bit and the
    # sticky remainder.
    r = a - b
    m = jnp.int32(1)
    for _ in range(23):
        r = r << 1
        bit = r >= b
        r = jnp.where(bit, r - b, r)
        m = (m << 1) | bit.astype(jnp.int32)
    r = r << 1
    guard = r >= b
    sticky = jnp.where(guard, r - b, r) != 0
    m = m + (guard & (sticky | ((m & 1) == 1))).astype(jnp.int32)
    carry = m == (1 << 24)
    m = jnp.where(carry, 1 << 23, m)
    e = e + carry.astype(jnp.int32)
    bits = ((e + 127) << 23) | (m - (1 << 23))
    q = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(num == 0, jnp.float32(0), q)


class AuditResult(NamedTuple):
    """Dense audit output over an m-entry log."""

    phase: Array        # (m, m) int32 — phase code for pair (i, j)
    violation: Array    # (m, m) bool — pair (i, j) violates its guarantee
    vio_kind: Array     # (m, m) int32 — phase code of the violated rule
    timed_vio: Array    # (m, m) bool — Δ-bound exceeded
    n_audited: Array    # () int32 — pairs classified (phase != NONE)
    n_violations: Array  # () int32
    severity: Array     # () float32 — weighted severity in [0, 1]


def classify_pairs(table: Duot, hb: Array | None = None) -> Array:
    """Phase classification matrix (paper Fig. 4), no violation check.

    ``hb`` lets callers reuse a precomputed happens-before matrix — the
    O(m²·n) term — instead of recomputing it."""
    m = table.capacity
    valid = table.valid
    pair_valid = valid[:, None] & valid[None, :]
    same_res = table.resource[:, None] == table.resource[None, :]
    ordered = table.seq[:, None] < table.seq[None, :]
    same_client = table.client[:, None] == table.client[None, :]
    if hb is None:
        hb = vclock.happens_before_matrix(table.vc)

    base = pair_valid & same_res & ordered
    ki = table.kind[:, None]
    kj = table.kind[None, :]

    phase = jnp.zeros((m, m), dtype=jnp.int32)
    sc_hb = base & same_client & hb
    phase = jnp.where(sc_hb & (ki == READ) & (kj == READ), PHASE_A1_MR, phase)
    phase = jnp.where(sc_hb & (ki == WRITE) & (kj == WRITE), PHASE_A2_MW, phase)
    phase = jnp.where(sc_hb & (ki == WRITE) & (kj == READ), PHASE_A3_RYW, phase)
    phase = jnp.where(sc_hb & (ki == READ) & (kj == WRITE), PHASE_A4_WFR, phase)
    phase = jnp.where(base & ~same_client & hb, PHASE_B1_TCC, phase)
    phase = jnp.where(base & ~hb, PHASE_B2_CONCURRENT, phase)
    return phase


def audit(
    table: Duot, *, delta: int | Array = 0, use_kernel: bool | None = None
) -> AuditResult:
    """Full audit: classify every pair and flag violations.

    Args:
      table: the DUOT.
      delta: timed bound Δ in ``seq`` (timestamp) units; 0 disables the
        timed check (pure causal audit).
      use_kernel: route the O(m²·n) pairwise pass through the tiled
        Pallas kernel (``repro.kernels.vclock_audit``) and rebuild the
        result from its packed codes.  ``None`` (default) picks the
        kernel on TPU and the jnp fallback everywhere else; the kernel
        needs a concrete ``delta``, so traced deltas (audit under jit)
        also fall back.  Both paths are bit-identical.
    """
    if use_kernel is None:
        # The kernel is built with TPU grid/compiler parameters; every
        # other backend takes the jnp fallback.
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel and not isinstance(delta, jax.core.Tracer):
        return _audit_from_codes(table, int(delta))
    hb = vclock.happens_before_matrix(table.vc)
    phase = classify_pairs(table, hb)
    vi = table.version[:, None]
    vj = table.version[None, :]
    ki = table.kind[:, None]
    kj = table.kind[None, :]

    viol = jnp.zeros(phase.shape, dtype=bool)
    viol |= (phase == PHASE_A1_MR) & (vj < vi)
    viol |= (phase == PHASE_A2_MW) & (vj <= vi)
    viol |= (phase == PHASE_A3_RYW) & (vj < vi)
    viol |= (phase == PHASE_A4_WFR) & (vj <= vi)
    # b1: a causally-later read must observe causally-earlier writes.
    viol |= (
        (phase == PHASE_B1_TCC) & (ki == WRITE) & (kj == READ) & (vj < vi)
    )

    # Timed bound: any (write, later read) on the same resource separated
    # by more than Δ timestamps must be visible regardless of causality.
    delta = jnp.asarray(delta, jnp.int32)
    gap = table.seq[None, :] - table.seq[:, None]
    timed_vio = (
        (delta > 0)
        & (phase != PHASE_NONE)
        & (ki == WRITE)
        & (kj == READ)
        & (gap > delta)
        & (vj < vi)
    )
    return _assemble_result(table, phase, viol, timed_vio)


def _assemble_result(
    table: Duot, phase: Array, viol: Array, timed_vio: Array
) -> AuditResult:
    """Counts + ODG-weighted severity from the per-pair flags.

    Everything downstream of the pairwise pass needs only the phase
    codes (``base ⇔ phase > 0``, ``base ∧ hb ⇔ 1 <= phase <= 5``) and
    the op kinds, so the dense jnp path and the Pallas-kernel path
    share this assembly — they cannot drift apart.

    Severity (paper §3.4.1): violated ODG edges weighted by kind over
    all audited edges.  Data edges: (write, later read) pairs on one
    resource; Causal edges: happens-before pairs; Timed edges: the
    remaining ordered same-resource pairs.
    """
    vio_kind = jnp.where(viol, phase, PHASE_NONE).astype(jnp.int32)
    n_audited = jnp.sum((phase != PHASE_NONE).astype(jnp.int32))
    n_violations = jnp.sum(viol.astype(jnp.int32)) + jnp.sum(
        timed_vio.astype(jnp.int32)
    )

    base = phase != PHASE_NONE
    causal_edge = (phase >= PHASE_A1_MR) & (phase <= PHASE_B1_TCC)
    ki = table.kind[:, None]
    kj = table.kind[None, :]
    data_edge = base & (ki == WRITE) & (kj == READ)
    w = (
        WEIGHT_DATA * (viol & data_edge)
        + WEIGHT_CAUSAL * (viol & causal_edge & ~data_edge)
        + WEIGHT_TIMED * ((viol | timed_vio) & ~causal_edge & ~data_edge)
    )
    denom = (
        WEIGHT_DATA * data_edge
        + WEIGHT_CAUSAL * (causal_edge & ~data_edge)
        + WEIGHT_TIMED * (base & ~causal_edge & ~data_edge)
    )
    severity = ratio_f32(jnp.sum(w), jnp.maximum(jnp.sum(denom), 1))

    return AuditResult(
        phase=phase,
        violation=viol,
        vio_kind=vio_kind,
        timed_vio=timed_vio,
        n_audited=n_audited,
        n_violations=n_violations,
        severity=severity.astype(jnp.float32),
    )


def _audit_from_codes(table: Duot, delta: int) -> AuditResult:
    """Rebuild an :class:`AuditResult` from the Pallas kernel's codes.

    The kernel emits ``phase | violation << 8 | timed << 9`` per pair;
    the O(m²·n) clock comparison never runs on the host.
    """
    from repro.kernels import ops as kernel_ops

    codes = kernel_ops.audit_duot(table, delta=delta)
    phase = codes & 0xFF
    viol = ((codes >> 8) & 1).astype(bool)
    timed_vio = ((codes >> 9) & 1).astype(bool)
    return _assemble_result(table, phase, viol, timed_vio)


audit_jit = jax.jit(
    functools.partial(audit, use_kernel=False), static_argnames=()
)


def session_guarantee_report(result: AuditResult) -> dict[str, Array]:
    """Per-guarantee violation counts (for Figs 12–13 style reporting)."""
    out = {}
    for code, name in [
        (PHASE_A1_MR, "monotonic_read"),
        (PHASE_A2_MW, "monotonic_write"),
        (PHASE_A3_RYW, "read_your_write"),
        (PHASE_A4_WFR, "write_follows_read"),
        (PHASE_B1_TCC, "timed_causal"),
    ]:
        out[name] = jnp.sum((result.vio_kind == code).astype(jnp.int32))
    out["timed_bound"] = jnp.sum(result.timed_vio.astype(jnp.int32))
    return out

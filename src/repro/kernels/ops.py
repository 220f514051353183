"""Jit'd public wrappers around the Pallas kernels.

On a TPU runtime the kernels compile natively; on CPU (tests, CI) they
run in interpret mode — same code path, Python-executed kernel body —
which is how the correctness sweeps in ``tests/test_kernels.py``
validate them against the ``ref.py`` oracles.  A kernel never runs
interpreted on a TPU: asking for it raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import digest_compare as _dc
from repro.kernels import flash_attention as _fa
from repro.kernels import histogram as _hg
from repro.kernels import op_ingest as _oi
from repro.kernels import placement_score as _pls
from repro.kernels import policy_score as _ps
from repro.kernels import session_floor as _sf
from repro.kernels import vclock_audit as _va


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _interpret(interpret: bool | None) -> bool:
    """Interpret mode off the TPU, compiled on it — never interpreted
    there, where a slow Python kernel body would pass unnoticed."""
    if interpret is None:
        return _on_cpu()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "Pallas kernels run compiled on a TPU, not interpreted"
        )
    return interpret


def resolve_op_ingest_impl(
    impl: str | None,
    *,
    batch: int,
    n_clients: int | None = None,
    n_replicas: int | None = None,
    n_resources: int | None = None,
    affine_op_index: bool = False,
) -> str:
    """Resolve the ``op_ingest`` implementation for a call shape.

    ``None``/``"auto"`` picks the fastest bit-identical path for the
    backend: the Pallas kernel on TPU; on CPU the closed-form fused path
    (O(B·R + B log B), no pair sweep) whenever the static state sizes
    are known, its packed segment keys fit int32, and the caller
    guarantees batch-affine op indices (``op_index[i] == op_index[0] +
    i`` — every store-layer batch; without cadence inputs the indices
    are irrelevant and fused is always safe); otherwise the tiled block
    walk.  Exposed so the store layer can pre-resolve the impl and feed
    the pending ring to the fused path directly.
    """
    if impl is not None and impl != "auto":
        return impl
    if jax.default_backend() == "tpu":
        return "pallas"
    if None not in (n_clients, n_replicas, n_resources) and affine_op_index:
        max_seg = max(n_clients, n_replicas) * n_resources
        if max_seg * max(batch, 1) < 2 ** 31:
            return "fused"
    return "tiled"


def op_ingest(
    client: jax.Array,     # (B,) int32
    replica: jax.Array,    # (B,) int32
    resource: jax.Array,   # (B,) int32
    is_write: jax.Array,   # (B,) bool
    g0: jax.Array,         # (B,) int32 — global_version gathered per op
    raw0: jax.Array,       # (B,) int32 — replica_version gathered per op
    floor0: jax.Array,     # (B,) int32 — session floor gathered per op
    *,
    op_index: jax.Array | None = None,
    apply_index: jax.Array | None = None,
    pend_version: jax.Array | None = None,
    pend_resource: jax.Array | None = None,
    pend_live: jax.Array | None = None,
    pend_apply: jax.Array | None = None,
    impl: str | None = None,
    block: int | None = None,
    interpret: bool | None = None,
    n_clients: int | None = None,
    n_replicas: int | None = None,
    n_resources: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched op-ingestion prefixes ``(occ, raw, floor)``.

    Same contract as ``repro.kernels.ref.op_ingest_ref`` (bit-exact) —
    the three per-op prefix reductions that ``xstcc.apply_op_batch``
    builds versions, admission, staleness, and floors from.  ``impl``
    selects the implementation:

      * ``"pallas"`` — the tiled TPU kernel (O(B·block) memory);
      * ``"tiled"``  — the jnp ``lax.scan`` twin of the kernel, the
        fast path on CPU where Pallas runs interpreted;
      * ``"fused"``  — the closed-form segmented-scan path (O(B·R +
        B log B), no pair sweep) — needs the static state sizes
        (``n_clients``/``n_replicas``/``n_resources``) and, when cadence
        inputs are present, batch-affine ``op_index``;
      * ``"dense"``  — the O(B²) oracle (the PR-1 masks, kept as the
        fallback and differential baseline);
      * ``None``     — "pallas" on accelerators; on CPU the fused path
        when eligible (see :func:`resolve_op_ingest_impl`), else tiled.
    """
    if impl is None or impl == "auto":
        # The Pallas kernel relies on TPU sequential-grid semantics
        # (cross steps read buffer rows published by earlier diagonal
        # steps); on every other backend the jnp paths are the safe
        # fast ones.  Auto only picks fused when the caller passed
        # op_index itself (the store layer's batches are affine); the
        # zeros fill below is NOT affine and would corrupt the fused
        # activation transform.
        impl = resolve_op_ingest_impl(
            impl, batch=client.shape[0],
            n_clients=n_clients, n_replicas=n_replicas,
            n_resources=n_resources,
            affine_op_index=(
                op_index is not None
                or (apply_index is None and pend_apply is None)
            ),
        )
    had_op_index = op_index is not None
    if op_index is None and (
        apply_index is not None or pend_apply is not None
    ):
        op_index = jnp.zeros(client.shape, jnp.int32)
    if impl == "fused":
        if None in (n_clients, n_replicas, n_resources):
            raise ValueError(
                "op_ingest impl='fused' needs n_clients/n_replicas/"
                "n_resources"
            )
        if not had_op_index and (
            apply_index is not None or pend_apply is not None
        ):
            raise ValueError(
                "op_ingest impl='fused' with cadence inputs needs a "
                "batch-affine op_index"
            )
        return _oi.op_ingest_fused(
            client, replica, resource, is_write, g0, raw0, floor0,
            n_clients=n_clients, n_replicas=n_replicas,
            n_resources=n_resources,
            op_index=op_index, apply_index=apply_index,
            pend_version=pend_version, pend_resource=pend_resource,
            pend_live=pend_live, pend_apply=pend_apply,
        )
    if impl == "dense":
        return _oi.op_ingest_ref(
            client, replica, resource, is_write, g0, raw0, floor0,
            op_index=op_index, apply_index=apply_index,
            pend_version=pend_version, pend_resource=pend_resource,
            pend_live=pend_live, pend_apply=pend_apply,
        )
    if block is None:
        # Wider strips amortize the scan overhead on CPU; 128 matches
        # the TPU lane width for the Pallas grid.
        block = 256 if impl == "tiled" else 128
    block = max(1, min(block, client.shape[0]))
    packed = _oi.pack_ops(
        client, replica, resource, is_write, g0, raw0, floor0,
        op_index=op_index, apply_index=apply_index,
        pend_version=pend_version, pend_resource=pend_resource,
        pend_live=pend_live, pend_apply=pend_apply, block=block,
    )
    if impl == "tiled":
        return _oi.op_ingest_tiled(packed, block=block)
    if impl == "pallas":
        interpret = _interpret(interpret)
        return _oi.op_ingest_pallas(packed, block=block, interpret=interpret)
    raise ValueError(f"unknown op_ingest impl: {impl!r}")


def digest_compare(
    a: jax.Array,  # (..., 4) int32 — side-A digests (SUM, MAX, CHK, CNT)
    b: jax.Array,  # (..., 4) int32 — side-B digests
    *,
    impl: str | None = None,
    block: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Diff two sides' range digests; ``(differ, a_behind, b_behind)``.

    Same contract as ``repro.kernels.ref.digest_compare_ref``
    (bit-exact): bool masks over the leading axes — ``differ`` is the
    stale-range mask the gossip scheduler turns into repair merges.
    Leading axes (e.g. ``(pairs, ranges)``) are flattened into packed
    rows for the tiled paths.  ``impl`` selects the implementation:

      * ``"pallas"`` — the tiled TPU kernel (O(rows·block) memory);
      * ``"tiled"``  — the jnp ``lax.map`` twin of the kernel, the
        fast path on CPU where Pallas runs interpreted;
      * ``"dense"``  — the whole-array oracle;
      * ``None``     — "pallas" on accelerators, "tiled" on CPU.
    """
    if impl is None or impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "tiled"
    if impl == "dense":
        from repro.kernels import ref as kernel_ref

        return kernel_ref.digest_compare_ref(a, b)
    lead = a.shape[:-1]
    a2 = jnp.asarray(a, jnp.int32).reshape(-1, a.shape[-1])
    b2 = jnp.asarray(b, jnp.int32).reshape(-1, b.shape[-1])
    m = a2.shape[0]
    block = max(1, min(block, m))
    packed = _dc.pack_digests(a2, b2, block=block)
    if impl == "tiled":
        out = _dc.digest_compare_tiled(packed, block=block)
    elif impl == "pallas":
        interpret = _interpret(interpret)
        out = _dc.digest_compare_pallas(
            packed, block=block, interpret=interpret
        )
    else:
        raise ValueError(f"unknown digest_compare impl: {impl!r}")
    out = out[:m]
    return (
        out[:, _dc.DIFFER].astype(bool).reshape(lead),
        out[:, _dc.A_BEHIND].astype(bool).reshape(lead),
        out[:, _dc.B_BEHIND].astype(bool).reshape(lead),
    )


def histogram(
    values: jax.Array,  # (B,) or (M, B) f32 — observation batches
    *,
    lo,                 # scalar or (M,) — bin range lower bound
    hi,                 # scalar or (M,) — bin range upper bound
    n_bins: int,
    mask: jax.Array | None = None,  # same shape as values; None = all
    impl: str | None = None,
    block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fixed-bin histograms of observation batches; ``(M, n_bins)``
    int32 counts (``(n_bins,)`` for a 1-D batch).

    Same contract as ``repro.kernels.ref.histogram_ref`` (bit-exact):
    each row bins into ``clip(floor((v - lo) / width), 0, n_bins-1)``
    — out-of-range observations saturate into the edge bins — and
    masked-out observations are not counted.  ``impl`` selects the
    implementation:

      * ``"pallas"`` — the tiled TPU kernel (O(M·(block+n_bins))
        memory, sequential accumulation over column tiles);
      * ``"tiled"``  — the jnp ``lax.map`` twin of the kernel, the
        fast path on CPU where Pallas runs interpreted;
      * ``"dense"``  — the whole-array oracle (the (M, B, n_bins)
        one-hot cube at once);
      * ``None``     — "pallas" on accelerators, "tiled" on CPU.
    """
    if impl is None or impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "tiled"
    one_d = values.ndim == 1
    vals = jnp.atleast_2d(jnp.asarray(values, jnp.float32))
    if mask is not None:
        mask = jnp.atleast_2d(mask)
    params = _hg.metric_params(lo, hi, n_bins)
    if params.shape[0] == 1 and vals.shape[0] > 1:
        params = jnp.broadcast_to(params, (vals.shape[0], 2))
    if impl == "dense":
        from repro.kernels import ref as kernel_ref

        msk = (
            jnp.ones(vals.shape, jnp.int32) if mask is None
            else jnp.asarray(mask, jnp.int32)
        )
        out = kernel_ref.histogram_ref(vals, msk, params, n_bins=n_bins)
    else:
        block = max(1, min(block, vals.shape[1]))
        vals, msk = _hg.pack_observations(vals, mask, block=block)
        if impl == "tiled":
            out = _hg.histogram_tiled(
                vals, msk, params, n_bins=n_bins, block=block
            )
        elif impl == "pallas":
            interpret = _interpret(interpret)
            out = _hg.histogram_pallas(
                vals, msk, params, n_bins=n_bins, block=block,
                interpret=interpret,
            )
        else:
            raise ValueError(f"unknown histogram impl: {impl!r}")
    return out[0] if one_d else out


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    layout: str = "bshd",
    interpret: bool | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """GQA flash attention.

    layout 'bshd': q (B, S, H, hd), k/v (B, T, Hkv, hd) — the model
    substrate's layout; internally transposed to the kernel's (B, H, S,
    hd).  layout 'bhsd': already kernel-native.
    """
    interpret = _interpret(interpret)
    if layout == "bshd":
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    if layout == "bshd":
        out = jnp.swapaxes(out, 1, 2)
    return out


def audit_duot(duot, *, delta: int = 0, block: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Run the Pallas audit over a ``repro.core.duot.Duot``.

    Returns the (M, M) packed code matrix (phase | viol<<8 | timed<<9).
    The log is padded to a block multiple with invalid entries."""
    interpret = _interpret(interpret)
    m = duot.capacity
    pad = (-m) % block
    def p(x, fill=0):
        if pad == 0:
            return x
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, width, constant_values=fill)

    return _va.vclock_audit(
        p(duot.vc),
        p(duot.client, -1),
        p(duot.kind),
        p(duot.resource, -1),
        p(duot.version),
        p(duot.seq),
        p(duot.valid, False),
        delta=delta,
        block=block,
        interpret=interpret,
    )[: m, : m]


def session_admit(
    replica_version: jax.Array,  # (P, R) int32
    read_floor: jax.Array,       # (C, R) int32
    write_floor: jax.Array,      # (C, R) int32
    client: jax.Array,           # (B,) int32
    replica: jax.Array,          # (B,) int32
    resource: jax.Array,         # (B,) int32
    *,
    enforce: bool = True,
    block: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched session-floor admission via the Pallas kernel.

    Same contract as ``repro.kernels.ref.session_admit_ref``: returns
    ``(served, admissible, floor, new_read_floor)``.  The batch is
    padded to a block multiple with invalid rows.

    The kernel does not lower for TPU yet (column scatters and a
    ``(B, C, R)`` product in its body), so only interpret mode runs it.
    """
    interpret = _interpret(interpret)
    if not interpret:
        raise NotImplementedError(
            "session_floor has no TPU lowering yet; admit with "
            "use_kernel=False"
        )
    b = client.shape[0]
    block = max(1, min(block, b))
    pad = (-b) % block

    def p1(x, fill=0):
        return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

    meta = jnp.zeros((b + pad, _sf.META_COLS), jnp.int32)
    meta = meta.at[:, _sf.CLIENT].set(p1(client.astype(jnp.int32)))
    meta = meta.at[:, _sf.REPLICA].set(p1(replica.astype(jnp.int32)))
    meta = meta.at[:, _sf.RESOURCE].set(p1(resource.astype(jnp.int32)))
    meta = meta.at[:, _sf.VALID].set(p1(jnp.ones((b,), jnp.int32)))

    out, new_rf = _sf.session_floor(
        replica_version, read_floor, write_floor, meta,
        enforce=enforce, block=block, interpret=interpret,
    )
    return (
        out[:b, _sf.SERVED],
        out[:b, _sf.ADMISSIBLE].astype(bool),
        out[:b, _sf.FLOOR],
        new_rf,
    )


def policy_score(
    sess: jax.Array,    # (S, SP_COLS) f32 — repro.policy.sla.session_params
    table: jax.Array,   # (LVL_COLS, L) f32 — repro.policy.sla.level_table
    stale: jax.Array,   # (S, L) f32
    viol: jax.Array,    # (S, L) f32
    count: jax.Array,   # (S, L) f32
    *,
    block_s: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched SLA feasibility/utility scoring via the Pallas kernel.

    Same contract as ``repro.kernels.ref.policy_score_ref`` (bit-exact):
    returns ``(utility, feasible)``.  The session axis is padded to a
    block multiple with invalid rows, which score utility 0/feasible 0
    and are stripped before returning.
    """
    interpret = _interpret(interpret)
    s = stale.shape[0]
    block_s = max(1, min(block_s, s))
    pad = (-s) % block_s
    if pad:
        sess = jnp.pad(sess, ((0, pad), (0, 0)))  # SP_VALID pads to 0
        stale = jnp.pad(stale, ((0, pad), (0, 0)))
        viol = jnp.pad(viol, ((0, pad), (0, 0)))
        count = jnp.pad(count, ((0, pad), (0, 0)))
    util, feas = _ps.policy_score(
        sess, table, stale, viol, count,
        block_s=block_s, interpret=interpret,
    )
    return util[:s], feas[:s]


def placement_score(
    reads: jax.Array,        # (R, G) f32 — repro.geo.placement.region_demand
    writes: jax.Array,       # (R, G) f32
    read_price: jax.Array,   # (K, G) f32 — repro.geo.placement.candidate_tables
    write_price: jax.Array,  # (K, G) f32
    read_rtt: jax.Array,     # (K, G) f32
    cand_meta: jax.Array,    # (2, K) f32 — [storage cost; validity]
    *,
    max_latency_ms: float,
    impl: str | None = None,
    block_r: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched (resources × candidate-plans) placement scoring.

    Same contract as ``repro.kernels.ref.placement_score_ref``
    (bit-exact): returns ``(utility, feasible)`` over the (R, K) grid.
    ``impl`` selects the implementation:

      * ``"pallas"`` — the tiled TPU kernel;
      * ``"tiled"``  — the jnp ``lax.map`` twin of the kernel, the
        fast path on CPU where Pallas runs interpreted;
      * ``"dense"``  — the reference oracle (whole (R, K) at once);
      * ``None``     — "pallas" on accelerators, "tiled" on CPU.

    The resource axis is padded to a block multiple with zero-demand
    rows, which are stripped before returning.
    """
    if impl is None or impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "tiled"
    if impl == "dense":
        from repro.kernels import ref as kernel_ref

        return kernel_ref.placement_score_ref(
            reads, writes, read_price, write_price, read_rtt, cand_meta,
            max_latency_ms=max_latency_ms,
        )
    r = reads.shape[0]
    block_r = max(1, min(block_r, r))
    pad = (-r) % block_r
    if pad:
        reads = jnp.pad(reads, ((0, pad), (0, 0)))
        writes = jnp.pad(writes, ((0, pad), (0, 0)))
    if impl == "tiled":
        util, feas = _pls.placement_score_tiled(
            reads, writes, read_price, write_price, read_rtt, cand_meta,
            max_latency_ms=max_latency_ms, block_r=block_r,
        )
    elif impl == "pallas":
        interpret = _interpret(interpret)
        util, feas = _pls.placement_score(
            reads, writes, read_price, write_price, read_rtt, cand_meta,
            max_latency_ms=max_latency_ms, block_r=block_r,
            interpret=interpret,
        )
    else:
        raise ValueError(f"unknown placement_score impl: {impl!r}")
    return util[:r], feas[:r]


def audit_summary(codes: jax.Array) -> dict[str, jax.Array]:
    """Counts from the packed code matrix."""
    phase = codes & 0xFF
    viol = (codes >> 8) & 1
    timed = (codes >> 9) & 1
    return {
        "n_audited": jnp.sum((phase > 0).astype(jnp.int32)),
        "n_violations": jnp.sum(viol) + jnp.sum(timed),
        "by_phase": jnp.stack(
            [jnp.sum(((phase == c) & (viol > 0)).astype(jnp.int32))
             for c in range(1, 6)]
        ),
    }

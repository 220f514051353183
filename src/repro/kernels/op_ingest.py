"""Tiled op-ingestion: the batched X-STCC hot path in O(B·tile) memory.

``repro.core.xstcc.apply_op_batch`` needs, per op ``i`` of a ``(B,)``
batch, three prefix reductions over the ops ``j < i`` (and the pending
ring): the per-resource write count ``occ``, the replica-visible version
``raw``, and the per-(client, resource) session-floor max ``floor``.
The dense formulation (``repro.kernels.ref.op_ingest_ref``) materializes
five ``(B, B)`` relation masks plus a ``(B, Q)`` pending mask — O(B²)
HBM that caps the batch size the engine can sustain.

This module computes the same reductions by streaming ``(Bi, Bj)``
blocks of the batch:

  * :func:`op_ingest_pallas` — the Pallas TPU kernel.  A sequential
    1-D grid ("arbitrary" semantics) walks the lower-triangular tile
    pairs ``(t, u <= t)``; each row tile accumulates its partial
    sums/maxima into its output block across the column tiles
    ``u < t``, then at the diagonal step ``u == t`` folds the
    intra-tile lower triangle, the pending ring (in lane chunks), and
    the gathered state vectors, and publishes the tile's write versions
    and floor contributions into a VMEM scratch buffer that later row
    tiles read — per-step memory is O(tile² + B + Q), never O(B²).
  * :func:`op_ingest_tiled` — the same tile-pair walk as a
    ``lax.scan`` over the identical tile math in plain jnp, the
    jnp twin the kernel is checked against on the chip.

Visibility inside a tile is the closed-form cadence predicate (no
precomputed masks cross the API):

    visible(i, j) = is_write(j) ∧ same_resource(i, j) ∧
                    (coordinator(i) == coordinator(j)
                     ∨ op_index(i) >= apply_index(j))

with ``apply_index`` = 0 for merge-every-op levels, the stream
scheduler's emulated apply points for the op-index / timed-Δ cadences,
and the ``NEVER`` sentinel for plain scalar-loop semantics.  All three
implementations are integer-exact and must agree bit-for-bit with the
oracle (``tests/test_op_ingest.py`` sweeps them).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEVER, op_ingest_ref

Array = jax.Array

# op meta columns (B, OP_COLS) int32
CLIENT, REPLICA, RESOURCE, IS_WRITE, GLOBAL0, RAW0, FLOOR0 = 0, 1, 2, 3, 4, 5, 6
OPIDX, APPLYIDX = 7, 8
OP_COLS = 16
# pending meta rows (PEND_ROWS, lanes) int32, chunked along the ring
PVER, PRES, PLIVE, PAPPLY = 0, 1, 2, 3
PEND_ROWS = 8
PEND_CHUNK = 512
# output columns (B, OUT_COLS) int32
OCC, RAW, FLOOR = 0, 1, 2
OUT_COLS = 8
# tile-exchange buffer rows (n_tiles, BUF_ROWS, block) int32
VERW, CONTRIB = 0, 1
BUF_ROWS = 8


class _Packed(NamedTuple):
    meta: Array       # (Bp, OP_COLS) int32 — one op per row
    meta_t: Array     # (OP_COLS, Bp) int32 — one op per lane
    pend: Array       # (n_chunks, PEND_ROWS, chunk) int32 — one slot per lane
    b: int            # true batch length (rows beyond it are inert pads)


def pack_ops(
    client: Array,
    replica: Array,
    resource: Array,
    is_write: Array,
    g0: Array,
    raw0: Array,
    floor0: Array,
    *,
    op_index: Array | None = None,
    apply_index: Array | None = None,
    pend_version: Array | None = None,
    pend_resource: Array | None = None,
    pend_live: Array | None = None,
    pend_apply: Array | None = None,
    block: int = 128,
) -> _Packed:
    """Pack the per-op vectors into the kernel's meta layouts.

    Pads the batch to a ``block`` multiple with inert rows (reads on
    resource ``-1`` — they match nothing and sort after every real op,
    so they contribute to no reduction) and the pending ring to a whole
    number of lane chunks with dead slots.  ``apply_index=None`` (scalar
    semantics) packs the ``NEVER`` sentinel so the cadence predicate is
    vacuously false.  The ops are packed twice — one op per row (the
    row side of a tile pair) and one op per lane (the column side) — so
    the kernel never transposes a vector.
    """
    b = client.shape[0]
    pad = (-b) % block

    def pcol(x, fill=0):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

    zeros = jnp.zeros((b,), jnp.int32)
    cols = {
        CLIENT: pcol(client),
        REPLICA: pcol(replica, -1),
        RESOURCE: pcol(resource, -1),
        IS_WRITE: pcol(jnp.asarray(is_write).astype(jnp.int32)),
        GLOBAL0: pcol(g0),
        RAW0: pcol(raw0),
        FLOOR0: pcol(floor0),
        OPIDX: pcol(zeros if op_index is None else op_index),
        APPLYIDX: pcol(
            jnp.full((b,), NEVER, jnp.int32)
            if apply_index is None else apply_index,
            NEVER,
        ),
    }
    blank = jnp.zeros((b + pad,), jnp.int32)
    meta_t = jnp.stack([cols.get(k, blank) for k in range(OP_COLS)])

    q = 0 if pend_version is None else pend_version.shape[0]
    chunk = min(PEND_CHUNK, max(128, q + (-q) % 128))
    qp = max(chunk, q + (-q) % chunk)

    def prow(x, fill):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, qp - q), constant_values=fill)

    dead = jnp.zeros((0,), jnp.int32)
    rows = {
        PVER: prow(dead if not q else pend_version, 0),
        PRES: prow(dead if not q else pend_resource, -1),
        PLIVE: prow(dead if not q else jnp.asarray(pend_live), 0),
        PAPPLY: prow(
            dead if not q else (
                jnp.full((q,), NEVER, jnp.int32) if pend_apply is None
                else pend_apply
            ),
            NEVER,
        ),
    }
    pblank = jnp.zeros((qp,), jnp.int32)
    pend = jnp.stack([rows.get(k, pblank) for k in range(PEND_ROWS)])
    pend = pend.reshape(PEND_ROWS, qp // chunk, chunk).swapaxes(0, 1)
    return _Packed(meta=meta_t.T, meta_t=meta_t, pend=pend, b=b)


# -- shared tile math (identical jnp ops in the Pallas body and the scan) ----
#
# A tile pair is ``rows`` (block, OP_COLS) — op i on sublane i — against
# ``cols`` (OP_COLS, block) — op j on lane j.  ``rows[:, k:k+1]`` is then
# a column vector and ``cols[k:k+1, :]`` a row vector, so every pair
# relation is a plain (block, block) broadcast and every per-op result a
# (block, 1) lane reduction: the Mosaic-friendly shapes.


def _pair_parts(rows: Array, cols: Array, prior):
    """Relation masks for one (rows × cols) block.

    ``prior`` is the order mask (row's global index > col's).  Returns
    ``(prior_w, vis, floor_mask)``: prior same-resource writes, the
    cadence-visible subset, and the session-floor (same client &
    resource) pairs.
    """
    def rc(k):
        return rows[:, k:k + 1]

    def cr(k):
        return cols[k:k + 1, :]

    same_r = rc(RESOURCE) == cr(RESOURCE)
    prior_w = prior & same_r & (cr(IS_WRITE) > 0)
    vis = prior_w & (
        (rc(REPLICA) == cr(REPLICA)) | (rc(OPIDX) >= cr(APPLYIDX))
    )
    floor_mask = prior & same_r & (rc(CLIENT) == cr(CLIENT))
    return prior_w, vis, floor_mask


def _masked_max(mask: Array, row: Array) -> Array:
    """(block, 1) max over lanes of ``row`` where ``mask`` (identity 0)."""
    return jnp.max(jnp.where(mask, row, 0), axis=1, keepdims=True)


def _count(mask: Array) -> Array:
    return jnp.sum(jnp.where(mask, 1, 0), axis=1, keepdims=True)


def _col_to_row(col: Array) -> Array:
    """(n, 1) -> (1, n) by a diagonal select and a sublane reduction —
    exact for integers, and no transpose for Mosaic to lower."""
    n = col.shape[0]
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, (n, n))
    return jnp.sum(jnp.where(iota(0) == iota(1), col, 0), axis=0,
                   keepdims=True)


def _cross_parts(rows: Array, cols: Array, buf: Array):
    """Partial reductions of one already-finalized column block.

    Every pair of a strictly-cross tile is ordered (row indices all
    exceed column indices), so the order mask is just True."""
    prior_w, vis, floor_mask = _pair_parts(rows, cols, True)
    return (
        _count(prior_w),
        _masked_max(vis, buf[VERW:VERW + 1, :]),
        _masked_max(floor_mask, buf[CONTRIB:CONTRIB + 1, :]),
    )


def _accumulate(acc: Array, rows: Array, cols: Array, buf: Array) -> Array:
    """Fold one cross tile's partials into the row tile's accumulator."""
    occ_p, vis_p, floor_p = _cross_parts(rows, cols, buf)
    return _with_cols(
        acc,
        acc[:, OCC:OCC + 1] + occ_p,
        jnp.maximum(acc[:, RAW:RAW + 1], vis_p),
        jnp.maximum(acc[:, FLOOR:FLOOR + 1], floor_p),
    )


def _with_cols(out: Array, occ: Array, raw: Array, floor: Array) -> Array:
    """``out`` with its OCC/RAW/FLOOR columns replaced — a lane select,
    since Mosaic has no column scatter."""
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    return jnp.where(
        lane == OCC, occ,
        jnp.where(lane == RAW, raw, jnp.where(lane == FLOOR, floor, out)),
    )


def _pend_max(rows: Array, n_chunks: int, chunk_of) -> Array:
    """(block, 1) freshest cadence-visible pending-ring version per op.

    Walks the ring ``chunk_of(k)`` — (PEND_ROWS, chunk) slices — so the
    live mask is (block, chunk), never (block, Q)."""
    def body(k, acc):
        pend = chunk_of(k)
        pvis = (
            (pend[PLIVE:PLIVE + 1, :] > 0)
            & (rows[:, RESOURCE:RESOURCE + 1] == pend[PRES:PRES + 1, :])
            & (rows[:, OPIDX:OPIDX + 1] >= pend[PAPPLY:PAPPLY + 1, :])
        )
        return jnp.maximum(acc, _masked_max(pvis, pend[PVER:PVER + 1, :]))

    init = jnp.zeros((rows.shape[0], 1), jnp.int32)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _finalize_tile(rows: Array, cols: Array, acc: Array, n_chunks: int,
                   chunk_of):
    """Diagonal step: intra-tile triangle + pending ring + state joins.

    ``acc`` holds the accumulated cross-tile partials in its OCC/RAW/
    FLOOR columns; ``cols`` is the same tile one op per lane.  Returns
    the tile's final output block plus its (BUF_ROWS, block) buffer
    rows (write versions and floor contributions) for later tiles.
    """
    t = rows.shape[0]
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, (t, t))
    prior_w, vis, floor_mask = _pair_parts(rows, cols, iota(0) > iota(1))

    occ = acc[:, OCC:OCC + 1] + _count(prior_w)
    is_w = rows[:, IS_WRITE:IS_WRITE + 1] > 0
    ver_w = rows[:, GLOBAL0:GLOBAL0 + 1] + occ + 1
    verw = _col_to_row(jnp.where(is_w, ver_w, 0))

    raw = jnp.maximum(
        jnp.maximum(rows[:, RAW0:RAW0 + 1], acc[:, RAW:RAW + 1]),
        jnp.maximum(_masked_max(vis, verw),
                    _pend_max(rows, n_chunks, chunk_of)),
    )
    contrib = _col_to_row(jnp.where(is_w, ver_w, raw))
    floor = jnp.maximum(
        jnp.maximum(rows[:, FLOOR0:FLOOR0 + 1], acc[:, FLOOR:FLOOR + 1]),
        _masked_max(floor_mask, contrib),
    )
    out = _with_cols(jnp.zeros(acc.shape, jnp.int32), occ, raw, floor)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BUF_ROWS, t), 0)
    buf = jnp.where(sub == VERW, verw, jnp.where(sub == CONTRIB, contrib, 0))
    return out, buf


def _tri_schedule(nb: int) -> tuple[np.ndarray, np.ndarray]:
    """(t, u) of every step of the lower-triangular (t, u <= t) walk:
    for each row tile, its cross partials in column order, then its
    diagonal finalize (which publishes the tile's buffer rows for later
    row tiles)."""
    ts = np.repeat(np.arange(nb, dtype=np.int32), np.arange(1, nb + 1))
    us = np.concatenate([np.arange(t + 1, dtype=np.int32) for t in range(nb)])
    return ts, us


# -- Pallas kernel -----------------------------------------------------------


def _op_ingest_kernel(ts_ref, us_ref, rows_ref, cols_ref, pend_ref, out_ref,
                      buf_ref):
    i = pl.program_id(0)
    t, u = ts_ref[i], us_ref[i]

    @pl.when(u == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    @pl.when(u < t)
    def _cross():
        out_ref[...] = _accumulate(
            out_ref[...], rows_ref[...], cols_ref[...], buf_ref[u]
        )

    @pl.when(u == t)
    def _diag():
        out, buf = _finalize_tile(
            rows_ref[...], cols_ref[...], out_ref[...],
            pend_ref.shape[0], lambda k: pend_ref[k],
        )
        out_ref[...] = out
        buf_ref[t] = buf


def op_ingest_pallas(
    packed: _Packed, *, block: int = 128, interpret: bool = False
) -> tuple[Array, Array, Array]:
    """Tiled ingest via ``pallas_call``.  Returns ``(occ, raw, floor)``."""
    meta, meta_t, pend, b = packed
    bp = meta.shape[0]
    assert bp % block == 0, f"padded B={bp} must tile into block={block}"
    nb = bp // block
    ts, us = _tri_schedule(nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # One step per ordered tile pair (t, u <= t) — the grid walks
        # only the lower triangle, nothing is fetched for u > t.
        grid=(len(ts),),
        in_specs=[
            pl.BlockSpec((block, OP_COLS), lambda i, ts, us: (ts[i], 0)),
            pl.BlockSpec((OP_COLS, block), lambda i, ts, us: (0, us[i])),
            pl.BlockSpec(pend.shape, lambda i, ts, us: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block, OUT_COLS), lambda i, ts, us: (ts[i], 0)
        ),
        scratch_shapes=[pltpu.VMEM((nb, BUF_ROWS, block), jnp.int32)],
    )
    out = pl.pallas_call(
        _op_ingest_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, OUT_COLS), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            # Row tiles accumulate across column steps and read buffer
            # rows published by earlier diagonal steps: strict order.
            dimension_semantics=("arbitrary",),
        ),
        name="op_ingest",
        interpret=interpret,
    )(jnp.asarray(ts), jnp.asarray(us), meta, meta_t, pend)
    return out[:b, OCC], out[:b, RAW], out[:b, FLOOR]


# -- jnp tiled twin (the CPU fast path) --------------------------------------


def op_ingest_tiled(packed: _Packed, *, block: int = 256):
    """The kernel's block walk as a ``lax.scan`` over tile *pairs*.

    Walks the same lower-triangular ``(t, u <= t)`` tile-pair sequence
    as the Pallas grid — a ``lax.cond`` picks the cross-tile partial
    step or the diagonal finalize step, both the kernel's own tile math
    — so only the ~B²/2 ordered pairs are ever touched and every step
    works on ``(block, block)`` tiles: peak memory O(B·block) for the
    carried accumulators, never O(B²).
    """
    meta, meta_t, pend, b = packed
    bp = meta.shape[0]
    nb = bp // block
    ts, us = _tri_schedule(nb)

    def tiles(t, u, out):
        rows = jax.lax.dynamic_slice(meta, (t * block, 0), (block, OP_COLS))
        cols = jax.lax.dynamic_slice(meta_t, (0, u * block), (OP_COLS, block))
        acc = jax.lax.dynamic_slice(out, (t * block, 0), (block, OUT_COLS))
        return rows, cols, acc

    def cross(carry, t, u):
        buf, out = carry
        rows, cols, acc = tiles(t, u, out)
        acc = _accumulate(acc, rows, cols, buf[u])
        return buf, jax.lax.dynamic_update_slice(out, acc, (t * block, 0))

    def diag(carry, t, u):
        buf, out = carry
        rows, cols, acc = tiles(t, u, out)
        acc, buft = _finalize_tile(
            rows, cols, acc, pend.shape[0], lambda k: pend[k]
        )
        out = jax.lax.dynamic_update_slice(out, acc, (t * block, 0))
        return buf.at[t].set(buft), out

    def step(carry, tu):
        t, u = tu
        carry = jax.lax.cond(
            u == t,
            lambda c: diag(c, t, u),
            lambda c: cross(c, t, u),
            carry,
        )
        return carry, None

    init = (
        jnp.zeros((nb, BUF_ROWS, block), jnp.int32),
        jnp.zeros((bp, OUT_COLS), jnp.int32),
    )
    (_, out), _ = jax.lax.scan(step, init, (jnp.asarray(ts), jnp.asarray(us)))
    return out[:b, OCC], out[:b, RAW], out[:b, FLOOR]


# -- fused closed-form path (the CPU hot path) -------------------------------


def _seg_prefix_max(seg: Array, val: Array, n_segs: int) -> Array:
    """Exclusive per-segment prefix max of ``val`` in stream order.

    ``out[i] = max(val[j] for j < i with seg[j] == seg[i])`` (identity
    0) in O(B log B): sort the packed key ``seg * B + i`` — the sorted
    key *is* the permutation (``key % B``) and the segment run map
    (``key // B``), so no argsort is ever materialized — then run a
    segmented inclusive max scan over the runs and shift it exclusive.
    Caller must guarantee ``n_segs * B < 2**31`` (the packed key stays
    int32); :func:`repro.kernels.ops.op_ingest` checks this before
    selecting the fused path.
    """
    b = seg.shape[0]
    assert n_segs * b < 2 ** 31, "packed segment key overflows int32"
    key = seg * jnp.int32(b) + jnp.arange(b, dtype=jnp.int32)
    skey = jax.lax.sort(key)
    perm = skey % jnp.int32(b)
    sseg = skey // jnp.int32(b)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), sseg[1:] != sseg[:-1]]
    )
    sval = val[perm]

    def combine(a, c):
        va, fa = a
        vc, fc = c
        return jnp.where(fc, vc, jnp.maximum(va, vc)), fa | fc

    incl, _ = jax.lax.associative_scan(combine, (sval, start))
    exc = jnp.where(
        start, 0, jnp.concatenate([jnp.zeros((1,), val.dtype), incl[:-1]])
    )
    return jnp.zeros((b,), val.dtype).at[perm].set(exc)


def op_ingest_fused(
    client: Array,
    replica: Array,
    resource: Array,
    is_write: Array,
    g0: Array,
    raw0: Array,
    floor0: Array,
    *,
    n_clients: int,
    n_replicas: int,
    n_resources: int,
    op_index: Array | None = None,
    apply_index: Array | None = None,
    pend_version: Array | None = None,
    pend_resource: Array | None = None,
    pend_live: Array | None = None,
    pend_apply: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Closed-form ingest: O(B·R + B log B), no O(B²) pair sweep.

    Bit-identical to :func:`repro.kernels.ref.op_ingest_ref` — the three
    reductions are per-segment prefix counts/maxima, so they collapse to

      * ``occ``   — an exclusive per-resource running count of writes
        (one ``(B, R)`` cumsum);
      * the coordinator-visible and session-floor maxima — exclusive
        per-(replica, resource) / per-(client, resource) prefix maxima
        via :func:`_seg_prefix_max`;
      * the cadence-visible and pending-ring maxima — an activation
        *timeline*: batch op indices are affine, so write ``j`` (pending
        slot ``q``) becomes visible to every op from batch-local index
        ``max(j+1, apply_index[j] - op_index[0])`` (``pend_apply[q] -
        op_index[0]``) on; scattering versions at their activation rows
        of a ``(B+1, R)`` grid and running a cumulative max down the op
        axis serves every op its visible per-resource max.

    Preconditions (checked by the dispatch in ``repro.kernels.ops``):
    ``op_index`` affine (``op_index[i] == op_index[0] + i`` — every
    store-layer batch is), ids in range, and the packed segment keys
    fit int32.  Unlike the tiled/Pallas paths this needs the static
    state sizes, but touches no padded pair blocks at all.
    """
    c = jnp.asarray(client, jnp.int32)
    p = jnp.asarray(replica, jnp.int32)
    r = jnp.asarray(resource, jnp.int32)
    is_w = jnp.asarray(is_write, bool)
    g0 = jnp.asarray(g0, jnp.int32)
    raw0 = jnp.asarray(raw0, jnp.int32)
    floor0 = jnp.asarray(floor0, jnp.int32)
    b = c.shape[0]
    R = n_resources

    # occ: exclusive per-resource prefix write count.
    onehot = (
        (r[:, None] == jnp.arange(R, dtype=jnp.int32)[None, :])
        & is_w[:, None]
    ).astype(jnp.int32)
    exc_cnt = jnp.cumsum(onehot, axis=0) - onehot
    occ = jnp.take_along_axis(exc_cnt, r[:, None], axis=1)[:, 0]

    ver_w = g0 + occ + 1
    verw = jnp.where(is_w, ver_w, 0)

    # Coordinator visibility: per-(replica, resource) prefix max.
    coord_max = _seg_prefix_max(p * jnp.int32(R) + r, verw, n_replicas * R)

    raw = jnp.maximum(raw0, coord_max)
    if apply_index is not None or pend_apply is not None:
        step0 = jnp.asarray(op_index, jnp.int32)[0]
        rows = jnp.arange(1, b + 1, dtype=jnp.int32)
        timeline = jnp.zeros((b + 1, R), jnp.int32)
        if apply_index is not None:
            act = jnp.clip(
                jnp.maximum(rows, jnp.asarray(apply_index, jnp.int32) - step0),
                0, b,
            )
            timeline = timeline.at[act, r].max(verw)
        if pend_apply is not None:
            pact = jnp.clip(
                jnp.asarray(pend_apply, jnp.int32) - step0, 0, b
            )
            res_safe = jnp.where(
                jnp.asarray(pend_live, bool),
                jnp.asarray(pend_resource, jnp.int32),
                R,
            )
            timeline = timeline.at[pact, res_safe].max(
                jnp.asarray(pend_version, jnp.int32), mode="drop"
            )
        seen = jax.lax.cummax(timeline, axis=0)
        cad = seen[jnp.arange(b, dtype=jnp.int32), r]
        raw = jnp.maximum(raw, cad)

    # Session floor: per-(client, resource) prefix max of contributions.
    contrib = jnp.where(is_w, ver_w, raw)
    floor = jnp.maximum(
        floor0,
        _seg_prefix_max(c * jnp.int32(R) + r, contrib, n_clients * R),
    )
    return occ, raw, floor


__all__ = [
    "pack_ops",
    "op_ingest_pallas",
    "op_ingest_tiled",
    "op_ingest_fused",
    "op_ingest_ref",
    "NEVER",
]

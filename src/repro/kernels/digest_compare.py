"""Pallas TPU kernel for the gossip digest-compare pass.

The gossip anti-entropy subsystem (``repro.gossip``) summarizes each
replica's ``(P, R)`` applied-version table into per-resource-range
digests — ``(K, 4)`` int32 components per replica (wrapping SUM, MAX,
weighted CHK, nonzero CNT; see ``repro.gossip.digest``) — and a digest
exchange diffs two replicas' summaries to find the stale ranges worth
repairing.  At fleet scale (every gossip round compares every scheduled
peer pair over every range) this is a dense elementwise VPU workload
over packed ``(pairs · ranges)`` rows: the same shape as
``kernels/placement_score``, so the kernel tiles the row axis and each
grid step loads one ``(block, DIG_COLS)`` slab of paired digest
components and writes the ``(block, OUT_COLS)`` verdict tile — O(rows ·
block) memory, never the dense (pairs, ranges, components) cube at
once.

The verdict math lives in one shared tile function
(:func:`compare_tile`) executed identically by the Pallas body and the
``lax.map`` twin (:func:`digest_compare_tiled`), and re-derived
whole-array by the dense oracle
(``repro.kernels.ref.digest_compare_ref``) — integer-only compares, so
all three are *bit-exact* replicas (``tests/test_gossip.py`` sweeps
range counts, tile sizes, and empty/fully-stale replicas).

Verdict semantics per (pair, range) row:

  * ``DIFFER``   — any digest component disagrees (the stale-range
    mask: this range needs a repair merge);
  * ``A_BEHIND`` / ``B_BEHIND`` — which side is missing versions,
    ordered by (MAX, then SUM); a tie on both with differing CHK/CNT
    means the replicas *diverged* within the range and both flags are
    set (the repair merge is symmetric anyway — direction is
    telemetry).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Packed input layout: one row per (pair, range), columns below, padded
# to DIG_COLS so tiles stay lane-aligned.  Inert rows have VALID=0 and
# produce all-zero verdicts.
A_SUM, A_MAX, A_CHK, A_CNT = 0, 1, 2, 3
B_SUM, B_MAX, B_CHK, B_CNT = 4, 5, 6, 7
VALID = 8
DIG_COLS = 16

# Output layout (int32 0/1 flags).
DIFFER, A_BEHIND, B_BEHIND = 0, 1, 2
OUT_COLS = 4


def compare_tile(tile: jax.Array) -> jax.Array:
    """Verdicts for one ``(block, DIG_COLS)`` tile — the one shared
    implementation of the compare math (integer-only, so the Pallas
    kernel, the jnp twin, and the dense oracle agree bit-for-bit).

    Columns are taken as ``(block, 1)`` slices and the verdicts written
    by a lane select: Mosaic lowers neither a stack of boolean vectors
    nor a column scatter."""
    def col(k):
        return tile[:, k:k + 1]

    d_sum = col(A_SUM) - col(B_SUM)
    d_max = col(A_MAX) - col(B_MAX)
    d_chk = col(A_CHK) - col(B_CHK)
    d_cnt = col(A_CNT) - col(B_CNT)
    valid = col(VALID) > 0
    differ = valid & (
        (d_sum != 0) | (d_max != 0) | (d_chk != 0) | (d_cnt != 0)
    )
    # Direction by (MAX, then SUM); a full tie that still differs
    # (CHK/CNT disagree) is divergence — both sides need the merge.
    tie = (d_max == 0) & (d_sum == 0)
    a_behind = differ & ((d_max < 0) | ((d_max == 0) & (d_sum < 0)) | tie)
    b_behind = differ & ((d_max > 0) | ((d_max == 0) & (d_sum > 0)) | tie)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile.shape[0], OUT_COLS), 1)
    return jnp.where(
        ((lane == DIFFER) & differ)
        | ((lane == A_BEHIND) & a_behind)
        | ((lane == B_BEHIND) & b_behind),
        1, 0,
    ).astype(jnp.int32)


def pack_digests(
    a: jax.Array,      # (M, 4) int32 — side-A digest components
    b: jax.Array,      # (M, 4) int32 — side-B digest components
    *,
    block: int,
) -> jax.Array:
    """Pack paired digest rows into the kernel's ``(M', DIG_COLS)``
    layout, padded to a ``block`` multiple with inert (VALID=0) rows."""
    m = a.shape[0]
    pad = (-m) % block
    packed = jnp.zeros((m + pad, DIG_COLS), jnp.int32)
    packed = packed.at[:m, A_SUM:A_CNT + 1].set(a.astype(jnp.int32))
    packed = packed.at[:m, B_SUM:B_CNT + 1].set(b.astype(jnp.int32))
    packed = packed.at[:m, VALID].set(1)
    return packed


def _digest_compare_kernel(in_ref, out_ref):
    out_ref[...] = compare_tile(in_ref[...])


def digest_compare_pallas(
    packed: jax.Array,  # (M', DIG_COLS) int32, M' a multiple of block
    *,
    block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Tiled digest compare; returns the ``(M', OUT_COLS)`` verdicts."""
    m = packed.shape[0]
    block = min(block, m)
    assert m % block == 0, f"M={m} must be a multiple of block={block}"
    nb = m // block
    return pl.pallas_call(
        _digest_compare_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block, DIG_COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, OUT_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, OUT_COLS), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            # Row tiles are independent; let the compiler parallelize.
            dimension_semantics=("parallel",),
        ),
        name="digest_compare",
        interpret=interpret,
    )(packed)


def digest_compare_tiled(
    packed: jax.Array,
    *,
    block: int = 128,
) -> jax.Array:
    """jnp twin of the Pallas kernel: same tile walk, ``lax.map`` grid.

    The CPU fast path (Pallas runs interpreted there) — O(block) rows
    live per step, and bit-exact with the kernel because every tile
    runs the identical :func:`compare_tile`."""
    m = packed.shape[0]
    block = min(block, m)
    assert m % block == 0, f"M={m} must be a multiple of block={block}"
    nb = m // block
    tiles = packed.reshape(nb, block, DIG_COLS)
    out = jax.lax.map(compare_tile, tiles)
    return out.reshape(m, OUT_COLS)

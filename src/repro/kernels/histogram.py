"""Pallas TPU kernel for device-side metric binning.

The observability plane (``repro.obs``) folds per-epoch observation
batches — staleness ages, read latencies, violation severities, hint
queue depths — into fixed-bin histograms that live in the unified
engine's scan carry, so a whole replay accumulates its distributions
device-side in one jit entry.  The hot shape is ``(M, B)``: M metric
rows (a handful), B observations per row (the epoch batch).  Each row
carries its own bin range as ``(lo, 1/width)`` params; an observation
maps to ``bin = clip(floor((v - lo) / width), 0, n_bins-1)`` — below
``lo`` saturates into bin 0, at-or-above ``hi`` into the top bin — and
masked-out observations contribute nothing.

The binning math lives in one shared tile function (:func:`bin_tile`)
executed identically by the Pallas body and the ``lax.map`` twin
(:func:`histogram_tiled`), and re-derived whole-array by the dense
oracle (``repro.kernels.ref.histogram_ref``).  The bin index is an
elementwise f32 multiply + floor and the counts are integer sums, so
all three implementations are *bit-exact* replicas regardless of tile
walk order (``tests/test_obs.py`` sweeps bin counts, batch sizes, and
empty/saturated bins).

The Pallas grid walks ``B`` in ``block``-column tiles and accumulates
partial counts into one persistent ``(M, n_bins)`` output block
(constant index map, zero-initialised at the first grid step) — O(M ·
(block + n_bins)) memory per step, never the ``(M, B, n_bins)`` one-hot
cube at once.

:func:`hist_percentile` extracts percentiles from the cumulative bins:
for integer-quantised observations (every engine metric — versions,
depths, and RTTs drawn from a fixed matrix binned at unit width) it
reproduces ``jnp.percentile(x, q, method="lower")`` exactly; for
general streams it returns the lower edge of the rank's bin.  An empty
histogram reports ``lo`` (percentile rows must stay finite for the
bench gates).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Per-row bin params layout: (M, 2) f32.
LO, INV_W = 0, 1


def bin_tile(
    vals: jax.Array,    # (M, block) f32
    mask: jax.Array,    # (M, block) int32 — 1 = count, 0 = inert
    params: jax.Array,  # (M, 2) f32 — [lo, 1/width] per metric row
    n_bins: int,
) -> jax.Array:
    """Partial counts for one column tile — the one shared
    implementation of the binning math (elementwise f32 index + integer
    sum, so the Pallas kernel, the jnp twin, and the dense oracle agree
    bit-for-bit)."""
    lo = params[:, LO:LO + 1]
    inv_w = params[:, INV_W:INV_W + 1]
    idx = jnp.floor((vals - lo) * inv_w).astype(jnp.int32)
    idx = jnp.clip(idx, 0, n_bins - 1)
    sel = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_bins), 2)
    hit = (idx[:, :, None] == sel) & (mask[:, :, None] > 0)
    return jnp.sum(hit.astype(jnp.int32), axis=1)


def metric_params(lo, hi, n_bins: int) -> jax.Array:
    """Pack per-row ``[lo, 1/width]`` bin params; ``lo``/``hi`` scalars
    or ``(M,)`` arrays (broadcast against each other)."""
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    lo, hi = jnp.broadcast_arrays(jnp.atleast_1d(lo), jnp.atleast_1d(hi))
    inv_w = jnp.float32(n_bins) / (hi - lo)
    return jnp.stack([lo, inv_w], axis=1)


def pack_observations(
    vals: jax.Array,           # (M, B) f32
    mask: jax.Array | None,    # (M, B) — 0/1; None counts everything
    *,
    block: int,
) -> tuple[jax.Array, jax.Array]:
    """Pad the observation axis to a ``block`` multiple with inert
    (mask=0) columns; returns ``(vals, mask)`` as f32/int32."""
    m, b = vals.shape
    vals = jnp.asarray(vals, jnp.float32)
    if mask is None:
        mask = jnp.ones((m, b), jnp.int32)
    else:
        mask = jnp.asarray(mask, jnp.int32)
    pad = (-b) % block
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return vals, mask


def _histogram_kernel(n_bins, val_ref, mask_ref, par_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += bin_tile(
        val_ref[...], mask_ref[...], par_ref[...], n_bins
    )


def histogram_pallas(
    vals: jax.Array,    # (M, B') f32, B' a multiple of block
    mask: jax.Array,    # (M, B') int32
    params: jax.Array,  # (M, 2) f32
    *,
    n_bins: int,
    block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Tiled binning via ``pallas_call``; returns ``(M, n_bins)`` int32
    counts.  The grid is sequential ("arbitrary") because every column
    tile accumulates into the same persistent output block."""
    m, b = vals.shape
    block = min(block, b)
    assert b % block == 0, f"B={b} must be a multiple of block={block}"
    nb = b // block
    return pl.pallas_call(
        functools.partial(_histogram_kernel, n_bins),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, block), lambda i: (0, i)),
            pl.BlockSpec((m, block), lambda i: (0, i)),
            pl.BlockSpec((m, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_bins), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            # Column tiles revisit the same output block; the grid must
            # run in order.
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(vals, mask, params)


def histogram_tiled(
    vals: jax.Array,
    mask: jax.Array,
    params: jax.Array,
    *,
    n_bins: int,
    block: int = 128,
) -> jax.Array:
    """jnp twin of the Pallas kernel: same tile walk, ``lax.map`` grid.

    The CPU fast path (Pallas runs interpreted there) — O(M · block)
    observations live per step, and bit-exact with the kernel because
    every tile runs the identical :func:`bin_tile` and integer count
    addition is order-free."""
    m, b = vals.shape
    block = min(block, b)
    assert b % block == 0, f"B={b} must be a multiple of block={block}"
    nb = b // block
    tiles = (
        vals.reshape(m, nb, block).swapaxes(0, 1),
        mask.reshape(m, nb, block).swapaxes(0, 1),
    )
    parts = jax.lax.map(
        lambda t: bin_tile(t[0], t[1], params, n_bins), tiles
    )
    return jnp.sum(parts, axis=0, dtype=jnp.int32)


def hist_edges(lo: float, hi: float, n_bins: int) -> jax.Array:
    """The ``n_bins + 1`` bin edges of one metric row."""
    return jnp.linspace(lo, hi, n_bins + 1, dtype=jnp.float32)


def hist_percentile(
    hist: jax.Array,  # (..., n_bins) int32 counts
    lo,               # scalar or (...,) — bin range lower bound
    width,            # scalar or (...,) — bin width
    q: float,
) -> jax.Array:
    """The q-th percentile's bin lower edge from cumulative counts.

    Rank semantics match ``jnp.percentile(x, q, method="lower")``:
    ``rank = floor(q/100 · (n-1))`` and the answer is the bin holding
    the rank-th sorted observation — exact when observations are
    quantised to bin lower edges, the lower-edge approximation
    otherwise.  Empty histograms report ``lo`` so downstream gates stay
    finite."""
    hist = jnp.asarray(hist, jnp.int32)
    n = jnp.sum(hist, axis=-1)
    rank = jnp.floor(
        jnp.float32(q) / 100.0
        * jnp.maximum(n - 1, 0).astype(jnp.float32)
    ).astype(jnp.int32)
    cum = jnp.cumsum(hist, axis=-1)
    idx = jnp.sum((cum <= rank[..., None]).astype(jnp.int32), axis=-1)
    idx = jnp.where(n > 0, jnp.minimum(idx, hist.shape[-1] - 1), 0)
    lo = jnp.asarray(lo, jnp.float32)
    width = jnp.asarray(width, jnp.float32)
    return lo + idx.astype(jnp.float32) * width

"""Pallas TPU kernel for the (resources × candidate-plans) placement scorer.

The replica-placement planner (``repro.geo.placement``) scores every
resource's regional demand vector against every candidate
(replication-factor × region-assignment) plan: an analytic eq. 5-8
bill blended over the (R, K, G) grid plus the SLA's structural latency
check.  At fleet scale (10^5-10^6 resources × hundreds of candidate
plans, re-planned as demand shifts) this is the same shape of VPU
workload as ``kernels/policy_score``: dense elementwise math over an
(R, K) grid with rank-1 broadcasts from per-candidate tables, reduced
over a tiny static region axis.

The kernel tiles the resource axis; each grid step loads one
``(block_r, G)`` slab of read/write demand plus the whole per-candidate
``(K, G)`` price/latency tables and the ``(2, K)`` candidate metadata
(storage cost, validity) — small, replicated to every step — and writes
the scored ``(block_r, K)`` utility/feasibility tiles.  The region
reduction is an unrolled fixed-order loop (``G`` is static and tiny),
which is what makes the kernel, the tiled jnp twin
(:func:`placement_score_tiled`), and the dense oracle
(``repro.kernels.ref.placement_score_ref``) *bit-exact* replicas of
each other — the acceptance bar checked in ``tests/test_geo.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import INFEASIBLE_PENALTY, STRUCTURAL_WEIGHT


def _placement_score_kernel(
    reads_ref, writes_ref, rprice_ref, wprice_ref, rtt_ref, meta_ref,
    util_ref, feas_ref, *, max_latency_ms: float,
):
    reads = reads_ref[...]          # (br, G)
    writes = writes_ref[...]        # (br, G)
    # Candidate tables arrive one candidate per lane, so region ``gi``
    # of every candidate is the (1, K) row ``x[gi:gi + 1, :]``.
    rprice = rprice_ref[...]        # (G, K)
    wprice = wprice_ref[...]        # (G, K)
    rtt = rtt_ref[...]              # (G, K)
    meta = meta_ref[...]            # (2, K)

    br, g = reads.shape
    k = rprice.shape[1]
    store = meta[0:1, :]
    valid = meta[1:2, :] > 0.0
    max_lat = jnp.float32(max_latency_ms)
    structural = jnp.float32(STRUCTURAL_WEIGHT)

    cost = jnp.broadcast_to(store, (br, k))
    excess = jnp.zeros((br, k), jnp.float32)
    for gi in range(g):             # static, fixed order — bit-exact twin
        cost = cost + reads[:, gi:gi + 1] * rprice[gi:gi + 1, :]
        cost = cost + writes[:, gi:gi + 1] * wprice[gi:gi + 1, :]
        demand = (reads[:, gi:gi + 1] + writes[:, gi:gi + 1]) > 0.0
        late = rtt[gi:gi + 1, :] > max_lat
        excess = excess + jnp.where(demand & late, structural, 0.0)
    excess = excess + jnp.where(valid, 0.0, structural)
    util_ref[...] = -cost - jnp.float32(INFEASIBLE_PENALTY) * excess
    feas_ref[...] = jnp.where(excess == 0.0, 1, 0)


def placement_score(
    reads: jax.Array,        # (R, G) f32
    writes: jax.Array,       # (R, G) f32
    read_price: jax.Array,   # (K, G) f32
    write_price: jax.Array,  # (K, G) f32
    read_rtt: jax.Array,     # (K, G) f32
    cand_meta: jax.Array,    # (2, K) f32
    *,
    max_latency_ms: float,
    block_r: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Tiled placement scoring.  Returns ``(utility, feasible)``:
    ``utility`` (R, K) float32, ``feasible`` (R, K) int32.

    ``R`` must be a multiple of ``block_r`` (pad with zero-demand rows —
    the jit'd wrapper ``repro.kernels.ops.placement_score`` does this).
    """
    r, g = reads.shape
    k = read_price.shape[0]
    block_r = min(block_r, r)
    assert r % block_r == 0, f"R={r} must be a multiple of block_r={block_r}"
    nb = r // block_r

    kernel = functools.partial(
        _placement_score_kernel, max_latency_ms=float(max_latency_ms)
    )
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_r, g), lambda i: (i, 0)),
            pl.BlockSpec((block_r, g), lambda i: (i, 0)),
            pl.BlockSpec((g, k), lambda i: (0, 0)),
            pl.BlockSpec((g, k), lambda i: (0, 0)),
            pl.BlockSpec((g, k), lambda i: (0, 0)),
            pl.BlockSpec((2, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, k), lambda i: (i, 0)),
            pl.BlockSpec((block_r, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k), jnp.float32),
            jax.ShapeDtypeStruct((r, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            # Tiles are independent; let the compiler parallelize.
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(reads, jnp.float32),
        jnp.asarray(writes, jnp.float32),
        jnp.asarray(read_price, jnp.float32).T,
        jnp.asarray(write_price, jnp.float32).T,
        jnp.asarray(read_rtt, jnp.float32).T,
        jnp.asarray(cand_meta, jnp.float32),
    )


def placement_score_tiled(
    reads: jax.Array,
    writes: jax.Array,
    read_price: jax.Array,
    write_price: jax.Array,
    read_rtt: jax.Array,
    cand_meta: jax.Array,
    *,
    max_latency_ms: float,
    block_r: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """jnp twin of the Pallas kernel: same tile walk, ``lax.map`` grid.

    The CPU fast path (Pallas runs interpreted there) — O(block_r·K)
    live per step instead of the oracle's whole (R, K) intermediates,
    and bit-exact with both the kernel and the oracle because every
    tile runs the identical unrolled-region reduction.
    """
    from repro.kernels.ref import placement_score_ref

    r, g = reads.shape
    block_r = min(block_r, r)
    assert r % block_r == 0, f"R={r} must be a multiple of block_r={block_r}"
    nb = r // block_r
    reads = jnp.asarray(reads, jnp.float32).reshape(nb, block_r, g)
    writes = jnp.asarray(writes, jnp.float32).reshape(nb, block_r, g)

    def tile(args):
        rd, wr = args
        return placement_score_ref(
            rd, wr, read_price, write_price, read_rtt, cand_meta,
            max_latency_ms=max_latency_ms,
        )

    util, feas = jax.lax.map(tile, (reads, writes))
    k = util.shape[-1]
    return util.reshape(r, k), feas.reshape(r, k)

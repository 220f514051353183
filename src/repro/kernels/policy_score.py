"""Pallas TPU kernel for the batched (sessions × levels) SLA scorer.

The adaptive control plane (``repro.policy``) re-scores every session
against every candidate consistency level each merge epoch: blend the
analytic per-level $ cost with windowed staleness telemetry, check the
four SLA bounds, and emit a utility whose argmax is the cheapest
feasible level.  At fleet scale (10^5-10^6 sessions × 6 levels, every
epoch) this is a pure VPU workload: all operands are dense, the math is
elementwise over the (S, L) grid with rank-1 broadcasts from the packed
session-parameter rows and level-table columns.

The kernel tiles the session axis; each grid step loads one
``(block_s, SP_COLS)`` slab of session params plus the whole
``(LVL_COLS, L)`` level table (tiny, replicated to every step) and the
matching ``(block_s, L)`` telemetry tiles, then writes the scored
``(block_s, L)`` utility/feasibility tiles.  No cross-tile state, so
grid steps are independent.

Semantics are defined by ``repro.kernels.ref.policy_score_ref`` — the
acceptance bar is *bit-exact* agreement (identical op order and
dtypes), checked in ``tests/test_policy.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (
    INFEASIBLE_PENALTY,
    LVL_COLS,
    LVL_READ_COST,
    LVL_READ_LAT,
    LVL_REPAIR_COST,
    LVL_STALE_AGE,
    LVL_WRITE_COST,
    SP_COLS,
    SP_MAX_AGE,
    SP_MAX_LAT,
    SP_MAX_STALE,
    SP_MAX_VIOL,
    SP_READ_FRAC,
    SP_VALID,
    STRUCTURAL_WEIGHT,
)


def _policy_score_kernel(sess_ref, lvl_ref, stale_ref, viol_ref, count_ref,
                         util_ref, feas_ref):
    sess = sess_ref[...]          # (bs, SP_COLS)
    table = lvl_ref[...]          # (LVL_COLS, L)
    stale = stale_ref[...]        # (bs, L)
    viol = viol_ref[...]
    count = count_ref[...]

    col = lambda i: sess[:, i:i + 1]          # noqa: E731
    rf = col(SP_READ_FRAC)
    max_stale = col(SP_MAX_STALE)
    max_viol = col(SP_MAX_VIOL)
    max_lat = col(SP_MAX_LAT)
    max_age = col(SP_MAX_AGE)
    valid = col(SP_VALID) > 0.0

    read_cost = table[LVL_READ_COST][None, :]
    write_cost = table[LVL_WRITE_COST][None, :]
    repair = table[LVL_REPAIR_COST][None, :]
    lat = table[LVL_READ_LAT][None, :]
    age = table[LVL_STALE_AGE][None, :]

    has = count > 0.0
    s_e = jnp.where(has, stale, 0.0)
    v_e = jnp.where(has, viol, 0.0)
    cost = rf * (read_cost + s_e * repair) + (1.0 - rf) * write_cost
    eps = jnp.float32(1.0e-6)
    structural = jnp.float32(STRUCTURAL_WEIGHT)
    excess = (
        jnp.maximum(s_e - max_stale, 0.0) / jnp.maximum(max_stale, eps)
        + jnp.maximum(v_e - max_viol, 0.0) / jnp.maximum(max_viol, eps)
        + structural * (lat > max_lat).astype(jnp.float32)
        + structural * (age > max_age).astype(jnp.float32)
    )
    feas = (excess == 0.0) & valid
    util_ref[...] = jnp.where(
        valid, -cost - jnp.float32(INFEASIBLE_PENALTY) * excess, 0.0
    )
    feas_ref[...] = feas.astype(jnp.int32)


def policy_score(
    sess: jax.Array,    # (S, SP_COLS) f32
    table: jax.Array,   # (LVL_COLS, L) f32
    stale: jax.Array,   # (S, L) f32
    viol: jax.Array,    # (S, L) f32
    count: jax.Array,   # (S, L) f32
    *,
    block_s: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Tiled fleet scoring.  Returns ``(utility, feasible)``:
    ``utility`` (S, L) float32, ``feasible`` (S, L) int32.

    ``S`` must be a multiple of ``block_s`` (pad with SP_VALID=0 rows —
    the jit'd wrapper ``repro.kernels.ops.policy_score`` does this).
    """
    s, l = stale.shape
    block_s = min(block_s, s)
    assert s % block_s == 0, f"S={s} must be a multiple of block_s={block_s}"
    nb = s // block_s

    return pl.pallas_call(
        _policy_score_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_s, SP_COLS), lambda i: (i, 0)),
            pl.BlockSpec((LVL_COLS, l), lambda i: (0, 0)),
            pl.BlockSpec((block_s, l), lambda i: (i, 0)),
            pl.BlockSpec((block_s, l), lambda i: (i, 0)),
            pl.BlockSpec((block_s, l), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, l), lambda i: (i, 0)),
            pl.BlockSpec((block_s, l), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, l), jnp.float32),
            jax.ShapeDtypeStruct((s, l), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            # Tiles are independent; let the compiler parallelize.
            dimension_semantics=("parallel",),
        ),
        name="policy_score",
        interpret=interpret,
    )(
        jnp.asarray(sess, jnp.float32),
        jnp.asarray(table, jnp.float32),
        jnp.asarray(stale, jnp.float32),
        jnp.asarray(viol, jnp.float32),
        jnp.asarray(count, jnp.float32),
    )

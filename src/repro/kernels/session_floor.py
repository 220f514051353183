"""Pallas TPU kernel for the batched X-STCC session-floor admission check.

This is the serving-scale per-op hot loop (paper §3.4 client side): for
every op of a ``(B,)`` batch, gather the replica's applied version and
the session's MR/RYW floor, decide admissibility
(``replica_version[p, r] >= max(read_floor, write_floor)``), lift the
served version to the floor under session enforcement, and scatter-max
the served versions back into the read floors.

TPU mapping: the gathers/scatters are irregular, so the kernel turns
them into dense one-hot contractions — MXU/VPU-friendly, no
gather/scatter primitives:

  * gather ``rv[p_i, r_i]``  ->  ``sum((onehot_p @ rv) * onehot_r, -1)``
  * scatter-max into floors  ->  ``max_b(onehot_c ⊗ onehot_r * served)``

The grid tiles the batch; each tile accumulates its partial floor
update into the (C, R) output across sequentially-executed grid steps
("arbitrary" dimension semantics), exactly the flash-attention
accumulator pattern.  int32 versions are exact in f32 up to 2^24 —
far above any snapshot version the engine produces.

Semantics are defined by ``repro.kernels.ref.session_admit_ref``; the
sweeps in ``tests/test_replicated_store.py`` check agreement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ops meta columns
CLIENT, REPLICA, RESOURCE, VALID = 0, 1, 2, 3
META_COLS = 8
# out columns
SERVED, ADMISSIBLE, FLOOR, RAW = 0, 1, 2, 3
OUT_COLS = 8


def _session_floor_kernel(
    rv_ref, rf_ref, wf_ref, ops_ref, out_ref, newrf_ref,
    *, n_replicas: int, n_clients: int, n_resources: int, enforce: bool,
):
    ops = ops_ref[...]                       # (bm, META_COLS)
    bm = ops.shape[0]
    c = ops[:, CLIENT]
    p = ops[:, REPLICA]
    r = ops[:, RESOURCE]
    ok = ops[:, VALID] > 0

    rv = rv_ref[...].astype(jnp.float32)     # (P, R)
    rf = rf_ref[...].astype(jnp.float32)     # (C, R)
    wf = wf_ref[...].astype(jnp.float32)     # (C, R)

    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
    oh_p = (p[:, None] == iota((bm, n_replicas), 1)).astype(jnp.float32)
    oh_c = (c[:, None] == iota((bm, n_clients), 1)).astype(jnp.float32)
    oh_r = (r[:, None] == iota((bm, n_resources), 1)).astype(jnp.float32)

    # One-hot gathers (exact for int32 versions < 2^24).
    raw = jnp.sum(jnp.dot(oh_p, rv) * oh_r, axis=-1)
    fl = jnp.maximum(
        jnp.sum(jnp.dot(oh_c, rf) * oh_r, axis=-1),
        jnp.sum(jnp.dot(oh_c, wf) * oh_r, axis=-1),
    )
    adm = jnp.logical_and(ok, raw >= fl)
    served = jnp.maximum(raw, fl) if enforce else raw
    served = jnp.where(ok, served, 0.0)

    out = jnp.zeros((bm, OUT_COLS), jnp.int32)
    out = out.at[:, SERVED].set(served.astype(jnp.int32))
    out = out.at[:, ADMISSIBLE].set(adm.astype(jnp.int32))
    out = out.at[:, FLOOR].set(jnp.where(ok, fl, 0.0).astype(jnp.int32))
    out = out.at[:, RAW].set(jnp.where(ok, raw, 0.0).astype(jnp.int32))
    out_ref[...] = out

    # Scatter-max of served versions into the read floors: dense
    # (bm, C, R) one-hot product reduced over the batch tile, then
    # max-accumulated into the (C, R) output across grid steps.
    upd = jnp.max(
        oh_c[:, :, None] * oh_r[:, None, :] * served[:, None, None],
        axis=0,
    ).astype(jnp.int32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        newrf_ref[...] = jnp.maximum(rf_ref[...], upd)

    @pl.when(pl.program_id(0) > 0)
    def _accum():
        newrf_ref[...] = jnp.maximum(newrf_ref[...], upd)


def session_floor(
    replica_version: jax.Array,  # (P, R) int32
    read_floor: jax.Array,       # (C, R) int32
    write_floor: jax.Array,      # (C, R) int32
    ops_meta: jax.Array,         # (B, META_COLS) int32
    *,
    enforce: bool = True,
    block: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Tiled batched admission check.

    Returns ``(out, new_read_floor)`` where ``out`` is ``(B, OUT_COLS)``
    int32 (columns SERVED / ADMISSIBLE / FLOOR / RAW) and
    ``new_read_floor`` is the (C, R) floor table after the batch.
    ``B`` must be a multiple of ``block`` (pad with VALID=0 rows).
    """
    b = ops_meta.shape[0]
    n_replicas, n_resources = replica_version.shape
    n_clients = read_floor.shape[0]
    block = min(block, b)
    assert b % block == 0, f"B={b} must be a multiple of block={block}"
    nb = b // block

    kernel = functools.partial(
        _session_floor_kernel,
        n_replicas=n_replicas, n_clients=n_clients,
        n_resources=n_resources, enforce=enforce,
    )
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((n_replicas, n_resources), lambda i: (0, 0)),
            pl.BlockSpec((n_clients, n_resources), lambda i: (0, 0)),
            pl.BlockSpec((n_clients, n_resources), lambda i: (0, 0)),
            pl.BlockSpec((block, META_COLS), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, OUT_COLS), lambda i: (i, 0)),
            pl.BlockSpec((n_clients, n_resources), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, OUT_COLS), jnp.int32),
            jax.ShapeDtypeStruct((n_clients, n_resources), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            # The floor accumulator carries across grid steps.
            dimension_semantics=("arbitrary",),
        ),
        name="session_floor",
        interpret=interpret,
    )(replica_version, read_floor, write_floor, ops_meta)

"""A batch's vector-clock chain as one Pallas TPU kernel.

``repro.core.xstcc.apply_op_batch`` stamps each op of a batch with the
receive rule, in op order: ``svc = max(session[c], replica[p])`` ticked
at ``c``; the session row takes ``svc``, and a write joins it into its
coordinator's row.  Each op reads what its predecessors wrote, so the
chain is serial.  As an XLA ``lax.scan`` every step is a handful of
small dynamic slices and updates, each its own op inside a ``while``
body; here a whole batch runs in one kernel, with the clocks held in
vector registers:

  * session clocks ``(C8, 128)``, replica clocks ``(8, 128)``, lane
    ``k`` clock component ``k`` (lanes past the last component 0);
  * an op reads its session's and its replica's row by a masked row
    maximum and writes them back by a masked select (the replica row
    only for a write): no dynamic index touches the state;
  * each op's clock goes to row ``i`` of an ``(8, 128)`` tile, stored
    whole every 8 ops.

Ops arrive packed in SMEM (``client | replica << 8 | write << 16``,
:func:`repro.kernels.cadence_schedule.pack_ops`), ``T`` per step of a
sequential grid; the clocks carry between steps in VMEM scratch.  The
batch is padded with ops of no session and no replica, which read and
change nothing.  The ``lax.scan`` in ``xstcc`` (``clock_chain``) is the
CPU path and the oracle (``tests/test_vclock_chain.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kernel_ops
from repro.kernels.cadence_schedule import LANES, pack_ops

Array = jax.Array

# Ops per grid step once a batch is longer than one step: an SMEM block
# of a multiple of 1024 ops.
STEP = 1024
# The packed code of a padding op: a session and a replica no row has.
NO_OP = 0xFF | (0xFF << 8)


def _ceil8(x: int) -> int:
    return -(-x // 8) * 8


def fits(n_clients: int, n_replicas: int) -> bool:
    """Whether the kernel holds the sessions and the replicas."""
    return n_clients <= LANES and n_replicas <= 8


def resolve_impl(n_clients: int, n_replicas: int) -> str:
    """The kernel on a TPU where it holds the shapes, else the scan."""
    if jax.default_backend() == "tpu" and fits(n_clients, n_replicas):
        return "pallas"
    return "scan"


def _kernel(code_ref, s0_ref, r0_ref, vcs_ref, s_out, r_out, s_ref, r_ref,
            *, T):
    C8 = s_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row_c = jax.lax.broadcasted_iota(jnp.int32, (C8, LANES), 0)
    row_p = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        s_ref[...] = s0_ref[...]
        r_ref[...] = r0_ref[...]

    def eight_ops(g, state):
        S, R = state
        tile = jnp.zeros((8, LANES), jnp.int32)
        for j in range(8):
            code = code_ref[g * 8 + j]
            c = code & 0xFF
            p = (code >> 8) & 0xFF
            w = code >> 16
            own = row_c == c
            srow = jnp.max(jnp.where(own, S, 0), axis=0, keepdims=True)
            rrow = jnp.max(jnp.where(row_p == p, R, 0), axis=0,
                           keepdims=True)
            svc = jnp.maximum(srow, rrow) + jnp.where(lane == c, 1, 0)
            S = jnp.where(own, svc, S)
            R = jnp.where(row_p == jnp.where(w > 0, p, -1),
                          jnp.maximum(R, svc), R)
            tile = jnp.where(row_p == j, svc, tile)
        vcs_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :] = tile
        return S, R

    S, R = jax.lax.fori_loop(0, T // 8, eight_ops, (s_ref[...], r_ref[...]))
    s_ref[...] = S
    r_ref[...] = R
    s_out[...] = S
    r_out[...] = R


@functools.partial(jax.jit, static_argnames=("interpret",))
def vclock_chain(session_vc: Array, replica_vc: Array, client: Array,
                 replica: Array, is_write: Array, *,
                 interpret: bool | None = None
                 ) -> tuple[Array, Array, Array]:
    """``(session_vc, replica_vc, vcs)`` after a batch of ops: the final
    ``(C, K)`` session and ``(P, K)`` replica clocks and each op's
    ``(B, K)`` clock, as the scan gives them."""
    (C, K), P = session_vc.shape, replica_vc.shape[0]
    n = client.shape[0]
    if not (fits(C, P) and K <= LANES):
        raise ValueError(f"{C} sessions, {K} clock components, {P} "
                         f"replicas: the kernel holds at most {LANES}, "
                         f"{LANES}, 8")
    T = _ceil8(n) if n <= STEP else STEP
    n_blk = -(-n // T)
    code = jnp.pad(pack_ops(client, replica, is_write),
                   (0, n_blk * T - n), constant_values=NO_OP)
    C8 = _ceil8(C)
    s0 = jnp.pad(session_vc, ((0, C8 - C), (0, LANES - K)))
    r0 = jnp.pad(replica_vc, ((0, 8 - P), (0, LANES - K)))
    vcs, s_fin, r_fin = pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((T,), lambda b: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((C8, LANES), lambda b: (0, 0)),
            pl.BlockSpec((8, LANES), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T, LANES), lambda b: (b, 0)),
            pl.BlockSpec((C8, LANES), lambda b: (0, 0)),
            pl.BlockSpec((8, LANES), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blk * T, LANES), jnp.int32),
            jax.ShapeDtypeStruct((C8, LANES), jnp.int32),
            jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((C8, LANES), jnp.int32),
                        pltpu.VMEM((8, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="vclock_scan",
        interpret=kernel_ops._interpret(interpret),
    )(code, s0, r0)
    return s_fin[:C, :K], r_fin[:P, :K], vcs[:n, :K]

"""The cadence scheduler's merge-period loop as one Pallas TPU kernel.

``repro.core.replicated_store`` replays the sequential protocol's clocks
and pending set once per stream to find each write's apply point: per
merge period of ``s`` ops, each op takes ``max(session clock, replica
clock)`` ticked at its session, a write joins its clock into its
coordinator's and enters the pending window, and the merge walks the
window once in ``(clock sum, session)`` order.  As an XLA scan that is
dozens of small device programs per period; here a whole stream runs in
one kernel, with the clocks and the window held in vector registers:

  * session clocks ``(C8, 128)``, replica clocks ``(8, 128)`` (rows past
    the last replica hold a large value, so the row minimum is the
    replica minimum), the window's clocks, liveness, sessions and apply
    points ``(K8, 128)``, lane ``k`` a session component (lanes past
    ``C`` 0), window columns broadcast over the lanes;
  * an op reads its session's and its replica's row by a masked row
    maximum and writes them back by a masked select: no dynamic index;
  * the merge ranks the window's entries by ``(clock sum, session)``
    (dead entries last), then visits them in rank order: an entry is
    applied if it is Δ-overdue or its clock less its own tick is at or
    below the replica minimum joined with the clocks applied before it.

The window is a ring of ``A`` groups of ``s`` slots: period ``k`` fills
group ``k mod A``, and after merge ``k`` the group filled at period
``k - A + 1`` leaves it with its ops' apply points final.  Those go to
the output in op order, ``128 / s`` periods to a row of 128 lanes, so
merge ``k`` writes lanes ``(k mod 128/s)*s ..`` of row ``k // (128/s)``
and the output, read flat from ``(A - 1) * s`` on, is the apply points
of the stream (the stream is padded by ``A - 1`` periods of reads).  Ops
arrive packed in SMEM (``client | replica << 8 | write << 16``), ``T``
periods per grid step.  The jnp scan in ``replicated_store`` is the
oracle (``tests/test_cadence_schedule.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kernel_ops
from repro.kernels.ref import NEVER

Array = jax.Array

LANES = 128


def _ceil8(x: int) -> int:
    return -(-x // 8) * 8


def pack_ops(client: Array, replica: Array, write: Array) -> Array:
    """One int32 per op: ``client | replica << 8 | write << 16``."""
    return (client.astype(jnp.int32) | (replica.astype(jnp.int32) << 8)
            | (write.astype(jnp.int32) << 16))


def fits(s: int, n_clients: int, n_replicas: int, periods: int) -> bool:
    """Whether the kernel holds the period, the window, the replicas and
    the sessions."""
    return (LANES % s == 0 and periods * s <= LANES and n_replicas <= 8
            and n_clients <= LANES)


def resolve_impl(s: int, n_clients: int, n_replicas: int,
                 periods: int) -> str:
    """The kernel on a TPU where it holds the shapes, else the scan."""
    if jax.default_backend() == "tpu" and fits(s, n_clients, n_replicas,
                                               periods):
        return "pallas"
    return "scan"


def _kernel(code_ref, group_ref, pos_ref, out_ref, cnt_ref,
            s_ref, r_ref, w_ref, live_ref, cl_ref, at_ref, *,
            s, d, C, P, A, K, T, n):
    blk = pl.program_id(0)
    C8, K8 = s_ref.shape[0], w_ref.shape[0]
    per_row = LANES // s
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row_c = jax.lax.broadcasted_iota(jnp.int32, (C8, LANES), 0)
    row_p = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    row_k = jax.lax.broadcasted_iota(jnp.int32, (K8, LANES), 0)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (K8, LANES), 1)

    @pl.when(blk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        r_ref[...] = jnp.where(row_p < P, 0, NEVER)
        w_ref[...] = jnp.zeros_like(w_ref)
        live_ref[...] = jnp.zeros_like(live_ref)
        cl_ref[...] = jnp.zeros_like(cl_ref)
        at_ref[...] = jnp.full_like(at_ref, NEVER)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    group = group_ref[...]
    pos = pos_ref[...]

    def one_period(i, state):
        S, R, W, LIVE, CL, AT, cnt, row = state
        k = blk * T + i
        g = k % A
        # -- the period's ops, one by one ------------------------------
        for j in range(s):
            code = code_ref[i * s + j]
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
            slot = row_k == g * s + j
            W = jnp.where(slot, svc, W)
            LIVE = jnp.where(slot, w, LIVE)
            CL = jnp.where(slot, c, CL)
            AT = jnp.where(slot, NEVER, AT)
        # -- the merge: rank the window, then one pass in rank order -----
        age = g - group
        age = jnp.where(age < 0, age + A, age)
        overdue = age * (s + 1) + s - pos >= d
        forced = jnp.where(overdue, LIVE, 0)
        total = jnp.sum(W, axis=1, keepdims=True) * (C + 1) + CL
        key = jnp.where(LIVE > 0, total, NEVER + row_k)
        rank = jnp.zeros((K8, LANES), jnp.int32)
        for r in range(K8):
            rank = rank + jnp.where(key > key[r:r + 1, :], 1, 0)
        floor = jnp.min(R, axis=0, keepdims=True)
        learned = jnp.zeros((1, LANES), jnp.int32)
        applied = jnp.zeros((K8, LANES), jnp.int32)
        for t in range(K):
            at = rank == t
            vc = jnp.max(jnp.where(at, W, 0), axis=0, keepdims=True)
            cl = jnp.max(jnp.where(at, CL, 0), axis=0, keepdims=True)
            lv = jnp.max(jnp.where(at, LIVE, 0), axis=0, keepdims=True)
            fo = jnp.max(jnp.where(at, forced, 0), axis=0, keepdims=True)
            dep = vc - jnp.where(lane == cl, 1, 0)
            short = jnp.max(jnp.where(dep > jnp.maximum(floor, learned),
                                      1, 0), axis=1, keepdims=True)
            ok = jnp.maximum(fo, lv * (1 - short))
            applied = jnp.where(at, ok, applied)
            learned = jnp.where(ok > 0, jnp.maximum(learned, vc), learned)
        R = jnp.maximum(R, learned)
        AT = jnp.where(applied > 0, (k + 1) * s, AT)
        before = LIVE
        LIVE = jnp.where(applied > 0, 0, LIVE)
        real = jnp.where((k + 1) * s <= n, 1, 0)
        last = age == A - 1
        cnt = (
            cnt[0] + real * jnp.sum(jnp.where(overdue, 0, applied), axis=0,
                                    keepdims=True),
            cnt[1] + real * jnp.sum(jnp.where(overdue, applied, 0), axis=0,
                                    keepdims=True),
            jnp.maximum(cnt[2], real * jnp.sum(before, axis=0,
                                               keepdims=True)),
            cnt[3] + real * jnp.sum(jnp.where(last, LIVE, 0), axis=0,
                                    keepdims=True),
        )
        # The leaving group's apply points to their lanes of the row.
        lanes = (k % per_row) * s + pos
        row = row + jnp.sum(jnp.where(last & (lane_k == lanes), AT, 0),
                            axis=0, keepdims=True)
        full = i % per_row == per_row - 1

        @pl.when(full)
        def _():
            out_ref[pl.ds(i // per_row, 1), :] = row

        row = row * (1 - full.astype(jnp.int32))
        return S, R, W, LIVE, CL, AT, cnt, row

    cnt0 = cnt_ref[...]
    state = (s_ref[...], r_ref[...], w_ref[...], live_ref[...],
             cl_ref[...], at_ref[...],
             tuple(cnt0[q:q + 1, :] for q in range(4)),
             jnp.zeros((1, LANES), jnp.int32))
    S, R, W, LIVE, CL, AT, cnt, _ = jax.lax.fori_loop(0, T, one_period,
                                                      state)
    s_ref[...] = S
    r_ref[...] = R
    w_ref[...] = W
    live_ref[...] = LIVE
    cl_ref[...] = CL
    at_ref[...] = AT
    row_8 = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    tile = jnp.zeros((8, LANES), jnp.int32)
    for q in range(4):
        tile = jnp.where(row_8 == q, cnt[q], tile)
    cnt_ref[...] = tile


@functools.partial(jax.jit, static_argnames=(
    "s", "d", "n_clients", "n_replicas", "periods", "interpret"))
def schedule_pallas(code: Array, *, s: int, d: int, n_clients: int,
                    n_replicas: int, periods: int,
                    interpret: bool | None = None) -> tuple[Array, Array]:
    """``(apply points, counts)`` of a packed stream.

    The apply point of each op, ``NEVER`` for a read and for a write no
    merge inside the stream applies; ``counts``: writes applied by the
    dependency test and as Δ-overdue, the window's peak and overflow,
    over the merges inside the stream."""
    C, P, A = n_clients, n_replicas, periods
    K = A * s
    if not fits(s, C, P, A):
        raise ValueError(f"period {s}, window {K}, {P} replicas, {C} "
                         f"sessions: the kernel holds a period dividing "
                         f"{LANES} and at most {LANES}, 8, {LANES}")
    n = code.shape[0]
    if n * (C + 1) >= NEVER:
        raise ValueError(f"{n} ops: a clock sum times {C + 1} must stay "
                         f"below {NEVER}")
    per_row = LANES // s
    # T periods a grid step: whole output tiles of 8 rows, and an SMEM
    # block of a multiple of 1024 ops.
    T = max(8 * per_row, 1024 // s)
    n_per = -(-n // s) + A - 1
    n_blk = -(-n_per // T)
    code = jnp.pad(code, (0, n_blk * T * s - n))
    C8, K8 = _ceil8(C), _ceil8(K)
    rows = np.arange(K8)[:, None] * np.ones((1, LANES), np.int64)
    group = jnp.asarray(np.minimum(rows // s, A - 1), jnp.int32)
    pos = jnp.asarray(rows % s, jnp.int32)
    kernel = functools.partial(_kernel, s=s, d=d, C=C, P=P, A=A, K=K, T=T,
                               n=n)
    out, counts = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((T * s,), lambda b: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((K8, LANES), lambda b: (0, 0)),
            pl.BlockSpec((K8, LANES), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T // per_row, LANES), lambda b: (b, 0)),
            pl.BlockSpec((8, LANES), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blk * T // per_row, LANES), jnp.int32),
            jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((C8, LANES), jnp.int32),
            pltpu.VMEM((8, LANES), jnp.int32),
            *(pltpu.VMEM((K8, LANES), jnp.int32) for _ in range(4)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="cadence_schedule",
        interpret=kernel_ops._interpret(interpret),
    )(code, group, pos)
    point = out.reshape(-1)[(A - 1) * s:(A - 1) * s + n]
    # A partial last period has no merge.
    return jnp.where(point > n, NEVER, point), counts[:4, 0]

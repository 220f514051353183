"""Pallas TPU kernel for the DUOT causality audit (paper §3.3-3.4).

The audit is O(M^2 * N) vector-clock comparisons over an M-entry op log
with N clients — the server-side hot-spot of X-STCC (every merge audits
the log; Cassandra-scale logs run to millions of ops).  The kernel tiles
the (M x M) pair space into (block x block) VMEM tiles; the N clock
components are reduced with an unrolled 2-D loop (max/min of component
differences), keeping every intermediate a (block x block) tile — TPU
vector-unit friendly, no 3-D temporaries.

happens-before(a, b)  <=>  max_n(a_n - b_n) <= 0  and  min_n(a_n - b_n) < 0

Output codes match ``repro.kernels.ref.vclock_audit_ref``:
``phase | violation << 8 | timed << 9``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# meta columns
CLIENT, KIND, RESOURCE, VERSION, SEQ, VALID = 0, 1, 2, 3, 4, 5
META_COLS = 8


def _audit_kernel(vci_ref, vcj_ref, mi_ref, mj_ref, out_ref,
                  *, n_clients: int, delta: int):
    # The i side is one op per sublane, the j side one op per lane, so
    # ``i(c)`` is a (bm, 1) column, ``j(c)`` a (1, bm) row, and every
    # pair relation a (bm, bm) broadcast — no vector transposes, and
    # only a few (bm, bm) tiles live at once.
    vci = vci_ref[...]          # (bm, N)
    vcj = vcj_ref[...]          # (N, bm)
    mi = mi_ref[...]            # (bm, META_COLS)
    mj = mj_ref[...]            # (META_COLS, bm)
    bm = vci.shape[0]

    def i(c):
        return mi[:, c:c + 1]

    def j(c):
        return mj[c:c + 1, :]

    big = jnp.int32(-(2 ** 30))
    maxd = jnp.full((bm, bm), big, jnp.int32)
    mind = jnp.full((bm, bm), -big, jnp.int32)
    for n in range(n_clients):
        diff = vci[:, n:n + 1] - vcj[n:n + 1, :]
        maxd = jnp.maximum(maxd, diff)
        mind = jnp.minimum(mind, diff)
    hb = jnp.logical_and(maxd <= 0, mind < 0)

    valid = jnp.logical_and(i(VALID) > 0, j(VALID) > 0)
    same_res = i(RESOURCE) == j(RESOURCE)
    ordered = i(SEQ) < j(SEQ)
    same_client = i(CLIENT) == j(CLIENT)
    ki = i(KIND)
    kj = j(KIND)
    vi = i(VERSION)
    vj = j(VERSION)

    base = valid & same_res & ordered
    sc = base & same_client & hb

    phase = jnp.zeros((bm, bm), jnp.int32)
    phase = jnp.where(sc & (ki == 0) & (kj == 0), 1, phase)
    phase = jnp.where(sc & (ki == 1) & (kj == 1), 2, phase)
    phase = jnp.where(sc & (ki == 1) & (kj == 0), 3, phase)
    phase = jnp.where(sc & (ki == 0) & (kj == 1), 4, phase)
    phase = jnp.where(base & ~same_client & hb, 5, phase)
    phase = jnp.where(base & ~hb, 6, phase)

    viol = (
        ((phase == 1) & (vj < vi))
        | ((phase == 2) & (vj <= vi))
        | ((phase == 3) & (vj < vi))
        | ((phase == 4) & (vj <= vi))
        | ((phase == 5) & (ki == 1) & (kj == 0) & (vj < vi))
    )
    codes = phase | jnp.where(viol, 1 << 8, 0)
    if delta > 0:
        gap = j(SEQ) - i(SEQ)
        timed = base & (ki == 1) & (kj == 0) & (vj < vi) & (gap > delta)
        codes = codes | jnp.where(timed, 1 << 9, 0)
    out_ref[...] = codes


def vclock_audit(
    vc: jax.Array,       # (M, N) int32
    client: jax.Array,   # (M,) int32
    kind: jax.Array,
    resource: jax.Array,
    version: jax.Array,
    seq: jax.Array,
    valid: jax.Array,    # (M,) bool
    *,
    delta: int = 0,
    block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Tiled pairwise audit.  Returns (M, M) int32 codes."""
    m, n = vc.shape
    block = min(block, m)
    assert m % block == 0, f"M={m} must divide block={block}"
    meta = jnp.stack(
        [
            client.astype(jnp.int32),
            kind.astype(jnp.int32),
            resource.astype(jnp.int32),
            version.astype(jnp.int32),
            seq.astype(jnp.int32),
            valid.astype(jnp.int32),
            jnp.zeros((m,), jnp.int32),
            jnp.zeros((m,), jnp.int32),
        ],
        axis=1,
    )  # (M, META_COLS)

    kernel = functools.partial(_audit_kernel, n_clients=n, delta=delta)
    nb = m // block
    return pl.pallas_call(
        kernel,
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, n), lambda i, j: (i, 0)),
            pl.BlockSpec((n, block), lambda i, j: (0, j)),
            pl.BlockSpec((block, META_COLS), lambda i, j: (i, 0)),
            pl.BlockSpec((META_COLS, block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="vclock_audit",
        interpret=interpret,
    )(vc, vc.T, meta, meta.T)

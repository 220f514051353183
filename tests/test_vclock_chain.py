"""The vector-clock chain kernel equals the scan it replaces.

``repro.kernels.vclock_chain`` (run interpreted here) must give the
final session clocks, the final replica clocks and every op's clock
that ``xstcc.clock_chain`` (the ``lax.scan``, the CPU path and the
oracle) gives, on ragged batches, one-sided mixes, odd session and
replica counts and clocks carried over from earlier rounds; and
``apply_op_batch`` must return the same ``BatchResult`` and state with
the kernel as with the scan, round after round and under ``vmap``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import xstcc
from repro.kernels import vclock_chain


def _ops(rng, b, c, p, write_share):
    return (jnp.asarray(rng.integers(0, c, b), jnp.int32),
            jnp.asarray(rng.integers(0, p, b), jnp.int32),
            jnp.asarray(rng.random(b) < write_share))


def _assert_same(got, want):
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("b,c,p,write_share,warm", [
    (2500, 16, 3, 0.5, False),   # B not a multiple of the 1024-op step
    (300, 16, 3, 0.0, False),    # all reads
    (300, 16, 3, 1.0, False),    # all writes
    (200, 1, 3, 0.5, False),     # one session
    (4096, 16, 3, 0.5, False),   # the cells' shape
    (777, 5, 1, 0.5, False),     # sessions not a multiple of 8, one replica
    (1500, 16, 3, 0.5, True),    # clocks carried over from earlier rounds
    (8, 16, 3, 0.5, True),       # a causal level's 8-op merge period
], ids=["ragged", "reads", "writes", "one-session", "cells", "odd-shape",
        "warm", "period"])
def test_kernel_equals_the_scan(b, c, p, write_share, warm):
    rng = np.random.default_rng(b + c + p)
    s0 = jnp.zeros((c, c), jnp.int32)
    r0 = jnp.zeros((p, c), jnp.int32)
    if warm:
        # A round before this one leaves non-zero, unequal clocks.
        s0, r0, _ = xstcc.clock_chain(s0, r0, *_ops(rng, 3000, c, p, 0.5))
        assert int(s0.min()) > 0 and int(r0.max()) > 0
    ops = _ops(rng, b, c, p, write_share)
    want = xstcc.clock_chain(s0, r0, *ops)
    got = vclock_chain.vclock_chain(s0, r0, *ops)
    _assert_same(got, want)
    assert got[2].shape == (b, c)


def test_oversize_shapes_take_the_scan(monkeypatch):
    assert vclock_chain.resolve_impl(16, 3) == "scan"      # CPU here
    monkeypatch.setattr(vclock_chain.jax, "default_backend", lambda: "tpu")
    assert vclock_chain.resolve_impl(16, 3) == "pallas"
    assert vclock_chain.resolve_impl(129, 3) == "scan"
    assert vclock_chain.resolve_impl(16, 9) == "scan"
    with pytest.raises(ValueError, match="holds at most"):
        vclock_chain.vclock_chain(jnp.zeros((16, 16), jnp.int32),
                                  jnp.zeros((9, 16), jnp.int32),
                                  *_ops(np.random.default_rng(0), 8, 16, 9,
                                        0.5), interpret=False)


def _rounds(impl, monkeypatch, n_rounds=3, b=512, c=16, p=3, r=64):
    """``n_rounds`` chained ``apply_op_batch`` rounds with the clock
    chain resolved to ``impl``."""
    monkeypatch.setattr(vclock_chain, "resolve_impl", lambda *_: impl)
    rng = np.random.default_rng(7)
    state = xstcc.make_cluster(p, c, r, 2 * b)
    out = []
    for k in range(n_rounds):
        res = xstcc.apply_op_batch(
            state,
            client=jnp.asarray(rng.integers(0, c, b), jnp.int32),
            replica=jnp.asarray(rng.integers(0, p, b), jnp.int32),
            resource=jnp.asarray(rng.integers(0, r, b), jnp.int32),
            kind=jnp.asarray(rng.random(b) < 0.5, jnp.int32),
            op_index=jnp.arange(b, dtype=jnp.int32) + k * b,
        )
        out.append(res)
        state, _ = xstcc.server_merge(res.state, delta=8)
    return out


def test_apply_op_batch_with_the_kernel_equals_the_scan(monkeypatch):
    scan = _rounds("scan", monkeypatch)
    kernel = _rounds("pallas", monkeypatch)
    for got, want in zip(kernel, scan, strict=True):
        assert isinstance(got, xstcc.BatchResult)
        jax.tree.map(
            lambda g, w: np.testing.assert_array_equal(np.asarray(g),
                                                       np.asarray(w)),
            got, want,
        )
    assert int(kernel[-1].state.session_vc.sum()) > 0


def test_the_vmapped_batch_uses_the_kernel_alike(monkeypatch):
    """The one-device layout ``vmap`` s the round over tenants: the
    kernel's batching rule must keep each tenant's chain its own."""
    n_t, b, c, p = 3, 300, 16, 3
    rng = np.random.default_rng(11)
    s0 = jnp.asarray(rng.integers(0, 50, (n_t, c, c)), jnp.int32)
    r0 = jnp.asarray(rng.integers(0, 50, (n_t, p, c)), jnp.int32)
    ops = (jnp.asarray(rng.integers(0, c, (n_t, b)), jnp.int32),
           jnp.asarray(rng.integers(0, p, (n_t, b)), jnp.int32),
           jnp.asarray(rng.random((n_t, b)) < 0.5))
    want = jax.vmap(xstcc.clock_chain)(s0, r0, *ops)
    got = jax.vmap(vclock_chain.vclock_chain)(s0, r0, *ops)
    _assert_same(got, want)

    monkeypatch.setattr(vclock_chain, "resolve_impl", lambda *_: "pallas")
    state = xstcc.make_cluster(p, c, 32, 2 * b)
    state = jax.tree.map(lambda x: jnp.stack([x] * n_t), state)
    res = jax.vmap(lambda st, cl, rp, w: xstcc.apply_op_batch(
        st, client=cl, replica=rp, resource=cl * 2, kind=w.astype(jnp.int32)
    ))(state, *ops)
    for t in range(n_t):
        s, r, v = xstcc.clock_chain(jnp.zeros((c, c), jnp.int32),
                                    jnp.zeros((p, c), jnp.int32),
                                    ops[0][t], ops[1][t], ops[2][t])
        _assert_same((res.state.session_vc[t], res.state.replica_vc[t],
                      res.vc[t]), (s, r, v))

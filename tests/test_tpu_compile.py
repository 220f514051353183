"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: a column scatter in a kernel body, more scoped VMEM
than a step may use, a vector cast Mosaic cannot lower.  These tests
compile each kernel ahead of time for one chip of a described (not
attached) v5e host, at the engine's real shapes, and check that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture, never at import, so that
only the worker given this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import vclock_audit as va

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


I32, F32, BOOL = jnp.int32, jnp.float32, jnp.bool_


@pytest.mark.parametrize("b,q", [(4096, 8192), (128, 256), (8, 128)])
def test_op_ingest_compiles(one_chip, b, q):
    """The engine's batch (B=4096, ring Q=2B) with cadence inputs, the
    default 128-op batch, and a causal level's 8-op merge period."""
    def ingest(c, p, r, w, g0, raw0, f0, opi, api, pv, pr, plive, pa):
        return ops.op_ingest(
            c, p, r, w, g0, raw0, f0, op_index=opi, apply_index=api,
            pend_version=pv, pend_resource=pr, pend_live=plive,
            pend_apply=pa, impl="pallas", interpret=False,
        )

    shapes = ([((b,), I32)] * 3 + [((b,), BOOL)] + [((b,), I32)] * 5
              + [((q,), I32)] * 2 + [((q,), BOOL), ((q,), I32)])
    assert "tpu_custom_call" in _hlo(ingest, one_chip, *shapes)


def test_vclock_audit_compiles(one_chip):
    """The DUOT audit at the engine's default log (M=2048, 16 clients)."""
    m, c = 2048, 16

    def audit(vc, client, kind, resource, version, seq, valid):
        return va.vclock_audit(
            vc, client, kind, resource, version, seq, valid, delta=8,
            interpret=False,
        )

    shapes = [((m, c), I32)] + [((m,), I32)] * 5 + [((m,), BOOL)]
    assert "tpu_custom_call" in _hlo(audit, one_chip, *shapes)


@pytest.mark.parametrize("rows,width", [(3, 4096), (1, 3)])
def test_histogram_compiles(one_chip, rows, width):
    """Obs rows of a 4096-op epoch (age, age, latency), and the hint
    depth of 3 replicas."""
    def hist(v, m):
        return ops.histogram(
            v, lo=jnp.zeros((rows,)), hi=jnp.full((rows,), 64.0),
            n_bins=64, mask=m, impl="pallas", interpret=False,
        )

    assert "tpu_custom_call" in _hlo(
        hist, one_chip, ((rows, width), F32), ((rows, width), I32)
    )


@pytest.mark.parametrize("pairs,ranges", [(1, 8), (3, 8), (12, 64)])
def test_digest_compare_compiles(one_chip, pairs, ranges):
    """Gossip digests: (pairs, ranges, 4) per exchange."""
    def diff(a, b):
        return ops.digest_compare(a, b, impl="pallas", interpret=False)

    shape = ((pairs, ranges, 4), I32)
    assert "tpu_custom_call" in _hlo(diff, one_chip, shape, shape)


# -- what runs on a TPU, decided without one ---------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the kernel wrappers see a TPU backend."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")


def test_floor_scatters_need_no_relayout(one_chip, on_tpu):
    """Two rounds of ``apply_op_batch`` at the engine's shapes (16
    sessions, 5M rows, B=4096, Pallas ingest) as a ``lax.scan``: the
    flat floors are scattered in place, so no loop or slice update of
    the compiled program moves a (16, 5M) floor between layouts."""
    from repro.core import xstcc

    c, r, b = 16, 5_000_000, 4096
    state = jax.eval_shape(lambda: xstcc.make_cluster(3, c, r, 2 * b))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        state,
    )

    def rounds(state, ops_):
        def step(st, op):
            res = xstcc.apply_op_batch(
                st, client=op[0], replica=op[1], resource=op[2],
                kind=op[3], ingest="pallas",
            )
            return res.state, res.version
        return jax.lax.scan(step, state, ops_)

    ops_ = jax.ShapeDtypeStruct((2, 4, b), I32, sharding=one_chip)
    text = jax.jit(rounds).lower(state, ops_).compile().as_text()
    assert "tpu_custom_call" in text
    relayouts = [
        line for line in text.splitlines()
        if re.search(r"\b(while|dynamic-update-slice)\(", line)
        and re.search(rf"s32\[(1,)?{c},{r}\]", line)
    ]
    assert relayouts == []


def test_cadence_scheduler_is_one_kernel(one_chip, on_tpu):
    """The X-STCC cadence (merge every 8, Δ 8) over a 1,048,576-op
    stream: the whole schedule is the Pallas kernel, with no XLA loop
    around it, and the store picks it on a TPU."""
    from repro.core import replicated_store as rs
    from repro.kernels import cadence_schedule as cs

    assert cs.resolve_impl(8, 16, 3, rs.pending_periods(8, 8)) == "pallas"
    assert cs.resolve_impl(8, 16, 3, 17) == "scan"
    n = 1 << 20
    sched = rs._stream_scheduler(8, 8, 16, 3, impl="pallas")
    text = sched.lower(*[
        jax.ShapeDtypeStruct((n,), I32, sharding=one_chip)] * 3
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\bwhile\(", text)


def test_clock_chain_is_one_kernel(one_chip, on_tpu):
    """Two rounds of ``apply_op_batch`` at the engine's shapes (16
    sessions, 3 replicas, 5M rows, B=4096): the clock chain is the
    ``vclock_scan`` kernel, and no loop over the (16, 16) session or
    (3, 16) replica clock rows is left in the round body; an oversize
    shape keeps the scan."""
    from repro.core import xstcc
    from repro.kernels import vclock_chain

    assert vclock_chain.resolve_impl(16, 3) == "pallas"
    assert vclock_chain.resolve_impl(256, 3) == "scan"
    assert vclock_chain.resolve_impl(16, 24) == "scan"
    c, r, b = 16, 5_000_000, 4096
    state = jax.eval_shape(lambda: xstcc.make_cluster(3, c, r, 2 * b))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        state,
    )

    def rounds(state, ops_):
        def step(st, op):
            res = xstcc.apply_op_batch(
                st, client=op[0], replica=op[1], resource=op[2],
                kind=op[3], ingest="pallas",
            )
            return res.state, res.vc
        return jax.lax.scan(step, state, ops_)

    ops_ = jax.ShapeDtypeStruct((2, 4, b), I32, sharding=one_chip)
    text = jax.jit(rounds).lower(state, ops_).compile().as_text()
    assert any(line.lstrip().startswith("%vclock_scan")
               and "tpu_custom_call" in line for line in text.splitlines())
    # The round loop carries the flat floors; any other loop carrying a
    # clock row is a clock chain left in XLA.
    clock_loops = [
        line for line in text.splitlines()
        if re.search(r"\bwhile\(", line)
        and re.search(r"s32\[(16|3),16\]", line)
        and f"s32[{c * r}]" not in line
    ]
    assert clock_loops == []


def test_kernels_never_interpreted_on_tpu(on_tpu):
    assert ops._interpret(None) is False
    assert ops._interpret(False) is False
    with pytest.raises(ValueError, match="not interpreted"):
        ops._interpret(True)
    assert ops.resolve_op_ingest_impl(
        "auto", batch=4096, n_clients=16, n_replicas=3,
        n_resources=24, affine_op_index=True,
    ) == "pallas"


def test_session_floor_raises_on_tpu(on_tpu):
    """The one kernel with no TPU lowering refuses to run there rather
    than fall back to interpret mode."""
    z = jnp.zeros((3, 8), jnp.int32)
    f = jnp.zeros((4, 8), jnp.int32)
    op = jnp.zeros((8,), jnp.int32)
    with pytest.raises(NotImplementedError, match="session_floor"):
        ops.session_admit(z, f, f, op, op, op)


@pytest.mark.parametrize("env", ["/elsewhere/cache", None])
def test_compile_cache_dir(monkeypatch, env):
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the fixed
    ``<repo>/.jax_cache``, never a temp, pid or time-derived path."""
    from repro.launch import compile_cache

    set_dirs = []
    monkeypatch.setattr(
        compile_cache.jax.config, "update",
        lambda name, value: set_dirs.append((name, value)),
    )
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.REPO_CACHE)
        assert compile_cache.enable_compile_cache() == want
        assert set_dirs == [("jax_compilation_cache_dir", want)]
        assert compile_cache.REPO_CACHE.parent == (
            compile_cache.pathlib.Path(__file__).resolve().parents[1]
        )
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert compile_cache.enable_compile_cache() == env
        assert set_dirs == []

"""The pool seam is drawn once per tenant per replay, on one device and
on four virtual CPU devices."""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

SCRIPT = textwrap.dedent("""
    import sys
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import cell as cell_lib
    from bench.run import replay_once
    from bench.seam import StreamPool
    tenants = {tenants}
    cell = cell_lib.load("quorum-5m.ycsb-a", overrides=dict(
        tenants=tenants, rows_per_tenant=2000, ops_per_tenant=2048,
        sessions_per_tenant=4, batch=512))
    pool = StreamPool(cell_lib.pool_streams(cell, 2 ** 31 + 3))
    for j in (0, 1, 0):
        prep, result, _ = replay_once(cell, pool, j)
    print("CALLS", pool.calls)
    print("LAYOUT", prep["layout"]["mode"], prep["layout"]["devices"])
""")


@pytest.mark.parametrize("tenants", [1, 4])
def test_seam_called_once_per_shard_per_replay(tenants):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR="")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                         tenants=tenants)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = dict(line.split(" ", 1) for line in proc.stdout.splitlines()
                 if line.startswith(("CALLS", "LAYOUT")))
    want = [j * tenants + s for j in (0, 1, 0) for s in range(tenants)]
    assert lines["CALLS"] == str(want)
    if tenants == 4:
        assert lines["LAYOUT"] == "shard_map 4"
    else:
        assert lines["LAYOUT"] == "single 1"


def test_seam_restores_and_rejects_wrong_size():
    import numpy as np

    from bench.seam import StreamPool
    from repro.engine import stream as stream_lib

    original = stream_lib.op_stream
    s = {k: np.zeros(8, np.int32)
         for k in ("client", "kind", "resource", "home")}
    pool = StreamPool({3: s})
    with pool.installed():
        assert stream_lib.op_stream is not original
        assert stream_lib.op_stream(None, 8, 1, 1, 3) is s
        with pytest.raises(ValueError):
            stream_lib.op_stream(None, 16, 1, 1, 3)
    assert stream_lib.op_stream is original
    assert pool.calls == [3]

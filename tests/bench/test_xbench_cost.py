"""Bytes the op-ingest kernel must move, and the table of peaks."""

import dataclasses

import pytest

from bench import cell as cell_lib
from bench import cost


def _ingest_geometry(cfg):
    from repro.engine import EpochEngine

    store, _ = EpochEngine(cfg).runner(cell_lib.workload(CELL))
    sub = EpochEngine(cfg).plan()[0]
    return sub, store.pending_cap


CELL = cell_lib.load("quorum-5m.ycsb-a", overrides=dict(
    rows_per_tenant=4096, ops_per_tenant=8192, batch=1024))


@pytest.mark.parametrize("level", ["QUORUM", "X_STCC"])
def test_ingest_bytes_do_not_depend_on_the_implementation(level):
    cell = dataclasses.replace(CELL, config=dict(CELL.config, level=level))
    base = cell_lib.engine_config(cell, 0)
    counts = set()
    for impl in ("pallas", "tiled"):
        b, q = _ingest_geometry(dataclasses.replace(base, ingest=impl))
        counts.add(cost.op_ingest_bytes(b, q))
    assert len(counts) == 1
    assert counts.pop() == 4 * (12 * 1024 + 4 * 2048)


def test_ingest_bytes_formula():
    assert cost.op_ingest_bytes(4096, 8192) == 4 * (12 * 4096 + 4 * 8192)


def test_peaks_known_and_unknown():
    p = cost.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")

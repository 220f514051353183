"""The YCSB generator against YCSB's own constants."""

import numpy as np
import pytest

from bench.traffic import ycsb


def _zeta_euler_maclaurin(n: int, s: float, m: int = 1_000_000) -> float:
    """sum_{i<=n} i^-s: exact to m, then the Euler-Maclaurin tail."""
    head = ycsb.zeta(m, s)
    tail = ((n ** (1 - s) - m ** (1 - s)) / (1 - s)
            + (n ** -s - m ** -s) / 2
            + s * (m ** (-s - 1) - n ** (-s - 1)) / 12)
    return head + tail


def test_zetan_is_zeta_of_ycsb_item_space():
    approx = _zeta_euler_maclaurin(ycsb.ITEM_COUNT, ycsb.USED_ZIPFIAN_CONSTANT)
    assert ycsb.ZETAN == 26.46902820178302
    assert abs(approx - ycsb.ZETAN) < 1e-6


def test_zeta_small_exact():
    assert ycsb.zeta(2, 0.99) == pytest.approx(1 + 0.5 ** 0.99, rel=1e-15)


def _fnv1a64_loop(v: int) -> int:
    """Java's Utils.fnvhash64, one octet at a time on 64-bit ints."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    signed = h - (1 << 64) if h >= 1 << 63 else h
    return abs(signed)


def test_fnvhash64_matches_octet_loop():
    vals = [0, 1, 2, 255, 256, 123456789, 2 ** 33 + 7, 10 ** 10, 2 ** 62]
    got = ycsb.fnvhash64(np.asarray(vals, np.int64))
    assert got.tolist() == [_fnv1a64_loop(v) for v in vals]
    assert (got >= 0).all()


def test_fnv_constants():
    assert ycsb.FNV_OFFSET_BASIS_64 == 0xCBF29CE484222325
    assert ycsb.FNV_PRIME_64 == 1099511628211


@pytest.mark.parametrize("mix_name", ["ycsb-a", "paper-b"])
def test_stream_shape_range_and_read_share(mix_name):
    mix = ycsb.load_mix(mix_name)
    n, rows = 200_000, 5_000_000
    s = ycsb.stream(mix, n_ops=n, n_sessions=16, n_rows=rows, n_replicas=3,
                    seed=(2 ** 31 + 11, 0, 0))
    assert set(s) == set(ycsb.COLUMNS)
    assert all(v.dtype == np.int32 and v.shape == (n,) for v in s.values())
    assert 0 <= s["resource"].min() and s["resource"].max() < rows
    assert 0 <= s["client"].min() and s["client"].max() < 16
    assert 0 <= s["home"].min() and s["home"].max() < 3
    p = mix["read_proportion"]
    sd = np.sqrt(p * (1 - p) / n)
    assert abs(np.mean(s["kind"] == ycsb.READ) - p) < 5 * sd
    # Mobility: 30% of ops leave the session's home DC.
    moved = np.mean(s["home"] != s["client"] % 3)
    assert abs(moved - mix["mobility_share"]) < 0.01


def test_zipfian_head_and_scramble():
    rng = np.random.default_rng(5)
    n = 400_000
    ranks = ycsb.zipfian(rng, n, 0.99)
    # P(item 0) = 1 / zeta(n, 0.99) in YCSB's zipfian.
    assert abs(np.mean(ranks == 0) - 1 / ycsb.ZETAN) < 0.002
    keys = np.fmod(ycsb.fnvhash64(ranks), 5_000_000)
    _, counts = np.unique(keys, return_counts=True)
    assert abs(counts.max() / n - 1 / ycsb.ZETAN) < 0.003


def test_same_seed_same_stream_other_seed_other_stream():
    mix = ycsb.load_mix("ycsb-a")
    kw = dict(n_ops=1000, n_sessions=16, n_rows=1000, n_replicas=3)
    a = ycsb.stream(mix, seed=(7, 0, 0), **kw)
    b = ycsb.stream(mix, seed=(7, 0, 0), **kw)
    c = ycsb.stream(mix, seed=(7, 1, 0), **kw)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["resource"], c["resource"])

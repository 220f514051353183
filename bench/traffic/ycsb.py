"""YCSB core-workload op streams, vectorised in numpy.

Keys follow YCSB's ``ScrambledZipfianGenerator``: a zipfian draw over
YCSB's fixed 10^10-item space with the precomputed zeta for constant
0.99, scrambled by the 64-bit FNV-1a hash and taken modulo the record
count.  Operation kinds are drawn by proportion (read, update), sessions
uniformly, and each op's replica by the engine's client-mobility model
(paper Fig. 2): the session's home DC, or for 30% of ops one of the next
two DCs.

A mix is a JSON file beside this module (``<traffic>.json``); nothing
here knows a mix by name.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

# YCSB ScrambledZipfianGenerator constants.
ITEM_COUNT = 10_000_000_000
USED_ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302          # zeta(ITEM_COUNT, 0.99), YCSB's own
# YCSB Utils.fnvhash64 (FNV-1a, 64 bit).
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

COLUMNS = ("client", "kind", "resource", "home")
READ, UPDATE = 0, 1

_HERE = pathlib.Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    """The parameters of traffic mix ``name`` (``traffic/<name>.json``)."""
    path = _HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    total = mix["read_proportion"] + mix["update_proportion"]
    if not np.isclose(total, 1.0):
        raise ValueError(f"mix {name!r}: proportions sum to {total}")
    if mix["request_distribution"] != "zipfian":
        raise ValueError(f"mix {name!r}: only the zipfian request "
                         "distribution is generated")
    return mix


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i^-theta, exact for small n (YCSB's ``zetastatic``)."""
    i = np.arange(1, n + 1, dtype=np.float64)
    return float(np.sum(i ** -theta))


def zipfian(rng: np.random.Generator, n: int, theta: float) -> np.ndarray:
    """``n`` draws of YCSB's ``ZipfianGenerator(0, ITEM_COUNT, theta,
    ZETAN)`` (items = ITEM_COUNT + 1, as YCSB constructs it)."""
    if theta != USED_ZIPFIAN_CONSTANT:
        raise ValueError("YCSB precomputes zeta for constant 0.99 only")
    items = ITEM_COUNT + 1
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / ZETAN)
    u = rng.random(n)
    uz = u * ZETAN
    tail = np.floor(items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` over int64 values: FNV-1a over the 8
    low-to-high octets, then Java's ``Math.abs`` of the signed result."""
    v = np.asarray(val, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        v >>= np.uint64(8)
        h *= prime                          # wraps mod 2^64, as in Java
    s = h.view(np.int64)
    return np.where(s < 0, -s, s)           # Long.MIN_VALUE stays negative


def scrambled_zipfian(
    rng: np.random.Generator, n: int, record_count: int, theta: float,
) -> np.ndarray:
    """``ScrambledZipfianGenerator(0, record_count - 1)`` draws."""
    return np.fmod(fnvhash64(zipfian(rng, n, theta)), record_count)


def stream(
    mix: dict, *, n_ops: int, n_sessions: int, n_rows: int, n_replicas: int,
    seed: tuple[int, ...],
) -> dict[str, np.ndarray]:
    """One op stream: int32 ``client``, ``kind`` (0 read, 1 update),
    ``resource`` in ``[0, n_rows)`` and ``home`` replica per op."""
    rng = np.random.default_rng(np.random.SeedSequence(list(seed)))
    kind = (rng.random(n_ops) >= mix["read_proportion"]).astype(np.int32)
    resource = scrambled_zipfian(
        rng, n_ops, n_rows, mix["zipfian_constant"]).astype(np.int32)
    # The engine's client-mobility model (engine/stream.py attach_clients).
    client = rng.integers(0, n_sessions, n_ops).astype(np.int32)
    move = rng.random(n_ops) < mix["mobility_share"]
    offset = rng.integers(1, mix["mobility_hops"] + 1, n_ops)
    home = ((client % n_replicas + np.where(move, offset, 0))
            % n_replicas).astype(np.int32)
    return {"client": client, "kind": kind, "resource": resource,
            "home": home}

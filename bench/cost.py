"""Work a kernel must do, reckoned from shapes, and the chip's peaks.

These functions take shapes, never an implementation: the op-ingest
kernel (``pallas``) and its jnp twin (``tiled``) are charged the same
bytes for the same round.
"""

from __future__ import annotations

import json
import pathlib

INT32 = 4
# Per-op columns the ingest reads: client, replica, resource, is_write,
# the gathered global version, replica version and session floor, and
# the op's index and emulated apply index.
INGEST_OP_INPUTS = 9
# Pending-ring columns it reads: version, row, live flag, apply index.
INGEST_RING_INPUTS = 4
# Per-op outputs it writes: the occurrence count, the raw version and
# the floor.
INGEST_OP_OUTPUTS = 3

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def op_ingest_bytes(batch: int, ring: int) -> int:
    """HBM bytes one op-ingest call must move: every input column read
    once, every output written once, each element an int32."""
    return INT32 * (
        (INGEST_OP_INPUTS + INGEST_OP_OUTPUTS) * batch
        + INGEST_RING_INPUTS * ring
    )


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]

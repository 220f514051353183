"""The one place the engine takes its traffic, served from a pool.

The engine offers no entry that takes an op stream: ``EpochEngine``
draws its stream through ``repro.engine.stream.op_stream(w, n_ops,
n_clients, n_resources, seed, n_replicas)``.  For the length of a run
that function is replaced by a lookup of ``seed`` in a pool of streams
the benchmark generated, and every call is counted, so a replay that
did not draw each of its shards' streams from the pool is caught.
"""

from __future__ import annotations

import contextlib

import numpy as np


class StreamPool:
    """Op streams keyed by the engine seed that asks for them."""

    def __init__(self, streams: dict[int, dict[str, np.ndarray]]):
        self.streams = streams
        self.calls: list[int] = []

    def op_stream(self, w, n_ops, n_clients, n_resources, seed,
                  n_replicas=3):
        s = self.streams[int(seed)]
        if len(s["client"]) != n_ops:
            raise ValueError(f"pool stream {seed} holds {len(s['client'])} "
                             f"ops, the engine asked for {n_ops}")
        if int(s["resource"].max()) >= n_resources:
            raise ValueError(f"pool stream {seed} addresses a row beyond "
                             f"{n_resources}")
        self.calls.append(int(seed))
        return s

    @contextlib.contextmanager
    def installed(self):
        """Serve ``repro.engine.stream.op_stream`` from the pool."""
        from repro.engine import stream as stream_lib

        original = stream_lib.op_stream
        stream_lib.op_stream = self.op_stream
        try:
            yield self
        finally:
            stream_lib.op_stream = original

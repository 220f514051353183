"""One reader per per-layer metric, ``<metric name>.py``, each with a
``read(ctx)`` that returns the number or ``None`` when the trace holds
nothing to read."""

"""Observability plane: device-resident metrics, traces, and reports.

  * :mod:`repro.obs.metrics` — the typed metric registry whose
    histogram/counter state lives in the unified engine's scan carry;
  * :mod:`repro.obs.trace`   — the engine's host spans on the
    profiler's clock (profiler TraceMe events, and a Chrome trace-event
    export from a recording ``Tracer``);
  * :mod:`repro.obs.report`  — per-run report rendering and the
    ``python -m repro.obs.report`` CLI.

Only the registry is imported eagerly: ``engine.config`` needs
:class:`ObsConfig` before the engine (which ``trace``/``report`` build
on) exists.
"""

from repro.obs.metrics import (  # noqa: F401
    COUNTERS,
    PERCENTILES,
    HostHistogram,
    MetricSpec,
    ObsConfig,
    build_metrics,
    host_percentile,
    summarize,
)

"""paddle_tpu.monitor — unified metrics + host-span tracing.

The observability layer the Fluid reference spreads over RecordEvent,
the CUPTI DeviceTracer, ``tools/timeline.py`` and ad-hoc VLOGs, rebuilt
TPU-native in three pieces:

* :mod:`~paddle_tpu.monitor.metrics` — process-global registry of
  counters / gauges / fixed-bucket histograms. ``PADDLE_TPU_METRICS=0``
  disables it (hot paths then pay a single branch). The Executor, readers
  and optimizer are pre-instrumented; ``monitor.snapshot()`` returns
  everything as a dict, ``monitor.to_text()`` as a table.
* :mod:`~paddle_tpu.monitor.tracer` — nested host wall-clock spans with
  Chrome-trace/Perfetto export. ``PADDLE_TPU_TRACE_FILE=/tmp/t.json``
  records for the whole process and writes the trace at exit; every
  ``span`` (and ``profiler.record_event``) is also a
  ``jax.profiler.TraceAnnotation``, so a device trace shows the same names.
* :mod:`~paddle_tpu.monitor.step_logger` — ``StepLogger``, the periodic
  throughput/step-time/loss line emitter used by ``bench.py`` and
  ``train/``; its ``summary()`` is the ``metrics`` section of bench JSON.
* :mod:`~paddle_tpu.monitor.device` — the DEVICE-side layer: per-op
  named-scope attribution in HLO/xprof + ``device_profile/*``
  cost/memory gauges, the in-graph numerics watchdog
  (``PADDLE_TPU_CHECK_NUMERICS``), explicit-collective byte accounting
  (``collectives/*``), and the crash flight recorder
  (``PADDLE_TPU_FLIGHT_DIR``).
* :mod:`~paddle_tpu.monitor.telemetry` — CONTINUOUS export: a background
  thread snapshots the registry on an interval into a bounded JSONL
  time-series ring (``PADDLE_TPU_TELEMETRY_DIR``), renders Prometheus
  text (``monitor.to_prometheus()``), and drives the per-tick SLO
  evaluation of the next module.
* :mod:`~paddle_tpu.monitor.slo` — declarative SLOs
  (``SLO("serving/request_latency_ms", p=99, max_ms=250)``) evaluated per
  export tick against interval deltas; breaches count, hit the flight
  recorder, and (opt-in) degrade ``ServingEngine.health()``.
* :mod:`~paddle_tpu.monitor.budgets` — checked-in closed-form
  collective-traffic budgets asserted against the measured
  ``collectives/*`` counters (``tools/check_budgets.py``).
* :mod:`~paddle_tpu.monitor.runlog` / :mod:`~paddle_tpu.monitor.stepstats`
  — the ACROSS-run layer: a provenance-stamped run ledger
  (``PADDLE_TPU_RUN_LEDGER``) whose ``run_id`` cross-links telemetry,
  traces and fleet events, and step-time bottleneck attribution.

Quick tour::

    from paddle_tpu import monitor

    monitor.tracer.start_tracing()
    for batch in data:
        exe.run(main, feed=batch, fetch_list=[loss])
    print(monitor.to_text())                       # cache hits, step times…
    monitor.tracer.stop_tracing("/tmp/trace.json")  # open in chrome://tracing
"""

from __future__ import annotations

import os

from . import (  # noqa: F401
    budgets, device, metrics, numerics, runlog, slo, stepstats,
    telemetry, tracer,
)
from .metrics import (  # noqa: F401
    counter, gauge, histogram, enabled, enable, disable,
    snapshot, to_json, to_text, to_prometheus, reset,
)
from .slo import SLO, SLOMonitor  # noqa: F401
from .step_logger import StepLogger  # noqa: F401
from .telemetry import TelemetryExporter  # noqa: F401

__all__ = [
    "budgets", "device", "metrics", "numerics", "runlog", "slo",
    "stepstats", "telemetry", "tracer",
    "StepLogger", "SLO", "SLOMonitor", "TelemetryExporter",
    "counter", "gauge", "histogram", "enabled", "enable", "disable",
    "snapshot", "to_json", "to_text", "to_prometheus", "reset",
    "GRAD_NORM_VAR", "grad_norm_enabled",
]

# Name of the (non-persistable — never checkpointed) program var the
# optimizer writes the pre-clip global gradient norm into when grad-norm
# monitoring is on; the Executor fetches it as a hidden extra and mirrors
# it into the "optimizer/grad_global_norm" gauge post-step.
GRAD_NORM_VAR = "@grad_global_norm@"


def grad_norm_enabled() -> bool:
    """Opt-in (env ``PADDLE_TPU_GRAD_NORM=1``): reading the norm gauge
    forces one scalar device sync per step, so it is off by default."""
    return os.environ.get("PADDLE_TPU_GRAD_NORM", "").strip().lower() in (
        "1", "true", "yes", "on")

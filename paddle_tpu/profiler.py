"""Profiler (reference: python/paddle/fluid/profiler.py:39-221 +
platform/profiler.cc / device_tracer.cc over CUPTI).

The TPU-native stack: ``jax.profiler`` captures both host events and device
(TPU) timelines into a trace viewable in TensorBoard/Perfetto — the role the
reference splits between RecordEvent, CUPTI DeviceTracer, profiler.proto and
tools/timeline.py. The context-manager UX is kept identical.

For always-on, TensorBoard-free observability see
:mod:`paddle_tpu.monitor`: a metrics registry (counters/gauges/histograms
pre-wired through the Executor and readers) and a host-span tracer whose
Chrome-trace export loads directly in ``chrome://tracing``.
``record_event`` below feeds BOTH layers — the jax.profiler device trace
and the monitor host-span timeline — so one annotation shows up wherever
you are looking.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

import jax

__all__ = ["profiler", "start_profiler", "stop_profiler", "cuda_profiler",
           "npu_profiler", "record_event"]

_active_dir: Optional[str] = None


def start_profiler(state: str = "All", tracer_option=None, log_dir: Optional[str] = None):
    """reference: profiler.py:125. state/tracer_option accepted for parity."""
    global _active_dir
    _active_dir = log_dir or os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
    jax.profiler.start_trace(_active_dir)


def stop_profiler(sorted_key=None, profile_path: Optional[str] = None):
    """reference: profiler.py:165. The trace lands in the log dir for
    TensorBoard/Perfetto instead of a text table."""
    global _active_dir
    jax.profiler.stop_trace()
    d, _active_dir = _active_dir, None
    return d


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key=None, profile_path: Optional[str] = None,
             tracer_option=None, log_dir: Optional[str] = None):
    """reference: profiler.py:221 context manager."""
    start_profiler(state, tracer_option, log_dir or profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# GPU-era aliases kept for API parity; both map to the same TPU trace.
cuda_profiler = profiler
npu_profiler = profiler


@contextlib.contextmanager
def record_event(name: str):
    """RAII scope marker (reference: platform/profiler.h:41 RecordEvent) —
    shows up as a named range in the jax.profiler device trace AND, when
    host tracing is active (``PADDLE_TPU_TRACE_FILE`` /
    ``monitor.tracer.start_tracing()``), as a host span in the Chrome-trace
    export."""
    from .monitor import tracer as _tr

    with _tr.span(name, cat="user"):
        yield


class StepProfiler:
    """Step-time statistics table (reference: profiler.py:221's sorted text
    table — per-OP rows don't exist under XLA fusion, so the rows here are
    named step scopes: wall time min/avg/max/total + calls, plus a pointer
    at the full device trace for kernel-level drill-down).

        prof = StepProfiler()
        for batch in data:
            with prof.step("train"):
                exe.run(...)
        print(prof.summary())
    """

    def __init__(self):
        self._records = {}

    @contextlib.contextmanager
    def step(self, name: str = "step"):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._records.setdefault(name, []).append(time.perf_counter() - t0)

    def reset(self):
        self._records.clear()

    def summary(self, sorted_key: str = "total") -> str:
        keys = {"total": lambda r: -sum(r[1]), "max": lambda r: -max(r[1]),
                "min": lambda r: -min(r[1]), "calls": lambda r: -len(r[1]),
                "ave": lambda r: -sum(r[1]) / len(r[1])}
        if sorted_key not in keys:
            raise ValueError("sorted_key must be one of %s, got %r"
                             % (sorted(keys), sorted_key))
        rows = sorted(self._records.items(), key=keys[sorted_key])
        lines = ["%-24s %8s %12s %12s %12s %12s %12s %12s %12s" % (
            "Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)", "Ave(ms)",
            "P50(ms)", "P95(ms)", "P99(ms)")]
        from .monitor.metrics import sorted_percentile

        for name, ts in rows:
            st = sorted(ts)
            lines.append(
                "%-24s %8d %12.3f %12.3f %12.3f %12.3f %12.3f %12.3f %12.3f" % (
                    name, len(ts), sum(ts) * 1e3, min(ts) * 1e3, max(ts) * 1e3,
                    sum(ts) / len(ts) * 1e3, sorted_percentile(st, 50) * 1e3,
                    sorted_percentile(st, 95) * 1e3,
                    sorted_percentile(st, 99) * 1e3))
        lines.append("(kernel-level drill-down: run under profiler()/"
                     "start_profiler and open the trace dir in TensorBoard)")
        return "\n".join(lines)


__all__ += ["StepProfiler"]

# Module-level default profiler: scripts that just want step timings can use
# ``default_step_profiler().step(...)`` without threading an instance around,
# and reset_profiler() has real state to clear (reference semantics).
_default_step_profiler = StepProfiler()


def default_step_profiler() -> StepProfiler:
    return _default_step_profiler


def reset_profiler():
    """Clear collected profile data (reference: profiler.py reset_profiler):
    resets the module-level default StepProfiler. jax.profiler device traces
    are per start/stop window and need no clearing."""
    _default_step_profiler.reset()


__all__ += ["reset_profiler", "default_step_profiler"]

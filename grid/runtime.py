"""What every driver needs around its window: the device it runs on, a
count of compilations, the profiler, and spans on the profiler's clock."""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from typing import Any, Dict, Optional

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or not the number of chips the cell asks for."""


def device_doc() -> Dict[str, Any]:
    """The device as JAX reports it: the ``device`` object of the last
    line, and what decides whether a run may start at all."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> Dict[str, Any]:
    dev = device_doc()
    if dev["platform"] != "tpu":
        raise NoAccelerator("JAX found no TPU (platform %r): a grid run "
                            "measures the chip or nothing" % dev["platform"])
    if dev["count"] != chips:
        raise NoAccelerator("the cell asks for %d chip(s), JAX has %d"
                            % (chips, dev["count"]))
    return dev


def held_bytes() -> int:
    """``memory_stats()["peak_bytes_in_use"]`` of the fullest chip: the
    buffers the process held at their peak (weights, optimizer state, KV
    pool, feeds). The TPU runtime leaves a running program's temporaries
    out of it."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices())


def memory(executables) -> Dict[str, int]:
    """Bytes on the fullest chip, in the two parts the TPU runtime keeps
    apart: ``held`` (above) and ``scratch``, the largest
    ``temp_size_in_bytes`` the compiler's memory analysis gives for the
    executables the cell ran, which lie beside the held buffers while that
    program runs. Their sum is the last line's ``memory_peak_bytes``: the
    peak where the held bytes are steady while the largest program runs,
    as they are in every cell so far, and an upper bound of it elsewhere."""
    scratch = max(int(x.memory_analysis().temp_size_in_bytes)
                  for x in executables)
    return {"held": held_bytes(), "scratch": scratch}


class CompileMeter:
    """Counts XLA compilations (a load from the persistent cache is not
    one), so that a window can show it held none."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """``jax.profiler`` around a short stretch of a traced run. Starting
    and stopping it stalls the host for a second or more, so drivers do
    both outside what they measure and tell the stall apart (``stall_s``)."""

    def __init__(self, out_dir: Optional[str]):
        self.out_dir = out_dir
        self.stall_s = 0.0
        self.on = False

    @property
    def wanted(self) -> bool:
        return self.out_dir is not None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python frames: large, and slow
        opts.host_tracer_level = 2       # TraceAnnotation spans
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.stall_s += time.perf_counter() - t0
        self.on = True

    def stop(self) -> None:
        import jax

        if self.on:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stall_s += time.perf_counter() - t0
            self.on = False


@contextlib.contextmanager
def stopping(profiler: Profiler):
    """Never leave the profiler running, whatever the window raised."""
    try:
        yield profiler
    finally:
        profiler.stop()

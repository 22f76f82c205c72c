"""Persistent XLA compile cache: always on, placed from outside or at one
fixed path; and the process's ONE compile log.

The TVM argument (PAPERS.md) applied to this stack: the traced step is an
ahead-of-time compilation artifact, yet without a cache every process
restart re-pays the full XLA compile — minutes for the big train steps. JAX
ships a persistent on-disk compilation cache; this module decides where it
lives, once, at ``paddle_tpu`` import:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself and the package
  sets no directory in code. That is how a scheduler, a test run or the
  chip tool places the cache.
* unset — ``<checkout>/.jax_cache``, derived from the package's own
  location and listed in ``.gitignore``. The path is part of the cache's
  key, so it is never a temporary, per-process or dated name: a directory
  that moves never hits.

JAX's own thresholds decide what is worth writing (compiles of a second or
more): the chip's compiles are far above them, and the small CPU
executables of the tests stay out of the directory.

Observability, all of it fed by JAX's own monitoring events through the
listeners registered here and nowhere else:

* the ``compile_cache/hit`` / ``compile_cache/miss`` counters in
  :mod:`paddle_tpu.monitor` (a miss counts where JAX WRITES the entry: a
  compile under its thresholds moves neither);
* the compile log (:func:`log`, :func:`report`): one entry an executable
  the process traced, lowered, compiled or loaded from the cache, by name
  and instant, always on and in memory. It answers "why did this replica
  take three minutes to start" and, since it never closes, "which step
  recompiled at 14:02": an entry whose ``t`` lies after start-up IS a
  recompile, with its name;
* the three start-up phases (:class:`phase`, :func:`phases`):
  ``startup/import``, ``startup/weights``, ``startup/pools``.

An entry costs a few dictionary writes WHEN JAX COMPILES and nothing
otherwise: JAX calls a listener only where it traces, lowers, compiles or
asks the cache, so no path that runs a cached executable does any work
here (``tests/test_startup_log.py`` counts the calls across decode cycles:
zero). :func:`cost` says what the listeners have cost the process so far.

What an entry holds (plain dict; ``log()`` returns copies, oldest first):

``name``
    the label of the seam the program was under (:class:`label`:
    ``prefill[1024]``, ``chunk[fuse=8]``, ``step[1a2b3c4d]``), else JAX's
    own ``fun_name`` (``jit(add)`` is filed as ``add``).
``labelled``
    whether ``name`` is a seam's label. A seam's entry is its own: one
    entry an executable. Entries outside a seam (eager ``jax.numpy``, the
    engine's one-operation bookkeeping programs, ``init_params``' per-layer
    calls) are merged by name, a burst at a time (``MERGE_WITHIN_S``), with
    ``count`` the executables merged, so a start's log stays a few dozen
    entries however many eager operations it makes.
``t``, ``t_last``
    ``time.perf_counter()`` (the clock of ``tracer.span`` and of the grid's
    marks) where the entry began (the seam's opening; outside a seam the
    start of its first event) and at its newest event.
``trace_s``, ``lower_s``, ``backend_s``, ``retrieval_s``, ``saved_s``
    seconds in JAX's ``jaxpr_trace``, ``jaxpr_to_mlir_module`` and
    ``backend_compile`` events (the OUTERMOST event on its thread: the
    ``jnp`` calls traced inside a function are that function's trace),
    ``cache_retrieval_time_sec`` and ``compile_time_saved_sec``. On this
    JAX the backend event wraps ``compile_or_get_cached``, so it fires on a
    load from the cache too: ``backend_s`` is the event LESS
    ``retrieval_s``, so the two never count an instant twice.
``cache``
    ``hit`` (loaded), ``miss`` (compiled and written) or ``none`` (JAX did
    not ask the cache, or compiled under its thresholds and wrote nothing).
    A merged entry says ``miss`` if any of its executables missed, else
    ``hit`` if any was loaded.
``phase``
    the start-up phase open on the thread when the entry began, or None.
``wall_s``
    a seam's own length, opening to close (labelled entries): what
    ``executor/compile_time_ms`` observes.
"""

from __future__ import annotations

import collections
import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .monitor import metrics as _mx
from .monitor import tracer as _tr

__all__ = ["setup_compile_cache", "compile_cache_dir", "label", "phase",
           "in_phase", "log", "phases", "report", "cost"]

# Registered at import so the counters exist (value 0) before the first
# compile — tools/dump_metrics --selftest asserts their presence.
_m_hit = _mx.counter("compile_cache/hit",
                     help="XLA executables loaded from the persistent "
                          "compile cache")
_m_miss = _mx.counter("compile_cache/miss",
                      help="XLA compiles that went to the compiler and were "
                           "written to the persistent cache")

_configured = False
_hooked = False     # the listeners: once a process, whatever places the cache

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
# JAX's three stages of a build, by the entry's field they add to. Each is
# announced when it opens too (a scalar event of the same name), which is
# how a stage nested in another is known
_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

MERGE_WITHIN_S = 2.0    # an unlabelled entry takes a same-named build that
#                         follows its newest event within this
_KEEP = 2048            # entries kept from the process's start, and as many
#                         of the newest after them
_KEEP_PHASES = 256

_first: List[Dict[str, Any]] = []
_newest: collections.deque = collections.deque(maxlen=_KEEP)
_made = 0               # entries ever made (the log's own count of drops)
_merging: Dict[tuple, Dict[str, Any]] = {}  # (name, phase) -> open entry
_phases: List[tuple] = []
_calls = 0              # listener calls (a plain count: a thread's may be
#                         lost to another's), and the seconds filing took
_spent_s = 0.0
_lock = threading.Lock()    # held while an event that is kept is filed


class _PerThread(threading.local):
    depth = 0           # JAX's stages open on this thread
    seam = None         # the label() the thread is under
    build = None        # the unlabelled entry whose build is under way
    phase = None        # the phase() open on it
    cache = None        # what the compile request in progress has heard
    retrieval_s = 0.0   # ... from the cache; its backend event takes them
    saved_s = 0.0


_tls = _PerThread()


def compile_cache_dir() -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR``, else the fixed
    ``.jax_cache`` beside the package. The tuned-kernel and calibration
    tables sit in the same directory (tune.table_path,
    monitor.numerics.table_path)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def _new_entry(name: str, labelled: bool, t: float) -> Dict[str, Any]:
    global _made
    entry = {"name": name, "labelled": labelled, "t": t, "t_last": t,
             "count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
             "cache": "none", "retrieval_s": 0.0, "saved_s": 0.0,
             "phase": _tls.phase}
    _made += 1
    (_first if len(_first) < _KEEP else _newest).append(entry)
    return entry


def _entry_for(fun_name: Optional[str], backend: bool, start: float,
               now: float) -> Dict[str, Any]:
    seam = _tls.seam
    if seam is not None:
        if seam.entry is None:
            seam.entry = _new_entry(seam.name, True, seam.t0)
        return seam.entry
    name = str(fun_name or "?")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]       # the lowering's and the backend's spelling
    entry = _tls.build      # the build whose earlier stage came last
    if entry is None or entry["name"] != name:
        key = (name, _tls.phase)
        entry = _merging.get(key)
        if entry is None or now - entry["t_last"] > MERGE_WITHIN_S:
            entry = _merging[key] = _new_entry(name, False, start)
    _tls.build = None if backend else entry
    return entry


def _on_start(event: str, value, **kwargs) -> None:
    global _calls
    _calls += 1
    if event in _STAGE:
        _tls.depth += 1


def _on_event(event: str, **kwargs) -> None:
    global _calls
    _calls += 1
    if event == _HIT_EVENT:
        _m_hit.inc()
        _tls.cache = "hit"
    elif event == _MISS_EVENT:
        _m_miss.inc()
        _tls.cache = "miss"


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _calls, _spent_s
    _calls += 1
    tls = _tls
    field = _STAGE.get(event)
    if field is None:
        # the cache's events carry no name and fall inside the backend
        # event of the executable they are about: it takes them
        if event == _RETRIEVAL_EVENT:
            tls.retrieval_s += duration
        elif event == _SAVED_EVENT:
            tls.saved_s += duration
        return
    tls.depth = max(tls.depth - 1, 0)
    backend = field == "backend_s"
    if backend:     # what the cache said was about this executable
        heard, retrieval_s, saved_s = (tls.cache or "none", tls.retrieval_s,
                                       tls.saved_s)
        tls.cache, tls.retrieval_s, tls.saved_s = None, 0.0, 0.0
    if tls.depth:
        return      # nested: the outermost stage on the thread holds it
    now = time.perf_counter()
    with _lock:     # two threads' builds may merge into one entry
        entry = _entry_for(kwargs.get("fun_name"), backend, now - duration,
                           now)
        entry["t_last"] = now
        if backend:
            entry["count"] += 1
            # retrieval_s is the part of the event spent reading the cache
            duration = max(duration - retrieval_s, 0.0)
            entry["retrieval_s"] += retrieval_s
            entry["saved_s"] += saved_s
            if heard == "miss" or entry["cache"] == "none":
                entry["cache"] = heard
        entry[field] += duration
        _spent_s += time.perf_counter() - now


class label:
    """The seam's name for what JAX builds inside it: ``with
    label("prefill[1024]") as seam`` files every event of the thread under
    ONE entry of that name until it closes (the program's two seams:
    ``executor._timed_lower_compile`` and the executor's miss path). A
    label inside another is the outer one's; ``seam.seconds`` is the seam's
    own length either way and ``seam.entry`` its entry, None where JAX
    built nothing in it."""

    __slots__ = ("name", "entry", "t0", "seconds", "_holds")

    def __init__(self, name: str):
        self.name = name
        self.entry = None
        self.t0 = self.seconds = 0.0

    def __enter__(self) -> "label":
        self._holds = _tls.seam is None
        if self._holds:
            _tls.seam = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self.t0
        if self._holds:
            _tls.seam = None
            if self.entry is not None:
                self.entry["wall_s"] = self.seconds
        return False


class phase(_tr.span):
    """One of the three start-up spans: a :class:`tracer.span` (so a
    ``TraceAnnotation`` on the device trace's clock in any capture taken
    over a start) whose two ``perf_counter`` ends are also kept, always on,
    in :func:`phases`. Kept is the OUTERMOST phase of a thread, and only
    one that JAX is not tracing (a model's ``init_params`` called under a
    ``jit`` is that trace's time, and the log's)."""

    __slots__ = ("_kept",)

    def __enter__(self) -> "phase":
        self._kept = _tls.phase is None and not _tls.depth
        if self._kept:
            _tls.phase = self.name
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        if self._kept:
            _tls.phase = None
            if len(_phases) < _KEEP_PHASES:
                _phases.append((self.name, self.t0, self.t1))
        return False


def in_phase(name: str):
    """Decorator: each call of the function is a :class:`phase` ``name``
    (a model's ``init_params`` is ``startup/weights``)."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)
        return timed
    return wrap


def log() -> List[Dict[str, Any]]:
    """The entries, oldest first, as copies (the module docstring says what
    each holds). Past ``2 x 2048`` entries the middle is dropped: the
    start's and the newest stay."""
    return [dict(e) for e in _first + list(_newest)]


def phases() -> List[Dict[str, Any]]:
    """The start-up phases that have closed: ``name``, ``t0``, ``t1``."""
    return [{"name": n, "t0": t0, "t1": t1} for n, t0, t1 in list(_phases)]


def cost() -> Dict[str, Any]:
    """What the log has cost the process: calls of its listeners (every
    event JAX announced, nested ones too: a few microseconds each, JAX's
    own call included), the seconds spent filing the ones it kept, and the
    entries made and dropped."""
    return {"calls": _calls, "seconds": _spent_s, "entries": _made,
            "dropped": _made - len(_first) - len(_newest)}


def report(since: Optional[float] = None) -> str:
    """The log as a table, an entry a line: when (seconds after the
    process's first entry or phase), name, executables, the four durations
    and hit, miss or none; then the phases and the totals. ``since``: only
    entries that began at or after that ``perf_counter`` instant (the
    recompiles after a start)."""
    entries = [e for e in log() if since is None or e["t"] >= since]
    spans = phases()
    origin = min([e["t"] for e in entries] + [p["t0"] for p in spans]
                 + [time.perf_counter()])
    rows = ["%9s  %-36s %5s %9s %9s %9s %9s  %s" % (
        "t_s", "name", "n", "trace_s", "lower_s", "backend_s", "load_s",
        "cache")]
    for e in entries:
        rows.append("%9.3f  %-36s %5d %9.3f %9.3f %9.3f %9.3f  %s" % (
            e["t"] - origin, e["name"][:36], e["count"], e["trace_s"],
            e["lower_s"], e["backend_s"], e["retrieval_s"], e["cache"]))
    for p in spans:
        if since is None or p["t0"] >= since:
            rows.append("%9.3f  %-36s %5s %9.3f" % (
                p["t0"] - origin, p["name"], "-", p["t1"] - p["t0"]))
    hits = sum(1 for e in entries if e["cache"] == "hit")
    spent = cost()
    rows.append(
        "total: %d entries (%d executables; %d entries loaded from the "
        "cache, %d compiled and written, %d neither), trace %.3f s, lower "
        "%.3f s, backend %.3f s, cache load %.3f s; listeners: %d calls, "
        "%.1f ms%s" % (
            len(entries), sum(e["count"] for e in entries), hits,
            sum(1 for e in entries if e["cache"] == "miss"),
            sum(1 for e in entries if e["cache"] == "none"),
            sum(e["trace_s"] for e in entries),
            sum(e["lower_s"] for e in entries),
            sum(e["backend_s"] for e in entries),
            sum(e["retrieval_s"] for e in entries),
            spent["calls"], spent["seconds"] * 1e3,
            "; %d entries dropped" % spent["dropped"]
            if spent["dropped"] else ""))
    return "\n".join(rows)


def setup_compile_cache() -> None:
    """Place the cache (see the module docstring) and hook the counters and
    the compile log to JAX's monitoring events. Idempotent; called at
    ``paddle_tpu`` import, before anything can compile."""
    global _configured, _hooked
    if _configured:
        return
    import jax
    from jax import monitoring

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    if not _hooked:     # a test resets _configured to place the cache anew
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _hooked = True
    _configured = True

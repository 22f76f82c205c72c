"""Host-span tracer: nested wall-clock spans → Chrome-trace/Perfetto JSON.

The role the reference splits between ``platform/profiler.cc`` RecordEvent
and ``tools/timeline.py`` (CUPTI → chrome://tracing converter): record
named, nested host spans with microsecond timestamps and export them as a
``chrome://tracing`` / Perfetto-loadable JSON — no TensorBoard required.
There is one kind of span: every :class:`span` (and so
``profiler.record_event``) also enters a ``jax.profiler.TraceAnnotation``,
so the same name shows up in the host plane of any ``jax.profiler`` capture,
on the device trace's clock, whether or not this tracer is recording.

Activation: ``start_tracing()`` explicitly, or set ``PADDLE_TPU_TRACE_FILE``
— tracing then starts at import and the Chrome trace is written to that
path at interpreter exit. An idle tracer costs a span its annotation and two
clock reads; callers that build records of their own (``serving/trace.py``)
guard on ``active()``, a single module bool read.

Two file formats:

* **raw spans** (``save_spans``): ``{"schema": "paddle_tpu.host_spans/v1",
  "spans": [{name, cat, ts_us, dur_us, pid, tid, args}]}`` — the stable
  interchange format ``tools/dump_metrics.py`` converts from.
* **Chrome trace** (``save_chrome_trace`` / ``to_chrome_trace``): complete
  ("ph": "X") events under ``traceEvents``, plus process/thread metadata.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "span", "start_tracing", "stop_tracing", "active", "get_spans",
    "clear_spans", "save_spans", "load_spans", "to_chrome_trace",
    "save_chrome_trace", "SPAN_SCHEMA",
    "virtual_track", "record_span", "record_instant", "now_us",
]

SPAN_SCHEMA = "paddle_tpu.host_spans/v1"

# Test-only clock skew (µs), read once at import: every timestamp this
# process records OR reports (now_us(), span()/instant(), record_span's
# explicit ts) is shifted by it — the process behaves as if its
# perf_counter epoch differed. The fleet clock-offset handshake
# (fleet.replica.ProcessReplica) measures exactly this shift, and
# tools/fleet_trace.py's selftest injects a known skew into its workers
# to assert the midpoint estimate recovers it. Never set in production.
try:
    _skew_us: int = int(
        os.environ.get("PADDLE_TPU_TRACE_CLOCK_SKEW_US", "0") or 0)
except ValueError:
    _skew_us = 0

_active: bool = False
_spans: List[Dict[str, Any]] = []
_spans_lock = threading.Lock()
_tls = threading.local()  # per-thread stack of open span names
_trace_file: Optional[str] = None

# Virtual tracks: named synthetic (pid, tid) rows for spans whose natural
# grouping is NOT the emitting thread — e.g. one Chrome-trace row per
# serving batch slot, regardless of which host thread drove the engine.
# Synthetic tids count down from -1 so they can never collide with real
# thread idents (which are non-negative).
_track_ids: Dict[str, int] = {}
_track_names: Dict[int, str] = {}
_next_track = [-1]

# Whole-process tracing (PADDLE_TPU_TRACE_FILE) on a long-running job must
# not grow memory without bound: past this cap new spans are dropped (count
# kept) and a single warning is logged. Override with
# PADDLE_TPU_TRACE_MAX_SPANS.
_max_spans: int = int(os.environ.get("PADDLE_TPU_TRACE_MAX_SPANS", "1000000"))
_dropped: int = 0


def active() -> bool:
    return _active


def now_us() -> int:
    """This process's span clock, µs: ``perf_counter`` plus the injected
    test skew — the value cross-process clock handshakes must report so
    the handshake measures the same clock the spans are stamped with."""
    return time.perf_counter_ns() // 1000 + _skew_us


def start_tracing() -> None:
    """Begin recording host spans (idempotent; keeps prior spans)."""
    global _active
    _active = True


def stop_tracing(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """Stop recording; optionally write the Chrome trace to ``path``.
    Returns the recorded spans (still held — ``clear_spans()`` drops them)."""
    global _active
    _active = False
    spans = get_spans()
    if path:
        save_chrome_trace(path, spans)
    return spans


def get_spans() -> List[Dict[str, Any]]:
    with _spans_lock:
        return list(_spans)


def clear_spans() -> None:
    global _dropped
    with _spans_lock:
        _spans.clear()
        _dropped = 0


def _record(name: str, cat: str, t0_us: int, dur_us: int,
            args: Optional[dict], depth: int = 0,
            parent: Optional[str] = None) -> None:
    rec = {
        "name": name,
        "cat": cat,
        "ts_us": t0_us,
        "dur_us": dur_us,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "depth": depth,
    }
    if parent is not None:
        rec["parent"] = parent
    if args:
        rec["args"] = args
    global _dropped
    with _spans_lock:
        if len(_spans) >= _max_spans:
            _dropped += 1
            just_hit = _dropped == 1
        else:
            _spans.append(rec)
            just_hit = False
    if just_hit:
        import logging

        logging.getLogger("paddle_tpu").warning(
            "monitor.tracer: span buffer full (%d spans); further spans are "
            "dropped — raise PADDLE_TPU_TRACE_MAX_SPANS or scope tracing "
            "with start_tracing()/stop_tracing()", _max_spans)


class span:
    """One named span, two destinations.

    It always enters ``jax.profiler.TraceAnnotation(name, **args)``, so any
    ``jax.profiler`` capture shows it in the host plane under its plain
    name, on the device trace's clock (the annotation costs well under a
    microsecond when no profile runs). While the host tracer is active it
    is also recorded in memory: name, start, duration, nesting depth and
    ``parent`` (the span that contains it on the same thread); ``args``
    ride along (spans of one request share its ``trace_id``).

    ``t0`` and ``t1`` are the span's own two ``time.perf_counter`` reads,
    for a caller that feeds a histogram from the same interval.
    """

    __slots__ = ("name", "cat", "args", "t0", "t1", "_ann", "_stack")

    def __init__(self, name: str, cat: str = "host",
                 args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **(self.args or {}))
        self._ann.__enter__()
        self._stack = None
        if _active:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self.name)
            self._stack = stack
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        stack = self._stack
        if stack is not None:
            stack.pop()
            if _active:
                _record(self.name, self.cat, int(self.t0 * 1e6) + _skew_us,
                        max(1, int((self.t1 - self.t0) * 1e6)), self.args,
                        len(stack), stack[-1] if stack else None)
        return False


def instant(name: str, cat: str = "host", args: Optional[dict] = None) -> None:
    """Zero-duration marker (rendered as an instant event)."""
    if not _active:
        return
    _record(name, cat, now_us(), 0, args)


__all__.append("instant")


def virtual_track(name: str) -> int:
    """Stable synthetic tid for a named trace row (``"serving slot 3"``).
    The name lands in the Chrome trace's ``thread_name`` metadata so
    Perfetto shows a labeled track instead of a thread id."""
    with _spans_lock:
        tid = _track_ids.get(name)
        if tid is None:
            tid = _next_track[0]
            _next_track[0] -= 1
            _track_ids[name] = tid
            _track_names[tid] = name
        return tid


def record_span(name: str, ts_us: int, dur_us: int, cat: str = "host",
                track: Optional[str] = None,
                args: Optional[dict] = None) -> None:
    """Record a complete span with EXPLICIT timestamps (µs on the
    ``time.perf_counter`` clock — the same clock :func:`span` uses, so
    mixed implicit/explicit spans stay on one timeline). ``track`` routes
    the span onto a named virtual row (see :func:`virtual_track`) instead
    of the calling thread. The serving request tracer reconstructs
    request lifecycles from wall-clock timestamps through this."""
    if not _active:
        return
    tid = virtual_track(track) if track is not None else None
    rec = {
        "name": name,
        "cat": cat,
        "ts_us": int(ts_us) + _skew_us,
        "dur_us": max(0, int(dur_us)),
        "pid": os.getpid(),
        "tid": tid if tid is not None else threading.get_ident(),
        "depth": 0,
    }
    if track is not None:
        # the label rides the span record itself, so a raw-span file
        # converted in ANOTHER process (tools/dump_metrics --to-chrome)
        # still renders named tracks, not synthetic tids
        rec["track"] = track
    if args:
        rec["args"] = args
    global _dropped
    with _spans_lock:
        if len(_spans) >= _max_spans:
            _dropped += 1
        else:
            _spans.append(rec)


def record_instant(name: str, ts_us: int, cat: str = "host",
                   track: Optional[str] = None,
                   args: Optional[dict] = None) -> None:
    """Explicit-timestamp zero-duration marker on an optional virtual
    track (terminal request states in the serving trace)."""
    record_span(name, ts_us, 0, cat=cat, track=track, args=args)


# -- serialization ------------------------------------------------------------

def save_spans(path: str, spans: Optional[List[dict]] = None) -> str:
    """Write the raw host-span interchange file (see module docstring)."""
    doc = {"schema": SPAN_SCHEMA, "spans": spans if spans is not None else get_spans()}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def load_spans(path: str) -> List[dict]:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and doc.get("schema") == SPAN_SCHEMA:
        return list(doc.get("spans", []))
    if isinstance(doc, dict) and "traceEvents" in doc:
        # accept a Chrome trace back (the dump_metrics round-trip): complete
        # events AND instant markers survive; metadata ("M") is regenerated
        # on the next export, with virtual-track labels re-attached from the
        # thread_name metadata so named rows survive repeated conversions
        labels = {}
        for ev in doc["traceEvents"]:
            if ev.get("ph") == "M" and ev.get("name") == "thread_name":
                name = (ev.get("args") or {}).get("name", "")
                if not name.startswith("host-thread-"):
                    labels[(ev.get("pid", 0), ev.get("tid", 0))] = name
        spans = []
        for ev in doc["traceEvents"]:
            if ev.get("ph") not in ("X", "i", "I"):
                continue
            track = labels.get((ev.get("pid", 0), ev.get("tid", 0)))
            spans.append({
                "name": ev.get("name", ""), "cat": ev.get("cat", "host"),
                "ts_us": int(ev.get("ts", 0)), "dur_us": int(ev.get("dur", 0)),
                "pid": ev.get("pid", 0), "tid": ev.get("tid", 0),
                **({"track": track} if track else {}),
                **({"args": ev["args"]} if ev.get("args") else {}),
            })
        return spans
    raise ValueError("%s: not a %s or Chrome-trace file" % (path, SPAN_SCHEMA))


def to_chrome_trace(spans: Optional[List[dict]] = None,
                    process_names: Optional[Dict[int, str]] = None) -> dict:
    """Spans → ``chrome://tracing`` JSON object (the ``tools/timeline.py``
    output format: ``traceEvents`` complete events + metadata).
    ``process_names`` labels pids individually (a merged multi-process
    fleet timeline names its router/worker rows); unlisted pids keep the
    default label."""
    spans = spans if spans is not None else get_spans()
    events: List[dict] = []
    seen_threads = set()
    for s in spans:
        pid, tid = s.get("pid", 0), s.get("tid", 0)
        if (pid, tid) not in seen_threads:
            seen_threads.add((pid, tid))
            label = s.get("track")
            if label is None:
                with _spans_lock:
                    label = _track_names.get(tid, "host-thread-%s" % tid)
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": label}})
        ev = {
            "ph": "X" if s.get("dur_us", 0) else "i",
            "name": s.get("name", ""),
            "cat": s.get("cat", "host"),
            "ts": s.get("ts_us", 0),
            "pid": pid,
            "tid": tid,
        }
        if s.get("dur_us", 0):
            ev["dur"] = s["dur_us"]
        else:
            ev["s"] = "t"  # instant scope: thread
        if s.get("args"):
            ev["args"] = s["args"]
        events.append(ev)
    for pid in {s.get("pid", 0) for s in spans}:
        label = (process_names or {}).get(pid, "paddle_tpu host")
        events.append({"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                       "args": {"name": label}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"producer": "paddle_tpu.monitor.tracer"}}


def save_chrome_trace(path: str, spans: Optional[List[dict]] = None,
                      process_names: Optional[Dict[int, str]] = None) -> str:
    with open(path, "w") as f:
        json.dump(to_chrome_trace(spans, process_names=process_names), f)
    return path


# -- env activation -----------------------------------------------------------

def _maybe_autostart() -> None:
    global _trace_file
    path = os.environ.get("PADDLE_TPU_TRACE_FILE", "").strip()
    if not path:
        return
    _trace_file = path
    start_tracing()

    @atexit.register
    def _flush():  # pragma: no cover — exercised via subprocess in tests
        if get_spans():
            try:
                save_chrome_trace(_trace_file)
            except OSError:
                pass


_maybe_autostart()

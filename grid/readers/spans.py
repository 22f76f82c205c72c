"""Metrics from the PROGRAM's own spans (``serving/...`` inside
``ServingEngine.step()``, ``executor/...`` inside ``Executor.run``), read
from the host planes of the same ``.xplane.pb`` the run just wrote: on the
device trace's clock, so that every idle instant of the chip can be given
to what the program was doing in it.

``grid/run.py`` hands a reader ``(record, trace)`` with neither the trace's
directory nor the cell's name, and ``reduce.load`` keeps ``grid/`` spans
only, so this module finds the file itself (the newest under
``grid_out/*/trace``) and parses its host planes once a process. The
interval arithmetic is ``grid/reduce.py``'s, unchanged. A trace without
program spans (a checkout from before they existed) gives every reader
nothing to return, and none raises.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import manifest, reduce

Span = Tuple[str, float, float]          # name, start, end, in seconds
Interval = reduce.Interval

PREFIXES = ("serving/", "executor/")
STEP = "serving/step"
LAUNCH = ("serving/prefill.launch", "serving/decode.launch")
SYNC = ("serving/prefill.sync", "serving/decode.sync")
NO_SPAN = "(no span)"
IDLE_TOLERANCE = 0.02

_loaded: Dict[str, List[Span]] = {}
_reduced: Dict[str, Optional[Dict[str, Any]]] = {}


def newest_xplane(root: Optional[str] = None) -> Optional[str]:
    found = glob.glob(os.path.join(
        root or manifest.ROOT, "grid_out", "*", "trace", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(xplane_path: str) -> List[Span]:
    """The program's spans of every host thread, sorted by start, on the
    clock ``reduce.load`` puts the device's operations on."""
    hit = _loaded.get(xplane_path)
    if hit is None:
        from jax.profiler import ProfileData

        hit = []
        for plane in ProfileData.from_file(xplane_path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        hit.append((e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
        hit.sort(key=lambda s: s[1])
        _loaded[xplane_path] = hit
    return hit


# -- arithmetic on a span list ------------------------------------------------


def inside(spans: Sequence[Span], win) -> List[Span]:
    """The spans that lie wholly inside the window."""
    lo, hi = win
    return [s for s in spans if lo <= s[1] and s[2] <= hi]


def seconds(spans: Sequence[Span], *names: str) -> float:
    return sum(e - s for n, s, e in spans if n in names)


def count(spans: Sequence[Span], name: str) -> int:
    return sum(1 for s in spans if s[0] == name)


def _starts(spans: Sequence[Span]) -> List[float]:
    """The spans' start instants; ``spans`` must be sorted by them, as
    :func:`load` and :func:`inside` leave them."""
    starts = [c[1] for c in spans]
    if any(a > b for a, b in zip(starts, starts[1:])):
        raise ValueError("the spans are not sorted by their start")
    return starts


def self_intervals(spans: Sequence[Span], span: Span,
                   starts: List[float]) -> List[Interval]:
    """The instants of ``span`` that no span nested in it covers (the
    program's spans nest on one thread: whatever lies wholly inside a span
    is its descendant). ``spans`` are sorted by start and ``starts`` are
    those instants (:func:`_starts`), so that only the spans that begin
    inside ``span`` are looked at: a traced stretch holds ten thousand
    spans, and passing over all of them once a span took minutes."""
    _, s, e = span
    near = spans[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)]
    nested = reduce.union((c[1], c[2]) for c in near
                          if c != span and c[2] <= e)
    return reduce.subtract([(s, e)], nested)


def self_seconds(spans: Sequence[Span], name: str) -> float:
    """Duration of the spans called ``name`` minus the part of each that
    its child spans cover."""
    starts = _starts(spans)
    return sum(reduce.total(self_intervals(spans, sp, starts))
               for sp in spans if sp[0] == name)


def idle_by_span(trace: reduce.Trace, spans: Sequence[Span], win
                 ) -> Dict[str, float]:
    """Each idle instant of the first chip, given to the innermost program
    span that covers it: what ``reduce.idle_gaps_by_span`` does for the
    grid's own spans, for spans that nest, and without passing over every
    gap once a span (a traced stretch has a hundred thousand gaps and a
    thousand spans)."""
    lo, hi = win
    chips = sorted(trace.ops)
    if not chips:
        return {}
    gaps = reduce.subtract([(lo, hi)], reduce.busy(trace, chips[0], (lo, hi)))
    starts = [g[0] for g in gaps]
    span_starts = _starts(spans)
    out: Dict[str, float] = {}
    for span in spans:
        idle = 0.0
        for a, b in reduce.clip(self_intervals(spans, span, span_starts),
                                lo, hi):
            near = gaps[max(bisect.bisect_right(starts, a) - 1, 0):
                        bisect.bisect_left(starts, b)]
            idle += reduce.total(reduce.clip(near, a, b))
        if idle > 0:
            out[span[0]] = out.get(span[0], 0.0) + idle
    rest = reduce.total(gaps) - sum(out.values())
    if rest > 1e-12:
        out[NO_SPAN] = rest
    return out


def idle_split(idle: Dict[str, float]) -> Dict[str, float]:
    """Seconds of idle under a launch, under a sync, under the rest of
    ``serving/step``, and under no ``serving/`` span at all."""
    out = {"launch": 0.0, "sync": 0.0, "bookkeeping": 0.0, "outside": 0.0}
    for name, s in idle.items():
        if name in LAUNCH:
            out["launch"] += s
        elif name in SYNC:
            out["sync"] += s
        elif name.startswith("serving/"):
            out["bookkeeping"] += s
        else:
            out["outside"] += s
    return out


def table(trace: reduce.Trace, spans: Sequence[Span], win
          ) -> Dict[str, Dict[str, float]]:
    """By span name: how many lie in the window, their seconds, their self
    seconds and the device-idle seconds given to them. What PERF.md's
    section 5 is written from."""
    spans = inside(spans, win)
    idle = idle_by_span(trace, spans, win)
    out = {}
    for name in sorted({s[0] for s in spans}):
        out[name] = {"n": count(spans, name), "s": seconds(spans, name),
                     "self_s": self_seconds(spans, name),
                     "idle_s": idle.get(name, 0.0)}
    out[NO_SPAN] = {"n": 0, "s": 0.0, "self_s": 0.0,
                    "idle_s": idle.get(NO_SPAN, 0.0)}
    return out


def serve_metrics(trace: reduce.Trace, spans: Sequence[Span], win
                  ) -> Optional[Dict[str, Any]]:
    """All nine metrics of one traced stretch, or nothing where it holds no
    whole ``serving/step``. The four idle metrics are left out, and
    ``problem`` says why, where their sum is not the stretch's idle time
    (``reduce.idle_share`` x its length) within 2%."""
    spans = inside(spans, win)
    steps = count(spans, STEP)
    if not steps:
        return None
    per_step = 1e3 / steps
    launches = count(spans, "serving/decode.launch")
    prefill = seconds(spans, "serving/prefill")
    out: Dict[str, Any] = {
        "steps": steps,
        "engine_self_ms_per_step": per_step * (
            seconds(spans, STEP) - prefill - seconds(spans, "serving/decode")),
        "scheduler_ms_per_step": per_step * (
            seconds(spans, "serving/expire", "serving/admit") - prefill),
        "retire_ms_per_step": per_step * seconds(spans, "serving/retire"),
        "decode_launch_ms_mean": 1e3 * seconds(
            spans, "serving/decode.launch") / launches if launches else None,
        "prefills_per_step": count(spans, "serving/prefill") / steps,
    }
    if not trace.ops:
        return out
    split = idle_split(idle_by_span(trace, spans, win))
    want = reduce.idle_share(trace, win) * (win[1] - win[0])
    got = sum(split.values())
    if abs(got - want) > IDLE_TOLERANCE * max(want, 1e-12):
        out["problem"] = ("idle under the program's spans %.6f s, idle of "
                          "the traced stretch %.6f s: over %d%% apart, the "
                          "four idle metrics are left out"
                          % (got, want, round(IDLE_TOLERANCE * 100)))
        return out
    out.update(idle_launch_ms_per_step=per_step * split["launch"],
               idle_sync_ms_per_step=per_step * split["sync"],
               idle_bookkeeping_ms_per_step=per_step * split["bookkeeping"],
               idle_outside_step_ms_per_step=per_step * split["outside"])
    return out


# -- the readers --------------------------------------------------------------


def _serve(record, trace) -> Dict[str, Any]:
    """This run's metrics, reduced once; the table and any problem go to an
    earlier line of the output."""
    path = newest_xplane() if trace is not None else None
    if path is None:
        return {}
    if path not in _reduced:
        spans = load(path)
        win = tuple(record["trace_window"])
        got = serve_metrics(trace, spans, win) if spans else None
        _reduced[path] = got
        if got is not None:
            print(json.dumps({"note": {
                "program_spans": table(trace, spans, win),
                "steps": got["steps"], "problem": got.get("problem")}}),
                flush=True)
    return _reduced[path] or {}


def _reader(name: str):
    def read(record, trace=None) -> Optional[float]:
        return _serve(record, trace).get(name)

    read.__name__ = name
    read.__doc__ = "``%s`` of :func:`serve_metrics`." % name
    return read


idle_launch_ms_per_step = _reader("idle_launch_ms_per_step")
idle_sync_ms_per_step = _reader("idle_sync_ms_per_step")
idle_bookkeeping_ms_per_step = _reader("idle_bookkeeping_ms_per_step")
idle_outside_step_ms_per_step = _reader("idle_outside_step_ms_per_step")
engine_self_ms_per_step = _reader("engine_self_ms_per_step")
scheduler_ms_per_step = _reader("scheduler_ms_per_step")
retire_ms_per_step = _reader("retire_ms_per_step")
decode_launch_ms_mean = _reader("decode_launch_ms_mean")
prefills_per_step = _reader("prefills_per_step")

"""Per-request lifecycle tracing: serving requests → Perfetto timeline.

Every :class:`~.request.Request` carries a ``trace_id``; when the host
tracer is active (``monitor.tracer.start_tracing()`` or
``PADDLE_TPU_TRACE_FILE``) the engine emits the request's lifecycle into
the SAME span stream the rest of the stack traces to, on virtual tracks:

* ``serving queue`` — ``submitted`` instants, the ``queued`` wait span
  (submission → admission; submission → timeout for requests that die in
  the queue), and terminal instants for never-admitted requests;
* ``serving slot <k>`` — one track per batch slot: the request's
  lifetime span (``req <trace_id>``, admission → retirement), its
  ``prefill(b=<bucket>)`` span, every ``decode`` chunk span it rode
  (``decode_fuse`` steps per span; pages held + fused step count in
  args), and the terminal instant (``retired`` / ``FAILED`` /
  ``TIMEOUT``).

Because spans nest by time containment per track, opening the Chrome
trace in Perfetto reconstructs the continuous-batching schedule visually:
slot occupancy, admission holes, prefill/decode interleave, and which
requests shared each fused dispatch. The flight recorder links crash
dumps to this timeline by carrying ``trace_id`` in the in-flight batch
spec.

Everything here guards on ``tracer.active()`` — an untraced engine pays
one bool read per call site.

:func:`validate_request_spans` is the invariant checker the tests run
over a drained stream: every terminal request
must have a COMPLETE, WELL-NESTED span set — no orphan ``queued``
without a terminal instant, no partially-overlapping spans on a track.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ..monitor import tracer as _tr

__all__ = [
    "QUEUE_TRACK", "slot_track",
    "on_submitted", "on_admitted", "on_prefill", "on_decode_chunk",
    "on_terminal",
    "request_spans", "validate_request_spans", "slot_assignments_from_spans",
    "assert_well_nested",
]

QUEUE_TRACK = "serving queue"
CAT = "serving"


def slot_track(slot: int) -> str:
    return "serving slot %d" % slot


def _us(t_s: float) -> int:
    return int(t_s * 1e6)


def _targs(req, **kw) -> dict:
    """Common span args: trace_id always; the fleet attempt number when
    this request is a fleet dispatch (attempt >= 1) — the key the merged
    cross-process timeline joins attempt-1/attempt-2 replays on."""
    args = {"trace_id": req.trace_id}
    attempt = getattr(req, "attempt", 0)
    if attempt:
        args["attempt"] = attempt
    args.update(kw)
    return args


def on_submitted(req) -> None:
    if not _tr.active():
        return
    _tr.record_instant(
        "submitted", _us(req.submitted_t), cat=CAT, track=QUEUE_TRACK,
        args=_targs(req, prompt_len=req.prompt_len,
                    max_new_tokens=req.max_new_tokens))


def on_admitted(req, slot: int) -> None:
    """Close the queue-wait span (submission → admission)."""
    if not _tr.active():
        return
    _tr.record_span(
        "queued", _us(req.submitted_t),
        _us(req.admitted_t) - _us(req.submitted_t), cat=CAT,
        track=QUEUE_TRACK,
        args=_targs(req, slot=slot, phase="queue", cause="engine"))


def on_prefill(req, slot: int, bucket: int, t0_s: float, t1_s: float,
               cause: str = "local") -> None:
    """``cause`` is the phase-ledger attribution: ``local`` for a cold
    prefill, ``resume`` when the prompt resumed from cached/shipped
    prefix pages (the remote-prefill consumption path)."""
    if not _tr.active():
        return
    _tr.record_span(
        "prefill(b=%d)" % bucket, _us(t0_s), _us(t1_s) - _us(t0_s), cat=CAT,
        track=slot_track(slot),
        args=_targs(req, bucket=bucket, prompt_len=req.prompt_len,
                    phase="prefill", cause=cause))


def on_decode_chunk(reqs_by_slot: Sequence, fuse: int, t0_s: float,
                    t1_s: float) -> None:
    """One fused decode dispatch: a ``decode`` span on EVERY occupied
    slot's track (same wall window — that is the point: Perfetto shows
    which requests shared the dispatch). ``reqs_by_slot[k]`` is the
    request in slot k or None."""
    if not _tr.active():
        return
    ts, dur = _us(t0_s), _us(t1_s) - _us(t0_s)
    for slot, req in enumerate(reqs_by_slot):
        if req is None:
            continue
        _tr.record_span(
            "decode", ts, dur, cat=CAT, track=slot_track(slot),
            args=_targs(req, steps=fuse, pages_held=len(req.pages),
                        generated=len(req.tokens_out), phase="decode"))


def on_terminal(req, state: str, slot: Optional[int]) -> None:
    """Retirement from a slot (emits the request-lifetime span + the
    terminal instant on the slot track) or from the queue (``slot=None``:
    the queue-wait span never closed at admission — close it here — plus
    the terminal instant on the queue track)."""
    if not _tr.active():
        return
    label = {"finished": "retired", "failed": "FAILED",
             "timeout": "TIMEOUT"}.get(state, state)
    args = _targs(req, state=state, tokens_out=len(req.tokens_out))
    # the engine-measured readouts ride the terminal instant so the phase
    # ledger can check its decomposition against them (no new clocks —
    # these are the same request timestamps the histograms observe)
    if req.first_token_t is not None:
        args["ttft_ms"] = round((req.first_token_t - req.submitted_t) * 1e3,
                                3)
    if req.finished_t is not None:
        args["latency_ms"] = round((req.finished_t - req.submitted_t) * 1e3,
                                   3)
    if slot is not None:
        track = slot_track(slot)
        _tr.record_span(
            "req %s" % req.trace_id, _us(req.admitted_t),
            _us(req.finished_t) - _us(req.admitted_t), cat=CAT, track=track,
            args=dict(args, prompt_len=req.prompt_len))
    else:
        track = QUEUE_TRACK
        _tr.record_span(
            "queued", _us(req.submitted_t),
            _us(req.finished_t) - _us(req.submitted_t), cat=CAT, track=track,
            args=_targs(req, slot=None, phase="queue", cause="shed"))
    _tr.record_instant(label, _us(req.finished_t), cat=CAT, track=track,
                       args=args)


# -- read-back / validation ---------------------------------------------------

def request_spans(spans: Sequence[dict]) -> Dict[str, List[dict]]:
    """Group serving-cat spans by ``args.trace_id``."""
    out: Dict[str, List[dict]] = {}
    for s in spans:
        if s.get("cat") != CAT:
            continue
        tid = (s.get("args") or {}).get("trace_id")
        if tid:
            out.setdefault(tid, []).append(s)
    return out


_TERMINALS = {"retired": "finished", "FAILED": "failed", "TIMEOUT": "timeout"}


def validate_request_spans(spans: Sequence[dict], requests: Sequence
                           ) -> Dict[str, dict]:
    """Assert every terminal request has a complete, well-nested span set.

    Per terminal request: a ``submitted`` instant, a ``queued`` span, the
    matching terminal instant; admitted requests additionally need the
    lifetime ``req <id>`` span and a ``prefill`` span, and the lifetime
    span must CONTAIN every prefill/decode span of the request. Per
    track: spans must be disjoint or nested, never partially overlapping.
    Returns {trace_id: digest} for further assertions."""
    by_req = request_spans(spans)
    digests: Dict[str, dict] = {}
    for req in requests:
        if req.state not in ("finished", "failed", "timeout"):
            continue
        mine = by_req.get(req.trace_id, [])
        names = [s["name"] for s in mine]
        assert "submitted" in names, \
            "request %s: no submitted instant (spans: %s)" % (
                req.trace_id, names)
        assert "queued" in names, \
            "request %s: no queued span" % req.trace_id
        terminals = [s for s in mine if s["name"] in _TERMINALS]
        assert terminals, ("request %s: queued-without-terminal orphan "
                           "(state=%s, spans=%s)"
                           % (req.trace_id, req.state, names))
        assert len(terminals) == 1, \
            "request %s: %d terminal instants" % (req.trace_id,
                                                  len(terminals))
        term = terminals[0]
        assert _TERMINALS[term["name"]] == req.state, \
            "request %s: terminal %r but state %r" % (
                req.trace_id, term["name"], req.state)
        was_admitted = req.admitted_t is not None
        queued_args = next((s.get("args") or {} for s in mine
                            if s["name"] == "queued"), {})
        digest = {"state": req.state, "admitted": was_admitted,
                  "decode_chunks": sum(1 for n in names if n == "decode"),
                  "slot": queued_args.get("slot"), "track": None}
        if was_admitted:
            life = [s for s in mine if s["name"].startswith("req ")]
            assert len(life) == 1, \
                "request %s: %d lifetime spans" % (req.trace_id, len(life))
            life = life[0]
            assert any(n.startswith("prefill(") for n in names), \
                "request %s admitted but has no prefill span" % req.trace_id
            lo = life["ts_us"]
            hi = lo + life["dur_us"]
            for s in mine:
                if s["name"].startswith("prefill(") or s["name"] == "decode":
                    assert lo <= s["ts_us"] and \
                        s["ts_us"] + s["dur_us"] <= hi, (
                            "request %s: %s span [%d,%d] escapes lifetime "
                            "[%d,%d]" % (req.trace_id, s["name"], s["ts_us"],
                                         s["ts_us"] + s["dur_us"], lo, hi))
            digest["track"] = life["tid"]
        digests[req.trace_id] = digest
    assert_well_nested(spans)
    return digests


def assert_well_nested(spans: Sequence[dict], cat: str = CAT,
                       exempt: Sequence[str] = ("queued",)) -> None:
    """Per (pid, tid) track: any two ``cat`` spans are disjoint or one
    contains the other — the property that makes the Chrome viewer's
    stacking (and a human's read of the schedule) unambiguous. Span names
    in ``exempt`` are skipped: request lifelines of concurrent requests
    (``queued`` waits, fleet ``attempt`` windows) legitimately overlap
    partially — they are independent lifelines, not a call stack. The
    fleet validator (tools/fleet_trace.py) reuses this core per merged
    worker process, which is why the category is a parameter."""
    tracks: Dict[tuple, List[tuple]] = {}
    exempt = set(exempt)
    for s in spans:
        if s.get("cat") != cat or not s.get("dur_us"):
            continue
        if s["name"] in exempt:
            continue
        tracks.setdefault((s.get("pid"), s.get("tid")), []).append(
            (s["ts_us"], s["ts_us"] + s["dur_us"], s["name"]))
    for key, ivs in tracks.items():
        ivs.sort()
        stack: List[tuple] = []
        for lo, hi, name in ivs:
            while stack and stack[-1][1] <= lo:
                stack.pop()
            if stack:
                assert hi <= stack[-1][1], (
                    "track %s: span %r [%d,%d] partially overlaps %r "
                    "[%d,%d]" % (key, name, lo, hi, stack[-1][2],
                                 stack[-1][0], stack[-1][1]))
            stack.append((lo, hi, name))


def slot_assignments_from_spans(spans: Sequence[dict]) -> Dict[int, List[str]]:
    """{tid: [trace ids in start order]} from lifetime spans — the
    schedule reconstruction that is cross-checked against the
    ``serving/*`` counters (sum of assignments == requests admitted)."""
    out: Dict[int, List[tuple]] = {}
    for s in spans:
        if s.get("cat") != CAT or not s["name"].startswith("req "):
            continue
        out.setdefault(s["tid"], []).append(
            (s["ts_us"], (s.get("args") or {}).get("trace_id")))
    return {tid: [t for _, t in sorted(v)] for tid, v in out.items()}

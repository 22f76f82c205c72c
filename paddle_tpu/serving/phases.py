"""Per-request phase ledger: latency decomposition from the span stream.

The serving trace (serving/trace.py) and the fleet trace (fleet/trace.py)
already record everything needed to answer "where did this request's
latency go?" — this module just reads it back. No new hot-path clocks:
the emitters only TAG their existing spans with ``phase`` + ``cause``
args, and the ledger is derived entirely from a (merged) span stream.

Phase taxonomy — every microsecond of a request's life lands in one of:

* ``queue``     — waiting to run: the router's dispatch queue (cause
  ``router``, first attempt) and the engine's admission queue (cause
  ``engine``); a drain shedding queued work closes with cause ``shed``.
* ``admission`` — the scheduler gap between engine admission and the
  prefill dispatch actually starting (slot assignment, page reservation).
* ``prefill``   — the prefill dispatch; ``cause`` distinguishes a cold
  local prefill (``local``) from a prefix-cache resume (``resume``) —
  the resume path is also how a remote-prefill replica's shipped pages
  are consumed, so a disaggregated decode replica shows ``resume``.
* ``ship``      — KV-page migration windows (export → binary ship →
  ingest) attributed to the requests the migration served; ``cause`` is
  the migration purpose (``disagg``/``remote_hit``/``rebalance``/...).
* ``decode``    — fused decode dispatches the request rode.
* ``retry``     — requeue gaps: a replica died or rejected, the request
  sat re-queued until its next dispatch (fleet queued span, attempt>=2).
* ``tail``      — the drain/timeout tail: time between the last dispatch
  touching the request and its terminal instant.

:func:`ledgers_from_spans` builds one :class:`RequestLedger` per
``trace_id``;  :meth:`RequestLedger.ttft_decomposition` explains the
engine-measured ``serving/ttft_ms`` as queue + admission + prefill
(+ pre-first-token ship), which ``tools/fleet_autopsy.py --selftest``
asserts sums to the measured value within tolerance. The fleet-scope
join (per-replica attribution, breach verdicts) lives in
``fleet/autopsy.py`` on top of this module.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

__all__ = [
    "QUEUE", "ADMISSION", "PREFILL", "SHIP", "DECODE", "RETRY",
    "TAIL", "PHASES",
    "PhaseInterval", "RequestLedger", "ledgers_from_spans",
]

QUEUE = "queue"
ADMISSION = "admission"
PREFILL = "prefill"
SHIP = "ship"
DECODE = "decode"
RETRY = "retry"
TAIL = "tail"

PHASES = (QUEUE, ADMISSION, PREFILL, SHIP, DECODE, RETRY, TAIL)

_SERVING_TERMINALS = {"retired": "finished", "FAILED": "failed",
                      "TIMEOUT": "timeout", "rejected": "rejected"}
_FLEET_TERMINALS = ("finished", "failed", "timeout", "rejected")


class PhaseInterval:
    """One attributed slice of a request's life: [t0_us, t1_us) spent in
    ``phase``, with the emitter's ``cause`` tag, the replica it ran on
    (None when unattributable), the fleet attempt it belongs to, and the
    span stream it came from (``src``: "serving" or "fleet")."""

    __slots__ = ("phase", "t0_us", "t1_us", "cause", "replica", "attempt",
                 "src", "args")

    def __init__(self, phase: str, t0_us: int, t1_us: int,
                 cause: Optional[str] = None, replica: Optional[int] = None,
                 attempt: Optional[int] = None, src: str = "serving",
                 args: Optional[dict] = None):
        self.phase = phase
        self.t0_us = int(t0_us)
        self.t1_us = max(int(t1_us), int(t0_us))
        self.cause = cause
        self.replica = replica
        self.attempt = attempt
        self.src = src
        self.args = args or {}

    @property
    def ms(self) -> float:
        return (self.t1_us - self.t0_us) / 1e3

    def to_doc(self) -> dict:
        return {"phase": self.phase, "t0_us": self.t0_us,
                "t1_us": self.t1_us, "ms": round(self.ms, 3),
                "cause": self.cause, "replica": self.replica,
                "attempt": self.attempt, "src": self.src}

    def __repr__(self):
        return ("PhaseInterval(%s, %.3fms, cause=%s, replica=%s, attempt=%s)"
                % (self.phase, self.ms, self.cause, self.replica,
                   self.attempt))


class RequestLedger:
    """Every attributed interval of one request, plus the request-level
    facts joined from its instants: terminal state, the engine-measured
    TTFT/latency the terminal instant carries, and which replicas served
    it. Intervals are sorted by start time — the waterfall order."""

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.state: Optional[str] = None
        self.intervals: List[PhaseInterval] = []
        self.submitted_us: Optional[int] = None
        self.terminal_us: Optional[int] = None
        self.measured_ttft_ms: Optional[float] = None
        self.measured_latency_ms: Optional[float] = None
        self.attempts: int = 0

    def add(self, iv: PhaseInterval) -> None:
        self.intervals.append(iv)

    @property
    def replicas(self) -> List[int]:
        return sorted({iv.replica for iv in self.intervals
                       if iv.replica is not None})

    def phase_ms(self) -> Dict[str, float]:
        """Total milliseconds per phase (all attempts, all replicas)."""
        out = {p: 0.0 for p in PHASES}
        for iv in self.intervals:
            out[iv.phase] = out.get(iv.phase, 0.0) + iv.ms
        return out

    def e2e_ms(self) -> Optional[float]:
        if self.submitted_us is not None and self.terminal_us is not None:
            return (self.terminal_us - self.submitted_us) / 1e3
        if self.measured_latency_ms is not None:
            return self.measured_latency_ms
        return None

    def ttft_decomposition(self) -> dict:
        """Explain the engine-measured ``serving/ttft_ms`` of the FINAL
        attempt as engine queue + admission + prefill (the engine clock
        starts at engine submission, so router queue / retry gaps / ship
        windows are reported alongside, not inside, ``explained_ms``)."""
        serving = [iv for iv in self.intervals if iv.src == "serving"]
        final = max((iv.attempt or 0) for iv in serving) if serving else 0
        mine = [iv for iv in serving if (iv.attempt or 0) == final]

        def tot(phase):
            return sum(iv.ms for iv in mine if iv.phase == phase)

        prefill_end = max((iv.t1_us for iv in mine if iv.phase == PREFILL),
                          default=None)
        ship = sum(iv.ms for iv in self.intervals if iv.phase == SHIP
                   and (prefill_end is None or iv.t1_us <= prefill_end))
        out = {
            "queue_ms": round(tot(QUEUE), 3),
            "admission_ms": round(tot(ADMISSION), 3),
            "prefill_ms": round(tot(PREFILL), 3),
            "ship_ms": round(ship, 3),
            "router_queue_ms": round(
                sum(iv.ms for iv in self.intervals
                    if iv.src == "fleet" and iv.phase in (QUEUE, RETRY)), 3),
            "attempt": final,
        }
        out["explained_ms"] = round(
            out["queue_ms"] + out["admission_ms"] + out["prefill_ms"], 3)
        out["measured_ttft_ms"] = self.measured_ttft_ms
        return out

    def to_doc(self) -> dict:
        doc = {"trace_id": self.trace_id, "state": self.state,
               "attempts": self.attempts, "replicas": self.replicas,
               "phase_ms": {k: round(v, 3)
                            for k, v in self.phase_ms().items() if v > 0},
               "e2e_ms": (round(self.e2e_ms(), 3)
                          if self.e2e_ms() is not None else None),
               "ttft": self.ttft_decomposition(),
               "intervals": [iv.to_doc() for iv in self.intervals]}
        return doc


def _num(v) -> Optional[float]:
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _build(trace_id: str, mine: Sequence[dict],
           pid_to_replica: Dict[int, int]) -> RequestLedger:
    led = RequestLedger(trace_id)
    lifetimes: List[dict] = []
    for s in sorted(mine, key=lambda s: int(s.get("ts_us", 0))):
        args = s.get("args") or {}
        name = str(s.get("name", ""))
        cat = s.get("cat")
        t0 = int(s.get("ts_us", 0))
        dur = int(s.get("dur_us", 0) or 0)
        attempt = args.get("attempt")
        attempt = int(attempt) if attempt is not None else None
        if cat == "serving":
            replica = pid_to_replica.get(s.get("pid"))
            if not dur:
                if name == "submitted":
                    continue  # engine submission: the fleet root wins
                state = _SERVING_TERMINALS.get(name)
                if state is not None:
                    led.state = led.state or state
                    if led.terminal_us is None:
                        led.terminal_us = t0
                    t = _num(args.get("ttft_ms"))
                    if t is not None:
                        led.measured_ttft_ms = t
                    t = _num(args.get("latency_ms"))
                    if t is not None:
                        led.measured_latency_ms = t
                continue
            if name == "queued":
                led.add(PhaseInterval(
                    QUEUE, t0, t0 + dur, cause=args.get("cause", "engine"),
                    replica=replica, attempt=attempt, src="serving"))
            elif name.startswith("prefill("):
                led.add(PhaseInterval(
                    PREFILL, t0, t0 + dur, cause=args.get("cause", "local"),
                    replica=replica, attempt=attempt, src="serving"))
            elif name == "decode":
                led.add(PhaseInterval(
                    DECODE, t0, t0 + dur, cause=args.get("cause"),
                    replica=replica, attempt=attempt, src="serving",
                    args=args))
            elif name.startswith("req "):
                lifetimes.append(s)
        elif cat == "fleet":
            if not dur:
                if name == "submitted":
                    led.submitted_us = (t0 if led.submitted_us is None
                                        else min(led.submitted_us, t0))
                elif name in _FLEET_TERMINALS:
                    led.state = name  # the router's view is authoritative
                    led.terminal_us = t0
                    led.attempts = int(args.get("attempts",
                                                led.attempts) or 0)
                continue
            if name == "queued":
                phase = args.get("phase") or (
                    RETRY if (attempt or 1) >= 2 else QUEUE)
                led.add(PhaseInterval(
                    phase if phase in (QUEUE, RETRY) else QUEUE,
                    t0, t0 + dur,
                    cause=args.get("cause",
                                   "requeue" if phase == RETRY else "router"),
                    replica=args.get("replica"), attempt=attempt,
                    src="fleet"))
    # admission gap: engine queued-span end (admission) -> prefill start,
    # per attempt — the scheduler/page-reservation slice of TTFT
    for pf in [iv for iv in led.intervals if iv.phase == PREFILL]:
        q = [iv for iv in led.intervals
             if iv.phase == QUEUE and iv.src == "serving"
             and (iv.attempt or 0) == (pf.attempt or 0)
             and iv.t1_us <= pf.t0_us]
        if q:
            adm_t0 = max(iv.t1_us for iv in q)
            if pf.t0_us > adm_t0:
                led.add(PhaseInterval(
                    ADMISSION, adm_t0, pf.t0_us, cause="scheduler",
                    replica=pf.replica, attempt=pf.attempt, src="serving"))
    # tail: lifetime end past the last dispatch that touched the request
    # (a drain or deadline retiring it without a closing dispatch)
    for life in lifetimes:
        lo = int(life.get("ts_us", 0))
        hi = lo + int(life.get("dur_us", 0) or 0)
        last = max((iv.t1_us for iv in led.intervals
                    if iv.phase in (PREFILL, DECODE)
                    and lo <= iv.t0_us and iv.t1_us <= hi), default=lo)
        if hi > last:
            args = life.get("args") or {}
            led.add(PhaseInterval(
                TAIL, last, hi, cause=args.get("state", led.state),
                replica=pid_to_replica.get(life.get("pid")),
                attempt=args.get("attempt"), src="serving"))
    led.intervals.sort(key=lambda iv: (iv.t0_us, iv.t1_us))
    return led


def ledgers_from_spans(spans: Sequence[dict],
                       pid_to_replica: Optional[Dict[int, int]] = None
                       ) -> Dict[str, RequestLedger]:
    """One :class:`RequestLedger` per ``args.trace_id`` in ``spans``.

    Works on a single-engine serving stream and on a
    merged fleet stream (``fleet.trace.load_fragments`` output — pass the
    manifest-derived ``pid_to_replica`` so engine-side intervals carry
    replica attribution). Migration (``ship``) windows are joined in from
    ``migrate *`` lifecycle spans via their ``trace_ids`` args."""
    p2r = dict(pid_to_replica or {})
    by_id: Dict[str, List[dict]] = {}
    ships: List[dict] = []
    for s in spans:
        args = s.get("args") or {}
        if (str(s.get("name", "")).startswith("migrate")
                and args.get("trace_ids") and s.get("dur_us")):
            ships.append(s)
        tid = args.get("trace_id")
        if tid:
            by_id.setdefault(tid, []).append(s)
    out = {tid: _build(tid, mine, p2r) for tid, mine in by_id.items()}
    for s in ships:
        args = s.get("args") or {}
        t0 = int(s.get("ts_us", 0))
        t1 = t0 + int(s.get("dur_us", 0) or 0)
        for tid in args.get("trace_ids") or []:
            led = out.get(tid)
            if led is not None:
                led.add(PhaseInterval(
                    SHIP, t0, t1, cause=args.get("cause", "migration"),
                    replica=args.get("dst"), src="fleet", args=args))
                led.intervals.sort(key=lambda iv: (iv.t0_us, iv.t1_us))
    return out

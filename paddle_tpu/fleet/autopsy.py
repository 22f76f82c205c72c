"""Breach autopsy: SLO breaches joined against the span-derived phase ledger.

``monitor/slo.py`` says *that* an objective was violated; this module says
*why*. It is pure read-side — nothing here runs in a hot path:

1. :func:`build_ledgers` replays a traced fleet run (the merged fragment
   stream ``fleet.trace.load_fragments`` produces) through
   ``serving.phases.ledgers_from_spans``, using the trace manifest to map
   worker pids onto replica indices — every request becomes a
   :class:`~paddle_tpu.serving.phases.RequestLedger` whose intervals carry
   (phase, cause, replica, attempt).
2. :func:`phase_stats` folds the ledgers into per-phase percentile budgets
   at fleet and per-replica scope, and
   :func:`observe_phase_histograms` feeds the same totals into the
   ``fleet/phase/<name>/ms`` registry histograms so the ordinary metrics
   surfaces (snapshot/telemetry/fleet_top) can render the decomposition.
3. :func:`autopsy_breaches` joins each recorded SLO breach against the
   ledgers (and, when available, the per-replica telemetry interval
   deltas of the breach window) and emits a typed :class:`BreachAutopsy`
   verdict: the dominant phase, the offending replica(s), exemplar
   ``trace_id``s to pull up in the merged timeline, and an actionable
   hint. The router journals each verdict in the fleet event log
   (``kind=breach_autopsy``, under the run's ``run_id``) and the flight
   ring when it closes a traced run; ``tools/fleet_autopsy.py`` is the
   offline CLI over the same artifacts.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from ..monitor import metrics as _mx
from ..monitor import telemetry as _telemetry
from ..serving import phases as _phases
from . import metrics as _fm
from .slo import sample_from_doc

__all__ = ["BreachAutopsy", "build_ledgers", "pid_to_replica",
           "phase_stats", "observe_phase_histograms", "autopsy_breaches",
           "run_autopsy"]

# dominant phase -> what an operator should actually do about it
_HINTS = {
    _phases.QUEUE: "queue-bound: requests waited for capacity — add "
                   "replicas, raise engine slots, or shed load earlier",
    _phases.ADMISSION: "admission-bound: slot arming / page reservation "
                       "gap between admission and prefill — check page "
                       "pool pressure",
    _phases.PREFILL: "prefill-bound: prompt compute dominates — enable "
                     "the prefix cache or disaggregate prefill",
    _phases.SHIP: "migration-bound: KV-page shipping dominates — check "
                  "page frame sizes and the migration path",
    _phases.DECODE: "decode-bound: per-step decode latency is the "
                    "problem on the offending replica — look for "
                    "interference, injected faults, or an overloaded "
                    "host",
    _phases.RETRY: "churn-bound: requeue gaps after replica loss — "
                   "check replica crash/restart history",
    _phases.TAIL: "tail-bound: drain/timeout tails past the last "
                  "dispatch — raise drain budget or deadlines",
}


class BreachAutopsy:
    """One SLO breach explained: which phase ate the time, where, and
    which requests to look at. ``to_doc`` is the event-log payload."""

    __slots__ = ("breach", "scope", "replica", "dominant_phase",
                 "dominant_ms", "dominant_share", "phase_ms", "offenders",
                 "exemplars", "requests", "hint")

    def __init__(self, breach: dict, scope: str, replica: Optional[int],
                 dominant_phase: Optional[str], dominant_ms: float,
                 dominant_share: float, phase_ms: Dict[str, float],
                 offenders: List[dict], exemplars: List[str],
                 requests: int, hint: str):
        self.breach = breach
        self.scope = scope
        self.replica = replica
        self.dominant_phase = dominant_phase
        self.dominant_ms = dominant_ms
        self.dominant_share = dominant_share
        self.phase_ms = phase_ms
        self.offenders = offenders
        self.exemplars = exemplars
        self.requests = requests
        self.hint = hint

    def to_doc(self) -> dict:
        return {
            "slo": self.breach.get("slo"),
            "metric": self.breach.get("metric"),
            "scope": self.scope,
            "replica": self.replica,
            "dominant_phase": self.dominant_phase,
            "dominant_ms": round(self.dominant_ms, 3),
            "dominant_share": round(self.dominant_share, 4),
            "phase_ms": {k: round(v, 3)
                         for k, v in self.phase_ms.items() if v > 0},
            "offenders": self.offenders,
            "exemplars": self.exemplars,
            "requests": self.requests,
            "hint": self.hint,
            "breach": self.breach,
        }

    def __repr__(self):
        off = (self.offenders[0].get("replica")
               if self.offenders else self.replica)
        return ("BreachAutopsy(%s: dominant=%s %.1fms (%.0f%%), "
                "replica=%s)" % (self.breach.get("slo"),
                                 self.dominant_phase, self.dominant_ms,
                                 self.dominant_share * 100.0, off))


def pid_to_replica(manifest: Optional[dict]) -> Dict[int, int]:
    """Worker pid -> replica index from the trace manifest (the join that
    gives engine-side serving spans their replica attribution)."""
    out: Dict[int, int] = {}
    for e in (manifest or {}).get("workers") or []:
        if e.get("pid") is not None and e.get("replica") is not None:
            out[int(e["pid"])] = int(e["replica"])
    return out


def build_ledgers(spans: Sequence[dict], manifest: Optional[dict] = None
                  ) -> Dict[str, "_phases.RequestLedger"]:
    """Phase ledgers for every traced request of a merged fleet stream
    (clock offsets must already be applied — ``load_fragments`` output)."""
    return _phases.ledgers_from_spans(spans, pid_to_replica(manifest))


def _per_request_phase_ms(led) -> Dict[str, float]:
    return {p: v for p, v in led.phase_ms().items() if v > 0}


def _replica_phase_ms(led) -> Dict[int, Dict[str, float]]:
    out: Dict[int, Dict[str, float]] = {}
    for iv in led.intervals:
        if iv.replica is None:
            continue
        d = out.setdefault(int(iv.replica), {})
        d[iv.phase] = d.get(iv.phase, 0.0) + iv.ms
    return out


def phase_stats(ledgers: Dict[str, "_phases.RequestLedger"]) -> dict:
    """Fold ledgers into per-phase budgets: per-request distributions at
    fleet scope and per replica. ``{"fleet": {phase: {count, total_ms,
    p50_ms, p99_ms}}, "replicas": {index: {...}}, "requests": n}``."""
    fleet_vals: Dict[str, List[float]] = {p: [] for p in _phases.PHASES}
    rep_vals: Dict[int, Dict[str, List[float]]] = {}
    n = 0
    for led in ledgers.values():
        if led.state is None:
            continue
        n += 1
        for p, v in _per_request_phase_ms(led).items():
            fleet_vals.setdefault(p, []).append(v)
        for r, pm in _replica_phase_ms(led).items():
            d = rep_vals.setdefault(r, {})
            for p, v in pm.items():
                if v > 0:
                    d.setdefault(p, []).append(v)

    def _fold(vals: Dict[str, List[float]]) -> Dict[str, dict]:
        out = {}
        for p, xs in vals.items():
            if not xs:
                continue
            xs = sorted(xs)
            out[p] = {"count": len(xs),
                      "total_ms": round(sum(xs), 3),
                      "p50_ms": round(_mx.sorted_percentile(xs, 50), 3),
                      "p99_ms": round(_mx.sorted_percentile(xs, 99), 3)}
        return out

    return {"fleet": _fold(fleet_vals),
            "replicas": {r: _fold(v) for r, v in sorted(rep_vals.items())},
            "requests": n}


def observe_phase_histograms(ledgers: Dict[str, "_phases.RequestLedger"]
                             ) -> int:
    """Feed per-request phase totals into the ``fleet/phase/<name>/ms``
    registry histograms (one observation per request per non-zero phase)
    — the metrics-surface face of the decomposition. Returns the number
    of requests observed."""
    n = 0
    for led in ledgers.values():
        if led.state is None:
            continue
        n += 1
        for p, v in _per_request_phase_ms(led).items():
            h = _fm.PHASE_MS.get(p)
            if h is not None:
                h.observe(v)
    return n


def _telemetry_offenders(breach: dict, telemetry_base: str) -> List[dict]:
    """Rank replicas by the breached metric's interval mean in (or near)
    the breach window, from each replica's telemetry ring. Only histogram
    metrics rank this way (the latency-shaped breaches); an empty list
    means the caller falls back to ledger attribution."""
    metric = breach.get("metric")
    if not metric or not telemetry_base or not os.path.isdir(telemetry_base):
        return []
    window = breach.get("window") or {}
    t_b = float(window.get("t", 0.0) or 0.0)
    dt_b = float(window.get("dt_s", 0.0) or 0.0)
    ranked: List[dict] = []
    for name in sorted(os.listdir(telemetry_base)):
        if not name.startswith("replica_"):
            continue
        try:
            idx = int(name.split("_", 1)[1])
        except (IndexError, ValueError):
            continue
        try:
            docs = _telemetry.read_series(
                os.path.join(telemetry_base, name))
        except Exception:
            continue
        in_window: List[float] = []
        anywhere: List[float] = []
        for doc in docs:
            s = sample_from_doc(doc)
            v = s.histogram_interval_mean(metric)
            if v is None:
                continue
            anywhere.append(v)
            if not t_b or abs(s.t - t_b) <= 2.0 * max(dt_b, s.dt_s, 1.0):
                in_window.append(v)
        vals = in_window or anywhere
        if vals:
            ranked.append({"replica": idx,
                           "mean_ms": round(max(vals), 3),
                           "source": "telemetry",
                           "in_window": bool(in_window)})
    ranked.sort(key=lambda d: -d["mean_ms"])
    return ranked


def _ledger_offenders(candidates, phase: str) -> List[dict]:
    """Rank replicas by mean per-request milliseconds attributed to
    ``phase`` across the candidate ledgers."""
    per_rep: Dict[int, List[float]] = {}
    for led in candidates:
        for r, pm in _replica_phase_ms(led).items():
            v = pm.get(phase, 0.0)
            if v > 0:
                per_rep.setdefault(r, []).append(v)
    ranked = [{"replica": r, "mean_ms": round(sum(xs) / len(xs), 3),
               "requests": len(xs), "source": "ledger"}
              for r, xs in per_rep.items()]
    ranked.sort(key=lambda d: -d["mean_ms"])
    return ranked


def autopsy_breaches(breaches: Sequence[dict],
                     ledgers: Dict[str, "_phases.RequestLedger"],
                     telemetry_base: Optional[str] = None
                     ) -> List[BreachAutopsy]:
    """One :class:`BreachAutopsy` per distinct recorded breach.

    ``breaches`` are breach docs (``Breach.to_doc()``) optionally
    enriched with ``scope`` ("replica"/"fleet") and ``replica`` the way
    the router's event log records them; duplicates (same slo/scope/
    replica across evaluation ticks) collapse to the LAST occurrence.
    Attribution: candidate requests are the terminal ledgers (restricted
    to the breached replica for replica-scope breaches); the dominant
    phase is the largest total-milliseconds phase across candidates;
    offenders rank by the breach window's telemetry interval deltas when
    a ring is available, else by per-replica ledger totals; exemplars are
    the candidate requests that spent the most time in the dominant
    phase."""
    terminal = [led for led in ledgers.values() if led.state is not None]
    dedup: Dict[tuple, dict] = {}
    for b in breaches:
        key = (b.get("slo"), b.get("scope", "fleet"), b.get("replica"))
        dedup[key] = b  # keep-last
    out: List[BreachAutopsy] = []
    for (slo, scope, replica), b in dedup.items():
        if replica is not None:
            replica = int(replica)
            candidates = [led for led in terminal
                          if replica in led.replicas]
            # an unattributable breach window still gets a fleet-wide read
            if not candidates:
                candidates = terminal
        else:
            candidates = terminal
        totals: Dict[str, float] = {p: 0.0 for p in _phases.PHASES}
        for led in candidates:
            for p, v in _per_request_phase_ms(led).items():
                totals[p] = totals.get(p, 0.0) + v
        all_ms = sum(totals.values())
        dominant = max(totals, key=totals.get) if all_ms > 0 else None
        dominant_ms = totals.get(dominant, 0.0) if dominant else 0.0
        if replica is not None:
            offenders = _ledger_offenders(candidates, dominant) \
                if dominant else []
            offenders = [o for o in offenders
                         if o["replica"] == replica] or \
                [{"replica": replica, "source": "breach"}]
        else:
            offenders = (_telemetry_offenders(b, telemetry_base or "")
                         or (_ledger_offenders(candidates, dominant)
                             if dominant else []))
        offender_rep = (offenders[0].get("replica") if offenders
                        else replica)
        ex_pool = [led for led in candidates
                   if offender_rep is None
                   or offender_rep in led.replicas] or candidates
        ex_pool.sort(key=lambda led: -led.phase_ms().get(dominant or "", 0.0))
        exemplars = [led.trace_id for led in ex_pool[:3]]
        hint = _HINTS.get(dominant or "", "no phase attribution available")
        if offender_rep is not None and dominant:
            hint = "replica %s is the offender — %s" % (offender_rep, hint)
        out.append(BreachAutopsy(
            breach=b, scope=scope or "fleet", replica=replica,
            dominant_phase=dominant, dominant_ms=dominant_ms,
            dominant_share=(dominant_ms / all_ms) if all_ms > 0 else 0.0,
            phase_ms=totals, offenders=offenders[:4], exemplars=exemplars,
            requests=len(candidates), hint=hint))
    return out


def run_autopsy(trace_dir: str, event_log: Optional[str] = None,
                telemetry_base: Optional[str] = None) -> dict:
    """Offline autopsy over a finished run's artifacts: merge the trace
    fragments, build the ledgers, and (when an event log is given) join
    its recorded ``slo_breach`` events. Returns ``{"ledgers", "stats",
    "autopsies", "manifest", "problems"}`` — the CLI's whole input."""
    from . import trace as _ftr
    from .events import KIND_SLO_BREACH, read_events

    spans, manifest, problems = _ftr.load_fragments(trace_dir)
    ledgers = build_ledgers(spans, manifest)
    breaches: List[dict] = []
    if event_log:
        breaches = read_events(event_log, kind=KIND_SLO_BREACH)
    return {
        "ledgers": ledgers,
        "stats": phase_stats(ledgers),
        "autopsies": autopsy_breaches(breaches, ledgers,
                                      telemetry_base=telemetry_base),
        "manifest": manifest,
        "problems": problems,
    }

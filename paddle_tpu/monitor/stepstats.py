"""Step-time decomposition: WHY a step costs what it costs.

Says where a step's time went, fusing what the repo already measures
into per-term millisecond estimates for one step:

* ``compute_ms`` — ``device_profile/flops`` / peak FLOP/s (the roofline
  numerator ``monitor.device.step_report`` lists per op);
* ``memory_ms`` — ``device_profile/bytes_accessed`` / HBM bandwidth;
* ``comms_ms``  — the closed-form ``collectives/*/bytes`` counters /
  ICI bandwidth (per-device bytes one step moves, trace-time accounting);
* ``host_ms``   — the bench's measured host dispatch gap per step;
* ``input_ms``  — mean feed wait per observation across the prefetch-
  instrumented readers (``data/prefetch_wait_ms``,
  ``reader/wait_time_ms``, ``prefetcher/wait_time_ms``).

On hardware where no peak table entry exists (CPU dry runs), the device
terms fall back to the measured residual ``step_ms - host_ms - input_ms``
so attribution still ranks measured terms instead of going silent.

:func:`attribute` labels the step **compute- / comms- / host- /
input-bound** by the dominant term (the device roofline pair compute +
memory both map to "compute" — they are the same knob family) and
attaches an actionable hint; :func:`render` is its text form.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import metrics as _mx

__all__ = ["collect_terms", "attribute", "decompose", "render", "PEAKS"]

# THE per-chip peak table, by device-kind fragment: bf16 FLOP/s, HBM GB/s
# and ICI GB/s per direction (public spec sheets; v5e: Google Cloud
# documentation "TPU v5e"). bench.py and chip_smoke.py read it too.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v3": {"flops": 123e12, "hbm_gbps": 900.0, "ici_gbps": 70.0},
    "TPU v4": {"flops": 275e12, "hbm_gbps": 1200.0, "ici_gbps": 100.0},
    "TPU v5e": {"flops": 197e12, "hbm_gbps": 819.0, "ici_gbps": 50.0},
    "TPU v5 lite": {"flops": 197e12, "hbm_gbps": 819.0, "ici_gbps": 50.0},
    "TPU v5p": {"flops": 459e12, "hbm_gbps": 2765.0, "ici_gbps": 100.0},
    "TPU v6e": {"flops": 918e12, "hbm_gbps": 1640.0, "ici_gbps": 100.0},
}

# which Program-level knob each bound label points at
HINTS = {
    "compute": "device-bound: check MFU vs roofline per op "
               "(monitor.device.step_report), precision, and fusion rewrites",
    "comms": "comms-bound: check collectives/* vs the closed-form budgets "
             "(tools/check_budgets) and overlap/sharding layout",
    "host": "host-bound: use the fused run_steps driver / AOT prepare so "
            "dispatch overlaps device work",
    "input": "input-bound: feed wait dominates — raise prefetch depth / "
             "reader workers (paddle_tpu.data), or move parsing off the "
             "step loop",
}

_WAIT_HISTS = ("data/prefetch_wait_ms", "reader/wait_time_ms",
               "prefetcher/wait_time_ms")

# dominant-term name -> bound label
_TERM_BOUND = {"compute_ms": "compute", "memory_ms": "compute",
               "comms_ms": "comms", "host_ms": "host", "input_ms": "input"}


def device_peaks(device_kind: Optional[str] = None) -> Dict[str, float]:
    """Peak table entry matched by device-kind fragment. The host CPU has
    no peak worth the name ({}: a dry run's terms stay None); an
    accelerator the table does not know is an error, not a default — a
    utilization against a guessed peak is worse than none."""
    if device_kind is None:
        from .device import raw_device_kind

        device_kind = raw_device_kind()
    kind = (device_kind or "").lower()
    for frag, peaks in PEAKS.items():
        if frag.lower() in kind:
            return dict(peaks)
    if kind in ("", "cpu", "unknown"):
        return {}
    raise KeyError("device kind %r is not in monitor.stepstats.PEAKS — add "
                   "its published peaks there" % device_kind)


def _hist_mean(snap: Dict[str, dict], name: str) -> Optional[float]:
    h = snap.get(name)
    if not h or h.get("type") != "histogram" or not h.get("count"):
        return None
    return float(h["sum"]) / float(h["count"])


def collect_terms(snapshot: Optional[Dict[str, dict]] = None, *,
                  host_ms: Optional[float] = None,
                  device_kind: Optional[str] = None,
                  peaks: Optional[Dict[str, float]] = None
                  ) -> Dict[str, Optional[float]]:
    """Per-step term estimates (ms) from a metrics snapshot (default: the
    live registry). Terms the snapshot cannot support come back None —
    :func:`attribute` ranks only what is known."""
    snap = _mx.snapshot() if snapshot is None else snapshot
    if peaks is None:
        peaks = device_peaks(device_kind)

    def gauge(name):
        s = snap.get(name)
        return float(s["value"]) if s and s.get("value") else None

    terms: Dict[str, Optional[float]] = {
        "compute_ms": None, "memory_ms": None, "comms_ms": None,
        "host_ms": host_ms, "input_ms": None,
    }
    flops = gauge("device_profile/flops")
    if flops and peaks.get("flops"):
        terms["compute_ms"] = 1e3 * flops / peaks["flops"]
    hbm_bytes = gauge("device_profile/bytes_accessed")
    if hbm_bytes and peaks.get("hbm_gbps"):
        terms["memory_ms"] = 1e3 * hbm_bytes / (peaks["hbm_gbps"] * 1e9)
    coll_bytes = sum(
        float(s.get("value", 0.0)) for name, s in snap.items()
        if name.startswith("collectives/") and name.endswith("/bytes")
        and name.count("/") == 2 and s.get("value"))
    if coll_bytes and peaks.get("ici_gbps"):
        terms["comms_ms"] = 1e3 * coll_bytes / (peaks["ici_gbps"] * 1e9)
    waits = [m for m in (_hist_mean(snap, n) for n in _WAIT_HISTS)
             if m is not None]
    if waits:
        terms["input_ms"] = sum(waits)
    return terms


def attribute(terms: Dict[str, Optional[float]],
              step_ms: Optional[float] = None) -> Dict[str, Any]:
    """Label a step by its dominant term.

    ``terms`` is the (possibly partial) dict :func:`collect_terms`
    builds; ``step_ms`` the measured wall step time when known. With no
    device-side estimate but a measured ``step_ms``, the residual after
    host + input is attributed to compute — measured terms keep ranking
    on peak-less hardware."""
    known = {k: float(v) for k, v in terms.items() if v is not None}
    out: Dict[str, Any] = {"terms": {k: round(v, 4)
                                     for k, v in known.items()}}
    if step_ms is not None:
        out["step_ms"] = round(float(step_ms), 4)
    device_known = any(k in known for k in
                       ("compute_ms", "memory_ms", "comms_ms"))
    if not device_known and step_ms is not None:
        residual = float(step_ms) - known.get("host_ms", 0.0) \
            - known.get("input_ms", 0.0)
        known["compute_ms"] = max(0.0, residual)
        out["terms"]["compute_ms"] = round(known["compute_ms"], 4)
        out["compute_is_residual"] = True
    if not known:
        out.update(bound="unknown", dominant=None,
                   hint="no terms measured — run with metrics enabled")
        return out
    dominant = max(known, key=lambda k: known[k])
    bound = _TERM_BOUND.get(dominant, "unknown")
    out["dominant"] = dominant
    out["bound"] = bound
    out["hint"] = HINTS.get(bound, "")
    if step_ms:
        covered = sum(known.values())
        out["attributed_frac"] = round(
            min(1.0, covered / float(step_ms)), 4)
    return out


def decompose(snapshot: Optional[Dict[str, dict]] = None, *,
              step_ms: Optional[float] = None,
              host_ms: Optional[float] = None,
              device_kind: Optional[str] = None,
              peaks: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """collect_terms + attribute in one call — the bench-tail surface."""
    return attribute(
        collect_terms(snapshot, host_ms=host_ms, device_kind=device_kind,
                      peaks=peaks),
        step_ms=step_ms)


def render(breakdown: Dict[str, Any], config: str = "step") -> str:
    """One short human block for a breakdown."""
    lines = ["%s: %s-bound (dominant: %s)"
             % (config, breakdown.get("bound", "unknown"),
                breakdown.get("dominant"))]
    terms = breakdown.get("terms", {})
    for name in ("compute_ms", "memory_ms", "comms_ms", "host_ms",
                 "input_ms"):
        if name in terms:
            note = (" (residual)" if name == "compute_ms"
                    and breakdown.get("compute_is_residual") else "")
            lines.append("  %-12s %10.3f ms%s" % (name, terms[name], note))
    if "step_ms" in breakdown:
        lines.append("  %-12s %10.3f ms" % ("step_ms", breakdown["step_ms"]))
    if breakdown.get("hint"):
        lines.append("  hint: %s" % breakdown["hint"])
    return "\n".join(lines)

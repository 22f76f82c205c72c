"""Metrics from the window's record: the harness's own clock around the
calls into the program, and the program's counters."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .. import stats


# -- serving --------------------------------------------------------------------


def _in_window(record) -> List:
    m = record["marks"]
    return [tr for tr in record["tracked"] if m["open"] <= tr.due < m["close"]]


def _cycles(record) -> List:
    """The window's whole cycles (it closes at the end of one)."""
    m = record["marks"]
    return [c for c in record["cycles"]
            if m["open"] <= c.start and c.end <= m["close"]]


def _window_s(record) -> float:
    """From the window's opening to the end of its last whole cycle: all
    the time in which the tokens counted were emitted."""
    cyc = _cycles(record)
    return cyc[-1].end - record["marks"]["open"]


def serve_tokens_per_s(record, trace=None) -> float:
    return sum(c.tokens for c in _cycles(record)) / _window_s(record)


def _request_gaps(record) -> List[float]:
    """Per-request mean token gap of every request whose first token fell
    in the window, from its stamps inside the window."""
    m = record["marks"]
    stamps = []
    for tr in record["tracked"]:
        if tr.stamps and m["open"] <= tr.stamps[0][0] <= m["close"]:
            stamps.append([s for s in tr.stamps if s[0] <= m["close"]])
    return stats.request_gaps_ms(stamps, record["min_tokens_for_gap"])


def tpot_p50_ms(record, trace=None) -> Optional[float]:
    gaps = _request_gaps(record)
    return stats.percentile(gaps, 50) if gaps else None


def tpot_p95_ms(record, trace=None) -> Optional[float]:
    gaps = _request_gaps(record)
    return stats.percentile(gaps, 95) if gaps else None


def _ttft_ms(record) -> List[float]:
    """First-token time minus the instant the schedule said the request
    was due; a failed or refused request, or one still without a token
    when the run ended, counts as the window's length."""
    m = record["marks"]
    whole = (m["close"] - m["open"]) * 1e3
    out = []
    for tr in _in_window(record):
        if tr.refused or tr.req.first_token_t is None \
                or tr.req.state in ("failed", "timeout", "rejected"):
            out.append(whole)
        else:
            out.append((tr.req.first_token_t - tr.due) * 1e3)
    return out


def ttft_p95_ms(record, trace=None) -> Optional[float]:
    ttft = _ttft_ms(record)
    return stats.percentile(ttft, 95) if ttft else None


def queue_wait_ms_p50(record, trace=None) -> Optional[float]:
    waits = [(tr.req.admitted_t - tr.due) * 1e3 for tr in _in_window(record)
             if not tr.refused and tr.req.admitted_t is not None]
    return stats.percentile(waits, 50) if waits else None


def slot_occupancy_mean(record, trace=None) -> float:
    cyc = _cycles(record)
    return sum(c.occupancy for c in cyc) / len(cyc)


def _counter_delta(record, key: str) -> float:
    m = record["marks"]
    return m["c_close"][key] - m["c_open"][key]


def decode_dispatch_ms_mean(record, trace=None) -> Optional[float]:
    n = _counter_delta(record, "decode_n")
    return _counter_delta(record, "decode_ms") / n if n else None


def prefill_ms_mean(record, trace=None) -> Optional[float]:
    n = _counter_delta(record, "prefill_n")
    return _counter_delta(record, "prefill_ms") / n if n else None


# -- training ---------------------------------------------------------------------


def train_tokens_per_s(record, trace=None) -> float:
    m = record["marks"]
    return m["steps"] * record["tokens_per_step"] / (m["close"] - m["open"])


def feed_wait_ms_per_step(record, trace=None) -> float:
    n = record["marks"]["steps"]
    return sum(record["feed_s"][:n]) * 1e3 / n


def exe_host_ms_per_step(record, trace=None) -> float:
    n = record["marks"]["steps"]
    return sum(record["exe_s"][:n]) * 1e3 / n


# -- the earlier line ---------------------------------------------------------------


def summary(record) -> Dict[str, Any]:
    """What is worth reading and is not judged: sample counts, and in a
    serving run the first-token times and the queue: how long it was
    after the window's first and last cycles, the share of the window's
    cycles that ended on an empty one (a queue STANDS where that is under
    a twentieth: ``grid/sweep.py``), and the requests the engine refused
    in the whole run."""
    if record["kind"] == "train":
        m = record["marks"]
        return {"steps": m["steps"], "window_s": m["close"] - m["open"]}
    ttft = _ttft_ms(record)
    cyc = _cycles(record)
    return {"requests_due_in_window": len(ttft),
            "requests_with_a_gap": len(_request_gaps(record)),
            "ttft_ms": {"p50": stats.percentile(ttft, 50),
                        "p95": stats.percentile(ttft, 95),
                        "max": max(ttft)} if ttft else None,
            "cycles": len(cyc), "window_s": _window_s(record),
            "queue_depth_at_open": cyc[0].queue,
            "queue_depth_at_close": cyc[-1].queue,
            "queue_depth_max": max(c.queue for c in cyc),
            "queue_empty_cycle_share":
                sum(1 for c in cyc if not c.queue) / len(cyc),
            "refused": sum(1 for tr in record["tracked"] if tr.refused),
            "slot_occupancy_mean": slot_occupancy_mean(record)}

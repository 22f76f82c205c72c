"""Metrics from each request's OWN timeline, stamped inside the engine.

``Request.timeline`` holds one entry a hand-over of tokens, ``(t, n,
prefill_clock_s)``: the ``time.perf_counter`` instant, the tokens the
request had after it, and the seconds the engine had then spent inside
``serving/prefill`` spans. ``Request.prefill_s`` is the length of the
request's own such span, launch to slot armed. The readers here take them
from ``record["tracked"][i].req`` over the run's window, under
``tpot_p50_ms``'s own rule: requests whose first token fell in the window,
entries up to the window's close, at least ``min_tokens_for_gap`` tokens
shown. (The window closes before the profiler starts, so a traced run's
window is as undisturbed as any other; the traced tail is a few requests
long and has no median.)

A program whose requests carry no timeline (the parent of the PR that
brought this file) gives every reader nothing to return, and none raises.
A timeline that breaks its own arithmetic (a stall longer than the time it
lies in) raises: the run then fails with no last line.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

from .. import stats

Entry = Tuple[float, int, float]     # t, tokens so far, prefill clock (s)
SLACK_S = 1e-6     # float sums of some hundred spans, far under one token


def _timelines(record) -> Optional[List[Sequence[Entry]]]:
    """The timelines of the requests that were submitted, or None where
    the program keeps none."""
    reqs = [tr.req for tr in record["tracked"] if tr.req is not None]
    if not reqs or not all(hasattr(r, "timeline") for r in reqs):
        return None
    return [r.timeline for r in reqs]


def in_window(timelines: Sequence[Sequence[Entry]], open_t: float,
              close_t: float, min_tokens: int) -> List[List[Entry]]:
    """``tpot_p50_ms``'s rule on the engine's entries: the entries up to
    the window's close of every request whose first token fell in the
    window and that showed at least ``min_tokens`` tokens by then (and more
    than its first: one entry has no gap)."""
    out = []
    for tl in timelines:
        if not tl or not open_t <= tl[0][0] <= close_t:
            continue
        seen = [e for e in tl if e[0] <= close_t]
        if seen[-1][1] >= min_tokens and seen[-1][1] > seen[0][1]:
            check(seen)
            out.append(seen)
    return out


def check(entries: Sequence[Entry]) -> None:
    """What the stamps promise: instants and counts rise, the prefill clock
    never runs backwards nor faster than the wall clock."""
    for (t0, n0, c0), (t1, n1, c1) in zip(entries, entries[1:]):
        if not (t1 > t0 and n1 > n0):
            raise ValueError("a request's timeline does not rise: %r then %r"
                             % ((t0, n0, c0), (t1, n1, c1)))
        if not -SLACK_S <= c1 - c0 <= (t1 - t0) + SLACK_S:
            raise ValueError(
                "a request stalled %.6f s behind prefills in the %.6f s "
                "between two of its hand-overs" % (c1 - c0, t1 - t0))


def mean_gap_ms(entries: Sequence[Entry]) -> float:
    """``tpot_p50_ms``'s own arithmetic (``stats.mean_gap_ms``) on the
    engine's instants and counts."""
    return stats.mean_gap_ms([e[:2] for e in entries], 0)


def stall_ms_per_token(entries: Sequence[Entry]) -> float:
    (_, n0, c0), (_, n1, c1) = entries[0], entries[-1]
    return (c1 - c0) * 1e3 / (n1 - n0)


def longest_gap_ms(entries: Sequence[Entry]) -> float:
    return max(b[0] - a[0] for a, b in zip(entries, entries[1:])) * 1e3


def _over_requests(record, per_request: Callable[[Sequence[Entry]], float],
                   q: float) -> Optional[float]:
    timelines = _timelines(record)
    if timelines is None:
        return None
    m = record["marks"]
    picked = in_window(timelines, m["open"], m["close"],
                       record["min_tokens_for_gap"])
    return stats.percentile([per_request(e) for e in picked], q) \
        if picked else None


def tpot_engine_p50_ms(record, trace=None) -> Optional[float]:
    return _over_requests(record, mean_gap_ms, 50)


def prefill_stall_ms_per_token_p50(record, trace=None) -> Optional[float]:
    return _over_requests(record, stall_ms_per_token, 50)


def prefill_stall_ms_per_token_p95(record, trace=None) -> Optional[float]:
    return _over_requests(record, stall_ms_per_token, 95)


def longest_handover_gap_ms_p50(record, trace=None) -> Optional[float]:
    return _over_requests(record, longest_gap_ms, 50)


def admission_ms_mean(record, trace=None) -> Optional[float]:
    """Mean ``prefill_s`` of the requests admitted in the window."""
    m = record["marks"]
    admitted = [tr.req for tr in record["tracked"]
                if tr.req is not None and tr.req.admitted_t is not None
                and m["open"] <= tr.req.admitted_t < m["close"]]
    spans = [r.prefill_s for r in admitted
             if getattr(r, "prefill_s", None) is not None]
    return sum(spans) * 1e3 / len(spans) if spans else None

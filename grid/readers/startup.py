"""Where a start goes: ``setup_s`` split by the program's own compile log
(``paddle_tpu.compile_cache.log()``: one entry an executable the process
traced, lowered, compiled or loaded from the persistent cache, by name) and
its three start-up phases (``.phases()``: ``startup/import``,
``startup/weights``, ``startup/pools``), read in the run's own process.

    setup_s = startup_import_s + startup_weights_s + startup_trace_lower_s
              + startup_backend_compile_s + startup_cache_load_s
              + preroll_s + startup_unattributed_s

The five durations are sums over entries and phases that CLOSED before the
pre-roll began (the schedule's origin: a tracked request's due instant less
its offset in the plan); ``preroll_s`` is from there to the window's
opening (the traffic's ``preroll_s`` and the cycle in progress at its end;
0 where the run has no schedule), so an executable built under the
pre-roll's traffic is inside it and is named in the note; the remainder is
DEFINED as the difference (the harness's own start, the TPU client, the
plan, the warm-up traffic's first runs, whatever no entry or phase covers),
so the seven add to ``setup_s`` exactly, and a remainder below zero (an
instant counted twice) RAISES: the run then has no last line. Entries and
phases older than the harness's first line are no part of this start. An entry's seconds are taken out of the phase it
began in. The two counts are of LABELLED entries (the program's own
executables: ``prefill[..]``, ``chunk[..]``, ``step[..]``) that began
before the window opened.

A program without the log (the parent of the PR that added it) gives every
reader here nothing to return, and none raises.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

STAGES = ("trace_s", "lower_s", "backend_s", "retrieval_s")
WEIGHT_PHASES = ("startup/weights", "startup/pools")
SLACK_S = 1e-6
_KEY = "_startup_split"     # the split, computed once a run, on the record


def preroll_origin(record) -> float:
    """The instant the schedule's clock started; the window's opening where
    the run has no schedule (training)."""
    opened = record["marks"]["open"]
    tracked = record.get("tracked") or []
    if not tracked:
        return opened
    return min(opened, tracked[0].due - tracked[0].planned.due_s)


def _seconds(entry) -> float:
    return sum(entry[k] for k in STAGES)


def split(record, entries: List[Dict[str, Any]],
          phases: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The eight metrics' values, and ``note``, from a run's record and the
    program's log and phases."""
    opened = float(record["marks"]["open"])
    setup = float(record["setup_s"])
    start, origin = opened - setup, preroll_origin(record)
    # the log is the process's: what is older than the harness's first line
    # (the grid's own tests run many cells in one process) is no part of
    # this start
    entries = [e for e in entries if e["t"] >= start]
    before = [e for e in entries if e["t_last"] <= origin]
    spans = [p for p in phases if start <= p["t0"] and p["t1"] <= origin]

    def phase_s(names) -> float:
        return sum(p["t1"] - p["t0"] for p in spans if p["name"] in names) \
            - sum(_seconds(e) for e in before if e["phase"] in names)

    hits = [e for e in before if e["cache"] == "hit"]
    parts = {
        "startup_import_s": phase_s(("startup/import",)),
        "startup_weights_s": phase_s(WEIGHT_PHASES),
        "startup_trace_lower_s": sum(e["trace_s"] + e["lower_s"]
                                     for e in before),
        "startup_backend_compile_s": sum(e["backend_s"] for e in before
                                         if e["cache"] != "hit"),
        "startup_cache_load_s": sum(e["retrieval_s"] for e in before)
        + sum(e["backend_s"] for e in hits),
    }
    parts = {k: float(v) for k, v in parts.items()}
    preroll = opened - origin
    rest = setup - preroll - sum(parts.values())
    if rest < -SLACK_S or min(parts.values()) < -SLACK_S:
        raise ValueError(
            "the start-up parts do not fit in setup_s: %r and a pre-roll of "
            "%.3f s in %.3f s (an instant counted twice: entries of two "
            "threads, or a phase around another?)" % (parts, preroll, setup))
    mine = [e for e in entries if e["labelled"] and e["t"] < opened]
    close = float(record["marks"].get("close", opened))

    def row(e):
        return [e["name"], e["count"], e["cache"], round(e["trace_s"], 3),
                round(e["lower_s"], 3), round(e["backend_s"], 3),
                round(e["retrieval_s"], 3), round(e["saved_s"], 1)]

    other = sorted((e for e in before if not e["labelled"]),
                   key=_seconds, reverse=True)
    out = dict(parts)
    out["startup_executables_compiled"] = float(sum(
        1 for e in mine if e["cache"] != "hit"))
    out["startup_executables_from_cache"] = float(sum(
        1 for e in mine if e["cache"] == "hit"))
    out["startup_unattributed_s"] = max(rest, 0.0)
    out["note"] = {
        "phase": "startup", "setup_s": setup, "preroll_s": preroll,
        "unattributed_s": rest,
        "parts": {k: round(v, 4) for k, v in parts.items()},
        "phases": [[p["name"], round(p["t0"] - start, 3),
                    round(p["t1"] - p["t0"], 3)] for p in spans],
        # name, executables, cache, trace, lower, backend, load, saved
        "labelled": [row(e) for e in mine],
        "unlabelled_entries": len(other),
        "unlabelled_executables": sum(e["count"] for e in other),
        "unlabelled_s": round(sum(_seconds(e) for e in other), 3),
        "unlabelled_largest": [row(e) for e in other[:8]],
        # built under traffic: under the pre-roll's, inside the window,
        # and after it (the reference's own programs, the traced tail)
        "entries_in_preroll": [row(e) for e in entries
                               if origin < e["t_last"] and e["t"] < opened],
        "entries_in_window": [row(e) for e in entries
                              if opened <= e["t"] < close],
        "entries_before_window": sum(1 for e in entries if e["t"] < opened),
        "entries_after_window": sum(1 for e in entries if e["t"] >= close)}
    return out


def _split(record) -> Dict[str, Any]:
    if _KEY not in record:
        from paddle_tpu import compile_cache

        log = getattr(compile_cache, "log", None)
        if log is None:             # a program without the log
            record[_KEY] = {}
        else:
            record[_KEY] = split(record, log(), compile_cache.phases())
            note = dict(record[_KEY]["note"], listeners=compile_cache.cost())
            print(json.dumps({"note": note}), flush=True)
    return record[_KEY]


def _reader(name: str):
    def read(record, trace=None) -> Optional[float]:
        return _split(record).get(name)

    read.__name__ = name
    read.__doc__ = "``%s`` of :func:`split`." % name
    return read


startup_import_s = _reader("startup_import_s")
startup_weights_s = _reader("startup_weights_s")
startup_trace_lower_s = _reader("startup_trace_lower_s")
startup_backend_compile_s = _reader("startup_backend_compile_s")
startup_cache_load_s = _reader("startup_cache_load_s")
startup_executables_compiled = _reader("startup_executables_compiled")
startup_executables_from_cache = _reader("startup_executables_from_cache")
startup_unattributed_s = _reader("startup_unattributed_s")

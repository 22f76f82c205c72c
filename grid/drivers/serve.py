"""Serves a decoder through ``ServingEngine`` under an open-loop schedule.

The harness owns the loop: submit what is due, ``engine.step()``, read the
clock and each live request's token count; sleep to the next due instant
only when the scheduler is idle. Requests are timed from when they were
DUE, and how late the generator ran is part of the record. Nothing here
searches for a rate: the traffic file fixes it.
"""

from __future__ import annotations

import bisect
import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

from .. import generate, runtime
from ..reference import decoder as reference


def build(job) -> Any:
    """Model and engine at the configuration's sizes, weights made on the
    device in one jitted call from the seed, in the type they are served
    in."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.decoder_lm import (DecoderConfig, DecoderLM,
                                              init_params)
    from paddle_tpu.serving import ServingConfig, ServingEngine

    m, e = job.config["model"], job.config["engine"]
    mcfg = DecoderConfig(vocab_size=m["vocab_size"], n_layer=m["n_layer"],
                         d_model=m["n_embd"], n_head=m["n_head"],
                         max_seq=m["n_positions"], dtype=m["dtype"])
    params = jax.jit(lambda s: init_params(mcfg, s))(
        jnp.asarray(generate.np_seed(job.seed), jnp.int32))
    model = DecoderLM(mcfg, params=params)
    engine = ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"]))
    return engine


def warm(engine, vocab: int) -> None:
    """Every executable the traffic will use, and every slot once: the
    engine's host-side bookkeeping jits a few one-operation programs on
    first use, and they too must be done before the window."""
    engine.warmup()
    buckets = engine.cfg.prompt_buckets
    for i in range(engine.cfg.slots):
        engine.submit([(7 * i + j) % vocab
                       for j in range(buckets[i % len(buckets)])], 3)
    engine.run()


class Cycle(NamedTuple):
    """One ``engine.step()`` as the harness saw it."""

    start: float
    end: float
    occupancy: int     # live slots after the cycle
    queue: int         # requests waiting after the cycle
    context: int       # sum of the live slots' context lengths after it
    tokens: int        # tokens the cycle emitted


class Tracked:
    """One planned request from submission on."""

    __slots__ = ("due", "planned", "req", "stamps", "seen", "refused")

    def __init__(self, due: float, planned):
        self.due = due              # perf_counter instant it was due at
        self.planned = planned
        self.req = None
        self.stamps: List = []      # (clock, tokens so far) when it grew
        self.seen = 0
        self.refused = False


def drive(engine, plan, window_s: float, preroll_s: float, tail_s: float,
          profiler: runtime.Profiler, log, meter=None) -> Dict[str, Any]:
    """Pre-roll, window, and (traced runs) a traced tail, in one loop.

    The schedule runs on a clock that stops while the profiler starts:
    that stall is the harness's, not the system's, and the engine (which
    does nothing by the clock) cannot tell. The window closes before the
    profiler starts, so a traced run's window is as undisturbed as any
    other."""
    from paddle_tpu.serving import metrics as sm
    from paddle_tpu.serving.request import BackpressureError

    def counters():
        return {"prefill_ms": sm.PREFILL_MS.sum,
                "prefill_n": sm.PREFILL_MS.count,
                "decode_ms": sm.DECODE_STEP_MS.sum,
                "decode_n": sm.DECODE_STEP_MS.count,
                "compiles": meter.compiles if meter else 0}

    sched = engine.scheduler
    tracked: List[Tracked] = []
    by_id: Dict[int, Tracked] = {}
    cycles: List[Cycle] = []
    marks: Dict[str, Any] = {}
    nxt = 0
    origin = time.perf_counter()
    open_at, close_at = preroll_s, preroll_s + window_s
    end_at = close_at + tail_s
    phase = "preroll"
    while True:
        now = time.perf_counter()
        t = now - origin
        if phase == "preroll" and t >= open_at:
            phase = "window"
            marks["open"] = now
            marks["c_open"] = counters()
        if phase == "window" and t >= close_at:
            marks["close"] = now
            marks["c_close"] = counters()
            if not tail_s:
                break
            phase = "tail"
            stall = profiler.stall_s
            profiler.start()
            origin += profiler.stall_s - stall
            marks["tail_open"] = time.perf_counter()
            continue
        if phase == "tail" and t >= end_at:
            marks["tail_close"] = now
            break
        while nxt < len(plan) and plan[nxt].due_s <= t:
            tr = Tracked(origin + plan[nxt].due_s, plan[nxt])
            try:
                tr.req = engine.submit(tr.planned.prompt,
                                       tr.planned.max_new_tokens)
                by_id[tr.req.id] = tr
            except BackpressureError:
                tr.refused = True
            tracked.append(tr)
            nxt += 1
        if sched.idle():
            due = plan[nxt].due_s if nxt < len(plan) else end_at
            edge = {"preroll": open_at, "window": close_at,
                    "tail": end_at}[phase]
            nap = min(due, edge) - t
            if nap > 0:
                with runtime.span("grid/idle"):
                    time.sleep(nap)
            continue
        with runtime.span("grid/engine.step"):
            done = engine.step()
        end = time.perf_counter()
        emitted = ctx = 0
        for req in list(sched.running()) + done:
            tr = by_id.get(req.id)
            if tr is None:
                continue
            n = len(req.tokens_out)
            if n > tr.seen:
                emitted += n - tr.seen
                tr.seen = n
                tr.stamps.append((end, n))
            if req.state == "running":
                ctx += req.prompt_len + n
        cycles.append(Cycle(now, end, sched.occupancy, sched.queue_depth,
                            ctx, emitted))
    profiler.stop()
    log({"phase": "drive", "planned": len(plan), "submitted": len(tracked),
         "cycles": len(cycles), "profiler_stall_s": profiler.stall_s})
    return {"tracked": tracked, "cycles": cycles, "marks": marks}


def harness_lateness(cycles, starts, tr) -> float:
    """Seconds the HARNESS added to a request's submission: from the end of
    the engine cycle in progress when it fell due (the loop submits between
    cycles, and a cycle with an admission in it lasts 20 ms in the GPT-2
    cells and 0.2-0.8 s in the sparse ones, so submission minus due time
    measures the engine's step) to its submission; from its due instant
    where no cycle was in progress. ``starts`` are the cycles' start
    instants, in order."""
    i = bisect.bisect_right(starts, tr.due) - 1
    free_at = tr.due
    if i >= 0 and cycles[i].end > tr.due:
        free_at = cycles[i].end
    return max(tr.req.submitted_t - free_at, 0.0)


def compared(n_failed: int, n_short: int, compiles: int, late_p50: float,
             decode_ms: float, margins, reference) -> Dict[str, List]:
    """Each number ``check`` compared beside its limit, by a short plain
    name: the last line's ``compared`` and the last lines on standard
    error (``grid/run.py``). ``margins`` are the sampled requests' worst
    rows, plain or as ``{"margin": .., "mean_gap": ..}``."""
    rows = [m if isinstance(m, dict) else {"margin": m} for m in margins]
    out = {"failed": [n_failed, 0], "short": [n_short, 0],
           "compiles": [compiles, 0], "late_p50_ms": [late_p50, decode_ms]}
    if rows:
        out["logit_margin"] = [max(r["margin"] for r in rows),
                               reference.LOGIT_MARGIN]
        if "mean_gap" in rows[0]:
            out["mean_gap"] = [max(r["mean_gap"] for r in rows),
                               reference.MEAN_GAP_LIMIT]
    return out


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window. The generator's lateness
    is counted from the end of the engine cycle in progress
    (:func:`harness_lateness`; until PR 37 from the due instant, which at
    12 of 32 slots read 5.3 ms for a dispatch of 5.1 and failed runs for
    the engine's own cycle length)."""
    marks = record["marks"]
    in_window = [tr for tr in record["tracked"]
                 if marks["open"] <= tr.due < marks["close"]]
    failed = [tr for tr in in_window
              if tr.refused or tr.req.state in ("failed", "timeout",
                                                "rejected")]
    finished = [tr for tr in in_window
                if not tr.refused and tr.req.state == "finished"]
    short = [tr for tr in finished
             if len(tr.req.tokens_out) != tr.planned.max_new_tokens]
    starts = [c.start for c in record["cycles"]]
    late = sorted(harness_lateness(record["cycles"], starts, tr)
                  for tr in in_window if not tr.refused)
    c0, c1 = marks["c_open"], marks["c_close"]
    decode_ms = ((c1["decode_ms"] - c0["decode_ms"])
                 / max(c1["decode_n"] - c0["decode_n"], 1))
    late_p50 = late[len(late) // 2] * 1e3 if late else 0.0
    problems = []
    if not in_window:
        problems.append("no request was due in the window")
    if failed:
        problems.append("%d requests failed or were refused" % len(failed))
    if short:
        problems.append("%d finished requests did not emit their budget"
                        % len(short))
    if not engine.page_accounting_ok():
        problems.append("page accounting does not balance")
    if compiles_in_window:
        problems.append("%d compilations inside the window"
                        % compiles_in_window)
    if late_p50 > decode_ms:
        problems.append("the generator ran late by %.1f ms at the median "
                        "beyond the engine cycle in progress, more than one "
                        "decode dispatch (%.1f ms)" % (late_p50, decode_ms))
    # two finished requests against the grid's own float32 reference
    sample = finished[:: max(len(finished) // 2, 1)][:2]
    margins = []
    for tr in sample:
        worst = reference.worst_margin(
            engine.params, job.config["model"], tr.planned.prompt,
            tr.req.tokens_out)
        margins.append(worst)
        if worst > reference.LOGIT_MARGIN:
            problems.append(
                "a served token ranks %.4f below the float32 reference's "
                "argmax (margin %.4f)" % (worst, reference.LOGIT_MARGIN))
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    return {"correct": not problems, "problems": problems,
            "attempted": len(in_window), "failed": len(failed),
            "generator_late_ms": {"p50": late_p50,
                                  "max": late[-1] * 1e3 if late else 0.0},
            "reference_margins": margins,
            "compared": compared(len(failed), len(short),
                                 compiles_in_window, late_p50, decode_ms,
                                 margins, reference)}


def run(job) -> Dict[str, Any]:
    traffic = job.traffic
    engine = build(job)
    with engine:
        job.log({"phase": "built", "decode_kernel":
                 list(engine.decode_kernel_info())})
        t0 = time.perf_counter()
        warm(engine, job.config["model"]["vocab_size"])
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0})
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        plan = generate.serve_plan(
            traffic, job.config["model"]["vocab_size"], job.seed,
            job.seconds, tail_s)
        with runtime.stopping(job.profiler):
            record = drive(engine, plan, job.seconds,
                           float(traffic["preroll_s"]), tail_s,
                           job.profiler, job.log, job.meter)
        marks = record["marks"]
        record["compiles"] = (marks["c_close"]["compiles"]
                              - marks["c_open"]["compiles"])
        record["memory"] = runtime.memory(
            list(engine._decode_exe.values())
            + list(engine._prefill_exe.values()))
        record["kind"] = "serve"
        record["min_tokens_for_gap"] = int(traffic["min_tokens_for_gap"])
        record["model"] = job.config["model"]
        record["slots"] = engine.cfg.slots
        record["pool_rows"] = engine.cfg.num_pages * engine.cfg.page_size
        record.update(check(engine, record, job, record["compiles"]))
    return record

"""Serves SmallThinker's sparse decoder through the same ``ServingEngine``
and the same open-loop harness as ``drivers/serve.py``: ``warm`` and
``drive`` (with their ``Tracked`` and ``Cycle``) are that module's. Its own are ``build`` (the
model, its cache groups' pools) and ``check`` (the float32 reference of
THIS architecture, with one of the two sampled requests past the window),
``plan`` (every ``--seed`` offers the same lengths in the same order),
and a sample a cycle of what the new counters read, for the readers of
``grid/readers/moe.py``. ``record["kind"]`` stays ``"serve"``: the window's
readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

from .. import generate, runtime
from ..reference import smallthinker as reference
from .serve import compared, drive, warm


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config."""
    from paddle_tpu.models.smallthinker import SmallThinkerConfig

    n = int(config["num_hidden_layers"])
    return SmallThinkerConfig(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        n_expert=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        d_expert=config["moe_ffn_hidden_size"],
        window=config["sliding_window_size"],
        rope_layout=config["rope_layout"][:n],
        window_layout=config["sliding_window_layout"][:n],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        max_seq=config["model"]["max_seq"], dtype=config["model"]["dtype"])


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.smallthinker import SmallThinkerLM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = SmallThinkerLM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


def plan(traffic: Dict[str, Any], vocab: int, seed: int, window_s: float,
         tail_s: float) -> List[generate.Planned]:
    """Every request of one run: ``generate.serve_plan``'s, but WHICH
    arrival gets which length belongs to the traffic file
    (``arrivals.order_seed``) as the arrival instants do, and ``--seed``
    draws the token ids alone (and, in ``build``, the weights).

    An unchunked prefill of 512 to 8,192 tokens stalls all 16 slots for
    50 to 420 ms and a 40 s window holds about 50 of them, so which lengths
    fall inside the window, and how long the contexts that live together
    are, is 5% of the tokens a second (the refusal of PR 27's first cell):
    every seed has to offer the same work at the same instants."""
    order = generate.serve_plan(traffic, 1,
                                int(traffic["arrivals"]["order_seed"]),
                                window_s, tail_s)
    rng = np.random.RandomState(generate.np_seed(seed))
    return [generate.Planned(p.due_s,
                             rng.randint(0, vocab, len(p.prompt)).tolist(),
                             p.max_new_tokens) for p in order]


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``."""

    end: float
    pages_used: Dict[str, int]     # by cache group
    blocked: float                 # serving/admission_blocked_on_pages
    touched_sum: float             # serving/moe_experts_touched, sum
    touched_n: int                 # ... and observations (a layer a step)
    window_ctx: int                # sum over live slots of min(ctx, window)


def sampling(engine, samples: List[Sample], window: int) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper)."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(),
            {p.name: p.num_used for p in engine.pools},
            sm.ADMISSION_BLOCKED.value, sm.MOE_EXPERTS_TOUCHED.sum,
            sm.MOE_EXPERTS_TOUCHED.count,
            sum(min(r.prompt_len + len(r.tokens_out) - 1, window)
                for r in engine.scheduler.running())))
        return done

    engine.step = stepped


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the experts a step touched and the contexts it read."""
    m = record["marks"]
    cyc = [c for c in record["cycles"]
           if m["open"] <= c.start and c.end <= m["close"]]
    inside = [s for s in record["samples"] if m["open"] <= s.end <= m["close"]]
    c0, c1 = m["c_open"], m["c_close"]
    note = {"phase": "window", "cycles": len(cyc),
            "longest_cycles_ms": sorted(
                round((c.end - c.start) * 1e3) for c in cyc)[-5:],
            "prefills": c1["prefill_n"] - c0["prefill_n"],
            "prefill_s": (c1["prefill_ms"] - c0["prefill_ms"]) / 1e3,
            "decodes": c1["decode_n"] - c0["decode_n"],
            "decode_s": (c1["decode_ms"] - c0["decode_ms"]) / 1e3,
            "context_mean": sum(c.context for c in cyc) / max(len(cyc), 1)}
    if len(inside) > 1:
        n = inside[-1].touched_n - inside[0].touched_n
        note["experts_touched_mean"] = (
            inside[-1].touched_sum - inside[0].touched_sum) / max(n, 1)
        note["blocked_cycles"] = inside[-1].blocked - inside[0].blocked
    return note


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window: ``drivers/serve.py``'s rule
    (the served token's rank below the float32 reference's best logit, in
    row standard deviations, over two finished requests), with ONE of the
    two a request whose context passed the window."""
    marks = record["marks"]
    window = int(job.config["sliding_window_size"])
    in_window = [tr for tr in record["tracked"]
                 if marks["open"] <= tr.due < marks["close"]]
    failed = [tr for tr in in_window
              if tr.refused or tr.req.state in ("failed", "timeout",
                                                "rejected")]
    finished = [tr for tr in in_window
                if not tr.refused and tr.req.state == "finished"]
    short = [tr for tr in finished
             if len(tr.req.tokens_out) != tr.planned.max_new_tokens]
    late = sorted(tr.req.submitted_t - tr.due for tr in in_window
                  if not tr.refused)
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
        problems.append("page accounting does not balance in every group")
    ring = engine.cache_ops.group_pages_per_slot(1)
    most = max((s.pages_used["window"] for s in record["samples"]),
               default=0)
    if most > ring * engine.cfg.slots:
        problems.append("the window group held %d pages, over %d a slot"
                        % (most, ring))
    if compiles_in_window:
        problems.append("%d compilations inside the window"
                        % compiles_in_window)
    if late_p50 > decode_ms:
        problems.append("the generator ran late by %.1f ms at the median, "
                        "more than one decode dispatch (%.1f ms)"
                        % (late_p50, decode_ms))

    def total(tr):
        return tr.req.prompt_len + len(tr.req.tokens_out)

    past = [tr for tr in finished if total(tr) > window]
    inside = [tr for tr in finished if total(tr) <= window]
    sample = past[:1] + (inside or past[1:])[:1]
    if not past:
        problems.append("no finished request's context passed the window "
                        "of %d" % window)
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    margins = []
    for tr in sample:
        worst = reference.worst_margin(engine.params, job.config,
                                       tr.planned.prompt, tr.req.tokens_out)
        margins.append({"context": total(tr), "margin": worst})
        if worst > reference.LOGIT_MARGIN:
            problems.append(
                "a served token ranks %.4f below the float32 reference's "
                "argmax (margin %.4f; context %d)"
                % (worst, reference.LOGIT_MARGIN, total(tr)))
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
    vocab = int(job.config["vocab_size"])
    t0 = time.perf_counter()
    engine = build(job)
    with engine:
        job.log({"phase": "built", "build_s": time.perf_counter() - t0,
                 "decode_kernel": list(engine.decode_kernel_info()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": engine.cache_ops.cache_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0})
        samples: List[Sample] = []
        sampling(engine, samples, int(job.config["sliding_window_size"]))
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        planned = plan(traffic, vocab, job.seed, job.seconds, tail_s)
        with runtime.stopping(job.profiler):
            record = drive(engine, planned, job.seconds,
                           float(traffic["preroll_s"]), tail_s,
                           job.profiler, job.log, job.meter)
        marks = record["marks"]
        record["samples"] = samples
        record["compiles"] = (marks["c_close"]["compiles"]
                              - marks["c_open"]["compiles"])
        job.log(window_note(record))
        executables = (list(engine._decode_exe.values())
                       + list(engine._prefill_exe.values()))
        record["memory"] = runtime.memory(executables)
        record["kind"] = "serve"
        record["min_tokens_for_gap"] = int(traffic["min_tokens_for_gap"])
        record["model"] = job.config
        record["slots"] = engine.cfg.slots
        record["pools"] = {p.name: p.num_pages for p in engine.pools}
        record["pool_rows"] = engine.cfg.num_pages * engine.cfg.page_size
        job.log({"phase": "executables", "bytes": {
            str(k): int(x.memory_analysis().generated_code_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())},
            "scratch": {str(k): int(x.memory_analysis().temp_size_in_bytes)
                        for k, x in list(engine._decode_exe.items())
                        + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

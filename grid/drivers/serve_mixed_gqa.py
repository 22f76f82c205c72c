"""Serves the decoder whose QUERY heads differ by layer type over the same
KV heads (full layers beside 512-row window layers, a head-wise gate, a
half share of the routed experts) through the same ``ServingEngine`` and
the same open-loop harness as ``drivers/serve.py``: ``warm`` and ``drive``
are that module's, ``plan`` is ``drivers/serve_moe.py``'s (every ``--seed``
offers the same lengths in the same order at the same instants; the seed
draws token ids, from the vocabulary SLICE the configuration holds, and the
weights). Its own are ``build`` (the model as one chip's share of the stated
deployment, its two cache groups' pools), ``check`` (the float32 reference
of THIS architecture given the same share, one of the two sampled requests
past ``LONG_CONTEXT`` rows; the paged kernel armed for both groups' query
heads a KV head; the generator's lateness counted from the end of the
engine cycle in progress, since a cycle here may hold a prefill of half a
second), and a sample a cycle of what the counters read, for the readers of
``grid/readers/mixed_gqa.py`` and, unchanged, ``grid/readers/moe.py``'s
page shares. ``record["kind"]`` stays ``"serve"``: the window's readers
apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

from .. import generate, runtime
from ..reference import laguna as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_moe import plan

LONG_CONTEXT = 4096    # one of the two compared requests is past this


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config.
    The router keeps its published width (``published.num_experts``);
    ``num_experts`` counts the experts held here, ``experts_held`` names
    them. The per-layer lists are kept whole as published; the layers held
    are their first ``num_hidden_layers``."""
    from paddle_tpu.models.laguna import LagunaConfig

    n = int(config["num_hidden_layers"])
    held = [int(e) for e in config["experts_held"]]
    if len(held) != int(config["num_experts"]):
        raise ValueError("experts_held names %d experts, num_experts says "
                         "%d are held" % (len(held), config["num_experts"]))
    stated = {"gating": "per-head", "decoder_sparse_step": 1,
              "norm_topk_prob": True, "moe_router_logit_softcapping": 0,
              "moe_apply_router_weight_on_input": False,
              "attention_bias": False, "tie_word_embeddings": False}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    if differs or set(config["gating_types"][:n]) != {"per_head"}:
        raise ValueError("the served layer is written for %s; the "
                         "configuration says %s" % (stated, differs))
    return LagunaConfig(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads_per_layer"][:n],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        layer_types=config["layer_types"][:n],
        window=config["sliding_window"], rope=config["rope_parameters"],
        d_dense=config["intermediate_size"],
        dense_layers=[i for i in config["mlp_only_layers"] if i < n],
        n_expert=config["published"]["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        rms_eps=config["rms_norm_eps"], max_seq=config["model"]["max_seq"],
        dtype=config["model"]["dtype"], experts_held=held)


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.laguna import LagunaLM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = LagunaLM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``. The
    first three fields are what ``readers/moe.py``'s page-share and
    admission readers take."""

    end: float
    pages_used: Dict[str, int]     # by cache group
    blocked: float                 # serving/admission_blocked_on_pages
    touched_sum: float             # serving/moe_experts_touched, sum
    touched_n: int                 # ... and observations (a layer a step)
    held_pairs_sum: float          # serving/moe_held_pairs, sum
    rows_global_sum: float         # serving/attn_rows_read.global, sum
    rows_window_sum: float         # serving/attn_rows_read.window, sum
    rows_n: int                    # ... and observations (one a step)


def sampling(engine, samples: List[Sample]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper).
    A program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    glob, win = sm.attn_rows_read("global"), sm.attn_rows_read("window")

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(),
            {p.name: p.num_used for p in engine.pools},
            sm.ADMISSION_BLOCKED.value, sm.MOE_EXPERTS_TOUCHED.sum,
            sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_HELD_PAIRS.sum,
            glob.sum, win.sum, glob.count))
        return done

    engine.step = stepped


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the held experts a step touched, the pairs it sent them
    and the rows each cache group read."""
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
        a, b = inside[0], inside[-1]
        n = max(b.touched_n - a.touched_n, 1)
        steps = max(b.rows_n - a.rows_n, 1)
        note["held_experts_touched_mean"] = (b.touched_sum
                                             - a.touched_sum) / n
        note["held_pairs_mean"] = (b.held_pairs_sum - a.held_pairs_sum) / n
        note["rows_read_global_mean"] = (b.rows_global_sum
                                         - a.rows_global_sum) / steps
        note["rows_read_window_mean"] = (b.rows_window_sum
                                         - a.rows_window_sum) / steps
        note["blocked_cycles"] = b.blocked - a.blocked
    return note


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window: ``drivers/serve.py``'s rule
    (the served token's rank below the float32 reference's best logit, in
    row standard deviations, over two finished requests of the timed run),
    with ONE of the two a request whose context passed ``LONG_CONTEXT``,
    the reference given the same share (the experts held, the vocabulary
    slice), and two limits: on a request's worst row and on the mean over
    its rows (the reference says why)."""
    marks = record["marks"]
    in_window = [tr for tr in record["tracked"]
                 if marks["open"] <= tr.due < marks["close"]]
    failed = [tr for tr in in_window
              if tr.refused or tr.req.state in ("failed", "timeout",
                                                "rejected")]
    # the window is over capacity by design: requests still queued at its
    # end have not failed, and those finished may have been due before it
    finished = [tr for tr in record["tracked"]
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
        problems.append("page accounting does not balance in every group")
    ring = engine.cache_ops.group_pages_per_slot(1)
    most = max((s.pages_used["window"] for s in record["samples"]),
               default=0)
    if most > ring * engine.cfg.slots:
        problems.append("the window group held %d pages, over %d a slot"
                        % (most, ring))
    kernel, why = engine.decode_kernel_info()
    if kernel != "paged":
        problems.append("the paged kernel is not armed for query heads a "
                        "KV head %s (%s): decode attention ran by the "
                        "gather" % (engine.cache_ops.q_per_kv, why))
    if compiles_in_window:
        problems.append("%d compilations inside the window"
                        % compiles_in_window)
    if late_p50 > decode_ms:
        problems.append("the generator ran late by %.1f ms at the median "
                        "beyond the engine cycle in progress, more than one "
                        "decode dispatch (%.1f ms)" % (late_p50, decode_ms))

    def total(tr):
        return tr.req.prompt_len + len(tr.req.tokens_out)

    past = [tr for tr in finished if total(tr) > LONG_CONTEXT]
    rest = [tr for tr in finished if total(tr) <= LONG_CONTEXT]
    sample = (past[:1] + rest + past[1:])[:2]
    if not past and int(job.traffic["prompt_len"]["hi"]) > LONG_CONTEXT:
        problems.append("no finished request's context passed %d"
                        % LONG_CONTEXT)
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    margins = []
    for tr in sample:
        gaps = reference.row_gaps(engine.params, job.config,
                                  tr.planned.prompt, tr.req.tokens_out)
        worst, mean = float(gaps.max()), float(gaps.mean())
        margins.append({"context": total(tr), "margin": worst,
                        "mean_gap": mean})
        if not worst <= reference.LOGIT_MARGIN:
            problems.append(
                "a served token ranks %.4f below the float32 reference's "
                "argmax (margin %.4f; context %d)"
                % (worst, reference.LOGIT_MARGIN, total(tr)))
        if not mean <= reference.MEAN_GAP_LIMIT:
            problems.append(
                "the served tokens rank %.4f below the float32 reference's "
                "argmax at the mean over a request's rows (limit %.4f; "
                "context %d)" % (mean, reference.MEAN_GAP_LIMIT, total(tr)))
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
                 "q_per_kv": engine.cache_ops.q_per_kv,
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": engine.cache_ops.cache_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0})
        samples: List[Sample] = []
        sampling(engine, samples)
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
        record["q_per_kv"] = dict(engine.cache_ops.q_per_kv)
        record["pools"] = {p.name: p.num_pages for p in engine.pools}
        record["pool_rows"] = engine.pools[0].num_pages * engine.cfg.page_size
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

"""Serves the hybrid decoder (linear-attention KDA layers beside a latent
MLA layer, a quarter share of group-limited routed experts) through the
same ``ServingEngine`` and the same open-loop harness as
``drivers/serve.py``: ``warm`` and ``drive`` are that module's, ``plan`` is
``drivers/serve_moe.py``'s (every ``--seed`` offers the same lengths in
the same order at the same instants; the seed draws token ids, from the
vocabulary SLICE the configuration holds, and the weights). Its own are
``build`` (the model as one chip's share of the stated deployment: its
latent pool and its per-slot states) and ``check`` (the float32 reference
of THIS architecture given the same share, one of the two sampled requests
the longest context that finished, past ``LONG_CONTEXT`` where the traffic
offers one; both kernels armed; the generator's lateness counted from the
end of the engine cycle in progress, since a cycle here may hold a prefill
of some hundred milliseconds), and a sample a cycle of what the counters
read, for the readers of ``grid/readers/hybrid.py`` and, unchanged, two
of ``grid/readers/mla.py``. ``record["kind"]`` stays ``"serve"``: the
window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

from .. import generate, runtime
from ..reference import ling3_flash as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_moe import plan

LONG_CONTEXT = 8000    # one of the two compared requests is past this
MIN_TOKENS = 512       # served tokens the two hold between them, at least


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config.
    The router keeps its published width (``published.num_experts``) and
    its groups; ``num_experts`` counts the experts held here,
    ``experts_held`` names them; ``layer_types`` and ``dense_layers_held``
    say what each layer HELD is."""
    from paddle_tpu.models.ling3_flash import Ling3FlashConfig

    n = int(config["num_hidden_layers"])
    held = [int(e) for e in config["experts_held"]]
    if len(held) != int(config["num_experts"]):
        raise ValueError("experts_held names %d experts, num_experts says "
                         "%d are held" % (len(held), config["num_experts"]))
    stated = {"score_function": "sigmoid", "norm_topk_prob": True,
              "q_lora_rank": None, "use_mla_nope": False, "linear_silu": True,
              "no_kda_lora": True, "kda_safe_gate": True, "use_qk_norm": True,
              "group_norm_size": 1, "scale_router_input": False,
              "gated_attention_proj_granularity_type": "head_wise",
              "moe_router_enable_expert_bias": True, "value_norm": False,
              "up_proj_norm": False, "use_nGPT": False}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    clamped = [i for i in config["published_layer_indices"]
               if config["expert_swiglu_limit_list"][i]
               or config["share_expert_swiglu_limit_list"][i]]
    if differs or clamped or len(config["layer_types"]) != n \
            or config["moe_shared_expert_intermediate_size"] \
            != config["moe_intermediate_size"]:
        raise ValueError("the served layer is written for %s, no SwiGLU "
                         "clamp and a shared expert of the experts' width; "
                         "the configuration says %s, clamps layers %s"
                         % (stated, differs, clamped))
    return Ling3FlashConfig(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        d_state=config["head_dim"], layer_types=config["layer_types"],
        kv_rank=config["kv_lora_rank"], d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"], d_v=config["v_head_dim"],
        d_dense=config["intermediate_size"],
        dense_layers=config["dense_layers_held"],
        n_expert=config["published"]["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scale=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"],
        conv_taps=config["short_conv_kernel_size"],
        lower_bound=config["kda_lower_bound"],
        rms_eps=config["rms_norm_eps"], max_seq=config["model"]["max_seq"],
        dtype=config["model"]["dtype"], experts_held=held,
        bias_std=config["model"]["selection_bias_std"],
        half_life=config["model"]["half_life_tokens"])


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.ling3_flash import Ling3FlashLM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = Ling3FlashLM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``. The
    first four fields are what ``readers/mla.py``'s page-share and
    touched-experts readers take."""

    end: float
    pages_used: int        # serving/pages_used.latent
    touched_sum: float     # serving/moe_experts_touched, sum
    touched_n: int         # ... and observations (an expert layer a step)
    held_pairs_sum: float  # serving/moe_held_pairs, sum
    stepped_sum: float     # serving/state_slots_stepped, sum
    stepped_n: int         # ... and observations (one a step)
    rows_latent_sum: float  # serving/attn_rows_read.latent, sum


def sampling(engine, samples: List[Sample]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper). A
    program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    used, rows = sm.pages_used("latent"), sm.attn_rows_read("latent")

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(), int(used.value), sm.MOE_EXPERTS_TOUCHED.sum,
            sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_HELD_PAIRS.sum,
            sm.STATE_SLOTS_STEPPED.sum, sm.STATE_SLOTS_STEPPED.count,
            rows.sum))
        return done

    engine.step = stepped


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the held experts a step touched, the pairs it sent them,
    the slots whose states it advanced and the latent rows it read."""
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
        steps = max(b.stepped_n - a.stepped_n, 1)
        note["held_experts_touched_mean"] = (b.touched_sum
                                             - a.touched_sum) / n
        note["held_pairs_mean"] = (b.held_pairs_sum - a.held_pairs_sum) / n
        note["state_slots_stepped_mean"] = (b.stepped_sum
                                            - a.stepped_sum) / steps
        note["rows_read_latent_mean"] = (b.rows_latent_sum
                                         - a.rows_latent_sum) / steps
    return note


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window, as
    ``drivers/serve_mla.check`` decides it, from what the timed run
    served: two finished requests, one of them the LONGEST context that
    finished (past ``LONG_CONTEXT`` where the traffic offers such a one),
    ``MIN_TOKENS`` served tokens between them at least, against the
    float32 reference given the same share and computed in blocks; two
    limits, on a request's worst row and on the mean over its rows (the
    reference says why)."""
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
        problems.append("page accounting does not balance")
    for what, (kernel, why) in (
            ("latent", engine.decode_kernel_info()),
            ("state", engine.cache_ops.state_kernel_mode())):
        if kernel in (None, "gather") and str(why).startswith("gate:"):
            problems.append("the %s kernel refused the cache's geometry "
                            "(%s): that layer's decode ran in plain XLA"
                            % (what, why))
    if compiles_in_window:
        problems.append("%d compilations inside the window"
                        % compiles_in_window)
    if late_p50 > decode_ms:
        problems.append("the generator ran late by %.1f ms at the median "
                        "beyond the engine cycle in progress, more than one "
                        "decode dispatch (%.1f ms)" % (late_p50, decode_ms))

    def total(tr):
        return tr.req.prompt_len + len(tr.req.tokens_out)

    by_length = sorted(finished, key=total, reverse=True)
    sample = by_length[:1] + by_length[1:][-1:]     # the longest, the shortest
    if by_length and total(by_length[0]) <= LONG_CONTEXT \
            and int(job.traffic["prompt_len"]["hi"]) > LONG_CONTEXT:
        problems.append("no finished request's context passed %d"
                        % LONG_CONTEXT)
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    elif sum(len(tr.req.tokens_out) for tr in sample) < MIN_TOKENS:
        problems.append("the two compared requests hold under %d served "
                        "tokens" % MIN_TOKENS)
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
        ops = engine.cache_ops
        job.log({"phase": "built", "build_s": time.perf_counter() - t0,
                 "decode_kernel": list(engine.decode_kernel_info()),
                 "state_kernel": list(ops.state_kernel_mode()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": ops.cache_bytes(engine._cache),
                 "state_bytes": ops.state_bytes(engine._cache)})
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
        record["pools"] = {p.name: p.num_pages for p in engine.pools}
        record["pool_rows"] = engine.pools[0].num_pages * engine.cfg.page_size
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

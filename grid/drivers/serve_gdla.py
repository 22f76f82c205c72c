"""Serves the grouped-differential latent decoder (Motif-3-Beta: 80 query
heads of which 16 are noise heads over latent rows, rings of the last 128
rows on three layers in four beside one page pool, four residual streams,
a sixteenth share of PolyNorm experts) through the same ``ServingEngine``
and the same open-loop harness as ``drivers/serve.py``: ``warm`` and
``drive`` are that module's, ``plan`` is ``drivers/serve_moe.py``'s (every
``--seed`` offers the same lengths in the same order at the same instants;
the seed draws token ids, from the vocabulary SLICE the configuration
holds, and the weights). Its own are ``build`` (the model as one chip's
share of the stated deployment: its two latent groups) and ``check`` (as
``drivers/serve_hybrid.check`` decides ``correct``: the float32 reference
of THIS architecture given the same share, one of the two sampled requests
the longest context that finished, past ``LONG_CONTEXT`` where the traffic
offers one; the kernel armed; the generator's lateness counted from the end
of the engine cycle in progress; and a THIRD number, which compares values
and not ranks: the norm of the residual streams' sum over the first
``reference.STREAM_ROWS`` positions of each compared request, the
program's own prefill forward against the reference's, which is what sees
the residual maps at a lower precision than stated), and a sample a cycle
of what the counters read, for the readers of ``grid/readers/gdla.py`` and,
unchanged, one of ``grid/readers/mla.py``; ``record["residual_ops"]`` is
the decode executable's own account of which of its instructions run under
``residual/mhc``. ``record["kind"]`` stays ``"serve"``: the window's
readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

from .. import generate, runtime
from ..readers.gdla import scoped_instructions
from ..reference import motif3 as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_moe import plan

LONG_CONTEXT = 8000    # one of the two compared requests is past this
MIN_TOKENS = 512       # served tokens the two hold between them, at least


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config.
    The router keeps its published width (``published.num_experts``);
    ``num_experts`` counts the experts held here, ``experts_held`` names
    them; ``layer_types`` and ``dense_layers_held`` say what each layer
    HELD is."""
    from paddle_tpu.models.motif3 import Motif3Config

    n = int(config["num_hidden_layers"])
    held = [int(e) for e in config["experts_held"]]
    if len(held) != int(config["num_experts"]):
        raise ValueError("experts_held names %d experts, num_experts says "
                         "%d are held" % (len(held), config["num_experts"]))
    stated = {"attention_cls": "gdla", "diff_v2": True,
              "elementwise_attn_output_gate": True,
              "headwise_attn_output_gate": False, "hidden_act": "poly_norm",
              "mhc_enabled": True, "score_func": "sigmoid",
              "route_norm": True, "score_before_experts": False,
              "num_shared_experts": 1, "interleave_moe_layer_step": 1,
              "k_ratio": 1, "sliding_window_pattern": "interleave",
              "polynorm_output_scale_per_layer": {}}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    heads, n_kv = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    if differs or len(config["layer_types"]) != n \
            or int(config["num_noise_heads"]) != n_kv \
            or config["rope_scaling"]["apply_yarn_scaling"]:
        raise ValueError("the served layer is written for %s, one noise "
                         "head a KV head and no YaRN temperature; the "
                         "configuration says %s, %d noise heads over %d KV "
                         "heads" % (stated, differs,
                                    config["num_noise_heads"], n_kv))
    rope = int(config["qk_rope_head_dim"])
    return Motif3Config(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"], n_head=heads, n_kv_head=n_kv,
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        d_nope=int(config["head_dim"]) - rope, d_rope=rope,
        d_v=config["v_head_dim"], layer_types=config["layer_types"],
        window=config["sliding_window"], d_dense=config["intermediate_size"],
        dense_layers=config["dense_layers_held"],
        n_expert=config["published"]["num_experts"],
        top_k=config["experts_top_k"],
        d_expert=config["moe_intermediate_size"],
        routed_scale=config["route_scale"], rope_theta=config["rope_theta"],
        window_rope_theta=config["swa_rope_theta"],
        rope_scaling=config["rope_scaling"],
        n_stream=config["mhc_expansion_rate"],
        sinkhorn_iters=config["mhc_sinkhorn_iters"],
        hidden_clamp=config["hidden_clamp"],
        act_scale=config["polynorm_output_scale"],
        act_bias_clamp=config["polynorm_bias_clamp"],
        rms_eps=config["rms_norm_eps"], max_seq=config["model"]["max_seq"],
        dtype=config["model"]["dtype"], experts_held=held)


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.motif3 import Motif3LM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = Motif3LM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``. The
    first four fields are what ``readers/mla.py``'s touched-experts reader
    takes."""

    end: float
    pages_used: int        # serving/pages_used.latent_full
    touched_sum: float     # serving/moe_experts_touched, sum
    touched_n: int         # ... and observations (an expert layer a step)
    held_pairs_sum: float  # serving/moe_held_pairs, sum
    rows_full_sum: float   # serving/attn_rows_read.latent_full, sum
    rows_ring_sum: float   # serving/attn_rows_read.latent_ring, sum
    rows_n: int            # ... and observations (one a step)


def sampling(engine, samples: List[Sample]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper). A
    program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    used = sm.pages_used("latent_full")
    full = sm.attn_rows_read("latent_full")
    ring = sm.attn_rows_read("latent_ring")

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(), int(used.value), sm.MOE_EXPERTS_TOUCHED.sum,
            sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_HELD_PAIRS.sum, full.sum,
            ring.sum, full.count))
        return done

    engine.step = stepped


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the held experts a step touched, the pairs it sent them
    and the rows a full and a window layer read."""
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
        note["rows_read_full_mean"] = (b.rows_full_sum
                                       - a.rows_full_sum) / steps
        note["rows_read_ring_mean"] = (b.rows_ring_sum
                                       - a.rows_ring_sum) / steps
    return note


def residual_ops(engine) -> List[str]:
    """The instructions of the decode executable that run under
    ``residual/mhc``, from the executable's own text (an executable loaded
    from the compile cache gives it too)."""
    names = set()
    for exe in engine._decode_exe.values():
        names.update(scoped_instructions(exe.as_text(), "residual/mhc"))
    return sorted(names)


def served_stream_sums(engine):
    """``tokens [n] -> [n, d]``: the residual streams' sum before the
    final norm as the PROGRAM computes it, by the function its prefill
    executable runs (``models/motif3.prefill_forward``) with the engine's
    own parameters and configuration, compiled here for ``n`` rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.motif3 import prefill_forward

    cfg = engine.model.cfg
    forward = jax.jit(lambda params, toks, n: prefill_forward(
        params, cfg, toks, n)[0][0])

    def sums(tokens):
        toks = jnp.asarray(tokens, jnp.int32)[None]
        return forward(engine.params, toks,
                       jnp.asarray([toks.shape[1]], jnp.int32))

    return sums


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window, as
    ``drivers/serve_mla.check`` decides it, from what the timed run
    served: two finished requests, one of them the LONGEST context that
    finished (past ``LONG_CONTEXT`` where the traffic offers such a one),
    ``MIN_TOKENS`` served tokens between them at least, against the
    float32 reference given the same share and computed in blocks; three
    limits, on a request's worst row, on the mean over its rows and on the
    norm of the streams' sum over its first positions (the reference says
    why each)."""
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
    kernel, why = engine.decode_kernel_info()
    if kernel == "gather" and str(why).startswith("gate:"):
        problems.append("the latent kernel refused the cache's geometry "
                        "(%s): decode attention ran by the gather" % why)
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
    served_sums = served_stream_sums(engine)
    for tr in sample:
        rows = min(reference.STREAM_ROWS, len(tr.planned.prompt))
        gaps, sums = reference.teacher_forced(
            engine.params, job.config, tr.planned.prompt, tr.req.tokens_out,
            sum_rows=rows)
        norm_gap = reference.stream_norm_gap(
            served_sums(tr.planned.prompt[:rows]), sums)
        worst, mean = float(gaps.max()), float(gaps.mean())
        margins.append({"context": total(tr), "margin": worst,
                        "mean_gap": mean, "stream_norm_gap": norm_gap})
        if not norm_gap <= reference.STREAM_NORM_LIMIT:
            problems.append(
                "the norm of the residual streams' sum departs from the "
                "float32 reference's by %.5f at the median over the first "
                "%d positions (limit %.5f; context %d)"
                % (norm_gap, rows, reference.STREAM_NORM_LIMIT, total(tr)))
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
    beside = compared(len(failed), len(short), compiles_in_window, late_p50,
                      decode_ms, margins, reference)
    if margins:
        beside["stream_norm_gap"] = [
            max(m["stream_norm_gap"] for m in margins),
            reference.STREAM_NORM_LIMIT]
    return {"correct": not problems, "problems": problems,
            "attempted": len(in_window), "failed": len(failed),
            "generator_late_ms": {"p50": late_p50,
                                  "max": late[-1] * 1e3 if late else 0.0},
            "reference_margins": margins, "compared": beside}


def run(job) -> Dict[str, Any]:
    traffic = job.traffic
    vocab = int(job.config["vocab_size"])
    t0 = time.perf_counter()
    engine = build(job)
    with engine:
        ops = engine.cache_ops
        job.log({"phase": "built", "build_s": time.perf_counter() - t0,
                 "decode_kernel": list(engine.decode_kernel_info()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": ops.cache_bytes(engine._cache),
                 "ring_bytes": ops.ring_bytes(engine._cache)})
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
        record["residual_ops"] = residual_ops(engine)
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

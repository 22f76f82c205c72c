"""Serves the latent decoder whose EVERY layer chooses single rows
(DeepSeek-V3.2: a lightning indexer of 64 heads over one index key a row,
the 2,048 rows of highest score read of a context, a group-limited router,
a sixteenth share of routed experts) through the same ``ServingEngine`` and
the same open-loop harness as ``drivers/serve.py``: ``warm`` and ``drive``
are that module's, ``plan`` is ``drivers/serve_moe.py``'s (every ``--seed``
offers the same lengths in the same order at the same instants; the seed
draws token ids, from the vocabulary SLICE the configuration holds, and the
weights), ``release_pools`` ``drivers/serve_ssm.py``'s. Its own are
``build`` (the model as one chip's share of the stated deployment: its
latent pool and the pool of index keys beside it) and ``check`` (as
``drivers/serve_dsa.check`` decides ``correct``: the float32 reference of
THIS architecture given the same share; the kernels armed; the generator's
lateness; and the SELECTION itself: every decode step returns the rows slot
0's first layer chose among its small ``stats``
(``engine.last_decode_stats``), and of the requests that lived in slot 0
the one with the most decode steps is compared with the reference's own
choice at the same positions, by overlap and by score mass, and once more
with the served choice FORCED on the reference; the latent rows and index
keys the cache KEEPS of slot 0's resident are compared as values), and a
sample a cycle of what the counters read, for the readers of
``grid/readers/rowdsa.py`` and, under the field names they read,
``readers/dsa.py`` and ``readers/mla.py``. The served model fills the chip,
so the pools are released before the reference runs.
``record["scoped_ops"]`` is the decode executable's own account of which of
its instructions run under each of this model's scopes. ``record["kind"]``
stays ``"serve"``: the window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

from .. import generate, runtime
from ..readers.gdla import scoped_instructions
from ..reference import deepseek_v32 as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_dsa import _probe_of
from .serve_moe import plan
from .serve_ssm import release_pools

LONG_CONTEXT = 8000    # one of the compared requests is past this
MIN_TOKENS = 512       # served tokens the compared hold between them
MIN_PROBED = 256       # decode steps whose selection is compared, at least
SCOPES = ("attn/dsa_index", "attn/dsa_select", "attn/dsa_sparse",
          "moe/router")


def model_config(config: Dict[str, Any], **control):
    """The configuration file's published keys as the program's config.
    The router keeps its published width (``published.n_routed_experts``);
    ``n_routed_experts`` counts the experts held here, ``experts_held``
    names them; ``dense_layers_held`` says which of the layers HELD are
    dense (the leading ones). ``control``: one thing wrong, for
    ``benchmarks/control_deepseek_v32.py``."""
    from paddle_tpu.models.deepseek_v32 import DeepSeekV32Config

    n = int(config["num_hidden_layers"])
    held = [int(e) for e in config["experts_held"]]
    if len(held) != int(config["n_routed_experts"]):
        raise ValueError("experts_held names %d experts, n_routed_experts "
                         "says %d are held" % (len(held),
                                               config["n_routed_experts"]))
    stated = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "norm_topk_prob": True, "n_shared_experts": 1,
              "hidden_act": "silu", "attention_bias": False,
              "moe_layer_freq": 1}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    dense = [int(i) for i in config["dense_layers_held"]]
    if differs or dense != list(range(len(dense))) \
            or int(config["num_key_value_heads"]) \
            != int(config["num_attention_heads"]):
        raise ValueError("the served layers are written for %s, leading "
                         "dense layers and as many KV heads as heads; the "
                         "configuration says %s, dense layers %s"
                         % (stated, differs, dense))
    m = config["model"]
    kw = dict(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"], index_topk=config["index_topk"],
        d_dense=config["intermediate_size"], n_dense=len(dense),
        n_expert=config["published"]["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scale=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"], rope_scaling=config["rope_scaling"],
        rms_eps=config["rms_norm_eps"], max_seq=m["max_seq"],
        dtype=m["dtype"], experts_held=held,
        bias_std=m["selection_bias_std"], score_std=m["score_std"])
    kw.update(control)
    return DeepSeekV32Config(**kw)


def build(job, **control) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.deepseek_v32 import DeepSeekV32LM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config, **control)
    model = DeepSeekV32LM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``, under
    the field names ``readers/mla.py`` (the first four) and
    ``readers/dsa.py`` (``stepped_n``: the decode steps; the rows read and
    held) take, and this cell's own two."""

    end: float
    pages_used: int        # serving/pages_used.latent_sparse
    touched_sum: float     # serving/moe_experts_touched, sum
    touched_n: int         # ... and observations (an expert layer a step)
    held_pairs_sum: float  # serving/moe_held_pairs, sum
    stepped_n: int         # decode steps (serving/attn_rows_read's count)
    rows_read_sum: float   # serving/attn_rows_read.latent_sparse, sum
    rows_ctx_sum: float    # serving/attn_rows_context.latent_sparse, sum
    scored_sum: float      # serving/index_rows_scored, sum
    groups_sum: float      # serving/moe_groups_kept_with_held, sum
    prefills_n: int        # serving/prefill_ms's observations


def sampling(engine, samples: List[Sample], probes: Dict[int, list]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    and every decode dispatch read leaves slot 0's selection in ``probes``,
    by the request that held the slot: ``(position, rows chosen)`` a step.
    A program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    used = sm.pages_used("latent_sparse")
    read = sm.attn_rows_read("latent_sparse")
    ctx = sm.attn_rows_context("latent_sparse")
    seen = [None]

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(), int(used.value), sm.MOE_EXPERTS_TOUCHED.sum,
            sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_HELD_PAIRS.sum, read.count,
            read.sum, ctx.sum, sm.INDEX_ROWS_SCORED.sum,
            sm.MOE_GROUPS_KEPT_WITH_HELD.sum, sm.PREFILL_MS.count))
        last = engine.last_decode_stats
        if last is not None and last is not seen[0]:
            seen[0] = last
            tenants, stats = last
            if tenants[0] is not None:
                for row in np.asarray(stats["dsa_probe"]):
                    if row[0] >= 0:
                        probes.setdefault(id(tenants[0]), []).append(
                            (int(row[0]), row[1:].copy()))
        return done

    engine.step = stepped


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the slots live as it opened, the held experts a step
    touched, the pairs it sent them, and what a layer read and scored of
    what the contexts hold."""
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
            "slots_live_at_open": cyc[0].occupancy if cyc else 0,
            "queue_at_open_and_close": ([cyc[0].queue, cyc[-1].queue]
                                        if cyc else []),
            "context_mean": sum(c.context for c in cyc) / max(len(cyc), 1)}
    if len(inside) > 1:
        a, b = inside[0], inside[-1]
        n = max(b.touched_n - a.touched_n, 1)
        steps = max(b.stepped_n - a.stepped_n, 1)
        note["held_experts_touched_mean"] = (b.touched_sum
                                             - a.touched_sum) / n
        note["held_pairs_mean"] = (b.held_pairs_sum - a.held_pairs_sum) / n
        note["groups_kept_with_held_mean"] = (b.groups_sum - a.groups_sum) / n
        note["rows_read_mean"] = (b.rows_read_sum - a.rows_read_sum) / steps
        note["rows_context_mean"] = (b.rows_ctx_sum - a.rows_ctx_sum) / steps
        note["rows_scored_mean"] = (b.scored_sum - a.scored_sum) / steps
    return note


def scoped_ops(engine) -> Dict[str, List[str]]:
    """The instructions of the decode executable that run under each of
    :data:`SCOPES`, from the executable's own text (an executable loaded
    from the compile cache gives it too)."""
    names = {scope: set() for scope in SCOPES}
    for exe in engine._decode_exe.values():
        text = exe.as_text()
        for scope in SCOPES:
            names[scope].update(scoped_instructions(text, scope))
    return {scope: sorted(found) for scope, found in names.items()}


def served_kept(engine, slot: int, n: int):
    """``(row [n, rank + rope], index keys [n, L])`` as the cache KEEPS
    them of the first ``n`` positions of the request resident in ``slot``:
    the first layer's latent rows and index keys, read through the slot's
    page table."""
    ops, cache = engine.cache_ops, engine._cache
    _, lanes, _ = ops.index
    ps = ops.page_size
    pt = np.asarray(cache["pt"][slot])
    pos = np.arange(n)
    rows = np.asarray(cache["c"][0][pt[pos // ps] * ps + pos % ps],
                      np.float32)[:, :ops.row_values]
    pages = np.arange(-(-n // ps))
    keys = np.asarray(cache["ik"][0][pt[pages]], np.float32
                      ).reshape(len(pages) * ps, lanes)
    return rows, keys[:n]


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window from what the timed run
    served, as ``drivers/serve_dsa.check`` decides it, against the float32
    reference given the same share and computed in blocks. Two requests:
    the LONGEST context that finished (past ``LONG_CONTEXT`` where the
    traffic offers such a one) and the one that lived longest in the
    probed slot (finished or not: what it was served so far), whose
    selection every decode step returned; seven limits, on a request's
    worst row and on the mean over its rows (the reference choosing its
    own rows), on the selection's overlap and score mass, on the mean over
    the probed request's rows with the served selection forced, and as
    VALUES on the latent rows and index keys the cache keeps
    (``reference`` says why each)."""
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
    ops = engine.cache_ops
    for what, (kernel, why) in (("sparse latent", ops.sparse_kernel_mode()),
                                ("index", ops.index_kernel_mode())):
        if kernel in (None, "gather") and str(why).startswith("gate:"):
            problems.append("the %s kernel refused the cache's geometry "
                            "(%s): that part of decode ran in plain XLA"
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
    if by_length and total(by_length[0]) <= LONG_CONTEXT \
            and int(job.traffic["prompt_len"]["hi"]) > LONG_CONTEXT:
        problems.append("no finished request's context passed %d"
                        % LONG_CONTEXT)
    # of the requests that lived in the probed slot, finished or still
    # running, the one whose decode steps left the most selections
    probed = max(((tr, p) for tr in record["tracked"]
                  if not tr.refused and tr.req is not None
                  for p in [_probe_of(tr, record["probes"])]
                  if p is not None), key=lambda c: len(c[1][0]),
                 default=None)
    if probed is None or len(probed[1][0]) < MIN_PROBED:
        problems.append("no request left %d decode steps' selections: "
                        "none lived in slot 0 that long" % MIN_PROBED)
        probed = None
    sample = by_length[:1]
    if probed is not None and probed[0] not in sample:
        sample.append(probed[0])
    elif len(by_length) > 1:
        sample.append(by_length[-1])    # the shortest, beside the longest
    if len(sample) < 2:
        problems.append("fewer than 2 requests to compare with the "
                        "reference")
    elif sum(len(tr.req.tokens_out) for tr in sample) < MIN_TOKENS:
        problems.append("the compared requests hold under %d served tokens"
                        % MIN_TOKENS)
    # what the cache KEEPS of the request resident in the probed slot at
    # the run's end, read before the pools are let go
    resident = engine.scheduler.slot_request(0)
    held = next((tr for tr in record["tracked"]
                 if tr.req is resident and resident is not None), None)
    n = 0 if held is None else \
        held.req.prompt_len + len(held.req.tokens_out) - 1
    got = served_kept(engine, 0, n) if n >= MIN_PROBED else None
    release_pools(engine)
    margins, selection, forced = [], None, None
    for tr in sample:
        probe = probed[1] if probed is not None and tr is probed[0] else None
        served = tr.req.tokens_out if probe is None \
            else tr.req.tokens_out[:len(probe[0]) + 1]
        out = reference.teacher_forced(
            engine.params, job.config, tr.planned.prompt, served,
            probe=probe)
        worst, mean = float(out["gaps"].max()), float(out["gaps"].mean())
        margins.append({"context": tr.req.prompt_len + len(served),
                        "margin": worst, "mean_gap": mean})
        if probe is not None:
            selection = out["selection"]
            forced = float(out["forced_gaps"][1:].mean())
            margins[-1].update(selection, forced_gap=forced, probed=True)
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
    if selection is not None:
        if not selection["overlap"] >= reference.OVERLAP_LIMIT \
                or selection["stray"] or selection["excess"]:
            problems.append(
                "the served decode steps chose %.4f of the rows the float32 "
                "reference chose at the same positions, at the mean (limit "
                "%.4f), %d rows past the position and %d more than "
                "index_topk allows"
                % (selection["overlap"], reference.OVERLAP_LIMIT,
                   selection["stray"], selection["excess"]))
        if not selection["mass"] >= reference.MASS_LIMIT:
            problems.append(
                "the served selection holds %.5f of the score mass of the "
                "reference's own, at the mean (limit %.5f)"
                % (selection["mass"], reference.MASS_LIMIT))
        if not forced <= reference.FORCED_GAP_LIMIT:
            problems.append(
                "with the served selection forced on the reference the "
                "served tokens rank %.4f below its argmax at the mean "
                "(limit %.4f)" % (forced, reference.FORCED_GAP_LIMIT))
        beside["selection_overlap"] = [selection["overlap"],
                                       reference.OVERLAP_LIMIT]
        beside["selection_mass"] = [selection["mass"], reference.MASS_LIMIT]
        beside["forced_gap"] = [forced, reference.FORCED_GAP_LIMIT]
    if got is None:
        problems.append("no request is resident in slot 0 at the run's end "
                        "with %d positions' rows to compare" % MIN_PROBED)
    else:
        tokens = (list(held.planned.prompt) + list(held.req.tokens_out))[:n]
        want_rows, want_keys = reference.kept_rows(engine.params, job.config,
                                                   tokens)
        row_gap = reference.relative_gap(got[0], want_rows)
        key_gap = reference.relative_gap(got[1], want_keys)
        for what, gap, limit in (
                ("latent rows", row_gap, reference.ROW_GAP_LIMIT),
                ("index keys", key_gap, reference.KEY_GAP_LIMIT)):
            if not gap <= limit:
                problems.append(
                    "the %s the cache keeps depart from the float32 "
                    "reference's by %.4f of their length at the median "
                    "(limit %.4f; %d positions)" % (what, gap, limit, n))
        beside["row_gap"] = [row_gap, reference.ROW_GAP_LIMIT]
        beside["key_gap"] = [key_gap, reference.KEY_GAP_LIMIT]
    return {"correct": not problems, "problems": problems,
            "attempted": len(in_window), "failed": len(failed),
            "generator_late_ms": {"p50": late_p50,
                                  "max": late[-1] * 1e3 if late else 0.0},
            "reference_margins": margins, "compared": beside}


def run(job, **control) -> Dict[str, Any]:
    traffic = job.traffic
    vocab = int(job.config["vocab_size"])
    t0 = time.perf_counter()
    engine = build(job, **control)
    with engine:
        ops = engine.cache_ops
        job.log({"phase": "built", "build_s": time.perf_counter() - t0,
                 "sparse_kernel": list(ops.sparse_kernel_mode()),
                 "index_kernel": list(ops.index_kernel_mode()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": ops.cache_bytes(engine._cache),
                 "index_bytes": ops.index_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0})
        samples: List[Sample] = []
        probes: Dict[int, list] = {}
        sampling(engine, samples, probes)
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        planned = plan(traffic, vocab, job.seed, job.seconds, tail_s)
        with runtime.stopping(job.profiler):
            record = drive(engine, planned, job.seconds,
                           float(traffic["preroll_s"]), tail_s,
                           job.profiler, job.log, job.meter)
        marks = record["marks"]
        record["samples"] = samples
        record["probes"] = probes
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
        record["scoped_ops"] = scoped_ops(engine)
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

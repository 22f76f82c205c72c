"""Serves the hybrid whose layers are ONE part each (Nemotron-3-Nano: a
Mamba-2 mixer of 64-lane heads, an ungated relu^2 expert layer or a 32/2
grouped-query layer; nine of 52 layers as one chip of an EP2 pair) through
the same ``ServingEngine`` and the same open-loop harness as
``drivers/serve.py``: ``drive`` is that module's (``warm`` its rule with
each bucket prefilled once), ``plan`` is ``drivers/serve_moe.py``'s (every ``--seed`` offers the same lengths in the
same order at the same instants; the seed draws token ids, from the
vocabulary SLICE the configuration holds, and the weights). Its own are
``build`` (the model as one chip's share of the stated deployment, the ONE
attention layer's page pool and the four ``M`` layers' per-slot states) and
``check`` (as ``drivers/serve_ssm.check`` decides ``correct``: two finished
requests, one the longest context that finished, prefill and then every
decoded position THROUGH the pool and the states, against the float32
reference's one full forward over the same tokens given the same share;
the states resident slots KEEP against the reference's token-by-token
recurrence; the paged kernel and the state kernel armed, the experts'
decode pass by the stream kernel; the generator's lateness counted from
the end of the engine cycle in progress), and a sample a cycle of what the
counters read, for the readers of ``grid/readers/nemotron.py`` and,
unchanged, two of ``grid/readers/moe.py``. The pool and the states are
released before the reference runs. ``record["kind"]`` stays ``"serve"``:
the window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

from .. import generate, runtime
from ..readers.gdla import scoped_instructions
from ..readers.nemotron import DECODE_MODULE, PREFILL_MODULE, SCOPES
from ..reference import nemotron3 as reference
from .serve import compared, drive, harness_lateness
from .serve_moe import plan

LONG_CONTEXT = 4000    # one of the two compared requests is past this
MIN_TOKENS = 256       # served tokens the two hold between them, at least
MIN_STATE_STEPS = 128  # decode steps behind a state that is compared
STATE_SAMPLES = 5      # resident requests whose kept states are compared


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config.
    The router keeps its published width (``published.n_routed_experts``);
    ``n_routed_experts`` counts the experts held here, ``experts_held``
    names them. ``hybrid_override_pattern`` is kept whole as published; the
    layers held are its first ``num_hidden_layers`` letters."""
    from paddle_tpu.models.nemotron3 import Nemotron3Config

    stated = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
              "mamba_hidden_act": "silu", "attention_bias": False,
              "mamba_proj_bias": False, "mlp_bias": False,
              "use_bias": False, "use_conv_bias": True,
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "n_shared_experts": 1, "tie_word_embeddings": False,
              "residual_in_fp32": False, "sliding_window": None}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    heads, d_head = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    held = [int(e) for e in config["experts_held"]]
    if differs:
        raise ValueError("the served layers are written for %s; the "
                         "configuration says %s" % (stated, differs))
    if len(held) != int(config["n_routed_experts"]):
        raise ValueError("experts_held names %d experts, n_routed_experts "
                         "says %d are held"
                         % (len(held), config["n_routed_experts"]))
    m = config["model"]
    return Nemotron3Config(
        vocab_size=config["vocab_size"],
        pattern=reference.pattern(config), d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        ssm_heads=heads, ssm_head_dim=d_head, ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"],
        n_expert=reference.n_experts(config),
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        routed_scale=config["routed_scaling_factor"], experts_held=held,
        conv_taps=config["conv_kernel"], chunk=config["chunk_size"],
        rms_eps=config["layer_norm_epsilon"], max_seq=m["max_seq"],
        dtype=m["dtype"], seed_rms=m["seed_rms"], dt_range=m["dt_range"],
        a_range=m["a_range"])


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.nemotron3 import Nemotron3LM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = Nemotron3LM(mcfg, params=init_params(
        mcfg, generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


def warm(engine, vocab: int) -> None:
    """Every executable the traffic will use and every slot once, as
    ``drivers/serve.warm``, but each bucket's prefill ONCE and the other
    slots through the smallest: 128 slots of 2k-8k-row prompts would be
    100 s of set-up that warms nothing the first three did not."""
    engine.warmup()
    buckets = sorted(engine.cfg.prompt_buckets)
    for i in range(engine.cfg.slots):
        rows = buckets[i] if i < len(buckets) else buckets[0]
        engine.submit([(7 * i + j) % vocab for j in range(rows)], 3)
    engine.run()


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``. The
    first three fields are what ``readers/moe.py``'s page-share and
    blocked-admission readers take."""

    end: float
    pages_used: Dict[str, int]   # by paged cache group
    blocked: float               # serving/admission_blocked_on_pages
    stepped_sum: float           # serving/state_slots_stepped, sum
    stepped_n: int               # ... and observations (one a step)
    rows_global_sum: float       # serving/attn_rows_read.global, sum
    touched_sum: float           # serving/moe_experts_touched, sum
    touched_n: int               # ... and observations (a layer a step)
    held_pairs_sum: float        # serving/moe_held_pairs, sum


def sampling(engine, samples: List[Sample]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper). A
    program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    rows = sm.attn_rows_read("global")

    def stepped():
        done = step()
        samples.append(Sample(
            time.perf_counter(), {p.name: p.num_used for p in engine.pools},
            sm.ADMISSION_BLOCKED.value, sm.STATE_SLOTS_STEPPED.sum,
            sm.STATE_SLOTS_STEPPED.count, rows.sum,
            sm.MOE_EXPERTS_TOUCHED.sum, sm.MOE_EXPERTS_TOUCHED.count,
            sm.MOE_HELD_PAIRS.sum))
        return done

    engine.step = stepped


FORM_COUNTERS = ("ssd/scan_calls.kernel", "ssd/scan_calls.blocked",
                 "ssd/step_calls.kernel", "ssd/step_calls.xla",
                 "moe/pass_form.stream", "moe/pass_form.grouped")


def traced_forms() -> Dict[str, int]:
    """The trace-time counters of the forms the executables took: the
    prefill's chunk scan, the decode's state step and the experts' passes
    (once a call of a traced program: a silent fall to an XLA form shows
    here)."""
    from paddle_tpu.monitor import metrics

    return {name: int(metrics.counter(name).value) for name in FORM_COUNTERS}


def scoped_ops(engine) -> Dict[str, Dict[str, List[str]]]:
    """The instructions of the decode and of the prefill executables that
    run under each of ``readers/ssm.SCOPES``, from the executables' own
    text (one loaded from the compile cache gives it too), a module: what
    the time-share readers tell an event by. The buckets' prefill
    executables share one module name; their names are pooled."""
    out = {}
    for module, exes in ((DECODE_MODULE, engine._decode_exe),
                         (PREFILL_MODULE, engine._prefill_exe)):
        names = {scope: set() for scope in SCOPES}
        for exe in exes.values():
            text = exe.as_text()
            for scope in SCOPES:
                names[scope].update(scoped_instructions(text, scope))
        out[module] = {scope: sorted(found)
                       for scope, found in names.items()}
    return out


def window_note(record) -> Dict[str, Any]:
    """What tells a run that did other work from one that was held up: the
    window's cycles, its longest, the time in prefills and in decode
    dispatches, the slots whose states a step advanced, the K and V rows a
    layer read and the pages in use."""
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
        steps = max(b.stepped_n - a.stepped_n, 1)
        note["state_slots_stepped_mean"] = (b.stepped_sum
                                            - a.stepped_sum) / steps
        note["rows_read_global_mean"] = (b.rows_global_sum
                                         - a.rows_global_sum) / steps
        layer_steps = max(b.touched_n - a.touched_n, 1)
        note["experts_touched_mean"] = (b.touched_sum
                                        - a.touched_sum) / layer_steps
        note["held_pairs_mean"] = (b.held_pairs_sum
                                   - a.held_pairs_sum) / layer_steps
        note["pages_used_mean"] = sum(
            s.pages_used["global"] for s in inside) / len(inside)
        note["blocked_cycles"] = b.blocked - a.blocked
    return note


def resident_states(engine, record):
    """``[(tracked request, its tokens consumed, states [M layers, H, N,
    P])]`` of ``STATE_SAMPLES`` of the requests resident in a slot at the
    run's end with more than ``MIN_STATE_STEPS`` decode steps behind their
    states: spread evenly over those by their steps, the one with the most
    among them. ONE request's gap behind an expert layer is its own routing
    flips' (the reference says how wide that spreads), so several are read.
    The float32 states as the cache KEEPS them (``cache_ops.slot_states``
    puts the pool's packed pairs of heads back in the model's order), every
    ``M`` layer's. ``engine.close()`` has read the last dispatch, so the
    states have consumed the prompt and every emitted token but the last.
    Empty where no slot holds such a request."""
    import numpy as np

    held = {id(tr.req): tr for tr in record["tracked"]
            if tr.req is not None and not tr.refused}
    live = sorted(
        ((len(req.tokens_out), slot) for slot in range(engine.cfg.slots)
         for req in [engine.scheduler.slot_request(slot)]
         if req is not None and id(req) in held
         and len(req.tokens_out) > MIN_STATE_STEPS))
    n = min(STATE_SAMPLES, len(live))
    picked = sorted({round(i * (len(live) - 1) / max(n - 1, 1))
                     for i in range(n)})
    ops = engine.cache_ops
    gi = next(i for i, g in enumerate(ops.groups) if g.name == "ssm")
    out = []
    for _, slot in (live[i] for i in picked):
        tr = held[id(engine.scheduler.slot_request(slot))]
        tokens = (list(tr.planned.prompt) + list(tr.req.tokens_out))[:-1]
        out.append((tr, tokens, np.asarray(
            ops.slot_states(engine._cache, gi, slot))))
    return out


def release_pools(engine) -> None:
    """The page pools and the states given back to the device: the run
    has served what it will, and the float32 reference of a 10k context
    wants the 2.4 GB they hold."""
    import jax

    for x in jax.tree_util.tree_leaves(engine._cache):
        x.delete()


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window, as
    ``drivers/serve_hybrid.check`` decides it, from what the timed run
    served: two finished requests, one of them the LONGEST context that
    finished (past ``LONG_CONTEXT`` where the traffic offers such a one),
    ``MIN_TOKENS`` served tokens between them at least, against the
    float32 reference computed in blocks; two limits, on a request's worst
    row and on the mean over its rows, and a third on a VALUE the cache
    keeps, which ranks do not see: the float32 states of ``STATE_SAMPLES``
    requests resident in slots at the run's end against the reference's
    after the same tokens, the FIRST ``M`` layer's at the worst head of
    the worst request under a limit that tells float32 states from
    bfloat16 ones, the later ``M`` layers' at the MEDIAN head of the
    request that reads LEAST there under a looser one (behind an expert
    layer a request's own routing flips add to its gap, by much or little;
    what every request shares is the least; the reference says why each)."""
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
            ("paged attention", engine.decode_kernel_info()),
            ("state", engine.cache_ops.state_kernel_mode())):
        if kernel in (None, "gather") and str(why).startswith("gate:"):
            problems.append("the %s kernel refused the cache's geometry "
                            "(%s): that mixer's decode ran in plain XLA"
                            % (what, why))
    forms = record.get("decode_forms") or {}
    if engine.decode_kernel_info()[0] == "paged" \
            and not forms.get("moe/pass_form.stream"):
        problems.append("the decode executable's expert passes were not "
                        "traced in the stream form (%s): ragged_dot ran"
                        % forms)
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
    engine.close()                  # the last dispatch read
    residents = resident_states(engine, record)
    release_pools(engine)
    margins = []
    for tr in sample:
        shares = []
        gaps = reference.row_gaps(engine.params, job.config,
                                  tr.planned.prompt, tr.req.tokens_out,
                                  shares=shares)
        worst, mean = float(gaps.max()), float(gaps.mean())
        margins.append({"context": total(tr), "margin": worst,
                        "mean_gap": mean,
                        # a layer's (part, residual) norms
                        "branch_rms": [[round(float(v), 4) for v in layer]
                                       for layer in shares]})
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
    if not residents:
        problems.append("no request is resident in a slot at the run's end "
                        "with %d decode steps behind its state"
                        % MIN_STATE_STEPS)
    else:
        import numpy as np

        firsts, deeps = [], []
        for tr, tokens, served in residents:
            want = reference.final_states(engine.params, job.config, tokens)
            by_layer = [reference.state_gaps(served[i], want[i])
                        for i in range(len(want))]
            firsts.append(reference.first_layer_gap(by_layer))
            deeps.append(reference.deep_layer_gap(by_layer))
            margins.append({"context": len(tokens), "resident": True,
                            "decode_steps": len(tr.req.tokens_out) - 1,
                            "state_gap": firsts[-1],
                            "state_gap_deep": deeps[-1],
                            # an M layer's (median, worst) head
                            "state_gap_by_layer": [
                                [float(np.median(g)), float(g[-1])]
                                for g in by_layer]})
        # the FIRST M layer's worst head (no expert layer stands before
        # it) at the worst of the residents; behind an expert layer a
        # resident's worst layer at its MEDIAN head, at the resident it
        # reads LEAST in (a request's own routing flips only add to it);
        # numpy's, so that a state that is not a number fails both
        gap, deep = float(np.max(firsts)), float(np.min(deeps))
        for what, value, limit in (
                ("first M layer's states", gap, reference.STATE_GAP_LIMIT),
                ("later M layers' states", deep,
                 reference.STATE_GAP_DEEP_LIMIT)):
            if not value <= limit:
                problems.append(
                    "the %s the cache keeps depart from the float32 "
                    "reference's by %.5f of their length (limit %.5f; %d "
                    "resident requests of %s decode steps)"
                    % (what, value, limit, len(residents),
                       [len(tr.req.tokens_out) - 1
                        for tr, _, _ in residents]))
        beside["state_gap"] = [gap, reference.STATE_GAP_LIMIT]
        beside["state_gap_deep"] = [deep, reference.STATE_GAP_DEEP_LIMIT]
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
                 "state_kernel": list(ops.state_kernel_mode()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": ops.cache_bytes(engine._cache),
                 "state_bytes": ops.state_bytes(engine._cache)})
        t0 = time.perf_counter()
        # the decode executable first and alone, so that the trace-time
        # counters say which forms ITS passes and steps took
        before = traced_forms()
        engine._get_decode_exe(engine.cfg.decode_fuse)
        decode_forms = {k: v - before[k] for k, v in traced_forms().items()}
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0,
                 "traced_forms": traced_forms(),
                 "decode_forms": decode_forms})
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
        record["prompt_buckets"] = sorted(engine.cfg.prompt_buckets)
        record["scoped_ops"] = scoped_ops(engine)
        record["traced_forms"] = traced_forms()
        record["decode_forms"] = decode_forms
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

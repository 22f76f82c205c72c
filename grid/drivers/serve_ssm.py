"""Serves the parallel hybrid (Falcon-H1: a Mamba-2 state AND a GQA page
pool in EVERY layer, muP multipliers, a dense SwiGLU, the whole vocabulary)
through the same ``ServingEngine`` and the same open-loop harness as
``drivers/serve.py``: ``warm`` and ``drive`` are that module's, ``plan`` is
``drivers/serve_moe.py``'s (every ``--seed`` offers the same lengths in the
same order at the same instants; the seed draws token ids and the weights).
Its own are ``build`` (five whole layers with the embedding and the head,
the K/V pool and the per-slot states) and ``check`` (as
``drivers/serve_hybrid.check`` decides ``correct``: two finished requests,
one the longest context that finished, prefill and then every decoded
position THROUGH the pools and the states, against the float32 reference's
one full forward over the same tokens; the paged kernel and the state
kernel armed; the generator's lateness counted from the end of the engine
cycle in progress), and a sample a cycle of what the counters read, for
the readers of ``grid/readers/ssm.py`` and, unchanged, two of
``grid/readers/moe.py``. The served model fills the chip, so the pools and
states are released before the reference runs. ``record["kind"]`` stays
``"serve"``: the window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

from .. import generate, runtime
from ..readers.gdla import scoped_instructions
from ..readers.ssm import DECODE_MODULE, PREFILL_MODULE, SCOPES
from ..reference import falcon_h1 as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_moe import plan

LONG_CONTEXT = 4000    # one of the two compared requests is past this
MIN_TOKENS = 256       # served tokens the two hold between them, at least
MIN_STATE_STEPS = 128  # decode steps behind the state that is compared


def model_config(config: Dict[str, Any], mup: Dict[str, Any] = None):
    """The configuration file's published keys as the program's config.
    ``mup``: multipliers that replace the published ones, for a control
    (``benchmarks/control_falcon_h1.py``)."""
    from paddle_tpu.models.falcon_h1 import MUP_KEYS, FalconH1Config

    stated = {"model_type": "falcon_h1", "hidden_act": "silu",
              "attention_bias": False, "mamba_proj_bias": False,
              "mlp_bias": False, "projectors_bias": False,
              "mamba_conv_bias": True, "mamba_rms_norm": True,
              "mamba_norm_before_gate": False, "mamba_use_mlp": True,
              "tie_word_embeddings": False, "rope_scaling": None,
              "attn_layer_indices": None}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if differs or heads * d_head != int(config["mamba_d_ssm"]):
        raise ValueError("the served layer is written for %s and a state "
                         "space of mamba_n_heads x mamba_d_head channels; "
                         "the configuration says %s, %d x %d against %s"
                         % (stated, differs, heads, d_head,
                            config["mamba_d_ssm"]))
    m = config["model"]
    return FalconH1Config(
        vocab_size=config["vocab_size"], n_layer=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], ssm_heads=heads,
        ssm_head_dim=d_head, ssm_groups=config["mamba_n_groups"],
        ssm_state=config["mamba_d_state"],
        mup=dict({k: config[k] for k in MUP_KEYS}, **(mup or {})),
        conv_taps=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        max_seq=m["max_seq"], dtype=m["dtype"], seed_rms=m["seed_rms"],
        dt_range=m["dt_range"], a_range=m["a_range"])


def build(job, **control) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type.
    ``control``: :func:`model_config`'s."""
    from paddle_tpu.models.falcon_h1 import FalconH1LM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    # the weights are the STATED configuration's, whatever a control does
    # to the program (a multiplier scales how a weight is seeded too)
    model = FalconH1LM(model_config(job.config, **control), params=init_params(
        model_config(job.config), generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


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
            sm.STATE_SLOTS_STEPPED.count, rows.sum))
        return done

    engine.step = stepped


def scan_forms() -> Dict[str, int]:
    """``ssd/scan_calls.<form>``: which form of the prefill's chunk scan
    the executables were traced with."""
    from paddle_tpu.monitor import metrics

    return {form: int(metrics.counter("ssd/scan_calls." + form).value)
            for form in ("kernel", "blocked")}


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
        note["pages_used_mean"] = sum(
            s.pages_used["global"] for s in inside) / len(inside)
        note["blocked_cycles"] = b.blocked - a.blocked
    return note


def resident_state(engine, record):
    """``(tracked request, its tokens consumed, states [L, H, N, P])`` of
    the request resident in a slot at the run's end with the most decode
    steps behind it: the float32 states as the cache KEEPS them, every
    layer's. ``engine.close()`` has read the last dispatch, so the states
    have consumed the prompt and every emitted token but the last. None
    where no slot holds a request."""
    import numpy as np

    held = {id(tr.req): tr for tr in record["tracked"]
            if tr.req is not None and not tr.refused}
    live = [(len(req.tokens_out), slot, held[id(req)])
            for slot in range(engine.cfg.slots)
            for req in [engine.scheduler.slot_request(slot)]
            if req is not None and id(req) in held and req.tokens_out]
    if not live:
        return None
    _, slot, tr = max(live, key=lambda c: c[:2])
    tokens = (list(tr.planned.prompt) + list(tr.req.tokens_out))[:-1]
    return tr, tokens, np.asarray(engine._cache["s.ssm"][:, slot])


def release_pools(engine) -> None:
    """The page pools and the states given back to the device: the run
    has served what it will, and the float32 reference of a 5k context
    needs the 3.4 GB they hold (weights and pools fill the chip to 13 GB
    of 16)."""
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
    keeps, which ranks do not see: the float32 states of the request that
    is resident in a slot at the run's end, every layer's, against the
    reference's after the same tokens (the reference says why each)."""
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
    resident = resident_state(engine, record)
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
                        # a layer's (SSM, attention, MLP, residual) norms
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
    if resident is None \
            or len(resident[0].req.tokens_out) <= MIN_STATE_STEPS:
        problems.append("no request is resident in a slot at the run's end "
                        "with %d decode steps behind its state"
                        % MIN_STATE_STEPS)
    else:
        tr, tokens, served = resident
        gaps = reference.state_gaps(served, reference.final_states(
            engine.params, job.config, tokens))
        gap = float(gaps[-1])       # the worst (layer, head)
        margins.append({"context": len(tokens), "resident": True,
                        "decode_steps": len(tr.req.tokens_out) - 1,
                        "state_gap": gap, "state_gap_quantiles": {
                            q: float(gaps[int(q * (len(gaps) - 1) / 100)])
                            for q in (0, 50, 90, 100)}})
        if not gap <= reference.STATE_GAP_LIMIT:
            problems.append(
                "the states the cache keeps depart from the float32 "
                "reference's by %.5f of their length (limit %.5f; %d "
                "positions, %d of them decoded)"
                % (gap, reference.STATE_GAP_LIMIT, len(tokens),
                   len(tr.req.tokens_out) - 1))
        beside["state_gap"] = [gap, reference.STATE_GAP_LIMIT]
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
                 "decode_kernel": list(engine.decode_kernel_info()),
                 "state_kernel": list(ops.state_kernel_mode()),
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_bytes": ops.cache_bytes(engine._cache),
                 "state_bytes": ops.state_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0,
                 "ssd_scan_calls": scan_forms()})
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
        job.log({"phase": "executables", "scratch": {
            str(k): int(x.memory_analysis().temp_size_in_bytes)
            for k, x in list(engine._decode_exe.items())
            + list(engine._prefill_exe.items())}})
        record.update(check(engine, record, job, record["compiles"]))
    return record

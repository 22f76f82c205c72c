"""Serves the looped decoder (Ouro: 48 layers run FOUR times a token, so a
layer's weights own four cache layers) through the same ``ServingEngine``
and the same open-loop harness as ``drivers/serve.py``: ``warm``, ``drive``,
``compared`` and ``harness_lateness`` are that module's, ``plan`` is
``drivers/serve_moe.py``'s (every ``--seed`` offers the same lengths in the
same order at the same instants; the seed draws token ids and the weights)
and ``release_pools`` ``drivers/serve_ssm.py``'s. Its own are ``build`` (the
model WHOLE and its 192-layer page pool), ``check`` (as
``drivers/serve_ssm.check`` decides ``correct``: two finished requests, one
the longest context that finished, prefill and then every decoded position
THROUGH the pool, against the float32 reference's one full forward over the
same tokens: the served token's rank, and the exit gate's four ``p_t`` a
decoded row as the decode step itself handed them out), and a sample a
cycle of what the counters read, for the readers of
``grid/readers/loop.py`` and, unchanged, two of ``grid/readers/moe.py``.
``run``, ``sampling`` and ``window_note`` are written here too: the other
drivers' read their own module's ``build`` and ``check`` and the
state-step counter, which this model does not feed. The served model fills
the chip, so the pool is released before the reference runs.
``record["kind"]`` stays ``"serve"``: the window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

from .. import generate, runtime
from ..readers.gdla import scoped_instructions
from ..readers.loop import DECODE_MODULE, PREFILL_MODULE, SCOPES
from ..reference import ouro as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_moe import plan
from .serve_ssm import release_pools

LONG_CONTEXT = 768     # one of the two compared requests is past this
MIN_TOKENS = 256       # served tokens the two hold between them, at least
MIN_GATE_ROWS = 64     # decoded rows a request's exit distribution is held on
MIN_ROW_STEPS = 64     # decode steps behind the rows that are compared


def model_config(config: Dict[str, Any], **control):
    """The configuration file's published keys as the program's config.
    ``control``: a ``ut_steps`` that replaces the stated one, for a
    control (``benchmarks/control_ouro.py``)."""
    from paddle_tpu.models.ouro import OuroConfig

    stated = {"model_type": "ouro", "hidden_act": "silu",
              "tie_word_embeddings": False, "rope_scaling": None,
              "use_sliding_window": False, "sliding_window": None,
              "early_exit_threshold": 1}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    n = int(config["num_hidden_layers"])
    kinds = set(config["layer_types"][:n])
    if differs or kinds != {"full_attention"}:
        raise ValueError("the served layer is written for %s and full "
                         "attention in every layer; the configuration says "
                         "%s, layer types %s" % (stated, differs,
                                                 sorted(kinds)))
    m = config["model"]
    sizes = dict(ut_steps=config["total_ut_steps"])
    sizes.update(control)
    return OuroConfig(
        vocab_size=config["vocab_size"], n_layer=n,
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"],
        exit_threshold=config["early_exit_threshold"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        max_seq=m["max_seq"], dtype=m["dtype"], seed_rms=m["seed_rms"],
        **sizes)


def build(job, **control) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type: the
    STATED configuration's, whatever a control does to the program."""
    from paddle_tpu.models.ouro import OuroLM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    model = OuroLM(model_config(job.config, **control), params=init_params(
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
    steps_n: int                 # decode steps read (one observation each)
    rows_global_sum: float       # serving/attn_rows_read.global, sum
    exit_step_sum: float         # serving/ut_expected_exit_step, sum


def sampling(engine, samples: List[Sample],
             exit_p: Dict[int, List[np.ndarray]]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper) and
    the exit distribution of every row the cycle's dispatch decoded goes
    to ``exit_p[request id]``, in the order the rows were decoded (the
    probe ``ut_exit_p`` of ``engine.last_decode_stats``: a row of zeros is
    a slot that was not live). A program without the counters (the parent
    of the PR that added this file) cannot build this model, so nothing
    here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    rows = sm.attn_rows_read("global")
    seen = [None]

    def stepped():
        done = step()
        read = engine.last_decode_stats
        if read is not None and read is not seen[0]:
            seen[0] = read
            tenants, stats = read
            for p in np.asarray(stats["ut_exit_p"]):        # a fused step
                for slot, req in enumerate(tenants):
                    if req is not None and p[slot].sum() > 0.5:
                        exit_p.setdefault(req.id, []).append(p[slot])
        samples.append(Sample(
            time.perf_counter(), {p.name: p.num_used for p in engine.pools},
            sm.ADMISSION_BLOCKED.value, rows.count, rows.sum,
            sm.UT_EXPECTED_EXIT_STEP.sum))
        return done

    engine.step = stepped


def labelled_executables() -> List[List[Any]]:
    """``[name, hit | miss | none, backend seconds, trace and lowering
    seconds]`` of each executable the program built under a label so far
    (its compile log: ``paddle_tpu.compile_cache.log()``): the loop's
    executables by name, each holding the layers' bodies ONCE."""
    from paddle_tpu import compile_cache

    return [[e["name"], e["cache"], round(e["backend_s"], 2),
             round(e["trace_s"] + e["lower_s"], 2)]
            for e in compile_cache.log() if e["labelled"]]


def scoped_ops(engine) -> Dict[str, Dict[str, List[str]]]:
    """The instructions of the decode and of the prefill executables that
    run under each of ``readers/loop.SCOPES``, from the executables' own
    text, a module: what the readers tell an event by. The buckets'
    prefill executables share one module name; their names are pooled."""
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
    dispatches, the rows a cache layer read, the gate's reading and the
    pages in use."""
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
        steps = max(b.steps_n - a.steps_n, 1)
        note["rows_read_global_mean"] = (b.rows_global_sum
                                         - a.rows_global_sum) / steps
        note["expected_exit_step_mean"] = (b.exit_step_sum
                                           - a.exit_step_sum) / steps / 100.0
        note["pages_used_mean"] = sum(
            s.pages_used["global"] for s in inside) / len(inside)
        note["blocked_cycles"] = b.blocked - a.blocked
    return note


def resident_rows(engine, record, probes):
    """``(tracked request, its tokens consumed, {(step, layer): K [n, Hkv
    D]})`` of the request resident in a slot at the run's end with the most
    decode steps behind it: its K rows as the pool KEEPS them in the cache
    layers ``probes``, through the slot's page table. ``engine.close()``
    has read the last dispatch, so the rows are the prompt's and every
    emitted token's but the last. None where no slot holds a request."""
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
    rows = {(t, layer): np.asarray(engine.cache_ops.context(
        engine._cache, layer, step=t)[0][slot, :len(tokens)],
        np.float32).reshape(len(tokens), -1) for t, layer in probes}
    return tr, tokens, rows


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window from what the timed run
    served: two finished requests, one of them the LONGEST context that
    finished (past ``LONG_CONTEXT``), ``MIN_TOKENS`` served tokens between
    them at least, against the float32 reference's full forward over the
    same tokens. Four limits (the reference says why each): a request's
    worst row and the mean over its rows, of the served token's rank below
    the reference's best logit; the served exit distribution's distance
    from the reference's at the mean over the request's decoded rows; and
    a VALUE the cache keeps, which neither sees well: the K rows of the
    request resident in a slot at the run's end, in the first cache layer
    (the last is read beside it), against the reference's after the same
    tokens."""
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
    if kernel in (None, "gather") and str(why).startswith("gate:"):
        problems.append("the paged attention kernel refused the cache's "
                        "geometry (%s): decode attention ran in plain XLA"
                        % why)
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
    if by_length and total(by_length[0]) <= LONG_CONTEXT:
        problems.append("no finished request's context passed %d"
                        % LONG_CONTEXT)
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    elif sum(len(tr.req.tokens_out) for tr in sample) < MIN_TOKENS:
        problems.append("the two compared requests hold under %d served "
                        "tokens" % MIN_TOKENS)
    engine.close()                  # the last dispatch read
    resident = resident_rows(engine, record, reference.probes(job.config))
    release_pools(engine)
    margins = []
    for tr in sample:
        shares = []
        gaps, want_p = reference.row_gaps(
            engine.params, job.config, tr.planned.prompt, tr.req.tokens_out,
            shares=shares)
        worst, mean = float(gaps.max()), float(gaps.mean())
        # decode step k consumed output token k: the row that chose token
        # k + 1 (the prefill chose token 0 and hands out no gate)
        served_p = np.asarray(record["exit_p"].get(tr.req.id, []),
                              np.float32).reshape(
                                  -1, engine.model.cfg.ut_steps)
        # a control that runs fewer steps hands out fewer numbers a row
        served_p = np.pad(served_p, ((0, 0), (
            0, want_p.shape[1] - served_p.shape[1])))[:len(want_p) - 1]
        p_gap = reference.exit_p_gap(served_p, want_p[1:1 + len(served_p)])
        gate = float(p_gap.mean()) if len(p_gap) >= MIN_GATE_ROWS \
            else float("inf")
        margins.append({"context": total(tr), "margin": worst,
                        "mean_gap": mean, "exit_p_gap": gate,
                        "exit_p_gap_worst": float(p_gap.max())
                        if len(p_gap) else None,
                        "gate_rows": int(len(p_gap)),
                        "expected_exit_step": float(
                            (want_p * np.arange(1, want_p.shape[1] + 1)
                             ).sum(axis=1).mean()),
                        # a step's (attention's add, the MLP's add, the
                        # state's movement) root mean squares
                        "step_rms": [[round(float(v), 4) for v in step]
                                     for step in shares]})
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
        if not gate <= reference.EXIT_P_GAP_LIMIT:
            problems.append(
                "the served exit distribution lies %.4f from the float32 "
                "reference's at the mean over %d decoded rows (limit %.4f; "
                "%d rows at least; context %d)"
                % (gate, len(p_gap), reference.EXIT_P_GAP_LIMIT,
                   MIN_GATE_ROWS, total(tr)))
    beside = compared(len(failed), len(short), compiles_in_window, late_p50,
                      decode_ms, margins, reference)
    if margins:
        beside["exit_p_gap"] = [max(m["exit_p_gap"] for m in margins),
                                reference.EXIT_P_GAP_LIMIT]
    if resident is None \
            or len(resident[0].req.tokens_out) <= MIN_ROW_STEPS:
        problems.append("no request is resident in a slot at the run's end "
                        "with %d decode steps behind its rows"
                        % MIN_ROW_STEPS)
    else:
        tr, tokens, served = resident
        want = reference.kept_rows(engine.params, job.config, tokens)
        gaps = {"step %d layer %d" % at: reference.row_gap(served[at],
                                                           want[at])
                for at in served}
        gap = next(iter(gaps.values()))     # the FIRST probe's is held
        margins.append({"context": len(tokens), "resident": True,
                        "decode_steps": len(tr.req.tokens_out) - 1,
                        "row_gap": gap, "row_gaps": gaps})
        if not gap <= reference.ROW_GAP_LIMIT:
            problems.append(
                "the K rows the pool keeps depart from the float32 "
                "reference's by %.5f of their length (limit %.5f; %s; %d "
                "positions, %d of them decoded)"
                % (gap, reference.ROW_GAP_LIMIT, gaps, len(tokens),
                   len(tr.req.tokens_out) - 1))
        beside["row_gap"] = [gap, reference.ROW_GAP_LIMIT]
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
                 "pools": {p.name: p.num_pages for p in engine.pools},
                 "cache_steps": ops.cache_steps,
                 "cache_bytes": ops.cache_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0,
                 "executables": labelled_executables()})
        samples: List[Sample] = []
        exit_p: Dict[int, List[np.ndarray]] = {}
        sampling(engine, samples, exit_p)
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        planned = plan(traffic, vocab, job.seed, job.seconds, tail_s)
        with runtime.stopping(job.profiler):
            record = drive(engine, planned, job.seconds,
                           float(traffic["preroll_s"]), tail_s,
                           job.profiler, job.log, job.meter)
        marks = record["marks"]
        record["samples"] = samples
        record["exit_p"] = exit_p
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

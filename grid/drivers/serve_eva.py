"""Serves the byte-level decoder with EVA attention (EvaByte: exact keys
inside a tumbling window, one pooled key and value a chunk of every closed
window, so a page pool whose closed windows are compacted in place) through
the same ``ServingEngine`` and the same open-loop harness as
``drivers/serve.py``: ``warm``, ``drive``, ``compared`` and
``harness_lateness`` are that module's, ``plan`` is ``drivers/serve_moe.py``'s
(every ``--seed`` offers the same lengths in the same order at the same
instants; the seed draws the bytes and the weights), ``release_pools``
``drivers/serve_ssm.py``'s and ``labelled_executables``
``drivers/serve_loop.py``'s. Its own are ``build``, a sample a cycle of
what the counters read (for the readers of ``grid/readers/eva.py`` and,
unchanged, ``readers/moe.admit_blocked_on_pages_share``), the served LOGITS
of every prediction head a decoded row (the decode step's probe
``eva_head_logits``, kept for the requests ``check`` may compare), and
``check``: two finished requests, one the longest context that finished
and one that CLOSED A WINDOW between its first and last decoded byte, every
decoded row as the timed path served it THROUGH the pool against the
float32 reference's one full forward over the same bytes; of the prompt's
last row the timed prefill hands out the byte it chose, which has to score
within the limit of the reference's best there. The served model fills the chip, so the pool
is released before the reference runs. ``record["kind"]`` stays
``"serve"``: the window's readers apply unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

from .. import generate, runtime
from ..readers.eva import SCOPES
from ..readers.gdla import scoped_instructions
from ..readers.loop import PREFILL_MODULE
from ..readers.moe import DECODE_MODULE
from ..reference import evabyte as reference
from .serve import compared, drive, harness_lateness, warm
from .serve_loop import labelled_executables
from .serve_moe import plan
from .serve_ssm import release_pools

MIN_TOKENS = 512       # served bytes the two compared requests hold, at least


def model_config(config: Dict[str, Any]):
    """The configuration file's published keys as the program's config
    (the layer is written for the stated values of the keys checked
    here: float32 where ``fp32_skip_add``, ``fp32_logits`` and
    ``mixedp_attn`` say)."""
    from paddle_tpu.models.evabyte import EvaByteConfig

    stated = {"model_type": "evabyte", "attention_class": "eva",
              "hidden_act": "silu", "attention_bias": False,
              "tie_word_embeddings": False, "rope_scaling": None,
              "norm_add_unit_offset": True, "fp32_skip_add": True,
              "fp32_logits": True, "mixedp_attn": True, "fp32_ln": False,
              "num_chunks": None}
    differs = {k: config[k] for k, v in stated.items() if config[k] != v}
    if differs:
        raise ValueError("the served layer is written for %s; the "
                         "configuration says %s" % (stated, differs))
    m = config["model"]
    return EvaByteConfig(
        vocab_size=config["vocab_size"], n_layer=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], window=config["window_size"],
        chunk=config["chunk_size"], n_pred_heads=config["num_pred_heads"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        max_seq=m["max_seq"], dtype=m["dtype"], seed_rms=m["seed_rms"])


def build(job) -> Any:
    """Model and engine at the configuration's sizes; the weights are made
    on the device from the seed, a layer a call, in the served type."""
    from paddle_tpu.models.evabyte import EvaByteLM, init_params
    from paddle_tpu.serving import ServingConfig, ServingEngine

    e = job.config["engine"]
    mcfg = model_config(job.config)
    model = EvaByteLM(mcfg, params=init_params(mcfg,
                                               generate.np_seed(job.seed)))
    return ServingEngine(model, ServingConfig(
        slots=e["slots"], page_size=e["page_size"], max_seq=e["max_seq"],
        prompt_buckets=tuple(job.traffic["prompt_buckets"]),
        max_queue=e["max_queue"], group_pages=dict(e["group_pages"])))


class Sample(NamedTuple):
    """What the program's counters read after one ``engine.step()``. The
    first three fields are what ``readers/moe.py``'s blocked-admission
    reader takes."""

    end: float
    pages_used: Dict[str, int]   # by paged cache group
    blocked: float               # serving/admission_blocked_on_pages
    steps_n: int                 # decode steps read (one observation each)
    exact_sum: float             # serving/attn_rows_read.eva_exact, sum
    summary_sum: float           # serving/attn_rows_read.eva_summary, sum
    context_sum: float           # serving/attn_rows_context.eva, sum
    chunks_closed: float         # serving/eva_chunks_closed, sum
    windows_closed: float        # serving/eva_windows_closed, sum


def closes_a_window(prompt_len: int, tokens_out: int, window: int) -> bool:
    """Whether a request's DECODE steps close a window: a step consumes a
    position p in ``[prompt_len, prompt_len + tokens_out - 1)`` (the last
    byte is never fed back) and closes p's window where ``(p + 1) %
    window == 0``."""
    return (prompt_len + tokens_out - 1) // window > prompt_len // window


def sampling(engine, samples: List[Sample],
             logits: Dict[int, List[np.ndarray]]) -> None:
    """Wrap ``engine.step`` so that every cycle leaves a :class:`Sample`
    (a few attribute reads; the harness's ``drive`` calls the wrapper) and
    the logits of every prediction head at every row the cycle's dispatch
    decoded go to ``logits[request id]`` [n_pred_heads, V], in the order
    the rows were decoded (the probe ``eva_head_logits`` of
    ``engine.last_decode_stats``; ``eva_row_live`` says which slots
    decoded one). A finished request's rows are kept only while ``check``
    may still want them: the longest context that finished, the shortest,
    and the shortest that closed a window while it decoded. A
    program without the counters (the parent of the PR that added this
    file) cannot build this model, so nothing here guards for it."""
    from paddle_tpu.serving import metrics as sm

    step = engine.step
    window = engine.model.cfg.window
    exact, pooled = (sm.attn_rows_read("eva_" + kind)
                     for kind in ("exact", "summary"))
    context = sm.attn_rows_context("eva")
    seen = [None]
    kept: Dict[str, Any] = {}       # role -> (request id, score)

    def stepped():
        done = step()
        read = engine.last_decode_stats
        if read is not None and read is not seen[0]:
            seen[0] = read
            tenants, stats = read
            rows = np.asarray(stats["eva_head_logits"])     # [fuse, B, n, V]
            live = np.asarray(stats["eva_row_live"])        # [fuse, B]
            for row, on in zip(rows, live):
                for slot, req in enumerate(tenants):
                    if req is not None and on[slot]:
                        logits.setdefault(req.id, []).append(row[slot])
        for req in done:
            if req.id not in logits:
                continue
            size = req.prompt_len + len(req.tokens_out)
            roles = {"longest": size, "shortest": -size}
            if closes_a_window(req.prompt_len, len(req.tokens_out), window):
                roles["closing"] = -size
            hold = False
            for role, score in roles.items():
                if req.state == "finished" and (
                        role not in kept or score > kept[role][1]):
                    kept[role] = (req.id, score)
                    hold = True
            if not hold:
                del logits[req.id]
        if done:    # and whoever lost its role to one of them
            holders = {rid for rid, _ in kept.values()} | {
                q.id for q in engine.scheduler.running()}
            for rid in set(logits) - holders:
                del logits[rid]
        samples.append(Sample(
            time.perf_counter(), {p.name: p.num_used for p in engine.pools},
            sm.ADMISSION_BLOCKED.value, exact.count, exact.sum, pooled.sum,
            context.sum, sm.EVA_CHUNKS_CLOSED.sum,
            sm.EVA_WINDOWS_CLOSED.sum))
        return done

    engine.step = stepped


def scoped_ops(engine) -> Dict[str, Dict[str, List[str]]]:
    """The instructions of the decode and of the prefill executables that
    run under each of ``readers/eva.SCOPES``, from the executables' own
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
    dispatches, the rows a layer read of each kind, what was closed and
    the pages in use."""
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
        note["rows_read_exact_mean"] = (b.exact_sum - a.exact_sum) / steps
        note["rows_read_summary_mean"] = (b.summary_sum
                                          - a.summary_sum) / steps
        note["rows_context_mean"] = (b.context_sum - a.context_sum) / steps
        note["chunks_closed"] = b.chunks_closed - a.chunks_closed
        note["windows_closed"] = b.windows_closed - a.windows_closed
        note["pages_used_mean"] = sum(
            s.pages_used["eva"] for s in inside) / len(inside)
        note["blocked_cycles"] = b.blocked - a.blocked
    return note


def check(engine, record, job, compiles_in_window: int) -> Dict[str, Any]:
    """``correct``, decided outside the window from what the timed run
    served: two finished requests, the LONGEST context that finished and
    one that closed a window between its first and last decoded byte
    (``MIN_TOKENS`` served bytes between them at least), against the
    float32 reference's full forward over the same bytes. Two limits (the
    reference says why each): the largest and the mean absolute gap of the
    served LOGITS, all prediction heads, at every decoded row, over the
    spread of the reference's. The prompt's last row is the prefill
    executable's, which hands out the byte it chose and no logits: that
    byte's logit in the reference's head 0 may lie under the row's best by
    twice the first limit (a row within the limit of the reference's
    cannot choose another)."""
    marks = record["marks"]
    window = int(job.config["window_size"])
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

    served = record["logits"]
    held = [tr for tr in finished if tr.req.id in served]
    closing = sorted((tr for tr in held if closes_a_window(
        tr.req.prompt_len, len(tr.req.tokens_out), window)), key=total)
    longest = sorted(held, key=total, reverse=True)[:1]
    if not closing:
        # the plan's lengths guarantee one: a window that finished none is
        # no window this comparison can stand on
        raise RuntimeError(
            "no finished request closed a window of %d between its first "
            "and last decoded byte (%d finished, contexts %s)"
            % (window, len(finished), sorted(total(tr) for tr in finished)))
    sample = longest + [tr for tr in closing[:1] if tr not in longest]
    if len(sample) < 2:     # the longest is the one that closed a window
        sample += [tr for tr in sorted(held, key=total)
                   if tr not in sample][:1]
    if len(sample) < 2:
        problems.append("fewer than 2 finished requests to compare with "
                        "the reference")
    elif sum(len(tr.req.tokens_out) for tr in sample) < MIN_TOKENS:
        problems.append("the two compared requests hold under %d served "
                        "bytes" % MIN_TOKENS)
    engine.close()                  # the last dispatch read
    release_pools(engine)
    margins = []
    for tr in sample:
        out = tr.req.tokens_out
        rows = np.stack(served[tr.req.id])
        if len(rows) != len(out) - 1:
            problems.append("request %d: %d rows of served logits for %d "
                            "decoded bytes" % (tr.req.id, len(rows),
                                               len(out) - 1))
            continue
        shares = []
        want = reference.request_logits(engine.params, job.config,
                                        tr.planned.prompt, out,
                                        shares=shares)
        first, want = want[0, 0], want[1:]
        behind = float(first.max() - first[out[0]]) / float(want.std())
        if not behind <= 2 * reference.LOGIT_MARGIN:
            problems.append(
                "the byte the prefill served scores %.4f of the "
                "reference's spread under the reference's best at the "
                "prompt's last row (limit %.4f; context %d)"
                % (behind, 2 * reference.LOGIT_MARGIN, total(tr)))
        worst, mean = reference.logit_gaps(rows, want)
        agree = float(np.mean(rows[:, 0].argmax(-1) == want[:, 0].argmax(-1)))
        margins.append({"context": total(tr), "margin": worst,
                        "mean_gap": mean, "first_byte_behind": behind,
                        "closed_a_window": closes_a_window(
                            tr.req.prompt_len, len(out), window),
                        "head_gaps": [round(float(v), 5) for v in np.abs(
                            rows - want).mean(axis=(0, 2)) / want.std()],
                        "argmax_agree": agree,
                        # a layer's (attention's add, the MLP's add, the
                        # residual) root mean squares
                        "layer_rms": [[round(float(v), 4) for v in layer]
                                      for layer in shares]})
        if not worst <= reference.LOGIT_MARGIN:
            problems.append(
                "a served logit lies %.4f of the reference's spread from "
                "the float32 reference's (limit %.4f; context %d)"
                % (worst, reference.LOGIT_MARGIN, total(tr)))
        if not mean <= reference.MEAN_GAP_LIMIT:
            problems.append(
                "the served logits lie %.5f of the reference's spread from "
                "the float32 reference's at the mean over a request's rows "
                "and heads (limit %.5f; context %d)"
                % (mean, reference.MEAN_GAP_LIMIT, total(tr)))
    beside = compared(len(failed), len(short), compiles_in_window, late_p50,
                      decode_ms, margins, reference)
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
                 "pages_a_slot": ops.pages_per_slot,
                 "cache_bytes": ops.cache_bytes(engine._cache)})
        t0 = time.perf_counter()
        warm(engine, vocab)
        job.log({"phase": "warm", "warm_s": time.perf_counter() - t0,
                 "executables": labelled_executables()})
        samples: List[Sample] = []
        logits: Dict[int, List[np.ndarray]] = {}
        sampling(engine, samples, logits)
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        planned = plan(traffic, vocab, job.seed, job.seconds, tail_s)
        with runtime.stopping(job.profiler):
            record = drive(engine, planned, job.seconds,
                           float(traffic["preroll_s"]), tail_s,
                           job.profiler, job.log, job.meter)
        marks = record["marks"]
        record["samples"] = samples
        record["logits"] = logits
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

"""Metrics of the cell whose cache group COMPACTS (EVA attention: a slot
holds its open window's exact rows and one summary a chunk of every closed
window): the paged kernel's calls, the weights' stream and the products' time in
the device trace, EVA attention's share of busy time, the step's share of the chip's
peak, and the counters the driver sampled after every cycle
(``drivers/serve_eva.Sample``).

An operation is told BY NAME from the executables' own text, whose metadata
keeps the ``jax.named_scope`` names that the profile's event text drops
(``readers/gdla.scoped_instructions``; the driver writes the names into
``record["scoped_ops"]``, a list a scope a module). A scope is looked for
WITH its closing slash: ``attn/eva/`` is the decode attention and the
row's write, and is not ``attn/eva_pool/`` (the chunk's summary),
``attn/eva_close/`` (the compaction) nor ``attn/eva_prefill/``; the
products run under ``attn/proj/``, ``mlp/`` and ``head/multibyte/``
(``benchmarks/diag_eva_step.py`` prints a step's time by all seven). A
reader that finds no such operation, or a record without the samples (the
parent of the PR that added this file has neither the model nor the
counters), returns nothing."""

from __future__ import annotations

from typing import Optional

from .. import flops_eva, reduce
from .loop import PREFILL_MODULE, _tail, _traced_buckets
from .moe import DECODE_MODULE, PALLAS, _delta, _in, _win

ATTN, POOL, CLOSE = "attn/eva/", "attn/eva_pool/", "attn/eva_close/"
PREFILL_ATTN, PROJ = "attn/eva_prefill/", "attn/proj/"
MLP, HEAD = "mlp/", "head/multibyte/"
SCOPES = (ATTN, POOL, CLOSE, PREFILL_ATTN, PROJ, MLP, HEAD)


def _is_record(record) -> bool:
    return ("samples" in record
            and record.get("model", {}).get("attention_class") == "eva"
            and "scoped_ops" in record)


def _named(record, module: str, *scopes):
    """The events of ``module`` named by the instructions that run under
    ``scopes`` in its executables' own text."""
    ops = (record.get("scoped_ops") or {}).get(module, {})
    names = frozenset(n for s in scopes for n in ops.get(s, ()))

    def pred(o):
        return o.module == module and o.name in names

    return pred


def _is_paged(record):
    """The paged kernel's calls: Pallas custom calls among the instructions
    of ``attn/eva/`` in the decode executable."""
    attn = _named(record, DECODE_MODULE, ATTN)

    def pred(o):
        return PALLAS in o.text and attn(o)

    return pred


def _rows(record, lo: str, hi: str) -> Optional[float]:
    """Rows ONE layer's attention read, of both kinds, between two marks."""
    samples, inside = _in(record, lo, hi)
    exact = _delta(samples, inside, "exact_sum")
    pooled = _delta(samples, inside, "summary_sum")
    return None if exact is None or pooled is None else exact + pooled


def eva_paged_attn_roofline(record, trace) -> Optional[float]:
    """``flops_eva.kv_need_s`` over the rows ONE layer read in the traced
    decode steps (``serving/attn_rows_read.eva_exact`` + ``.eva_summary``)
    over the device time of the paged kernel's calls."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_paged(record), _win(record))
    rows = _rows(record, "tail_open", "tail_close")
    if not kernel_s or not rows:
        return None
    return 100.0 * flops_eva.kv_need_s(
        rows, record["model"], record["peaks"]) / kernel_s


def _decode_s(trace, record) -> float:
    """Device time of the whole decode executable in the traced stretch."""
    return reduce.time_where(trace, lambda o: o.module == DECODE_MODULE,
                             _win(record))


def eva_weight_stream_roofline(record, trace) -> Optional[float]:
    """The weights' least share of a decode STEP, no kernel's roofline: the
    least time the chip could take to read the weights the traced decode
    steps must read (``flops_eva.weight_need_s``: every layer and the
    heads once a step) over the device time of the WHOLE decode
    executable. The products' own time cannot stand under it: the compiler
    moves weights into fast memory by asynchronous copies that start
    before a paged kernel's call and end after it, and the wait for a
    copy is an operation of no scope, so the products' scopes hold less
    time than their bytes need (PERF.md, section 5, has the copies by
    name; ``eva_products_time_share.serve`` keeps the products' own
    time)."""
    if trace is None or not _is_record(record):
        return None
    decode_s = _decode_s(trace, record)
    decode_steps = _tail(record, "steps_n")
    if not decode_s or not decode_steps:
        return None
    return 100.0 * flops_eva.weight_need_s(
        decode_steps, record["model"], record["peaks"]) / decode_s


def eva_products_time_share(record, trace) -> Optional[float]:
    """Device time of the decode executable's products (the operations
    under ``attn/proj/``, ``mlp/`` and ``head/multibyte/``: the norms and
    the rotation with them) over the whole decode executable's: what a
    change to the matmuls moves."""
    if trace is None or not _is_record(record):
        return None
    decode_s = _decode_s(trace, record)
    products_s = reduce.time_where(
        trace, _named(record, DECODE_MODULE, PROJ, MLP, HEAD), _win(record))
    if not decode_s or not products_s:
        return None
    return 100.0 * products_s / decode_s


def eva_attn_time_share(record, trace) -> Optional[float]:
    """Device time of EVA attention in both executables (the decode step's
    attention, pooling and compaction; the prefill's attention and
    pooling) over busy device time in the traced stretch."""
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    decode = _named(record, DECODE_MODULE, ATTN, POOL, CLOSE)
    prefill = _named(record, PREFILL_MODULE, PREFILL_ATTN, POOL)
    attn_s = reduce.time_where(trace, lambda o: decode(o) or prefill(o), win)
    if not busy or not attn_s:
        return None
    return 100.0 * attn_s / busy


def evabyte_step_mfu(record, trace) -> Optional[float]:
    """The model's operations for what the traced stretch computed
    (``flops_eva.step_flops``) over busy device seconds times the chip's
    bf16 peak. A dozen rows against 4.9 GB of weights and a few GB of
    pages a step: a few per cent, and bandwidth's, not a fault."""
    if trace is None or not _is_record(record):
        return None
    busy = reduce.busy_seconds(trace, _win(record))
    rows = _rows(record, "tail_open", "tail_close")
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    buckets = _traced_buckets(record)
    # a token a decoded row, but each admission's first: the prefill's
    slot_steps = sum(c.tokens for c in record["cycles"]
                     if lo <= c.end <= hi) - len(buckets)
    if not busy or slot_steps <= 0 or rows is None:
        return None
    flops = flops_eva.step_flops(slot_steps, rows, buckets, record["model"])
    return 100.0 * flops / (busy * record["peaks"]["bf16_flops_per_s"])


def _per_step(field: str):
    def read(record, trace=None) -> Optional[float]:
        if not _is_record(record):
            return None
        samples, inside = _in(record, "open", "close")
        n = _delta(samples, inside, "steps_n")
        return _delta(samples, inside, field) / n if n else None

    return read


eva_exact_rows_per_step = _per_step("exact_sum")
eva_summary_rows_per_step = _per_step("summary_sum")


def eva_rows_kept_share(record, trace=None) -> Optional[float]:
    """Rows a layer's decode attention read over the same slots' whole
    contexts (``serving/attn_rows_context.eva``), over the window."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    context = _delta(samples, inside, "context_sum")
    rows = _rows(record, "open", "close")
    return rows / context if rows is not None and context else None


def eva_pages_used_share(record, trace=None) -> Optional[float]:
    """Pages of the ``eva`` cache group in use after each cycle, mean over
    the window, over the group's pool."""
    if not _is_record(record) or "eva" not in record.get("pools", {}):
        return None
    samples, inside = _in(record, "open", "close")
    if not inside:
        return None
    used = sum(samples[i].pages_used["eva"] for i in inside) / len(inside)
    return 100.0 * used / record["pools"]["eva"]

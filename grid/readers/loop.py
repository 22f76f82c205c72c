"""Metrics of the looped decoder's cell (the same layers run four times a
token: a device loop whose body is the layers and whose carry is a
192-layer page pool): the loop's weight stream and its paged-attention
calls in the device trace, attention's share of the decode executable, the
step's share of the chip's peak, and the counters the driver sampled after
every cycle (``drivers/serve_loop.Sample``).

An operation is told BY NAME from the executables' own text, whose metadata
keeps the ``jax.named_scope`` names that the profile's event text drops
(``readers/gdla.scoped_instructions``; the driver writes the names into
``record["scoped_ops"]``, a list a scope a module): ``loop/step`` is the
device loop's body, ``attn/loop`` a layer's attention half inside it, of
which the paged kernel's calls are the Pallas custom calls, ``head`` the
output head after the loop. The ``while`` itself is an event too, as long
as everything inside it. The weights' stream is held against the WHOLE
loop: the compiler fetches the next products' weights into fast memory
while the paged kernel runs (``slice-start`` .. ``slice-done`` around it in
the executable's text; on the chip the products themselves read 2 ms of a
50 ms step and the waits for the fetches 11), so the kernel's time cannot
be taken out of the stream's (the share then read 327%). A reader
that finds no such operation, or a record without the samples (the parent
of the PR that added this file has neither the model nor the counters),
returns nothing."""

from __future__ import annotations

import bisect
from typing import List, Optional

from .. import flops_loop, reduce
from .moe import DECODE_MODULE, PALLAS, _delta, _in, _win

PREFILL_MODULE = "jit_prefill"
STEP_SCOPE, ATTN_SCOPE, MLP_SCOPE = "loop/step", "attn/loop", "mlp/loop"
GATE_SCOPE, HEAD_SCOPE = "loop/gate", "head"
SCOPES = (STEP_SCOPE, ATTN_SCOPE, MLP_SCOPE, GATE_SCOPE, HEAD_SCOPE)


def _is_record(record) -> bool:
    return ("samples" in record
            and "total_ut_steps" in record.get("model", {})
            and "scoped_ops" in record)


def _decode_named(record, *scopes):
    """The decode executable's events named by the instructions that run
    under ``scopes``."""
    ops = (record.get("scoped_ops") or {}).get(DECODE_MODULE, {})
    named = frozenset(n for s in scopes for n in ops.get(s, ()))

    def pred(o):
        return o.module == DECODE_MODULE and o.name in named

    return pred


def _is_paged(record):
    """The paged kernel's calls inside the loop: Pallas custom calls among
    the instructions of ``attn/loop``."""
    attn = _decode_named(record, ATTN_SCOPE)

    def pred(o):
        return PALLAS in o.text and attn(o)

    return pred


def _tail(record, field: str) -> Optional[float]:
    samples, inside = _in(record, "tail_open", "tail_close")
    return _delta(samples, inside, field)


def _traced_buckets(record) -> List[int]:
    """The bucket of each prompt admitted in the traced stretch: the rows
    its prefill computed."""
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    buckets = record["prompt_buckets"]
    return [buckets[bisect.bisect_left(buckets, tr.req.prompt_len)]
            for tr in record["tracked"]
            if tr.req is not None and not tr.refused
            and tr.req.admitted_t is not None
            and lo <= tr.req.admitted_t <= hi]


def loop_weight_stream_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take to read the weights the traced
    decode steps must read (``flops_loop.weight_need_s``: every layer once
    a loop step and the head once, a decode step) over the device time of
    the decode executable's loop (the ``while`` and whatever runs under
    ``loop/step``, the paged kernel's calls too: the stream runs beside
    them) and of the head, whose bytes are among those counted."""
    if trace is None or not _is_record(record):
        return None
    named = _decode_named(record, STEP_SCOPE, HEAD_SCOPE)
    loop_s = reduce.time_where(
        trace, lambda o: named(o) or (o.module == DECODE_MODULE
                                      and o.opcode == "while"), _win(record))
    decode_steps = _tail(record, "steps_n")
    if not loop_s or not decode_steps:
        return None
    return 100.0 * flops_loop.weight_need_s(
        decode_steps, record["model"], record["peaks"]) / loop_s


def loop_paged_attn_roofline(record, trace) -> Optional[float]:
    """``flops_loop.kv_need_s`` over the rows ONE cache layer read in the
    traced decode steps (``serving/attn_rows_read.global``) over the device
    time of the paged kernel's calls inside the loop."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_paged(record), _win(record))
    rows = _tail(record, "rows_global_sum")
    if not kernel_s or not rows:
        return None
    return 100.0 * flops_loop.kv_need_s(
        rows, record["model"], record["peaks"]) / kernel_s


def loop_attn_time_share(record, trace) -> Optional[float]:
    """Device time of the paged kernel's calls inside the loop over the
    decode executable's busy time in the traced stretch."""
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    decode_s = reduce.time_where(
        trace, lambda o: o.module == DECODE_MODULE, win)
    kernel_s = reduce.time_where(trace, _is_paged(record), win)
    if not decode_s or not kernel_s:
        return None
    return 100.0 * kernel_s / decode_s


def ouro_step_mfu(record, trace) -> Optional[float]:
    """The model's operations for what the traced stretch computed
    (``flops_loop.step_flops``: the live slot-steps four times through the
    layers and once through the head with the context rows a cache layer
    read, and each prefill as its bucket computes it) over busy device
    seconds times the chip's bf16 peak. A dozen rows against 20 GB of
    weights a step: a few per cent, and bandwidth's, not a fault."""
    if trace is None or not _is_record(record):
        return None
    busy = reduce.busy_seconds(trace, _win(record))
    rows = _tail(record, "rows_global_sum")
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    buckets = _traced_buckets(record)
    # a token a decoded row, but each admission's first: the prefill's
    slot_steps = sum(c.tokens for c in record["cycles"]
                     if lo <= c.end <= hi) - len(buckets)
    if not busy or slot_steps <= 0 or rows is None:
        return None
    flops = flops_loop.step_flops(slot_steps, rows, buckets,
                                  record["model"])
    return 100.0 * flops / (busy * record["peaks"]["bf16_flops_per_s"])


def loop_rows_read_per_step(record, trace=None) -> Optional[float]:
    """``serving/attn_rows_read.global``: context rows ONE cache layer read
    in a decode step over the live slots, mean over the window's steps."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "steps_n")
    return _delta(samples, inside, "rows_global_sum") / n if n else None

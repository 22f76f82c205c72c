"""Metrics of the latent-attention decoder's cell: the latent paged kernel
and the sparse block (router, routed experts held here, shared expert) in
the device trace, and the counters the driver sampled after every cycle
(``drivers/serve_mla.Sample``).

An operation is told by what survives in the profile's event text (the
``jax.named_scope`` names reach the HLO and not that text): the latent
kernel is a Pallas call named ``mla_latent_decode``, the grouped matmul the
compiler makes of ``ragged_dot`` is a kernel named ``ragged-dot...``, and
the router's and the shared expert's matrix products carry their widths
(``n_routed_experts`` as published, ``moe_intermediate_size``) in their
result or operand shapes; besides by the scope names (``attn/mla``,
``moe/router``, ``moe/experts``, ``moe/shared``) where a text does carry
them. A reader that finds no such operation, or a record without the
samples (the parent of the PR that added this file has neither the kernel
nor the counters), returns nothing."""

from __future__ import annotations

import re
from typing import List, Optional

from .. import flops_mla, reduce
from .moe import DECODE_MODULE, _delta, _in, _win

KERNEL = "mla_latent_decode"


def _is_mla(o) -> bool:
    return o.module == DECODE_MODULE and KERNEL in o.text


def _is_routed(record):
    """The routed experts' operations: the grouped-matmul kernels, and the
    loop over a share's passes that holds them with their gathers and the
    scatter-add (a ``while`` whose carried tuple has the held experts'
    ``[E_held, d, f]`` weights)."""
    m = record["model"]
    held = "[%d,%d,%d]" % (int(m["n_routed_experts"]), int(m["hidden_size"]),
                           int(m["moe_intermediate_size"]))

    def pred(o):
        return o.module == DECODE_MODULE and (
            "ragged-dot" in o.text or "ragged_dot" in o.text
            or "moe/experts" in o.text
            or (o.opcode == "while" and held in o.text))

    return pred


def _sparse_shapes(record) -> List[re.Pattern]:
    """Shapes only the sparse block's dense products have: ``[slots, E]``
    (the router's scores and what is made of them) and ``[slots, f]`` (the
    shared expert's gate and up) or an operand ``[.., f]``/``[f, ..]`` of
    the hidden size."""
    m, slots = record["model"], int(record["slots"])
    e = int(m["published"]["n_routed_experts"])
    d, f = int(m["hidden_size"]), int(m["moe_intermediate_size"])
    return [re.compile(p) for p in (
        r"\[%d,%d\]" % (slots, e), r"\[%d,%d\]" % (d, e),
        r"\[%d,%d\]" % (slots, f), r"\[%d,%d\]" % (d, f),
        r"\[%d,%d\]" % (f, d))]


def _is_sparse(record):
    shapes = _sparse_shapes(record)
    routed = _is_routed(record)

    def pred(o):
        return o.module == DECODE_MODULE and (
            routed(o) or "moe/" in o.text
            or any(p.search(o.text) for p in shapes))

    return pred


def _is_mla_record(record) -> bool:
    return "kv_lora_rank" in record.get("model", {})


def _traced_live_rows(record) -> int:
    """Context rows the traced decode steps attended over: a slot whose
    context is P + n after the cycle (prompt and tokens emitted) read P +
    n - 1 rows in it, its newest token's row among them; so the context
    after each cycle, one less a slot, as ``readers/moe.py`` counts."""
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    return sum(c.context - c.occupancy for c in record["cycles"]
               if lo <= c.end <= hi)


def mla_paged_attn_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take for the traced decode steps'
    latent attention (``flops_mla.mla_decode_need_s``: the larger of live
    rows x layers x 576 values over the HBM rate and live rows x layers x
    64 heads x (576 + 512) x 2 operations over the bf16 peak) over the
    latent kernel's device time in the decode executable."""
    if trace is None or not _is_mla_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_mla, _win(record))
    rows = _traced_live_rows(record)
    if not kernel_s or not rows:
        return None
    need = flops_mla.mla_decode_need_s(rows, record["model"],
                                       record["peaks"])
    return 100.0 * need / kernel_s


def mla_attn_time_share(record, trace) -> Optional[float]:
    """The latent kernel's share of busy device time."""
    if trace is None or not _is_mla_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    kernel_s = reduce.time_where(trace, _is_mla, win)
    if not busy or not kernel_s:
        return None
    return 100.0 * kernel_s / busy


def held_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the HELD experts the traced decode steps
    touched (``serving/moe_experts_touched``) over the peak HBM rate, over
    the device time of the routed experts' operations of the decode
    executable (:func:`_is_routed`: the grouped matmuls with the loop that
    gathers their rows and adds their results)."""
    if trace is None or not _is_mla_record(record):
        return None
    samples, inside = _in(record, "tail_open", "tail_close")
    touched = _delta(samples, inside, "touched_sum")
    routed_s = reduce.time_where(trace, _is_routed(record), _win(record))
    if not touched or not routed_s:
        return None
    need = flops_mla.held_expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / routed_s


def sparse_block_time_share(record, trace) -> Optional[float]:
    """Share of busy device time in the decode executable's router, routed
    expert and shared expert operations."""
    if trace is None or not _is_mla_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    sparse_s = reduce.time_where(trace, _is_sparse(record), win)
    if not busy or not sparse_s:
        return None
    return 100.0 * sparse_s / busy


def held_experts_touched_per_layer_mean(record, trace=None
                                        ) -> Optional[float]:
    """Held experts with at least one live row, an expert layer a decode
    step, mean over the window."""
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "touched_n")
    return _delta(samples, inside, "touched_sum") / n if n else None


def latent_pages_used_share(record, trace=None) -> Optional[float]:
    """Pages of the latent cache in use after each cycle, mean over the
    window, over the pool."""
    if "latent" not in record.get("pools", {}):
        return None
    samples, inside = _in(record, "open", "close")
    if not inside:
        return None
    used = sum(samples[i].pages_used for i in inside) / len(inside)
    return 100.0 * used / record["pools"]["latent"]

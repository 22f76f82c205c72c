"""Metrics of the hybrid decoder's cell (KDA layers beside an MLA layer):
the recurrent-state kernel, the prefill's chunk scan, the latent kernel and
the routed experts held here in the device trace, and the counters the
driver sampled after every cycle (``drivers/serve_hybrid.Sample``).

An operation is told by what survives in the profile's event text (the
``jax.named_scope`` names reach the HLO and not that text, which does
carry result and operand shapes): the state kernel is a Pallas call named
``kda_state_step``; the chunk scan is the ``while`` of the prefill
executable that carries the ``[H, dk, dv]`` float32 state; the latent
kernel is ``mla_latent_decode``; the routed experts are the ``ragged-dot``
kernels and the ``while`` that carries the held experts' ``[E_held, d,
f]`` weights; a KDA layer's dense products have 3 x 4096 or 4096 columns
against the hidden size. A reader that finds no such operation, or a
record without the samples (the parent of the PR that added this file has
neither the kernels nor the counters), returns nothing."""

from __future__ import annotations

import re
from typing import Optional

from .. import flops_hybrid, flops_mla, reduce
from .moe import DECODE_MODULE, _delta, _in, _win

STEP_KERNEL = "kda_state_step"
LATENT_KERNEL = "mla_latent_decode"
PREFILL_MODULE = "jit_prefill"


def _is_record(record) -> bool:
    return ("samples" in record
            and "kda_lower_bound" in record.get("model", {})
            and "layer_types" in record["model"])


def _is_step(o) -> bool:
    return o.module == DECODE_MODULE and STEP_KERNEL in o.text


def _is_latent(o) -> bool:
    return o.module == DECODE_MODULE and LATENT_KERNEL in o.text


def _state_shape(record) -> str:
    m = record["model"]
    return "f32[%d,%d,%d]" % (int(m["num_attention_heads"]),
                              int(m["head_dim"]), int(m["head_dim"]))


def _is_scan(record):
    """The chunk scan's loops: a ``while`` of a prefill executable whose
    carried tuple holds the float32 state."""
    state = _state_shape(record)

    def pred(o):
        return (o.module.startswith(PREFILL_MODULE) and o.opcode == "while"
                and state in o.text)

    return pred


def _is_routed(record):
    """The routed experts' operations, as ``readers/mla.py`` tells them,
    under this configuration's key for the experts held."""
    m = record["model"]
    held = "[%d,%d,%d]" % (int(m["num_experts"]), int(m["hidden_size"]),
                           int(m["moe_intermediate_size"]))

    def pred(o):
        return o.module == DECODE_MODULE and (
            "ragged-dot" in o.text or "ragged_dot" in o.text
            or "moe/experts" in o.text
            or (o.opcode == "while" and held in o.text))

    return pred


def _is_kda(record):
    """What runs under ``attn/kda`` in the decode executable but the
    output projections: the state kernel, and every operation whose text
    holds a shape only a KDA layer has: ``3C`` or ``C`` columns (C = heads
    x head_dim) beside the hidden size or the slots (the fused q, k, v
    product, the tail, the convolution, the decay's product), or the
    kernel's packed ``[slots, ., head_dim, 128]`` columns."""
    m, slots = record["model"], int(record["slots"])
    d = int(m["hidden_size"])
    hd = int(m["head_dim"])
    c = int(m["num_attention_heads"]) * hd
    shapes = [re.compile(p) for p in (
        r"\[%d,%d\]" % (d, 3 * c), r"\[%d,%d\]" % (slots, 3 * c),
        r"\[\d+,%d,\d+,%d\]" % (slots, 3 * c),
        r"\[%d,\d+,%d\]" % (slots, 3 * c), r"\[%d,%d\]" % (d, c),
        r"\[%d,\d+,%d,128\]" % (slots, hd))]

    def pred(o):
        return o.module == DECODE_MODULE and (
            STEP_KERNEL in o.text or "attn/kda" in o.text
            or any(p.search(o.text) for p in shapes))

    return pred


def _is_out_proj(record):
    """The attention halves' output projections ``[C, d]``: a KDA layer's
    and the MLA layer's have one shape (32 x 128 values a token onto the
    hidden size)."""
    m = record["model"]
    c = int(m["num_attention_heads"]) * int(m["head_dim"])
    shape = "[%d,%d]" % (c, int(m["hidden_size"]))
    routed = _is_routed(record)

    def pred(o):
        return (o.module == DECODE_MODULE and shape in o.text
                and not routed(o))

    return pred


def kda_state_step_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take to stream the traced decode
    steps' recurrent states (``flops_hybrid.kda_step_need_s``: live slots
    x KDA layers x the state read and written and the step's vectors, over
    the HBM rate) over the ``kda_state_step`` kernel's device time in the
    decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_step, _win(record))
    samples, inside = _in(record, "tail_open", "tail_close")
    stepped = _delta(samples, inside, "stepped_sum")
    if not kernel_s or not stepped:
        return None
    need = flops_hybrid.kda_step_need_s(stepped, record["model"],
                                        record["peaks"])
    return 100.0 * need / kernel_s


def kda_chunk_scan_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take for the recurrence over the
    prompts prefilled in the traced stretch (``flops_hybrid
    .kda_scan_need_s`` over their tokens) over the device time of the
    chunk scan's loops in the prefill executables."""
    if trace is None or not _is_record(record):
        return None
    scan_s = reduce.time_where(trace, _is_scan(record), _win(record))
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    prompts = [tr.req.prompt_len for tr in record["tracked"]
               if tr.req is not None and not tr.refused
               and tr.req.admitted_t is not None
               and lo <= tr.req.admitted_t <= hi]
    if not scan_s or not prompts:
        return None
    need = flops_hybrid.kda_scan_need_s(sum(prompts), len(prompts),
                                        record["model"], record["peaks"])
    return 100.0 * need / scan_s


def kda_time_share(record, trace) -> Optional[float]:
    """Device time of everything under ``attn/kda`` in the decode
    executable over busy device time: what only a KDA layer runs
    (:func:`_is_kda`) and, of the output projections, whose shape the MLA
    layer shares, the KDA layers' part by count."""
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    kda = _is_kda(record)
    own_s = reduce.time_where(trace, kda, win)
    if not busy or not own_s:
        return None
    out = _is_out_proj(record)
    out_s = reduce.time_where(trace, lambda o: out(o) and not kda(o), win)
    n_kda = flops_hybrid.layers_of(record["model"], flops_hybrid.KDA)
    n_mla = flops_hybrid.layers_of(record["model"], flops_hybrid.MLA)
    return 100.0 * (own_s + out_s * n_kda / (n_kda + n_mla)) / busy


def hybrid_latent_attn_roofline(record, trace) -> Optional[float]:
    """``flops_mla``'s need over the rows ONE latent layer read in the
    traced decode steps (``serving/attn_rows_read.latent``), the MLA
    layers counted from ``layer_types``, over the latent kernel's device
    time in the decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_latent, _win(record))
    samples, inside = _in(record, "tail_open", "tail_close")
    rows = _delta(samples, inside, "rows_latent_sum")
    if not kernel_s or not rows:
        return None
    need = flops_hybrid.latent_decode_need_s(rows, record["model"],
                                             record["peaks"])
    return 100.0 * need / kernel_s


def quarter_share_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the HELD experts the traced decode steps
    touched (``serving/moe_experts_touched``;
    ``flops_mla.held_expert_stream_bytes``) over the peak HBM rate, over
    the device time of the routed experts' operations of the decode
    executable."""
    if trace is None or not _is_record(record):
        return None
    samples, inside = _in(record, "tail_open", "tail_close")
    touched = _delta(samples, inside, "touched_sum")
    routed_s = reduce.time_where(trace, _is_routed(record), _win(record))
    if not touched or not routed_s:
        return None
    need = flops_mla.held_expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / routed_s


def state_slots_stepped_mean(record, trace=None) -> Optional[float]:
    """``serving/state_slots_stepped``: live slots whose states a decode
    step advanced, mean over the window's steps."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "stepped_n")
    return _delta(samples, inside, "stepped_sum") / n if n else None

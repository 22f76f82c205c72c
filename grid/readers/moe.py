"""Metrics of the sparse decoder's cell: the expert layers and the grouped
paged-attention kernel in the device trace, and the counters the driver
sampled after every cycle (``drivers/serve_moe.Sample``).

An operation is told by the ``jax.named_scope`` the program gave it
(``moe/router``, ``moe/experts``, ``attn/global``, ``attn/window``) where
the trace's event text carries it, and besides by what survives without:
the grouped matmul the compiler makes of ``ragged_dot`` is a kernel named
``ragged-dot...``, the paged kernel is named ``paged_attention``, and an
expert operand has the expert dimensions ``[E, d, f]`` in its text. A
reader that finds no such operation, or a record without the samples,
returns nothing."""

from __future__ import annotations

from typing import List, Optional

from .. import flops_moe, reduce

DECODE_MODULE = "jit_chunk"
PALLAS = 'custom_call_target="tpu_custom_call"'


def _win(record):
    return tuple(record["trace_window"])


def _expert_dims(record) -> List[str]:
    m = record["model"]
    e, d, f = (int(m["moe_num_primary_experts"]), int(m["hidden_size"]),
               int(m["moe_ffn_hidden_size"]))
    return ["[%d,%d,%d]" % (e, d, f), "[%d,%d,%d]" % (e, f, d)]


def _is_expert_op(record):
    dims = _expert_dims(record)

    def pred(o):
        return o.module == DECODE_MODULE and (
            "moe/experts" in o.text or "ragged-dot" in o.text
            or "ragged_dot" in o.text or any(d in o.text for d in dims))

    return pred


def _in(record, lo_key: str, hi_key: str):
    lo, hi = record["marks"][lo_key], record["marks"][hi_key]
    s = record.get("samples") or []
    inside = [i for i, x in enumerate(s) if lo <= x.end <= hi]
    return s, inside


def moe_time_share(record, trace) -> Optional[float]:
    """Share of busy device time in the decode executable's operations
    under ``moe/`` (router and experts)."""
    if trace is None or "model" not in record \
            or "moe_num_primary_experts" not in record["model"]:
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    expert = _is_expert_op(record)
    moe_s = reduce.time_where(
        trace, lambda o: o.module == DECODE_MODULE
        and ("moe/" in o.text or expert(o)), win)
    if not busy or not moe_s:
        return None
    return 100.0 * moe_s / busy


def _delta(samples, inside, field) -> Optional[float]:
    """Growth of a cumulative counter over the cycles ``inside``: from the
    sample before the first of them to the last."""
    if not inside or inside[0] == 0:
        return None
    return (getattr(samples[inside[-1]], field)
            - getattr(samples[inside[0] - 1], field))


def moe_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the experts the traced decode steps touched
    (``serving/moe_experts_touched``) over the peak HBM rate, over the
    device time of every expert operation of the decode executable."""
    if trace is None or "samples" not in record:
        return None
    samples, inside = _in(record, "tail_open", "tail_close")
    touched = _delta(samples, inside, "touched_sum")
    expert_s = reduce.time_where(trace, _is_expert_op(record), _win(record))
    if not touched or not expert_s:
        return None
    need = flops_moe.expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / expert_s


def gqa_paged_attn_roofline(record, trace) -> Optional[float]:
    """Live K and V bytes of the traced decode steps (a global layer's
    whole context, a window layer's ``min(ctx, window)``, at the KV heads'
    width) over the peak HBM rate, over the paged kernel's device time."""
    if trace is None or "samples" not in record:
        return None
    kernel_s = reduce.time_where(
        trace, lambda o: o.module == DECODE_MODULE and PALLAS in o.text
        and "paged_attention" in o.text, _win(record))
    samples, inside = _in(record, "tail_open", "tail_close")
    if not kernel_s or not inside:
        return None
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    # what each traced step attended over: the live slots' lengths BEFORE
    # the step, one less a slot than after it
    global_ctx = sum(c.context - c.occupancy for c in record["cycles"]
                     if lo <= c.end <= hi)
    window_ctx = sum(samples[i].window_ctx for i in inside)
    need = flops_moe.grouped_kv_bytes(global_ctx, window_ctx,
                                      record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / kernel_s


def experts_touched_per_layer_mean(record, trace=None) -> Optional[float]:
    """Experts with at least one live row, a layer a decode step, mean over
    the window."""
    if "samples" not in record:
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "touched_n")
    return _delta(samples, inside, "touched_sum") / n if n else None


def _pages_share(group: str):
    def read(record, trace=None) -> Optional[float]:
        if "samples" not in record or group not in record.get("pools", {}):
            return None
        samples, inside = _in(record, "open", "close")
        if not inside:
            return None
        used = sum(samples[i].pages_used[group] for i in inside) / len(inside)
        return 100.0 * used / record["pools"][group]

    read.__name__ = "kv_pages_used_share_" + group
    read.__doc__ = ("Pages of the %s cache group in use after each cycle, "
                    "mean over the window, over the group's pool." % group)
    return read


kv_pages_used_share_global = _pages_share("global")
kv_pages_used_share_window = _pages_share("window")


def admit_blocked_on_pages_share(record, trace=None) -> Optional[float]:
    """Share of the window's cycles in which the head of the queue had a
    free slot and a cache group had no pages for it."""
    if "samples" not in record:
        return None
    samples, inside = _in(record, "open", "close")
    blocked = _delta(samples, inside, "blocked")
    return None if blocked is None else 100.0 * blocked / len(inside)

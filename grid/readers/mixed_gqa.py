"""Metrics of the cell whose decoder has query heads by layer type: the
paged kernel at each cache group's query shape and the routed experts held
here in the device trace, and the counters the driver sampled after every
cycle (``drivers/serve_mixed_gqa.Sample``).

An operation is told by what survives in the profile's event text: the
paged kernel is a Pallas call named ``paged_attention`` whose RESULT is
``[slots, rows, n_kv * head_dim]`` with ``rows`` the group's query heads a
KV head padded to whole sublanes (``flops_laguna.kernel_query_rows``: 8 at
6, 16 at 9), so the two groups' calls differ in shape; the grouped matmul
the compiler makes of ``ragged_dot`` is a kernel named ``ragged-dot...``,
and the loop over a share's passes is a ``while`` that carries the held
experts' ``[E_held, d, f]`` weights; besides by the scope names
(``moe/routed``, ``moe/experts``) where a text does carry them. A reader
that finds no such operation, or a record without the samples or the
groups' query heads (the parent of the PR that added this file has neither
the model nor the counters), returns nothing."""

from __future__ import annotations

from typing import Optional

from .. import flops_laguna, reduce
from .moe import DECODE_MODULE, PALLAS, _delta, _in, _win

KERNEL = "paged_attention"


def _is_record(record) -> bool:
    return ("q_per_kv" in record and "samples" in record
            and "num_attention_heads_per_layer" in record.get("model", {}))


def _is_kernel(record, group: str):
    """The paged kernel's calls at ``group``'s query shape."""
    m = record["model"]
    shape = "[%d,%d,%d]" % (
        int(record["slots"]),
        flops_laguna.kernel_query_rows(int(record["q_per_kv"][group])),
        int(m["num_key_value_heads"]) * int(m["head_dim"]))

    def pred(o):
        return (o.module == DECODE_MODULE and PALLAS in o.text
                and KERNEL in o.text and o.shape.endswith(shape))

    return pred


def _is_routed(record):
    """The routed experts' operations: the grouped-matmul kernels, and the
    loop over the share's passes that holds them with their gathers and
    the scatter-add."""
    m = record["model"]
    held = "[%d,%d,%d]" % (int(m["num_experts"]), int(m["hidden_size"]),
                           int(m["moe_intermediate_size"]))

    def pred(o):
        return o.module == DECODE_MODULE and (
            "ragged-dot" in o.text or "ragged_dot" in o.text
            or "moe/routed" in o.text or "moe/experts" in o.text
            or (o.opcode == "while" and held in o.text))

    return pred


def _roofline(group: str):
    def read(record, trace) -> Optional[float]:
        if trace is None or not _is_record(record) \
                or group not in record["q_per_kv"]:
            return None
        kernel_s = reduce.time_where(trace, _is_kernel(record, group),
                                     _win(record))
        samples, inside = _in(record, "tail_open", "tail_close")
        rows = _delta(samples, inside, "rows_%s_sum" % group)
        if not kernel_s or not rows:
            return None
        need = flops_laguna.attn_need_s(rows, record["model"], group,
                                        record["peaks"])
        return 100.0 * need / kernel_s

    read.__name__ = "mixed_gqa_attn_roofline_" + group
    read.__doc__ = (
        "The least time the chip could take for the traced decode steps' "
        "%s layers' attention (flops_laguna.attn_need_s: the live K and V "
        "rows the group's counter read, a layer, over the HBM rate, or the "
        "operations over the bf16 peak where larger) over the paged "
        "kernel's device time at that group's query shape." % group)
    return read


mixed_gqa_attn_roofline_global = _roofline("global")
mixed_gqa_attn_roofline_window = _roofline("window")


def _share(record, trace, pred) -> Optional[float]:
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    part = reduce.time_where(trace, pred, win)
    if not busy or not part:
        return None
    return 100.0 * part / busy


def global_attn_time_share(record, trace) -> Optional[float]:
    """The paged kernel's calls at the full layers' query shape as a share
    of busy device time."""
    if trace is None or not _is_record(record) \
            or "global" not in record["q_per_kv"]:
        return None
    return _share(record, trace, _is_kernel(record, "global"))


def routed_block_time_share(record, trace) -> Optional[float]:
    """The routed experts' operations of the decode executable as a share
    of busy device time."""
    if trace is None or not _is_record(record):
        return None
    return _share(record, trace, _is_routed(record))


def half_share_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the HELD experts the traced decode steps
    touched (``serving/moe_experts_touched``) over the peak HBM rate, over
    the device time of the routed experts' operations of the decode
    executable."""
    if trace is None or not _is_record(record):
        return None
    samples, inside = _in(record, "tail_open", "tail_close")
    touched = _delta(samples, inside, "touched_sum")
    routed_s = reduce.time_where(trace, _is_routed(record), _win(record))
    if not touched or not routed_s:
        return None
    need = flops_laguna.held_expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / routed_s


def half_share_experts_touched_per_layer_mean(record, trace=None
                                              ) -> Optional[float]:
    """Held experts with at least one live row, an expert layer a decode
    step, mean over the window."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "touched_n")
    return _delta(samples, inside, "touched_sum") / n if n else None


def _rows_per_step(group: str):
    def read(record, trace=None) -> Optional[float]:
        if not _is_record(record):
            return None
        samples, inside = _in(record, "open", "close")
        n = _delta(samples, inside, "rows_n")
        return _delta(samples, inside, "rows_%s_sum" % group) / n \
            if n else None

    read.__name__ = "attn_rows_read_per_step_" + group
    read.__doc__ = ("serving/attn_rows_read.%s: context rows one layer of "
                    "the %s cache group read in a decode step over the live "
                    "slots, mean over the window's steps." % (group, group))
    return read


attn_rows_read_per_step_global = _rows_per_step("global")
attn_rows_read_per_step_window = _rows_per_step("window")

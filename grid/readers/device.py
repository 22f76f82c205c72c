"""Metrics from the reduced profiler trace (``grid/reduce.py``) and from
the device's memory statistics."""

from __future__ import annotations

import re
from typing import Optional

from .. import flops, reduce

PALLAS = 'custom_call_target="tpu_custom_call"'
DECODE_MODULE = "jit_chunk"
TRAIN_MODULE = "jit_step"


def _win(record):
    return tuple(record["trace_window"])


def device_idle_share(record, trace) -> Optional[float]:
    if trace is None or not trace.ops:
        return None
    return 100.0 * reduce.idle_share(trace, _win(record))


def hbm_peak_gb(record, trace=None) -> float:
    """Held buffers at their peak plus the largest program's scratch
    (``runtime.memory``): the last line's ``memory_peak_bytes``."""
    return sum(record["memory"].values()) / 1e9


def hbm_scratch_gb(record, trace=None) -> float:
    """The compiler's temporaries of the largest program the cell ran."""
    return record["memory"]["scratch"] / 1e9


def _share(trace, record, pred) -> float:
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    if not busy:
        return None
    return 100.0 * reduce.time_where(trace, pred, win) / busy


def pallas_time_share(record, trace) -> Optional[float]:
    """Pallas kernels (``tpu_custom_call``) over device busy time."""
    if trace is None:
        return None
    return _share(trace, record, lambda o: PALLAS in o.text)


def pool_copy_time_share(record, trace) -> Optional[float]:
    """Copies and slices whose result or operand is the whole KV pool of
    one layer or of all layers, over device busy time."""
    if trace is None:
        return None
    rows = str(record["pool_rows"])
    pool = re.compile(r"\[(\d+,)?%s,\d+,\d+\]" % rows)

    def whole_pool(o):
        return o.opcode in ("copy", "slice") and bool(pool.search(o.text))

    return _share(trace, record, whole_pool)


def paged_attn_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take to read the live K and V rows
    of the traced decode steps (bytes from the slots' context lengths over
    the peak HBM rate; the kernel is bandwidth-bound) over the device time
    of the decode executable's Pallas kernel. The program gives that
    kernel no name of its own (the trace shows ``closed_call``): it is
    told by being the ``tpu_custom_call`` of ``jit_chunk``."""
    if trace is None:
        return None
    win = _win(record)
    kernel_s = reduce.time_where(
        trace, lambda o: o.module == DECODE_MODULE and PALLAS in o.text, win)
    if not kernel_s:
        return None
    lo, hi = record["marks"]["tail_open"], record["marks"]["tail_close"]
    # context each traced cycle's decode step attended over: the live
    # slots' lengths BEFORE the step, one less a slot than after it
    ctx = sum(c.context - c.occupancy for c in record["cycles"]
              if lo <= c.start < hi)
    m = record["model"]
    need = flops.paged_attention_kv_bytes(
        ctx, m["n_layer"], m["n_head"], m["n_embd"] // m["n_head"], 2)
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / kernel_s


def step_device_ms(record, trace) -> Optional[float]:
    """Device busy time a run of the step executable, per chip."""
    if trace is None:
        return None
    runs = reduce.module_runs(trace, TRAIN_MODULE, _win(record))
    if not runs:
        return None
    whole = (runs[0][0], runs[-1][1])
    busy = reduce.time_where(trace, lambda o: o.module == TRAIN_MODULE, whole)
    return busy * 1e3 / len(runs)


def allreduce_exposed_ms_per_step(record, trace) -> Optional[float]:
    if trace is None:
        return None
    runs = reduce.module_runs(trace, TRAIN_MODULE, _win(record))
    if not runs or not any(reduce.is_collective(o)
                           for ops in trace.ops.values() for o in ops):
        return None
    whole = (runs[0][0], runs[-1][1])
    return reduce.exposed_collective_seconds(trace, whole) * 1e3 / len(runs)


def train_mfu(record, trace) -> Optional[float]:
    """Model FLOP/s utilization of the traced stretch: operations a token
    times the tokens a second of the steps that ran in it (from the start
    of the first whole step to the start of the last), over chips times
    peak."""
    if trace is None:
        return None
    runs = reduce.module_runs(trace, TRAIN_MODULE, _win(record))
    if len(runs) < 2:
        return None
    per_token = flops.train_flops_per_token(record["model"], record["seq"])
    rate = (len(runs) - 1) * record["tokens_per_step"] \
        / (runs[-1][0] - runs[0][0])
    return 100.0 * per_token * rate / (
        record["chips"] * record["peaks"]["bf16_flops_per_s"])

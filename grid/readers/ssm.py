"""Metrics of the parallel hybrid's cell (a Mamba-2 state beside a GQA page
pool in every layer): the state-step kernel, the prefill's chunk scan and
the paged kernel in the device trace, the shares of the step's parts, the
step's share of the chip's peak, and the counters the driver sampled after
every cycle (``drivers/serve_ssm.Sample``).

The kernels are told by name in the profile's event text
(``ssd_state_step``, ``paged_attention``; the scan by its kernel's name
``ssd_chunk_scan`` AND by the ``while`` of a prefill executable that
carries a layer's ``[H, N, P]`` float32 state, so that whichever form ran,
and work a later PR moves out of the loop, is still told). Everything else
is told BY NAME from the executables' own text, whose metadata keeps the
``jax.named_scope`` names that the event text drops
(``readers/gdla.scoped_instructions``; the driver writes the names into
``record["scoped_ops"]``, a list a scope a module). A reader that finds no
such operation, or a record without the samples (the parent of the PR that
added this file has neither the model nor the counters), returns
nothing."""

from __future__ import annotations

import bisect
from typing import List, Optional

from .. import flops_ssm, reduce
from .moe import DECODE_MODULE, PALLAS, _delta, _in, _win

STEP_KERNEL = "ssd_state_step"
SCAN_KERNEL = "ssd_chunk_scan"
PAGED_KERNEL = "paged_attention"
PREFILL_MODULE = "jit_prefill"
SSM_SCOPES = ("mixer/ssm_in", "mixer/ssm_conv", "mixer/ssm_step",
              "mixer/ssm_scan", "mixer/ssm_norm", "mixer/out/ssm")
MLP_SCOPE, HEAD_SCOPE = "mlp", "lm_head"
SCOPES = SSM_SCOPES + (MLP_SCOPE, HEAD_SCOPE)


def _is_record(record) -> bool:
    return ("samples" in record
            and "mamba_d_state" in record.get("model", {}))


def _is_step(o) -> bool:
    return o.module == DECODE_MODULE and STEP_KERNEL in o.text


def _is_paged(o) -> bool:
    return (o.module == DECODE_MODULE and PALLAS in o.text
            and PAGED_KERNEL in o.text)


def _is_scan(record):
    """The chunk scan: the kernel's calls, and the loops of a prefill
    executable whose carried tuple holds a layer's float32 state."""
    m = record["model"]
    state = "f32[%d,%d,%d]" % (int(m["mamba_n_heads"]),
                               int(m["mamba_d_state"]),
                               int(m["mamba_d_head"]))

    def pred(o):
        return o.module.startswith(PREFILL_MODULE) and (
            SCAN_KERNEL in o.text
            or (o.opcode == "while" and state in o.text))

    return pred


def _scoped(record, *scopes):
    """The events named by the instructions that run under ``scopes`` in
    the decode or a prefill executable."""
    by_module = record.get("scoped_ops") or {}
    named = {module: frozenset(n for s in scopes
                               for n in ops.get(s, ()))
             for module, ops in by_module.items()}

    def pred(o):
        module = DECODE_MODULE if o.module == DECODE_MODULE else (
            PREFILL_MODULE if o.module.startswith(PREFILL_MODULE) else None)
        return module in named and o.name in named[module]

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


def ssd_state_step_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take to stream the traced decode
    steps' recurrent states (``flops_ssm.ssd_step_need_s``: live slots x
    layers x the state read and written, over the HBM rate) over the
    ``ssd_state_step`` kernel's device time in the decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_step, _win(record))
    stepped = _tail(record, "stepped_sum")
    if not kernel_s or not stepped:
        return None
    return 100.0 * flops_ssm.ssd_step_need_s(
        stepped, record["model"], record["peaks"]) / kernel_s


def ssd_chunk_scan_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take for the recurrence over the
    rows the traced stretch's prefills computed (their buckets;
    ``flops_ssm.ssd_scan_need_s``) over the device time of the chunk scan
    in the prefill executables."""
    if trace is None or not _is_record(record):
        return None
    scan_s = reduce.time_where(trace, _is_scan(record), _win(record))
    buckets = _traced_buckets(record)
    if not scan_s or not buckets:
        return None
    return 100.0 * flops_ssm.ssd_scan_need_s(
        sum(buckets), len(buckets), record["model"], record["peaks"]) / scan_s


def parallel_gqa_attn_roofline(record, trace) -> Optional[float]:
    """``flops_ssm.gqa_decode_need_s`` over the rows ONE layer read in the
    traced decode steps (``serving/attn_rows_read.global``) over the paged
    kernel's device time in the decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_paged, _win(record))
    rows = _tail(record, "rows_global_sum")
    if not kernel_s or not rows:
        return None
    return 100.0 * flops_ssm.gqa_decode_need_s(
        rows, record["model"], record["peaks"]) / kernel_s


def _share(record, trace, pred) -> Optional[float]:
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    part = reduce.time_where(trace, pred, win)
    if not busy or not part:
        return None
    return 100.0 * part / busy


def ssd_time_share(record, trace) -> Optional[float]:
    """Device time of the SSM branch (the state-step kernel, the chunk
    scan, the convolution, the gated norm and the branch's two
    projections, in the decode and the prefill executables) over busy
    device time in the traced stretch."""
    if not _is_record(record):
        return None
    scoped, scan = _scoped(record, *SSM_SCOPES), _is_scan(record)
    return _share(record, trace,
                  lambda o: _is_step(o) or scan(o) or scoped(o))


def dense_mlp_time_share(record, trace) -> Optional[float]:
    """Device time of the dense SwiGLU (scope ``mlp``) over busy device
    time in the traced stretch."""
    if not _is_record(record):
        return None
    return _share(record, trace, _scoped(record, MLP_SCOPE))


def head_time_share(record, trace) -> Optional[float]:
    """Device time of the final norm and the untied head (scope
    ``lm_head``) over busy device time in the traced stretch: what the
    cut's whole vocabulary beside five layers is on the chip."""
    if not _is_record(record):
        return None
    return _share(record, trace, _scoped(record, HEAD_SCOPE))


def falcon_h1_step_mfu(record, trace) -> Optional[float]:
    """The model's operations for what the traced stretch computed
    (``flops_ssm.step_flops``: the live slot-steps through the layers and
    the head with the context rows their attention read, and each prefill
    as its bucket computes it) over busy device seconds times the chip's
    bf16 peak."""
    if trace is None or not _is_record(record):
        return None
    busy = reduce.busy_seconds(trace, _win(record))
    stepped, rows = (_tail(record, "stepped_sum"),
                     _tail(record, "rows_global_sum"))
    if not busy or not stepped or rows is None:
        return None
    flops = flops_ssm.step_flops(stepped, rows, _traced_buckets(record),
                                 record["model"])
    return 100.0 * flops / (busy * record["peaks"]["bf16_flops_per_s"])


def ssd_state_slots_stepped_mean(record, trace=None) -> Optional[float]:
    """``serving/state_slots_stepped``: live slots whose states a decode
    step advanced (every layer's), mean over the window's steps."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "stepped_n")
    return _delta(samples, inside, "stepped_sum") / n if n else None


def parallel_gqa_rows_read_per_step(record, trace=None) -> Optional[float]:
    """``serving/attn_rows_read.global``: context rows one layer read in a
    decode step over the live slots, mean over the window's steps."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "stepped_n")
    return _delta(samples, inside, "rows_global_sum") / n if n else None

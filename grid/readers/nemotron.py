"""Metrics of the cell whose layers are ONE part each (Nemotron-3-Nano: a
Mamba-2 mixer of 64-lane heads, an ungated expert layer or grouped-query
attention): the experts' decode pass, the state step and the prefill's
chunk scan in the device trace, the shares of the three kinds of part, the
step's share of the chip's peak, and the counters the driver sampled after
every cycle (``drivers/serve_nemotron.Sample``). (ISSUE 65 named two more,
the ``*`` layer's share of busy time and the experts touched a layer-step;
``BENCHMARK.json`` may hold 128 per-layer metrics and held 121: the window
note of a run prints both numbers, ``experts_touched_mean`` and the
scopes' times, and no metric reads them.)

Every operation is told BY THE WORK, not by a kernel's name: by the
``jax.named_scope`` it was traced under (``nemotron/mamba`` with
``ssm_step`` and ``ssm_scan`` inside it, ``nemotron/moe`` with the expert
layer's own ``moe/experts`` inside it, ``nemotron/attn``, ``lm_head``),
which the executables' own text keeps in its metadata
(``readers/gdla.scoped_instructions``; the driver writes the names into
``record["scoped_ops"]``, a list a scope a module). So a roofline share
reads the same work whether a Pallas kernel (``ragged_dot_stream``,
``ssd_state_step``, ``ssd_chunk_scan``) or the XLA form implements it, and
``nemotron_expert_pass_stream_share`` says which form the decode
executable's expert passes were traced in. A reader that finds no such
operation, or a record without the samples (the parent of the PR that added
this file has neither the model nor the counters), returns nothing."""

from __future__ import annotations

import bisect
from typing import List, Optional

from .. import flops_nemotron, reduce
from .moe import DECODE_MODULE, _delta, _in, _win

PREFILL_MODULE = "jit_prefill"
MAMBA_SCOPE, MOE_SCOPE, ATTN_SCOPE = ("nemotron/mamba", "nemotron/moe",
                                      "nemotron/attn")
STEP_SCOPE, SCAN_SCOPE = MAMBA_SCOPE + "/ssm_step", MAMBA_SCOPE + "/ssm_scan"
EXPERTS_SCOPE = MOE_SCOPE + "/moe/experts"
HEAD_SCOPE = "lm_head"
SCOPES = (MAMBA_SCOPE, MOE_SCOPE, ATTN_SCOPE, STEP_SCOPE, SCAN_SCOPE,
          EXPERTS_SCOPE, HEAD_SCOPE)


def _is_record(record) -> bool:
    return ("samples" in record
            and record.get("model", {}).get("model_type") == "nemotron_h")


def _scoped(record, module: Optional[str], *scopes):
    """The events named by the instructions that run under ``scopes`` in
    the decode or a prefill executable (``module``: one of the two, or
    None for both)."""
    by_module = record.get("scoped_ops") or {}
    named = {m: frozenset(n for s in scopes for n in ops.get(s, ()))
             for m, ops in by_module.items() if module in (None, m)}

    def pred(o):
        m = DECODE_MODULE if o.module == DECODE_MODULE else (
            PREFILL_MODULE if o.module.startswith(PREFILL_MODULE) else None)
        return m in named and o.name in named[m]

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


def nemotron_expert_stream_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take for the traced decode steps'
    routed experts (``flops_nemotron.expert_need_s``: the TOUCHED held
    experts' two matrices over the HBM rate, or the held pairs' operations
    where those were more) over the device time of the decode executable's
    operations under the expert layer's ``moe/experts`` scope."""
    if trace is None or not _is_record(record):
        return None
    expert_s = reduce.time_where(
        trace, _scoped(record, DECODE_MODULE, EXPERTS_SCOPE), _win(record))
    touched, pairs = (_tail(record, "touched_sum"),
                      _tail(record, "held_pairs_sum"))
    if not expert_s or not touched:
        return None
    return 100.0 * flops_nemotron.expert_need_s(
        touched, pairs or 0.0, record["model"], record["peaks"]) / expert_s


def nemotron_ssd_state_step_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take to stream the traced decode
    steps' recurrent states (``flops_nemotron.ssd_step_need_s``: live slots
    x ``M`` layers x the 2 MiB state read and written, over the HBM rate)
    over the device time of the decode executable's operations under
    ``ssm_step``."""
    if trace is None or not _is_record(record):
        return None
    step_s = reduce.time_where(
        trace, _scoped(record, DECODE_MODULE, STEP_SCOPE), _win(record))
    stepped = _tail(record, "stepped_sum")
    if not step_s or not stepped:
        return None
    return 100.0 * flops_nemotron.ssd_step_need_s(
        stepped, record["model"], record["peaks"]) / step_s


def nemotron_ssd_chunk_scan_roofline(record, trace) -> Optional[float]:
    """The least time the chip could take for the recurrence over the rows
    the traced stretch's prefills computed (their buckets;
    ``flops_nemotron.ssd_scan_need_s``) over the device time of the prefill
    executables' operations under ``ssm_scan``."""
    if trace is None or not _is_record(record):
        return None
    scan_s = reduce.time_where(
        trace, _scoped(record, PREFILL_MODULE, SCAN_SCOPE), _win(record))
    buckets = _traced_buckets(record)
    if not scan_s or not buckets:
        return None
    return 100.0 * flops_nemotron.ssd_scan_need_s(
        sum(buckets), len(buckets), record["model"], record["peaks"]) / scan_s


def _share(record, trace, scope: str) -> Optional[float]:
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    busy = reduce.busy_seconds(trace, win)
    part = reduce.time_where(trace, _scoped(record, None, scope), win)
    if not busy or not part:
        return None
    return 100.0 * part / busy


def nemotron_moe_time_share(record, trace) -> Optional[float]:
    """Device time of the ``E`` layers (router, routed experts, shared
    expert; decode and prefill executables) over busy device time in the
    traced stretch."""
    return _share(record, trace, MOE_SCOPE)


def nemotron_ssd_time_share(record, trace) -> Optional[float]:
    """Device time of the ``M`` layers (projections, convolution, state
    step or chunk scan, gated norm) over busy device time."""
    return _share(record, trace, MAMBA_SCOPE)


def nemotron3_step_mfu(record, trace) -> Optional[float]:
    """The model's operations for what the traced stretch computed
    (``flops_nemotron.step_flops``: the live slot-steps through the layers
    and the head with the pairs they sent held experts and the context rows
    their attention read, and each prefill as its bucket computes it) over
    busy device seconds times the chip's bf16 peak."""
    if trace is None or not _is_record(record):
        return None
    busy = reduce.busy_seconds(trace, _win(record))
    stepped, rows, pairs = (_tail(record, "stepped_sum"),
                            _tail(record, "rows_global_sum"),
                            _tail(record, "held_pairs_sum"))
    if not busy or not stepped or rows is None:
        return None
    flops = flops_nemotron.step_flops(stepped, rows, pairs or 0.0,
                                      _traced_buckets(record),
                                      record["model"])
    return 100.0 * flops / (busy * record["peaks"]["bf16_flops_per_s"])


def nemotron_expert_pass_stream_share(record, trace=None) -> Optional[float]:
    """``moe/pass_form.stream`` over ``.stream`` + ``.grouped`` as the
    DECODE executable's trace counted them (``record["decode_forms"]``):
    100 where every expert pass of a decode step takes the fused stream
    kernel, 0 where the gate refused the geometry and ``ragged_dot`` ran."""
    forms = record.get("decode_forms") if _is_record(record) else None
    if not forms:
        return None
    stream, grouped = (forms.get("moe/pass_form." + f, 0)
                       for f in ("stream", "grouped"))
    return 100.0 * stream / (stream + grouped) if stream + grouped else None

"""Metrics of the sparse latent hybrid's cell: the sparse read, the index
scoring and the selection, the KDA layers, the residual path and the routed
experts held here in the device trace, and the counters the driver sampled
after every cycle (``drivers/serve_dsa.Sample``).

The sparse read is a Pallas call named ``dsa_sparse_decode``; the state
step is ``kda_state_step``; the routed experts are the ``ragged_dot``
kernels and the ``while`` that carries the held experts' ``[E_held, d, f]``
weights. Everything else is told BY NAME from the decode executable's own
text, whose metadata keeps the ``jax.named_scope`` names that the
profile's event text drops (``readers/gdla.scoped_instructions``; the
driver writes the names into ``record["scoped_ops"]``, a list a scope). A
reader that finds no such operation, or a record without the samples (the
parent of the PR that added this file has neither the kernels nor the
counters), returns nothing."""

from __future__ import annotations

from typing import Optional

from .. import flops_dsa, reduce
from .moe import DECODE_MODULE, _delta, _in, _win

SPARSE_KERNEL = "dsa_sparse_decode"
STEP_KERNEL = "kda_state_step"
INDEX, SELECT, SPARSE = "attn/dsa_index", "attn/dsa_select", "attn/dsa_sparse"
KDA_SCOPE, MHC_SCOPE = "attn/kda", "residual/mhc"


def _is_record(record) -> bool:
    return ("samples" in record and "scoped_ops" in record
            and "index_kpool" in record.get("model", {})
            and "layer_types_held" in record["model"])


def _is_decode(o) -> bool:
    return o.module == DECODE_MODULE


def _is_sparse_kernel(o) -> bool:
    return o.module == DECODE_MODULE and SPARSE_KERNEL in o.text


def _is_step(o) -> bool:
    return o.module == DECODE_MODULE and STEP_KERNEL in o.text


def _is_routed(record):
    """The routed experts' operations of the decode executable."""
    m = record["model"]
    held = "[%d,%d,%d]" % (int(m["n_routed_experts"]), int(m["hidden_size"]),
                           int(m["moe_intermediate_size"]))

    def pred(o):
        return o.module == DECODE_MODULE and (
            "ragged-dot" in o.text or "ragged_dot" in o.text
            or "moe/experts" in o.text
            or (o.opcode == "while" and held in o.text))

    return pred


def _scoped(record, *scopes):
    """The decode executable's events named by the instructions that run
    under ``scopes``, less what the experts claim."""
    named = frozenset(n for s in scopes
                      for n in record["scoped_ops"].get(s, ()))
    routed = _is_routed(record)
    return lambda o: (o.module == DECODE_MODULE and o.name in named
                      and not routed(o))


def _tail(record, field: str) -> Optional[float]:
    samples, inside = _in(record, "tail_open", "tail_close")
    return _delta(samples, inside, field)


def dsa_sparse_attn_roofline(record, trace) -> Optional[float]:
    """``flops_dsa.sparse_read_need_s`` over the rows ONE DSA layer read in
    the traced decode steps (``serving/attn_rows_read.latent_sparse``: the
    rows the selection kept), over the ``dsa_sparse_decode`` kernel's
    device time in the decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_sparse_kernel, _win(record))
    rows = _tail(record, "rows_read_sum")
    if not kernel_s or not rows:
        return None
    return 100.0 * flops_dsa.sparse_read_need_s(
        rows, record["model"], record["peaks"]) / kernel_s


def dsa_index_roofline(record, trace) -> Optional[float]:
    """``flops_dsa.index_score_need_s`` over the closed blocks the traced
    decode steps scored (``serving/index_blocks_scored``), over the device
    time of what runs under ``attn/dsa_index`` in the decode executable
    (the index's projections, its rotation, the gather of a slot's keys
    and the scores)."""
    if trace is None or not _is_record(record):
        return None
    index_s = reduce.time_where(trace, _scoped(record, INDEX), _win(record))
    blocks = _tail(record, "scored_sum")
    if not index_s or not blocks:
        return None
    return 100.0 * flops_dsa.index_score_need_s(
        blocks, record["model"], record["peaks"]) / index_s


def _decode_share(record, trace, which) -> Optional[float]:
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    decode_s = reduce.time_where(trace, _is_decode, win)
    own_s = reduce.time_where(trace, which, win)
    if not decode_s or not own_s:
        return None
    return 100.0 * own_s / decode_s


def dsa_select_time_share(record, trace) -> Optional[float]:
    """Device time of what runs under ``attn/dsa_select`` (the top 511 of a
    slot's scores and the table of chosen blocks) over the decode
    executable's busy device time."""
    if trace is None or not _is_record(record):
        return None
    return _decode_share(record, trace, _scoped(record, SELECT))


def dsa_time_share(record, trace) -> Optional[float]:
    """Index, selection and sparse read (the kernel, and the table and row
    mask made for it) over the decode executable's busy device time."""
    if trace is None or not _is_record(record):
        return None
    scoped = _scoped(record, INDEX, SELECT, SPARSE)
    return _decode_share(record, trace,
                         lambda o: scoped(o) or _is_sparse_kernel(o))


def glm_kda_time_share(record, trace) -> Optional[float]:
    """Device time of what runs under ``attn/kda`` in the decode executable
    (the state kernel, the fused q, k, v product, the convolution, the
    decay, the output) over its busy device time."""
    if trace is None or not _is_record(record):
        return None
    scoped = _scoped(record, KDA_SCOPE)
    return _decode_share(record, trace, lambda o: scoped(o) or _is_step(o))


def glm_mhc_time_share(record, trace) -> Optional[float]:
    """Device time of what runs under ``residual/mhc`` ON ITS OWN in the
    decode executable over its busy device time (``readers/gdla
    .mhc_time_share``'s rule: a fusion is the residual path's by its
    products, else by most of its instructions; the mixing fused behind a
    projection is that projection's)."""
    if trace is None or not _is_record(record):
        return None
    others = _scoped(record, INDEX, SELECT, SPARSE, KDA_SCOPE)
    scoped = _scoped(record, MHC_SCOPE)
    return _decode_share(record, trace,
                         lambda o: scoped(o) and not others(o))


def glm_kda_state_step_roofline(record, trace) -> Optional[float]:
    """``flops_dsa.kda_step_need_s`` over the live slots the traced decode
    steps advanced (``serving/state_slots_stepped``) over the
    ``kda_state_step`` kernel's device time in the decode executable."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _is_step, _win(record))
    stepped = _tail(record, "stepped_sum")
    if not kernel_s or not stepped:
        return None
    return 100.0 * flops_dsa.kda_step_need_s(
        stepped, record["model"], record["peaks"]) / kernel_s


def eighth_share_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the HELD experts the traced decode steps
    touched (``serving/moe_experts_touched``;
    ``flops_dsa.expert_stream_bytes``) over the peak HBM rate, over the
    device time of the routed experts' operations of the decode
    executable."""
    if trace is None or not _is_record(record):
        return None
    touched = _tail(record, "touched_sum")
    routed_s = reduce.time_where(trace, _is_routed(record), _win(record))
    if not touched or not routed_s:
        return None
    need = flops_dsa.expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / routed_s


def _window_mean(record, field: str, count: str) -> Optional[float]:
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, count)
    return _delta(samples, inside, field) / n if n else None


def eighth_share_experts_touched_per_layer_mean(record, trace=None
                                                ) -> Optional[float]:
    """``serving/moe_experts_touched``: held experts with at least one row,
    mean over the window's steps and expert layers."""
    return _window_mean(record, "touched_sum", "touched_n")


def attn_rows_read_per_step_latent_sparse(record, trace=None
                                          ) -> Optional[float]:
    """``serving/attn_rows_read.latent_sparse``: rows one DSA layer read in
    a decode step, over the live slots, mean over the window's steps."""
    return _window_mean(record, "rows_read_sum", "stepped_n")


def index_blocks_scored_per_step(record, trace=None) -> Optional[float]:
    """``serving/index_blocks_scored``: closed blocks one DSA layer scored
    in a decode step, over the live slots, mean over the window's steps."""
    return _window_mean(record, "scored_sum", "stepped_n")


def dsa_rows_kept_share(record, trace=None) -> Optional[float]:
    """Rows the DSA layer read over the rows the same slots' contexts hold
    (``serving/attn_rows_read.latent_sparse`` over
    ``serving/attn_rows_context.latent_sparse``), over the window."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    ctx = _delta(samples, inside, "rows_ctx_sum")
    return 100.0 * _delta(samples, inside, "rows_read_sum") / ctx \
        if ctx else None


def dsa_latent_pages_used_share(record, trace=None) -> Optional[float]:
    """Pages of the latent group in use after each cycle, mean over the
    window, over its pool."""
    if not _is_record(record) \
            or "latent_sparse" not in record.get("pools", {}):
        return None
    samples, inside = _in(record, "open", "close")
    if not inside:
        return None
    used = sum(samples[i].pages_used for i in inside) / len(inside)
    return 100.0 * used / record["pools"]["latent_sparse"]

"""Metrics of the cell whose every layer chooses single rows
(``dsv32-sparsedoc-sat``): the sparse read, the index scoring and the
whole decode step in the device trace, and the counters the driver sampled
after every cycle (``drivers/serve_rowdsa.Sample``).

What runs under ``attn/dsa_sparse`` (the ``dsa_sparse_decode`` calls and
the mask made for them) and under ``attn/dsa_index`` (the
``dsa_index_scores`` kernel among it) is told BY NAME from the decode executable's own text
(``readers/gdla.scoped_instructions``; the driver writes the names into
``record["scoped_ops"]``). The selection's and the whole mechanism's
shares, the rows read and kept and the pages in use are
``readers/dsa.py``'s, and the held experts' ``readers/mla.py``'s: the
driver samples under the field names they read. A reader that finds no
such operation, or a record that is not this kind's (the parent of the PR
that added this file cannot build the model), returns nothing."""

from __future__ import annotations

from typing import Optional

from .. import flops_rowdsa, reduce
from .dsa import INDEX, SPARSE, _is_decode, _scoped, _tail
from .moe import _delta, _in, _win


def _is_record(record) -> bool:
    return ("samples" in record and "scoped_ops" in record
            and record.get("model", {}).get("kind") == "serve_rowdsa")


def rowdsa_sparse_attn_roofline(record, trace) -> Optional[float]:
    """``flops_rowdsa.sparse_read_need_s`` over the rows ONE layer read in
    the traced decode steps (``serving/attn_rows_read.latent_sparse``: the
    rows the selection kept), over the device time of what runs under
    ``attn/dsa_sparse`` in the decode executable (the sparse read's calls,
    whatever form they take, and what is made for them)."""
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, _scoped(record, SPARSE),
                                 _win(record))
    rows = _tail(record, "rows_read_sum")
    if not kernel_s or not rows:
        return None
    return 100.0 * flops_rowdsa.sparse_read_need_s(
        rows, record["model"], record["peaks"]) / kernel_s


def rowdsa_index_roofline(record, trace) -> Optional[float]:
    """``flops_rowdsa.index_score_need_s`` over the rows the traced decode
    steps scored (``serving/index_rows_scored``), over the device time of
    what runs under ``attn/dsa_index`` in the decode executable (the
    index's projections, its rotation, the key's write, the gather of a
    slot's keys and the scores)."""
    if trace is None or not _is_record(record):
        return None
    index_s = reduce.time_where(trace, _scoped(record, INDEX), _win(record))
    rows = _tail(record, "scored_sum")
    if not index_s or not rows:
        return None
    return 100.0 * flops_rowdsa.index_score_need_s(
        rows, record["model"], record["peaks"]) / index_s


def _slot_steps(record, lo_key: str, hi_key: str) -> Optional[float]:
    """Rows decoded between two marks: a token a live slot a decode step,
    so the tokens the cycles emitted less each admission's first, which is
    its prefill's."""
    lo, hi = record["marks"][lo_key], record["marks"][hi_key]
    samples, inside = _in(record, lo_key, hi_key)
    prefills = _delta(samples, inside, "prefills_n")
    if prefills is None:
        return None
    tokens = sum(c.tokens for c in record["cycles"] if lo <= c.end <= hi)
    return tokens - prefills


def dsv32_step_mfu(record, trace) -> Optional[float]:
    """The model's operations for the rows the traced decode steps decoded
    (``flops_rowdsa.step_flops``: every held layer's products, the chosen
    rows' attention, the index scores, the pairs sent to held experts, the
    head) over the decode executable's busy device seconds times the
    chip's bf16 peak: the share of a WHOLE step."""
    if trace is None or not _is_record(record):
        return None
    decode_s = reduce.time_where(trace, _is_decode, _win(record))
    rows, scored = _tail(record, "rows_read_sum"), _tail(record, "scored_sum")
    pairs = _tail(record, "held_pairs_sum")
    slot_steps = _slot_steps(record, "tail_open", "tail_close")
    if not decode_s or not slot_steps or slot_steps <= 0 or not rows \
            or not scored:
        return None
    flops = flops_rowdsa.step_flops(slot_steps, rows, scored, pairs or 0.0,
                                    record["model"])
    return 100.0 * flops / (decode_s * record["peaks"]["bf16_flops_per_s"])


def _window_mean(record, field: str, count: str) -> Optional[float]:
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, count)
    return _delta(samples, inside, field) / n if n else None


def index_rows_scored_per_step(record, trace=None) -> Optional[float]:
    """``serving/index_rows_scored``: context rows one layer scored in a
    decode step, over the live slots, mean over the window's steps."""
    return _window_mean(record, "scored_sum", "stepped_n")


def moe_groups_kept_with_held_share(record, trace=None) -> Optional[float]:
    """Of the rows the window's decode steps decoded, those of which a
    group the router kept holds an expert held here
    (``serving/moe_groups_kept_with_held``, an observation an expert layer
    a step, over the rows decoded times the expert layers)."""
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    steps = _delta(samples, inside, "stepped_n")
    rows = _slot_steps(record, "open", "close")
    if not steps or not rows or rows <= 0:
        return None
    layers = _delta(samples, inside, "touched_n") / steps
    if not layers:
        return None
    return 100.0 * _delta(samples, inside, "groups_sum") / (rows * layers)

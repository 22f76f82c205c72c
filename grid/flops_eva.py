"""Operations and bytes the byte-level decoder with EVA attention (exact
keys inside a tumbling window, one pooled key and value a chunk of every
closed window) needs, from shapes and from the row counts the program's
counters give: the denominators of the roofline shares and of the step's
share of the peak in ``grid/readers/eva.py``. The counts are of the
mathematics, whatever implements it. Beside the other ``grid/flops_*.py``,
which a later PR may not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict, Sequence


def layers(model: Dict[str, Any]) -> int:
    return int(model["num_hidden_layers"])


def width(model: Dict[str, Any]) -> int:
    return int(model["hidden_size"])


def layer_matmul_params(model: Dict[str, Any]) -> int:
    """Weights a row is multiplied by in ONE layer: q, k, v and o and the
    three of the SwiGLU (4 x 4,096^2 + 3 x 4,096 x 11,008 =
    202,375,168)."""
    d = width(model)
    return 4 * d * d + 3 * d * int(model["intermediate_size"])


def layer_params(model: Dict[str, Any]) -> int:
    """One layer's parameters: its products, its two norms' gains, phi and
    mu (202,391,552)."""
    return layer_matmul_params(model) + 4 * width(model)


def head_params(model: Dict[str, Any]) -> int:
    """The prediction heads, one product: 4,096 x 8 x 320."""
    return (width(model) * int(model["num_pred_heads"])
            * int(model["vocab_size"]))


def weight_bytes_per_step(model: Dict[str, Any], bytes_per_value: int = 2
                          ) -> int:
    """What ONE decode step must read of the weights whatever the batch:
    every layer and the heads once (12 x 202,391,552 + 10,485,760 values:
    4.88 GB in bfloat16). The embedding's rows (one a live slot) and the
    final norm are a few KB beside it and are not counted."""
    return bytes_per_value * (layers(model) * layer_params(model)
                              + head_params(model))


def weight_need_s(decode_steps: float, model: Dict[str, Any],
                  peaks: Dict[str, float]) -> float:
    """``decode_steps`` weight passes over the HBM rate. A dozen rows a
    step are 12 operations a weight byte against a ridge of 240: the
    bytes."""
    return decode_steps * weight_bytes_per_step(model) \
        / peaks["hbm_bytes_per_s"]


def kv_row_bytes(model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one row (a position's, or a chunk's summary) in ONE
    layer: 2 x 32 x 128 x 2 = 16,384."""
    return 2 * int(model["num_key_value_heads"]) \
        * (width(model) // int(model["num_attention_heads"])) \
        * bytes_per_value


def kv_need_s(rows: float, model: Dict[str, Any], peaks: Dict[str, float]
              ) -> float:
    """The least the decode attention of every layer must take: each row
    of a live slot's view (exact or summary) once a layer over the HBM
    rate. ``rows`` is what ONE layer read
    (``serving/attn_rows_read.eva_exact`` + ``.eva_summary``). One query
    head a KV head: 4 operations a value read, the bytes bound it."""
    return rows * layers(model) * kv_row_bytes(model) \
        / peaks["hbm_bytes_per_s"]


def head_flops(model: Dict[str, Any]) -> int:
    """Every head over one row."""
    return 2 * head_params(model)


def row_flops(model: Dict[str, Any]) -> float:
    """One row through every layer's products, but attention's context
    part, the pooling and the heads."""
    return layers(model) * 2 * layer_matmul_params(model)


def attn_flops_per_context_row(model: Dict[str, Any]) -> int:
    """A query row against ONE row of its view in one layer: every head a
    score and a weighted sum over the head's lanes (4 x 4,096)."""
    return 4 * width(model)


def pool_flops_per_row(model: Dict[str, Any]) -> int:
    """A row's part in its chunk's summary in one layer: the pooling
    logit (k . phi) and the weighted sums of k and of v (6 x 4,096)."""
    return 6 * width(model)


def prefill_pairs(s: int, model: Dict[str, Any]) -> float:
    """(query, row) pairs ONE layer's attention scores over a prompt of
    ``s`` rows: in each window the causal pairs of its own rows, and every
    query against the summaries of the windows before its own."""
    w, c = int(model["window_size"]), int(model["chunk_size"])
    pairs, start = 0.0, 0
    while start < s:
        n = min(w, s - start)
        pairs += n * (n + 1) / 2 + n * (w // c) * (start // w)
        start += n
    return pairs


def step_flops(decode_rows: float, decode_view_rows: float,
               prefill_buckets: Sequence[int], model: Dict[str, Any]
               ) -> float:
    """The model's operations for what a stretch computed: ``decode_rows``
    live slot-steps (each through the layers and the heads, and pooling
    its open chunk's rows in every layer, as the step does) reading
    ``decode_view_rows`` rows a layer in all, and a prefill of each of
    ``prefill_buckets`` rows as the bucket computes it: every row through
    the layers and the pooling, attention over :func:`prefill_pairs`, the
    heads on ONE row."""
    per_row, per_pair = row_flops(model), attn_flops_per_context_row(model)
    pool = layers(model) * pool_flops_per_row(model)
    total = decode_rows * (per_row + head_flops(model)
                           + int(model["chunk_size"]) * pool) \
        + decode_view_rows * layers(model) * per_pair
    for s in prefill_buckets:
        total += s * (per_row + pool) \
            + layers(model) * per_pair * prefill_pairs(s, model) \
            + head_flops(model)
    return total

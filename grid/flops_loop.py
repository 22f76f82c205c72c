"""Operations and bytes the looped decoder (the same layers run several
times over a token; a cache layer a step a layer) needs, from shapes: the
denominators of the roofline shares and of the step's share of the peak in
``grid/readers/loop.py``. The counts are of the mathematics, whatever
implements it. Beside the other ``grid/flops_*.py``, which a later PR may
not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict, Sequence


def layers(model: Dict[str, Any]) -> int:
    """The layers of WEIGHTS."""
    return int(model["num_hidden_layers"])


def steps(model: Dict[str, Any]) -> int:
    """How often every layer runs over a token."""
    return int(model["total_ut_steps"])


def cache_layers(model: Dict[str, Any]) -> int:
    """Cache layers: one a (step, layer), 4 x 48 = 192."""
    return layers(model) * steps(model)


def layer_matmul_params(model: Dict[str, Any]) -> int:
    """Weights a row is multiplied by in ONE layer: q, k, v and o and the
    three of the SwiGLU (4 x 2,048^2 + 3 x 2,048 x 5,632 = 51,380,224)."""
    d = int(model["hidden_size"])
    hq = int(model["num_attention_heads"]) * int(model["head_dim"])
    hkv = int(model["num_key_value_heads"]) * int(model["head_dim"])
    return (d * (hq + 2 * hkv) + hq * d
            + 3 * d * int(model["intermediate_size"]))


def layer_params(model: Dict[str, Any]) -> int:
    """One layer's parameters: its products and its four norms' gains
    (51,388,416)."""
    return layer_matmul_params(model) + 4 * int(model["hidden_size"])


def head_params(model: Dict[str, Any]) -> int:
    """The untied head: 2,048 x 49,152."""
    return int(model["hidden_size"]) * int(model["vocab_size"])


def weight_bytes_per_step(model: Dict[str, Any], bytes_per_value: int = 2
                          ) -> int:
    """What ONE decode step must read of the weights whatever the batch:
    every layer once a loop step and the head once (4 x 48 x 51,388,416 +
    100,663,296 values: 19.93 GB in bfloat16). The embedding's rows (one a
    live slot), the final norm and the gate are a few KB beside it and are
    not counted."""
    return bytes_per_value * (cache_layers(model) * layer_params(model)
                              + head_params(model))


def weight_need_s(decode_steps: float, model: Dict[str, Any],
                  peaks: Dict[str, float]) -> float:
    """``decode_steps`` weight passes over the HBM rate. A dozen rows a
    step are 12 operations a weight byte against a ridge of 240: the
    bytes."""
    return decode_steps * weight_bytes_per_step(model) \
        / peaks["hbm_bytes_per_s"]


def kv_row_bytes(model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position in ONE cache layer: 2 x 16 x 128 x 2 =
    8,192."""
    return (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * bytes_per_value)


def kv_token_bytes(model: Dict[str, Any]) -> int:
    """What one token holds in the pool: a row in every cache layer, 192 x
    8,192 B = 1.5 MiB."""
    return cache_layers(model) * kv_row_bytes(model)


def kv_need_s(rows: float, model: Dict[str, Any], peaks: Dict[str, float]
              ) -> float:
    """The least the decode attention of every (step, layer) must take:
    the K and V row of every live position once a cache layer over the HBM
    rate. ``rows`` is what ONE cache layer read
    (``serving/attn_rows_read.global``). One query head a KV head: 4
    operations a value read, the bytes bound it."""
    return rows * kv_token_bytes(model) / peaks["hbm_bytes_per_s"]


def head_flops(model: Dict[str, Any]) -> int:
    """The head over one row: 2 x 2,048 x 49,152."""
    return 2 * head_params(model)


def row_flops(model: Dict[str, Any]) -> float:
    """One row through every layer at every step, but attention's context
    part and the head."""
    return cache_layers(model) * 2 * layer_matmul_params(model)


def attn_flops_per_context_row(model: Dict[str, Any]) -> int:
    """A query row against ONE context row in one cache layer: every query
    head a score and a weighted sum over ``head_dim``."""
    return 4 * int(model["num_attention_heads"]) * int(model["head_dim"])


def step_flops(decode_rows: float, decode_context_rows: float,
               prefill_buckets: Sequence[int], model: Dict[str, Any]
               ) -> float:
    """The model's operations for what a stretch computed: ``decode_rows``
    live slot-steps (each four times through the layers and once through
    the head) reading ``decode_context_rows`` context rows a cache layer
    in all, and a prefill of each of ``prefill_buckets`` rows as the bucket
    computes it: every row four times through the layers, causal attention
    over ``S (S + 1) / 2`` pairs a cache layer, the head on ONE row."""
    per_row, per_pair = row_flops(model), attn_flops_per_context_row(model)
    total = decode_rows * (per_row + head_flops(model)) \
        + decode_context_rows * cache_layers(model) * per_pair
    for s in prefill_buckets:
        total += s * per_row \
            + cache_layers(model) * per_pair * s * (s + 1) / 2 \
            + head_flops(model)
    return total

"""Operations and bytes from shapes. A later PR cannot change what a
utilization is measured against: these functions and ``peaks.json`` are
the denominators of every share the grid reports."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``, by exact name. A device the table
    does not know is an error: a share of a guessed peak is worse than
    none."""
    with open(_PEAKS) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError("grid/peaks.json has no device %r (it has %s)"
                       % (device_kind, sorted(table)))
    return table[device_kind]


def transformer_train_flops_per_example(seq: int, vocab: int, n_layer: int,
                                        d_model: int, d_inner: int) -> float:
    """Model operations of one training example of the encoder-decoder
    Transformer with source and target both ``seq`` long: forward matrix
    multiplications (2 per multiply-add) times 3 for forward and backward.
    Recomputed work does not count. The same count as
    ``bench._transformer_train_flops_per_example`` (98.5 GFLOP an example
    for Transformer-base at 256 and V 30000), copied here.

    Encoder layer: q, k, v, o projections 8 s d^2; scores and the weighted
    sum 4 s^2 d; feed-forward 4 s d d_inner. Decoder layer: self- and
    cross-attention, so twice the attention terms. Output projection
    2 s d V.
    """
    s, d, di, v = seq, d_model, d_inner, vocab
    enc = n_layer * (8 * s * d * d + 4 * s * s * d + 4 * s * d * di)
    dec = n_layer * (16 * s * d * d + 8 * s * s * d + 4 * s * d * di)
    return 3.0 * (enc + dec + 2 * s * d * v)


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Per target position (the unit of ``train_tokens_per_s``)."""
    return transformer_train_flops_per_example(
        seq, model["vocab_size"], model["n_layer"], model["d_model"],
        model["d_inner"]) / seq


def paged_attention_kv_bytes(context_tokens: int, n_layer: int, n_head: int,
                             d_head: int, bytes_per_value: int) -> int:
    """The least a decode step must read for attention: the K and the V row
    of every live context position, in every layer. ``context_tokens`` is
    the sum of the slots' context lengths over the steps counted."""
    return context_tokens * n_layer * 2 * n_head * d_head * bytes_per_value

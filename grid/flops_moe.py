"""Bytes the sparse decoder's decode step has to move, from shapes: the
denominators of the roofline shares of ``grid/readers/moe.py``. Beside
``grid/flops.py``, which a later PR may not edit; the same rule holds
here."""

from __future__ import annotations

from typing import Any, Dict


def expert_weight_bytes(model: Dict[str, Any], bytes_per_value: int = 2
                        ) -> int:
    """One expert's three matrices (gate, up: d x f; down: f x d)."""
    return (3 * int(model["hidden_size"]) * int(model["moe_ffn_hidden_size"])
            * bytes_per_value)


def expert_stream_bytes(experts_touched: float, model: Dict[str, Any],
                        bytes_per_value: int = 2) -> float:
    """The least the expert layers must read: the weights of every expert
    that received a row, once. ``experts_touched`` is the sum, over the
    decode steps and layers counted, of the experts with at least one
    row."""
    return experts_touched * expert_weight_bytes(model, bytes_per_value)


def grouped_kv_bytes(global_ctx: int, window_ctx: int,
                     model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """The least decode attention must read: the K and the V row (at the
    KV heads' width: grouped queries read a row once) of every live
    position a layer holds. ``global_ctx`` is the sum over steps and slots
    of the context length, ``window_ctx`` of ``min(context, window)``."""
    n = int(model["num_hidden_layers"])
    windowed = sum(model["sliding_window_layout"][:n])
    row = (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
           * bytes_per_value)
    return (global_ctx * (n - windowed) + window_ctx * windowed) * row

"""Operations and bytes the latent-attention decoder's decode step needs,
from shapes: the denominators of the roofline shares of
``grid/readers/mla.py``. Beside ``grid/flops.py`` and ``grid/flops_moe.py``,
which a later PR may not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict


def latent_row_values(model: Dict[str, Any]) -> int:
    """What the mathematics keeps of a token in a layer: the KV latent and
    the one rotary key (576 at the published sizes). The pool stores each
    row padded to whole lane tiles (640); the padding is the store's, not
    the algorithm's, and is not counted."""
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def mla_decode_bytes(live_rows: float, model: Dict[str, Any],
                     bytes_per_value: int = 2) -> float:
    """The least absorbed decode attention must read: every live context
    row of every layer, once (all heads share it). ``live_rows`` is the
    sum over the decode steps counted and their live slots of the context
    length attended over."""
    return (live_rows * int(model["num_hidden_layers"])
            * latent_row_values(model) * bytes_per_value)


def mla_decode_flops(live_rows: float, model: Dict[str, Any]) -> float:
    """Its operations: for each row and each of the H heads, a score over
    the row's ``rank + rope`` values and a weighted sum over its ``rank``
    (2 a multiply-add)."""
    per_row = (int(model["num_attention_heads"])
               * (latent_row_values(model) + int(model["kv_lora_rank"])) * 2)
    return live_rows * int(model["num_hidden_layers"]) * per_row


def mla_decode_need_s(live_rows: float, model: Dict[str, Any],
                      peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of bytes over the
    HBM rate and operations over the bf16 peak (on a v5e, at 121
    operations a byte against a ridge of 240, the bytes)."""
    return max(mla_decode_bytes(live_rows, model) / peaks["hbm_bytes_per_s"],
               mla_decode_flops(live_rows, model) / peaks["bf16_flops_per_s"])


def held_expert_weight_bytes(model: Dict[str, Any], bytes_per_value: int = 2
                             ) -> int:
    """One routed expert's three matrices (gate, up: d x f; down: f x d)."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * bytes_per_value)


def held_expert_stream_bytes(experts_touched: float, model: Dict[str, Any],
                             bytes_per_value: int = 2) -> float:
    """The least the routed expert layers must read: the weights of every
    HELD expert that received a row, once. ``experts_touched`` is the sum,
    over the decode steps and expert layers counted, of the held experts
    with at least one row."""
    return experts_touched * held_expert_weight_bytes(model, bytes_per_value)

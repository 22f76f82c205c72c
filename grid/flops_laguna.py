"""Bytes and operations the decoder with query heads by layer type needs in
a decode step, from shapes: the denominators of the roofline shares of
``grid/readers/mixed_gqa.py``. Beside ``grid/flops.py``, ``grid/flops_moe.py``
and ``grid/flops_mla.py``, which a later PR may not edit; the same rule
holds here.

A cache GROUP is the layers of one kind among the first
``num_hidden_layers`` of the published lists: ``global`` the
``full_attention`` layers, ``window`` the ``sliding_attention`` ones. Both
keep K and V of the same ``num_key_value_heads`` heads of ``head_dim``; the
QUERY heads are the group's (48 and 72 over 8: 6 and 9 a KV head).
"""

from __future__ import annotations

from typing import Any, Dict

KIND = {"global": "full_attention", "window": "sliding_attention"}
SUBLANES = 8    # rows of a float32 tile: the paged kernel pads G to them


def group_layers(model: Dict[str, Any], group: str) -> int:
    """Layers of cache group ``group`` among those held."""
    n = int(model["num_hidden_layers"])
    return sum(1 for t in model["layer_types"][:n] if t == KIND[group])


def query_heads(model: Dict[str, Any], group: str) -> int:
    """Query heads of a layer of ``group`` (one number a group)."""
    n = int(model["num_hidden_layers"])
    heads = {h for h, t in zip(model["num_attention_heads_per_layer"][:n],
                               model["layer_types"][:n]) if t == KIND[group]}
    if len(heads) != 1:
        raise ValueError("group %r has layers of %s query heads"
                         % (group, sorted(heads)))
    return heads.pop()


def q_per_kv(model: Dict[str, Any], group: str) -> int:
    return query_heads(model, group) // int(model["num_key_value_heads"])


def kernel_query_rows(g: int) -> int:
    """Rows of the paged kernel's query tile at ``g`` query heads a KV
    head: 1 ungrouped, else ``g`` padded to whole sublanes (6 -> 8, 9 ->
    16). The kernel's result is ``[slots, rows, n_kv * head_dim]``, which
    is how a trace tells the two groups' calls apart."""
    return 1 if g == 1 else -(-g // SUBLANES) * SUBLANES


def kv_row_bytes(model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position in one layer: 2 x 8 x 128 x 2 = 4,096."""
    return (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * bytes_per_value)


def attn_kv_bytes(rows: float, model: Dict[str, Any], group: str,
                  bytes_per_value: int = 2) -> float:
    """The least ``group``'s decode attention must read: the K and the V
    row of every live position, once a layer (grouped queries read a row
    once for all their heads). ``rows`` is the sum over the decode steps
    counted of what ONE layer of the group attended over
    (``serving/attn_rows_read.<group>``)."""
    return rows * group_layers(model, group) * kv_row_bytes(
        model, bytes_per_value)


def attn_flops(rows: float, model: Dict[str, Any], group: str) -> float:
    """Its operations: for each row, each layer and each of the group's
    QUERY heads a score and a weighted sum over ``head_dim`` (2 a
    multiply-add): 24,576 a row at 48 heads, 36,864 at 72."""
    return (rows * group_layers(model, group) * query_heads(model, group)
            * int(model["head_dim"]) * 2 * 2)


def attn_need_s(rows: float, model: Dict[str, Any], group: str,
                peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of bytes over the
    HBM rate and operations over the bf16 peak (at 6 and 9 operations a
    byte against a ridge of 240, the bytes)."""
    return max(attn_kv_bytes(rows, model, group) / peaks["hbm_bytes_per_s"],
               attn_flops(rows, model, group) / peaks["bf16_flops_per_s"])


def expert_weight_bytes(model: Dict[str, Any], bytes_per_value: int = 2
                        ) -> int:
    """One routed expert's three matrices (gate, up: d x f; down: f x d):
    3 x 3072 x 1024 x 2 = 18.9 MB."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * bytes_per_value)


def held_expert_stream_bytes(experts_touched: float, model: Dict[str, Any],
                             bytes_per_value: int = 2) -> float:
    """The least the routed expert layers must read: the weights of every
    HELD expert that received a row, once. ``experts_touched`` is the sum,
    over the decode steps and expert layers counted, of the held experts
    with at least one row."""
    return experts_touched * expert_weight_bytes(model, bytes_per_value)

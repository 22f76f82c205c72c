"""Operations and bytes the hybrid of one-part layers (Nemotron-3-Nano: a
Mamba-2 mixer, an ungated expert layer or grouped-query attention a layer)
needs, from shapes: the denominators of the roofline shares and of the
step's share of the peak in ``grid/readers/nemotron.py``. The counts are of
the mathematics, whatever implements it. Beside the other
``grid/flops_*.py``, which a later PR may not edit; the same rule holds
here.

``model`` is the configuration file: the published keys, with
``num_hidden_layers`` the layers HELD (the first letters of
``hybrid_override_pattern``), ``n_routed_experts`` the experts held and
``published.n_routed_experts`` the router's width."""

from __future__ import annotations

from typing import Any, Dict, Sequence


def pattern(model: Dict[str, Any]) -> str:
    return model["hybrid_override_pattern"][:int(model["num_hidden_layers"])]


def layers(model: Dict[str, Any], kind: str) -> int:
    """Layers of ``kind`` (``M``, ``E`` or ``*``) among those held."""
    return pattern(model).count(kind)


def state_values(model: Dict[str, Any]) -> int:
    """Values of one slot's recurrent state in one ``M`` layer: a ``d_state
    x d_head`` matrix a head (64 x 128 x 64 = 524,288)."""
    return (int(model["mamba_num_heads"]) * int(model["ssm_state_size"])
            * int(model["mamba_head_dim"]))


def ssd_step_bytes(model: Dict[str, Any]) -> int:
    """The least one decode step of one slot in one ``M`` layer must move:
    the float32 state read and written, 2 x 2,097,152 B (whatever padding
    an implementation's layout adds is its own cost)."""
    return 2 * 4 * state_values(model)


def ssd_step_need_s(slot_steps: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """``slot_steps`` (live slots summed over the decode steps counted)
    times the ``M`` layers' state bytes over the HBM rate: five operations
    a state value against 8 bytes, so the bytes bound it."""
    return (slot_steps * layers(model, "M") * ssd_step_bytes(model)
            / peaks["hbm_bytes_per_s"])


def ssd_scan_bytes(rows: float, prefills: float, model: Dict[str, Any]
                   ) -> float:
    """What the recurrence over ``rows`` prompt positions of ``prefills``
    prompts must move, an ``M`` layer: x in and y out in float32 (a value a
    channel), B and C in float32 (a value a group's state lane), the
    log-decay (a head), and each prompt's final float32 state."""
    h, p = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    gn = int(model["n_groups"]) * int(model["ssm_state_size"])
    return rows * 4 * (2 * h * p + 2 * gn + h) \
        + prefills * 4 * state_values(model)


def ssd_scan_flops(rows: float, model: Dict[str, Any]) -> float:
    """The recurrence's own operations a position and ``M`` layer: a head's
    decay (N x P multiplies), the rank-one write and ``S^T C`` at 2 N P
    each."""
    return rows * 5 * state_values(model)


def ssd_scan_need_s(rows: float, prefills: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """The larger of the two over the chip's peaks, every ``M`` layer."""
    return layers(model, "M") * max(
        ssd_scan_bytes(rows, prefills, model) / peaks["hbm_bytes_per_s"],
        ssd_scan_flops(rows, model) / peaks["bf16_flops_per_s"])


def expert_weight_bytes(model: Dict[str, Any], bytes_per_value: int = 2
                        ) -> int:
    """One routed expert's TWO matrices (up: d x f; down: f x d): 2 x 2,688
    x 1,856 x 2 = 19.96 MB."""
    return (2 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * bytes_per_value)


def expert_stream_bytes(experts_touched: float, model: Dict[str, Any],
                        bytes_per_value: int = 2) -> float:
    """The least the routed experts' decode passes must read: the weights
    of every HELD expert that received a row, once. ``experts_touched`` is
    the sum, over the decode steps and ``E`` layers counted, of the held
    experts with at least one row."""
    return experts_touched * expert_weight_bytes(model, bytes_per_value)


def expert_need_s(experts_touched: float, held_pairs: float,
                  model: Dict[str, Any], peaks: Dict[str, float]) -> float:
    """The larger of the touched experts' bytes over the HBM rate and the
    held pairs' operations (two products of ``d x f`` a pair) over the bf16
    peak: 6 rows an expert against a ridge of 240, so the bytes."""
    flops = held_pairs * 4 * int(model["hidden_size"]) \
        * int(model["moe_intermediate_size"])
    return max(expert_stream_bytes(experts_touched, model)
               / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def kv_row_bytes(model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position in one ``*`` layer: 2 x 2 x 128 x 2 =
    1,024."""
    return (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * bytes_per_value)


def m_layer_params(model: Dict[str, Any]) -> int:
    """Weights a row is multiplied by in an ``M`` layer: 2,688 x 10,304 +
    4,096 x 2,688 = 38.7M."""
    d = int(model["hidden_size"])
    d_ssm = int(model["mamba_num_heads"]) * int(model["mamba_head_dim"])
    gn = int(model["n_groups"]) * int(model["ssm_state_size"])
    return d * (2 * d_ssm + 2 * gn + int(model["mamba_num_heads"])) \
        + d_ssm * d


def conv_channels(model: Dict[str, Any]) -> int:
    return (int(model["mamba_num_heads"]) * int(model["mamba_head_dim"])
            + 2 * int(model["n_groups"]) * int(model["ssm_state_size"]))


def e_layer_row_flops(model: Dict[str, Any], held_share: float) -> float:
    """An ``E`` layer over one row: the router over its published width, the
    shared expert, and ``held_share`` of the row's ``num_experts_per_tok``
    routed experts (the pairs that fall on held experts; the others are
    another chip's)."""
    d = int(model["hidden_size"])
    router = int(model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]))
    return 2 * d * router \
        + 4 * d * int(model["moe_shared_expert_intermediate_size"]) \
        + held_share * int(model["num_experts_per_tok"]) * 4 * d \
        * int(model["moe_intermediate_size"])


def attn_layer_params(model: Dict[str, Any]) -> int:
    """q, k, v and o: 2,688 x (4,096 + 2 x 256) + 4,096 x 2,688 = 23.4M."""
    d = int(model["hidden_size"])
    hq = int(model["num_attention_heads"]) * int(model["head_dim"])
    hkv = int(model["num_key_value_heads"]) * int(model["head_dim"])
    return d * (hq + 2 * hkv) + hq * d


def head_flops(model: Dict[str, Any]) -> int:
    """The untied head over one row: 2 x 2,688 x 65,536."""
    return 2 * int(model["hidden_size"]) * int(model["vocab_size"])


def held_share(model: Dict[str, Any]) -> float:
    """The part of a token's routed pairs an even router sends the held
    experts: 64 / 128."""
    return int(model["n_routed_experts"]) / int(model.get(
        "published", {}).get("n_routed_experts", model["n_routed_experts"]))


def row_flops(model: Dict[str, Any], share: float = None) -> float:
    """One row through every layer, but attention's context part and the
    head: the products, the convolution's taps and the recurrence, and the
    expert layers at ``share`` of a row's routed pairs (default: an even
    router's)."""
    share = held_share(model) if share is None else share
    m = 2 * m_layer_params(model) \
        + 2 * int(model["conv_kernel"]) * conv_channels(model) \
        + 5 * state_values(model)
    return (layers(model, "M") * m
            + layers(model, "E") * e_layer_row_flops(model, share)
            + layers(model, "*") * 2 * attn_layer_params(model))


def attn_flops_per_context_row(model: Dict[str, Any]) -> int:
    """A query row against ONE context row, a ``*`` layer: every query head
    a score and a weighted sum over ``head_dim``."""
    return 4 * int(model["num_attention_heads"]) * int(model["head_dim"])


def step_flops(decode_rows: float, decode_context_rows: float,
               decode_held_pairs: float, prefill_buckets: Sequence[int],
               model: Dict[str, Any]) -> float:
    """The model's operations for what a stretch computed: ``decode_rows``
    live slot-steps (each through the layers and the head) reading
    ``decode_context_rows`` context rows a ``*`` layer in all and sending
    ``decode_held_pairs`` pairs to held experts (summed over the ``E``
    layers, as ``serving/moe_held_pairs`` counts them), and a prefill of
    each of ``prefill_buckets`` rows as the bucket computes it: every row
    through the layers (an even router's share of its pairs), causal
    attention over ``S (S + 1) / 2`` pairs a ``*`` layer, the head on ONE
    row."""
    per_pair = attn_flops_per_context_row(model) * layers(model, "*")
    routed = 4 * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"])
    total = decode_rows * (row_flops(model, 0.0) + head_flops(model)) \
        + decode_held_pairs * routed + decode_context_rows * per_pair
    for s in prefill_buckets:
        total += s * row_flops(model) + per_pair * s * (s + 1) / 2 \
            + head_flops(model)
    return total

"""Operations and bytes the parallel hybrid (a Mamba-2 state beside a GQA
page pool in every layer) needs, from shapes: the denominators of the
roofline shares and of the step's share of the peak in
``grid/readers/ssm.py``. The counts are of the mathematics, whatever
implements it. Beside the other ``grid/flops_*.py``, which a later PR may
not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict, Sequence


def layers(model: Dict[str, Any]) -> int:
    """Every layer HELD has both mixers."""
    return int(model["num_hidden_layers"])


def state_values(model: Dict[str, Any]) -> int:
    """Values of one slot's recurrent state in one layer: a ``d_state x
    d_head`` matrix a head (32 x 256 x 128 = 1,048,576)."""
    return (int(model["mamba_n_heads"]) * int(model["mamba_d_state"])
            * int(model["mamba_d_head"]))


def ssd_step_bytes(model: Dict[str, Any]) -> int:
    """The least one decode step of one slot in one layer must move: the
    float32 state read and written, 2 x 4,194,304 B. (The step's x, B, C
    and decay are 21 KB beside it and are not counted.)"""
    return 2 * 4 * state_values(model)


def ssd_step_need_s(slot_steps: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """``slot_steps`` (live slots summed over the decode steps counted)
    times the layers' state bytes over the HBM rate: five operations a
    state value against 8 bytes, so the bytes bound it."""
    return (slot_steps * layers(model) * ssd_step_bytes(model)
            / peaks["hbm_bytes_per_s"])


def ssd_scan_bytes(rows: float, prefills: float, model: Dict[str, Any]
                   ) -> float:
    """What the recurrence over ``rows`` prompt positions of ``prefills``
    prompts must move, a layer: x in and y out in float32 (a value a
    channel), B and C in float32 (a value a group's state lane), the
    log-decay (a head), and each prompt's final float32 state."""
    h, p = int(model["mamba_n_heads"]), int(model["mamba_d_head"])
    gn = int(model["mamba_n_groups"]) * int(model["mamba_d_state"])
    per_row = 4 * (2 * h * p + 2 * gn + h)
    return rows * per_row + prefills * 4 * state_values(model)


def ssd_scan_flops(rows: float, model: Dict[str, Any]) -> float:
    """The recurrence's own operations a position and layer: a head's
    decay (N x P multiplies), the rank-one write and ``S^T C`` at 2 N P
    each."""
    return rows * 5 * state_values(model)


def ssd_scan_need_s(rows: float, prefills: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """The larger of the two over the chip's peaks, every layer."""
    return layers(model) * max(
        ssd_scan_bytes(rows, prefills, model) / peaks["hbm_bytes_per_s"],
        ssd_scan_flops(rows, model) / peaks["bf16_flops_per_s"])


def kv_row_bytes(model: Dict[str, Any], bytes_per_value: int = 2) -> int:
    """K and V of one position in one layer: 2 x 4 x 128 x 2 = 2,048."""
    return (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * bytes_per_value)


def gqa_decode_need_s(rows: float, model: Dict[str, Any],
                      peaks: Dict[str, float]) -> float:
    """The least the layers' decode attention must take: the K and V row
    of every live position once a layer (grouped queries read a row once
    for all their heads) over the HBM rate. ``rows`` is what ONE layer
    read (``serving/attn_rows_read.global``). At 5 query heads a KV head
    the operations are 5 a byte against a ridge of 240: the bytes."""
    return rows * layers(model) * kv_row_bytes(model) \
        / peaks["hbm_bytes_per_s"]


def layer_matmul_params(model: Dict[str, Any]) -> int:
    """Weights a row is multiplied by in ONE layer: the SSM's input and
    output projections, q, k, v and o, and the three of the SwiGLU
    (430,080,000 at the published widths)."""
    d = int(model["hidden_size"])
    d_ssm = int(model["mamba_d_ssm"])
    gn = int(model["mamba_n_groups"]) * int(model["mamba_d_state"])
    hq = int(model["num_attention_heads"]) * int(model["head_dim"])
    hkv = int(model["num_key_value_heads"]) * int(model["head_dim"])
    return (d * (2 * d_ssm + 2 * gn + int(model["mamba_n_heads"]))
            + d_ssm * d + d * (hq + 2 * hkv) + hq * d
            + 3 * d * int(model["intermediate_size"]))


def head_flops(model: Dict[str, Any]) -> int:
    """The untied head over one row: 2 x 5,120 x 261,120."""
    return 2 * int(model["hidden_size"]) * int(model["vocab_size"])


def row_flops(model: Dict[str, Any]) -> float:
    """One row through every layer, but attention's context part and the
    head: the products, the convolution's taps and the recurrence."""
    conv = 2 * int(model["mamba_d_conv"]) * (
        int(model["mamba_d_ssm"]) + 2 * int(model["mamba_n_groups"])
        * int(model["mamba_d_state"]))
    return layers(model) * (2 * layer_matmul_params(model) + conv
                            + 5 * state_values(model))


def attn_flops_per_context_row(model: Dict[str, Any]) -> int:
    """A query row against ONE context row, a layer: every query head a
    score and a weighted sum over ``head_dim``."""
    return 4 * int(model["num_attention_heads"]) * int(model["head_dim"])


def step_flops(decode_rows: float, decode_context_rows: float,
               prefill_buckets: Sequence[int], model: Dict[str, Any]
               ) -> float:
    """The model's operations for what a stretch computed:
    ``decode_rows`` live slot-steps (each through the layers and the
    head) reading ``decode_context_rows`` context rows a layer in all, and
    a prefill of each of ``prefill_buckets`` rows as the bucket computes
    it: every row through the layers, causal attention over ``S (S + 1) /
    2`` pairs a layer, the head on ONE row."""
    per_row, per_pair = row_flops(model), attn_flops_per_context_row(model)
    total = decode_rows * (per_row + head_flops(model)) \
        + decode_context_rows * layers(model) * per_pair
    for s in prefill_buckets:
        total += s * per_row + layers(model) * per_pair * s * (s + 1) / 2 \
            + head_flops(model)
    return total

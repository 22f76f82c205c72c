"""Operations and bytes the sparse latent hybrid (a learned indexer over
latent rows beside KDA layers) needs in a decode step, from shapes and from
the program's counters: the denominators of the roofline shares of
``grid/readers/dsa.py``. The counts are of the WORK (rows the selection
kept, blocks scored), whatever implements it: the sparse read copies
8-row tiles of which it may keep four rows, and the index scoring gathers
a slot's whole table; neither is in the need. Layers are counted BY KIND
from the configuration's ``layer_types_held``. Beside ``grid/flops_mla.py``,
``flops_hybrid.py`` and ``flops_gdla.py``, which a later PR may not edit;
the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict

from . import flops_mla

KDA, DSA = "linear_attention", "deepseek_sparse_attention"


def layers_of(model: Dict[str, Any], kind: str) -> int:
    """How many of the layers HELD are of ``kind``."""
    n = int(model["num_hidden_layers"])
    return sum(1 for t in model["layer_types_held"][:n] if t == kind)


def sparse_read_need_s(rows_read: float, model: Dict[str, Any],
                       peaks: Dict[str, float]) -> float:
    """The least time the chip could take for the sparse decode attention
    of the DSA layers: ``rows_read`` is what ONE such layer read over the
    steps counted (``serving/attn_rows_read.latent_sparse``: the rows the
    selection kept, over the live slots). The larger of rows x 512 values x
    2 bytes over the HBM rate and rows x 64 heads x (512 + 512) x 2
    operations over the bf16 peak (128 operations a byte against a ridge
    of 240: the bytes), times the layers."""
    rank = int(model["kv_lora_rank"])
    heads = int(model["num_attention_heads"])
    return layers_of(model, DSA) * max(
        rows_read * rank * 2 / peaks["hbm_bytes_per_s"],
        rows_read * heads * 2 * rank * 2 / peaks["bf16_flops_per_s"])


def index_score_need_s(blocks_scored: float, model: Dict[str, Any],
                       peaks: Dict[str, float]) -> float:
    """The least time for the index scores: ``blocks_scored`` closed blocks
    (``serving/index_blocks_scored``, one DSA layer, over the live slots
    and the steps counted), each one pooled key of ``index_head_dim``
    values in bf16 (256 B) scored by ``index_n_heads`` heads (32 x 128 x 2
    operations, and the 32 weighted ReLUs): the larger of the two over the
    chip's peaks (32 operations a byte: the bytes), times the layers."""
    lanes, heads = int(model["index_head_dim"]), int(model["index_n_heads"])
    return layers_of(model, DSA) * max(
        blocks_scored * lanes * 2 / peaks["hbm_bytes_per_s"],
        blocks_scored * heads * (lanes * 2 + 2) / peaks["bf16_flops_per_s"])


def kda_step_bytes(model: Dict[str, Any]) -> int:
    """The least one decode step of one slot in one KDA layer must move
    (``flops_hybrid.kda_step_bytes`` under this configuration's keys): the
    float32 state read and written (64 x 128 x 128), and the step's q, k,
    log-decay, v, o (a value a channel) and beta (a head), at 4 bytes."""
    lin = model["linear_attn_config"]
    h, d = int(lin["num_heads"]), int(lin["head_dim"])
    return 4 * (2 * h * d * d + 5 * h * d + h)


def kda_step_need_s(slot_steps: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """``slot_steps`` (live slots summed over the decode steps counted)
    times the KDA layers' bytes over the HBM rate."""
    return (slot_steps * layers_of(model, KDA) * kda_step_bytes(model)
            / peaks["hbm_bytes_per_s"])


def expert_stream_bytes(experts_touched: float, model: Dict[str, Any]
                        ) -> float:
    """``flops_mla.held_expert_stream_bytes``, whose count is right for
    this model (``hidden_size`` x ``moe_intermediate_size`` x 3 an expert):
    every HELD expert that received a row, once; ``experts_touched`` summed
    over the steps and the expert layers counted."""
    return flops_mla.held_expert_stream_bytes(experts_touched, model)

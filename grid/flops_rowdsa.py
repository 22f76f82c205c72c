"""Operations and bytes the latent decoder whose every layer chooses single
rows (DeepSeek-V3.2's lightning indexer: a key a row, 2,048 rows read)
needs in a decode step, from shapes and from the program's counters: the
denominators of the roofline shares and of the whole step's share of
``grid/readers/rowdsa.py``. The counts are of the WORK (rows the selection
kept, rows scored), whatever implements it: a sparse read that copies the
whole context and masks what was not chosen, and the projections that run
under the index's scope, have neither in the need. EVERY held layer
chooses (``num_hidden_layers`` of them). Beside ``grid/flops_mla.py`` and
``flops_dsa.py``, which a later PR may not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict

from . import flops_mla


def sparse_read_need_s(rows_read: float, model: Dict[str, Any],
                       peaks: Dict[str, float]) -> float:
    """The least time the chip could take for the sparse decode attention:
    ``rows_read`` is what ONE layer read over the steps counted
    (``serving/attn_rows_read.latent_sparse``: the rows the selection
    kept, over the live slots). The larger of rows x (512 + 64) values x 2
    bytes over the HBM rate and rows x 128 heads x (576 + 512) x 2
    operations over the bf16 peak (242 operations a byte against a ridge
    of 240: the two meet), times the layers."""
    row = flops_mla.latent_row_values(model)
    heads = int(model["num_attention_heads"])
    return int(model["num_hidden_layers"]) * max(
        rows_read * row * 2 / peaks["hbm_bytes_per_s"],
        rows_read * heads * (row + int(model["kv_lora_rank"])) * 2
        / peaks["bf16_flops_per_s"])


def index_score_ops(rows_scored: float, model: Dict[str, Any]) -> float:
    """Operations of one layer's index scores: each row's key of
    ``index_head_dim`` values against ``index_n_heads`` heads (a
    multiply-add a value) and the heads' weighted ReLUs."""
    lanes, heads = int(model["index_head_dim"]), int(model["index_n_heads"])
    return rows_scored * heads * (lanes * 2 + 2)


def index_score_need_s(rows_scored: float, model: Dict[str, Any],
                       peaks: Dict[str, float]) -> float:
    """The least time for the index scores: ``rows_scored`` context rows
    (``serving/index_rows_scored``, one layer, over the live slots and the
    steps counted), each one key of ``index_head_dim`` values in bf16 (256
    B) scored by 64 heads: the larger of the bytes over the HBM rate and
    the operations over the bf16 peak (64 operations a byte: the bytes),
    times the layers."""
    lanes = int(model["index_head_dim"])
    return int(model["num_hidden_layers"]) * max(
        rows_scored * lanes * 2 / peaks["hbm_bytes_per_s"],
        index_score_ops(rows_scored, model) / peaks["bf16_flops_per_s"])


def layer_product_flops(model: Dict[str, Any]) -> float:
    """One row through ONE layer's attention half outside the context: the
    query's two projections, the latent's, the absorbed pair (the query
    into the latent, the output out of it), the output projection and the
    indexer's three, 2 operations a multiply-add."""
    d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
    qr, rank = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv = int(model["v_head_dim"])
    hi, li = int(model["index_n_heads"]), int(model["index_head_dim"])
    return 2.0 * (d * qr + qr * h * (nope + rope) + d * (rank + rope)
                  + h * nope * rank + h * rank * dv + h * dv * d
                  + qr * hi * li + d * li + d * hi)


def step_flops(slot_steps: float, rows_read: float, rows_scored: float,
               held_pairs: float, model: Dict[str, Any]) -> float:
    """The model's operations for ``slot_steps`` decoded rows: each through
    every held layer's products, the dense layers' SwiGLU, the sparse
    layers' router and shared expert and the head (the vocabulary slice);
    ``held_pairs`` (token, expert) pairs through a held expert's three
    matrices; the chosen rows' attention (``rows_read``) and the index
    scores (``rows_scored``), each ONE layer's count times the layers."""
    n = int(model["num_hidden_layers"])
    dense = len(model["dense_layers_held"])
    d, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    per_row = (n * layer_product_flops(model)
               + dense * 6.0 * d * int(model["intermediate_size"])
               + (n - dense) * (2.0 * d * int(
                   model["published"]["n_routed_experts"]) + 6.0 * d * f)
               + 2.0 * d * int(model["vocab_size"]))
    row = flops_mla.latent_row_values(model)
    attn = (rows_read * int(model["num_attention_heads"])
            * (row + int(model["kv_lora_rank"])) * 2.0)
    return (slot_steps * per_row + held_pairs * 6.0 * d * f
            + n * (attn + index_score_ops(rows_scored, model)))

"""Operations and bytes the grouped-differential latent decoder needs in a
decode step, from shapes: the denominators of the roofline shares of
``grid/readers/gdla.py``. The counts are of the mathematics, whatever
implements it, and count layers BY KIND from the configuration's
``layer_types`` (``full``: every position of a slot's context; ``window``:
the last ``sliding_window``). Beside ``grid/flops_mla.py`` and
``grid/flops_hybrid.py``, which a later PR may not edit; the same rule
holds here."""

from __future__ import annotations

from typing import Any, Dict

from . import flops_mla

FULL, RING = "full", "window"


def layers_of(model: Dict[str, Any], kind: str) -> int:
    """How many of the layers HELD are of ``kind``."""
    n = int(model["num_hidden_layers"])
    return sum(1 for t in model["layer_types"][:n] if t == kind)


def _mla_keys(model: Dict[str, Any]) -> Dict[str, Any]:
    """The keys ``flops_mla`` reads, for ONE layer of this model."""
    return {"num_hidden_layers": 1, "kv_lora_rank": model["kv_lora_rank"],
            "qk_rope_head_dim": model["qk_rope_head_dim"],
            "num_attention_heads": model["num_attention_heads"]}


def attn_decode_need_s(rows_a_layer: float, kind: str, model: Dict[str, Any],
                       peaks: Dict[str, float]) -> float:
    """The least time the chip could take for the decode attention of the
    layers of ``kind``: ``rows_a_layer`` is what ONE such layer read over
    the steps counted (``serving/attn_rows_read.<group>``: a slot's whole
    context in a full layer, ``min(context, window)`` in a window layer).
    The larger of rows x 576 values x 2 bytes over the HBM rate and rows x
    80 heads x (576 + 512) x 2 operations over the bf16 peak (151
    operations a byte against a ridge of 240: the bytes), times the
    layers; the noise heads' rows of scores are part of the mathematics
    and are counted."""
    return layers_of(model, kind) * flops_mla.mla_decode_need_s(
        rows_a_layer, _mla_keys(model), peaks)


def expert_stream_bytes(experts_touched: float, model: Dict[str, Any]
                        ) -> float:
    """``flops_mla.held_expert_stream_bytes``, whose count is right for
    this model: the three matrices (gate, up: d x f; down: f x d) of every
    HELD expert that received a row, once; ``experts_touched`` summed over
    the steps and the expert layers counted. An expert's four PolyNorm
    numbers are 16 bytes and are not counted."""
    return flops_mla.held_expert_stream_bytes(experts_touched, model)

"""Operations and bytes the hybrid (linear-attention beside latent-attention)
decoder needs, from shapes: the denominators of the roofline shares of
``grid/readers/hybrid.py``. The counts are of the mathematics, whatever
implements it, and count layers BY KIND from the configuration's
``layer_types`` (``flops_mla.mla_decode_bytes`` multiplies by
``num_hidden_layers``: right where every layer is latent, seven times too
much where one in seven is). Beside ``grid/flops_mla.py``, which a later PR
may not edit; the same rule holds here."""

from __future__ import annotations

from typing import Any, Dict

from . import flops_mla

KDA, MLA = "kda", "mla"


def layers_of(model: Dict[str, Any], kind: str) -> int:
    """How many of the layers HELD are of ``kind``."""
    n = int(model["num_hidden_layers"])
    return sum(1 for t in model["layer_types"][:n] if t == kind)


def state_values(model: Dict[str, Any]) -> int:
    """Values of one slot's recurrent state in one KDA layer: a ``head_dim
    x head_dim`` matrix a head (32 x 128 x 128)."""
    return int(model["num_attention_heads"]) * int(model["head_dim"]) ** 2


def kda_step_bytes(model: Dict[str, Any]) -> int:
    """The least one decode step of one slot in one KDA layer must move:
    the float32 state read and written, and the step's q, k, the log-decay
    (a value a channel of every head), v, o (the same) and beta (a head),
    at 4 bytes a value."""
    h, d = int(model["num_attention_heads"]), int(model["head_dim"])
    return 4 * (2 * state_values(model) + 5 * h * d + h)


def kda_step_need_s(slot_steps: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """``slot_steps`` (live slots summed over the decode steps counted)
    times the KDA layers' bytes over the HBM rate: some 7 operations a
    state value against 8 bytes, so the bytes bound it."""
    return (slot_steps * layers_of(model, KDA) * kda_step_bytes(model)
            / peaks["hbm_bytes_per_s"])


def kda_scan_bytes(tokens: float, prefills: float, model: Dict[str, Any]
                   ) -> float:
    """What the recurrence over ``tokens`` prompt positions of
    ``prefills`` prompts must move, a KDA layer: q, k, v in bf16, the
    log-decay and o in float32 (a value a channel), beta (a head), and
    each prompt's final float32 state."""
    h, d = int(model["num_attention_heads"]), int(model["head_dim"])
    per_token = h * d * (3 * 2 + 2 * 4) + h * 4
    return tokens * per_token + prefills * 4 * state_values(model)


def kda_scan_flops(tokens: float, model: Dict[str, Any]) -> float:
    """The recurrence's own operations a token and KDA layer: a head's
    decay (dk x dv multiplies) and ``k^T S``, the rank-one write and ``q^T
    S`` at 2 dk x dv each."""
    return tokens * 7 * state_values(model)


def kda_scan_need_s(tokens: float, prefills: float, model: Dict[str, Any],
                    peaks: Dict[str, float]) -> float:
    """The larger of the two over the chip's peaks, every KDA layer."""
    return layers_of(model, KDA) * max(
        kda_scan_bytes(tokens, prefills, model) / peaks["hbm_bytes_per_s"],
        kda_scan_flops(tokens, model) / peaks["bf16_flops_per_s"])


def latent_decode_need_s(live_rows: float, model: Dict[str, Any],
                         peaks: Dict[str, float]) -> float:
    """``flops_mla.mla_decode_need_s`` with the MLA layers counted:
    ``live_rows`` is what ONE latent layer read."""
    per_layer = dict(model, num_hidden_layers=1)
    return layers_of(model, MLA) * flops_mla.mla_decode_need_s(
        live_rows, per_layer, peaks)

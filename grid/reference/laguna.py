"""Laguna-S-2.1's forward pass, plain (the model's public ``config.json``,
``model_type: laguna``, poolside). For layer ``l`` with input ``x`` [d],
position ``p``, ``K`` KV heads of ``D`` lanes and ``H_l`` query heads
(``num_attention_heads_per_layer[l]``: 48 on full layers, 72 on sliding
ones, over the same 8 KV heads), ``G_l = H_l / K``

    h     = RMSNorm(x; g1)
    q     = h Wq (H_l heads), k = h Wk, v = h Wv (K heads), no biases
    full layer:     q, k = RoPE over the FIRST ``D * partial_rotary_factor``
                    lanes of a head (64 of 128), YaRN frequencies
                    (:func:`rope_table`), cos and sin multiplied by
                    ``attention_factor``; the other lanes carry no position
    sliding layer:  q, k = RoPE over all D lanes, theta 10,000, plain
    allowed(i, j) = j <= i                          full layer
                  = j <= i and i - j < window       sliding layer
    a_n   = softmax_j(q_n . k_{n // G_l} / sqrt(D) over allowed) v_{n // G_l}
    gamma = sigmoid(h Wgamma)            [H_l]: ``gating: "per-head"``
    x     = x + concat_n(gamma_n a_n) Wo
    u     = RMSNorm(x; g2)
    l in mlp_only_layers:  x = x + (silu(u Wg) * (u Wu)) Wd
    else:  s = softmax(u Wr) over ALL experts;  T = the top_k largest of s
           w_e = moe_routed_scaling_factor * s_e / sum_{e in T} s_e
           x = x + sum_{e in T, e held} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
                 + (silu(u Wg_s) * (u Wu_s)) Wd_s

and ``logits = RMSNorm(x; gf) W_head``, the head not tied to the embedding.
Float32 throughout at ``jax.default_matmul_precision("highest")``; no cache,
no kernels, no batching; a plain loop over the held experts, each applied
to EVERY row and weighted by ``w`` (zero where not chosen). So that five
layers at 9,216 positions fit a chip beside the served model, the weights
are cast to float32 a matrix at a time, attention is computed in blocks of
query rows, the dense layer in blocks of its width, and the head is applied
to the rows asked for only.

What the config does not state, and what is assumed (the configuration's
file lists each with its reason):

* SiLU gates (no ``hidden_act`` key; the family's convention);
* a softmax router over all experts (no score-function key; the keys are
  the Qwen-MoE set), no soft cap (``moe_router_logit_softcapping`` 0), the
  weight applied to the expert's OUTPUT
  (``moe_apply_router_weight_on_input`` false);
* no gate on the shared expert and no QK norm (no key for either);
* the head-wise gate reads the same ``h`` as ``q`` (arXiv:2505.06708);
* rotary pairing: lane i of the rotary lanes is rotated with lane i + rot /
  2 (rotate-half). With seeded weights another pairing is a permutation of
  ``Wq``'s and ``Wk``'s columns, and the scores are the same.

Departures, each a share:

* ``experts_held`` names the global ids of the experts in ``wg``/``wu``/
  ``wd`` (default: all of them); an expert not held adds nothing, as in
  the served layer (the chip that holds it adds its part). The shared
  expert and the router are whole on every chip;
* the vocabulary may be a slice: ``tok_emb`` and ``head`` have the rows and
  columns they have.

The parameter tree is the served one (``tok_emb``, ``head``, ``gf`` and a
layer ``g1 g2 wq wk wv wgam wo`` with ``wg wu wd`` of a dense layer, or ``wr
wg wu wd sg su sd`` of an expert layer).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/decoder.py``, with two limits of this configuration's own,
# each set from two readings on the chip at the published widths (PERF.md,
# Findings, PR 34). A request's WORST row is set by a near-tie among the 10
# chosen of 256 experts that bf16 flips, not by rounding (a tenth of a
# request's rows pick another token than the reference's best, nearly all
# of them within 0.1): served in bf16 it read at most 0.502 over 52
# requests of 15 runs (contexts 2,613-8,337; 0.25 at the median); the
# float32 reference with every matrix rounded to fp8 e4m3, the nearest
# precision below the stated one, 1.284, 1.306 and 1.381 (contexts 2,613,
# 4,532 and 8,337). LOGIT_MARGIN is their geometric middle and catches a
# row gone wrong; what tells a lower precision apart is the MEAN over a
# request's rows, which a rare flip barely moves: served at most 0.0074,
# the fp8 reference 0.188, 0.205 and 0.209 (twenty-five times the served
# reading), and MEAN_GAP_LIMIT is near their geometric middle (0.037).
LOGIT_MARGIN = 0.8
MEAN_GAP_LIMIT = 0.035

Q_BLOCK = 64
F_BLOCK = 2048


def rope_table(d_head: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """``(inv_freq [rot / 2], attention_factor)`` of one entry of
    ``rope_parameters``, over the ``rot = d_head * partial_rotary_factor``
    rotary lanes. ``default``: pair i runs at ``theta^(-2i / rot)``, factor
    1. ``yarn``: pair i keeps that frequency below the correction range,
    runs at it over ``factor`` above the range, and ramps linearly between;
    the range is floor/ceil of the pairs that make ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position_embeddings``;
    cos and sin are multiplied by ``attention_factor`` (default ``0.1
    ln(factor) + 1``)."""
    rot = int(round(d_head * float(rope.get("partial_rotary_factor", 1.0))))
    theta = float(rope["rope_theta"])
    half = rot // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rot)
    if rope.get("rope_type", "default") == "default":
        return freq, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("rope_type %r" % rope["rope_type"])
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rot * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    att = rope.get("attention_factor")
    return (freq * (1.0 - ramp) + freq / factor * ramp,
            float(att) if att is not None else 0.1 * math.log(factor) + 1.0)


def layer_rope(model: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """The entry of ``rope_parameters`` that layer ``layer`` uses."""
    return model["rope_parameters"][model["layer_types"][layer]]


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, inv_freq, factor):
    """Rotate-half over the first ``2 * len(inv_freq)`` lanes of ``x`` [S,
    H, D]; the other lanes pass."""
    half = len(inv_freq)
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def _attention(q, k, v, window):
    """``q`` [S, Hq, D], ``k``/``v`` [S, Hkv, D]; ``window`` 0 = full."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(b, qi):
        rows = b * bq + jnp.arange(bq)[:, None]
        ok = cols <= rows
        if window:
            ok = ok & (rows - cols < window)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(
        lambda a: block(*a),
        (jnp.arange(s // bq), q.reshape(s // bq, bq, hkv, hq // hkv, d)))
    return out.reshape(s, hq, d)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _attend(lp, x, pos, n_head, n_kv_head, window, inv_freq, factor, eps):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        h = _rms(x, lp["g1"].astype(f32), eps)
        q = (h @ lp["wq"].astype(f32)).reshape(s, n_head, -1)
        k = (h @ lp["wk"].astype(f32)).reshape(s, n_kv_head, -1)
        v = (h @ lp["wv"].astype(f32)).reshape(s, n_kv_head, -1)
        q, k = _rope(q, pos, inv_freq, factor), _rope(k, pos, inv_freq, factor)
        a = _attention(q, k, v, window)
        gamma = jax.nn.sigmoid(h @ lp["wgam"].astype(f32))       # [S, H]
        a = (a * gamma[:, :, None]).reshape(s, -1)
        return x + a @ lp["wo"].astype(f32)


def _swiglu(u, wg, wu, wd):
    """``(silu(u Wg) * (u Wu)) Wd``, over ``F_BLOCK`` columns of the
    width at a time where it is that wide (the dense layer's 12,288): the
    sum over the blocks is the same product."""
    f32 = jnp.float32
    f = wg.shape[1]
    bs = F_BLOCK if f % F_BLOCK == 0 else f

    def part(i, acc):
        g = jax.lax.dynamic_slice_in_dim(wg, i * bs, bs, 1).astype(f32)
        up = jax.lax.dynamic_slice_in_dim(wu, i * bs, bs, 1).astype(f32)
        dn = jax.lax.dynamic_slice_in_dim(wd, i * bs, bs, 0).astype(f32)
        return acc + (jax.nn.silu(u @ g) * (u @ up)) @ dn

    return jax.lax.fori_loop(0, f // bs, part,
                             jnp.zeros((u.shape[0], wd.shape[1]), f32))


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(lp, x, eps):
    with jax.default_matmul_precision("highest"):
        u = _rms(x, lp["g2"].astype(jnp.float32), eps)
        return x + _swiglu(u, lp["wg"], lp["wu"], lp["wd"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _sparse(lp, x, top_k, routed_scale, eps, held):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        n = x.shape[0]
        u = _rms(x, lp["g2"].astype(f32), eps)
        s = jax.nn.softmax(u @ lp["wr"].astype(f32), axis=-1)
        chosen, idx = jax.lax.top_k(s, top_k)
        w_top = routed_scale * chosen / jnp.sum(chosen, axis=-1,
                                                keepdims=True)
        # [N, E]: the weight of expert e in row n, zero where not chosen
        w = jnp.zeros_like(s).at[jnp.arange(n)[:, None], idx].set(w_top)
        x = x + _swiglu(u, lp["sg"], lp["su"], lp["sd"])

        def expert(j, acc):
            y = _swiglu(u, lp["wg"][j], lp["wu"][j], lp["wd"][j])
            return acc + w[:, jnp.asarray(held)[j]][:, None] * y

        return jax.lax.fori_loop(0, len(held), expert, x)


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(gf, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gf.astype(jnp.float32), eps) @ head.astype(jnp.float32)


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens
           ) -> jnp.ndarray:
    """``x`` [S, d] after the last layer of one sequence ``tokens`` [S].
    ``model`` gives the sizes under the published config's own keys."""
    eps = float(model["rms_norm_eps"])
    d_head = int(model["head_dim"])
    x = params["tok_emb"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for i, lp in enumerate(params["layers"]):
        inv_freq, factor = rope_table(d_head, layer_rope(model, i))
        sliding = model["layer_types"][i] == "sliding_attention"
        x = _attend(lp, x, pos,
                    int(model["num_attention_heads_per_layer"][i]),
                    int(model["num_key_value_heads"]),
                    int(model["sliding_window"]) if sliding else 0,
                    tuple(float(f) for f in inv_freq), factor, eps)
        if "wr" in lp:
            held = tuple(model.get("experts_held")
                         or range(lp["wg"].shape[0]))
            x = _sparse(lp, x, int(model["num_experts_per_tok"]),
                        float(model["moe_routed_scaling_factor"]), eps, held)
        else:
            x = _dense(lp, x, eps)
    return x


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None) -> jnp.ndarray:
    """Logits of one sequence: every row [S, V], or the ``rows`` asked
    for."""
    x = hidden(params, model, jnp.asarray(tokens))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _logits(params["gf"], params["head"], x,
                   float(model["rms_norm_eps"]))


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 256) -> np.ndarray:
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): for each of the
    output's tokens, how far the served token ranks below the row's best
    logit, in row standard deviations (0 where it IS the best). The
    sequence is padded to a multiple of ``pad_to`` (causality keeps the
    padding out of every row that is read)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    logits = forward(params, model, toks,
                     rows=np.arange(first, first + len(output)))
    picked = jnp.take_along_axis(
        logits, jnp.asarray(output, jnp.int32)[:, None], axis=-1)[:, 0]
    return np.asarray((logits.max(-1) - picked) / logits.std(-1))

"""Kimi-K2-Instruct's forward pass, plain (the model's public
``config.json``, ``model_type: kimi_k2``: the DeepSeek-V3 block). For layer
``l`` with input ``x`` [d], position ``p`` and ``H`` heads

    h    = RMSNorm(x; g1)
    cq   = RMSNorm(h Wqa; gq)
    q    = cq Wqb                         H x (nope | rope)
    ckv, kr = split(h Wkva, rank | rope)
    c    = RMSNorm(ckv; gkv)              the latent
    q_r, kr' = RoPE(q_rope, kr; p)        ONE rotary key for all heads
    [k_nope_n | v_n] = c Wkvb, per head n
    score_n(i, j) = scale (q_nope_n(i) . k_nope_n(j) + q_r_n(i) . kr'(j)),
                    j <= i
    a_n  = softmax_j(score_n) v_n ;  x = x + concat_n(a_n) Wo
    u    = RMSNorm(x; g2)
    l < first_k_dense_replace:  x = x + (silu(u Wg) * (u Wu)) Wd
    else:  s = sigmoid(u Wr);  T = top-k of (s + b)
           w_e = routed_scaling_factor * s_e / (sum_{e in T} s_e + 1e-20)
           x = x + sum_{e in T, e held} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
                 + (silu(u Wg_s) * (u Wu_s)) Wd_s

and ``logits = RMSNorm(x; gf) W_head``, the head not tied to the embedding,
no biases anywhere. ``scale = (nope + rope)^-0.5 * m^2`` with ``m = 0.1
mscale_all_dim ln(factor) + 1`` (YaRN); the rotary frequencies are YaRN's
(:func:`yarn_inv_freq`), and cos and sin carry ``mscale / mscale_all_dim``
= 1. Float32 throughout at ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no batching, no absorption (K and V of every head are
made from the latent, as the equations say); a plain loop over the held
experts, each applied to EVERY row and weighted by ``w`` (zero where not
chosen). So that seven layers at 9,216 positions fit a chip beside the
served model, the weights are cast to float32 a matrix at a time,
attention is computed in blocks of query rows, and the head is applied to
the rows asked for only.

Departures from the published description, each a relabelling or a share:

* rotary pairing: lane i of the rotary part is rotated with lane i + rope /
  2 (rotate-half). The family's code first de-interleaves the lanes (2i,
  2i + 1 -> i, i + rope / 2) and then rotates halves; with seeded weights
  that is a permutation of ``Wqb``'s and ``Wkva``'s rotary columns, and the
  scores are the same;
* ``n_group = topk_group = 1``: the group-limited selection is the plain
  top-k, and is written so;
* ``experts_held`` names the global ids of the experts in ``wg``/``wu``/
  ``wd`` (default: all of them); an expert not held adds nothing, as in
  the served layer (the chip that holds it adds its part). The shared
  expert and the router are whole on every chip;
* the vocabulary may be a slice: ``tok_emb`` and ``head`` have the rows and
  columns they have.

The parameter tree is the served one (``tok_emb``, ``head``, ``gf`` and a
layer ``g1 g2 gq gkv wqa wqb wkva wkvb wo`` with ``wg wu wd`` of a dense
layer, or ``wr br wg wu wd sg su sd`` of an expert layer).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/decoder.py``, with two limits of this configuration's own,
# each set from two readings on the chip at the published widths (PERF.md,
# Findings, PR 32). A request's WORST row is a heavy-tailed reading here:
# a near-tie among the 8 chosen of 384 experts that bf16 flips, where the
# flipped expert is one held here, moves a row's logits by a tenth of
# their spread, so the worst of a few hundred rows is set by the rare flip
# and not by rounding. Served in bf16 it read at most 0.669 over 196
# requests of 54 runs (contexts 1,538-8,481; 0.17-0.35 at the median), and
# the model's own bf16 PREFILL path, teacher-forced with no cache and no
# kernel, 0.731 over 8 (it flips as many rows as the served path: the gap
# is bf16's, not the cache's); the float32 reference with every matrix
# rounded to fp8 e4m3, the nearest precision below the stated one, 1.097,
# 1.135, 1.180 and 1.202. LOGIT_MARGIN bounds the worst row, between the
# two, and catches a row gone wrong; what tells a lower precision apart is
# the MEAN over a request's rows, which a rare flip barely moves: served
# at most 0.0038 over 160 requests, the fp8 reference 0.122, 0.135, 0.148
# and 0.152 (forty times the served reading), and MEAN_GAP_LIMIT is their
# geometric middle.
LOGIT_MARGIN = 0.9
MEAN_GAP_LIMIT = 0.02

Q_BLOCK = 128
F_BLOCK = 2048


def yarn_inv_freq(rope_dim: int, theta: float, scaling: Dict[str, Any]
                  ) -> np.ndarray:
    """The ``rope_dim / 2`` rotary frequencies under YaRN: pair i keeps
    ``theta^(-2i / rope_dim)`` below the correction range, runs at that
    over ``factor`` above it, and ramps linearly between."""
    half = rope_dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rope_dim)
    if not scaling:
        return freq
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rope_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               rope_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return freq * (1.0 - ramp) + freq / float(scaling["factor"]) * ramp


def softmax_scale(model: Dict[str, Any]) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``mscale^2`` where the config
    scales all dimensions."""
    scale = (int(model["qk_nope_head_dim"])
             + int(model["qk_rope_head_dim"])) ** -0.5
    sc = model.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        m = 0.1 * float(sc["mscale_all_dim"]) * math.log(sc["factor"]) + 1.0
        scale *= m * m
    return scale


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, inv_freq):
    """Rotate-half over the last axis of ``x`` [S, ..., rope]."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv_freq, jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attention(q, k, v, scale):
    """``q``/``k`` [S, H, Dqk], ``v`` [S, H, Dv]; causal."""
    s, h, _ = q.shape
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(b, qi):
        rows = b * bq + jnp.arange(bq)[:, None]
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        p = jax.nn.softmax(jnp.where((cols <= rows)[None], sc, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(s // bq), q.reshape(s // bq, bq, h, -1)))
    return out.reshape(s, -1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _attend(lp, x, pos, n_head, nope, rope, scale, eps, inv_freq):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        rank = lp["gkv"].shape[0]
        h = _rms(x, lp["g1"].astype(f32), eps)
        cq = _rms(h @ lp["wqa"].astype(f32), lp["gq"].astype(f32), eps)
        q = (cq @ lp["wqb"].astype(f32)).reshape(s, n_head, nope + rope)
        kva = h @ lp["wkva"].astype(f32)
        c = _rms(kva[:, :rank], lp["gkv"].astype(f32), eps)
        q_r = _rope(q[..., nope:], pos, inv_freq)
        kr = _rope(kva[:, rank:], pos, inv_freq)
        kv = (c @ lp["wkvb"].astype(f32)).reshape(s, n_head, -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[:, None], (s, n_head, rope))],
            axis=-1)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        a = _attention(q, k, kv[..., nope:], scale)
        return x + a @ lp["wo"].astype(f32)


def _swiglu(u, wg, wu, wd):
    """``(silu(u Wg) * (u Wu)) Wd``, over ``F_BLOCK`` columns of the
    width at a time where it is that wide (the dense layer's 18,432): the
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
        s = jax.nn.sigmoid(u @ lp["wr"].astype(f32))
        _, idx = jax.lax.top_k(s + lp["br"].astype(f32), top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        w_top = routed_scale * chosen / (
            jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
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
    rope = int(model["qk_rope_head_dim"])
    inv_freq = tuple(float(f) for f in yarn_inv_freq(
        rope, float(model["rope_theta"]), model.get("rope_scaling")))
    x = params["tok_emb"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for lp in params["layers"]:
        x = _attend(lp, x, pos, int(model["num_attention_heads"]),
                    int(model["qk_nope_head_dim"]), rope,
                    softmax_scale(model), eps, inv_freq)
        if "wr" in lp:
            held = tuple(model.get("experts_held")
                         or range(lp["wg"].shape[0]))
            x = _sparse(lp, x, int(model["num_experts_per_tok"]),
                        float(model["routed_scaling_factor"]), eps, held)
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


def worst_margin(params, model: Dict[str, Any], prompt: Sequence[int],
                 output: List[int], pad_to: int = 256) -> float:
    """The worst of :func:`row_gaps`."""
    return float(row_gaps(params, model, prompt, output, pad_to).max())

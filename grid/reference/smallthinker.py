"""SmallThinker-21BA3B-Instruct's forward pass, plain (the model's public
``config.json``; PowerInfer, 2025): for layer ``l`` with input ``x`` and
positions ``p``

    h = RMSNorm(x; g1)
    q = h Wq (n_head heads), k = h Wk, v = h Wv (n_kv_head heads), no biases
    rope_layout[l] == 1: q, k = RoPE(q, k; theta, p), rotate-half over the
                         whole head
    allowed(i, j) = j <= i                         sliding_window_layout[l] == 0
                  = j <= i and i - j < window      sliding_window_layout[l] == 1
    a = softmax(q k^T / sqrt(d_head) over allowed) v, query head n on KV
        head n // (n_head / n_kv_head)
    x = x + a Wo
    r = h Wr                       (the router reads h, what attention read)
    T = the top_k largest of r;  w = softmax(r[T])
    u = RMSNorm(x; g2)
    x = x + sum over e in T of w_e (relu(u Wg_e) * (u Wu_e)) Wd_e

and ``logits = RMSNorm(x; gf) W_head`` after the last layer, the head not
tied to the embedding. Float32 throughout at
``jax.default_matmul_precision("highest")``; no cache, no kernels, no
batching; a plain loop over the experts, each applied to EVERY row and
weighted by ``w`` (zero where not chosen). So that twelve layers at 12,800
positions fit a chip beside the served model, the weights are cast to
float32 one layer (and one expert) at a time, attention is computed in
blocks of query rows, and the head is applied to the rows asked for only.

The parameter tree is the served one (``tok_emb``, ``head``, ``gf`` and a
layer ``g1 g2 wq wk wv wo wr wg wu wd``); ``experts_held`` names the global
ids of the experts in ``wg``/``wu``/``wd`` (default: all of them, in
order), and the experts not held add nothing, as in the served layer.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/decoder.py``, with a limit of this configuration's own. Two
# readings on the chip at the published widths set it (PERF.md, Findings,
# PR 27). Served in bf16, the worst gap over 60 sampled requests of 30 runs
# (contexts 703-12,184, half of them past the window) was 0.177: a
# near-tie among the 6 chosen experts that bf16 flips moves a logit by more
# than rounding alone does (GPT-2's worst is 0.057). The float32 reference
# itself with every layer's and the head's weights rounded to fp8 e4m3, the
# nearest precision below the stated one, picks tokens 0.38 (context 2,935)
# and 0.75 (context 8,438) below, and is refused. The limit is the middle of
# the two readings.
LOGIT_MARGIN = 0.28

Q_BLOCK = 256


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attention(q, k, v, window):
    """``q`` [S, Hq, D], ``k``/``v`` [S, Hkv, D]; ``window`` 0 = global."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(b, qi):
        rows = b * bq + jnp.arange(bq)[:, None]
        ok = cols <= rows
        if window:
            ok = ok & (rows - cols < window)
        sc = jnp.einsum("qhd,khd->hqk", qi, kr) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vr)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(s // bq), q.reshape(s // bq, bq, hq, d)))
    return out.reshape(s, hq * d)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _layer(lp, x, pos, n_head, n_kv_head, top_k, rope, window, theta, eps,
           held):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        h = _rms(x, lp["g1"].astype(f32), eps)
        q = (h @ lp["wq"].astype(f32)).reshape(s, n_head, -1)
        k = (h @ lp["wk"].astype(f32)).reshape(s, n_kv_head, -1)
        v = (h @ lp["wv"].astype(f32)).reshape(s, n_kv_head, -1)
        if rope:
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        x = x + _attention(q, k, v, window) @ lp["wo"].astype(f32)
        r = h @ lp["wr"].astype(f32)
        top, idx = jax.lax.top_k(r, top_k)
        w_top = jax.nn.softmax(top, axis=-1)
        # [S, E]: the weight of expert e in row s, zero where not chosen
        w = jnp.zeros_like(r).at[jnp.arange(s)[:, None], idx].set(w_top)
        u = _rms(x, lp["g2"].astype(f32), eps)

        def expert(j, acc):
            gate = jax.nn.relu(u @ lp["wg"][j].astype(f32))
            up = u @ lp["wu"][j].astype(f32)
            y = (gate * up) @ lp["wd"][j].astype(f32)
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
    n_layer = len(params["layers"])
    e_held = params["layers"][0]["wg"].shape[0]
    held = tuple(model.get("experts_held") or range(e_held))
    x = params["tok_emb"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for i in range(n_layer):
        x = _layer(params["layers"][i], x, pos,
                   int(model["num_attention_heads"]),
                   int(model["num_key_value_heads"]),
                   int(model["moe_num_active_primary_experts"]),
                   bool(model["rope_layout"][i]),
                   int(model["sliding_window_size"])
                   if model["sliding_window_layout"][i] else 0,
                   float(model["rope_theta"]), float(model["rms_norm_eps"]),
                   held)
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


def worst_margin(params, model: Dict[str, Any], prompt: Sequence[int],
                 output: List[int], pad_to: int = 256) -> float:
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): the worst, over
    the output's tokens, of how far the served token ranks below the row's
    best logit, in row standard deviations. The sequence is padded to a
    multiple of ``pad_to`` (causality keeps the padding out of every row
    that is read)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    logits = forward(params, model, toks,
                     rows=np.arange(first, first + len(output)))
    picked = jnp.take_along_axis(
        logits, jnp.asarray(output, jnp.int32)[:, None], axis=-1)[:, 0]
    gaps = (logits.max(-1) - picked) / logits.std(-1)
    return float(gaps.max())

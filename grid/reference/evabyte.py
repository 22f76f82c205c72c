"""EvaByte's byte-level decoder, plain (the model's public ``config.json``,
``model_type: evabyte``, ``attention_class: eva``; the attention is
"Efficient Attention via Control Variates", Zheng, Yuan, Wang, Kong, ICLR
2023, arXiv:2302.04542, in the learned-pooling form the family's model card
and modelling code describe). With ``d`` the hidden size, H heads of ``D =
d / H`` (as many KV heads), window ``W``, chunk ``c``, eps 1e-5:

    x <- E[byte]                                  the residual float32
    for l in 0..L-1:
      a = RMSNorm(x; 1 + g1_l)                    the gain is (1 + g)
      q, k, v = a Wq_l, a Wk_l, a Wv_l            no bias
      q, k = rope(q, pos), rope(k, pos)           rotate-half, D lanes, theta 1e5
      -- chunk C is positions [c C, c C + c); a head h; k AFTER the rotation:
      w_j  = softmax_j((k_j . phi_lh) / sqrt(D))  j over the chunk's c rows
      ks_C = sum_j w_j k_j + mu_lh
      vs_C = sum_j w_j v_j
      -- what position p attends to (Wp = p // W; W / c chunks a window):
      K_p  = [ks_C : C < (W / c) Wp] ++ [k_j : W Wp <= j <= p]
      V_p  = [vs_C : C < (W / c) Wp] ++ [v_j : W Wp <= j <= p]
      o_p  = softmax(q_p K_p^T / sqrt(D)) V_p      ONE softmax over both parts
      x = x + o Wo_l
      b = RMSNorm(x; 1 + g2_l)
      x = x + (silu(b Wg_l) * (b Wu_l)) Wd_l
    x = RMSNorm(x; 1 + gf)
    logits[p, i] = x_p Wh_i,  i in 0..n-1         head i scores byte p + 1 + i

A window is a TUMBLING one: it opens at a multiple of W; a query never
sees an exact row of an earlier window, nor a summary of its own window's
chunks. Float32 throughout at ``jax.default_matmul_precision("highest")``;
a Python loop over the layers; ``K_p`` and ``V_p`` are a MASK over ``[every
chunk's summary ++ every position's row]``, a block of query rows at a
time; no cache, no pages, no kernels, no batching, no row map. So that it
fits a chip beside the served weights, a layer's matrices are lifted to
float32 inside that layer's call and the heads are applied to the rows
asked for only.

What the config does not give is a reading, listed under ``assumed`` in
the configuration file: the rotary pairing, ``phi`` and ``mu`` (their
shapes, where they enter, the scale inside the pooling softmax, pooling
AFTER the rotation), that a window's summaries are seen only once it has
closed, the heads' layout and which byte head i scores. The parameter tree
is the served one (``models/evabyte.py``): ``head`` is ``[d, n V]``, head
i its columns ``[i V, (i + 1) V)``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# How far the served logits (all prediction heads, every decoded row of a
# request) may lie from this reference's: the largest absolute gap and the
# mean absolute gap, each over the spread (standard deviation) of the
# reference's logits at the same rows. With 320 logits a row a head the
# VALUES are compared, not a rank. Each lies between readings on the chip
# at the published widths, every control read THROUGH ``grid.run`` on the
# limits below (PERF.md, Findings, PR 58, has every one). Served in
# bfloat16 with the float32 residual and pooling the configuration states,
# the requests compared (contexts of 4.4k and 16.6k bytes, each across a
# window's close) read at most 0.030 at their worst logit and
# 0.0038-0.0043 at the mean (twenty runs). The structural controls of
# ``benchmarks/control_evabyte.py`` read far beyond both (closed windows
# dropped 4.74 and 0.262; ``mu`` left out 1.82 and 0.127; the pooling
# weights uniform 0.437 and 0.031: 14 and 7 times the served runs' at
# least). The residual and the pooling in bfloat16 (``bf16``: the nearest
# precision below the stated one) read 0.059 and 0.0090, ``correct: false``
# by the MEAN alone: bfloat16 operands already put the served logits
# 0.004 from float32's, a bfloat16 residual doubles that and no statistic
# of the logits parts the two further. 0.006 lies 41% over the served
# runs' largest mean and the control 50% over it: narrow as a ratio, wide
# against the statistic's own movement (a request's mean over its million
# logits moves within 4% between seeds, 0.00376-0.00387 and
# 0.00409-0.00424; the control's 0.0086-0.0087 and 0.0090-0.0091 at its
# two). The worst logit is one value of a request's million
# and moves more between requests; its limit stands three times over the
# served runs' worst and a quarter of the way to the nearest structural
# control, and does not see the precision control. The prompt's last row
# is the prefill executable's, which hands out the byte it chose: that
# byte's logit in this reference's head 0 may lie twice ``LOGIT_MARGIN``
# under the row's best (``drivers/serve_eva.check``; 0 in every served
# run).
LOGIT_MARGIN = 0.1
MEAN_GAP_LIMIT = 0.006

Q_BLOCK = 128


def _f32(w):
    """A stored matrix as the float32 the reference multiplies by."""
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    """RMSNorm with the gain ``1 + g``."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    """Rotate-half over the whole last axis of ``x`` [S, H, D]."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def summaries(k, v, phi, mu, chunk: int):
    """``(ks, vs)`` [S / c, H, D]: every chunk's pooled key and value of
    ``k``/``v`` [S, H, D] (k rotated), by the definition above."""
    s, h, d = k.shape
    kc, vc = (t.reshape(s // chunk, chunk, h, d) for t in (k, v))
    w = jax.nn.softmax(jnp.sum(kc * phi, axis=-1) * d ** -0.5, axis=1)
    return (jnp.sum(w[..., None] * kc, axis=1) + mu,
            jnp.sum(w[..., None] * vc, axis=1))


def _attention(q, k, v, ks, vs, window: int, chunk: int):
    """``q``/``k``/``v`` [S, H, D], ``ks``/``vs`` [S / c, H, D]: position
    p over ``K_p``, ``V_p`` as defined above, a mask over ``[chunks ++
    positions]``; a block of query rows at a time."""
    s, h, d = q.shape
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    keys, values = jnp.concatenate([ks, k]), jnp.concatenate([vs, v])
    chunks = jnp.arange(ks.shape[0])[None, :]
    cols = jnp.arange(s)[None, :]

    def block(bi, qi):
        p = bi * bq + jnp.arange(bq)[:, None]
        opened = window * (p // window)         # the query's window's first
        seen = jnp.concatenate([
            chunks < (window // chunk) * (p // window),
            (opened <= cols) & (cols <= p)], axis=1)
        sc = jnp.einsum("qhd,khd->hqk", qi, keys) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, values)

    out = jax.lax.map(lambda t: block(*t),
                      (jnp.arange(s // bq), q.reshape(s // bq, bq, h, d)))
    return out.reshape(s, h, d)


def _frozen(model: Dict[str, Any]) -> Tuple:
    """The numbers a jitted layer closes over, hashable."""
    return (int(model["num_attention_heads"]), float(model["rms_norm_eps"]),
            float(model["rope_theta"]), int(model["window_size"]),
            int(model["chunk_size"]))


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(lp, x, pos, frozen):
    """One layer over ``x`` [S, d]; returns ``(x', (|attention's add|,
    |MLP's add|) root mean squares)``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        n_head, eps, theta, window, chunk = frozen
        s = x.shape[0]
        a = _rms(x, lp["g1"].astype(f32), eps)
        q = _rope((a @ _f32(lp["wq"])).reshape(s, n_head, -1), pos, theta)
        k = _rope((a @ _f32(lp["wk"])).reshape(s, n_head, -1), pos, theta)
        v = (a @ _f32(lp["wv"])).reshape(s, n_head, -1)
        ks, vs = summaries(k, v, lp["phi"].astype(f32),
                           lp["mu"].astype(f32), chunk)
        att = _attention(q, k, v, ks, vs, window, chunk).reshape(s, -1) \
            @ _f32(lp["wo"])
        x = x + att
        b = _rms(x, lp["g2"].astype(f32), eps)
        ff = (jax.nn.silu(b @ _f32(lp["wg"])) * (b @ _f32(lp["wu"]))) \
            @ _f32(lp["wd"])

        def rms(t):
            return jnp.sqrt(jnp.mean(jnp.square(t)))

        return x + ff, jnp.stack([rms(att), rms(ff), rms(x + ff)])


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(gf, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gf.astype(jnp.float32), eps) @ _f32(head)


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None, shares: List = None) -> jnp.ndarray:
    """The logits of every prediction head for one sequence ``tokens`` [S]
    (whole chunks): ``[S, n, V]``, or the ``rows`` asked for. ``model``
    gives the sizes under the published config's own keys. ``shares``, a
    list, is given a layer's ``(|attention's add|, |MLP's add|, |x|)`` root
    mean squares."""
    frozen = _frozen(model)
    tokens = jnp.asarray(tokens)
    x = _f32(params["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0])
    for lp in params["layers"][:int(model["num_hidden_layers"])]:
        x, norms = _layer(lp, x, pos, frozen)
        if shares is not None:
            shares.append(norms)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = _logits(params["gf"], params["head"], x, frozen[1])
    return logits.reshape(x.shape[0], int(model["num_pred_heads"]),
                          int(model["vocab_size"]))


def request_logits(params, model: Dict[str, Any], prompt: Sequence[int],
                   output: List[int], pad_to: int = 128,
                   shares: List = None) -> np.ndarray:
    """Teacher-forced in ONE forward over prompt + output (row i depends on
    bytes <= i only, so row ``len(prompt) - 1 + j`` is the row from which
    the j-th output byte was chosen): the logits ``[len(output), n, V]`` of
    those rows. The sequence is padded to a multiple of ``pad_to`` (whole
    chunks; causality and the tumbling window keep the padding out of
    every row that is read: a chunk that holds padding is summarised for
    later windows, which are padding)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    return np.asarray(forward(params, model, toks,
                              rows=np.arange(first, first + len(output)),
                              shares=shares))


def logit_gaps(served: np.ndarray, want: np.ndarray) -> Tuple[float, float]:
    """``(largest, mean)`` absolute gap of the served logits ``[N, n, V]``
    from the reference's, over the spread of the reference's."""
    want = np.asarray(want, np.float32)
    gap = np.abs(np.asarray(served, np.float32) - want)
    spread = float(want.std())
    return float(gap.max()) / spread, float(gap.mean()) / spread

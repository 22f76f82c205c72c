"""Ouro's looped decoder, plain (the model's public ``config.json``,
``model_type: ouro``; the family's paper is "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741). The SAME ``L`` layers run ``T``
= ``total_ut_steps`` times over a token. With ``d`` the hidden size, 16
heads of 128 (as many KV heads), eps 1e-6, position ``pos``:

    x <- E[token]
    for t in 0..T-1:                   the same weights every t
      for l in 0..L-1:
        a = RMSNorm(x; g1_l)
        q, k, v = a Wq_l, a Wk_l, a Wv_l           no bias
        q, k rotated: rotate-half RoPE over all 128 lanes, theta 1e6
        o = causal softmax(q k^T 128^-0.5) v       over the k, v that THIS
                                                   step of THIS layer made
        x = x + RMSNorm(o Wo_l; g2_l)              the sublayer's OUTPUT is
                                                   normed too (sandwich)
        b = RMSNorm(x; g3_l)
        x = x + RMSNorm((silu(b Wg_l) * (b Wu_l)) Wd_l; g4_l)
      x = RMSNorm(x; gf)                           the ONE final norm, after
                                                   EVERY step, carried on
      lambda_t = sigmoid(x . w_e + b_e)            the exit gate
    p_t = lambda_t prod_{j<t} (1 - lambda_j),  p_{T-1} = prod_{j<T-1} (1 - lambda_j)
    logits = x W_head                              the last step's state;
                                                   the head not tied

``early_exit_threshold`` 1: every step runs and the last state is read.
Float32 throughout at ``jax.default_matmul_precision("highest")``; a
Python loop over the ``T x L`` layer applications; no cache, no kernels,
no batching. So that it fits a chip beside the served weights, a layer's
matrices are lifted to float32 inside that layer's call (one layer at a
time: all 48 in float32 are 10 GB), attention is computed in blocks of
query rows, and the head is applied to the rows asked for only.

What the config does not give is a reading, listed under ``assumed`` in
the configuration file: no attention or MLP bias, the rotary pairing, the
two sandwich norms a layer and where they sit, the final norm after every
step with its output carried on, the gate's form, and that no step shares
K and V with another (the paper's last-step reuse is an approximation: a
different result). The parameter tree is the served one
(``models/ouro.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary (the rule of
# ``reference/falcon_h1.py``), on a request's WORST row and at the MEAN
# over its rows, and how far the served exit distribution may lie from
# this one: the largest ``|p_t - p_t'|`` of a row's four, at the mean over
# a request's decoded rows. Each lies between readings on the chip at the
# published widths (PERF.md, Findings, PR 56, has every one). Served in
# bfloat16, 24 requests of twelve runs (contexts 237-893) read at most
# 0.036 at their worst row, 0.0012 at the mean and 0.0050 on the gate
# (0.0029-0.0050: a mean over 139-491 rows, the steadiest of the three).
# The structural controls of ``benchmarks/control_ouro.py`` read far beyond
# all three (one cache layer for the four steps 0.226, 0.0896 and 0.214;
# three steps 0.474, 0.0745 and 0.145: the first two limits are set a
# third of the way up to them, three times the served runs' worst). This
# reference with every matrix rounded to float8 e4m3 (``ref_fp8``: the
# nearest precision below the stated one) reads 0.0-0.044, 0.0-0.0018 and
# 0.0091-0.0108 in three runs: the rank limits do not see it (a looped
# model's state is normed 192 times on its way and forgives a rounding; at
# one seed the greedy stream settles on a token and every rank reads 0),
# the gate's does, 0.008 lying between 0.0050 and 0.0091 with 60% of room
# over the served runs and 12% under the control, which the rows' limit
# below catches four times over.
LOGIT_MARGIN = 0.1
MEAN_GAP_LIMIT = 0.01
EXIT_P_GAP_LIMIT = 0.008
# What NO rank and no gate sees well is the precision of the POOL's rows: K
# and V kept in float8 (``pool_fp8``) read 0.0-0.036, 0.0-0.0012 and
# 0.0050-0.0064 in three runs, inside all three. So a fourth limit holds a VALUE the
# cache keeps: the K rows of the request resident in a slot at the run's
# end, as the pool holds them in the FIRST of ``ROW_PROBES``' cache layers
# (first step, first layer: nothing upstream of it but the embedding, so
# what it reads is the rows' own rounding), against this reference's K
# after the same tokens, as a share of their length (Frobenius). On the
# chip a bfloat16 pool reads 0.0027-0.0029 there (eight runs), a float8
# pool 0.0267 and this reference in float8 0.0271-0.0281 (on the CPU at
# the same widths 0.0017, 0.0264 and 0.0281): the limit is near the
# geometric middle, 2.4 times over the one and 3.8 under the others. The last probe (last step, last layer:
# everything upstream) is read and reported beside it, 0.0121, 0.0290 and
# 0.0165, and held to nothing: the activations' own rounding is in it.
ROW_GAP_LIMIT = 0.007
ROW_PROBES = ((0, 0), (-1, -1))        # (step, layer), from either end

Q_BLOCK = 128


def _f32(w):
    """A stored matrix as the float32 the reference multiplies by."""
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half over the whole last axis of ``x`` [S, H, D]."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attention(q, k, v, scale):
    """``q`` [S, Hq, D], ``k``/``v`` [S, Hkv, D]; causal, query head n on
    KV head ``n // (Hq / Hkv)``; a block of query rows at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(bi, qi):
        rows = bi * bq + jnp.arange(bq)[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", qi.reshape(bq, hkv, hq // hkv, d),
                        k) * scale
        pr = jax.nn.softmax(jnp.where((cols <= rows)[None, None], sc,
                                      -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(bq, hq, d)

    out = jax.lax.map(lambda t: block(*t),
                      (jnp.arange(s // bq), q.reshape(s // bq, bq, hq, d)))
    return out.reshape(s, hq, d)


def _frozen(model: Dict[str, Any]) -> Tuple:
    """The numbers a jitted layer closes over, hashable."""
    return (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            float(model["rms_norm_eps"]), float(model["rope_theta"]))


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(lp, x, pos, frozen):
    """One layer over ``x`` [S, d]; returns ``(x', (|attention's add|,
    |MLP's add|) root mean squares, K [S, Hkv D] as a cache keeps it:
    rotated)``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        n_head, n_kv, d_head, eps, theta = frozen
        s = x.shape[0]
        a = _rms(x, lp["g1"].astype(f32), eps)
        q = (a @ _f32(lp["wq"])).reshape(s, n_head, d_head)
        k = (a @ _f32(lp["wk"])).reshape(s, n_kv, d_head)
        v = (a @ _f32(lp["wv"])).reshape(s, n_kv, d_head)
        k = _rope(k, pos, theta)
        o = _attention(_rope(q, pos, theta), k, v, d_head ** -0.5)
        att = _rms(o.reshape(s, -1) @ _f32(lp["wo"]),
                   lp["g2"].astype(f32), eps)
        x = x + att
        b = _rms(x, lp["g3"].astype(f32), eps)
        ff = _rms((jax.nn.silu(b @ _f32(lp["wg"])) * (b @ _f32(lp["wu"])))
                  @ _f32(lp["wd"]), lp["g4"].astype(f32), eps)

        def rms(t):
            return jnp.sqrt(jnp.mean(jnp.square(t)))

        return x + ff, jnp.stack([rms(att), rms(ff)]), k.reshape(s, -1)


@functools.partial(jax.jit, static_argnums=(4,))
def _end_of_step(gf, w_e, b_e, x, eps):
    """The final norm and the gate: ``(x', lambda [S])``."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, gf.astype(jnp.float32), eps)
        return x, jax.nn.sigmoid(x @ w_e.astype(jnp.float32)
                                 + b_e.astype(jnp.float32))


@jax.jit
def _logits(head, x):
    with jax.default_matmul_precision("highest"):
        return x @ _f32(head)


def exit_distribution(lam) -> jnp.ndarray:
    """``p`` [S, T] of the gates ``lam`` [S, T], by the definition above:
    the last step takes what the earlier ones left."""
    steps = lam.shape[1]
    p, stay = [], jnp.ones_like(lam[:, 0])
    for t in range(steps - 1):
        p.append(lam[:, t] * stay)
        stay = stay * (1.0 - lam[:, t])
    return jnp.stack(p + [stay], axis=1)


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens,
           shares: List = None, rows: Dict = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(x [S, d] after the last step's final norm, p [S, T])`` of one
    sequence ``tokens`` [S]. ``model`` gives the sizes under the published
    config's own keys. ``shares``, a list, is given a step's ``(|attention's
    add|, |MLP's add|`` at the mean over the layers, ``|x_t - x_{t-1}|)``
    root mean squares, the state a step starts from having length 1 a lane
    (but the first's, the embedding's). ``rows``, a dict keyed ``(step,
    layer)``, is given the K rows [S, Hkv D] that step of that layer
    made."""
    frozen = _frozen(model)
    eps = frozen[3]
    x = _f32(params["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0])
    lam = []
    layers = params["layers"][:int(model["num_hidden_layers"])]
    for t in range(int(model["total_ut_steps"])):
        start, adds = x, []
        for i, lp in enumerate(layers):
            x, norms, k = _layer(lp, x, pos, frozen)
            adds.append(norms)
            if rows is not None and (t, i) in rows:
                rows[t, i] = k
        x, gate = _end_of_step(params["gf"], params["w_e"], params["b_e"],
                               x, eps)
        lam.append(gate)
        if shares is not None:
            shares.append(jnp.concatenate([
                jnp.mean(jnp.stack(adds), axis=0),
                jnp.sqrt(jnp.mean(jnp.square(x - start)))[None]]))
    return x, exit_distribution(jnp.stack(lam, axis=1))


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None, shares: List = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(logits, p)`` of one sequence: every row ([S, V], [S, T]), or the
    ``rows`` asked for."""
    x, p = hidden(params, model, jnp.asarray(tokens), shares)
    if rows is not None:
        x, p = x[jnp.asarray(rows)], p[jnp.asarray(rows)]
    return _logits(params["head"], x), p


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 128, shares: List = None
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): ``(gaps [N], p
    [N, T])``, for each of the output's tokens how far the served token
    ranks below the row's best logit, in row standard deviations (0 where
    it IS the best), and the row's exit distribution. The sequence is
    padded to a multiple of ``pad_to`` (causality keeps the padding out of
    every row that is read)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    logits, p = forward(params, model, toks,
                        rows=np.arange(first, first + len(output)),
                        shares=shares)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(output, jnp.int32)[:, None], axis=1)[:, 0]
    gaps = (logits.max(axis=-1) - picked) / logits.std(axis=-1)
    return np.asarray(gaps), np.asarray(p)


def probes(model: Dict[str, Any]) -> List[Tuple[int, int]]:
    """``ROW_PROBES`` as ``(step, layer)`` of this configuration."""
    ends = (int(model["total_ut_steps"]), int(model["num_hidden_layers"]))
    return [tuple(i % n for i, n in zip(probe, ends))
            for probe in ROW_PROBES]


def kept_rows(params, model: Dict[str, Any], tokens: Sequence[int],
              pad_to: int = 128) -> Dict[Tuple[int, int], np.ndarray]:
    """``{(step, layer): K [n, Hkv D]}`` of :func:`probes`: what a served
    slot KEEPS in those cache layers once it has consumed ``tokens``. The
    sequence is padded to a multiple of ``pad_to`` (causality keeps the
    padding out of the rows that are read)."""
    size = -(-len(tokens) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(tokens)] = tokens
    rows = dict.fromkeys(probes(model))
    hidden(params, model, jnp.asarray(toks), rows=rows)
    return {at: np.asarray(k[:len(tokens)]) for at, k in rows.items()}


def row_gap(served: np.ndarray, want: np.ndarray) -> float:
    """``|served - want| / |want|`` (Frobenius) of one cache layer's rows."""
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(np.asarray(served, np.float32) - want)
                 / np.linalg.norm(want))


def exit_p_gap(served: np.ndarray, want: np.ndarray) -> np.ndarray:
    """The largest ``|p_t - p_t'|`` of each row's steps, [N]."""
    return np.abs(np.asarray(served, np.float32)
                  - np.asarray(want, np.float32)).max(axis=-1)

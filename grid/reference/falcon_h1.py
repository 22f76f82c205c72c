"""Falcon-H1's decoder, plain (the model's public ``config.json``,
``model_type: falcon_h1``). Every block has TWO mixers that read the same
normed input, a Mamba-2 state-space mixer and grouped-query attention, and
adds both to the residual before a dense SwiGLU. With ``d`` the hidden
size, ``d_ssm = H P`` (32 heads of 128), ``G`` = 2 groups of ``N`` = 256
state lanes, a convolution of 4 taps over ``C = d_ssm + 2 G N`` channels,
20 query heads over 4 KV heads of 128, eps 1e-5, and the config's muP
multipliers by their keys, layer input ``h`` [d] at position ``t``:

    h0 = E[token] * embedding_multiplier
    u  = RMSNorm(h; g1)

    SSM branch:
    p  = (W_in (u * ssm_in_multiplier)) * mup     mup scales p's segments
                                                  [z d_ssm | x d_ssm | B G N
                                                  | C G N | dt H] by
                                                  ssm_multipliers[0..4]
    xBC = silu(sum_{j=0..3} w_j p[x|B|C]_{t-3+j} + b_conv)
                                                  depth-wise, zeros before
                                                  the request's start
    dt = softplus(p[dt] + dt_bias)                a head; no clamp
    a  = exp(dt A),  A = -exp(A_log)              one number a head
    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T          S [H, P, N] float32, zero
                                                  at the start; B_t, C_t of
                                                  group h // (H / G)
    y_t = S_t C_t + D x_t
    y  = RMSNorm_group(y * silu(z); gn)           the gate FIRST, then an
                                                  RMS norm over each of the
                                                  G groups' d_ssm / G
                                                  channels
    m  = (W_out y) * ssm_out_multiplier

    attention branch, from the SAME u:
    q = W_q (u * attention_in_multiplier)
    k = (W_k (u * attention_in_multiplier)) * key_multiplier
    v = W_v (u * attention_in_multiplier)
    q, k rotated: rotate-half RoPE over all 128 lanes, theta 1e11
    o = causal softmax(q k^T 128^-0.5) v          query head n reads KV head
                                                  n // 5
    a = (W_o o) * attention_out_multiplier

    h' = h + m + a
    v2 = RMSNorm(h'; g2)
    h'' = h' + (W_d (W_u v2 * silu(W_g v2 * mlp_multipliers[0])))
               * mlp_multipliers[1]

and ``logits = (W_head RMSNorm(h_L; gf)) * lm_head_multiplier``, the head
not tied to the embedding. Float32 throughout at
``jax.default_matmul_precision("highest")``; the recurrence token by
token; no cache, no kernels, no chunks, no batching. So that five layers at
five thousand positions fit a chip beside the served model, the weights
are cast to float32 a matrix at a time, attention is computed in blocks of
query rows, and the head is applied to the rows asked for only.

Departures from the published forward, each a reading the config does not
settle (the configuration file lists them under ``assumed``):

* rotary pairing is rotate-half; the group of SSM head ``h`` is ``h // (H /
  G)``; ``mamba_rms_norm`` true with ``mamba_norm_before_gate`` false is
  the gated norm above, in ``mamba_n_groups`` groups; the family's default
  ``time_step_limit`` is (0, inf), so the step is not clamped;
* the state here is ``[H, N, P]`` (the served cache's order, the
  transpose of the text's ``[H, P, N]``): ``S_t = a_t S_{t-1} + B_t (dt_t
  x_t)^T``, ``y_t = S_t^T C_t``, the same numbers;
* ``W_q``, ``W_k``, ``W_v`` and the SSM's five input segments are stored
  as the served tree stores them (``wq``, ``wk``, ``wv``; ``w_in`` the
  segments side by side: a concatenation, not a change).

The parameter tree is the served one (``models/falcon_h1.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/ling3_flash.py`` with its two limits, on a request's WORST
# row (a heavy-tailed reading: one near-tie among 261,120 logits) and on
# the MEAN over its rows. Each lies between readings on the chip at the
# published widths (PERF.md, Findings, PR 51, has every one). Served in
# bf16 with the state in float32, 28 requests of 14 runs (contexts
# 423-4,739) read at most 0.030 at their worst row (0.015 at the median)
# and at most 0.00036 at the mean. This reference with every
# matrix rounded to float8 e4m3, the nearest precision below the stated
# one (``control_falcon_h1.py ref_fp8``), reads 0.168 and 0.124 at the
# worst row and 0.0043 and 0.0077 at the mean: each limit lies between,
# and fp8 fails both. The other controls read far beyond them (the wrong
# group 0.86-1.02 and 0.086-0.10; B's multiplier at 1 0.88-0.92 and 0.12;
# a branch left out or the keys' multiplier at 1 1.4-3.6 and 0.25-1.26).
LOGIT_MARGIN = 0.1
MEAN_GAP_LIMIT = 0.001
# What NO rank sees is the precision of the recurrent state: served with
# the state rounded to bfloat16 after the prefill's scan and after every
# decode step (``state_bf16``) the same two numbers read 0.011 and 0.00012,
# as good as float32's. So a third limit holds a VALUE the cache keeps: the
# float32 states of the request resident in a slot at the run's end, every
# layer's, against this reference's after the same tokens, as a share of
# their length (Frobenius, a head), at the WORST of the 160 (layer, head)
# pairs: the heads that forget slowest carry a rounding longest. Float32
# states read 0.0130-0.0169 there over seven runs (0.0063 at the median
# head: the bfloat16 activations upstream of them); bfloat16 states read
# 0.0957 and 0.0973 (0.0094 at the median head, 0.0136 at the ninth
# decile: only the worst head tells them apart): the limit is near the
# geometric middle.
STATE_GAP_LIMIT = 0.035

Q_BLOCK = 128


def _f32(w):
    """A stored matrix as the float32 the reference multiplies by. The
    ONE place a precision control lowers (``benchmarks/
    control_falcon_h1.py ref_fp8``)."""
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


def sizes(model: Dict[str, Any]) -> Dict[str, int]:
    """The layer's sizes from the published keys."""
    h, p = int(model["mamba_n_heads"]), int(model["mamba_d_head"])
    g, n = int(model["mamba_n_groups"]), int(model["mamba_d_state"])
    if h * p != int(model["mamba_d_ssm"]):
        raise ValueError("mamba_d_ssm %s is not mamba_n_heads x mamba_d_head"
                         % model["mamba_d_ssm"])
    return {"H": h, "P": p, "G": g, "N": n, "d_ssm": h * p,
            "taps": int(model["mamba_d_conv"]),
            "n_head": int(model["num_attention_heads"]),
            "n_kv": int(model["num_key_value_heads"]),
            "d_head": int(model["head_dim"])}


def _frozen(model: Dict[str, Any]):
    """The numbers a jitted layer closes over, hashable."""
    z = sizes(model)
    keys = ("ssm_in_multiplier", "ssm_out_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier")
    return (tuple(sorted(z.items())),
            tuple(float(model[k]) for k in keys),
            tuple(float(m) for m in model["ssm_multipliers"]),
            tuple(float(m) for m in model["mlp_multipliers"]),
            float(model["rms_norm_eps"]), float(model["rope_theta"]))


def _ssm(lp, u, z, ssm_in, ssm_out, seg_mults, length):
    """The SSM branch's ``(m [S, d], S [H, N, P])`` of the normed input ``u``
    [S, d]: ``S`` is the state after the first ``length`` positions (the
    positions after them, a caller's padding, leave it as it is)."""
    f32 = jnp.float32
    s = u.shape[0]
    h, p, g, n, d_ssm = z["H"], z["P"], z["G"], z["N"], z["d_ssm"]
    widths = (d_ssm, d_ssm, g * n, g * n, h)
    mup = jnp.concatenate([jnp.full((w,), m, f32)
                           for w, m in zip(widths, seg_mults)])
    proj = ((u * ssm_in) @ _f32(lp["w_in"])) * mup
    gate = proj[:, :d_ssm]
    xbc = proj[:, d_ssm:2 * d_ssm + 2 * g * n]
    dt = jax.nn.softplus(proj[:, 2 * d_ssm + 2 * g * n:]
                         + lp["dt_bias"].astype(f32))        # [S, H]
    cw = lp["cw"].astype(f32)
    taps = cw.shape[0]
    up = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(cw[j] * up[j:j + s] for j in range(taps))
                       + lp["cb"].astype(f32))
    x = conv[:, :d_ssm].reshape(s, h, p)
    b = conv[:, d_ssm:d_ssm + g * n].reshape(s, g, n)
    c = conv[:, d_ssm + g * n:].reshape(s, g, n)
    a = jnp.exp(dt * -jnp.exp(lp["a_log"].astype(f32)))     # [S, H]
    per = h // g

    def step(carry, t):
        state, kept = carry
        xt, bt, ct, at, dtt, live = t
        bt, ct = (jnp.repeat(v, per, axis=0) for v in (bt, ct))   # [H, N]
        state = state * at[:, None, None] \
            + bt[:, :, None] * (dtt[:, None] * xt)[:, None, :]
        return (state, jnp.where(live, state, kept)), jnp.einsum(
            "hn,hnp->hp", ct, state)

    zero = jnp.zeros((h, n, p), f32)
    (_, kept), y = jax.lax.scan(step, (zero, zero), (
        x, b, c, a, dt, jnp.arange(s) < length))
    y = y + lp["dskip"].astype(f32)[:, None] * x
    y = (y.reshape(s, d_ssm) * jax.nn.silu(gate)).reshape(s, g, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + z["eps"])
    y = y.reshape(s, d_ssm) * lp["gn"].astype(f32)
    return (y @ _f32(lp["w_out"])) * ssm_out, kept


def _attention(q, k, v, scale):
    """``q`` [S, Hq, D], ``k``/``v`` [S, Hkv, D]; causal, grouped."""
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


def _attn(lp, u, pos, z, att_in, att_out, key_mult, theta):
    f32 = jnp.float32
    s = u.shape[0]
    ui = u * att_in
    q = (ui @ _f32(lp["wq"])).reshape(s, z["n_head"], z["d_head"])
    k = ((ui @ _f32(lp["wk"])) * key_mult).reshape(
        s, z["n_kv"], z["d_head"])
    v = (ui @ _f32(lp["wv"])).reshape(s, z["n_kv"], z["d_head"])
    o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v,
                   z["d_head"] ** -0.5)
    return (o.reshape(s, -1) @ _f32(lp["wo"])) * att_out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(lp, x, pos, frozen, leave_out, length):
    """One block; returns ``(x', (|m|, |a|, |mlp|, |h|) root mean squares,
    the SSM's state after ``length`` positions)``. ``leave_out`` names a
    branch a test leaves out."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        zs, mults, seg, mlp, eps, theta = frozen
        z = dict(zs, eps=eps)
        ssm_in, ssm_out, att_in, att_out, key_mult = mults
        u = _rms(x, lp["g1"].astype(f32), eps)
        m, state = _ssm(lp, u, z, ssm_in, ssm_out, seg, length)
        if leave_out == "ssm":
            m = 0.0
        a = 0.0 if leave_out == "attn" else _attn(
            lp, u, pos, z, att_in, att_out, key_mult, theta)
        x1 = x + m + a
        v2 = _rms(x1, lp["g2"].astype(f32), eps)
        wg, wu, wd = (_f32(lp[k]) for k in ("wg", "wu", "wd"))
        rows = Q_BLOCK
        while x.shape[0] % rows:
            rows //= 2
        # a block of rows at a time: [S, 21504] float32 three times over
        # is more than the chip has left beside the served model
        ff = jax.lax.map(
            lambda r: ((r @ wu) * jax.nn.silu((r @ wg) * mlp[0])) @ wd,
            v2.reshape(-1, rows, v2.shape[-1])).reshape(x.shape) * mlp[1]

        def rms(t):      # a branch left out is the scalar 0
            return jnp.sqrt(jnp.mean(jnp.square(jnp.broadcast_to(t, x.shape))))

        return x1 + ff, jnp.stack([rms(m), rms(a), rms(ff), rms(x)]), state


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(gf, head, x, eps, mult):
    with jax.default_matmul_precision("highest"):
        return (_rms(x, gf.astype(jnp.float32), eps)
                @ _f32(head)) * mult


HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gap_parts(gf, head, x, picked_ids, eps, mult):
    """``(max, the picked token's logit, deviation)`` [R] of the logits of
    rows ``x`` [R, d], the head applied a BLOCK of the vocabulary at a
    time: 261,120 x 5,120 in float32 is 5.3 GB, beside a served model that
    fills the chip."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        v = head.shape[1]
        blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
        size = v // blocks
        xn = _rms(x, gf.astype(f32), eps)

        def block(i, acc):
            best, picked, total, squares = acc
            w = jax.lax.dynamic_slice_in_dim(head, i * size, size, axis=1)
            lg = (xn @ _f32(w)) * mult                       # [R, size]
            local = picked_ids - i * size
            mine = (local >= 0) & (local < size)
            got = jnp.take_along_axis(
                lg, jnp.clip(local, 0, size - 1)[:, None], axis=1)[:, 0]
            return (jnp.maximum(best, lg.max(-1)),
                    jnp.where(mine, got, picked), total + lg.sum(-1),
                    squares + jnp.square(lg).sum(-1))

        r = x.shape[0]
        best, picked, total, squares = jax.lax.fori_loop(
            0, blocks, block, (jnp.full((r,), -jnp.inf, f32),
                               jnp.zeros((r,), f32), jnp.zeros((r,), f32),
                               jnp.zeros((r,), f32)))
        mean = total / v
        return best, picked, jnp.sqrt(jnp.maximum(squares / v - mean * mean,
                                                  0.0))


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens,
           leave_out: str = None, shares: List = None, states: List = None,
           length: int = None) -> jnp.ndarray:
    """``x`` [S, d] after the last layer of one sequence ``tokens`` [S].
    ``model`` gives the sizes and multipliers under the published config's
    own keys. ``shares``, a list, is given a layer's ``(|m|, |a|, |mlp|,
    |h|)``: what each branch adds beside the residual it is added to;
    ``states``, a list, a layer's SSM state ``[H, N, P]`` after the first
    ``length`` positions (default: all of them)."""
    frozen = _frozen(model)
    x = _f32(params["tok_emb"][tokens]) \
        * float(model["embedding_multiplier"])
    pos = jnp.arange(tokens.shape[0])
    length = jnp.asarray(tokens.shape[0] if length is None else length)
    for lp in params["layers"][:int(model["num_hidden_layers"])]:
        x, norms, state = _layer(lp, x, pos, frozen, leave_out, length)
        if shares is not None:
            shares.append(norms)
        if states is not None:
            states.append(state)
    return x


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None, leave_out: str = None, shares: List = None
            ) -> jnp.ndarray:
    """Logits of one sequence: every row [S, V], or the ``rows`` asked
    for."""
    x = hidden(params, model, jnp.asarray(tokens), leave_out, shares)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _logits(params["gf"], params["head"], x,
                   float(model["rms_norm_eps"]),
                   float(model["lm_head_multiplier"]))


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 256, shares: List = None
             ) -> np.ndarray:
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
    x = hidden(params, model, jnp.asarray(toks), shares=shares)[
        first:first + len(output)]
    best, picked, std = _gap_parts(
        params["gf"], params["head"], x, jnp.asarray(output, jnp.int32),
        float(model["rms_norm_eps"]), float(model["lm_head_multiplier"]))
    return np.asarray((best - picked) / std)


def final_states(params, model: Dict[str, Any], tokens: Sequence[int],
                 pad_to: int = 256) -> np.ndarray:
    """``[L, H, N, P]``: every layer's SSM state after ``tokens``, what a
    served slot KEEPS once it has consumed them. The sequence is padded to
    a multiple of ``pad_to`` (the blocks of :func:`hidden` want whole
    blocks of rows); the state is taken before the padding."""
    size = -(-len(tokens) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(tokens)] = tokens
    states = []
    hidden(params, model, jnp.asarray(toks), states=states,
           length=len(tokens))
    return np.stack([np.asarray(s) for s in states])


def state_gaps(served: np.ndarray, want: np.ndarray) -> np.ndarray:
    """``|served - want| / |want|`` of each head's state (Frobenius), a
    value a (layer, head), ascending: the last is the worst head's."""
    axes = (-2, -1)
    return np.sort((np.linalg.norm(served - want, axis=axes)
                    / np.linalg.norm(want, axis=axes)).reshape(-1))


"""Motif-3-Beta's forward pass, plain (the model's public ``config.json``,
``model_type: Motif``; its multi-token-prediction layer is a draft head and
is not here). Three things set it apart, and each is written out below as
the paper its config key names has it; where the config does not settle a
reading, the configuration file lists it under ``assumed``.

**The residual path** (``mhc_enabled``; manifold-constrained hyper-
connections, arXiv:2512.24880, n = ``mhc_expansion_rate`` = 4). The
embedding is copied into four streams, ``X`` in R^{4 x d}. Each HALF of a
layer (attention, then the MLP or the sparse block; each has maps of its
own) does, for every token,

    z      = vec(X) / sqrt(mean(vec(X)^2) + eps)        16,384 values, no gain
    a      = alpha_pre  (z Phi_pre)  + b_pre            R^4
    p      = alpha_post (z Phi_post) + b_post           R^4
    R      = alpha_res  mat(z Phi_res) + b_res          R^{4 x 4}
    H_pre  = sigmoid(a);  H_post = 2 sigmoid(p)
    H_res  = Sinkhorn(R): M = exp(R), then ``mhc_sinkhorn_iters`` = 20
             times rows, then columns, divided by their sums
    u      = H_pre X                                    R^d
    y      = clamp(F(RMSNorm(u; g)), +-hidden_clamp)    F the half's function
    X'     = H_res X + H_post^T y                       stream i gains H_post[i] y

and after the last layer ``logits = RMSNorm(sum_i X_i; gf) W_head``.

**GDLA attention** (``attention_cls: gdla``: Motif's grouped differential
attention, arXiv:2510.06949, in the subtraction-after-attention form of
``diff_v2``, over DeepSeek's latent KV), ``h`` the half's normed input at
position ``t``, 80 query heads over 16 KV heads:

    c_q = RMSNorm(h Wqa; gq)  [1024];  q = c_q Wqb      80 x (nope 128 | rope 64)
    [c | k_r] = h Wkva;  c = RMSNorm(c; gkv)  [512];  k_r [64], one for all heads
    q_r, k_r rotated (rotate-half): a window layer at ``swa_rope_theta``
        plainly, a full layer at YaRN's frequencies, no temperature factor
    K_g = c Wuk[g]  [128],  V_g = c Wuv[g]  [128]        KV head g of 16
    query head n reads KV head g = n // 5; of a group's five heads the
        first four are SIGNAL heads and the fifth its NOISE head
    o_n = softmax_j(192^-0.5 (q_nope_n . K_g(j) + q_r_n . k_r(j))) V_g(j)
        over every j <= t in a full layer, t - 127 <= j <= t in a window layer
    lambda_s = sigmoid(h w_lambda_s)                     a number a token a signal head
    y_s = o_s - lambda_s o_noise(g(s))                   64 heads; no norm after
    out = ([y_0 .. y_63] * sigmoid(h Wgate)) Wo          gate and heads 8,192 wide

**PolyNorm** (``hidden_act: poly_norm``, arXiv:2411.03884), the gate's
activation in every MLP, with numbers of its own for EVERY MLP and so for
every expert, reducing over the MLP's own width:

    n(t)   = t / sqrt(mean(t^2) + eps)
    act(v) = 0.5 (w_1 n(v) + w_2 n(v^2) + w_3 n(v^3) + clip(b, -0.5, 0.5))
    MLP(u) = (act(u Wg) * (u Wu)) Wd

A dense layer's MLP is 12,288 wide. A sparse layer: ``s = sigmoid(u Wr)``
[384], the 8 largest chosen (no selection bias), ``w_e = 2 s_e / sum of the
chosen``, ``sum_{e chosen, e held} w_e MLP_e(u) + MLP_shared(u)``.

Float32 throughout at ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no absorption (K and V of every KV head are made from
the latent, a full softmax with a mask for the window), Sinkhorn as
written, a plain loop over the held experts, each applied to EVERY row and
weighted by ``w`` (zero where not chosen). So that nine layers at ten
thousand positions fit a chip beside the served model, the weights are
cast to float32 a matrix at a time, what is a function of one token runs
in blocks of rows, attention in blocks of query rows, and the head is
applied to the rows asked for only.

A share: ``experts_held`` names the global ids of the experts in ``wg``/
``wu``/``wd``/``pn`` (default: all of them); an expert not held adds
nothing, as in the served layer (the chip that holds it adds its part);
the shared expert and the router are whole on every chip; the vocabulary
may be a slice. The parameter tree is the served one
(``models/motif3.py``): a layer has ``pa aa ba`` and ``pm am bm`` (the two
halves' ``Phi`` [4d, 24] = [pre 4 | post 4 | res 16], ``alpha`` [3] and
``b`` [24]), ``g1 g2 gq gkv wqa wqb wkva wkvb wlam wgate wo``, and ``wg wu
wd pn`` of a dense MLP or ``wr wg wu wd pn sg su sd spn`` of a sparse
block (``pn`` [.., 4] = ``w_1 w_2 w_3 b``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/decoder.py`` with two limits, as ``reference/kimi_k2.py`` has
# them and for its reason: a request's WORST row is a heavy-tailed reading
# (a near-tie among the 8 chosen of 384 experts that bf16 flips, where the
# flipped expert is one held here), and what tells a lower precision apart
# is the MEAN over a request's rows, which a rare flip barely moves. Each
# lies between two readings on the chip at the published widths (PERF.md,
# Findings, PR 43, has every reading). Served in bf16 with the residual
# maps in float32, 46 requests of 23 runs (contexts 2,730-9,270) read at
# most 0.408 at their worst row (0.23 at the median) and at most 0.00418
# at the mean (0.0023 on average; 0.00358 over the first 30, from which
# the limits were set). The same run with every latent row
# rounded to float8 e4m3 as it is written to either pool (pages and
# rings: the nearest precision below the stated one, in what this cache
# adds) reads 0.0161 and 0.0184, at another tree 0.0182 and 0.0159, at a
# third seed 0.0192 and 0.0212 and at a fourth 0.0097 and 0.0107, at the
# mean over a request's rows (0.36-0.62 at the worst): MEAN_GAP_LIMIT is
# the geometric middle of 0.00358 and 0.0159, the first readings; over all
# of them it stands 1.8 times above the largest sound reading and 1.3
# times under the lowest of the control's. This reference with EVERY
# matrix rounded to fp8 e4m3 reads 0.944 and 1.202 at the worst row (0.106
# and 0.109 at the mean): LOGIT_MARGIN lies between 0.408 and 0.944, ABOVE
# their geometric middle (0.61), with twice the served reading of room
# below it and 15% above it, because a worst row's tail is long (Kimi-K2's
# read 0.669 once in 196 requests with the same router and a median like
# this one's) and one run over it refuses a check: it is there to catch a
# row gone wrong, not to tell precisions apart: it has 2.3 times the
# served readings' largest below its upper reading, not three, and a
# float8 pool (0.36-0.62) does NOT fail it; only MEAN_GAP_LIMIT tells that
# control. Neither sees the residual maps, Sinkhorn's matrices and the
# heads' lambda computed in bfloat16 (0.0023 and 0.0013 at the mean, 0.48
# at the worst: inside the served readings): both rank tokens, and a 4 x 4
# doubly stochastic matrix rounded to 8 bits still has rows that sum to 1
# within 0.4%. What it does change is each stream's SCALE, the same for
# all 4,096 lanes of a token, where the served type's rounding is a lane's
# own: the norm of the streams' sum, which the lanes' own rounding moves
# in the second order, moves in the first. STREAM_NORM_LIMIT holds the
# MEDIAN over the first STREAM_ROWS positions of a compared request of
# | |sum of the streams, served| / |the same, here| - 1 |, the served side
# being the program's own prefill forward over those tokens (the median,
# because a routing flip moves a row's sum by several percent and 5 rows
# in 100 have one). On the chip at the published widths, 2,048 rows,
# seeds 7, 2147483747, 303: 0.00045, 0.00040, 0.00046 served as stated;
# 0.00131, 0.00125, 0.00127 with the maps and lambda at bfloat16's
# precision; in the cell's own runs 0.00035-0.00047 (18 requests of 9
# runs) and 0.00135, 0.00140 in the control's (PERF.md, Findings, PR 43):
# the limit is the geometric middle of 0.00046 and 0.00125, 1.6 times of
# room on either side. (The relative error of the sum, 2.2-2.4% at the
# mean, and the logits compared as values, the same, do NOT tell the two
# apart: both are the lanes' own rounding and the flips.)
LOGIT_MARGIN = 0.8
MEAN_GAP_LIMIT = 0.0075
STREAM_NORM_LIMIT = 0.00075
STREAM_ROWS = 2048

Q_BLOCK = 128
ROW_BLOCK = 1024
FULL, RING = "full", "window"


def yarn_inv_freq(rope_dim: int, theta: float,
                  scaling: Optional[Dict[str, Any]]) -> np.ndarray:
    """The ``rope_dim / 2`` rotary frequencies: ``theta^(-2i / rope_dim)``,
    and under YaRN (``scaling``) pair i keeps that below the correction
    range, runs at that over ``factor`` above it, and ramps linearly
    between (``reference/kimi_k2.py`` has the same lines)."""
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


def sinkhorn(r, iters: int):
    """``r`` [..., n, n] made doubly stochastic: ``exp``, then ``iters``
    times the rows and then the columns divided by their sums."""
    m = jnp.exp(r)
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def poly_norm(v, p, scale: float = 0.5, clamp: float = 0.5,
              eps: float = 1e-6):
    """``scale (p[0] n(v) + p[1] n(v^2) + p[2] n(v^3) + clip(p[3]))`` over
    the last axis of ``v``, in float32; ``p`` four values that broadcast
    against ``v``'s rows."""
    v = v.astype(jnp.float32)

    def n(t):
        return t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    return scale * (p[0] * n(v) + p[1] * n(v ** 2) + p[2] * n(v ** 3)
                    + jnp.clip(p[3], -clamp, clamp))


def mhc_maps(phi, alpha, bias, x, n: int, iters: int, eps: float):
    """The three maps of one half for tokens ``x`` [..., n, d] (float32):
    ``(H_pre [..., n], H_post [..., n], H_res [..., n, n])``."""
    lead = x.shape[:-2]
    z = x.reshape(lead + (-1,))
    z = z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)
    m = z @ phi
    a = alpha[0] * m[..., :n] + bias[:n]
    p = alpha[1] * m[..., n:2 * n] + bias[n:2 * n]
    r = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(lead + (n, n))
    return jax.nn.sigmoid(a), 2.0 * jax.nn.sigmoid(p), sinkhorn(r, iters)


def _by_rows(fn, *xs):
    """``fn`` of row blocks of the leading axis (a function of one token,
    so that the float32 temporaries are a block's)."""
    s = xs[0].shape[0]
    b = ROW_BLOCK
    while s % b:
        b //= 2
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape((s // b, b) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((s,) + o.shape[2:]), out)


def _mix_in(lp, which, x, g, n, iters, eps):
    """``u = RMSNorm(H_pre X; g)`` and what the half's end needs."""
    f32 = jnp.float32
    h_pre, h_post, h_res = mhc_maps(
        lp["p" + which].astype(f32), lp["a" + which].astype(f32),
        lp["b" + which].astype(f32), x, n, iters, eps)
    u = jnp.einsum("sn,snd->sd", h_pre, x)
    return _rms(u, g.astype(f32), eps), h_post, h_res


def _mix_out(x, y, h_post, h_res, clamp):
    y = jnp.clip(y, -clamp, clamp)
    return jnp.einsum("snm,smd->snd", h_res, x) \
        + h_post[..., None] * y[:, None, :]


def _attention(q, k, v, scale, window):
    """``q`` [S, Hkv, G, Dqk], ``k`` [S, Hkv, Dqk], ``v`` [S, Hkv, Dv];
    causal, and inside ``window`` positions where one is given."""
    s = q.shape[0]
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(b, qi):
        rows = b * bq + jnp.arange(bq)[:, None]
        ok = cols <= rows
        if window is not None:
            ok = ok & (rows - cols < window)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k) * scale
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(lambda a: block(*a), (
        jnp.arange(s // bq), q.reshape((s // bq, bq) + q.shape[1:])))
    return out.reshape((s,) + q.shape[1:-1] + (v.shape[-1],))


@functools.partial(jax.jit, static_argnums=(3,))
def _gdla(lp, x, pos, st):
    """The attention half over streams ``x`` [S, n, d]. ``st`` (static):
    ``(n_head, n_kv, nope, rope, d_v, window, inv_freq, n, iters, eps,
    clamp)``."""
    n_head, n_kv, nope, rope, d_v, window, inv_freq, n, iters, eps, clamp = st
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        g = n_head // n_kv
        rank = lp["gkv"].shape[0]
        h, h_post, h_res = _by_rows(
            lambda xb: _mix_in(lp, "a", xb, lp["g1"], n, iters, eps), x)
        cq = _rms(h @ lp["wqa"].astype(f32), lp["gq"].astype(f32), eps)
        q = (cq @ lp["wqb"].astype(f32)).reshape(s, n_kv, g, nope + rope)
        kva = h @ lp["wkva"].astype(f32)
        c = _rms(kva[:, :rank], lp["gkv"].astype(f32), eps)
        q_r = _rope(q[..., nope:], pos, inv_freq)
        kr = _rope(kva[:, rank:], pos, inv_freq)
        kv = (c @ lp["wkvb"].astype(f32)).reshape(s, n_kv, nope + d_v)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[:, None], (s, n_kv, rope))],
            axis=-1)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        o = _attention(q, k, kv[..., nope:], (nope + rope) ** -0.5, window)
        lam = jax.nn.sigmoid(h @ lp["wlam"].astype(f32)
                             ).reshape(s, n_kv, g - 1)
        y = o[:, :, :g - 1] - lam[..., None] * o[:, :, g - 1:]
        gate = jax.nn.sigmoid(h @ lp["wgate"].astype(f32))
        out = (y.reshape(s, -1) * gate) @ lp["wo"].astype(f32)
        return _by_rows(lambda *a: _mix_out(*a, clamp), x, out, h_post,
                        h_res)


def _mlp(u, wg, wu, wd, pn, act):
    f32 = jnp.float32
    return (act(u @ wg.astype(f32), pn.astype(f32)) * (u @ wu.astype(f32))
            ) @ wd.astype(f32)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(lp, x, st):
    n, iters, eps, clamp, act = st
    with jax.default_matmul_precision("highest"):
        def rows(xb):
            u, h_post, h_res = _mix_in(lp, "m", xb, lp["g2"], n, iters, eps)
            y = _mlp(u, lp["wg"], lp["wu"], lp["wd"], lp["pn"], act)
            return _mix_out(xb, y, h_post, h_res, clamp)

        return _by_rows(rows, x)


@functools.partial(jax.jit, static_argnums=(2,))
def _sparse(lp, x, st):
    n, iters, eps, clamp, act, top_k, scale, held = st
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32

        def rows(xb):
            u, h_post, h_res = _mix_in(lp, "m", xb, lp["g2"], n, iters, eps)
            s = jax.nn.sigmoid(u @ lp["wr"].astype(f32))
            top, idx = jax.lax.top_k(s, top_k)
            w_top = scale * top / jnp.sum(top, axis=-1, keepdims=True)
            # [N, E]: the weight of expert e in row n, zero where not chosen
            w = jnp.zeros_like(s).at[
                jnp.arange(s.shape[0])[:, None], idx].set(w_top)
            y = _mlp(u, lp["sg"], lp["su"], lp["sd"], lp["spn"], act)

            def expert(j, acc):
                ye = _mlp(u, lp["wg"][j], lp["wu"][j], lp["wd"][j],
                          lp["pn"][j], act)
                return acc + w[:, jnp.asarray(held)[j]][:, None] * ye

            y = jax.lax.fori_loop(0, len(held), expert, y)
            return _mix_out(xb, y, h_post, h_res, clamp)

        return _by_rows(rows, x)


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(gf, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(jnp.sum(x, axis=1), gf.astype(jnp.float32), eps) \
            @ head.astype(jnp.float32)


def layer_kinds(model: Dict[str, Any]) -> List[str]:
    """The attention kind of each layer HELD: ``layer_types`` where the
    configuration lists them (a cut keeps other layers than the first
    ones), else layer i full where ``(i + 1) % sliding_window_period == 0``
    and a window layer otherwise."""
    n = int(model["num_hidden_layers"])
    if "layer_types" in model:
        return list(model["layer_types"])[:n]
    period = int(model["sliding_window_period"])
    return [FULL if (i + 1) % period == 0 else RING for i in range(n)]


def rotary(model: Dict[str, Any]) -> Dict[str, tuple]:
    """The rotary frequencies by layer kind: a window layer plain at
    ``swa_rope_theta``, a full layer YaRN's over ``rope_theta`` (no
    temperature factor: ``apply_yarn_scaling`` false, ``mscale`` 1)."""
    rope = int(model["qk_rope_head_dim"])
    return {RING: tuple(float(f) for f in yarn_inv_freq(
                rope, float(model["swa_rope_theta"]), None)),
            FULL: tuple(float(f) for f in yarn_inv_freq(
                rope, float(model["rope_theta"]), model["rope_scaling"]))}


def activation(model: Dict[str, Any]):
    """PolyNorm at the configuration's output scale and bias clamp: one
    object a configuration, so that the jitted layers are traced once."""
    return _activation(float(model["polynorm_output_scale"]),
                       float(model["polynorm_bias_clamp"]))


@functools.lru_cache(maxsize=None)
def _activation(scale: float, clamp: float):
    return functools.partial(poly_norm, scale=scale, clamp=clamp)


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens
           ) -> jnp.ndarray:
    """The streams ``X`` [S, 4, d] after the last layer of one sequence
    ``tokens`` [S]. ``model`` gives the sizes under the published config's
    own keys."""
    eps = float(model["rms_norm_eps"])
    rope = int(model["qk_rope_head_dim"])
    n = int(model["mhc_expansion_rate"])
    iters = int(model["mhc_sinkhorn_iters"])
    clamp = float(model["hidden_clamp"])
    act = activation(model)
    freqs = rotary(model)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    pos = jnp.arange(tokens.shape[0])
    for lp, kind in zip(params["layers"], layer_kinds(model)):
        x = _gdla(lp, x, pos, (
            int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]),
            int(model["head_dim"]) - rope, rope, int(model["v_head_dim"]),
            int(model["sliding_window"]) if kind == RING else None,
            freqs[kind], n, iters, eps, clamp))
        if "wr" in lp:
            held = tuple(model.get("experts_held")
                         or range(lp["wg"].shape[0]))
            x = _sparse(lp, x, (n, iters, eps, clamp, act,
                                int(model["experts_top_k"]),
                                float(model["route_scale"]), held))
        else:
            x = _dense(lp, x, (n, iters, eps, clamp, act))
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


def teacher_forced(params, model: Dict[str, Any], prompt: Sequence[int],
                  output: List[int], pad_to: int = 256, sum_rows: int = 0):
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): ``(gaps, sums)``.
    ``gaps``: for each of the output's tokens, how far the served token
    ranks below the row's best logit, in row standard deviations (0 where
    it IS the best). ``sums`` [sum_rows, d]: the streams' sum before the
    final norm at the first ``sum_rows`` positions. The sequence is padded
    to a multiple of ``pad_to`` (causality keeps the padding out of every
    row that is read)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    x = hidden(params, model, jnp.asarray(toks))
    logits = _logits(params["gf"], params["head"],
                     x[first:first + len(output)],
                     float(model["rms_norm_eps"]))
    picked = jnp.take_along_axis(
        logits, jnp.asarray(output, jnp.int32)[:, None], axis=-1)[:, 0]
    gaps = np.asarray((logits.max(-1) - picked) / logits.std(-1))
    return gaps, np.asarray(jnp.sum(x[:sum_rows], axis=1))


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 256) -> np.ndarray:
    """The ``gaps`` of :func:`teacher_forced`."""
    return teacher_forced(params, model, prompt, output, pad_to)[0]


def worst_margin(params, model: Dict[str, Any], prompt: Sequence[int],
                 output: List[int], pad_to: int = 256) -> float:
    """The worst of :func:`row_gaps`."""
    return float(row_gaps(params, model, prompt, output, pad_to).max())


def stream_norm_gap(served, plain) -> float:
    """The median over rows of ``| |served| / |plain| - 1 |``, the two the
    streams' sums [rows, d] of the same tokens: what STREAM_NORM_LIMIT
    holds (above: a scale the residual maps put on a whole stream)."""
    served = np.asarray(served, np.float64)
    plain = np.asarray(plain, np.float64)
    ratio = np.linalg.norm(served, axis=-1) / np.linalg.norm(plain, axis=-1)
    return float(np.median(np.abs(ratio - 1.0)))

"""DeepSeek-V3.2's language model, plain (the model's public ``config.json``,
``model_type: deepseek_v32``; the multi-token-prediction layer is a draft
head and is not here). Where the config does not settle a reading the
configuration file lists it under ``assumed``.

**A layer's attention half**, ``h = RMSNorm(x; g1)`` the normed input of
row t, H = 128 heads:

    q_lat = RMSNorm(h Wqa; gq)  [1536];  q_i = (q_lat Wqb)_i   128 + 64 lanes
    [c_raw | k_r] = h Wkva  [512 + 64];  c = RMSNorm(c_raw; gkv)
    q_r,i = rot(q_i[128:]),  k_r = rot(k_r):  rotate-half over the 64 lanes
        at the row's position, frequencies under YaRN (factor 40, beta 32
        and 1, 4,096 original positions, theta 10,000)
    the row a cache keeps: [c | k_r]
    k_n,i = c Wuk_i,  v_i = c Wuv_i                    128 lanes each

    the lightning indexer (64 heads of 128, one key a row):
    qI_j = rot64(q_lat WIq)_j;   kI = rot64(LayerNorm(h WIk; gik, bik))
    w_j = (h WIw)_j 64^-1/2 128^-1/2
        rot64: the FIRST 64 lanes rotate-half at the row's position, the
        attention's own frequencies; the other 64 pass
    I(t, s) = sum_j w_t,j ReLU(qI_t,j . kI_s)            for s <= t
    S_t = the 2,048 rows s <= t of largest I(t, s) (``index_topk``; every
          row where t + 1 <= 2,048; no row is forced in; a tie to the
          lower row)

    score_i(t, s) = (q_n,i . k_n,i(s) + q_r,i . k_r(s)) x scale,  s in S_t
        scale = 192^-1/2 x (0.1 ln 40 + 1)^2 = 0.1352
    o_i = sum_{s in S_t} softmax_s(score_i) v_i(s);  x' = x + concat(o_i) Wo

**The feed-forward half**, ``u = RMSNorm(x'; g2)``: a dense SwiGLU of
18,432 in the leading layers; else ``s = sigmoid(u Wr)`` [256], the 256
experts in ``n_group`` = 8 runs of 32, a group's score the sum of its two
largest ``s + b``, the ``topk_group`` = 4 best groups stay, the 8 largest
``s + b`` among THEIR experts are chosen, ``w_e = 2.5 s_e / sum of the
chosen s``, and ``x'' = x' + sum_{e chosen, e held} w_e MLP_e(u) +
MLP_shared(u)``. No bias anywhere. ``logits = RMSNorm(x; gf) W_head``.

Float32 throughout at ``jax.default_matmul_precision("highest")``; no
cache, no kernel, no absorption, no batching: the index scores of every row
against every row before it, the choice row by row (``lax.top_k``), the
attention over ``S_t`` by a mask, a plain loop over the held experts. So
that five layers at ten thousand positions fit a chip beside the served
weights, the weights are cast a matrix at a time, attention runs in blocks
of query rows and groups of heads, the wide products in blocks of columns, and the head is
applied to the rows asked for only.

``experts_held`` names the global ids of the experts in ``wg``/``wu``/
``wd`` (an expert not held adds nothing: the chip that holds it adds its
part; :func:`routed` is that part alone, which ``tests/test_deepseek_v32
.py`` sums over the sixteen shares), the shared expert and the router are
whole on every chip, and the vocabulary may be a slice. The parameter tree
is the served one (``models/deepseek_v32.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .glm5_flash import relative_gap, selection_agreement  # noqa: F401
from .kimi_k2 import softmax_scale, yarn_inv_freq

# ``correct``'s limits. Every reading is from the chip at the published
# widths (my chip runs, PR 62; PERF.md, Findings, PR 62, has each run): the
# served path over ten runs of nine seeds (contexts 7.5k-9.7k; the model's
# ``score_std`` 0.02: the configuration file says why) and the controls of
# ``benchmarks/control_deepseek_v32.py``. A limit lies between the largest
# served reading and the control it is there to fail, at or near their
# geometric middle, and says which.
#
# RANKS (``reference/kimi_k2.py``'s two: a served token's rank below this
# reference's best logit, in row deviations, THIS reference choosing its
# own rows). What sets them here is not rounding but the CHOICE: bfloat16
# flips 5 or 6 of a row's 2,048 rows at the index's threshold (the overlap
# below), the first layer's attention over independent embedding rows is
# sharp enough that one flipped row in a few hundred is a head's largest
# weight, and the mean over a request's rows reads 0.04 where Kimi's block
# without a choice reads 0.004; with this reference FORCED to the served
# choice in that one layer it reads 0.003. So LOGIT_MARGIN and
# MEAN_GAP_LIMIT stand far out, for a READ gone wrong under whatever
# selection: LOGIT_MARGIN over a request's worst row (served 0.65-2.08
# over twenty requests; the newest 2,048 rows read in place of the best 7.1, every
# row read 6.5), MEAN_GAP_LIMIT over the mean (served 0.029-0.041;
# those two controls 3.4 and 3.3). FORCED_GAP_LIMIT holds the mean over
# the probed request's decode rows with the served selection forced on the
# first held layer, and is what tells a program apart: served
# 0.0019-0.0034; the plain top-8 of 256 in the group-limited router's
# place (``no_group_limit``) 0.0139 and 0.0162, which the unforced mean
# does not see (0.045 beside 0.04); latent rows at float8 0.0194-0.0235; the limit is the
# geometric middle of the served reading and ``no_group_limit``'s.
#
# THE SELECTION, values and not ranks, over the probed slot's decode steps
# in the first held layer: OVERLAP_LIMIT under the mean share of this
# reference's 2,048 rows that the served step chose too: served
# 0.99720-0.99739 (a mean over a thousand steps of 2,048 rows: it moves in
# the fifth digit from seed to seed); the index scores accumulated in
# bfloat16 (``bf16_scores``) 0.99551 and 0.99626, the newest 2,048 rows 0.292;
# the limit the geometric middle of the two distances from 1. MASS_LIMIT
# under the served choice's score mass over the reference's own: served
# 0.999996, the newest rows 0.763 (GLM's 0.9999: a wrong choice, not a
# precision).
#
# VALUES the cache KEEPS of the request resident in the probed slot at the
# run's end (``relative_gap``): ROW_GAP_LIMIT over the latent rows ``[c |
# k_r]``: served 0.00287 (bfloat16 rows of the first layer: the
# embedding's own products), rounded to float8 e4m3 (``fp8_rows``)
# 0.02674-0.02677; the geometric middle. KEY_GAP_LIMIT over the index keys: served
# 0.00261; no control of this PR lowers a key's precision (the published
# model's FP8 keys wait on files: PERF.md 7 (xx)); it stands at three
# times the served reading, for a key written to another row's place.
LOGIT_MARGIN = 3.2
MEAN_GAP_LIMIT = 0.36
FORCED_GAP_LIMIT = 0.0075
OVERLAP_LIMIT = 0.9965
MASS_LIMIT = 0.9999
ROW_GAP_LIMIT = 0.0088
KEY_GAP_LIMIT = 0.008

Q_BLOCK = 64
HEAD_GROUP = 32
ROW_BLOCK = 1024
F_BLOCK = 2048


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


def _rope_first(x, pos, inv_freq):
    """The first ``2 len(inv_freq)`` lanes of ``x`` rotated, the rest as
    they are."""
    rot = 2 * len(inv_freq)
    return jnp.concatenate([_rope(x[..., :rot], pos, inv_freq),
                            x[..., rot:]], axis=-1)


def choose(score, rows, topk: int):
    """``S_t`` for the query rows ``rows`` [Q] with index scores ``score``
    [Q, N] over the sequence's N rows: ``(chosen [Q, N] bool, masked
    scores)``. A row s <= t may be chosen, the ``topk`` of highest score
    are (``top_k``: a tie to the lower row), all where there are fewer."""
    n = score.shape[1]
    masked = jnp.where(jnp.arange(n)[None, :] <= rows[:, None], score,
                       -jnp.inf)
    vals, idx = jax.lax.top_k(masked, min(topk, n))
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], idx].set(vals > -jnp.inf)
    return chosen, masked


def _inputs(lp, x, pos, st):
    """What a layer's attention half makes of its input ``x`` [S, d]
    before the heads: the normed query latent [S, q_rank], the row a cache
    keeps ``[c | k_r]`` [S, rank + rope], the index queries [S, Hi, L],
    their weights [S, Hi] and the index keys [S, L]."""
    _, _, _, _, hi, li, _, _, eps, inv_freq = st
    f32 = jnp.float32
    s = x.shape[0]
    rank = lp["gkv"].shape[0]
    h = _rms(x, lp["g1"].astype(f32), eps)
    q_lat = _rms(h @ lp["wqa"].astype(f32), lp["gq"].astype(f32), eps)
    kva = h @ lp["wkva"].astype(f32)
    row = jnp.concatenate([_rms(kva[:, :rank], lp["gkv"].astype(f32), eps),
                           _rope(kva[:, rank:], pos, inv_freq)], axis=-1)
    q_idx = _rope_first((q_lat @ lp["wiq"].astype(f32)).reshape(s, hi, li),
                        pos, inv_freq)
    ki = h @ lp["wik"].astype(f32)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki / jnp.sqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + 1e-6)
    ki = _rope_first(ki * lp["gik"].astype(f32) + lp["bik"].astype(f32),
                     pos, inv_freq)
    w_idx = (h @ lp["wiw"].astype(f32)) * hi ** -0.5 * li ** -0.5
    return q_lat, row, q_idx, w_idx, ki


@functools.partial(jax.jit, static_argnums=(3,))
def _kept(lp, x, pos, st):
    """``(row [S, rank + rope], index keys [S, L])``: what a cache keeps of
    a layer's rows."""
    with jax.default_matmul_precision("highest"):
        _, row, _, _, ki = _inputs(lp, x, pos, st)
        return row, ki


@functools.partial(jax.jit, static_argnums=(6,))
def _attend(lp, x, pos, forced_on, forced_rows, probe_rows, st):
    """``x + attention`` of one layer over ``x`` [S, d], with ``(scores,
    chosen)`` [m, S] of the rows ``probe_rows`` [m]. Row t's choice is
    replaced by ``forced_rows[t]`` [S] bool where ``forced_on[t]``. The
    rows' sets ``S_t`` are made first, a block of query rows at a time,
    then the heads attend over them ``HEAD_GROUP`` heads at a time (the
    same sum: a head's output does not depend on another's)."""
    n_head, nope, rope, d_v, _, _, topk, scale, _, inv_freq = st
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        rank = lp["gkv"].shape[0]
        q_lat, row, q_idx, w_idx, ki = _inputs(lp, x, pos, st)
        c, k_r = row[:, :rank], row[:, rank:]
        bq = Q_BLOCK
        while s % bq:
            bq //= 2
        cols = jnp.arange(s)[None, :]

        def scores_of(qi, wi):
            return jnp.einsum("qh,qhn->qn", wi, jax.nn.relu(
                jnp.einsum("qhl,nl->qhn", qi, ki)))

        def split(t):
            return t.reshape((s // bq, bq) + t.shape[1:])

        def read_by(args):
            b, qib, wib, on, given = args
            rows = b * bq + jnp.arange(bq)
            chosen, _ = choose(scores_of(qib, wib), rows, topk)
            return jnp.where(on[:, None], given, chosen) \
                & (cols <= rows[:, None])

        ok = jax.lax.map(read_by, (jnp.arange(s // bq), split(q_idx),
                                   split(w_idx), split(forced_on),
                                   split(forced_rows)))       # [S/bq, bq, S]
        group = min(HEAD_GROUP, n_head)
        wq = lp["wqb"].astype(f32).reshape(-1, n_head, nope + rope)
        wkv = lp["wkvb"].astype(f32).reshape(rank, n_head, nope + d_v)

        def heads(g):
            wq_g = jax.lax.dynamic_slice_in_dim(wq, g * group, group, 1)
            wkv_g = jax.lax.dynamic_slice_in_dim(wkv, g * group, group, 1)
            q = jnp.einsum("sr,rhd->shd", q_lat, wq_g)
            q_n, q_r = q[..., :nope], _rope(q[..., nope:], pos, inv_freq)
            kv = jnp.einsum("sr,rhd->shd", c, wkv_g)
            k_n, v = kv[..., :nope], kv[..., nope:]

            def block(args):
                qn, qr, okb = args
                sc = (jnp.einsum("qhd,khd->hqk", qn, k_n)
                      + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
                p = jax.nn.softmax(jnp.where(okb[None], sc, -jnp.inf),
                                   axis=-1)
                return jnp.einsum("hqk,khd->qhd", p, v)

            return jax.lax.map(block, (split(q_n), split(q_r), ok)
                               ).reshape(s, group * d_v)

        o = jax.lax.map(heads, jnp.arange(n_head // group))   # [G, S, g dv]
        o = o.transpose(1, 0, 2).reshape(s, n_head * d_v)
        chosen, masked = choose(scores_of(q_idx[probe_rows],
                                          w_idx[probe_rows]),
                                probe_rows, topk)
        return x + o @ lp["wo"].astype(f32), masked, chosen


def _swiglu(u, wg, wu, wd):
    """``(silu(u Wg) * (u Wu)) Wd``, over ``F_BLOCK`` columns of the width
    at a time where it is that wide (the dense layer's 18,432)."""
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


def _by_rows(fn, x):
    s = x.shape[0]
    b = ROW_BLOCK
    while s % b:
        b //= 2
    out = jax.lax.map(fn, x.reshape((s // b, b) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(lp, x, eps):
    with jax.default_matmul_precision("highest"):
        return _by_rows(lambda xb: xb + _swiglu(
            _rms(xb, lp["g2"].astype(jnp.float32), eps), lp["wg"], lp["wu"],
            lp["wd"]), x)


def route(u, wr, br, top_k: int, scale: float, n_group: int,
          topk_group: int):
    """The group-limited router over normed rows ``u`` [N, d]: ``w`` [N, E]
    float32, the weight of expert e in row n, zero where not chosen."""
    f32 = jnp.float32
    n = u.shape[0]
    s = jax.nn.sigmoid(u @ wr.astype(f32))
    biased = s + br.astype(f32)
    e = s.shape[1]
    by_group = biased.reshape(n, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], keep].set(True)
    _, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(kept, e // n_group, axis=1), biased, -jnp.inf),
        top_k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    w_top = scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(n)[:, None], idx].set(w_top)


def _routed(lp, u, st):
    top_k, scale, n_group, topk_group, held = st
    w = route(u, lp["wr"], lp["br"], top_k, scale, n_group, topk_group)

    def expert(j, acc):
        y = _swiglu(u, lp["wg"][j], lp["wu"][j], lp["wd"][j])
        return acc + w[:, jnp.asarray(held)[j]][:, None] * y

    return jax.lax.fori_loop(0, len(held), expert, jnp.zeros_like(u))


@functools.partial(jax.jit, static_argnums=(2, 3))
def routed(lp, x, eps, st):
    """The routed experts' part alone of a sparse layer's second half over
    its input ``x`` [N, d], for the experts ``st`` names as held:
    ``sum_{e chosen, e held} w_e MLP_e(RMSNorm(x; g2))``."""
    with jax.default_matmul_precision("highest"):
        return _routed(lp, _rms(x, lp["g2"].astype(jnp.float32), eps), st)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sparse(lp, x, eps, st):
    with jax.default_matmul_precision("highest"):
        def rows(xb):
            u = _rms(xb, lp["g2"].astype(jnp.float32), eps)
            return xb + _swiglu(u, lp["sg"], lp["su"], lp["sd"]) \
                + _routed(lp, u, st)

        return _by_rows(rows, x)


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(gf, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gf.astype(jnp.float32), eps) @ head.astype(jnp.float32)


def _attention_static(model: Dict[str, Any]):
    rope = int(model["qk_rope_head_dim"])
    inv_freq = tuple(float(f) for f in yarn_inv_freq(
        rope, float(model["rope_theta"]), model.get("rope_scaling")))
    return (int(model["num_attention_heads"]),
            int(model["qk_nope_head_dim"]), rope, int(model["v_head_dim"]),
            int(model["index_n_heads"]), int(model["index_head_dim"]),
            int(model["index_topk"]), softmax_scale(model),
            float(model["rms_norm_eps"]), inv_freq)


def router_static(model: Dict[str, Any], lp):
    """``(top_k, scale, n_group, topk_group, held)`` of a sparse layer."""
    held = tuple(model.get("experts_held") or range(lp["wg"].shape[0]))
    return (int(model["num_experts_per_tok"]),
            float(model["routed_scaling_factor"]), int(model["n_group"]),
            int(model["topk_group"]), held)


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens,
           forced=None, probe_rows=None, kept_only: bool = False):
    """``(x [S, d] after the last layer, probe)`` of one sequence ``tokens``
    [S]. ``model`` gives the sizes under the published config's own keys.
    ``probe_rows`` [m]: ``probe`` is then the FIRST layer's ``(scores [m,
    S] (-inf where a row may not be chosen), chosen [m, S] bool)`` at those
    rows, else None. ``forced`` = ``(rows [m], picked [m, K] int, -1 where
    fewer)``: in that layer those rows read the rows given in place of
    their choice. ``kept_only``: stop at the first layer and return ``(row
    [S, rank + rope], index keys [S, L])``, what a cache keeps of it."""
    eps = float(model["rms_norm_eps"])
    st = _attention_static(model)
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    x = params["tok_emb"][tokens].astype(jnp.float32)
    pos = jnp.arange(s)
    on = np.zeros((s,), bool)
    given = np.zeros((s, s), bool)
    if forced is not None:
        rows, picked = np.asarray(forced[0]), np.asarray(forced[1])
        on[rows] = True
        r, c = np.nonzero(picked >= 0)
        given[rows[r], picked[r, c]] = True
    rows_probed = jnp.asarray(
        probe_rows if probe_rows is not None else [0], jnp.int32)
    probe = None
    off = jnp.zeros((s,), bool)
    for i, lp in enumerate(params["layers"]):
        if kept_only:
            return _kept(lp, x, pos, st), None
        x, scores, chosen = _attend(
            lp, x, pos, jnp.asarray(on) if i == 0 else off,
            jnp.asarray(given), rows_probed, st)
        if i == 0:
            probe = (scores, chosen)
        if "wr" in lp:
            x = _sparse(lp, x, eps, router_static(model, lp))
        else:
            x = _dense(lp, x, eps)
    return x, (probe if probe_rows is not None else None)


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None) -> jnp.ndarray:
    """Logits of one sequence: every row [S, V], or the ``rows`` asked
    for."""
    x, _ = hidden(params, model, tokens)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _logits(params["gf"], params["head"], x,
                   float(model["rms_norm_eps"]))


def _padded(prompt: Sequence[int], output: List[int], pad_to: int):
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    return toks


def _gaps(params, model, x, first: int, output: List[int]) -> np.ndarray:
    logits = _logits(params["gf"], params["head"],
                     x[first:first + len(output)],
                     float(model["rms_norm_eps"]))
    picked = jnp.take_along_axis(
        logits, jnp.asarray(output, jnp.int32)[:, None], axis=-1)[:, 0]
    return np.asarray((logits.max(-1) - picked) / logits.std(-1))


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 256) -> np.ndarray:
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): for each of the
    output's tokens, how far the served token ranks below the row's best
    logit, in row standard deviations (0 where it IS the best). The
    sequence is padded to a multiple of ``pad_to`` (a multiple of the
    block's rows; causality keeps the padding out of every row read)."""
    x, _ = hidden(params, model, _padded(prompt, output, pad_to))
    return _gaps(params, model, x, len(prompt) - 1, output)


def kept_rows(params, model: Dict[str, Any], tokens: Sequence[int],
              pad_to: int = 256):
    """``(row [n, rank + rope], index keys [n, L])`` the first layer keeps
    of the ``n`` tokens."""
    n = len(tokens)
    toks = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
    toks[:n] = tokens
    (row, keys), _ = hidden(params, model, toks, kept_only=True)
    return np.asarray(row[:n]), np.asarray(keys[:n])


def teacher_forced(params, model: Dict[str, Any], prompt: Sequence[int],
                   output: List[int], probe=None, pad_to: int = 256
                   ) -> Dict[str, Any]:
    """``{"gaps": row_gaps}`` and, with ``probe`` = ``(positions [m],
    picked [m, K])`` (the rows the served FIRST layer chose at decode
    positions of this request), ``"selection"``
    (``selection_agreement`` at those positions) and ``"forced_gaps"``:
    the gaps of a second forward in which those positions read the served
    choice."""
    toks = _padded(prompt, output, pad_to)
    first = len(prompt) - 1
    if probe is None:
        x, _ = hidden(params, model, toks)
        return {"gaps": _gaps(params, model, x, first, output)}
    rows, picked = np.asarray(probe[0]), np.asarray(probe[1])
    x, (scores, chosen) = hidden(params, model, toks, probe_rows=rows)
    out = {"gaps": _gaps(params, model, x, first, output),
           "selection": selection_agreement(scores, chosen, picked)}
    del x
    xf, _ = hidden(params, model, toks, forced=(rows, picked))
    out["forced_gaps"] = _gaps(params, model, xf, first, output)
    return out


def worst_margin(params, model: Dict[str, Any], prompt: Sequence[int],
                 output: List[int], pad_to: int = 256) -> float:
    """The worst of :func:`row_gaps`."""
    return float(row_gaps(params, model, prompt, output, pad_to).max())

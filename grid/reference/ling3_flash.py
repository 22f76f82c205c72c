"""Ling-3.0-flash-VL's language model, plain (the model's public
``config.json``; the vision tower and the multi-token-prediction head are
not in it and are not here). Layer ``i`` with input ``x`` [d] at position
``p`` has an attention half of one of two kinds and a feed-forward half of
one of two kinds; ``h = RMSNorm(x; g1)`` and ``u = RMSNorm(x; g2)`` (eps
1e-6) precede the halves, ``H`` = 32 heads.

A KDA layer (``layer_types[i] == "kda"``: every layer but each sixth; Kimi
Delta Attention, arXiv:2510.26692 section 3), ``dk = dv = 128``:

    [u_q | u_k | u_v] = h Wqkv                       3 x 4096
    c(t) = silu(sum_{j=0..3} w_j u_{t-3+j})          a depthwise causal
                                                      convolution, 4 taps a
                                                      channel, zeros before
                                                      the request's start
    q = L2norm_head(c_q) dk^-0.5,  k = L2norm_head(c_k),  v = c_v
    a = -5 sigmoid(exp(A_log_head) (h Wa + dt_bias))  log-decay, a channel
                                                      of every head, in
                                                      (-5, 0)
    beta = sigmoid(h Wb)                              a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   S in R^{dk x dv}, a
                                                      head, zero at the start
    x = x + (RMSNorm_head(o; gn) * sigmoid(h Wgam)_head) Wo

An MLA layer (``"mla"``): Kimi-K2's latent attention with no query latent
and a head-wise gate,

    q = h Wq                            H x (nope 128 | rope 64)
    ckv, kr = split(h Wkva, 512 | 64);  c = RMSNorm(ckv; gkv)
    q_r, kr' = RoPE(q_rope, kr; p)      theta 6e6, no scaling, ONE rotary
                                        key for all heads
    [k_nope_n | v_n] = c Wkvb, per head n
    score_n(i, j) = 192^-0.5 (q_nope_n(i) . k_nope_n(j) + q_r_n(i) . kr'(j))
    a_n = softmax_{j <= i}(score_n) v_n
    x = x + concat_n(sigmoid(h Wgam)_n a_n) Wo

A dense layer: ``x = x + (silu(u Wg) * (u Wu)) Wd``. A sparse layer:

    s = sigmoid(u Wr)                   512 scores
    a group's score: the sum of its two largest s + b   8 groups of 64
    the 4 best groups stay; T = the 8 largest s + b among their experts
    w_e = 2.5 s_e / (sum_{e in T} s_e + 1e-20)
    x = x + sum_{e in T, e held} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
          + (silu(u Wg_s) * (u Wu_s)) Wd_s

and ``logits = RMSNorm(x; gf) W_head``, the head not tied to the
embedding, no biases but the decay's. Float32 throughout at
``jax.default_matmul_precision("highest")``; the recurrence token by
token; no cache, no kernels, no chunks, no batching, no absorption; a
plain loop over the held experts, each applied to EVERY row and weighted
by ``w`` (zero where not chosen). So that seven layers at ten thousand
positions fit a chip beside the served model, the weights are cast to
float32 a matrix at a time, attention is computed in blocks of query
rows, and the head is applied to the rows asked for only.

Departures from the published description, each a reading the config does
not settle (the configuration file lists them under ``assumed``) or a
share:

* the safe gate's form (``kda_safe_gate``, ``kda_lower_bound`` -5) is the
  one above; a KDA layer has no rotary embedding; ``use_qk_norm`` is the
  per-head L2 norm of q and k (gains of 1); the output gate reads the same
  normed input as q; rotary pairing is rotate-half over the 64 rotary
  lanes;
* ``Wq``, ``Wk``, ``Wv`` are stored side by side as ``wqkv`` and the three
  convolutions as one ``cw`` [4, 3 x 4096] (a concatenation, not a change);
* ``experts_held`` names the global ids of the experts in ``wg``/``wu``/
  ``wd`` (default: all of them); an expert not held adds nothing, as in
  the served layer (the chip that holds it adds its part). The shared
  expert and the router are whole on every chip;
* the vocabulary may be a slice: ``tok_emb`` and ``head`` have the rows and
  columns they have.

The parameter tree is the served one (``models/ling3_flash.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/decoder.py`` with two limits, as ``reference/kimi_k2.py`` has
# them and for its reason: a request's WORST row is a heavy-tailed reading
# (a near-tie that bf16 flips among the 4 kept of 8 groups or the 8 chosen
# of 512 experts, where a flipped expert is one held here, moves a row's
# logits by a tenth of their spread), and what tells a lower precision
# apart is the MEAN over a request's rows, which a rare flip barely moves.
# Each lies between readings on the chip at the published widths (PERF.md,
# Findings, PR 41). Served in bf16 with the state in float32, 38 requests
# of 19 runs (contexts 1,604-9,719) read at most 0.709 at their worst row
# (0.36 at the median) and at most 0.0152 at the mean (0.0114 on average,
# deviation 0.0017). This reference with every matrix rounded to fp8 e4m3,
# the nearest precision below the stated one, reads 1.811 and 1.853 at the
# worst row and 0.312 and 0.329 at the mean: LOGIT_MARGIN is the geometric
# middle of 0.709 and 1.811, and fp8 fails both limits. The served run with
# the recurrent state kept in bfloat16 (rounded after the prefill's scan
# and after every decode step) reads 0.0225 and, at a second seed, 0.0241
# at the mean over its long request's rows (0.0125 and 0.0127 with the
# state in float32: the same seeds, the same request) and 0.589 and 0.656
# at the worst: MEAN_GAP_LIMIT lies between the served maximum and those,
# five deviations above the served average, and a state in bfloat16 fails
# it.
LOGIT_MARGIN = 1.1
MEAN_GAP_LIMIT = 0.02

Q_BLOCK = 128
KDA, MLA = "kda", "mla"


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, pos, inv_freq):
    """Rotate-half over the last axis of ``x`` [S, ..., rope]."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv_freq, jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def log_decay(z, a_log, lower_bound):
    """A step's log-decay ``lower_bound sigmoid(exp(A_log) z)`` from the
    gate's pre-activation ``z`` [..., H, dk] and ``a_log`` [H]: in
    (``lower_bound``, 0) whatever ``z`` is (the safe gate)."""
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * z)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _kda(lp, x, n_head, lower_bound, eps):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        h = _rms(x, lp["g1"].astype(f32), eps)
        u = h @ lp["wqkv"].astype(f32)                      # [S, 3C]
        cw = lp["cw"].astype(f32)                           # [taps, 3C]
        taps = cw.shape[0]
        up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        c = jax.nn.silu(sum(cw[j] * up[j:j + s] for j in range(taps)))
        q, k, v = (t.reshape(s, n_head, -1) for t in jnp.split(c, 3, axis=1))
        dk = q.shape[-1]
        q, k = _l2(q) * dk ** -0.5, _l2(k)
        z = (h @ lp["wa"].astype(f32) + lp["dt_bias"].astype(f32)
             ).reshape(s, n_head, dk)
        a = log_decay(z, lp["a_log"].astype(f32), lower_bound)
        beta = jax.nn.sigmoid(h @ lp["wb"].astype(f32))     # [S, H]

        def step(state, t):
            qt, kt, vt, at, bt = t
            state = state * jnp.exp(at)[..., None]
            ks = jnp.einsum("hk,hkv->hv", kt, state)
            state = state + kt[..., None] * (bt[:, None]
                                             * (vt - ks))[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", qt, state)

        _, o = jax.lax.scan(
            step, jnp.zeros((n_head, dk, v.shape[-1]), f32),
            (q, k, v, a, beta))
        o = _rms(o, lp["gn"].astype(f32).reshape(n_head, -1), eps)
        gate = jax.nn.sigmoid(h @ lp["wgam"].astype(f32))
        return x + (o * gate[..., None]).reshape(s, -1) @ lp["wo"].astype(f32)


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
    return out.reshape(s, h, -1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _mla(lp, x, pos, n_head, nope, rope, eps, inv_freq):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        s = x.shape[0]
        rank = lp["gkv"].shape[0]
        h = _rms(x, lp["g1"].astype(f32), eps)
        q = (h @ lp["wq"].astype(f32)).reshape(s, n_head, nope + rope)
        kva = h @ lp["wkva"].astype(f32)
        c = _rms(kva[:, :rank], lp["gkv"].astype(f32), eps)
        q_r = _rope(q[..., nope:], pos, inv_freq)
        kr = _rope(kva[:, rank:], pos, inv_freq)
        kv = (c @ lp["wkvb"].astype(f32)).reshape(s, n_head, -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[:, None], (s, n_head, rope))],
            axis=-1)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        a = _attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
        gate = jax.nn.sigmoid(h @ lp["wgam"].astype(f32))
        return x + (a * gate[..., None]).reshape(s, -1) @ lp["wo"].astype(f32)


def _swiglu(u, wg, wu, wd):
    f32 = jnp.float32
    return (jax.nn.silu(u @ wg.astype(f32)) * (u @ wu.astype(f32))) \
        @ wd.astype(f32)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(lp, x, eps):
    with jax.default_matmul_precision("highest"):
        u = _rms(x, lp["g2"].astype(jnp.float32), eps)
        return x + _swiglu(u, lp["wg"], lp["wu"], lp["wd"])


def route(s, bias, top_k: int, n_group: int, topk_group: int, scale: float):
    """The group-limited choice over scores ``s`` [N, E] (sigmoids) with
    the selection bias ``bias`` [E]: ``(idx [N, k], w [N, k])``."""
    n, e = s.shape
    biased = s + bias
    by_group = biased.reshape(n, n_group, e // n_group)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(score, topk_group)
    kept = jnp.zeros((n, n_group), bool).at[jnp.arange(n)[:, None],
                                            keep].set(True)
    biased = jnp.where(jnp.repeat(kept, e // n_group, axis=1), biased,
                       -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _sparse(lp, x, top_k, n_group, topk_group, routed_scale, eps, held):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        n = x.shape[0]
        u = _rms(x, lp["g2"].astype(f32), eps)
        s = jax.nn.sigmoid(u @ lp["wr"].astype(f32))
        idx, w_top = route(s, lp["br"].astype(f32), top_k, n_group,
                           topk_group, routed_scale)
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


def layer_kinds(model: Dict[str, Any]) -> List[str]:
    """The attention kind of each layer HELD: ``layer_types`` where the
    configuration lists them (a cut keeps other layers than the first
    ones), else every ``layer_group_size``-th layer latent and the others
    KDA."""
    n = int(model["num_hidden_layers"])
    if "layer_types" in model:
        return list(model["layer_types"])[:n]
    period = int(model["layer_group_size"])
    return [MLA if (i + 1) % period == 0 else KDA for i in range(n)]


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens
           ) -> jnp.ndarray:
    """``x`` [S, d] after the last layer of one sequence ``tokens`` [S].
    ``model`` gives the sizes under the published config's own keys."""
    eps = float(model["rms_norm_eps"])
    rope = int(model["qk_rope_head_dim"])
    n_head = int(model["num_attention_heads"])
    inv_freq = tuple(float(f) for f in float(model["rope_theta"]) ** (
        -np.arange(rope // 2, dtype=np.float64) * 2.0 / rope))
    x = params["tok_emb"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for lp, kind in zip(params["layers"], layer_kinds(model)):
        if kind == KDA:
            x = _kda(lp, x, n_head, float(model["kda_lower_bound"]), eps)
        else:
            x = _mla(lp, x, pos, n_head, int(model["qk_nope_head_dim"]),
                     rope, eps, inv_freq)
        if "wr" in lp:
            held = tuple(model.get("experts_held")
                         or range(lp["wg"].shape[0]))
            x = _sparse(lp, x, int(model["num_experts_per_tok"]),
                        int(model["n_group"]), int(model["topk_group"]),
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

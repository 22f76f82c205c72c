"""What the served decoders share: the blocks more than one of them is made
of, the host-side tables their configs are built from, and the ONE class
through which ``serving.ServingEngine`` drives any of them
(:class:`ServedLM`, whose docstring is the contract).

``smallthinker.py``, ``kimi_k2.py``, ``laguna.py``, ``ling3_flash.py``,
``motif3.py``, ``glm5_flash.py``, ``falcon_h1.py``, ``ouro.py``,
``evabyte.py``, ``deepseek_v32.py`` and ``nemotron3.py`` take their blocks
from here and keep what only they have. A
block two models need is written HERE under a public name; no model module
imports another's underscore names. Two forms of a block are one function
only where the merged one needs no argument that says who calls it and the
models' executables come out as they were: the rotary block stays two
(:func:`rope` over a whole last axis, :func:`rope_lanes` over its first
lanes with an attention factor). ``decoder_lm.py`` (GPT-2: LayerNorm,
learned positions) keeps its own blocks.

The plain float32 statement each model is compared with is the benchmark's
(``grid/reference/<model>.py``). Nothing here or in a model module imports
it: the tables below (:func:`yarn_inv_freq`, :func:`mla_softmax_scale`,
:func:`rope_table`, :func:`l2_normalize`, :func:`log_decay`) are the
program's own text, and ``tests/test_model_blocks.py`` holds each against
the reference's at the published configurations' values.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile_cache as _cc
from ..ops import moe_ops
from ..ops.pallas_kernels import kda as kda_ops

__all__ = ["ServedLM", "absorbed_output", "absorbed_query", "at_precision",
           "causal_conv_prefill", "causal_conv_step", "gated",
           "gated_group_norm", "head", "held_experts", "kda_inputs",
           "kda_output", "kda_prefill",
           "l2_normalize", "latent", "log_decay", "maps_precision", "mix_in",
           "mix_out", "mla_softmax_scale", "moe_stats", "rms_norm", "rope",
           "rope_lanes", "rope_table", "routed_feed_forward", "seeded_params",
           "swiglu", "yarn_inv_freq"]


# -- host-side tables a config is built from ----------------------------------

def yarn_inv_freq(rot: int, theta: float,
                  scaling: Optional[Dict[str, Any]] = None) -> np.ndarray:
    """The ``rot / 2`` rotary frequencies of ``rot`` lanes at base
    ``theta``, float64. Plain (``scaling`` empty): pair i runs at
    ``theta^(-2i / rot)``. Under YaRN (``factor``, ``beta_fast``,
    ``beta_slow``, ``original_max_position_embeddings``): a pair that turns
    more than ``beta_fast`` times over the original context keeps that
    frequency, one that turns fewer than ``beta_slow`` times runs at it
    over ``factor``, and the pairs between ramp linearly from one to the
    other."""
    pair = np.arange(rot // 2, dtype=np.float64)
    freq = theta ** (-pair * 2.0 / rot)
    if not scaling:
        return freq
    span = float(scaling["original_max_position_embeddings"])

    def pair_turning(turns: float) -> float:
        return (rot * math.log(span / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(pair_turning(float(scaling["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((pair - low) / (high - low), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / float(scaling["factor"]) * ramp


def mla_softmax_scale(d_head: int,
                      scaling: Optional[Dict[str, Any]] = None) -> float:
    """A latent-attention layer's score scale: ``d_head^-0.5`` over a
    query's ``nope + rope`` lanes, times YaRN's ``mscale^2`` where the
    scaling names ``mscale_all_dim``."""
    scale = d_head ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = (0.1 * float(scaling["mscale_all_dim"])
             * math.log(scaling["factor"]) + 1.0)
        scale *= m * m
    return scale


def rope_table(d_head: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """``(inv_freq, attention factor)`` of one published ``rope_parameters``
    entry, what :func:`rope_lanes` takes: the frequencies of the first
    ``d_head * partial_rotary_factor`` lanes (``rope_type`` ``default``:
    plain, factor 1; ``yarn``: :func:`yarn_inv_freq`'s, and cos and sin
    times ``attention_factor``, by default ``0.1 ln(factor) + 1``)."""
    rot = int(round(d_head * float(rope.get("partial_rotary_factor", 1.0))))
    theta = float(rope["rope_theta"])
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return yarn_inv_freq(rot, theta), 1.0
    if kind != "yarn":
        raise ValueError("rope_type %r" % kind)
    att = rope.get("attention_factor")
    return (yarn_inv_freq(rot, theta, rope),
            float(att) if att is not None
            else 0.1 * math.log(float(rope["factor"])) + 1.0)


# -- blocks -------------------------------------------------------------------

def rms_norm(x, g, eps):
    """RMSNorm over the last axis, computed in float32, in ``x``'s type."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def l2_normalize(x):
    """``x`` over its last axis' length (1e-6 under the root)."""
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def log_decay(z, a_log, lower_bound):
    """A recurrence step's log-decay ``lower_bound sigmoid(exp(A_log) z)``
    from the gate's pre-activation ``z`` [..., H, dk] and ``a_log`` [H]:
    inside (``lower_bound``, 0) whatever ``z`` is."""
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * z)


def rope(x, pos, inv_freq):
    """Rotate-half over the WHOLE last axis of ``x`` [..., rope] at
    positions ``pos`` (the leading axes of ``x``; any axes between them and
    the last turn alike) and frequencies ``inv_freq`` [rope / 2]."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32).reshape(
        pos.shape + (1,) * (x.ndim - pos.ndim)) \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_lanes(x, pos, table):
    """Rotate-half over the FIRST ``2 * len(inv_freq)`` lanes of ``x`` [...,
    H, D] at positions ``pos`` [...] (one a row of heads), cos and sin times
    the attention factor; the other lanes pass. ``table`` is
    :func:`rope_table`'s ``(inv_freq, factor)``."""
    inv_freq, factor = table
    half = len(inv_freq)
    ang = pos.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., 2 * half:]], axis=-1)


def swiglu(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def head(params, cfg, x):
    """The final RMSNorm and the output head (not tied to the embedding)."""
    return rms_norm(x, params["gf"], cfg.rms_eps) @ params["head"]


def gated(lp, h, o):
    """``gamma_n a_n``: attention's output ``o`` [..., H, D] under the
    head-wise gate ``sigmoid(h Wgamma)`` of the same normed input ``h``
    [..., d], in float32, flattened to [..., H * D] for the output
    projection."""
    with jax.named_scope("attn/gate"):
        gamma = jax.nn.sigmoid(jnp.dot(h, lp["wgam"],
                                       preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gamma[..., None]).astype(o.dtype)
        return o.reshape(o.shape[:-2] + (-1,))


def latent(cfg, lp, h, pos):
    """What latent attention reads of the normed input ``h`` [..., d] at
    ``pos`` [...]: the queries ``(q_nope, q_rope)`` [..., H, nope | rope],
    rotated, and the cache row ``[c | kr']`` [..., rank + rope]. A layer
    without a query latent (``q_lora_rank`` null) projects ``h`` by ``wq``.
    ``cfg`` gives ``n_head``, ``d_head``, ``d_nope``, ``kv_rank``,
    ``rms_eps`` and ``inv_freq``."""
    if "wq" in lp:
        q = h @ lp["wq"]
    else:
        q = rms_norm(h @ lp["wqa"], lp["gq"], cfg.rms_eps) @ lp["wqb"]
    q = q.reshape(h.shape[:-1] + (cfg.n_head, cfg.d_head))
    kva = h @ lp["wkva"]
    c = rms_norm(kva[..., :cfg.kv_rank], lp["gkv"], cfg.rms_eps)
    kr = rope(kva[..., cfg.kv_rank:], pos, cfg.inv_freq)
    q_r = rope(q[..., cfg.d_nope:], pos, cfg.inv_freq)
    return q[..., :cfg.d_nope], q_r, jnp.concatenate([c, kr], axis=-1)


def absorbed_query(cfg, wkvb, q_n, q_r):
    """``[q_nope_n Wuk_n^T | q_rope_n]`` [B, H, rank + rope]: the query of
    head n over the cache row's lanes, every head with an up-projection of
    its own."""
    w = wkvb.reshape(cfg.kv_rank, cfg.n_head, cfg.d_nope + cfg.d_v)
    q_lat = jnp.einsum("bhn,chn->bhc", q_n, w[..., :cfg.d_nope],
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_n.dtype), q_r], axis=-1)


def absorbed_output(cfg, wkvb, o_lat):
    """``o_lat_n Wuv_n`` [B, H * d_v] of ``o_lat`` [B, H, rank]."""
    w = wkvb.reshape(cfg.kv_rank, cfg.n_head, cfg.d_nope + cfg.d_v)
    a = jnp.einsum("bhc,chv->bhv", o_lat, w[..., cfg.d_nope:],
                   preferred_element_type=jnp.float32)
    return a.astype(o_lat.dtype).reshape(o_lat.shape[0], -1)


# -- a KDA layer's half (Ling-3.0-flash, GLM-5.3-Flash) ------------------------

def kda_inputs(cfg, lp, h, taps):
    """What a KDA layer's recurrence reads at each position (``cfg`` gives
    ``n_head``, ``d_state``, ``lower_bound``): ``h`` [..., d] the
    normed input, ``taps`` the convolution's inputs there, oldest first,
    each [..., 3C]. Returns ``(q, k [..., H, dk], v [..., H, dv], a [...,
    H, dk] float32, beta [..., H] float32)``."""
    f32 = jnp.float32
    lead = h.shape[:-1]
    cw = lp["cw"].astype(f32)
    c = jax.nn.silu(sum(x.astype(f32) * cw[j] for j, x in enumerate(taps)))
    q, k, v = (t.reshape(lead + (cfg.n_head, cfg.d_state))
               for t in jnp.split(c, 3, axis=-1))
    q = l2_normalize(q) * cfg.d_state ** -0.5
    # the decay's projection whole (``wa``) or through a low rank
    z = jnp.dot(h, lp["wa"], preferred_element_type=f32) if "wa" in lp \
        else jnp.dot(h @ lp["wa1"], lp["wa2"], preferred_element_type=f32)
    z = (z + lp["dt_bias"]).reshape(lead + (cfg.n_head, cfg.d_state))
    a = log_decay(z, lp["a_log"], cfg.lower_bound)
    beta = jax.nn.sigmoid(jnp.dot(h, lp["wb"], preferred_element_type=f32))
    return (q.astype(h.dtype), l2_normalize(k).astype(h.dtype),
            v.astype(h.dtype), a, beta)


def kda_output(cfg, lp, h, o):
    """``(RMSNorm_head(o; gn) * gate_head) Wo`` of ``o`` [..., H, dv]
    float32."""
    gn = lp["gn"].reshape(cfg.n_head, cfg.d_state)
    return gated(lp, h, rms_norm(o, gn, cfg.rms_eps).astype(h.dtype)
                 ) @ lp["wo"]


def kda_prefill(cfg, lp, h, length):
    """One sequence's KDA half: ``h`` [S, d] normed, ``length`` its valid
    rows. Returns ``(y [S, d], state [H, dk, dv] float32, tail [taps - 1,
    3C])``: the state and the convolution inputs the first ``length``
    tokens leave."""
    s = h.shape[0]
    rows = cfg.conv_taps - 1
    u = h @ lp["wqkv"]
    up = jnp.pad(u, ((rows, 0), (0, 0)))
    q, k, v, a, beta = kda_inputs(
        cfg, lp, h, [up[j:j + s] for j in range(cfg.conv_taps)])
    valid = jnp.arange(s) < length
    a = jnp.where(valid[:, None, None], a, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    o, state = kda_ops.kda_chunk_scan(q, k, v, a, beta)
    tail = jax.lax.dynamic_slice_in_dim(up, length, rows, axis=0)
    return kda_output(cfg, lp, h, o), state, tail


# -- a state-space mixer's ends (Falcon-H1's Mamba-2 branch) -------------------

def causal_conv_prefill(u, cw, cb, length):
    """A depth-wise causal convolution over ONE sequence, with the tail it
    leaves: ``u`` [S, C] the inputs (zeros stand before the request's
    start), ``cw`` [taps, C] the taps oldest first, ``cb`` [C] the bias or
    None, ``length`` the valid rows. Returns ``(silu(conv) [S, C] float32,
    tail [taps - 1, C])``: the tail is the last ``taps - 1`` inputs before
    position ``length``, what :func:`causal_conv_step` reads next."""
    s, rows = u.shape[0], cw.shape[0] - 1
    up = jnp.pad(u, ((rows, 0), (0, 0)))
    out = causal_conv_step(
        jnp.stack([up[j:j + s] for j in range(rows + 1)], axis=1), cw, cb)
    return out, jax.lax.dynamic_slice_in_dim(up, length, rows, axis=0)


def causal_conv_step(window, cw, cb):
    """``silu(sum_j cw_j window[:, j] + cb)`` float32 of ``window`` [B,
    taps, C], each row's last ``taps`` inputs oldest first (the cache's
    ``tail_step`` hands a decode step that)."""
    f32 = jnp.float32
    y = jnp.sum(window.astype(f32) * cw.astype(f32), axis=1)
    return jax.nn.silu(y if cb is None else y + cb.astype(f32))


def gated_group_norm(y, z, g, groups: int, eps):
    """``RMSNorm_group(y * silu(z); g)``: the gate FIRST, then an RMS norm
    over each of ``groups`` equal runs of the last axis (Mamba-2's gated
    norm with ``norm_before_gate`` false). ``y``, ``z`` [..., C]; float32
    inside, ``z``'s type out."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    vg = v.reshape(v.shape[:-1] + (groups, -1))
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return (vg.reshape(v.shape) * g.astype(f32)).astype(z.dtype)


# -- four residual streams (Motif-3, GLM-5.3-Flash) ---------------------------

def at_precision(x, dtype):
    """``x`` at ``dtype``'s precision, in its own type (itself where they
    are one): ``reduce_precision``, because the chip's compiler elides a
    pair of converts. What a precision CONTROL lowers (a configuration's
    ``maps_dtype``, ``row_dtype``, ``index_dtype``); the configurations
    as stated pass through."""
    if jnp.dtype(dtype) == x.dtype:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def maps_precision(cfg, x):
    """``x`` (float32) at ``cfg.maps_dtype``'s precision."""
    return at_precision(x, cfg.maps_dtype)


def mix_in(cfg, lp, which: str, x, g):
    """A half's input from the streams ``x`` [n, ..., d]: ``(RMSNorm(H_pre
    X; g) [..., d], H_post [..., n], H_res [..., n, n])``, the maps in
    float32 whatever the streams' type. The streams lie stream-major, so
    that each is whole lane tiles of its own (four rows of a ``[.., 4,
    d]`` array fill a quarter of a bfloat16 tile's sixteen) and ``z Phi``
    is the sum of the streams' own products: no ``[.., 4 d]`` row is
    built."""
    with jax.named_scope("residual/mhc"):
        f32 = jnp.float32
        n, d = cfg.n_stream, x.shape[-1]
        xf = [x[m].astype(f32) for m in range(n)]
        inv = jax.lax.rsqrt(
            sum(jnp.sum(t * t, axis=-1, keepdims=True) for t in xf)
            / (n * d) + cfg.rms_eps)
        phi = lp["p" + which].astype(f32).reshape(n, d, -1)
        m = maps_precision(cfg, sum(
            jnp.dot(maps_precision(cfg, t * inv), phi[j],
                    precision=jax.lax.Precision.HIGHEST)
            for j, t in enumerate(xf)))
        alpha, bias = lp["a" + which], lp["b" + which]
        h_pre = maps_precision(cfg, jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n]))
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n]
                                      + bias[n:2 * n])
        r = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(
            m.shape[:-1] + (n, n))
        mat = maps_precision(cfg, jnp.exp(maps_precision(cfg, r)))
        def total(t, axis):
            # ``hc_eps`` in the divisions where the configuration has one
            t = jnp.sum(t, axis=axis, keepdims=True)
            return t + cfg.sinkhorn_eps if cfg.sinkhorn_eps else t

        for _ in range(cfg.sinkhorn_iters):
            mat = maps_precision(cfg, mat / total(mat, -1))
            mat = maps_precision(cfg, mat / total(mat, -2))
        u = sum(h_pre[..., j, None] * t for j, t in enumerate(xf))
        return rms_norm(u.astype(x.dtype), g, cfg.rms_eps), h_post, mat


def mix_out(cfg, x, y, h_post, h_res):
    """``H_res X + H_post^T y`` [n, ..., d] in float32, each stream
    rounded once to the streams' type; ``y`` [..., d] clamped where the
    configuration publishes a clamp (``cfg.hidden_clamp``)."""
    with jax.named_scope("residual/mhc"):
        f32 = jnp.float32
        n = cfg.n_stream
        y = y.astype(f32)
        if cfg.hidden_clamp is not None:
            y = jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp)
        xf = [x[m].astype(f32) for m in range(n)]
        h_post = maps_precision(cfg, h_post)
        return jnp.stack([
            (sum(h_res[..., i, j, None] * xf[j] for j in range(n))
             + h_post[..., i, None] * y).astype(x.dtype)
            for i in range(n)])


def held_experts(cfg):
    """``ops.moe_ops.expert_layer``'s ``held``: None where every expert is
    here, else the global ids of the share in ``wg``/``wu``/``wd``."""
    return (None if len(cfg.experts_held) == cfg.n_expert
            else cfg.experts_held)


def routed_feed_forward(cfg, lp, x, row_valid, count_groups: bool = False):
    """The DeepSeek-V3 layer's second half over rows ``x`` [N, d]: the
    dense SwiGLU, or the sigmoid-routed SwiGLU experts held here (the
    router group-limited where ``cfg.n_group`` > 1) plus the shared
    expert. Returns ``(x, stats or None)``. ``count_groups`` (a
    group-limited router's share): ``stats`` also counts
    ``groups_kept_with_held``, the valid rows of which a kept group holds
    an expert held here."""
    u = rms_norm(x, lp["g2"], cfg.rms_eps)
    if "wr" not in lp:
        return x + swiglu(u, lp["wg"], lp["wu"], lp["wd"]), None
    limited = ({} if cfg.n_group == 1 else
               {"n_group": cfg.n_group, "topk_group": cfg.topk_group})
    if count_groups:
        limited["with_groups"] = True
    idx, w, *kept = moe_ops.route_sigmoid_topk(
        u, lp["wr"], lp["br"], cfg.top_k, cfg.routed_scale, **limited)
    y, stats = moe_ops.expert_layer(
        u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=held_experts(cfg), row_valid=row_valid, activation=jax.nn.silu)
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    if count_groups:
        # the valid rows of which a kept group holds an expert held here:
        # only those can send this share a pair
        ours = np.zeros((cfg.n_group,), bool)
        ours[[e * cfg.n_group // cfg.n_expert for e in cfg.experts_held]] \
            = True
        with_held = jnp.any(kept[0] & jnp.asarray(ours), axis=-1)
        if row_valid is not None:
            with_held = with_held & row_valid
        stats["groups_kept_with_held"] = jnp.sum(with_held).astype(jnp.int32)
    with jax.named_scope("moe/shared"):
        shared = swiglu(u, lp["sg"], lp["su"], lp["sd"])
    return x + (y + shared.astype(jnp.float32)).astype(x.dtype), stats


def moe_stats(stats) -> Dict:
    """A decode step's expert counts from its EXPERT layers'
    ``expert_layer`` stats (with ``held_pairs``), stacked a layer: what
    the engine feeds the ``serving/moe_*`` histograms."""
    return {"moe_experts_touched": jnp.stack(
                [s["experts_touched"] for s in stats]),
            "moe_max_expert_rows": jnp.stack(
                [s["max_expert_rows"] for s in stats]),
            "moe_held_pairs": jnp.stack([s["held_pairs"] for s in stats])}


@_cc.in_phase("startup/weights")
def seeded_params(cfg, seed, init_layer: Callable, layer_args: Callable
                  ) -> Dict:
    """Seeded random weights, made where JAX computes (the device), in
    ``cfg.dtype``, one layer a call: the largest temporary is one layer.
    ``init_layer(cfg, key, *layer_args(i))`` makes layer i; its arguments
    after the key are static (one compile a kind of layer)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    n_static = len(layer_args(0))
    layer = jax.jit(lambda k, *static: init_layer(cfg, k, *static),
                    static_argnums=tuple(range(1, 1 + n_static)))
    emb = jax.jit(lambda k, shape: 0.02 * jax.random.normal(
        k, shape, cfg.dtype), static_argnums=1)
    return {"tok_emb": emb(keys[0], (cfg.vocab_size, cfg.d_model)),
            "head": emb(keys[1], (cfg.d_model, cfg.vocab_size)),
            "gf": jnp.ones((cfg.d_model,), cfg.dtype),
            "layers": [layer(keys[2 + i], *layer_args(i))
                       for i in range(cfg.n_layer)]}


# -- the contract -------------------------------------------------------------

class ServedLM:
    """THE SERVING CONTRACT: what ``serving.ServingEngine`` may ask of a
    model and of its config. ``SmallThinkerLM``, ``KimiK2LM``, ``LagunaLM``,
    ``Ling3FlashLM``, ``Motif3LM``, ``Glm5FlashLM``, ``FalconH1LM``,
    ``OuroLM``, ``EvaByteLM``, ``DeepSeekV32LM`` and ``Nemotron3LM`` are
    this class over
    their module's
    ``init_params``, ``prefill_forward`` and ``decode_forward`` (and, where
    the head is not the plain one, ``head``);
    ``decoder_lm.DecoderLM`` meets it with methods of its own.

    The engine reads ``model.cfg``, ``model.params`` and calls:

    * ``prefill(params, tokens [B, S], lengths [B]) -> (logits [B, S, V],
      kept)``: causal over bucket-padded prompts. ``kept`` is, a layer,
      what the cache's ``write_prompt`` takes with a leading batch axis:
      ``(k, v)`` [B, S, Hkv, D] of a K-and-V layer, ``(row,)`` [B, S, rank +
      rope] of a latent layer, ``(state [B, H, dk, dv], tail [B, rows,
      width])`` of a state layer (the state the prompt LEAVES, not rows);
      under ``cache_steps`` T > 1, ``(k, v)`` [T, B, S, Hkv, D]: a leading
      STEP axis, the rows each loop step made, which the engine writes a
      step at a time (``write_prompt(..., step=t)``); of a layer in a
      COMPACTING group (``serving/kv_cache.py``: a window's rows are
      replaced by one summary a chunk when the window closes) ``(k_open,
      v_open, k_sum, v_sum)``: the ``min(S, window)`` rows [B, .., Hkv, D]
      from ``kv_cache.open_window_start(length, S, window)`` on, which
      hold the window the prompt leaves open, and a summary a chunk of the
      bucket [B, S / chunk, Hkv, D], the model's own pooling
      (the cache's ``write_prompt`` unpacks a layer's ``kept`` by the
      layer's group: it puts the closed windows' summaries where
      attention reads them and the open window's where they wait);
      ``None`` of a layer that stands in NO cache group (a feed-forward
      that is a layer of its own: nothing is written for it);
    * ``prefill_last`` (optional; the engine asks ``hasattr``): the same
      with ``logits [B, V]`` of each prompt's LAST row only ([B, S, V] at S
      = 8,192 and V = 151,936 would be 5 GB). Absent: the engine calls
      ``prefill`` and takes the row itself;
    * ``decode(params, cache, cache_ops, tokens [B], pos [B], active [B])
      -> (logits [B, V], cache)`` or ``(logits, cache, stats)``: one
      position a slot through ``cache_ops``, which owns the cache's groups,
      rings and the gather-or-kernel choice; a model with ``cache_steps``
      names the loop step whose cache layer a call means by ``step=`` on
      ``write_token`` and ``decode_attention`` (an int, or an int32 scalar
      traced inside the model's own device loop, whose carry then holds
      the cache). ``stats`` is a dict of small
      int arrays a step that the engine feeds to the ``serving/*``
      histograms of the same names: ``moe_experts_touched``,
      ``moe_max_expert_rows``, ``moe_held_pairs`` [expert layers],
      ``state_slots_stepped``, ``attn_rows_read.<group>``,
      ``attn_rows_context.<group>``, ``index_blocks_scored``,
      ``index_rows_scored``, ``moe_groups_kept_with_held``,
      ``ut_expected_exit_step``, ``eva_chunks_closed``,
      ``eva_windows_closed``; a name
      without a histogram (a probe) rides to ``engine.last_decode_stats``
      only. A model over a compacting group calls, a layer,
      ``write_token``, ``open_chunk``, ``write_summary`` and
      ``decode_attention``, and ``close_windows`` after its last layer.

    Of ``model.cfg`` the engine reads ``n_layer``, ``n_head`` (the QUERY
    heads: one number, or one a layer), ``d_head``, ``max_seq``, ``dtype``,
    and, by ``getattr``, each with what its absence means:

    * ``n_kv_head``: the heads of K and V, the same in every layer, which
      size the cache. Absent: ``n_head`` (queries are not grouped);
    * ``cache_groups``: a list of ``(name, layers, window)``, ``(name,
      layers, window, kind)`` or ``(name, layers, window, kind, chunk)``
      (a ``chunk`` makes a ``KV`` group COMPACTING: ``window`` is then a
      tumbling window, replaced by one summary a ``chunk`` positions when
      it closes; over such a group the engine refuses the prefix cache,
      the int8 pool and the contiguous layout, and page export and import
      raise; absent: not compacting); the layers of a
      group share one ``n_head``
      (a group's decode attention is one kernel shape), ``window`` rows a
      slot are kept as a ring (None: every position, in pages), and the
      first group is the one admission counts pages of. A layer is named
      once a KIND of group: in one paged group (``KV`` or ``LATENT``), in
      one ``STATE`` group, in one of each (a block whose two mixers, one
      over pages and one over a recurrent state, read the same input), or
      in none (a layer that keeps nothing);
      the state groups come after the paged ones, and the engine's page
      accounting is the paged groups' alone. Absent: one group
      of every layer that keeps every position. ``kind`` is one of
      ``KV``, ``LATENT``, ``STATE``, names that ``serving/kv_cache.py``
      OWNS (``serving`` lies below ``models``, which imports them at
      module top); absent: ``KV``;
    * ``cache_steps``: the cache layers EACH layer of a paged ``KV`` group
      keeps, one a loop step, of a model that runs its layers that many
      times over a token with the same weights (``n_layer`` stays the
      weights' layers: pools, page bytes and a page payload are sized by
      ``n_layer x cache_steps``; a group's one page table serves them all,
      so admission counts the pages it counted). Absent: 1. Over it the
      engine refuses a latent cache and the int8 pool;
    * ``latent_row``: ``(rank, rope)``; the cache is then a
      ``LatentPagedCache`` of ONE ``[c | kr']`` row a token a layer, over
      every layer or over the ``LATENT`` groups. Absent: K and V rows;
    * ``slot_state``: ``(heads, dk, dv, tail rows, tail width)`` that each
      layer of a ``STATE`` group keeps a SLOT, and no pages. Absent: the
      model has no state group;
    * ``state_recurrence``: ``"kda"`` or ``"ssd"``, the recurrence whose
      step ``cache_ops.state_step`` runs over those states (and so what
      its inputs are: ``serving/kv_cache.py``). Absent: ``"kda"``;
    * ``index_row``: ``(rows a block, lanes of an index key, blocks a
      query reads)`` of a latent cache whose layers choose the rows a
      query reads: the cache then keeps a pooled index key a block beside
      the rows, through the same page table, and the open block's raw
      keys a slot, or, with blocks of ONE row, a key a row and nothing a
      slot (``LatentPagedCache(index=)``). Absent: no index;
    * ``experts_held`` (with ``n_expert``, ``top_k``): the global ids of
      the routed experts held here, from which the engine tells the form
      of an executable's grouped product. Absent: no expert layer.
    """

    # a subclass's module functions, bound as static methods
    init_params: Callable
    prefill_forward: Callable
    decode_forward: Callable
    head = staticmethod(head)   # (params, cfg, x) -> logits: the plain one

    def __init__(self, cfg, params: Dict = None, seed: int = 0):
        self.cfg = cfg
        self.params = (params if params is not None
                       else self.init_params(cfg, seed))

    def prefill(self, params, tokens, lengths):
        x, kept = self.prefill_forward(params, self.cfg, tokens, lengths)
        return self.head(params, self.cfg, x), kept

    def prefill_last(self, params, tokens, lengths):
        x, kept = self.prefill_forward(params, self.cfg, tokens, lengths)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return self.head(params, self.cfg, last), kept

    def decode(self, params, cache, cache_ops, tokens, pos, active):
        return self.decode_forward(params, self.cfg, cache, cache_ops, tokens,
                                   pos, active)

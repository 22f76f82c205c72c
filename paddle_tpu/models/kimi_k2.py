"""Kimi-K2-Instruct's decoder block (the DeepSeek-V3 block: latent
attention, a sigmoid-routed expert layer with a shared expert) as pure JAX
functions, with ``models.decoder_lm.DecoderLM``'s serving contract
(``cfg``, ``params``, ``prefill_last``, ``decode``), so the same
``ServingEngine``, scheduler and page pool serve it. The plain float32
statement of the same equations is ``models/kimi_k2_reference.py``; read
the layer there.

What is particular to serving it:

* the cache keeps ONE row a token a layer, ``[c | kr']``: the normed KV
  latent (``kv_lora_rank`` values) and the rotated rotary key every head
  shares (``cfg.latent_row``; ``serving.kv_cache.LatentPagedCache``);
* PREFILL EXPANDS: K and V of every head are made from the latent by
  ``wkvb``, as the equations say, and attention is causal over queries and
  keys of ``nope + rope`` lanes and values of ``d_v``
  (``ops.attention_ops.mla_causal_attention``);
* DECODE ABSORBS (same mathematics, other order): with ``Wuk_n``, ``Wuv_n``
  [rank, 128] the two halves of head n's block of ``wkvb``, ``q_lat_n =
  q_nope_n Wuk_n^T``, the score is ``scale (q_lat_n . c(j) + q_r_n .
  kr'(j))``, ``o_lat_n = sum_j p_j c(j)`` and ``a_n = o_lat_n Wuv_n``: the
  cache row is read as it is, once for all heads
  (``ops/pallas_kernels/mla_attention.py``);
* the routed experts may be a SHARE (``cfg.experts_held``, the global ids
  of the experts in ``wg``/``wu``/``wd``): the router scores all
  ``n_expert``, a pair routed to an absent expert adds nothing here, and
  the shared expert and the router are whole (``ops/moe_ops.py``).

The rotary pairing is rotate-half over the rotary lanes; the reference
says how that relates to the family's code.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops, moe_ops
from . import kimi_k2_reference as _ref

__all__ = ["KimiK2Config", "KimiK2LM", "init_params"]


class KimiK2Config:
    """Static hyperparameters, under this package's names. ``n_dense`` is
    ``first_k_dense_replace``: the leading layers with a dense SwiGLU of
    ``d_dense``; every later layer routes ``top_k`` of ``n_expert`` experts
    of ``d_expert`` and adds one shared expert of the same width."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, q_rank: int, kv_rank: int, d_nope: int,
                 d_rope: int, d_v: int, d_dense: int, n_dense: int,
                 n_expert: int, top_k: int, d_expert: int,
                 routed_scale: float = 1.0, rope_theta: float = 5e4,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 rms_eps: float = 1e-6, max_seq: int = 16384,
                 dtype="float32",
                 experts_held: Optional[Sequence[int]] = None,
                 bias_std: float = 0.001, n_group: int = 1,
                 topk_group: int = 1):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = int(d_nope), int(d_rope), int(d_v)
        self.d_head = self.d_nope + self.d_rope      # a query's lanes
        self.d_dense, self.n_dense = int(d_dense), int(n_dense)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.d_expert = int(d_expert)
        self.routed_scale = float(routed_scale)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.bias_std = float(bias_std)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        self.inv_freq = _ref.yarn_inv_freq(self.d_rope, self.rope_theta,
                                           self.rope_scaling)
        self.sm_scale = _ref.softmax_scale(
            {"qk_nope_head_dim": self.d_nope, "qk_rope_head_dim": self.d_rope,
             "rope_scaling": self.rope_scaling})

    @property
    def latent_row(self) -> Tuple[int, int]:
        """``(rank, rope)`` of the one row a token a layer keeps: what
        ``ServingEngine`` sizes its latent cache from."""
        return self.kv_rank, self.d_rope

    def __repr__(self):
        return ("KimiK2Config(V=%d, L=%d (%d dense), d=%d, H=%d, q_rank=%d, "
                "latent %d+%d, E=%d of %d held, top-%d of %d, %s)"
                % (self.vocab_size, self.n_layer, self.n_dense, self.d_model,
                   self.n_head, self.q_rank, self.kv_rank, self.d_rope,
                   len(self.experts_held), self.n_expert, self.top_k,
                   self.d_expert, self.dtype))


def _init_layer(cfg: KimiK2Config, key, dense: bool) -> Dict:
    d, h = cfg.d_model, cfg.n_head
    k = jax.random.split(key, 13)

    def nrm(kk, shape, std=0.02):
        # drawn in the served type: no float32 copy of a 10 GB tree
        return std * jax.random.normal(kk, shape, cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    lp = {"g1": ones(d), "g2": ones(d), "gq": ones(cfg.q_rank),
          "gkv": ones(cfg.kv_rank),
          "wqa": nrm(k[0], (d, cfg.q_rank)),
          "wqb": nrm(k[1], (cfg.q_rank, h * cfg.d_head)),
          "wkva": nrm(k[2], (d, cfg.kv_rank + cfg.d_rope)),
          "wkvb": nrm(k[3], (cfg.kv_rank, h * (cfg.d_nope + cfg.d_v))),
          "wo": nrm(k[4], (h * cfg.d_v, d))}
    if dense:
        f = cfg.d_dense
        lp.update(wg=nrm(k[5], (d, f)), wu=nrm(k[6], (d, f)),
                  wd=nrm(k[7], (f, d)))
        return lp
    e, f = len(cfg.experts_held), cfg.d_expert
    lp.update(wr=nrm(k[5], (d, cfg.n_expert)),
              br=nrm(k[6], (cfg.n_expert,), cfg.bias_std),
              wg=nrm(k[7], (e, d, f)), wu=nrm(k[8], (e, d, f)),
              wd=nrm(k[9], (e, f, d)), sg=nrm(k[10], (d, f)),
              su=nrm(k[11], (d, f)), sd=nrm(k[12], (f, d)))
    return lp


def init_params(cfg: KimiK2Config, seed) -> Dict:
    """Seeded random weights, made where JAX computes (the device), in
    ``cfg.dtype``, one layer a call: the largest temporary is one layer.
    The selection bias ``br`` is drawn with ``cfg.bias_std``: of the size of
    the gaps between the largest sigmoid scores, so that the selection by
    ``s + b`` differs from the selection by ``s`` without the bias alone
    choosing the experts."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    layer = jax.jit(lambda k, dense: _init_layer(cfg, k, dense),
                    static_argnums=1)
    emb = jax.jit(lambda k, shape: 0.02 * jax.random.normal(
        k, shape, cfg.dtype), static_argnums=1)
    return {"tok_emb": emb(keys[0], (cfg.vocab_size, cfg.d_model)),
            "head": emb(keys[1], (cfg.d_model, cfg.vocab_size)),
            "gf": jnp.ones((cfg.d_model,), cfg.dtype),
            "layers": [layer(keys[2 + i], i < cfg.n_dense)
                       for i in range(cfg.n_layer)]}


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, inv_freq):
    """Rotate-half over the last axis: ``x`` [..., rope] (any axes between
    the leading position axes and the last), ``pos`` the leading axes."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32).reshape(
        pos.shape + (1,) * (x.ndim - pos.ndim)) \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def _latent(cfg, lp, h, pos):
    """What attention reads of the normed input ``h`` [..., d] at ``pos``
    [...]: the queries ``(q_nope, q_rope)`` [..., H, nope | rope], rotated,
    and the cache row ``[c | kr']`` [..., rank + rope]. A layer without a
    query latent (``q_lora_rank`` null) projects ``h`` by ``wq``."""
    if "wq" in lp:
        q = h @ lp["wq"]
    else:
        q = _rms(h @ lp["wqa"], lp["gq"], cfg.rms_eps) @ lp["wqb"]
    q = q.reshape(h.shape[:-1] + (cfg.n_head, cfg.d_head))
    kva = h @ lp["wkva"]
    c = _rms(kva[..., :cfg.kv_rank], lp["gkv"], cfg.rms_eps)
    kr = _rope(kva[..., cfg.kv_rank:], pos, cfg.inv_freq)
    q_r = _rope(q[..., cfg.d_nope:], pos, cfg.inv_freq)
    return q[..., :cfg.d_nope], q_r, jnp.concatenate([c, kr], axis=-1)


def _feed_forward(cfg, lp, x, row_valid):
    """The layer's second half over rows ``x`` [N, d]: the dense SwiGLU,
    or the routed experts held here plus the shared expert. Returns ``(x,
    stats or None)``."""
    u = _rms(x, lp["g2"], cfg.rms_eps)
    if "wr" not in lp:
        return x + _swiglu(u, lp["wg"], lp["wu"], lp["wd"]), None
    limited = ({} if cfg.n_group == 1 else
               {"n_group": cfg.n_group, "topk_group": cfg.topk_group})
    idx, w = moe_ops.route_sigmoid_topk(u, lp["wr"], lp["br"], cfg.top_k,
                                        cfg.routed_scale, **limited)
    y, stats = moe_ops.expert_layer(
        u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=(None if len(cfg.experts_held) == cfg.n_expert
              else cfg.experts_held), row_valid=row_valid,
        activation=jax.nn.silu)
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    with jax.named_scope("moe/shared"):
        shared = _swiglu(u, lp["sg"], lp["su"], lp["sd"])
    return x + (y + shared.astype(jnp.float32)).astype(x.dtype), stats


def prefill_forward(params: Dict, cfg: KimiK2Config, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S], K and V
    EXPANDED from the latent. Returns ``(x [B, S, d] before the final
    norm, rows)`` with ``rows`` one ``(row,)`` [B, S, rank + rope] a layer:
    what the latent cache keeps. A padding position's row is garbage that
    no valid row reads (causality), and the routed experts do not compute
    it."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = (pos < lengths[:, None]).reshape(b * s)
    rows = []
    for lp in params["layers"]:
        h = _rms(x, lp["g1"], cfg.rms_eps)
        q_n, q_r, row = _latent(cfg, lp, h, pos)
        rows.append((row,))
        kv = (row[..., :cfg.kv_rank] @ lp["wkvb"]).reshape(
            b, s, cfg.n_head, cfg.d_nope + cfg.d_v)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        att = [attention_ops.mla_causal_attention(
            q[j], kv[j, ..., :cfg.d_nope], row[j, :, cfg.kv_rank:],
            kv[j, ..., cfg.d_nope:], cfg.sm_scale) for j in range(b)]
        o = jnp.stack(att).reshape(b, s, cfg.n_head * cfg.d_v)
        x = x + o @ lp["wo"]
        x, _ = _feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, rows


def _head(params, cfg, x):
    return _rms(x, params["gf"], cfg.rms_eps) @ params["head"]


def absorbed_query(cfg: KimiK2Config, wkvb, q_n, q_r):
    """``[q_nope_n Wuk_n^T | q_rope_n]`` [B, H, rank + rope]: the query of
    head n over the cache row's lanes."""
    w = wkvb.reshape(cfg.kv_rank, cfg.n_head, cfg.d_nope + cfg.d_v)
    q_lat = jnp.einsum("bhn,chn->bhc", q_n, w[..., :cfg.d_nope],
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_n.dtype), q_r], axis=-1)


def absorbed_output(cfg: KimiK2Config, wkvb, o_lat):
    """``o_lat_n Wuv_n`` [B, H * d_v] of ``o_lat`` [B, H, rank]."""
    w = wkvb.reshape(cfg.kv_rank, cfg.n_head, cfg.d_nope + cfg.d_v)
    a = jnp.einsum("bhc,chv->bhv", o_lat, w[..., cfg.d_nope:],
                   preferred_element_type=jnp.float32)
    return a.astype(o_lat.dtype).reshape(o_lat.shape[0], -1)


def decode_forward(params: Dict, cfg: KimiK2Config, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot, ABSORBED, through ``cache_ops`` (the
    latent cache owns the gather-or-kernel choice). Returns ``(logits [B,
    V], cache, stats)``; ``stats`` holds, for each EXPERT layer,
    ``moe_experts_touched``, ``moe_max_expert_rows`` and ``moe_held_pairs``
    [n_layer - n_dense] int32 of the live slots' rows, over the experts
    held here."""
    x = params["tok_emb"][tokens]
    stats = []
    for i, lp in enumerate(params["layers"]):
        h = _rms(x, lp["g1"], cfg.rms_eps)
        q_n, q_r, row = _latent(cfg, lp, h, pos)
        cache = cache_ops.write_token(cache, i, row, pos, active)
        with jax.named_scope("attn/mla"):
            o_lat = cache_ops.decode_attention(
                cache, i, absorbed_query(cfg, lp["wkvb"], q_n, q_r),
                pos + 1, active, sm_scale=cfg.sm_scale)
            x = x + absorbed_output(cfg, lp["wkvb"], o_lat) @ lp["wo"]
        x, st = _feed_forward(cfg, lp, x, active)
        if st is not None:
            stats.append(st)
    return _head(params, cfg, x), cache, {
        "moe_experts_touched": jnp.stack(
            [s["experts_touched"] for s in stats]),
        "moe_max_expert_rows": jnp.stack(
            [s["max_expert_rows"] for s in stats]),
        "moe_held_pairs": jnp.stack([s["held_pairs"] for s in stats])}


class KimiK2LM:
    """The serving contract over :class:`KimiK2Config`. No ``verify``
    method: speculation resolves off for this model."""

    def __init__(self, cfg: KimiK2Config, params: Dict = None,
                 seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else init_params(cfg, seed)

    def prefill(self, params, tokens, lengths):
        x, rows = prefill_forward(params, self.cfg, tokens, lengths)
        return _head(params, self.cfg, x), rows

    def prefill_last(self, params, tokens, lengths):
        """The head for each prompt's LAST row only: ``(logits [B, V],
        rows)``."""
        x, rows = prefill_forward(params, self.cfg, tokens, lengths)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return _head(params, self.cfg, last), rows

    def decode(self, params, cache, cache_ops, tokens, pos, active):
        return decode_forward(params, self.cfg, cache, cache_ops, tokens,
                              pos, active)

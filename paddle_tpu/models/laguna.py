"""Laguna-S-2.1's decoder block as pure JAX functions, with
``models.decoder_lm.DecoderLM``'s serving contract (``cfg``, ``params``,
``prefill``/``prefill_last``, ``decode``), so the same ``ServingEngine``,
scheduler, page pools, paged cache and paged-attention kernel serve it.
The plain float32 statement of the same equations is
``models/laguna_reference.py``; read the layer there.

What is particular to serving it:

* the QUERY heads differ by layer type over the same KV heads (48 on full
  layers, 72 on sliding ones, over 8): ``cfg.n_head`` is one number a
  layer, the cache's geometry is one, and the query heads a KV head (6 and
  9) belong to the cache GROUP (``cfg.cache_groups``: ``global`` the full
  layers, ``window`` the sliding ones, a ring of ``window`` rows a slot);
* two rotary tables a model: full layers rotate the first half of a head's
  lanes at YaRN frequencies with the attention factor in cos and sin,
  sliding layers rotate the whole head plainly. K is stored in the cache
  AFTER its rotation, the attention factor in it, so the order of a ring's
  rows does not matter to the softmax;
* a head-wise gate ``sigmoid(h Wgamma)`` on attention's output, computed
  outside the kernel, in float32;
* the routed experts may be a SHARE (``cfg.experts_held``, the global ids
  of the experts in ``wg``/``wu``/``wd``): the router scores all
  ``n_expert``, a pair routed to an absent expert adds nothing here, and
  the shared expert and the router are whole (``ops/moe_ops.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops, moe_ops
from . import laguna_reference as _ref

__all__ = ["LagunaConfig", "LagunaLM", "init_params"]

FULL, SLIDING = "full_attention", "sliding_attention"


class LagunaConfig:
    """Static hyperparameters, under this package's names. ``n_head`` is
    the QUERY heads of each layer, ``layer_types`` its kind (``FULL`` or
    ``SLIDING``), ``rope`` the published ``rope_parameters`` (one entry a
    kind); the layers in ``dense_layers`` have a dense SwiGLU of
    ``d_dense``, every other one routes ``top_k`` of ``n_expert`` experts
    of ``d_expert`` and adds one shared expert of ``d_shared``."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: Sequence[int], n_kv_head: int, d_head: int,
                 layer_types: Sequence[str], window: int,
                 rope: Dict[str, Dict[str, Any]], d_dense: int,
                 dense_layers: Sequence[int], n_expert: int, top_k: int,
                 d_expert: int, d_shared: int, routed_scale: float = 1.0,
                 rms_eps: float = 1e-6, max_seq: int = 16384,
                 dtype="float32",
                 experts_held: Optional[Sequence[int]] = None):
        if len(n_head) != n_layer or len(layer_types) != n_layer:
            raise ValueError("n_head and layer_types name one entry a layer")
        if any(h % n_kv_head for h in n_head):
            raise ValueError("every layer's n_head must be a multiple of "
                             "n_kv_head")
        if set(layer_types) - {FULL, SLIDING}:
            raise ValueError("layer_types: %s" % sorted(set(layer_types)))
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = tuple(int(h) for h in n_head)
        self.n_kv_head = int(n_kv_head)
        self.d_head = int(d_head)
        self.layer_types = tuple(layer_types)
        self.window = int(window)
        self.d_dense = int(d_dense)
        self.dense_layers = tuple(int(i) for i in dense_layers)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.d_expert, self.d_shared = int(d_expert), int(d_shared)
        self.routed_scale = float(routed_scale)
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.sm_scale = 1.0 / math.sqrt(self.d_head)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        # (inv_freq [rot / 2], attention factor) of each kind of layer
        self.rope = {kind: _ref.rope_table(self.d_head, rope[kind])
                     for kind in set(self.layer_types)}

    @property
    def cache_groups(self) -> List[Tuple[str, Tuple[int, ...], Optional[int]]]:
        """``(name, layers, window)`` of each cache group: what
        ``ServingEngine`` builds its pools from."""
        glob = tuple(i for i, t in enumerate(self.layer_types) if t == FULL)
        win = tuple(i for i, t in enumerate(self.layer_types) if t == SLIDING)
        groups = []
        if glob:
            groups.append(("global", glob, None))
        if win:
            groups.append(("window", win, self.window))
        return groups

    def __repr__(self):
        return ("LagunaConfig(V=%d, L=%d (dense %s), d=%d, Hq=%s over Hkv=%d, "
                "D=%d, W=%d, E=%d of %d held, top-%d of %d, %s)"
                % (self.vocab_size, self.n_layer, list(self.dense_layers),
                   self.d_model, sorted(set(self.n_head)), self.n_kv_head,
                   self.d_head, self.window, len(self.experts_held),
                   self.n_expert, self.top_k, self.d_expert, self.dtype))


def _init_layer(cfg: LagunaConfig, key, n_head: int, dense: bool) -> Dict:
    d = cfg.d_model
    hq, hkv = n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    k = jax.random.split(key, 12)

    def nrm(kk, shape):
        # drawn in the served type: no float32 copy of an 11 GB tree
        return 0.02 * jax.random.normal(kk, shape, cfg.dtype)

    lp = {"g1": jnp.ones((d,), cfg.dtype), "g2": jnp.ones((d,), cfg.dtype),
          "wq": nrm(k[0], (d, hq)), "wk": nrm(k[1], (d, hkv)),
          "wv": nrm(k[2], (d, hkv)), "wgam": nrm(k[3], (d, n_head)),
          "wo": nrm(k[4], (hq, d))}
    if dense:
        f = cfg.d_dense
        lp.update(wg=nrm(k[5], (d, f)), wu=nrm(k[6], (d, f)),
                  wd=nrm(k[7], (f, d)))
        return lp
    e, f, fs = len(cfg.experts_held), cfg.d_expert, cfg.d_shared
    lp.update(wr=nrm(k[5], (d, cfg.n_expert)),
              wg=nrm(k[6], (e, d, f)), wu=nrm(k[7], (e, d, f)),
              wd=nrm(k[8], (e, f, d)), sg=nrm(k[9], (d, fs)),
              su=nrm(k[10], (d, fs)), sd=nrm(k[11], (fs, d)))
    return lp


def init_params(cfg: LagunaConfig, seed) -> Dict:
    """Seeded random weights, made where JAX computes (the device), in
    ``cfg.dtype``, one layer a call: the largest temporary is one layer."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    layer = jax.jit(lambda k, n_head, dense: _init_layer(cfg, k, n_head,
                                                         dense),
                    static_argnums=(1, 2))
    emb = jax.jit(lambda k, shape: 0.02 * jax.random.normal(
        k, shape, cfg.dtype), static_argnums=1)
    return {"tok_emb": emb(keys[0], (cfg.vocab_size, cfg.d_model)),
            "head": emb(keys[1], (cfg.d_model, cfg.vocab_size)),
            "gf": jnp.ones((cfg.d_model,), cfg.dtype),
            "layers": [layer(keys[2 + i], cfg.n_head[i],
                             i in cfg.dense_layers)
                       for i in range(cfg.n_layer)]}


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, table):
    """Rotate-half over the first ``2 * len(inv_freq)`` lanes of ``x`` [...,
    H, D] at positions ``pos`` [...] (one a row of heads), cos and sin times
    the attention factor; the other lanes pass. ``table`` is one entry of
    ``cfg.rope``."""
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


def _qkv(cfg, lp, i: int, h, pos):
    """The rotated queries [..., H_i, D] and K, and V [..., Hkv, D] of
    layer ``i`` over its normed input ``h`` [..., d] at ``pos`` [...]."""
    lead = h.shape[:-1]
    q = (h @ lp["wq"]).reshape(lead + (cfg.n_head[i], cfg.d_head))
    k = (h @ lp["wk"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    v = (h @ lp["wv"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    table = cfg.rope[cfg.layer_types[i]]
    return _rope(q, pos, table), _rope(k, pos, table), v


def _gated(lp, h, o):
    """``gamma_n a_n``: attention's output ``o`` [..., H, D] under the
    head-wise gate of the same normed input ``h`` [..., d], flattened to
    [..., H * D] for the output projection."""
    with jax.named_scope("attn/gate"):
        gamma = jax.nn.sigmoid(jnp.dot(h, lp["wgam"],
                                       preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gamma[..., None]).astype(o.dtype)
        return o.reshape(o.shape[:-2] + (-1,))


def _swiglu(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def _feed_forward(cfg, lp, x, row_valid):
    """The layer's second half over rows ``x`` [N, d]: the dense SwiGLU,
    or the routed experts held here plus the shared expert. Returns ``(x,
    stats or None)``."""
    u = _rms(x, lp["g2"], cfg.rms_eps)
    if "wr" not in lp:
        return x + _swiglu(u, lp["wg"], lp["wu"], lp["wd"]), None
    with jax.named_scope("moe/route"):
        # the softmax over the chosen logits IS the softmax over all the
        # experts kept at the chosen ones and renormalised
        idx, w = moe_ops.route_topk(u, lp["wr"], cfg.top_k)
        w = w * cfg.routed_scale
    with jax.named_scope("moe/routed"):
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


def prefill_forward(params: Dict, cfg: LagunaConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S].
    Returns ``(x [B, S, d] before the final norm, kvs)`` with ``kvs`` one
    ``(k, v)`` [B, S, Hkv, D] pair a layer, K rotated. A padding position's
    row is garbage that no valid row reads (causality), and the routed
    experts do not compute it."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = (pos < lengths[:, None]).reshape(b * s)
    kvs = []
    for i, lp in enumerate(params["layers"]):
        h = _rms(x, lp["g1"], cfg.rms_eps)
        q, k, v = _qkv(cfg, lp, i, h, pos)
        kvs.append((k, v))
        if cfg.layer_types[i] == SLIDING:
            att = [attention_ops.windowed_causal_attention(
                q[j], k[j], v[j], cfg.window, cfg.sm_scale) for j in range(b)]
        else:
            att = [attention_ops.gqa_causal_attention(
                q[j], k[j], v[j], cfg.sm_scale) for j in range(b)]
        x = x + _gated(lp, h, jnp.stack(att)) @ lp["wo"]
        x, _ = _feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, kvs


def _head(params, cfg, x):
    return _rms(x, params["gf"], cfg.rms_eps) @ params["head"]


def decode_forward(params: Dict, cfg: LagunaConfig, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot, through ``cache_ops`` (the cache owns
    its groups, its rings and the gather-or-kernel choice). Returns
    ``(logits [B, V], cache, stats)``; ``stats`` holds, for each EXPERT
    layer, ``moe_experts_touched``, ``moe_max_expert_rows`` and
    ``moe_held_pairs`` [n_layer - dense] int32 of the live slots' rows over
    the experts held here, and for each cache group
    ``attn_rows_read.<group>``, the rows a layer of it read."""
    x = params["tok_emb"][tokens]
    stats = []
    for i, lp in enumerate(params["layers"]):
        h = _rms(x, lp["g1"], cfg.rms_eps)
        q, k, v = _qkv(cfg, lp, i, h, pos)
        cache = cache_ops.write_token(cache, i, k, v, pos, active)
        with jax.named_scope("attn/window" if cfg.layer_types[i] == SLIDING
                             else "attn/global"):
            o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                           sm_scale=cfg.sm_scale)
        x = x + _gated(lp, h, o) @ lp["wo"]
        x, st = _feed_forward(cfg, lp, x, active)
        if st is not None:
            stats.append(st)
    return _head(params, cfg, x), cache, {
        "moe_experts_touched": jnp.stack(
            [s["experts_touched"] for s in stats]),
        "moe_max_expert_rows": jnp.stack(
            [s["max_expert_rows"] for s in stats]),
        "moe_held_pairs": jnp.stack([s["held_pairs"] for s in stats]),
        **cache_ops.rows_read(pos + 1, active)}


class LagunaLM:
    """The serving contract over :class:`LagunaConfig`. No ``verify``
    method: speculation resolves off for this model."""

    def __init__(self, cfg: LagunaConfig, params: Dict = None, seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else init_params(cfg, seed)

    def prefill(self, params, tokens, lengths):
        x, kvs = prefill_forward(params, self.cfg, tokens, lengths)
        return _head(params, self.cfg, x), kvs

    def prefill_last(self, params, tokens, lengths):
        """The head for each prompt's LAST row only: ``(logits [B, V],
        kvs)``."""
        x, kvs = prefill_forward(params, self.cfg, tokens, lengths)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return _head(params, self.cfg, last), kvs

    def decode(self, params, cache, cache_ops, tokens, pos, active):
        return decode_forward(params, self.cfg, cache, cache_ops, tokens,
                              pos, active)

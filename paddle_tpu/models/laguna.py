"""Laguna-S-2.1's decoder block as pure JAX functions under the serving
contract (``models.blocks.ServedLM``), so the same ``ServingEngine``,
scheduler, page pools, paged cache and paged-attention kernel serve it.
The plain float32 statement of the same equations, which the tests and the
benchmark compare this with, is ``grid/reference/laguna.py``; read the
layer there.

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
from .blocks import (ServedLM, gated, head, held_experts, moe_stats, rms_norm,
                     rope_lanes, rope_table, seeded_params, swiglu)

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
        self.rope = {kind: rope_table(self.d_head, rope[kind])
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
    """Seeded random weights (``blocks.seeded_params``)."""
    return seeded_params(cfg, seed, _init_layer,
                         lambda i: (cfg.n_head[i], i in cfg.dense_layers))


def _qkv(cfg, lp, i: int, h, pos):
    """The rotated queries [..., H_i, D] and K, and V [..., Hkv, D] of
    layer ``i`` over its normed input ``h`` [..., d] at ``pos`` [...]."""
    lead = h.shape[:-1]
    q = (h @ lp["wq"]).reshape(lead + (cfg.n_head[i], cfg.d_head))
    k = (h @ lp["wk"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    v = (h @ lp["wv"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    table = cfg.rope[cfg.layer_types[i]]
    return rope_lanes(q, pos, table), rope_lanes(k, pos, table), v


def _feed_forward(cfg, lp, x, row_valid):
    """The layer's second half over rows ``x`` [N, d]: the dense SwiGLU,
    or the routed experts held here plus the shared expert. Returns ``(x,
    stats or None)``."""
    u = rms_norm(x, lp["g2"], cfg.rms_eps)
    if "wr" not in lp:
        return x + swiglu(u, lp["wg"], lp["wu"], lp["wd"]), None
    with jax.named_scope("moe/route"):
        # the softmax over the chosen logits IS the softmax over all the
        # experts kept at the chosen ones and renormalised
        idx, w = moe_ops.route_topk(u, lp["wr"], cfg.top_k)
        w = w * cfg.routed_scale
    with jax.named_scope("moe/routed"):
        y, stats = moe_ops.expert_layer(
            u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
            held=held_experts(cfg), row_valid=row_valid,
            activation=jax.nn.silu)
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    with jax.named_scope("moe/shared"):
        shared = swiglu(u, lp["sg"], lp["su"], lp["sd"])
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
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q, k, v = _qkv(cfg, lp, i, h, pos)
        kvs.append((k, v))
        if cfg.layer_types[i] == SLIDING:
            att = [attention_ops.windowed_causal_attention(
                q[j], k[j], v[j], cfg.window, cfg.sm_scale) for j in range(b)]
        else:
            att = [attention_ops.gqa_causal_attention(
                q[j], k[j], v[j], cfg.sm_scale) for j in range(b)]
        x = x + gated(lp, h, jnp.stack(att)) @ lp["wo"]
        x, _ = _feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, kvs


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
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q, k, v = _qkv(cfg, lp, i, h, pos)
        cache = cache_ops.write_token(cache, i, k, v, pos, active)
        with jax.named_scope("attn/window" if cfg.layer_types[i] == SLIDING
                             else "attn/global"):
            o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                           sm_scale=cfg.sm_scale)
        x = x + gated(lp, h, o) @ lp["wo"]
        x, st = _feed_forward(cfg, lp, x, active)
        if st is not None:
            stats.append(st)
    return head(params, cfg, x), cache, {
        **moe_stats(stats), **cache_ops.rows_read(pos + 1, active)}


class LagunaLM(ServedLM):
    """The serving contract over :class:`LagunaConfig`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

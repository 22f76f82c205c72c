"""SmallThinker's decoder block as pure JAX functions under the serving
contract (``models.blocks.ServedLM``), so the same ``ServingEngine``,
scheduler, page pool, paged cache and paged-attention kernel serve it.

The layer (PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``; the
plain float32 statement of the same equations, which the tests and the
benchmark compare this with, is ``grid/reference/smallthinker.py``):

* RMSNorm; Q of ``n_head`` heads and K, V of ``n_kv_head`` heads of
  ``d_head`` (grouped queries: query head n reads KV head ``n // G``), no
  biases, no QK norm;
* ``rope_layout[l] == 1``: rotary positions (rotate-half over the whole
  head, theta ``rope_theta``) on q and k; 0: no positions at all;
* ``sliding_window_layout[l] == 1``: position i sees ``i - j < window``;
  0: every earlier position. The layers of each kind form a CACHE GROUP
  (``cfg.cache_groups``): window layers keep a ring of ``window`` rows a
  slot, global layers keep everything;
* the router reads the SAME normed input attention read and picks
  ``top_k`` of ``n_expert`` ReGLU experts, weights the softmax over the
  chosen logits; every routed token is computed (``ops/moe_ops.py``);
* a final RMSNorm and an output head that is not tied to the embedding.

K is stored in the cache AFTER its rotation, so the order of a window
ring's rows does not matter to the softmax.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops, moe_ops
from .blocks import (ServedLM, head, held_experts, rms_norm, rope,
                     seeded_params)

__all__ = ["SmallThinkerConfig", "SmallThinkerLM", "init_params"]


class SmallThinkerConfig:
    """Static hyperparameters. ``n_head`` counts the QUERY heads; the cache
    and the paged kernel are sized by ``n_kv_head``."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, n_kv_head: int, d_head: int, n_expert: int,
                 top_k: int, d_expert: int, window: int,
                 rope_layout: Sequence[int], window_layout: Sequence[int],
                 rope_theta: float = 1.5e6, rms_eps: float = 1e-6,
                 max_seq: int = 16384, dtype="float32",
                 experts_held: Optional[Sequence[int]] = None):
        if n_head % n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if len(rope_layout) != n_layer or len(window_layout) != n_layer:
            raise ValueError("rope_layout and window_layout name one entry "
                             "a layer")
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.n_kv_head = int(n_kv_head)
        self.d_head = int(d_head)
        self.n_expert = int(n_expert)
        self.top_k = int(top_k)
        self.d_expert = int(d_expert)
        self.window = int(window)
        self.rope_layout = tuple(int(x) for x in rope_layout)
        self.window_layout = tuple(int(x) for x in window_layout)
        self.rope_theta = float(rope_theta)
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.sm_scale = 1.0 / math.sqrt(self.d_head)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))

    @property
    def cache_groups(self) -> List[Tuple[str, Tuple[int, ...], Optional[int]]]:
        """``(name, layers, window)`` of each cache group: what
        ``ServingEngine`` builds its pools from."""
        glob = tuple(i for i, w in enumerate(self.window_layout) if not w)
        win = tuple(i for i, w in enumerate(self.window_layout) if w)
        groups = []
        if glob:
            groups.append(("global", glob, None))
        if win:
            groups.append(("window", win, self.window))
        return groups

    def __repr__(self):
        return ("SmallThinkerConfig(V=%d, L=%d, d=%d, Hq=%d, Hkv=%d, D=%d, "
                "E=%d top-%d of %d, W=%d, %s)"
                % (self.vocab_size, self.n_layer, self.d_model, self.n_head,
                   self.n_kv_head, self.d_head, self.n_expert, self.top_k,
                   self.d_expert, self.window, self.dtype))


def _init_layer(cfg: SmallThinkerConfig, key) -> Dict:
    d, f = cfg.d_model, cfg.d_expert
    hq, hkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    e = len(cfg.experts_held)
    k = jax.random.split(key, 8)

    def nrm(kk, shape):
        # drawn in the served type: no float32 copy of an 11 GB tree
        return 0.02 * jax.random.normal(kk, shape, cfg.dtype)

    return {"g1": jnp.ones((d,), cfg.dtype), "g2": jnp.ones((d,), cfg.dtype),
            "wq": nrm(k[0], (d, hq)), "wk": nrm(k[1], (d, hkv)),
            "wv": nrm(k[2], (d, hkv)), "wo": nrm(k[3], (hq, d)),
            "wr": nrm(k[4], (d, cfg.n_expert)),
            "wg": nrm(k[5], (e, d, f)), "wu": nrm(k[6], (e, d, f)),
            "wd": nrm(k[7], (e, f, d))}


def init_params(cfg: SmallThinkerConfig, seed) -> Dict:
    """Seeded random weights (``blocks.seeded_params``)."""
    return seeded_params(cfg, seed, _init_layer, lambda i: ())


def _rope(cfg, x, pos):
    """Rotate-half over the whole head of ``x`` [..., H, D] at ``pos``
    [...], the frequencies made in the program from ``cfg.rope_theta``."""
    half = x.shape[-1] // 2
    return rope(x, pos, cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half))


def _experts(cfg, lp, x, idx, w, row_valid):
    u = rms_norm(x, lp["g2"], cfg.rms_eps)
    y, stats = moe_ops.expert_layer(
        u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=held_experts(cfg), row_valid=row_valid)
    return x + y.astype(x.dtype), stats


def prefill_forward(params: Dict, cfg: SmallThinkerConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S].
    Returns ``(x [B, S, d] before the final norm, kvs)`` with ``kvs`` one
    ``(k, v)`` [B, S, Hkv, D] pair a layer, K rotated where the layer has
    positions. A padding position's row is garbage that no valid row reads
    (causality), and the expert layer does not compute it."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    pos = jnp.arange(s)
    valid = (pos[None, :] < lengths[:, None]).reshape(b * s)
    kvs = []
    for i, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_head, cfg.d_head)
        k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_head, cfg.d_head)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_head, cfg.d_head)
        if cfg.rope_layout[i]:
            q = _rope(cfg, q, pos[None])
            k = _rope(cfg, k, pos[None])
        kvs.append((k, v))
        if cfg.window_layout[i]:
            att = [attention_ops.windowed_causal_attention(
                q[j], k[j], v[j], cfg.window, cfg.sm_scale) for j in range(b)]
        else:
            att = [attention_ops.gqa_causal_attention(
                q[j], k[j], v[j], cfg.sm_scale) for j in range(b)]
        o = jnp.stack(att).reshape(b, s, cfg.n_head * cfg.d_head)
        x = x + o @ lp["wo"]
        idx, w = moe_ops.route_topk(h.reshape(b * s, -1), lp["wr"], cfg.top_k)
        x, _ = _experts(cfg, lp, x.reshape(b * s, -1), idx, w, valid)
        x = x.reshape(b, s, -1)
    return x, kvs


def decode_forward(params: Dict, cfg: SmallThinkerConfig, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot, through ``cache_ops`` (the cache owns
    its groups, its rings and the gather-or-kernel choice). Returns
    ``(logits [B, V], cache, stats)``; ``stats`` holds, a layer,
    ``moe_experts_touched`` and ``moe_max_expert_rows`` [n_layer] int32 of
    the live slots' rows."""
    b = tokens.shape[0]
    x = params["tok_emb"][tokens]
    touched, biggest = [], []
    for i, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q = (h @ lp["wq"]).reshape(b, cfg.n_head, cfg.d_head)
        k = (h @ lp["wk"]).reshape(b, cfg.n_kv_head, cfg.d_head)
        v = (h @ lp["wv"]).reshape(b, cfg.n_kv_head, cfg.d_head)
        if cfg.rope_layout[i]:
            q = _rope(cfg, q, pos)
            k = _rope(cfg, k, pos)
        cache = cache_ops.write_token(cache, i, k, v, pos, active)
        with jax.named_scope("attn/window" if cfg.window_layout[i]
                             else "attn/global"):
            o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                           sm_scale=cfg.sm_scale)
        x = x + o.reshape(b, cfg.n_head * cfg.d_head) @ lp["wo"]
        idx, w = moe_ops.route_topk(h, lp["wr"], cfg.top_k)
        x, stats = _experts(cfg, lp, x, idx, w, active)
        touched.append(stats["experts_touched"])
        biggest.append(stats["max_expert_rows"])
    return head(params, cfg, x), cache, {
        "moe_experts_touched": jnp.stack(touched),
        "moe_max_expert_rows": jnp.stack(biggest)}


class SmallThinkerLM(ServedLM):
    """The serving contract over :class:`SmallThinkerConfig`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

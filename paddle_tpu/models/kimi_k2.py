"""Kimi-K2-Instruct's decoder block (the DeepSeek-V3 block: latent
attention, a sigmoid-routed expert layer with a shared expert) as pure JAX
functions under the serving contract (``models.blocks.ServedLM``), so the
same ``ServingEngine``, scheduler and page pool serve it. The plain float32
statement of the same equations, which the tests and the benchmark compare
this with, is ``grid/reference/kimi_k2.py``; read the layer there. The
latent projection, the absorbed pair and the feed-forward half are
``models/blocks.py``'s: Ling-3 and Motif-3 are made of them too.

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

from ..ops import attention_ops
from .blocks import (ServedLM, absorbed_output, absorbed_query, head, latent,
                     mla_softmax_scale, moe_stats, rms_norm,
                     routed_feed_forward, seeded_params, yarn_inv_freq)

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
        self.inv_freq = yarn_inv_freq(self.d_rope, self.rope_theta,
                                      self.rope_scaling)
        self.sm_scale = mla_softmax_scale(self.d_head, self.rope_scaling)

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
    """Seeded random weights (``blocks.seeded_params``). The selection bias
    ``br`` is drawn with ``cfg.bias_std``: of the size of the gaps between
    the largest sigmoid scores, so that the selection by ``s + b`` differs
    from the selection by ``s`` without the bias alone choosing the
    experts."""
    return seeded_params(cfg, seed, _init_layer,
                         lambda i: (i < cfg.n_dense,))


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
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q_n, q_r, row = latent(cfg, lp, h, pos)
        rows.append((row,))
        kv = (row[..., :cfg.kv_rank] @ lp["wkvb"]).reshape(
            b, s, cfg.n_head, cfg.d_nope + cfg.d_v)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        att = [attention_ops.mla_causal_attention(
            q[j], kv[j, ..., :cfg.d_nope], row[j, :, cfg.kv_rank:],
            kv[j, ..., cfg.d_nope:], cfg.sm_scale) for j in range(b)]
        o = jnp.stack(att).reshape(b, s, cfg.n_head * cfg.d_v)
        x = x + o @ lp["wo"]
        x, _ = routed_feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, rows


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
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q_n, q_r, row = latent(cfg, lp, h, pos)
        cache = cache_ops.write_token(cache, i, row, pos, active)
        with jax.named_scope("attn/mla"):
            o_lat = cache_ops.decode_attention(
                cache, i, absorbed_query(cfg, lp["wkvb"], q_n, q_r),
                pos + 1, active, sm_scale=cfg.sm_scale)
            x = x + absorbed_output(cfg, lp["wkvb"], o_lat) @ lp["wo"]
        x, st = routed_feed_forward(cfg, lp, x, active)
        if st is not None:
            stats.append(st)
    return head(params, cfg, x), cache, moe_stats(stats)


class KimiK2LM(ServedLM):
    """The serving contract over :class:`KimiK2Config`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

"""Ling-3.0-flash-VL's language model as pure JAX functions under the
serving contract (``models.blocks.ServedLM``), so the same
``ServingEngine``, scheduler and page pool serve it. The plain float32
statement of the same equations, which the tests and the benchmark compare
this with, is ``grid/reference/ling3_flash.py``; read the layers there.

A HYBRID: five layers in six are Kimi Delta Attention (KDA), a
linear-attention recurrence, and each sixth is latent attention (MLA).
What is particular to serving it:

* two KINDS of state in one cache (``cfg.cache_groups``;
  ``serving.kv_cache``): the MLA layers keep one ``[c | kr']`` row a token
  in pages (``cfg.latent_row``), the KDA layers keep, a SLOT, one
  ``[H, dk, dv]`` float32 state and the last three inputs of their
  four-tap convolution (``cfg.slot_state``), whatever the context's
  length, and no pages at all;
* a KDA layer's PREFILL is a chunk-wise scan
  (``ops/pallas_kernels/kda.kda_chunk_scan``) that hands the cache the
  state the prompt leaves and the convolution's tail, not rows: the
  bucket's padding is given a log-decay of 0 and a write strength of 0,
  so it leaves the state as the last prompt token left it;
* its DECODE step reads the slot's tail, then streams the slot's state
  through ``cache_ops.state_step`` (the ``kda_state_step`` kernel) once a
  layer; the decay is computed and the state kept in float32;
* the MLA layer is Kimi-K2's (``blocks.latent`` and the absorbed pair),
  with no query latent (``q_lora_rank`` null: ``wq``) and Laguna's
  head-wise gate (``blocks.gated``) on the heads' outputs: expanded
  prefill, absorbed decode over the latent kernel;
* the feed-forward half is Kimi-K2's (``blocks.routed_feed_forward``), the
  router group-limited (``ops/moe_ops.route_sigmoid_topk``); the routed
  experts may be a SHARE (``cfg.experts_held``).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops
from ..ops.pallas_kernels import kda as kda_ops
from ..serving.kv_cache import LATENT, STATE
from .blocks import (ServedLM, absorbed_output, absorbed_query, gated, head,
                     kda_inputs, kda_output, kda_prefill, latent, moe_stats,
                     rms_norm, routed_feed_forward, seeded_params)

__all__ = ["Ling3FlashConfig", "Ling3FlashLM", "init_params"]

KDA, MLA = "kda", "mla"                # ``layer_types``' two names


class Ling3FlashConfig:
    """Static hyperparameters, under this package's names. ``layer_types``
    gives each layer's attention (``KDA`` or ``MLA``); the layers in
    ``dense_layers`` have a dense SwiGLU of ``d_dense``, every other one
    routes ``top_k`` of ``n_expert`` experts of ``d_expert`` in
    ``n_group`` groups (``topk_group`` stay) and adds one shared expert
    of the same width. ``d_state`` is a KDA head's ``dk = dv``;
    ``half_life`` the range over which :func:`init_params` spreads the
    channels' half-lives at a zero pre-activation."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, d_state: int, layer_types: Sequence[str],
                 kv_rank: int, d_nope: int, d_rope: int, d_v: int,
                 d_dense: int, dense_layers: Sequence[int], n_expert: int,
                 top_k: int, d_expert: int, n_group: int = 1,
                 topk_group: int = 1, routed_scale: float = 1.0,
                 rope_theta: float = 6e6, conv_taps: int = 4,
                 lower_bound: float = -5.0, rms_eps: float = 1e-6,
                 max_seq: int = 16384, dtype="float32",
                 experts_held: Optional[Sequence[int]] = None,
                 bias_std: float = 0.001,
                 half_life: Tuple[float, float] = (4.0, 4096.0)):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.d_state = int(d_state)
        self.layer_types = tuple(layer_types)
        if len(self.layer_types) != self.n_layer \
                or set(self.layer_types) - {KDA, MLA}:
            raise ValueError("layer_types names %d layers of %s; the model "
                             "has %d of %s" % (len(self.layer_types),
                                               sorted(set(self.layer_types)),
                                               self.n_layer, (KDA, MLA)))
        self.kv_rank = int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = int(d_nope), int(d_rope), int(d_v)
        self.d_head = self.d_nope + self.d_rope     # an MLA query's lanes
        self.d_dense = int(d_dense)
        self.dense_layers = tuple(int(i) for i in dense_layers)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.d_expert = int(d_expert)
        self.routed_scale = float(routed_scale)
        self.rope_theta = float(rope_theta)
        self.conv_taps = int(conv_taps)
        self.lower_bound = float(lower_bound)
        if not kda_ops.LOWER_BOUND <= self.lower_bound < 0:
            raise ValueError("the chunk scan's exponents are safe for a "
                             "log-decay above %g a step; lower_bound=%g"
                             % (kda_ops.LOWER_BOUND, self.lower_bound))
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.bias_std = float(bias_std)
        self.half_life = (float(half_life[0]), float(half_life[1]))
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        self.inv_freq = self.rope_theta ** (
            -jnp.arange(self.d_rope // 2, dtype=jnp.float32) * 2.0
            / self.d_rope)
        self.sm_scale = self.d_head ** -0.5

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def latent_row(self) -> Tuple[int, int]:
        """``(rank, rope)`` of the row an MLA layer keeps a token."""
        return self.kv_rank, self.d_rope

    @property
    def slot_state(self) -> Tuple[int, int, int, int, int]:
        """What a KDA layer keeps a SLOT: ``(heads, dk, dv, tail rows,
        tail width)``."""
        return (self.n_head, self.d_state, self.d_state, self.conv_taps - 1,
                3 * self.n_head * self.d_state)

    @property
    def cache_groups(self):
        """The latent group (pages, admission) first, the state group
        (bound to the slot) after it."""
        return [("latent", self.layers_of(MLA), None, LATENT),
                ("state", self.layers_of(KDA), None, STATE)]

    def __repr__(self):
        return ("Ling3FlashConfig(V=%d, L=%d (%d KDA, %d MLA, %d dense), "
                "d=%d, H=%d, state %dx%d, latent %d+%d, E=%d of %d held in "
                "%d groups, top-%d of %d, %s)"
                % (self.vocab_size, self.n_layer, len(self.layers_of(KDA)),
                   len(self.layers_of(MLA)), len(self.dense_layers),
                   self.d_model, self.n_head, self.d_state, self.d_state,
                   self.kv_rank, self.d_rope, len(self.experts_held),
                   self.n_expert, self.n_group, self.top_k, self.d_expert,
                   self.dtype))


def _init_layer(cfg: Ling3FlashConfig, key, kind: str, dense: bool) -> Dict:
    d, h = cfg.d_model, cfg.n_head
    k = jax.random.split(key, 16)

    def nrm(kk, shape, std=0.02):
        # drawn in the served type: no float32 copy of a 10 GB tree
        return std * jax.random.normal(kk, shape, cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    lp = {"g1": ones(d), "g2": ones(d), "wgam": nrm(k[0], (d, h))}
    if kind == KDA:
        c = h * cfg.d_state
        # half-lives log-uniform over cfg.half_life at a zero
        # pre-activation: with A_log = 0 the gate is lower_bound x
        # sigmoid(dt_bias) there, which is -ln 2 / half-life at this bias.
        # Kept float32, as the gate's argument is computed
        lo, hi = cfg.half_life
        life = lo * (hi / lo) ** jax.random.uniform(k[1], (c,), jnp.float32)
        p = math.log(2.0) / (-cfg.lower_bound * life)
        lp.update(wqkv=nrm(k[2], (d, 3 * c)),
                  cw=nrm(k[3], (cfg.conv_taps, 3 * c), 0.5),
                  wa=nrm(k[4], (d, c)), wb=nrm(k[5], (d, h)),
                  a_log=jnp.zeros((h,), jnp.float32),
                  dt_bias=jnp.log(p) - jnp.log1p(-p), gn=ones(c),
                  wo=nrm(k[6], (c, d)))
    else:
        lp.update(gkv=ones(cfg.kv_rank),
                  wq=nrm(k[2], (d, h * cfg.d_head)),
                  wkva=nrm(k[3], (d, cfg.kv_rank + cfg.d_rope)),
                  wkvb=nrm(k[4], (cfg.kv_rank, h * (cfg.d_nope + cfg.d_v))),
                  wo=nrm(k[5], (h * cfg.d_v, d)))
    if dense:
        f = cfg.d_dense
        lp.update(wg=nrm(k[7], (d, f)), wu=nrm(k[8], (d, f)),
                  wd=nrm(k[9], (f, d)))
        return lp
    e, f = len(cfg.experts_held), cfg.d_expert
    lp.update(wr=nrm(k[7], (d, cfg.n_expert)),
              br=nrm(k[8], (cfg.n_expert,), cfg.bias_std),
              wg=nrm(k[9], (e, d, f)), wu=nrm(k[10], (e, d, f)),
              wd=nrm(k[11], (e, f, d)), sg=nrm(k[12], (d, f)),
              su=nrm(k[13], (d, f)), sd=nrm(k[14], (f, d)))
    return lp


def init_params(cfg: Ling3FlashConfig, seed) -> Dict:
    """Seeded random weights (``blocks.seeded_params``). The convolution's
    taps are drawn at 0.5 (four of them over inputs of deviation 1: an
    output of deviation 1, as a trained short convolution gives), the
    selection bias at ``cfg.bias_std`` (``models/kimi_k2.py`` says why),
    and ``dt_bias`` so that the channels' half-lives spread log-uniformly
    over ``cfg.half_life`` tokens at a zero pre-activation: a state that
    holds something of a long context, so that a comparison can see an
    error in it."""
    return seeded_params(
        cfg, seed, _init_layer,
        lambda i: (cfg.layer_types[i], i in cfg.dense_layers))


def _mla_prefill(cfg, lp, h, pos):
    """One sequence's MLA half, K and V EXPANDED from the latent: ``(y [S,
    d], row [S, rank + rope])``."""
    s = h.shape[0]
    q_n, q_r, row = latent(cfg, lp, h, pos)
    kv = (row[..., :cfg.kv_rank] @ lp["wkvb"]).reshape(
        s, cfg.n_head, cfg.d_nope + cfg.d_v)
    o = attention_ops.mla_causal_attention(
        jnp.concatenate([q_n, q_r], axis=-1), kv[..., :cfg.d_nope],
        row[:, cfg.kv_rank:], kv[..., cfg.d_nope:], cfg.sm_scale)
    return gated(lp, h, o) @ lp["wo"], row


def prefill_forward(params: Dict, cfg: Ling3FlashConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d] before the final norm, kept)`` with ``kept`` a layer
    what the cache's ``write_prompt`` takes: ``(row,)`` [B, S, rank + rope]
    of an MLA layer, ``(state [B, H, dk, dv], tail [B, taps - 1, 3C])`` of
    a KDA layer."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    pos = jnp.arange(s)
    valid = (pos[None] < lengths[:, None]).reshape(b * s)
    kept = []
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        if kind == KDA:
            with jax.named_scope("attn/kda"):
                ys, *keep = zip(*(kda_prefill(cfg, lp, h[j], lengths[j])
                                  for j in range(b)))
        else:
            ys, *keep = zip(*(_mla_prefill(cfg, lp, h[j], pos)
                              for j in range(b)))
        kept.append(tuple(jnp.stack(t) for t in keep))
        x = x + jnp.stack(ys)
        x, _ = routed_feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, kept


def decode_forward(params: Dict, cfg: Ling3FlashConfig, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``: a KDA layer
    advances the slot's convolution tail and state, an MLA layer writes
    its row and attends ABSORBED. Returns ``(logits [B, V], cache,
    stats)``: ``models/kimi_k2.py``'s three ``moe_*`` an EXPERT layer,
    ``state_slots_stepped`` (the live slots, whose states every KDA layer
    advanced) and the cache's ``attn_rows_read.latent``."""
    x = params["tok_emb"][tokens]
    stats = []
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.layer_types)):
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        if kind == KDA:
            with jax.named_scope("attn/kda"):
                window, cache = cache_ops.tail_step(cache, i, h @ lp["wqkv"],
                                                    active)
                o, cache = cache_ops.state_step(
                    cache, i, *kda_inputs(
                        cfg, lp, h,
                        [window[:, j] for j in range(cfg.conv_taps)]),
                    active)
                x = x + kda_output(cfg, lp, h, o)
        else:
            q_n, q_r, row = latent(cfg, lp, h, pos)
            cache = cache_ops.write_token(cache, i, row, pos, active)
            with jax.named_scope("attn/mla"):
                o_lat = cache_ops.decode_attention(
                    cache, i, absorbed_query(cfg, lp["wkvb"], q_n, q_r),
                    pos + 1, active, sm_scale=cfg.sm_scale)
                a = absorbed_output(cfg, lp["wkvb"], o_lat)
                x = x + gated(lp, h, a.reshape(-1, cfg.n_head, cfg.d_v)
                               ) @ lp["wo"]
        x, st = routed_feed_forward(cfg, lp, x, active)
        if st is not None:
            stats.append(st)
    return head(params, cfg, x), cache, {
        **moe_stats(stats),
        "state_slots_stepped": jnp.sum(active).astype(jnp.int32),
        **cache_ops.rows_read(pos + 1, active)}


class Ling3FlashLM(ServedLM):
    """The serving contract over :class:`Ling3FlashConfig`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

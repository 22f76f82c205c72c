"""GLM-5.3-Flash's language model as pure JAX functions under the serving
contract (``models.blocks.ServedLM``), so the same ``ServingEngine``,
scheduler and page pool serve it. The plain float32 statement of the same
equations, which the tests and the benchmark compare this with, is
``grid/reference/glm5_flash.py``; read the layers there.

Three layers in four are Kimi Delta Attention (``blocks.kda_*``: Ling-3's
layer at 64 heads, the decay's projection through a low rank), each fourth
is latent attention WITHOUT rotary lanes under a learned INDEXER
(``deepseek_sparse_attention``), and the layer loop carries four residual
streams (``blocks.mix_in``/``mix_out``: Motif-3's). What is particular to
serving it:

* a query reads ``index_topk`` = 2,048 of its context's latent rows: its
  own block of ``index_kpool`` = 4 rows, always, and the 511 CLOSED blocks
  whose pooled index key scores highest against the query's 32 index heads
  (``ops.attention_ops.dsa_index_scores``/``dsa_select``). The cache keeps
  one pooled index key a block beside the latent pages, through the same
  page table, and the raw keys of the block still open a slot
  (``serving.kv_cache.LatentPagedCache(index=)``);
* DECODE scores the slot's closed blocks, chooses, and attends ABSORBED
  over the chosen blocks only (``cache_ops.sparse_decode_attention``: the
  latent kernel over a second, shorter table a step, under the name
  ``dsa_sparse_decode``); what a step costs no longer grows with the
  context, but for the index scores (one 128-lane key a block of four
  512-lane rows);
* PREFILL expands K and V and attends under a mask that is each ROW's own
  (``ops.attention_ops.dsa_causal_attention``: a selection depends on its
  query), by blocks of query rows up to the prompt's end (a block past it
  comes back as zeros), and hands the cache the rows, the pooled keys and
  the open block's keys;
* position reaches the latent layer through the KDA layers and the
  indexer only (``qk_rope_head_dim`` 0): the indexer's first 64 lanes are
  rotated, pairs interleaved;
* the routed experts may be a SHARE (``cfg.experts_held``); every SwiGLU
  clamps its pre-activations (``swiglu_limit``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops, moe_ops
from ..ops.pallas_kernels import kda as kda_ops
from ..serving.kv_cache import LATENT, STATE
from .blocks import (ServedLM, absorbed_output, absorbed_query, at_precision,
                     head, held_experts, kda_inputs, kda_output, kda_prefill,
                     mix_in, mix_out, moe_stats, rms_norm, seeded_params)

__all__ = ["Glm5FlashConfig", "Glm5FlashLM", "init_params", "index_rope"]

KDA, DSA = "linear_attention", "deepseek_sparse_attention"   # layer_types


def _clamped_silu(limit: float, gate):
    """SwiGLU's gate under ``swiglu_limit``: the pre-activation clamped
    above."""
    return jax.nn.silu(jnp.minimum(gate, limit))


@functools.lru_cache(maxsize=None)
def _activation(limit: float):
    """One object a configuration, so that jitted callers trace once."""
    return functools.partial(_clamped_silu, limit)


class Glm5FlashConfig:
    """Static hyperparameters, under this package's names. ``layer_types``
    gives each layer's attention (``KDA`` or ``DSA``); the layers in
    ``dense_layers`` have a dense SwiGLU of ``d_dense``, every other one
    routes ``top_k`` of ``n_expert`` experts of ``d_expert`` and adds one
    shared expert of the same width. ``n_head`` heads in both kinds of
    layer; ``d_state`` is a KDA head's ``dk = dv``; a DSA head has
    ``d_nope`` query and key lanes and ``d_v`` value lanes over a latent
    row of ``kv_rank`` lanes and NO rotary lane. The indexer:
    ``index_heads`` of ``index_dim`` lanes, the first ``index_rope`` of
    them rotated at ``index_theta``; ``index_topk`` rows a query, in blocks
    of ``index_kpool``. ``maps_dtype`` (the residual maps), ``row_dtype``
    and ``index_dtype`` (what a latent row and an index key are rounded to
    before they are kept) are float32 / the served type as the
    configuration states; a lower one is the control the cell's comparison
    has to fail (``benchmarks/control_glm5_flash.py``)."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, d_state: int, layer_types: Sequence[str],
                 q_rank: int, kv_rank: int, d_nope: int, d_v: int,
                 index_heads: int, index_dim: int, index_topk: int,
                 index_kpool: int, d_dense: int, dense_layers: Sequence[int],
                 n_expert: int, top_k: int, d_expert: int,
                 routed_scale: float = 1.0, swiglu_limit: float = 10.0,
                 index_rope: int = 64, index_theta: float = 8e6,
                 decay_rank: int = 128, conv_taps: int = 4,
                 lower_bound: float = -5.0, n_stream: int = 4,
                 sinkhorn_iters: int = 20, sinkhorn_eps: float = 1e-6,
                 rms_eps: float = 1e-5, max_seq: int = 16384,
                 dtype="float32", experts_held: Optional[Sequence[int]] = None,
                 bias_std: float = 0.001,
                 half_life: Tuple[float, float] = (4.0, 4096.0),
                 score_std: float = 0.05, maps_dtype="float32",
                 row_dtype=None, index_dtype=None):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.d_state = int(d_state)
        self.layer_types = tuple(layer_types)
        if len(self.layer_types) != self.n_layer \
                or set(self.layer_types) - {KDA, DSA}:
            raise ValueError("layer_types names %d layers of %s; the model "
                             "has %d of %s" % (len(self.layer_types),
                                               sorted(set(self.layer_types)),
                                               self.n_layer, (KDA, DSA)))
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.d_nope, self.d_v = int(d_nope), int(d_v)
        self.d_head = self.d_nope            # a DSA query's lanes: no rotary
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.index_topk, self.index_kpool = int(index_topk), int(index_kpool)
        if self.index_topk % self.index_kpool:
            raise ValueError("index_topk=%d rows are not whole blocks of %d"
                             % (self.index_topk, self.index_kpool))
        self.index_rope = int(index_rope)
        self.index_inv_freq = float(index_theta) ** (
            -jnp.arange(self.index_rope // 2, dtype=jnp.float32) * 2.0
            / self.index_rope)
        self.index_scale = self.index_heads ** -0.5 * self.index_dim ** -0.5
        self.decay_rank = int(decay_rank)
        self.d_dense = int(d_dense)
        self.dense_layers = tuple(int(i) for i in dense_layers)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.d_expert = int(d_expert)
        self.routed_scale = float(routed_scale)
        self.activation = _activation(float(swiglu_limit))
        self.swiglu_limit = float(swiglu_limit)
        self.conv_taps = int(conv_taps)
        self.lower_bound = float(lower_bound)
        if not kda_ops.LOWER_BOUND <= self.lower_bound < 0:
            raise ValueError("the chunk scan's exponents are safe for a "
                             "log-decay above %g a step; lower_bound=%g"
                             % (kda_ops.LOWER_BOUND, self.lower_bound))
        self.n_stream = int(n_stream)
        self.sinkhorn_iters = int(sinkhorn_iters)
        self.sinkhorn_eps = float(sinkhorn_eps)
        self.hidden_clamp = None             # no clamp is published
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.maps_dtype = jnp.dtype(maps_dtype)
        self.row_dtype = jnp.dtype(row_dtype or dtype)
        self.index_dtype = jnp.dtype(index_dtype or dtype)
        self.bias_std = float(bias_std)
        self.half_life = (float(half_life[0]), float(half_life[1]))
        self.score_std = float(score_std)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        self.sm_scale = self.d_head ** -0.5

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def latent_row(self) -> Tuple[int, int]:
        """``(rank, rope)`` of the row a DSA layer keeps a token: no
        rotary lane."""
        return self.kv_rank, 0

    @property
    def index_row(self) -> Tuple[int, int, int]:
        """``(rows a block, lanes of an index key, blocks a query reads)``."""
        return (self.index_kpool, self.index_dim,
                self.index_topk // self.index_kpool)

    @property
    def slot_state(self) -> Tuple[int, int, int, int, int]:
        """What a KDA layer keeps a SLOT: ``(heads, dk, dv, tail rows,
        tail width)``."""
        return (self.n_head, self.d_state, self.d_state, self.conv_taps - 1,
                3 * self.n_head * self.d_state)

    @property
    def cache_groups(self):
        """The latent group (pages, the index beside them, admission)
        first, the state group (bound to the slot) after it."""
        return [("latent_sparse", self.layers_of(DSA), None, LATENT),
                ("state", self.layers_of(KDA), None, STATE)]

    def __repr__(self):
        return ("Glm5FlashConfig(V=%d, L=%d (%d KDA, %d DSA, %d dense), "
                "d=%d x %d streams, H=%d, state %dx%d, latent %d, index "
                "%dx%d top %d by %d, E=%d of %d held, top-%d, experts of %d, %s)"
                % (self.vocab_size, self.n_layer, len(self.layers_of(KDA)),
                   len(self.layers_of(DSA)), len(self.dense_layers),
                   self.d_model, self.n_stream, self.n_head, self.d_state,
                   self.d_state, self.kv_rank, self.index_heads,
                   self.index_dim, self.index_topk, self.index_kpool,
                   len(self.experts_held), self.n_expert, self.top_k,
                   self.d_expert, self.dtype))


def _init_layer(cfg: Glm5FlashConfig, key, kind: str, dense: bool) -> Dict:
    d, h, n = cfg.d_model, cfg.n_head, cfg.n_stream
    k = jax.random.split(key, 28)
    f32 = jnp.float32

    def nrm(kk, shape, std=0.02):
        # drawn in the served type: no float32 copy of a 9 GB tree
        return std * jax.random.normal(kk, shape, cfg.dtype)

    def ones(m):
        return jnp.ones((m,), cfg.dtype)

    def maps(kp, kb):
        # Motif-3's seeds: alpha 0.1; b_pre, b_post 0; b_res normal(0, 1)
        bias = jnp.concatenate([jnp.zeros((2 * n,), f32),
                                jax.random.normal(kb, (n * n,), f32)])
        return (nrm(kp, (n * d, 2 * n + n * n)), jnp.full((3,), 0.1, f32),
                bias)

    pa, aa, ba = maps(k[0], k[1])
    pm, am, bm = maps(k[2], k[3])
    lp = {"pa": pa, "aa": aa, "ba": ba, "pm": pm, "am": am, "bm": bm,
          "g1": ones(d), "g2": ones(d)}
    if kind == KDA:
        c = h * cfg.d_state
        # Ling-3's seeds: half-lives log-uniform over cfg.half_life at a
        # zero pre-activation, kept float32 as the gate's argument is
        lo, hi = cfg.half_life
        life = lo * (hi / lo) ** jax.random.uniform(k[4], (c,), f32)
        p = math.log(2.0) / (-cfg.lower_bound * life)
        lp.update(wgam=nrm(k[5], (d, h)), wqkv=nrm(k[6], (d, 3 * c)),
                  cw=nrm(k[7], (cfg.conv_taps, 3 * c), 0.5),
                  wa1=nrm(k[8], (d, cfg.decay_rank)),
                  wa2=nrm(k[9], (cfg.decay_rank, c)),
                  wb=nrm(k[10], (d, h)), a_log=jnp.zeros((h,), f32),
                  dt_bias=jnp.log(p) - jnp.log1p(-p), gn=ones(c),
                  wo=nrm(k[11], (c, d)))
    else:
        hi_, li_ = cfg.index_heads, cfg.index_dim
        # the query's and the key's up-projections at cfg.score_std: a
        # score's deviation is near 2, so that attention has rows it
        # prefers and a selection that drops them shows
        kv = jax.random.normal(k[12], (cfg.kv_rank, h, cfg.d_nope + cfg.d_v),
                               cfg.dtype)
        std = jnp.concatenate([jnp.full((cfg.d_nope,), cfg.score_std, f32),
                               jnp.full((cfg.d_v,), 0.02, f32)])
        lp.update(gq=ones(cfg.q_rank), gkv=ones(cfg.kv_rank),
                  wqa=nrm(k[13], (d, cfg.q_rank)),
                  wqb=nrm(k[14], (cfg.q_rank, h * cfg.d_nope),
                          cfg.score_std),
                  wkva=nrm(k[15], (d, cfg.kv_rank)),
                  wkvb=(kv * std.astype(cfg.dtype)).reshape(cfg.kv_rank, -1),
                  wo=nrm(k[16], (h * cfg.d_v, d)),
                  wiq=nrm(k[17], (cfg.q_rank, hi_ * li_)),
                  wik=nrm(k[18], (d, li_)), wiw=nrm(k[19], (d, hi_)),
                  gik=ones(li_), bik=jnp.zeros((li_,), cfg.dtype))
    if dense:
        f = cfg.d_dense
        lp.update(wg=nrm(k[20], (d, f)), wu=nrm(k[21], (d, f)),
                  wd=nrm(k[22], (f, d)))
        return lp
    e, f = len(cfg.experts_held), cfg.d_expert
    lp.update(wr=nrm(k[20], (d, cfg.n_expert)),
              br=nrm(k[21], (cfg.n_expert,), cfg.bias_std),
              wg=nrm(k[22], (e, d, f)), wu=nrm(k[23], (e, d, f)),
              wd=nrm(k[24], (e, f, d)), sg=nrm(k[25], (d, f)),
              su=nrm(k[26], (d, f)), sd=nrm(k[27], (f, d)))
    return lp


def init_params(cfg: Glm5FlashConfig, seed) -> Dict:
    """Seeded random weights (``blocks.seeded_params``): Ling-3's seeds in
    a KDA layer (taps at 0.5, half-lives spread over ``cfg.half_life``),
    Motif-3's in the residual maps (alpha 0.1, ``b_res`` normal(0, 1)), the
    selection bias at ``cfg.bias_std``, and the DSA layer's query and key
    up-projections at ``cfg.score_std``: at 0.02 every attention weight is
    nearly equal and a wrong selection reads like a right one."""
    return seeded_params(
        cfg, seed, _init_layer,
        lambda i: (cfg.layer_types[i], i in cfg.dense_layers))


def index_rope(x, pos, inv_freq):
    """The indexer's rotation: the first ``2 len(inv_freq)`` lanes of ``x``
    [..., D] turned at ``pos`` (the leading axes of ``x``; axes between
    them and the last turn alike), pairs INTERLEAVED (lanes 2i and 2i + 1
    are pair i); the other lanes pass."""
    half = inv_freq.shape[0]
    ang = pos.astype(jnp.float32).reshape(
        pos.shape + (1,) * (x.ndim - pos.ndim)) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x[..., :2 * half].astype(jnp.float32).reshape(
        x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (2 * half,)).astype(x.dtype),
         x[..., 2 * half:]], axis=-1)


def _dsa_inputs(cfg, lp, h, pos):
    """What the sparse latent layer reads of the normed input ``h`` [...,
    d] at ``pos`` [...]: the heads' queries ``q`` [..., H, nope], the
    cache row ``c`` [..., rank], the index queries [..., Hi, L], their
    weights [..., Hi] float32 and the position's index key [..., L]."""
    f32 = jnp.float32
    q_lat = rms_norm(h @ lp["wqa"], lp["gq"], cfg.rms_eps)
    q = (q_lat @ lp["wqb"]).reshape(h.shape[:-1] + (cfg.n_head, cfg.d_nope))
    c = at_precision(rms_norm(h @ lp["wkva"], lp["gkv"], cfg.rms_eps),
                   cfg.row_dtype)
    with jax.named_scope("attn/dsa_index"):
        q_idx = index_rope(
            (q_lat @ lp["wiq"]).reshape(
                h.shape[:-1] + (cfg.index_heads, cfg.index_dim)),
            pos, cfg.index_inv_freq)
        w_idx = jnp.dot(h, lp["wiw"], preferred_element_type=f32) \
            * cfg.index_scale
        k = jnp.dot(h, lp["wik"], preferred_element_type=f32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + 1e-6)
        k = (k * lp["gik"].astype(f32) + lp["bik"].astype(f32)
             ).astype(h.dtype)
        k_idx = at_precision(index_rope(k, pos, cfg.index_inv_freq),
                           cfg.index_dtype)
    return q, c, q_idx, w_idx, k_idx


def _pooled_keys(cfg, k_idx):
    """One index key a block of ``index_kpool`` rows of ``k_idx`` [S, L]:
    the mean of the block's keys, in float32, kept at the index's
    precision."""
    s, kpool = k_idx.shape[0], cfg.index_kpool
    pooled = jnp.mean(k_idx.astype(jnp.float32).reshape(
        s // kpool, kpool, -1), axis=1)
    return at_precision(pooled.astype(k_idx.dtype), cfg.index_dtype)


def _dsa_prefill(cfg, lp, h, pos, length):
    """One sequence's sparse latent half, K and V EXPANDED from the latent:
    ``(y [S, d], row [S, rank], pooled index keys [S / kpool, L], the open
    block's keys [kpool - 1, L])``."""
    s = h.shape[0]
    kpool = cfg.index_kpool
    q, c, q_idx, w_idx, k_idx = _dsa_inputs(cfg, lp, h, pos)
    kv = (c @ lp["wkvb"]).reshape(s, cfg.n_head, cfg.d_nope + cfg.d_v)
    pooled = _pooled_keys(cfg, k_idx)
    o = attention_ops.dsa_causal_attention(
        q, kv[..., :cfg.d_nope], kv[..., cfg.d_nope:], q_idx, w_idx, pooled,
        kpool, cfg.index_topk // kpool, cfg.sm_scale, length=length)
    tail = jax.lax.dynamic_slice_in_dim(
        jnp.pad(k_idx, ((0, kpool - 1), (0, 0))), length // kpool * kpool,
        kpool - 1, axis=0)
    return o.reshape(s, -1) @ lp["wo"], c, pooled, tail


def _mlp(cfg, u, wg, wu, wd):
    """SwiGLU under ``swiglu_limit``: the gate's pre-activation clamped
    above, the up projection's on both sides (the dense MLP and the shared
    expert; a routed expert's activation sees its gate only, and
    ``expert_layer`` clamps that)."""
    lim = cfg.swiglu_limit
    up = jnp.clip(u @ wu, -lim, lim)
    return (cfg.activation(u @ wg) * up) @ wd


def _feed_forward(cfg, lp, u, row_valid):
    """The layer's second half over normed rows ``u`` [N, d]: the dense
    SwiGLU, or the sigmoid-routed experts held here plus the shared
    expert. Returns ``(y [N, d], stats or None)``."""
    if "wr" not in lp:
        return _mlp(cfg, u, lp["wg"], lp["wu"], lp["wd"]), None
    idx, w = moe_ops.route_sigmoid_topk(u, lp["wr"], lp["br"], cfg.top_k,
                                        cfg.routed_scale)
    y, stats = moe_ops.expert_layer(
        u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=held_experts(cfg), row_valid=row_valid,
        activation=cfg.activation)
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    with jax.named_scope("moe/shared"):
        shared = _mlp(cfg, u, lp["sg"], lp["su"], lp["sd"])
    return (y + shared.astype(jnp.float32)).astype(u.dtype), stats


def _streams(params, cfg, tokens):
    """The embedding copied into the streams: ``[n, ..., d]``."""
    x = params["tok_emb"][tokens]
    return jnp.broadcast_to(x[None], (cfg.n_stream,) + x.shape)


def _streams_sum(x):
    return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)


def prefill_forward(params: Dict, cfg: Glm5FlashConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d], the streams' sum before the final norm, kept)`` with
    ``kept`` a layer what the cache's ``write_prompt`` takes: ``(state [B,
    H, dk, dv], tail [B, taps - 1, 3C])`` of a KDA layer, ``(row [B, S,
    rank], pooled keys [B, S / kpool, L], open block's keys [B, kpool - 1,
    L])`` of a DSA layer."""
    b, s = tokens.shape
    x = _streams(params, cfg, tokens)
    pos = jnp.arange(s)
    valid = (pos[None] < lengths[:, None]).reshape(b * s)
    kept = []
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h, h_post, h_res = mix_in(cfg, lp, "a", x, lp["g1"])
        if kind == KDA:
            with jax.named_scope("attn/kda"):
                ys, *keep = zip(*(kda_prefill(cfg, lp, h[j], lengths[j])
                                  for j in range(b)))
        else:
            ys, *keep = zip(*(_dsa_prefill(cfg, lp, h[j], pos, lengths[j])
                              for j in range(b)))
        kept.append(tuple(jnp.stack(t) for t in keep))
        x = mix_out(cfg, x, jnp.stack(ys), h_post, h_res)
        u, h_post, h_res = mix_in(cfg, lp, "m", x, lp["g2"])
        y, _ = _feed_forward(cfg, lp, u.reshape(b * s, -1), valid)
        x = mix_out(cfg, x, y.reshape(b, s, -1), h_post, h_res)
    return _streams_sum(x), kept


def decode_forward(params: Dict, cfg: Glm5FlashConfig, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``: a KDA layer
    advances the slot's convolution tail and state; a DSA layer writes its
    row and its index key, scores the slot's closed blocks, chooses, and
    attends ABSORBED over the chosen blocks. Returns ``(logits [B, V],
    cache, stats)``: ``models/kimi_k2.py``'s three ``moe_*`` an EXPERT
    layer, ``state_slots_stepped``, and of the FIRST DSA layer
    ``attn_rows_read.latent_sparse`` (the rows it read, over the live
    slots), ``attn_rows_context.latent_sparse`` (the same slots' whole
    contexts), ``index_blocks_scored`` and ``dsa_probe`` [1 + top blocks]
    int32: slot 0's position (-1 where it holds no request) and the closed
    blocks it chose, ascending, -1 where there were fewer."""
    x = _streams(params, cfg, tokens)
    stats, dsa = [], None
    kpool, top_blocks = cfg.index_kpool, cfg.index_topk // cfg.index_kpool
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.layer_types)):
        h, h_post, h_res = mix_in(cfg, lp, "a", x, lp["g1"])
        if kind == KDA:
            with jax.named_scope("attn/kda"):
                window, cache = cache_ops.tail_step(cache, i, h @ lp["wqkv"],
                                                    active)
                o, cache = cache_ops.state_step(
                    cache, i, *kda_inputs(
                        cfg, lp, h,
                        [window[:, j] for j in range(cfg.conv_taps)]),
                    active)
                y = kda_output(cfg, lp, h, o)
        else:
            q, c, q_idx, w_idx, k_idx = _dsa_inputs(cfg, lp, h, pos)
            cache = cache_ops.write_token(cache, i, c, pos, active)
            with jax.named_scope("attn/dsa_index"):
                cache = cache_ops.write_index(cache, i, k_idx, pos, active)
                scores, closed = cache_ops.index_scores(
                    cache, i, q_idx, w_idx, pos + 1, active)
            chosen, picked = attention_ops.dsa_select(scores, pos // kpool,
                                                      top_blocks)
            q_abs = absorbed_query(cfg, lp["wkvb"], q, q[..., :0])
            with jax.named_scope("attn/dsa_sparse"):
                o_lat, read = cache_ops.sparse_decode_attention(
                    cache, i, q_abs, chosen, pos + 1, active,
                    sm_scale=cfg.sm_scale)
            y = absorbed_output(cfg, lp["wkvb"], o_lat) @ lp["wo"]
            if dsa is None:
                live = jnp.where(active, pos + 1, 0)
                dsa = {
                    "attn_rows_read.latent_sparse": jnp.sum(read),
                    "attn_rows_context.latent_sparse":
                        jnp.sum(live).astype(jnp.int32),
                    "index_blocks_scored": jnp.sum(closed).astype(jnp.int32),
                    "dsa_probe": jnp.concatenate([
                        jnp.where(active[:1], pos[:1], -1).astype(jnp.int32),
                        picked[0]])}
        x = mix_out(cfg, x, y, h_post, h_res)
        u, h_post, h_res = mix_in(cfg, lp, "m", x, lp["g2"])
        y, st = _feed_forward(cfg, lp, u, active)
        x = mix_out(cfg, x, y, h_post, h_res)
        if st is not None:
            stats.append(st)
    return head(params, cfg, _streams_sum(x)), cache, {
        **moe_stats(stats),
        "state_slots_stepped": jnp.sum(active).astype(jnp.int32), **dsa}


class Glm5FlashLM(ServedLM):
    """The serving contract over :class:`Glm5FlashConfig` (text only; the
    published multi-token-prediction layer is not served)."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

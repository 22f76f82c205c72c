"""DeepSeek-V3.2's language model as pure JAX functions under the serving
contract (``models.blocks.ServedLM``), so the same ``ServingEngine``,
scheduler and page pool serve it. The plain float32 statement of the same
equations, which the tests and the benchmark compare this with, is
``grid/reference/deepseek_v32.py``; read the layer there.

The block is the DeepSeek-V3 block (``models/kimi_k2.py``: latent attention
with rotary lanes, a sigmoid-routed expert layer with a shared expert; the
latent projection, the absorbed pair and the feed-forward half are
``models/blocks.py``'s) with, in EVERY layer, a lightning INDEXER that
chooses the ``index_topk`` = 2,048 single ROWS a query reads. What is
particular to serving it:

* the cache keeps, a token a layer, the latent row ``[c | kr']`` AND one
  index key of ``index_dim`` lanes, in a second pool through the same page
  table (``serving.kv_cache.LatentPagedCache(index=)`` with blocks of 1:
  no pooled key, no open block);
* DECODE writes the row and the key, scores the slot's whole context (64
  index heads against a key a row: ``cache_ops.index_scores``), chooses
  exactly the reference's set without a sort
  (``ops.attention_ops.dsa_select_rows``: the 2,048th score by bisection)
  and attends ABSORBED over the chosen rows only
  (``cache_ops.rows_decode_attention``: the latent kernel's wave under the
  choice as a row mask on the chip, a gather of the chosen rows off it);
  no row is forced in, and a context of at most 2,048 rows is read whole;
* PREFILL expands K and V and attends under a mask that is each ROW's own
  (``ops.attention_ops.dsa_rows_causal_attention``: row t's 2,048 of its
  prefix AND the causal triangle; told the prompt's length, so that a
  query block past it is neither scored, chosen nor attended and one under
  ``index_topk`` reads its prefix with nothing scored), and hands the cache
  the rows and the index keys;
* the indexer's first ``d_rope`` lanes are rotated at the row's position,
  rotate-half, at the attention's own frequencies (the reference says how
  that relates to the family's code);
* the router is GROUP-LIMITED (``n_group`` 8, ``topk_group`` 4:
  ``ops.moe_ops.route_sigmoid_topk`` through ``blocks.routed_feed_forward``)
  and the routed experts may be a SHARE (``cfg.experts_held``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops
from ..serving.kv_cache import LATENT
from .blocks import (ServedLM, absorbed_output, absorbed_query, at_precision,
                     head, latent, mla_softmax_scale, moe_stats, rms_norm,
                     rope, routed_feed_forward, seeded_params, yarn_inv_freq)

__all__ = ["DeepSeekV32Config", "DeepSeekV32LM", "init_params"]


class DeepSeekV32Config:
    """Static hyperparameters, under this package's names
    (``kimi_k2.KimiK2Config``'s, and the indexer's). ``n_dense`` is the
    leading layers with a dense SwiGLU of ``d_dense``; every later layer
    routes ``top_k`` of ``n_expert`` experts of ``d_expert`` under the
    group limit and adds one shared expert. The indexer: ``index_heads`` of
    ``index_dim`` lanes, the first ``d_rope`` of them rotated;
    ``index_topk`` rows a query. ``row_dtype`` (what a latent row is
    rounded to before it is kept) and ``score_dtype`` (what the index
    products and their weighted sum are rounded to) are the served type and
    float32 as the configuration states; a lower one is the control the
    cell's comparison has to fail (``benchmarks/control_deepseek_v32.py``)."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, q_rank: int, kv_rank: int, d_nope: int,
                 d_rope: int, d_v: int, index_heads: int, index_dim: int,
                 index_topk: int, d_dense: int, n_dense: int, n_expert: int,
                 top_k: int, d_expert: int, n_group: int = 1,
                 topk_group: int = 1, routed_scale: float = 1.0,
                 rope_theta: float = 1e4,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 rms_eps: float = 1e-6, max_seq: int = 16384,
                 dtype="float32",
                 experts_held: Optional[Sequence[int]] = None,
                 bias_std: float = 0.001, score_std: float = 0.05,
                 row_dtype=None, score_dtype="float32"):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = int(d_nope), int(d_rope), int(d_v)
        self.d_head = self.d_nope + self.d_rope      # a query's lanes
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.index_topk = int(index_topk)
        if self.d_rope > self.index_dim:
            raise ValueError("the indexer rotates %d lanes of %d"
                             % (self.d_rope, self.index_dim))
        self.index_scale = self.index_heads ** -0.5 * self.index_dim ** -0.5
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
        self.row_dtype = jnp.dtype(row_dtype or dtype)
        self.score_dtype = jnp.dtype(score_dtype)
        self.bias_std = float(bias_std)
        self.score_std = float(score_std)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        self.inv_freq = yarn_inv_freq(self.d_rope, self.rope_theta,
                                      self.rope_scaling)
        self.sm_scale = mla_softmax_scale(self.d_head, self.rope_scaling)

    @property
    def latent_row(self) -> Tuple[int, int]:
        """``(rank, rope)`` of the row a token a layer keeps."""
        return self.kv_rank, self.d_rope

    @property
    def index_row(self) -> Tuple[int, int, int]:
        """``(rows a block, lanes of an index key, blocks a query reads)``:
        a key a ROW, ``index_topk`` rows a query."""
        return 1, self.index_dim, self.index_topk

    @property
    def cache_groups(self):
        """ONE latent group of every layer: pages, the index keys beside
        them, admission."""
        return [("latent_sparse", tuple(range(self.n_layer)), None, LATENT)]

    def __repr__(self):
        return ("DeepSeekV32Config(V=%d, L=%d (%d dense), d=%d, H=%d, "
                "q_rank=%d, latent %d+%d, index %dx%d top %d, E=%d of %d "
                "held, top-%d of %d in %d of %d groups, %s)"
                % (self.vocab_size, self.n_layer, self.n_dense, self.d_model,
                   self.n_head, self.q_rank, self.kv_rank, self.d_rope,
                   self.index_heads, self.index_dim, self.index_topk,
                   len(self.experts_held), self.n_expert, self.top_k,
                   self.d_expert, self.topk_group, self.n_group, self.dtype))


def _init_layer(cfg: DeepSeekV32Config, key, dense: bool) -> Dict:
    d, h = cfg.d_model, cfg.n_head
    hi, li = cfg.index_heads, cfg.index_dim
    k = jax.random.split(key, 16)

    def nrm(kk, shape, std=0.02):
        # drawn in the served type: no float32 copy of a 9 GB tree
        return std * jax.random.normal(kk, shape, cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    # the query's up-projection and the key half of the latent's at
    # cfg.score_std: attention then has rows it prefers, and a selection
    # that drops them shows
    kv = jax.random.normal(k[3], (cfg.kv_rank, h, cfg.d_nope + cfg.d_v),
                           cfg.dtype)
    std = jnp.concatenate([jnp.full((cfg.d_nope,), cfg.score_std),
                           jnp.full((cfg.d_v,), 0.02)]).astype(cfg.dtype)
    lp = {"g1": ones(d), "g2": ones(d), "gq": ones(cfg.q_rank),
          "gkv": ones(cfg.kv_rank),
          "wqa": nrm(k[0], (d, cfg.q_rank)),
          "wqb": nrm(k[1], (cfg.q_rank, h * cfg.d_head), cfg.score_std),
          "wkva": nrm(k[2], (d, cfg.kv_rank + cfg.d_rope)),
          "wkvb": (kv * std).reshape(cfg.kv_rank, -1),
          "wo": nrm(k[4], (h * cfg.d_v, d)),
          "wiq": nrm(k[13], (cfg.q_rank, hi * li)),
          "wik": nrm(k[14], (d, li)), "wiw": nrm(k[15], (d, hi)),
          "gik": ones(li), "bik": jnp.zeros((li,), cfg.dtype)}
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


def init_params(cfg: DeepSeekV32Config, seed) -> Dict:
    """Seeded random weights (``blocks.seeded_params``): Kimi-K2's seeds,
    the selection bias at ``cfg.bias_std``, the query's up-projection and
    the key half of the latent's at ``cfg.score_std`` (at 0.02 every
    attention weight is nearly equal and a wrong selection reads like a
    right one), the indexer's own weights at 0.02."""
    return seeded_params(cfg, seed, _init_layer,
                         lambda i: (i < cfg.n_dense,))


def _rope_first(cfg, x, pos):
    """The indexer's rotation: the first ``d_rope`` lanes of ``x`` [...,
    L] rotate-half at ``pos`` (the leading axes of ``x``), the rest as they
    are."""
    return jnp.concatenate(
        [rope(x[..., :cfg.d_rope], pos, cfg.inv_freq), x[..., cfg.d_rope:]],
        axis=-1)


def _inputs(cfg, lp, h, pos):
    """What a layer's attention half reads of the normed input ``h`` [...,
    d] at ``pos`` [...]: ``blocks.latent``'s ``(q_nope, q_rope, row)``, the
    row at ``cfg.row_dtype``'s precision, and the index queries [..., Hi,
    L], their weights [..., Hi] float32 and the position's index key [...,
    L]."""
    f32 = jnp.float32
    q_n, q_r, row = latent(cfg, lp, h, pos)
    with jax.named_scope("attn/dsa_index"):
        # the query latent once more: the compiler shares it with latent's
        q_lat = rms_norm(h @ lp["wqa"], lp["gq"], cfg.rms_eps)
        q_idx = _rope_first(cfg, (q_lat @ lp["wiq"]).reshape(
            h.shape[:-1] + (cfg.index_heads, cfg.index_dim)), pos)
        w_idx = jnp.dot(h, lp["wiw"], preferred_element_type=f32) \
            * cfg.index_scale
        k = jnp.dot(h, lp["wik"], preferred_element_type=f32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + 1e-6)
        k = (k * lp["gik"].astype(f32) + lp["bik"].astype(f32)
             ).astype(h.dtype)
        k_idx = _rope_first(cfg, k, pos)
    return q_n, q_r, at_precision(row, cfg.row_dtype), q_idx, w_idx, k_idx


def _probed(chosen, topk: int):
    """The rows ONE slot chose, ascending, -1 where there were fewer:
    ``chosen`` [1, rows] bool -> [topk] int32 (a decode step's probe)."""
    rows, held = attention_ops.dsa_chosen_rows(chosen, topk)
    return jnp.where(held[0], rows[0], -1)


def _attend_prefill(cfg, lp, h, pos, length):
    """One sequence's attention half, K and V EXPANDED from the latent:
    ``(y [S, d], row [S, rank + rope], index keys [S, L])``; ``length`` of
    the S rows are the prompt's, and the query blocks past it are not
    attended (``y`` is zero there)."""
    s = h.shape[0]
    q_n, q_r, row, q_idx, w_idx, k_idx = _inputs(cfg, lp, h, pos)
    kv = (row[:, :cfg.kv_rank] @ lp["wkvb"]).reshape(
        s, cfg.n_head, cfg.d_nope + cfg.d_v)
    k = jnp.concatenate(
        [kv[..., :cfg.d_nope],
         jnp.broadcast_to(row[:, None, cfg.kv_rank:],
                          (s, cfg.n_head, cfg.d_rope))], axis=-1)
    o = attention_ops.dsa_rows_causal_attention(
        jnp.concatenate([q_n, q_r], axis=-1), k, kv[..., cfg.d_nope:],
        q_idx, w_idx, k_idx, cfg.index_topk, cfg.sm_scale,
        score_dtype=cfg.score_dtype, length=length)
    return o.reshape(s, -1) @ lp["wo"], row, k_idx


def prefill_forward(params: Dict, cfg: DeepSeekV32Config, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d] before the final norm, kept)`` with ``kept`` a layer
    ``(row [B, S, rank + rope], index keys [B, S, L])``: what the cache's
    ``write_prompt`` takes. A padding position's latent row and index key
    are still garbage, which ``write_prompt`` places by length and no valid
    row reads (causality); neither the three sparse-attention passes (index
    scores, selection, masked attention: a query block past the prompt's
    end comes out of attention as ZEROS) nor the routed experts compute
    it."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    pos = jnp.arange(s)
    valid = (pos[None] < lengths[:, None]).reshape(b * s)
    kept = []
    for lp in params["layers"]:
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        ys, *keep = zip(*(_attend_prefill(cfg, lp, h[j], pos, lengths[j])
                          for j in range(b)))
        kept.append(tuple(jnp.stack(t) for t in keep))
        x = x + jnp.stack(ys)
        x, _ = routed_feed_forward(cfg, lp, x.reshape(b * s, -1), valid)
        x = x.reshape(b, s, -1)
    return x, kept


def decode_forward(params: Dict, cfg: DeepSeekV32Config, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``: every layer writes
    its row and its index key, scores the slot's context, chooses, and
    attends ABSORBED over the chosen rows. Returns ``(logits [B, V], cache,
    stats)``: ``models/kimi_k2.py``'s three ``moe_*`` an EXPERT layer,
    ``moe_groups_kept_with_held`` (the live rows whose kept groups include
    one that holds an expert held here, an expert layer), and of the FIRST
    layer ``attn_rows_read.latent_sparse`` (the rows it read, over the live
    slots), ``attn_rows_context.latent_sparse`` (the same slots' whole
    contexts), ``index_rows_scored`` and ``dsa_probe`` [1 + index_topk]
    int32: slot 0's position (-1 where it holds no request) and the rows
    it chose, ascending, -1 where there were fewer."""
    x = params["tok_emb"][tokens]
    stats, dsa = [], None
    for i, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["g1"], cfg.rms_eps)
        q_n, q_r, row, q_idx, w_idx, k_idx = _inputs(cfg, lp, h, pos)
        cache = cache_ops.write_token(cache, i, row, pos, active)
        with jax.named_scope("attn/dsa_index"):
            cache = cache_ops.write_index(cache, i, k_idx, pos, active)
            scores, scored = cache_ops.index_scores(
                cache, i, q_idx, w_idx, pos + 1, active, cfg.score_dtype)
        chosen = attention_ops.dsa_select_rows(scores, cfg.index_topk)
        q_abs = absorbed_query(cfg, lp["wkvb"], q_n, q_r)
        with jax.named_scope("attn/dsa_sparse"):
            o_lat, read = cache_ops.rows_decode_attention(
                cache, i, q_abs, chosen, pos + 1, active,
                sm_scale=cfg.sm_scale)
        x = x + absorbed_output(cfg, lp["wkvb"], o_lat) @ lp["wo"]
        if dsa is None:
            live = jnp.where(active, pos + 1, 0)
            dsa = {
                "attn_rows_read.latent_sparse": jnp.sum(read),
                "attn_rows_context.latent_sparse":
                    jnp.sum(live).astype(jnp.int32),
                "index_rows_scored": jnp.sum(scored).astype(jnp.int32),
                "dsa_probe": jnp.concatenate([
                    jnp.where(active[:1], pos[:1], -1).astype(jnp.int32),
                    _probed(chosen[:1], cfg.index_topk)])}
        x, st = routed_feed_forward(cfg, lp, x, active, count_groups=True)
        if st is not None:
            stats.append(st)
    return head(params, cfg, x), cache, {
        **moe_stats(stats),
        "moe_groups_kept_with_held": jnp.stack(
            [s["groups_kept_with_held"] for s in stats]), **dsa}


class DeepSeekV32LM(ServedLM):
    """The serving contract over :class:`DeepSeekV32Config` (the published
    multi-token-prediction layer is not served)."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

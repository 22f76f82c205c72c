"""Motif-3-Beta as pure JAX functions under the serving contract
(``models.blocks.ServedLM``), so the same ``ServingEngine``, scheduler and
page pool serve it. The plain float32 statement of the same equations,
which the tests and the benchmark compare this with, is
``grid/reference/motif3.py``; read the layers there.

What is particular to serving it:

* the layer loop carries FOUR residual streams, ``[4, B, d]`` in the
  served type (stream-major: each stream whole lane tiles of its own):
  each half of a layer mixes them into one input by a map of
  the token (``H_pre``), and writes its output back through two more
  (``H_res``, made doubly stochastic by Sinkhorn's iterations, and
  ``H_post``); the maps are computed in float32 (``cfg.maps_dtype``);
* TWO latent cache groups (``cfg.cache_groups``; ``serving.kv_cache
  .LatentPagedCache``): the one layer in four that attends over every
  position keeps its ``[c | kr']`` rows in pages (``latent_full``), the
  three that see the last ``window`` positions keep theirs in a RING of
  ``window`` rows a slot (``latent_ring``): a slot's cost grows with its
  context in a quarter of the layers only. The window layers rotate at
  their own rotary base;
* attention is GROUPED and DIFFERENTIAL: 80 query heads over 16 KV heads
  made from the latent, and of a KV head's five query heads the fifth is
  a noise head whose output is subtracted, weighed by a number computed
  for every token and signal head, from each of the other four
  (``ops.attention_ops.differential_combine``). PREFILL EXPANDS K and V at
  16 heads (the flash kernel in a full layer, the banded form in a window
  layer); DECODE ABSORBS, Kimi-K2's way, over either group,
  and because a group's heads share one value up-projection the
  subtraction is taken on the LATENT outputs: 64 up-projections, not 80;
* every MLP's activation is PolyNorm with four numbers of its own, so a
  routed expert's come with it (``ops.moe_ops.expert_layer(act_params=)``)
  through the ``ragged_dot`` path, the share's passes and the fused
  kernel; the routed experts may be a SHARE (``cfg.experts_held``).
"""

from __future__ import annotations

import functools
import types
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention_ops, moe_ops
from ..serving.kv_cache import LATENT
from .blocks import (ServedLM, head, held_experts, latent, maps_precision,
                     mix_in, mix_out, moe_stats, rms_norm, seeded_params,
                     yarn_inv_freq)

__all__ = ["Motif3Config", "Motif3LM", "init_params", "poly_norm"]

FULL, RING = "full", "window"          # ``layer_types``' two names


def poly_norm(v, p, scale: float = 0.5, clamp: float = 0.5,
              eps: float = 1e-6):
    """PolyNorm of the rows of ``v`` [..., f]: the first three powers of
    ``v``, each scaled to a mean square of one over the MLP's own width
    ``f``, weighed by ``p[0..2]``, the bias ``p[3]`` held inside ``clamp``,
    all times ``scale``; in float32. ``p`` is four numbers that broadcast
    against ``v``'s rows: an MLP's own, or in the expert paths each row's
    expert's (scalars from SMEM in the fused kernel). Written here on its
    own: ``grid/reference/motif3.py`` states the same mathematics plainly,
    and ``tests/test_motif3.py`` holds the two against each other."""
    v = v.astype(jnp.float32)
    acc, power = None, v
    for k in range(3):
        unit = power * jax.lax.rsqrt(
            jnp.mean(jnp.square(power), axis=-1, keepdims=True) + eps)
        acc = p[k] * unit if acc is None else acc + p[k] * unit
        power = power * v
    return scale * (acc + jnp.clip(p[3], -clamp, clamp))


@functools.lru_cache(maxsize=None)
def _activation(scale: float, clamp: float):
    """One object a configuration, so that jitted callers trace once."""
    return functools.partial(poly_norm, scale=scale, clamp=clamp)


class Motif3Config:
    """Static hyperparameters, under this package's names. ``layer_types``
    gives each layer's attention (``FULL`` or ``RING``: the last
    ``window`` positions); the layers in ``dense_layers`` have a dense MLP
    of ``d_dense``, every other one routes ``top_k`` of ``n_expert``
    experts of ``d_expert`` and adds one shared expert of the same width.
    ``n_head`` counts the query heads, ``n_kv_head`` the heads K and V are
    expanded to; ``n_head // n_kv_head - 1`` of a KV head's query heads
    are signal heads. ``maps_dtype`` is what the residual maps and the
    heads' subtraction are computed in: float32, as the configuration
    states; bfloat16 is the control that the cell's comparison has to
    fail (``benchmarks/control_motif3.py maps_bf16``)."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, n_kv_head: int, q_rank: int, kv_rank: int,
                 d_nope: int, d_rope: int, d_v: int,
                 layer_types: Sequence[str], window: int, d_dense: int,
                 dense_layers: Sequence[int], n_expert: int, top_k: int,
                 d_expert: int, routed_scale: float = 1.0,
                 rope_theta: float = 1e4, window_rope_theta: float = 1e4,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 n_stream: int = 4, sinkhorn_iters: int = 20,
                 hidden_clamp: float = 1e6, act_scale: float = 0.5,
                 act_bias_clamp: float = 0.5, rms_eps: float = 1e-5,
                 max_seq: int = 16384, dtype="float32",
                 experts_held: Optional[Sequence[int]] = None,
                 maps_dtype="float32"):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        if self.n_head % self.n_kv_head or self.n_head == self.n_kv_head:
            raise ValueError("%d query heads over %d KV heads: a KV head "
                             "needs signal heads and one noise head"
                             % (self.n_head, self.n_kv_head))
        self.n_signal = self.n_head - self.n_kv_head
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = int(d_nope), int(d_rope), int(d_v)
        self.d_head = self.d_nope + self.d_rope      # a query's lanes
        self.layer_types = tuple(layer_types)
        if len(self.layer_types) != self.n_layer \
                or set(self.layer_types) - {FULL, RING}:
            raise ValueError("layer_types names %d layers of %s; the model "
                             "has %d of %s" % (len(self.layer_types),
                                               sorted(set(self.layer_types)),
                                               self.n_layer, (FULL, RING)))
        self.window = int(window)
        self.d_dense = int(d_dense)
        self.dense_layers = tuple(int(i) for i in dense_layers)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.d_expert = int(d_expert)
        self.routed_scale = float(routed_scale)
        self.n_stream = int(n_stream)
        self.sinkhorn_iters = int(sinkhorn_iters)
        self.hidden_clamp = float(hidden_clamp)
        self.sinkhorn_eps = 0.0     # the iterations divide by the sums alone
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.maps_dtype = jnp.dtype(maps_dtype)
        self.experts_held = (tuple(range(self.n_expert))
                             if experts_held is None
                             else tuple(int(e) for e in experts_held))
        self.activation = _activation(float(act_scale),
                                      float(act_bias_clamp))
        self.sm_scale = self.d_head ** -0.5
        # a full layer's frequencies are YaRN's (no temperature factor), a
        # window layer's plain at its own base
        freqs = {FULL: yarn_inv_freq(self.d_rope, float(rope_theta),
                                     rope_scaling),
                 RING: yarn_inv_freq(self.d_rope, float(window_rope_theta),
                                     None)}
        # what blocks.latent reads of a config, a layer kind: the
        # same sizes under the kind's own rotary frequencies
        self.latent_of = {kind: types.SimpleNamespace(
            n_head=self.n_head, d_head=self.d_head, d_nope=self.d_nope,
            kv_rank=self.kv_rank, rms_eps=self.rms_eps, inv_freq=f)
            for kind, f in freqs.items()}

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def latent_row(self) -> Tuple[int, int]:
        """``(rank, rope)`` of the row a layer keeps a token."""
        return self.kv_rank, self.d_rope

    @property
    def cache_groups(self):
        """The full layers' pages first (admission is by them), the
        window layers' rings after."""
        return [("latent_full", self.layers_of(FULL), None, LATENT),
                ("latent_ring", self.layers_of(RING), self.window, LATENT)]

    def __repr__(self):
        return ("Motif3Config(V=%d, L=%d (%d full, %d window %d, %d dense), "
                "d=%d x %d streams, H=%d over %d (%d signal), q_rank=%d, "
                "latent %d+%d, E=%d of %d held, top-%d of %d, %s)"
                % (self.vocab_size, self.n_layer, len(self.layers_of(FULL)),
                   len(self.layers_of(RING)), self.window,
                   len(self.dense_layers), self.d_model, self.n_stream,
                   self.n_head, self.n_kv_head, self.n_signal, self.q_rank,
                   self.kv_rank, self.d_rope, len(self.experts_held),
                   self.n_expert, self.top_k, self.d_expert, self.dtype))


def _init_layer(cfg: Motif3Config, key, dense: bool) -> Dict:
    d, h, n = cfg.d_model, cfg.n_head, cfg.n_stream
    k = jax.random.split(key, 22)
    f32 = jnp.float32

    def nrm(kk, shape, std=0.02):
        # drawn in the served type: no float32 copy of a 9 GB tree
        return std * jax.random.normal(kk, shape, cfg.dtype)

    def ones(m):
        return jnp.ones((m,), cfg.dtype)

    def maps(kp, kb):
        # alpha 0.1 each; b_pre, b_post 0; b_res normal(0, 1): float32
        bias = jnp.concatenate([jnp.zeros((2 * n,), f32),
                                jax.random.normal(kb, (n * n,), f32)])
        return (nrm(kp, (n * d, 2 * n + n * n)), jnp.full((3,), 0.1, f32),
                bias)

    def poly(kk, lead):
        # w_1..w_3 at 1/3 +- 0.1, b inside the clamp, drawn for EACH
        u = jax.random.uniform(kk, lead + (4,), f32, -1.0, 1.0)
        return jnp.concatenate([1.0 / 3.0 + 0.1 * u[..., :3],
                                0.4 * u[..., 3:]], axis=-1)

    pa, aa, ba = maps(k[0], k[1])
    pm, am, bm = maps(k[2], k[3])
    lp = {"pa": pa, "aa": aa, "ba": ba, "pm": pm, "am": am, "bm": bm,
          "g1": ones(d), "g2": ones(d), "gq": ones(cfg.q_rank),
          "gkv": ones(cfg.kv_rank),
          "wqa": nrm(k[4], (d, cfg.q_rank)),
          "wqb": nrm(k[5], (cfg.q_rank, h * cfg.d_head)),
          "wkva": nrm(k[6], (d, cfg.kv_rank + cfg.d_rope)),
          "wkvb": nrm(k[7], (cfg.kv_rank,
                             cfg.n_kv_head * (cfg.d_nope + cfg.d_v))),
          "wlam": nrm(k[8], (d, cfg.n_signal)),
          "wgate": nrm(k[9], (d, cfg.n_signal * cfg.d_v)),
          "wo": nrm(k[10], (cfg.n_signal * cfg.d_v, d))}
    if dense:
        f = cfg.d_dense
        lp.update(wg=nrm(k[11], (d, f)), wu=nrm(k[12], (d, f)),
                  wd=nrm(k[13], (f, d)), pn=poly(k[14], ()))
        return lp
    e, f = len(cfg.experts_held), cfg.d_expert
    lp.update(wr=nrm(k[11], (d, cfg.n_expert)),
              wg=nrm(k[12], (e, d, f)), wu=nrm(k[13], (e, d, f)),
              wd=nrm(k[14], (e, f, d)), pn=poly(k[15], (e,)),
              sg=nrm(k[16], (d, f)), su=nrm(k[17], (d, f)),
              sd=nrm(k[18], (f, d)), spn=poly(k[19], ()))
    return lp


def init_params(cfg: Motif3Config, seed) -> Dict:
    """Seeded random weights (``blocks.seeded_params``). The residual maps'
    ``alpha`` are 0.1 and ``b_res`` normal(0, 1) (at the paper's small
    alpha the dynamic part is a hundredth of the static and no comparison
    could see an error in it), PolyNorm's weights 1/3 +- 0.1 and its bias
    inside the clamp, drawn for each MLP and each expert (at one value for
    all, the wrong expert's numbers would pass); both kept float32."""
    return seeded_params(cfg, seed, _init_layer,
                         lambda i: (i in cfg.dense_layers,))


def _kv_weights(cfg, wkvb):
    return wkvb.reshape(cfg.kv_rank, cfg.n_kv_head, cfg.d_nope + cfg.d_v)


def absorbed_query(cfg: Motif3Config, wkvb, q_n, q_r):
    """``[q_nope_n Wuk_g^T | q_rope_n]`` [B, H, rank + rope] with ``g = n
    // G``: ``blocks.absorbed_query`` where ``G`` query heads share a KV
    head's up-projection."""
    b, h, n = q_n.shape
    w = _kv_weights(cfg, wkvb)[..., :cfg.d_nope]
    q_lat = jnp.einsum("bgjn,cgn->bgjc",
                       q_n.reshape(b, cfg.n_kv_head, -1, n), w,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_n.dtype).reshape(b, h, -1), q_r],
                           axis=-1)


def absorbed_output(cfg: Motif3Config, wkvb, y_lat):
    """``y_lat_s Wuv_g`` [B, signal heads * d_v] of the signal heads'
    combined latent outputs ``y_lat`` [B, signal heads, rank]."""
    b = y_lat.shape[0]
    w = _kv_weights(cfg, wkvb)[..., cfg.d_nope:]
    a = jnp.einsum("bgjc,cgv->bgjv",
                   y_lat.reshape(b, cfg.n_kv_head, -1, cfg.kv_rank), w,
                   preferred_element_type=jnp.float32)
    return a.astype(y_lat.dtype).reshape(b, -1)


def _combine(cfg, lp, h, o):
    """The subtraction on ``o`` [..., H, D] (values, or latent outputs)
    with ``lambda = sigmoid(h w_lambda)``: [..., signal heads, D]."""
    with jax.named_scope("attn/diff_combine"):
        lam = jax.nn.sigmoid(jnp.dot(h, lp["wlam"],
                                     preferred_element_type=jnp.float32))
        return attention_ops.differential_combine(o, maps_precision(cfg, lam),
                                                  cfg.n_kv_head)


def _attn_out(lp, h, a):
    """``(a * sigmoid(h Wgate)) Wo`` of the heads' values ``a`` [..., signal
    heads * d_v]."""
    gate = jax.nn.sigmoid(jnp.dot(h, lp["wgate"],
                                  preferred_element_type=jnp.float32))
    return (a.astype(jnp.float32) * gate).astype(h.dtype) @ lp["wo"]


def _gdla_prefill(cfg, lp, kind, h, pos):
    """One sequence's attention, K and V EXPANDED at the KV heads: ``(y [S,
    d], row [S, rank + rope])``."""
    s = h.shape[0]
    g = cfg.n_head // cfg.n_kv_head
    q_n, q_r, row = latent(cfg.latent_of[kind], lp, h, pos)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    kv = (row[..., :cfg.kv_rank] @ lp["wkvb"]).reshape(
        s, cfg.n_kv_head, cfg.d_nope + cfg.d_v)
    k_n, v = kv[..., :cfg.d_nope], kv[..., cfg.d_nope:]
    k_r = row[:, cfg.kv_rank:]
    if kind == FULL:
        with jax.named_scope("attn/gdla_full"):
            o = attention_ops.mla_causal_attention(
                q, jnp.repeat(k_n, g, axis=1), k_r, jnp.repeat(v, g, axis=1),
                cfg.sm_scale)
    else:
        with jax.named_scope("attn/gdla_ring"):
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_r[:, None, :], (s, cfg.n_kv_head, cfg.d_rope))], axis=-1)
            o = attention_ops.windowed_causal_attention(
                q, k, v, cfg.window, cfg.sm_scale)
    y = _combine(cfg, lp, h, o)
    return _attn_out(lp, h, y.reshape(s, -1)), row


def _mlp(cfg, u, wg, wu, wd, pn):
    gate = jnp.dot(u, wg, preferred_element_type=jnp.float32)
    return (cfg.activation(gate, pn) * (u @ wu)).astype(u.dtype) @ wd


def _feed_forward(cfg, lp, u, row_valid):
    """The layer's second half over normed rows ``u`` [N, d]: the dense
    MLP, or the routed experts held here plus the shared expert. Returns
    ``(y [N, d], stats or None)``."""
    if "wr" not in lp:
        return _mlp(cfg, u, lp["wg"], lp["wu"], lp["wd"], lp["pn"]), None
    idx, w = moe_ops.route_sigmoid_topk(
        u, lp["wr"], jnp.zeros((cfg.n_expert,), jnp.float32), cfg.top_k,
        cfg.routed_scale)
    y, stats = moe_ops.expert_layer(
        u, idx, w, lp["wg"], lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=held_experts(cfg), row_valid=row_valid,
        activation=cfg.activation, act_params=lp["pn"])
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    with jax.named_scope("moe/shared"):
        shared = _mlp(cfg, u, lp["sg"], lp["su"], lp["sd"], lp["spn"])
    return (y + shared.astype(jnp.float32)).astype(u.dtype), stats


def _streams(params, cfg, tokens):
    """The embedding copied into the streams: ``[n, ..., d]``."""
    x = params["tok_emb"][tokens]
    return jnp.broadcast_to(x[None], (cfg.n_stream,) + x.shape)


def prefill_forward(params: Dict, cfg: Motif3Config, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d], the streams' sum before the final norm, rows)`` with
    ``rows`` one ``(row,)`` [B, S, rank + rope] a layer: what either latent
    group keeps (a ring keeps the last ``window`` of them)."""
    b, s = tokens.shape
    x = _streams(params, cfg, tokens)
    pos = jnp.arange(s)
    valid = (pos[None] < lengths[:, None]).reshape(b * s)
    rows = []
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h, h_post, h_res = mix_in(cfg, lp, "a", x, lp["g1"])
        ys, row = zip(*(_gdla_prefill(cfg, lp, kind, h[j], pos)
                        for j in range(b)))
        rows.append((jnp.stack(row),))
        x = mix_out(cfg, x, jnp.stack(ys), h_post, h_res)
        u, h_post, h_res = mix_in(cfg, lp, "m", x, lp["g2"])
        y, _ = _feed_forward(cfg, lp, u.reshape(b * s, -1), valid)
        x = mix_out(cfg, x, y.reshape(b, s, -1), h_post, h_res)
    return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype), rows


def decode_forward(params: Dict, cfg: Motif3Config, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot, ABSORBED, through ``cache_ops`` (pages
    or ring: the cache's). Returns ``(logits [B, V], cache, stats)``:
    ``models/kimi_k2.py``'s three ``moe_*`` an EXPERT layer and the
    cache's ``attn_rows_read.latent_full`` and ``.latent_ring``."""
    x = _streams(params, cfg, tokens)
    stats = []
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.layer_types)):
        h, h_post, h_res = mix_in(cfg, lp, "a", x, lp["g1"])
        q_n, q_r, row = latent(cfg.latent_of[kind], lp, h, pos)
        cache = cache_ops.write_token(cache, i, row, pos, active)
        with jax.named_scope("attn/gdla_full" if kind == FULL
                             else "attn/gdla_ring"):
            o_lat = cache_ops.decode_attention(
                cache, i, absorbed_query(cfg, lp["wkvb"], q_n, q_r),
                pos + 1, active, sm_scale=cfg.sm_scale)
        y_lat = _combine(cfg, lp, h, o_lat)
        y = _attn_out(lp, h, absorbed_output(cfg, lp["wkvb"], y_lat))
        x = mix_out(cfg, x, y, h_post, h_res)
        u, h_post, h_res = mix_in(cfg, lp, "m", x, lp["g2"])
        y, st = _feed_forward(cfg, lp, u, active)
        x = mix_out(cfg, x, y, h_post, h_res)
        if st is not None:
            stats.append(st)
    x = jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)
    return head(params, cfg, x), cache, {
        **moe_stats(stats), **cache_ops.rows_read(pos + 1, active)}


class Motif3LM(ServedLM):
    """The serving contract over :class:`Motif3Config` (the published
    draft layer is not served)."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)

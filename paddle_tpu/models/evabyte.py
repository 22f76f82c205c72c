"""EvaByte's byte-level decoder as pure JAX functions under the serving
contract (``models.blocks.ServedLM``), so the same ``ServingEngine``,
scheduler, page pool and paged-attention kernel serve it. The plain float32
statement of the same equations, which the tests and the benchmark compare
this with, is ``grid/reference/evabyte.py``; read the layer there.

EVA attention in its learned-pooling form: positions fall into TUMBLING
windows of ``window`` positions and chunks of ``chunk``. A query attends to
the exact keys of its own window up to itself and, for every window that
has CLOSED, to one pooled (key, value) pair a chunk, all under ONE softmax.
What is particular to serving it:

* the cache group is COMPACTING (``serving.kv_cache``): a slot holds a
  window's rows only while the window is open, and ``window / chunk``
  summaries of it afterwards, so a context of n positions reads ``(window
  / chunk) (n // window) + n mod window + 1`` rows a layer, not n. K is
  stored rotated and pooled rotated, so decode attention is the paged
  kernel over the slot's view, given a length;
* a decode step, a layer: the position's row in, the open chunk's rows
  out (``open_chunk``), their summary in (``write_summary``: kept only
  where the position ends its chunk), attention; after the last layer the
  compaction (``close_windows``: where the position ends its window);
* ``kept`` from prefill is ``(k_open, v_open, k_sum, v_sum)`` a layer: the
  ``min(S, window)`` rows from ``kv_cache.open_window_start`` on, which
  hold the window the prompt leaves open, and a summary a chunk of the
  bucket. The prefill attention is ONE softmax a query over ``[every
  earlier window's summaries ++ its own window's rows up to itself]``: on
  a TPU one ``eva_prefill_attention`` kernel call a layer
  (``ops/pallas_kernels/eva_prefill.py``: an online softmax over the
  closed windows' summary tiles and the own window's tiles, no score
  through HBM), elsewhere blocked XLA with the softmax written by hand
  over both parts (``_prefill_attention`` chooses from the backend and
  ``eva_prefill_gate``'s shape rules and counts the choice,
  ``attn/eva_prefill_calls.kernel|blocked``);
* the residual stream is float32 (``fp32_skip_add``), the pooling and
  every softmax float32 (``mixedp_attn``), the products take the model
  type's operands with float32 accumulation, a norm applies ``1 + g``
  (``norm_add_unit_offset``);
* ``n_pred_heads`` heads over the byte vocabulary are ONE product; head i
  scores byte ``p + 1 + i``. The served token is head 0's; a decode step
  hands out every head's logits as the probe ``eva_head_logits``.
"""

from __future__ import annotations

import math
from typing import Dict, Mapping

import jax
import jax.numpy as jnp

from ..ops import attention_ops
from ..ops.pallas_kernels import eva_prefill
from ..serving.kv_cache import KV, open_window_start
from .blocks import ServedLM, rms_norm, rope, seeded_params

__all__ = ["EvaByteConfig", "EvaByteLM", "SEED_RMS", "init_params",
           "summarize"]

# What :func:`init_params` seeds: a projection's OUTPUT deviation for an
# input of deviation 1 (its weights' is that over ``sqrt(fan_in)``), the
# embedding's rows, the deviation of the pooling softmax's logit ``(k .
# phi) / sqrt(D)`` (``pool_logit``: near 1, so the pooling weights are far
# from uniform) and of ``mu``'s lanes (a key's own).
SEED_RMS = {"embedding": 1.0, "q": 1.5, "k": 1.5, "v": 1.0, "attn_out": 0.5,
            "mlp_gate": 1.0, "mlp_up": 1.0, "mlp_down": 0.5, "head": 1.0,
            "pool_logit": 1.0, "mu": 1.5}
_SEEDED_STD = 0.02      # what ``blocks.seeded_params`` draws the embedding at
_PREFILL_BLOCK = 512    # query rows a block of the prefill attention


class EvaByteConfig:
    """Static hyperparameters, under this package's names. ``window`` and
    ``chunk`` are the tumbling window and the pooled chunk;
    ``cache_groups`` says them to the cache (one compacting group, ``eva``,
    of every layer)."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, n_kv_head: int, d_ff: int, window: int = 2048,
                 chunk: int = 16, n_pred_heads: int = 8,
                 rope_theta: float = 1e5, rms_eps: float = 1e-5,
                 max_seq: int = 1024, dtype="float32",
                 seed_rms: Mapping[str, float] = None):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.d_ff = int(d_ff)
        self.window, self.chunk = int(window), int(chunk)
        self.n_pred_heads = int(n_pred_heads)
        if self.n_head != self.n_kv_head or self.d_model % self.n_head \
                or self.window % self.chunk:
            raise ValueError(
                "%d query heads over %d KV heads of a width of %d, chunks "
                "of %d in windows of %d: the layer is written for one query "
                "head a KV head and whole chunks a window"
                % (self.n_head, self.n_kv_head, self.d_model, self.chunk,
                   self.window))
        self.d_head = self.d_model // self.n_head
        self.rope_theta = float(rope_theta)
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.seed_rms = dict(SEED_RMS, **(seed_rms or {}))
        self.inv_freq = self.rope_theta ** (
            -jnp.arange(self.d_head // 2, dtype=jnp.float32) * 2.0
            / self.d_head)
        self.sm_scale = self.d_head ** -0.5
        self.cache_groups = [("eva", tuple(range(self.n_layer)), self.window,
                              KV, self.chunk)]

    def __repr__(self):
        return ("EvaByteConfig(V=%d x %d heads, L=%d, d=%d, H=%d, D=%d, "
                "ff=%d, window=%d, chunk=%d, %s)"
                % (self.vocab_size, self.n_pred_heads, self.n_layer,
                   self.d_model, self.n_head, self.d_head, self.d_ff,
                   self.window, self.chunk, self.dtype))


def _init_layer(cfg: EvaByteConfig, key) -> Dict:
    d, f, dt, rms = cfg.d_model, cfg.d_ff, cfg.dtype, cfg.seed_rms
    k = jax.random.split(key, 9)

    def proj(kk, fan_in, fan_out, target):
        # drawn in the served type: no float32 copy of a 400 MB layer
        return (target / math.sqrt(fan_in)) * jax.random.normal(
            kk, (fan_in, fan_out), dt)

    heads = (cfg.n_head, cfg.d_head)
    return {"g1": jnp.zeros((d,), dt), "g2": jnp.zeros((d,), dt),
            "wq": proj(k[0], d, d, rms["q"]),
            "wk": proj(k[1], d, d, rms["k"]),
            "wv": proj(k[2], d, d, rms["v"]),
            "wo": proj(k[3], d, d, rms["attn_out"]),
            "wg": proj(k[4], d, f, rms["mlp_gate"]),
            "wu": proj(k[5], d, f, rms["mlp_up"]),
            "wd": proj(k[6], f, d, rms["mlp_down"]),
            # the pooling's query and the summaries' offset, a head: float32
            # (32 x 128 each)
            "phi": (rms["pool_logit"] / rms["k"]) * jax.random.normal(
                k[7], heads, jnp.float32),
            "mu": rms["mu"] * jax.random.normal(k[8], heads, jnp.float32)}


def init_params(cfg: EvaByteConfig, seed) -> Dict:
    """Seeded random weights through ``blocks.seeded_params`` (made on the
    device, a layer a call, in ``cfg.dtype``): a layer's seven projections,
    its two gains stored as g (the norm applies ``1 + g``), ``phi`` and
    ``mu`` [H, D]; the final norm's ``gf`` (g too), the embedding scaled
    to ``seed_rms["embedding"]``, and the ``n_pred_heads`` untied heads as
    ONE ``[d, n_pred_heads * V]`` matrix, head i its columns ``[i V, (i +
    1) V)``."""
    params = seeded_params(cfg, seed, _init_layer, lambda i: ())
    rms = cfg.seed_rms
    params["tok_emb"] = jax.jit(
        lambda e: e * jnp.asarray(rms["embedding"] / _SEEDED_STD, e.dtype),
        donate_argnums=0)(params["tok_emb"])
    params["gf"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    head_key = jax.random.fold_in(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    params["head"] = (rms["head"] / math.sqrt(cfg.d_model)) \
        * jax.random.normal(
            head_key, (cfg.d_model, cfg.n_pred_heads * cfg.vocab_size),
            cfg.dtype)
    return params


def _norm(cfg, x, g):
    """RMSNorm with the gain ``1 + g``, computed in float32, handed to a
    product in the model's type."""
    return rms_norm(x, 1.0 + g.astype(jnp.float32),
                    cfg.rms_eps).astype(cfg.dtype)


def head(params, cfg: EvaByteConfig, x):
    """Every prediction head over the residual ``x`` [..., d]: the final
    norm and ONE product; ``[..., n_pred_heads, V]`` float32
    (``fp32_logits``)."""
    with jax.named_scope("head/multibyte"):
        logits = jnp.dot(_norm(cfg, x, params["gf"]), params["head"],
                         preferred_element_type=jnp.float32)
        return logits.reshape(x.shape[:-1] + (cfg.n_pred_heads,
                                              cfg.vocab_size))


def _qkv(cfg, lp, x, pos):
    """``(q, k, v)`` [..., H, D] of the residual ``x``, q and k rotated at
    ``pos``."""
    with jax.named_scope("attn/proj"):
        a = _norm(cfg, x, lp["g1"])
        heads = a.shape[:-1] + (cfg.n_head, cfg.d_head)
        q = rope((a @ lp["wq"]).reshape(heads), pos, cfg.inv_freq)
        k = rope((a @ lp["wk"]).reshape(heads), pos, cfg.inv_freq)
        return q, k, (a @ lp["wv"]).reshape(heads)


def _attn_out(cfg, lp, x, o):
    """The float32 residual ``x`` plus the heads' output through ``wo``."""
    with jax.named_scope("attn/proj"):
        return x + jnp.dot(o.reshape(o.shape[:-2] + (-1,)), lp["wo"],
                           preferred_element_type=jnp.float32)


def _mlp(cfg, lp, x):
    with jax.named_scope("mlp"):
        b = _norm(cfg, x, lp["g2"])
        h = jax.nn.silu(b @ lp["wg"]) * (b @ lp["wu"])
        return x + jnp.dot(h, lp["wd"], preferred_element_type=jnp.float32)


def summarize(cfg: EvaByteConfig, lp, k, v):
    """A chunk's summary: ``k``/``v`` [..., chunk, H, D], the chunk's
    ROTATED keys and its values as the cache keeps them. The pooling
    weights are a softmax over the chunk's rows of ``(k . phi) / sqrt(D)``
    a head; the pooled key takes ``mu``. Computed in float32
    (``mixedp_attn``), returned ``[..., H, D]`` in the model's type. The
    callers name the scope (``attn/eva_pool``)."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    logit = jnp.sum(kf * lp["phi"], axis=-1) * cfg.sm_scale
    w = jax.nn.softmax(logit, axis=-2)[..., None]
    ks = jnp.sum(w * kf, axis=-3) + lp["mu"]
    vs = jnp.sum(w * vf, axis=-3)
    return ks.astype(cfg.dtype), vs.astype(cfg.dtype)


def _prefill_attention(cfg: EvaByteConfig, q, k, v, ks, vs):
    """EVA attention of ONE bucket-padded sequence: ``q``/``k``/``v`` [S,
    H, D], ``ks``/``vs`` [S / chunk, H, D] a summary a chunk (none at all
    where ``S <= window``: no window has closed). A query of window w
    meets the summaries of the windows before w and the rows of w up to
    itself under ONE softmax in float32. On a TPU, where
    ``eva_prefill_gate`` takes the shapes, that is ONE
    ``eva_prefill_attention`` kernel call (pallas_kernels/eva_prefill.py:
    no score reaches HBM, a query block reads the closed windows' summary
    tiles and its own window's tiles up to its last row, and no others);
    elsewhere the queries go in blocks of ``_PREFILL_BLOCK`` rows, each
    against its WHOLE window and ALL the summaries, masked, the softmax
    over both parts written out (one max, one sum).
    ``attn/eva_prefill_calls.kernel`` and ``.blocked`` count which. A
    summary of a chunk that holds padding is seen by padding alone.
    Returns [S, H, D]."""
    s, h, d = q.shape
    wm = min(s, cfg.window)
    kernel = attention_ops._on_tpu() and eva_prefill.eva_prefill_gate(
        h, d, s, wm, cfg.chunk, q.dtype.itemsize) is None
    attention_ops._count("kernel" if kernel else "blocked",
                         "attn/eva_prefill_calls",
                         "evabyte._prefill_attention")
    with jax.named_scope("attn/eva_prefill"):
        if kernel:
            return eva_prefill.eva_prefill_attention(
                q, k, v, ks, vs, wm, cfg.chunk, sm_scale=cfg.sm_scale)
        return _blocked_prefill_attention(cfg, q, k, v, ks, vs)


def _blocked_prefill_attention(cfg: EvaByteConfig, q, k, v, ks, vs):
    """:func:`_prefill_attention` in plain XLA, a ``lax.map`` over query
    blocks: the form off the TPU and what the kernel is held against."""
    s, h, d = q.shape
    wm = min(s, cfg.window)
    bq = _PREFILL_BLOCK if wm % _PREFILL_BLOCK == 0 else wm
    kept = cfg.window // cfg.chunk
    n_sum = kept * (s // wm - 1)        # what the last window's queries see
    ksum, vsum = ks[:n_sum], vs[:n_sum]
    neg = attention_ops.neg_inf(jnp.float32)

    def block(i):
        q0 = i * bq
        w = q0 // wm
        qb = jax.lax.dynamic_slice(q, (q0, 0, 0), (bq, h, d))
        kw = jax.lax.dynamic_slice(k, (w * wm, 0, 0), (wm, h, d))
        vw = jax.lax.dynamic_slice(v, (w * wm, 0, 0), (wm, h, d))
        rows = q0 + jnp.arange(bq)
        sc = jnp.einsum("qhd,khd->hqk", qb, kw,
                        preferred_element_type=jnp.float32) * cfg.sm_scale
        sc = jnp.where((w * wm + jnp.arange(wm))[None, :] <= rows[:, None],
                       sc, neg)
        top = jnp.max(sc, axis=-1, keepdims=True)
        if n_sum:
            ss = jnp.einsum("qhd,khd->hqk", qb, ksum,
                            preferred_element_type=jnp.float32) * cfg.sm_scale
            ss = jnp.where(jnp.arange(n_sum) < kept * w, ss, neg)
            top = jnp.maximum(top, jnp.max(ss, axis=-1, keepdims=True))
        e = jnp.exp(sc - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("hqk,khd->hqd", e.astype(v.dtype), vw,
                       preferred_element_type=jnp.float32)
        if n_sum:
            es = jnp.exp(ss - top)
            total = total + jnp.sum(es, axis=-1, keepdims=True)
            o = o + jnp.einsum("hqk,khd->hqd", es.astype(v.dtype), vsum,
                               preferred_element_type=jnp.float32)
        return (o / total).transpose(1, 0, 2).astype(q.dtype)

    return jax.lax.map(block, jnp.arange(s // bq)).reshape(s, h, d)


def prefill_forward(params: Dict, cfg: EvaByteConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S] (whole
    chunks, and whole windows past one). Returns ``(x [B, S, d] float32,
    the residual :func:`head` takes, kept)`` with ``kept`` a layer
    ``(k_open, v_open [B, min(S, window), H, D], k_sum, v_sum [B, S /
    chunk, H, D])``: what a compacting group's ``write_prompt`` takes."""
    b, s = tokens.shape
    pos = jnp.arange(s)[None]
    wm = min(s, cfg.window)
    if s % cfg.chunk or s % wm:
        raise ValueError("a bucket of %d rows is not whole chunks of %d and "
                         "whole windows of %d" % (s, cfg.chunk, cfg.window))
    start = open_window_start(lengths, s, cfg.window)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    kept = []
    for lp in params["layers"]:
        q, k, v = _qkv(cfg, lp, x, pos)
        in_chunks = (b, s // cfg.chunk, cfg.chunk, cfg.n_head, cfg.d_head)
        with jax.named_scope("attn/eva_pool"):
            ks, vs = summarize(cfg, lp, k.reshape(in_chunks),
                               v.reshape(in_chunks))
        o = jnp.stack([_prefill_attention(cfg, q[j], k[j], v[j], ks[j],
                                          vs[j]) for j in range(b)])
        x = _attn_out(cfg, lp, x, o)
        left = tuple(
            jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
                rows, at, wm))(t, start) for t in (k, v)) + (ks, vs)
        # what the layer leaves is made HERE: left to itself the compiler
        # computes the open window's rows from the layer's input when the
        # cache is written, after the last layer, and keeps every layer's
        # float32 residual until then (0.3 GB a layer at 16,384 rows)
        x, left = jax.lax.optimization_barrier((x, left))
        kept.append(left)
        x = _mlp(cfg, lp, x)
    return x, kept


def decode_forward(params: Dict, cfg: EvaByteConfig, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``. Returns ``(logits
    [B, n_pred_heads, V] float32, cache, stats)``: what ONE layer read of
    each kind (``attn_rows_read.eva_exact``, ``.eva_summary``), the same
    slots' whole contexts (``attn_rows_context.eva``), the live slots that
    ended a chunk and a window in the step (``eva_chunks_closed``,
    ``eva_windows_closed``)."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for i, lp in enumerate(params["layers"]):
        q, k, v = _qkv(cfg, lp, x, pos)
        with jax.named_scope("attn/eva"):
            cache = cache_ops.write_token(cache, i, k, v, pos, active)
        with jax.named_scope("attn/eva_pool"):
            ks, vs = summarize(cfg, lp, *cache_ops.open_chunk(cache, i, pos))
            cache = cache_ops.write_summary(cache, i, ks, vs, pos, active)
        with jax.named_scope("attn/eva"):
            o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                           sm_scale=cfg.sm_scale)
        x = _attn_out(cfg, lp, x, o)
        x = _mlp(cfg, lp, x)
    with jax.named_scope("attn/eva_close"):
        cache = cache_ops.close_windows(cache, pos, active)

    def live(ends):
        return jnp.sum(active & ((pos + 1) % ends == 0)).astype(jnp.int32)

    return head(params, cfg, x), cache, {
        **cache_ops.rows_read(pos + 1, active),
        "attn_rows_context.eva": jnp.sum(
            jnp.where(active, pos + 1, 0)).astype(jnp.int32),
        "eva_chunks_closed": live(cfg.chunk),
        "eva_windows_closed": live(cfg.window)}


class EvaByteLM(ServedLM):
    """The serving contract over :class:`EvaByteConfig`. The engine is
    handed head 0's logits (the served byte); a decode step's ``stats``
    carry every head's as the probe ``eva_head_logits`` [B, n_pred_heads,
    V], and beside it ``eva_row_live`` [B]: the slots that decoded a row
    in the step."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)
    head = staticmethod(head)

    def prefill(self, params, tokens, lengths):
        logits, kept = super().prefill(params, tokens, lengths)
        return logits[..., 0, :], kept

    def prefill_last(self, params, tokens, lengths):
        logits, kept = super().prefill_last(params, tokens, lengths)
        return logits[..., 0, :], kept

    def decode(self, params, cache, cache_ops, tokens, pos, active):
        logits, cache, stats = super().decode(params, cache, cache_ops,
                                              tokens, pos, active)
        return logits[:, 0], cache, dict(stats, eva_head_logits=logits,
                                         eva_row_live=active)

"""Decoder-only transformer LM as pure JAX functions — the serving workload.

The model the serving stack (paddle_tpu.serving) drives: a pre-LN GPT-style
decoder with tied input/output embeddings, written as pure functions over a
params pytree so the engine can AOT-compile one prefill per prompt bucket
and one incremental decode step whose KV cache stays on device (the
static-graph models in this package build Programs; a Program-authored
decoder plugs into the same engine once ROADMAP item 6's ``to_static``
extraction lands, via the ``prefill_forward``/``decode_forward`` contract).

The decode loop is cache-layout-blind: it threads an opaque cache pytree
through ``cache_ops`` (serving.kv_cache.PagedKVCache or ContiguousKVCache),
writing each new position's K/V before attending over the gathered context
with ``ops.attention_ops.decode_attention``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile_cache as _cc
from ..ops import attention_ops

__all__ = ["DecoderConfig", "DecoderLM", "init_params", "prefill_forward",
           "decode_forward", "reference_decode",
           "reference_tokens"]


class DecoderConfig:
    """Static decoder hyperparameters (closed over at trace time)."""

    def __init__(self, vocab_size: int = 256, n_layer: int = 2,
                 d_model: int = 64, n_head: int = 4, max_seq: int = 128,
                 ffn_mult: int = 4, dtype="float32"):
        if d_model % n_head != 0:
            raise ValueError("d_model must divide by n_head")
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head = int(n_head)
        self.d_head = self.d_model // self.n_head
        self.max_seq = int(max_seq)
        self.ffn_mult = int(ffn_mult)
        self.dtype = jnp.dtype(dtype)
        self.sm_scale = 1.0 / math.sqrt(self.d_head)

    def __repr__(self):
        return ("DecoderConfig(V=%d, L=%d, d=%d, H=%d, S=%d, %s)"
                % (self.vocab_size, self.n_layer, self.d_model, self.n_head,
                   self.max_seq, self.dtype))


@_cc.in_phase("startup/weights")
def init_params(cfg: DecoderConfig, seed: int = 0) -> Dict:
    key = jax.random.PRNGKey(seed)
    d, f = cfg.d_model, cfg.d_model * cfg.ffn_mult

    def nrm(k, shape, scale=0.02):
        return (scale * jax.random.normal(k, shape)).astype(cfg.dtype)

    keys = jax.random.split(key, 2 + 6 * cfg.n_layer)
    params = {
        "tok_emb": nrm(keys[0], (cfg.vocab_size, d)),
        "pos_emb": nrm(keys[1], (cfg.max_seq, d)),
        "lnf_g": jnp.ones((d,), cfg.dtype),
        "lnf_b": jnp.zeros((d,), cfg.dtype),
        "layers": [],
    }
    for i in range(cfg.n_layer):
        k = keys[2 + 6 * i: 8 + 6 * i]
        params["layers"].append({
            "ln1_g": jnp.ones((d,), cfg.dtype),
            "ln1_b": jnp.zeros((d,), cfg.dtype),
            "wq": nrm(k[0], (d, d)),
            "wk": nrm(k[1], (d, d)),
            "wv": nrm(k[2], (d, d)),
            "wo": nrm(k[3], (d, d)),
            "ln2_g": jnp.ones((d,), cfg.dtype),
            "ln2_b": jnp.zeros((d,), cfg.dtype),
            "w1": nrm(k[4], (d, f)),
            "b1": jnp.zeros((f,), cfg.dtype),
            "w2": nrm(k[5], (f, d)),
            "b2": jnp.zeros((d,), cfg.dtype),
        })
    return params


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _ffn(x, lp):
    return jax.nn.gelu(x @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]


def prefill_forward(params: Dict, cfg: DecoderConfig, tokens, lengths
                    ) -> Tuple[jnp.ndarray, List[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Full causal forward over (bucket-padded) prompts.

    ``tokens`` [B,S] int32, ``lengths`` [B]. Returns (logits [B,S,V], kvs)
    where ``kvs`` is one (k, v) pair [B,S,H,D] per layer for the caller to
    write into its cache layout. Padding positions are masked out of valid
    queries' attention via segment ids; their own rows are garbage the
    caller must ignore (read logits at ``lengths-1``, write KV < length).
    """
    b, s = tokens.shape
    x = params["tok_emb"][tokens] + params["pos_emb"][:s][None]
    valid = (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.int32)
    kvs = []
    for lp in params["layers"]:
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_head, cfg.d_head)
        k = (h @ lp["wk"]).reshape(b, s, cfg.n_head, cfg.d_head)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_head, cfg.d_head)
        kvs.append((k, v))
        o = attention_ops.sdpa(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            segment_ids_q=valid, segment_ids_kv=valid,
            causal=True, sm_scale=cfg.sm_scale)
        x = x + o.transpose(0, 2, 1, 3).reshape(b, s, cfg.d_model) @ lp["wo"]
        x = x + _ffn(_ln(x, lp["ln2_g"], lp["ln2_b"]), lp)
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    return x @ params["tok_emb"].T, kvs


def decode_forward(params: Dict, cfg: DecoderConfig, cache, cache_ops,
                   tokens, pos, active):
    """One incremental decode position for every batch slot.

    ``tokens``/``pos``/``active`` are [B]; the token at ``pos[b]`` has its
    K/V written into the cache (inactive slots dropped inside the scatter)
    BEFORE attention over the context masked to ``pos+1`` valid positions
    (the cache makes that 0 for a slot that is not ``active``: ``pos`` stays
    where a slot's last request ended) — dispatched through
    ``cache_ops.decode_attention``, so the layout owns the
    gather-vs-fused-Pallas-kernel choice and this loop stays layout-blind.
    Returns (logits [B,V], cache') — the cache pytree threads functionally
    so the engine's fused scan carries it on device.
    """
    b = tokens.shape[0]
    pos_c = jnp.clip(pos, 0, cfg.max_seq - 1)
    x = params["tok_emb"][tokens] + params["pos_emb"][pos_c]
    for i, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q = (h @ lp["wq"]).reshape(b, cfg.n_head, cfg.d_head)
        k = (h @ lp["wk"]).reshape(b, cfg.n_head, cfg.d_head)
        v = (h @ lp["wv"]).reshape(b, cfg.n_head, cfg.d_head)
        cache = cache_ops.write_token(cache, i, k, v, pos, active)
        o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                       sm_scale=cfg.sm_scale)
        x = x + o.reshape(b, cfg.d_model) @ lp["wo"]
        x = x + _ffn(_ln(x, lp["ln2_g"], lp["ln2_b"]), lp)
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    return x @ params["tok_emb"].T, cache


class DecoderLM:
    """Meets the serving contract (``models.blocks.ServedLM``'s docstring)
    with methods of its own: a config + params pytree with ``prefill``
    and ``decode``."""

    def __init__(self, cfg: DecoderConfig, params: Dict = None, seed: int = 0):
        self.cfg = cfg
        self.params = params if params is not None else init_params(cfg, seed)

    def prefill(self, params, tokens, lengths):
        return prefill_forward(params, self.cfg, tokens, lengths)

    def decode(self, params, cache, cache_ops, tokens, pos, active):
        return decode_forward(params, self.cfg, cache, cache_ops,
                              tokens, pos, active)


def reference_decode(params: Dict, cfg: DecoderConfig, prompt,
                     max_new_tokens: int):
    """O(S²) no-cache greedy reference: recompute the FULL causal forward
    for every generated token. The yardstick the incremental paged/
    contiguous decode paths are parity-tested against (ragged-vs-padded
    logit parity at mixed lengths). Returns (tokens list, logits list)."""
    seq = [int(t) for t in prompt]
    out_tokens, out_logits = [], []
    for _ in range(max_new_tokens):
        toks = jnp.asarray(np.asarray(seq, np.int32)[None])
        lengths = jnp.asarray([len(seq)], jnp.int32)
        logits, _ = prefill_forward(params, cfg, toks, lengths)
        last = np.asarray(logits[0, len(seq) - 1])
        nxt = int(np.argmax(last))
        out_tokens.append(nxt)
        out_logits.append(last)
        seq.append(nxt)
        if len(seq) >= cfg.max_seq:
            break
    return out_tokens, out_logits


_jit_prefill_forward = jax.jit(prefill_forward, static_argnums=1)


def reference_tokens(params: Dict, cfg: DecoderConfig, prompt, output,
                     pad_multiple: int = 128) -> List[int]:
    """:func:`reference_decode`'s verdict on an ``output`` some other
    decoder produced for ``prompt``, in ONE forward instead of one per
    token (on an accelerator each of reference_decode's steps has a new
    shape, so every op of it compiles again: 32 tokens of a 12-layer model
    cost minutes of compile).

    The same full-recompute forward runs once, teacher-forced, over
    ``prompt + output[:-1]``. A causal model's logits at position i depend
    on tokens <= i only, so row ``len(prompt) - 1 + i`` is the row
    reference_decode computes for its i-th token as long as the first i
    tokens agree. Returns those rows' greedy tokens: equal to ``output``
    iff ``reference_decode(prompt, len(output))`` is, and first different
    at the same index if not. The sequence is padded (masked through
    ``lengths``) to a multiple of ``pad_multiple`` so that requests share
    one compiled forward."""
    seq = [int(t) for t in prompt] + [int(t) for t in output[:-1]]
    padded = min(-(-len(seq) // pad_multiple) * pad_multiple, cfg.max_seq)
    toks = np.zeros((1, padded), np.int32)
    toks[0, :len(seq)] = seq
    logits, _ = _jit_prefill_forward(params, cfg, jnp.asarray(toks),
                                     jnp.asarray([len(seq)], jnp.int32))
    rows = logits[0, len(prompt) - 1:len(seq)]
    return [int(t) for t in np.asarray(jnp.argmax(rows, axis=-1))]

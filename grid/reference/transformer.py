"""The encoder-decoder Transformer of Vaswani et al. 2017 ("Attention Is All
You Need", section 3), plain: token embeddings scaled by sqrt(d_model) plus
the sinusoid position table, N encoder blocks (self-attention, ReLU
feed-forward), N decoder blocks (causal self-attention, attention over the
encoder's output, feed-forward), a linear projection to the target
vocabulary, and cross-entropy against labels smoothed by eps
(q = (1 - eps) onehot + eps / V). Float32 at
``jax.default_matmul_precision("highest")``; inference mode, so no dropout.

Departures from the paper, because the program under test makes them
(``paddle_tpu/models/transformer.py``): layer norm sits BEFORE each
sub-layer (pre-norm) with one more after the last block of each stack;
source and target have separate embeddings and the output projection is
not tied to them and has a bias; the attention projections have no bias.

Parameters come by the program's own names (``src_emb``, ``enc_0_qkv.w_0``,
``layer_norm_3.b_0``, ...) as a ``{name: array}`` dict; the fused ``qkv``
weight is [d, 3 d] with q, k and v side by side. Rows are packed (every
mask is 1), as every grid cell feeds them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, i, eps=1e-5):
    g, b = p["layer_norm_%d.w_0" % i], p["layer_norm_%d.b_0" % i]
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _positions(n, d):
    pos = np.arange(n)[:, None]
    dim = np.arange(d)[None, :]
    angle = pos / np.power(10000.0, 2 * (dim // 2) / d)
    return jnp.asarray(np.where(dim % 2 == 0, np.sin(angle), np.cos(angle)),
                       jnp.float32)


def _attend(q, k, v, n_head, causal):
    b, sq, d = q.shape
    sk = k.shape[1]
    dh = d // n_head
    q = q.reshape(b, sq, n_head, dh)
    k = k.reshape(b, sk, n_head, dh)
    v = v.reshape(b, sk, n_head, dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), scores,
                           -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(b, sq, d)


def _ffn(x, p, name):
    h = jax.nn.relu(x @ p[name + "_fc1.w_0"] + p[name + "_fc1.b_0"])
    return h @ p[name + "_fc2.w_0"] + p[name + "_fc2.b_0"]


def _self_attention(x, p, name, n_head, causal):
    q, k, v = jnp.split(x @ p[name + "_qkv.w_0"], 3, axis=-1)
    return _attend(q, k, v, n_head, causal) @ p[name + "_out.w_0"]


def logits(params: Dict[str, jnp.ndarray], n_layer: int, n_head: int,
           src, trg) -> jnp.ndarray:
    """``[B, S, V]`` for source ids ``src`` and target ids ``trg``."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    d = p["src_emb"].shape[1]
    with jax.default_matmul_precision("highest"):
        x = p["src_emb"][src] * np.sqrt(d) + _positions(src.shape[1], d)
        ln = 0
        for i in range(n_layer):
            name = "enc_%d" % i
            x = x + _self_attention(_ln(x, p, ln), p, name, n_head, False)
            x = x + _ffn(_ln(x, p, ln + 1), p, name)
            ln += 2
        memory = _ln(x, p, ln)
        ln += 1
        y = p["trg_emb"][trg] * np.sqrt(d) + _positions(trg.shape[1], d)
        for i in range(n_layer):
            name = "dec_%d" % i
            y = y + _self_attention(_ln(y, p, ln), p, name + "_self",
                                    n_head, True)
            h = _ln(y, p, ln + 1)
            cross = _attend(h @ p[name + "_cross_q.w_0"],
                            memory @ p[name + "_cross_k.w_0"],
                            memory @ p[name + "_cross_v.w_0"], n_head, False)
            y = y + cross @ p[name + "_cross_out.w_0"]
            y = y + _ffn(_ln(y, p, ln + 2), p, name)
            ln += 3
        return _ln(y, p, ln) @ p["predict.w_0"] + p["predict.b_0"]


def loss(logits_, labels, eps: float) -> jnp.ndarray:
    """Mean over tokens of the cross-entropy against smoothed labels."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return ((1 - eps) * nll + eps * (-logp.mean(-1))).mean()

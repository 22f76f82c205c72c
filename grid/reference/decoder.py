"""GPT-2's forward pass (Radford et al. 2019), plain: learned token and
position embeddings, pre-LN blocks of causal multi-head attention and a
4x GELU (tanh form, GPT-2's ``gelu_new``) feed-forward, a final layer norm,
and the output projection tied to the token embedding. Float32 throughout
at ``jax.default_matmul_precision("highest")``; no cache, no batching, no
kernels.

Departure from the published model, because the served parameter set has
none: the attention projections carry no bias (GPT-2's are there and are
zero at initialisation).

The parameter tree is the served one (``tok_emb``, ``pos_emb``, ``lnf_*``
and per layer ``ln1_*``, ``wq wk wv wo``, ``ln2_*``, ``w1 b1 w2 b2``), cast
to float32 here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary. Greedy
# decoding of random weights ties at the top: served in bf16, the worst gap
# read on the chip was 0.046 (26 sampled requests over 13 runs of
# gpt2s-doc-steady; PERF.md, Findings), and the margin is about twice that.
# What it catches, as far as it was run (PERF.md, Findings: float32 on the
# CPU at full width, weights only): rounding the weights to fp8 e4m3 puts
# the greedy token 0.12-0.31 below in every 64-token output and fails;
# per-channel int8 weights read 0.04-0.09 and would pass. A step that also
# rounds activations was not run.
LOGIT_MARGIN = 0.1


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(params: Dict[str, Any], n_head: int, tokens) -> jnp.ndarray:
    """Logits ``[S, V]`` of one sequence ``tokens [S]``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[0]
    d = p["tok_emb"].shape[1]
    dh = d // n_head
    x = p["tok_emb"][tokens] + p["pos_emb"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for lp in p["layers"]:
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q = (h @ lp["wq"]).reshape(s, n_head, dh)
        k = (h @ lp["wk"]).reshape(s, n_head, dh)
        v = (h @ lp["wv"]).reshape(s, n_head, dh)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + att.reshape(s, d) @ lp["wo"]
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        x = x + _gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    return _ln(x, p["lnf_g"], p["lnf_b"]) @ p["tok_emb"].T


@functools.partial(jax.jit, static_argnums=(1,))
def _gaps(params, n_head, tokens, chosen):
    """For each position, how far the ``chosen`` next token ranks below the
    row's best logit, in row standard deviations."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, n_head, tokens)
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return (logits.max(-1) - picked) / logits.std(-1)


def worst_margin(params, model: Dict[str, Any], prompt: Sequence[int],
                 output: List[int]) -> float:
    """Teacher-forced in ONE forward over prompt + output: a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is the
    row from which the j-th output token was chosen. The sequence is padded
    to ``n_positions`` (causality keeps the padding out of every row that
    is read), so every request shares one compiled forward. Returns the
    worst gap over the output's tokens."""
    seq = list(prompt) + list(output[:-1])
    size = int(model["n_positions"])
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    chosen = np.zeros((size,), np.int32)
    first = len(prompt) - 1
    chosen[first:first + len(output)] = output
    gaps = np.asarray(_gaps(params, int(model["n_head"]), jnp.asarray(toks),
                            jnp.asarray(chosen)))
    return float(gaps[first:first + len(output)].max())

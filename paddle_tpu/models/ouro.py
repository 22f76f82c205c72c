"""Ouro's looped decoder as pure JAX functions under the serving contract
(``models.blocks.ServedLM``), so the same ``ServingEngine``, scheduler,
page pool and paged-attention kernel serve it. The plain float32 statement
of the same equations, which the tests and the benchmark compare this
with, is ``grid/reference/ouro.py``; read the layer there.

A LOOPED language model: the same ``n_layer`` layers run ``ut_steps`` times
over a token, the one final norm after every step and its output carried
into the next, and an exit gate reads each step's state. What is
particular to serving it:

* one layer's WEIGHTS own ``ut_steps`` CACHE layers (``cfg.cache_steps``):
  step t of layer l attends over what step t of layer l wrote at every
  earlier position, so an exact decode keeps K and V a (step, layer). The
  cache's ``step=`` says which (``serving.kv_cache``); ``kept`` from
  prefill is ``(k, v)`` [steps, B, S, H, D] a layer;
* the steps are ONE device loop (``lax.fori_loop`` in decode, ``lax.scan``
  in prefill, whose ``ys`` are the steps' K and V) over a body of the
  ``n_layer`` layers: the executables hold each layer's body once, not
  once a step, and the step index the cache is given is TRACED. The page
  pool is the decode loop's carry;
* sandwich norms: a sublayer's OUTPUT is normed too before it joins the
  residual, so the gains of those norms (``g2``, ``g4``) set what a
  sublayer adds whatever its projections' scale;
* the exit gate ``lambda_t = sigmoid(x_t . w_e + b_e)`` gives the
  distribution ``p_t`` over the step a token would leave at. With
  ``exit_threshold`` 1 (the published setting) it selects nothing: every
  token runs every step and the last state is read. The decode step still
  computes it, for the counter ``ut_expected_exit_step`` and the probe
  ``ut_exit_p`` (the four ``p_t`` a slot, zeros where a slot is not live);
* plain multi-head attention (``n_head`` = ``n_kv_head``: one query head a
  KV head in the paged kernel), rotate-half RoPE over the whole head, a
  dense SwiGLU, an untied head over the already-normed last state.
"""

from __future__ import annotations

import math
from typing import Dict, Mapping

import jax
import jax.numpy as jnp

from ..ops import attention_ops
from .blocks import ServedLM, rms_norm, rope, seeded_params, swiglu

__all__ = ["OuroConfig", "OuroLM", "SEED_RMS", "exit_distribution",
           "init_params"]

# What :func:`init_params` seeds: a projection's OUTPUT deviation for an
# input of deviation 1 (its weights' is that over ``sqrt(fan_in)``), the
# gains of the two output norms a layer (``post_gain``: what a sublayer
# adds to a residual that the final norm holds at 1 a step: 2 x 48 of them
# move the first step's state by 0.8 of its length and the later steps'
# by less, 0.7, 0.4 and 0.2, as the loop nears a fixed point), the
# embedding's rows, and the gate's weights and bias (a pre-activation of
# deviation 1 around -1: exits spread over the four steps).
SEED_RMS = {"embedding": 1.0, "q": 1.5, "k": 1.5, "v": 1.0, "attn_out": 1.0,
            "mlp_gate": 1.0, "mlp_up": 1.0, "mlp_down": 1.0,
            "post_gain": 0.1, "gate": 1.0, "gate_bias": -1.0}
_SEEDED_STD = 0.02      # what ``blocks.seeded_params`` draws the embedding at


class OuroConfig:
    """Static hyperparameters, under this package's names. ``n_layer``
    counts the WEIGHTS' layers; ``ut_steps`` how often they run over a
    token, which is also ``cache_steps``, what the engine reads: the cache
    layers a layer keeps, one a step (the family's last-step reuse, ONE
    for all the steps, is an approximation and a different result: not
    served)."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, n_kv_head: int, d_head: int, d_ff: int,
                 ut_steps: int = 4, exit_threshold: float = 1.0,
                 rope_theta: float = 1e6, rms_eps: float = 1e-6,
                 max_seq: int = 1024, dtype="float32",
                 seed_rms: Mapping[str, float] = None):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.d_head = int(d_head)
        self.d_ff = int(d_ff)
        self.ut_steps = int(ut_steps)
        self.exit_threshold = float(exit_threshold)
        if self.n_head % self.n_kv_head or self.ut_steps < 1:
            raise ValueError("%d query heads over %d KV heads, %d steps"
                             % (self.n_head, self.n_kv_head, self.ut_steps))
        if self.exit_threshold < 1.0:
            raise ValueError(
                "exit_threshold %g: a token that leaves the loop early "
                "needs a batch whose slots stop at different depths, which "
                "the decode step does not have" % self.exit_threshold)
        self.cache_steps = self.ut_steps
        self.rope_theta = float(rope_theta)
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.seed_rms = dict(SEED_RMS, **(seed_rms or {}))
        self.inv_freq = self.rope_theta ** (
            -jnp.arange(self.d_head // 2, dtype=jnp.float32) * 2.0
            / self.d_head)
        self.sm_scale = self.d_head ** -0.5

    def __repr__(self):
        return ("OuroConfig(V=%d, L=%d x %d steps, d=%d, Hq=%d, Hkv=%d, "
                "D=%d, ff=%d, %s)"
                % (self.vocab_size, self.n_layer, self.ut_steps,
                   self.d_model, self.n_head, self.n_kv_head, self.d_head,
                   self.d_ff, self.dtype))


def _init_layer(cfg: OuroConfig, key) -> Dict:
    d, f, dt, rms = cfg.d_model, cfg.d_ff, cfg.dtype, cfg.seed_rms
    hq, hkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    k = jax.random.split(key, 7)

    def proj(kk, fan_in, fan_out, target):
        # drawn in the served type: no float32 copy of a 100 MB layer
        return (target / math.sqrt(fan_in)) * jax.random.normal(
            kk, (fan_in, fan_out), dt)

    post = jnp.full((d,), rms["post_gain"], dt)
    return {"g1": jnp.ones((d,), dt), "g2": post,
            "g3": jnp.ones((d,), dt), "g4": post,
            "wq": proj(k[0], d, hq, rms["q"]),
            "wk": proj(k[1], d, hkv, rms["k"]),
            "wv": proj(k[2], d, hkv, rms["v"]),
            "wo": proj(k[3], hq, d, rms["attn_out"]),
            "wg": proj(k[4], d, f, rms["mlp_gate"]),
            "wu": proj(k[5], d, f, rms["mlp_up"]),
            "wd": proj(k[6], f, d, rms["mlp_down"])}


def init_params(cfg: OuroConfig, seed) -> Dict:
    """Seeded random weights through ``blocks.seeded_params`` (made on the
    device, a layer a call, in ``cfg.dtype``): a layer's seven projections
    and four gains, the ONE final norm's ``gf``, the untied head, the
    embedding scaled to ``seed_rms["embedding"]``, and the exit gate's
    ``w_e`` [d] and ``b_e`` (float32: one row)."""
    params = seeded_params(cfg, seed, _init_layer, lambda i: ())
    rms = cfg.seed_rms
    params["tok_emb"] = jax.jit(
        lambda e: e * jnp.asarray(rms["embedding"] / _SEEDED_STD, e.dtype),
        donate_argnums=0)(params["tok_emb"])
    gate_key = jax.random.fold_in(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    params["w_e"] = (rms["gate"] / math.sqrt(cfg.d_model)) \
        * jax.random.normal(gate_key, (cfg.d_model,), jnp.float32)
    params["b_e"] = jnp.asarray(rms["gate_bias"], jnp.float32)
    return params


def head(params, cfg: OuroConfig, x):
    """The untied head over the last step's state, which the loop's final
    norm has normed already; the logits float32."""
    with jax.named_scope("head"):
        return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def exit_distribution(lam):
    """``p`` [..., T] of the gates ``lam`` [..., T]: ``p_t = lam_t prod_{j<t}
    (1 - lam_j)``, and the last step takes what is left, ``p_{T-1} =
    prod_{j<T-1} (1 - lam_j)``."""
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]],
                           axis=-1)


def _gate(params, x):
    """``lambda`` [...] float32 of a step's normed state ``x`` [..., d]."""
    with jax.named_scope("loop/gate"):
        return jax.nn.sigmoid(jnp.sum(
            x.astype(jnp.float32) * params["w_e"], axis=-1) + params["b_e"])


def _qkv(cfg, lp, x, pos):
    """``(q, k, v)`` [..., heads, D] of the residual ``x``, q and k
    rotated at ``pos``."""
    a = rms_norm(x, lp["g1"], cfg.rms_eps)
    lead = a.shape[:-1]
    q = (a @ lp["wq"]).reshape(lead + (cfg.n_head, cfg.d_head))
    k = (a @ lp["wk"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    v = (a @ lp["wv"]).reshape(lead + (cfg.n_kv_head, cfg.d_head))
    return rope(q, pos, cfg.inv_freq), rope(k, pos, cfg.inv_freq), v


def _attn_out(cfg, lp, x, o):
    """The residual after attention's output ``o`` [..., H, D]: projected,
    then NORMED (the sandwich norm), then added."""
    return x + rms_norm(o.reshape(o.shape[:-2] + (-1,)) @ lp["wo"],
                        lp["g2"], cfg.rms_eps)


def _mlp(cfg, lp, x):
    with jax.named_scope("mlp/loop"):
        b = rms_norm(x, lp["g3"], cfg.rms_eps)
        return x + rms_norm(swiglu(b, lp["wg"], lp["wu"], lp["wd"]),
                            lp["g4"], cfg.rms_eps)


def prefill_forward(params: Dict, cfg: OuroConfig, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]: the
    steps as one ``lax.scan`` over the layers' body. Returns ``(x [B, S, d]
    after the last step's final norm: what :func:`head` takes, kept)`` with
    ``kept`` a layer ``(k, v)`` [steps, B, S, Hkv, D], a cache layer a
    step."""
    b, s = tokens.shape
    pos = jnp.arange(s)[None]

    def step(x, _):
        kept = []
        with jax.named_scope("loop/step"):
            for lp in params["layers"]:
                with jax.named_scope("attn/loop"):
                    q, k, v = _qkv(cfg, lp, x, pos)
                    o = jnp.stack([attention_ops.gqa_causal_attention(
                        q[j], k[j], v[j], cfg.sm_scale) for j in range(b)])
                    x = _attn_out(cfg, lp, x, o)
                kept.append((k, v))
                x = _mlp(cfg, lp, x)
            x = rms_norm(x, params["gf"], cfg.rms_eps)
        return x, kept

    return jax.lax.scan(step, params["tok_emb"][tokens], None,
                        length=cfg.ut_steps)


def decode_forward(params: Dict, cfg: OuroConfig, cache, cache_ops, tokens,
                   pos, active):
    """One decode position a slot through ``cache_ops``: the steps as one
    ``lax.fori_loop`` whose body is the layers and whose carry holds the
    cache; layer l at step t writes and attends over ITS cache layer
    (``step=t``, traced). Returns ``(logits [B, V] float32, cache,
    stats)``: ``ut_expected_exit_step`` (``sum_t (t + 1) p_t`` x 100, mean
    over the live slots), the probe ``ut_exit_p`` [B, steps], and what ONE
    cache layer read, ``attn_rows_read.global`` (every row of a live
    context: ``attn_rows_context.global`` reads the same)."""
    steps = cfg.ut_steps

    def step(t, carry):
        x, cache, lam = carry
        with jax.named_scope("loop/step"):
            for i, lp in enumerate(params["layers"]):
                with jax.named_scope("attn/loop"):
                    q, k, v = _qkv(cfg, lp, x, pos)
                    cache = cache_ops.write_token(cache, i, k, v, pos,
                                                  active, step=t)
                    o = cache_ops.decode_attention(
                        cache, i, q, pos + 1, active, sm_scale=cfg.sm_scale,
                        step=t)
                    x = _attn_out(cfg, lp, x, o)
                x = _mlp(cfg, lp, x)
            x = rms_norm(x, params["gf"], cfg.rms_eps)
            lam = jax.lax.dynamic_update_index_in_dim(
                lam, _gate(params, x), t, axis=1)
        return x, cache, lam

    x, cache, lam = jax.lax.fori_loop(
        0, steps, step, (params["tok_emb"][tokens], cache,
                         jnp.zeros((tokens.shape[0], steps), jnp.float32)))
    p = jnp.where(active[:, None], exit_distribution(lam), 0.0)
    expected = jnp.sum(p * jnp.arange(1, steps + 1, dtype=jnp.float32))
    rows = cache_ops.rows_read(pos + 1, active)
    return head(params, cfg, x), cache, {
        "ut_expected_exit_step": jnp.round(
            100.0 * expected / jnp.maximum(jnp.sum(active), 1)
        ).astype(jnp.int32),
        "ut_exit_p": p,
        "attn_rows_context.global": rows["attn_rows_read.global"], **rows}


class OuroLM(ServedLM):
    """The serving contract over :class:`OuroConfig`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)
    head = staticmethod(head)

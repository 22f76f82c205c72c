"""Falcon-H1's decoder as pure JAX functions under the serving contract
(``models.blocks.ServedLM``), so the same ``ServingEngine``, scheduler and
page pool serve it. The plain float32 statement of the same equations,
which the tests and the benchmark compare this with, is
``grid/reference/falcon_h1.py``; read the layer there.

A PARALLEL hybrid: every block has a Mamba-2 (SSD) mixer AND a grouped-query
attention mixer that read ONE normed input, and their outputs are added
before the residual. What is particular to serving it:

* every layer stands in TWO cache groups (``cfg.cache_groups``;
  ``serving.kv_cache``): its attention keeps K and V rows a token in pages
  (``n_kv_head`` heads of ``d_head``), its SSM keeps, a SLOT, one ``[H, N,
  P]`` float32 state and the last three inputs of a four-tap convolution
  over ``d_ssm + 2 G N`` channels (``cfg.slot_state``), whatever the
  context's length. ``kept`` from prefill is ``((k, v), (state, tail))`` a
  layer;
* the SSM's PREFILL is a chunk-wise scan
  (``ops/pallas_kernels/ssd.ssd_chunk_scan``, chunks of
  ``mamba_chunk_size``) that hands the cache the state the prompt leaves
  and the convolution's tail; the bucket's padding is given a log-decay of
  0 and an input of 0, so it leaves the state as the last prompt token
  left it. Its DECODE step reads the slot's tail, then streams the slot's
  state through ``cache_ops.state_step`` (the ``ssd_state_step`` kernel)
  once a layer; the step, the decay and the state are float32;
* fourteen muP multipliers from the published config (``cfg.mup``) sit
  where the reference applies them, each as a multiply on an ACTIVATION:
  none is folded into a weight, so a checkpoint's weights would be served
  as they are stored;
* attention is plain GQA (``n_head / n_kv_head`` query heads a KV head
  through the paged kernel's grouped fold), rotate-half RoPE over the whole
  head; the feed-forward half a dense SwiGLU; the head is not tied and its
  logits are float32 (``head``).
"""

from __future__ import annotations

import math
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from .. import compile_cache as _cc
from ..ops import attention_ops
from ..ops.pallas_kernels import ssd as ssd_ops
from ..serving.kv_cache import KV, STATE
from .blocks import (ServedLM, causal_conv_prefill, causal_conv_step,
                     gated_group_norm, rms_norm, rope)

__all__ = ["FalconH1Config", "FalconH1LM", "MUP_KEYS", "SEED_RMS",
           "init_params"]

# the published config's multipliers, by their keys there
MUP_KEYS = ("embedding_multiplier", "lm_head_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "mlp_multipliers")

# What :func:`init_params` seeds a projection to: the deviation of its
# output AFTER its multipliers for an input of deviation 1 (a weight's own
# deviation is that over ``sqrt(fan_in)`` and over the multipliers), so that
# at the PUBLISHED multipliers the pre-activations are of order 1 and the
# SSM branch, the attention branch and the MLP each add a comparable share
# of the residual's norm. With a plain normal(0, 0.02) the 0.0375 and 0.088
# output multipliers would leave the mixers invisible beside the embedding.
SEED_RMS = {"embedding": 1.0, "ssm_in": 1.0, "q": 1.5, "k": 1.5, "v": 1.0,
            "attn_out": 0.5, "ssm_out": 0.3, "mlp_gate": 1.0, "mlp_up": 1.0,
            "mlp_down": 0.5, "head": 1.0, "conv": 0.5, "conv_bias": 0.1}


class FalconH1Config:
    """Static hyperparameters, under this package's names. ``n_head`` query
    heads over ``n_kv_head`` KV heads of ``d_head``; the SSM has
    ``ssm_heads`` heads of ``ssm_head_dim`` channels (``d_ssm`` in all) in
    ``ssm_groups`` groups that share ``B`` and ``C`` of ``ssm_state``
    lanes; ``mup`` holds the published multipliers under ``MUP_KEYS``."""

    def __init__(self, vocab_size: int, n_layer: int, d_model: int,
                 n_head: int, n_kv_head: int, d_head: int, d_ff: int,
                 ssm_heads: int, ssm_head_dim: int, ssm_groups: int,
                 ssm_state: int, mup: Mapping[str, object],
                 conv_taps: int = 4, chunk: int = ssd_ops.CHUNK,
                 rope_theta: float = 1e11, rms_eps: float = 1e-5,
                 max_seq: int = 8192, dtype="float32",
                 seed_rms: Mapping[str, float] = None,
                 dt_range: Tuple[float, float] = (0.001, 0.1),
                 a_range: Tuple[float, float] = (1.0, 16.0)):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.d_model = int(d_model)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.d_head = int(d_head)
        self.d_ff = int(d_ff)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_groups, self.ssm_state = int(ssm_groups), int(ssm_state)
        if self.ssm_heads % self.ssm_groups or self.n_head % self.n_kv_head:
            raise ValueError(
                "%d SSM heads in %d groups, %d query heads over %d KV heads: "
                "each must divide" % (self.ssm_heads, self.ssm_groups,
                                      self.n_head, self.n_kv_head))
        self.d_ssm = self.ssm_heads * self.ssm_head_dim
        self.d_conv = self.d_ssm + 2 * self.ssm_groups * self.ssm_state
        self.conv_taps = int(conv_taps)
        self.chunk = int(chunk)
        missing = [k for k in MUP_KEYS if k not in mup]
        if missing or len(mup["ssm_multipliers"]) != 5 \
                or len(mup["mlp_multipliers"]) != 2:
            raise ValueError("mup needs %s (five ssm_multipliers, two "
                             "mlp_multipliers); missing %s"
                             % (list(MUP_KEYS), missing))
        self.mup = {k: (tuple(float(v) for v in mup[k])
                        if isinstance(mup[k], (list, tuple))
                        else float(mup[k])) for k in MUP_KEYS}
        self.rope_theta = float(rope_theta)
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.seed_rms = dict(SEED_RMS, **(seed_rms or {}))
        self.dt_range = (float(dt_range[0]), float(dt_range[1]))
        self.a_range = (float(a_range[0]), float(a_range[1]))
        self.inv_freq = self.rope_theta ** (
            -jnp.arange(self.d_head // 2, dtype=jnp.float32) * 2.0
            / self.d_head)
        self.sm_scale = self.d_head ** -0.5

    @property
    def in_segments(self) -> Tuple[int, ...]:
        """The widths of the SSM input projection's five segments ``[z | x |
        B | C | dt]``, which ``ssm_multipliers`` scale in this order."""
        gn = self.ssm_groups * self.ssm_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    @property
    def slot_state(self) -> Tuple[int, int, int, int, int]:
        """What a layer's SSM keeps a SLOT: ``(heads, dk = N, dv = P, tail
        rows, tail width)``."""
        return (self.ssm_heads, self.ssm_state, self.ssm_head_dim,
                self.conv_taps - 1, self.d_conv)

    state_recurrence = "ssd"

    @property
    def cache_groups(self):
        """EVERY layer twice: its K and V pages (admission counts these),
        then its state a slot."""
        layers = tuple(range(self.n_layer))
        return [("global", layers, None, KV), ("ssm", layers, None, STATE)]

    def __repr__(self):
        return ("FalconH1Config(V=%d, L=%d, d=%d, Hq=%d, Hkv=%d, D=%d, "
                "ssm %d heads x %d in %d groups of state %d, ff=%d, %s)"
                % (self.vocab_size, self.n_layer, self.d_model, self.n_head,
                   self.n_kv_head, self.d_head, self.ssm_heads,
                   self.ssm_head_dim, self.ssm_groups, self.ssm_state,
                   self.d_ff, self.dtype))


def _mup_vector(cfg: FalconH1Config):
    """``ssm_in_multiplier`` times ``ssm_multipliers`` laid over the input
    projection's columns, float32 [sum(in_segments)]."""
    return jnp.concatenate([
        jnp.full((w,), cfg.mup["ssm_in_multiplier"] * m, jnp.float32)
        for w, m in zip(cfg.in_segments, cfg.mup["ssm_multipliers"])])


def _normal(key, shape, std, dtype):
    # drawn in the served type: no float32 copy of a 10 GB tree
    return std * jax.random.normal(key, shape, dtype)


def _init_layer(cfg: FalconH1Config, key) -> Dict:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    rms, mup = cfg.seed_rms, cfg.mup
    k = jax.random.split(key, 16)
    hq, hkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head

    def proj(kk, fan_in, fan_out, target, *mults):
        return _normal(kk, (fan_in, fan_out),
                       target / (math.sqrt(fan_in) * math.prod(mults)), dt)

    w_in = jnp.concatenate([
        proj(kk, d, w, rms["ssm_in"], mup["ssm_in_multiplier"], m)
        for kk, w, m in zip(jax.random.split(k[0], 5), cfg.in_segments,
                            mup["ssm_multipliers"])], axis=1)
    lo, hi = cfg.dt_range
    step = lo * (hi / lo) ** jax.random.uniform(k[1], (cfg.ssm_heads,),
                                                jnp.float32)
    a_lo, a_hi = cfg.a_range
    att_in = mup["attention_in_multiplier"]
    return {
        "g1": jnp.ones((d,), dt), "g2": jnp.ones((d,), dt),
        "w_in": w_in,
        "cw": _normal(k[2], (cfg.conv_taps, cfg.d_conv), rms["conv"], dt),
        "cb": _normal(k[3], (cfg.d_conv,), rms["conv_bias"], dt),
        # the inverse softplus of the step: softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jax.random.uniform(
            k[4], (cfg.ssm_heads,), jnp.float32, a_lo, a_hi)),
        "dskip": jnp.ones((cfg.ssm_heads,), jnp.float32),
        "gn": jnp.ones((cfg.d_ssm,), dt),
        "w_out": proj(k[5], cfg.d_ssm, d, rms["ssm_out"],
                      mup["ssm_out_multiplier"]),
        "wq": proj(k[6], d, hq, rms["q"], att_in),
        "wk": proj(k[7], d, hkv, rms["k"], att_in, mup["key_multiplier"]),
        "wv": proj(k[8], d, hkv, rms["v"], att_in),
        "wo": proj(k[9], hq, d, rms["attn_out"],
                   mup["attention_out_multiplier"]),
        "wg": proj(k[10], d, f, rms["mlp_gate"], mup["mlp_multipliers"][0]),
        "wu": proj(k[11], d, f, rms["mlp_up"]),
        "wd": proj(k[12], f, d, rms["mlp_down"], mup["mlp_multipliers"][1]),
    }


_VOCAB_BLOCKS = 8


def _vocab_matrix(key, rows: int, cols: int, std: float, dtype, by_rows: bool):
    """A ``[rows, cols]`` normal matrix drawn a block of the vocabulary at
    a time into one buffer: at 261,120 x 5,120 the generator's temporaries
    for the whole matrix would be twice the matrix."""
    n_vocab = rows if by_rows else cols
    blocks = _VOCAB_BLOCKS if n_vocab % _VOCAB_BLOCKS == 0 else 1
    size = n_vocab // blocks

    def fill(i, buf):
        shape = (size, cols) if by_rows else (rows, size)
        part = _normal(jax.random.fold_in(key, i), shape, std, dtype)
        at = (i * size, 0) if by_rows else (0, i * size)
        return jax.lax.dynamic_update_slice(buf, part, at)

    return jax.lax.fori_loop(0, blocks, fill, jnp.zeros((rows, cols), dtype))


@_cc.in_phase("startup/weights")
def init_params(cfg: FalconH1Config, seed) -> Dict:
    """Seeded random weights, made where JAX computes (the device), in
    ``cfg.dtype``, one layer a call. Each projection's deviation follows
    ``cfg.seed_rms`` (:data:`SEED_RMS` says why); Mamba-2's own seeds: the
    step ``softplus(dt_bias)`` log-uniform over ``cfg.dt_range`` a head,
    ``A = -exp(a_log)`` with ``exp(a_log)`` uniform over ``cfg.a_range``,
    ``D`` ones, the gated norm's and the RMS norms' gains ones."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    v, d, rms = cfg.vocab_size, cfg.d_model, cfg.seed_rms
    matrix = jax.jit(_vocab_matrix, static_argnums=(1, 2, 3, 4, 5))
    layer = jax.jit(lambda k: _init_layer(cfg, k))
    return {
        "tok_emb": matrix(keys[0], v, d, rms["embedding"]
                          / cfg.mup["embedding_multiplier"], cfg.dtype, True),
        "head": matrix(keys[1], d, v, rms["head"] / (
            math.sqrt(d) * cfg.mup["lm_head_multiplier"]), cfg.dtype, False),
        "gf": jnp.ones((d,), cfg.dtype),
        "layers": [layer(keys[2 + i]) for i in range(cfg.n_layer)]}


def head(params, cfg: FalconH1Config, x):
    """The final RMSNorm and the untied head, the logits float32, times
    ``lm_head_multiplier``."""
    with jax.named_scope("lm_head"):
        return jnp.dot(rms_norm(x, params["gf"], cfg.rms_eps),
                       params["head"], preferred_element_type=jnp.float32) \
            * cfg.mup["lm_head_multiplier"]


def _ssm_in(cfg, lp, u):
    """The SSM's input projection with its multipliers: ``(z [..., d_ssm]
    in the served type, xBC [..., d_conv] in the served type (what the
    convolution reads and the tail keeps), dt pre-activation [..., H]
    float32)``."""
    with jax.named_scope("mixer/ssm_in"):
        p = jnp.dot(u, lp["w_in"], preferred_element_type=jnp.float32) \
            * _mup_vector(cfg)
        z, xbc, dt = jnp.split(p, (cfg.d_ssm, cfg.d_ssm + cfg.d_conv),
                               axis=-1)
        return z.astype(u.dtype), xbc.astype(u.dtype), dt


def _ssd_inputs(cfg, lp, conv, dt):
    """What the recurrence reads of the convolution's output ``conv`` [...,
    d_conv] float32 and the step's pre-activation ``dt`` [..., H]: ``(x
    [..., H, P], x dt, B, C [..., G, N], a = dt A [..., H])``, float32."""
    lead = conv.shape[:-1]
    gn = cfg.ssm_groups * cfg.ssm_state
    x, b, c = jnp.split(conv, (cfg.d_ssm, cfg.d_ssm + gn), axis=-1)
    x = x.reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim))
    b, c = (t.reshape(lead + (cfg.ssm_groups, cfg.ssm_state))
            for t in (b, c))
    # the family's time_step_limit is (0, inf): no clamp
    step = jax.nn.softplus(dt + lp["dt_bias"])
    return x, x * step[..., None], b, c, -jnp.exp(lp["a_log"]) * step


def _ssm_out(cfg, lp, y, x, z):
    """``(W_out grouped_rmsnorm((y + D x) * silu(z))) * ssm_out_multiplier``
    of the recurrence's ``y`` and the heads' inputs ``x`` [..., H, P]
    float32."""
    with jax.named_scope("mixer/ssm_norm"):
        y = y + lp["dskip"][:, None] * x
        y = gated_group_norm(y.reshape(y.shape[:-2] + (cfg.d_ssm,)), z,
                             lp["gn"], cfg.ssm_groups, cfg.rms_eps)
    with jax.named_scope("mixer/out/ssm"):
        return (y @ lp["w_out"]) * jnp.asarray(
            cfg.mup["ssm_out_multiplier"], y.dtype)


def _qkv(cfg, lp, u):
    """``(q, k, v)`` [..., heads, D] of the normed input ``u``, with the
    attention's input multiplier and the keys' own."""
    m = cfg.mup["attention_in_multiplier"]
    lead = u.shape[:-1]
    q = (u @ lp["wq"]) * jnp.asarray(m, u.dtype)
    k = (u @ lp["wk"]) * jnp.asarray(m * cfg.mup["key_multiplier"], u.dtype)
    v = (u @ lp["wv"]) * jnp.asarray(m, u.dtype)
    return (q.reshape(lead + (cfg.n_head, cfg.d_head)),
            k.reshape(lead + (cfg.n_kv_head, cfg.d_head)),
            v.reshape(lead + (cfg.n_kv_head, cfg.d_head)))


def _attn_out(cfg, lp, o):
    with jax.named_scope("mixer/out/attn"):
        return (o.reshape(o.shape[:-2] + (-1,)) @ lp["wo"]) * jnp.asarray(
            cfg.mup["attention_out_multiplier"], o.dtype)


def _mlp(cfg, lp, x):
    with jax.named_scope("mlp"):
        u = rms_norm(x, lp["g2"], cfg.rms_eps)
        g0, g1 = cfg.mup["mlp_multipliers"]
        gate = jax.nn.silu((u @ lp["wg"]) * jnp.asarray(g0, u.dtype))
        return x + ((gate * (u @ lp["wu"])) @ lp["wd"]) * jnp.asarray(
            g1, u.dtype)


def _ssm_prefill(cfg, lp, u, length):
    """One sequence's SSM branch: ``u`` [S, d] normed, ``length`` its valid
    rows. Returns ``(m [S, d], state [H, N, P] float32, tail [taps - 1,
    d_conv])``."""
    z, xbc, dt = _ssm_in(cfg, lp, u)
    with jax.named_scope("mixer/ssm_conv"):
        conv, tail = causal_conv_prefill(xbc, lp["cw"], lp["cb"], length)
    x, xdt, b, c, a = _ssd_inputs(cfg, lp, conv, dt)
    valid = jnp.arange(u.shape[0]) < length
    with jax.named_scope("mixer/ssm_scan"):
        y, state = ssd_ops.ssd_chunk_scan(
            jnp.where(valid[:, None, None], xdt, 0.0), b, c,
            jnp.where(valid[:, None], a, 0.0), chunk=cfg.chunk)
    return _ssm_out(cfg, lp, y, x, z), state, tail


def prefill_forward(params: Dict, cfg: FalconH1Config, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d] before the final norm, kept)`` with ``kept`` a layer
    ``((k, v) [B, S, Hkv, D], (state [B, H, N, P], tail [B, taps - 1,
    d_conv]))``: the contract's order for a layer in a paged and a state
    group."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens] * jnp.asarray(
        cfg.mup["embedding_multiplier"], cfg.dtype)
    pos = jnp.arange(s)
    kept = []
    for lp in params["layers"]:
        u = rms_norm(x, lp["g1"], cfg.rms_eps)
        ms, states, tails = zip(*(_ssm_prefill(cfg, lp, u[j], lengths[j])
                                  for j in range(b)))
        with jax.named_scope("mixer/attn"):
            q, k, v = _qkv(cfg, lp, u)
            q = rope(q, pos[None], cfg.inv_freq)
            k = rope(k, pos[None], cfg.inv_freq)
            o = jnp.stack([attention_ops.gqa_causal_attention(
                q[j], k[j], v[j], cfg.sm_scale) for j in range(b)])
        kept.append(((k, v), (jnp.stack(states), jnp.stack(tails))))
        x = _mlp(cfg, lp, x + jnp.stack(ms) + _attn_out(cfg, lp, o))
    return x, kept


def decode_forward(params: Dict, cfg: FalconH1Config, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``: every layer
    advances the slot's convolution tail and state AND writes its K and V
    row and attends over its pages. Returns ``(logits [B, V] float32,
    cache, stats)``: ``state_slots_stepped`` (the live slots, whose states
    every layer advanced) and the cache's ``attn_rows_read.global``."""
    x = params["tok_emb"][tokens] * jnp.asarray(
        cfg.mup["embedding_multiplier"], cfg.dtype)
    for i, lp in enumerate(params["layers"]):
        u = rms_norm(x, lp["g1"], cfg.rms_eps)
        z, xbc, dt = _ssm_in(cfg, lp, u)
        with jax.named_scope("mixer/ssm_conv"):
            window, cache = cache_ops.tail_step(cache, i, xbc, active)
            conv = causal_conv_step(window, lp["cw"], lp["cb"])
        xh, xdt, b, c, a = _ssd_inputs(cfg, lp, conv, dt)
        with jax.named_scope("mixer/ssm_step"):
            y, cache = cache_ops.state_step(cache, i, xdt, b, c, a, active)
        m = _ssm_out(cfg, lp, y, xh, z)
        with jax.named_scope("mixer/attn"):
            q, k, v = _qkv(cfg, lp, u)
            q, k = rope(q, pos, cfg.inv_freq), rope(k, pos, cfg.inv_freq)
            cache = cache_ops.write_token(cache, i, k, v, pos, active)
            o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                           sm_scale=cfg.sm_scale)
        x = _mlp(cfg, lp, x + m + _attn_out(cfg, lp, o))
    return head(params, cfg, x), cache, {
        "state_slots_stepped": jnp.sum(active).astype(jnp.int32),
        **cache_ops.rows_read(pos + 1, active)}


class FalconH1LM(ServedLM):
    """The serving contract over :class:`FalconH1Config`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)
    head = staticmethod(head)

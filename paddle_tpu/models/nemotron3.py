"""NVIDIA-Nemotron-3-Nano's decoder (``model_type: nemotron_h``) as pure
JAX functions under the serving contract (``models.blocks.ServedLM``), so
the same ``ServingEngine``, scheduler and page pool serve it. The plain
float32 statement of the same equations, which the tests and the benchmark
compare this with, is ``grid/reference/nemotron3.py``; read the layers
there.

A layer of ``hybrid_override_pattern`` is ONE part with its own pre-norm
and residual, ``x <- x + part(rms_norm(x))``: ``M`` a Mamba-2 mixer, ``E``
a sparse feed-forward, ``*`` grouped-query attention. There is no block of
two. What is particular to serving it:

* an ``E`` layer keeps nothing and stands in NO cache group; an ``M`` layer
  stands in the ``STATE`` group only (a SLOT's ``[H, N, P]`` float32 state
  and the last three inputs of a four-tap convolution), a ``*`` layer in
  the ``KV`` group only (``cfg.cache_groups``, the ``KV`` group first:
  admission counts its pages). ``kept`` from prefill is, a layer, ``(state,
  tail)``, None or ``(k, v)``;
* the SSM's heads are 64 channels over 128 state lanes: half a lane tile.
  The cache keeps two heads of a group side by side
  (``ops/pallas_kernels/ssd.state_shape``: 32 x [128, 128], 2 MiB a slot a
  layer and no padding), and both SSD kernels take it; the model hands in
  and gets back ``[H, N, P]``. No muP multipliers;
* the experts are UNGATED: ``relu(u W_up)^2 W_down``, two matrices of width
  1,856 (14.5 lane tiles) an expert, through ``moe_ops.expert_layer`` with
  ``wg=None`` (the decode pass by the ``ragged_dot_stream`` kernel, two
  matrices an expert read once); ``wu`` holds each expert's ``W_up``
  TRANSPOSED, ``[E, 1856, 2688]``, so that both matrices lie with the
  hidden size in the lanes and no copy stands in front of the kernel; the router is the DeepSeek-V3 family's
  sigmoid top-k (``moe_ops.route_sigmoid_topk``, no group limit) and one
  shared expert of the same form is always on;
* attention has NO position embedding (the ``nemotron_h`` model code
  applies none in its attention layers: arXiv:2504.03624, section 2.1): 32
  query heads over 2 KV heads of 128 through the paged kernel's grouped
  fold at 16 query heads a KV head; the head is not tied and its logits
  are float32.
"""

from __future__ import annotations

import math
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import compile_cache as _cc
from ..ops import attention_ops, moe_ops
from ..ops.pallas_kernels import ssd as ssd_ops
from ..serving.kv_cache import KV, STATE
from .blocks import (ServedLM, causal_conv_prefill, causal_conv_step,
                     gated_group_norm, held_experts, moe_stats, rms_norm)

__all__ = ["Nemotron3Config", "Nemotron3LM", "KINDS", "SEED_RMS",
           "init_params"]

# the letters of ``hybrid_override_pattern``
MAMBA, MOE, ATTN = "M", "E", "*"
KINDS = (MAMBA, MOE, ATTN)

# What :func:`init_params` seeds a projection to: the deviation of its
# output for an input of deviation 1 (a weight's own deviation is that over
# ``sqrt(fan_in)``), so that pre-activations are of order 1 and, at the
# published ``routed_scaling_factor`` 2.5 and relu^2, each of the three
# kinds of part adds a comparable share of the residual's norm: a relu^2
# of a unit normal has mean square 1.5 and the six routed outputs arrive
# weighed 2.5 / 6 each, so the experts' down projections are seeded lower
# than the mixers' outputs. With a plain normal(0, 0.02) the parts would be
# invisible beside the embedding and the comparison blind to them.
SEED_RMS = {"embedding": 1.0, "ssm_in": 1.0, "ssm_out": 0.6, "conv": 0.5,
            "conv_bias": 0.1, "q": 1.5, "k": 1.5, "v": 1.0, "attn_out": 0.6,
            "router": 1.0, "up": 1.0, "down": 0.35, "shared_down": 0.25,
            "head": 1.0}


class Nemotron3Config:
    """Static hyperparameters, under this package's names. ``pattern``: a
    letter a layer (:data:`KINDS`). ``n_head`` query heads over
    ``n_kv_head`` KV heads of ``d_head``; the SSM has ``ssm_heads`` heads of
    ``ssm_head_dim`` channels (``d_ssm`` in all) in ``ssm_groups`` groups
    that share ``B`` and ``C`` of ``ssm_state`` lanes; ``n_expert`` routed
    experts of width ``d_expert`` of which ``experts_held`` are here, and a
    shared one of ``d_shared``."""

    n_group = topk_group = 1      # the router's: no group limit
    state_recurrence = "ssd"

    def __init__(self, vocab_size: int, pattern: str, d_model: int,
                 n_head: int, n_kv_head: int, d_head: int, ssm_heads: int,
                 ssm_head_dim: int, ssm_groups: int, ssm_state: int,
                 n_expert: int, top_k: int, d_expert: int, d_shared: int,
                 routed_scale: float = 1.0,
                 experts_held: Optional[Sequence[int]] = None,
                 conv_taps: int = 4, chunk: int = ssd_ops.CHUNK,
                 rms_eps: float = 1e-5, max_seq: int = 8192,
                 dtype="float32", seed_rms: Mapping[str, float] = None,
                 dt_range: Tuple[float, float] = (0.001, 0.1),
                 a_range: Tuple[float, float] = (1.0, 16.0)):
        self.vocab_size = int(vocab_size)
        self.layer_kinds = tuple(pattern)
        unknown = set(self.layer_kinds) - set(KINDS)
        if unknown or not self.layer_kinds:
            raise ValueError("a layer is one of %s; the pattern %r has %s"
                             % (KINDS, pattern, sorted(unknown)))
        self.n_layer = len(self.layer_kinds)
        self.d_model = int(d_model)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.d_head = int(d_head)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_groups, self.ssm_state = int(ssm_groups), int(ssm_state)
        if self.ssm_heads % self.ssm_groups or self.n_head % self.n_kv_head:
            raise ValueError(
                "%d SSM heads in %d groups, %d query heads over %d KV heads: "
                "each must divide" % (self.ssm_heads, self.ssm_groups,
                                      self.n_head, self.n_kv_head))
        self.d_ssm = self.ssm_heads * self.ssm_head_dim
        self.d_conv = self.d_ssm + 2 * self.ssm_groups * self.ssm_state
        self.conv_taps, self.chunk = int(conv_taps), int(chunk)
        self.n_expert, self.top_k = int(n_expert), int(top_k)
        self.d_expert, self.d_shared = int(d_expert), int(d_shared)
        self.routed_scale = float(routed_scale)
        self.experts_held = tuple(
            range(self.n_expert) if experts_held is None
            else (int(e) for e in experts_held))
        self.rms_eps = float(rms_eps)
        self.max_seq = int(max_seq)
        self.dtype = jnp.dtype(dtype)
        self.seed_rms = dict(SEED_RMS, **(seed_rms or {}))
        self.dt_range = (float(dt_range[0]), float(dt_range[1]))
        self.a_range = (float(a_range[0]), float(a_range[1]))
        self.sm_scale = self.d_head ** -0.5

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def slot_state(self) -> Tuple[int, int, int, int, int]:
        """What an ``M`` layer keeps a SLOT: ``(heads, dk = N, dv = P, tail
        rows, tail width)``."""
        return (self.ssm_heads, self.ssm_state, self.ssm_head_dim,
                self.conv_taps - 1, self.d_conv)

    @property
    def cache_groups(self):
        """The ``*`` layers' K and V pages first (admission counts these),
        then the ``M`` layers' states a slot; an ``E`` layer is in
        neither."""
        groups = [("global", self.layers_of(ATTN), None, KV),
                  ("ssm", self.layers_of(MAMBA), None, STATE)]
        return [g for g in groups if g[1]]

    def __repr__(self):
        return ("Nemotron3Config(V=%d, %s, d=%d, Hq=%d, Hkv=%d, D=%d, ssm %d "
                "heads x %d in %d groups of state %d, %d of %d experts x %d "
                "top-%d, shared %d, %s)"
                % (self.vocab_size, "".join(self.layer_kinds), self.d_model,
                   self.n_head, self.n_kv_head, self.d_head, self.ssm_heads,
                   self.ssm_head_dim, self.ssm_groups, self.ssm_state,
                   len(self.experts_held), self.n_expert, self.d_expert,
                   self.top_k, self.d_shared, self.dtype))


def _normal(key, shape, std, dtype):
    # drawn in the served type: no float32 copy of a 6 GB tree
    return std * jax.random.normal(key, shape, dtype)


def _init_layer(cfg: Nemotron3Config, key, kind: str) -> Dict:
    d, dt, rms = cfg.d_model, cfg.dtype, cfg.seed_rms
    k = jax.random.split(key, 8)

    def proj(kk, shape, target, fan_in=None):
        return _normal(kk, shape, target / math.sqrt(fan_in or shape[-2]), dt)

    lp = {"g": jnp.ones((d,), dt)}
    if kind == MAMBA:
        lo, hi = cfg.dt_range
        step = lo * (hi / lo) ** jax.random.uniform(k[1], (cfg.ssm_heads,),
                                                    jnp.float32)
        lp.update({
            "w_in": proj(k[0], (d, cfg.d_ssm + cfg.d_conv + cfg.ssm_heads),
                         rms["ssm_in"]),
            "cw": _normal(k[2], (cfg.conv_taps, cfg.d_conv), rms["conv"], dt),
            "cb": _normal(k[3], (cfg.d_conv,), rms["conv_bias"], dt),
            # the inverse softplus of the step: softplus(dt_bias) = step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(jax.random.uniform(
                k[4], (cfg.ssm_heads,), jnp.float32, *cfg.a_range)),
            "dskip": jnp.ones((cfg.ssm_heads,), jnp.float32),
            "gn": jnp.ones((cfg.d_ssm,), dt),
            "w_out": proj(k[5], (cfg.d_ssm, d), rms["ssm_out"])})
    elif kind == MOE:
        e, f = len(cfg.experts_held), cfg.d_expert
        lp.update({
            # the router's weights and bias float32, all n_expert outputs
            "wr": _normal(k[0], (d, cfg.n_expert),
                          rms["router"] / math.sqrt(d), jnp.float32),
            "br": _normal(k[1], (cfg.n_expert,), 0.02, jnp.float32),
            # an expert's W_up TRANSPOSED, [f, d]: the width is 14.5 lane
            # tiles, and stored [d, f] the chip's compiler keeps it this
            # way itself and copies it whole in front of the kernel
            "wu": proj(k[2], (e, f, d), rms["up"], fan_in=d),
            "wd": proj(k[3], (e, f, d), rms["down"]),
            "su": proj(k[4], (d, cfg.d_shared), rms["up"]),
            "sd": proj(k[5], (cfg.d_shared, d), rms["shared_down"])})
    else:
        hq, hkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
        lp.update({"wq": proj(k[0], (d, hq), rms["q"]),
                   "wk": proj(k[1], (d, hkv), rms["k"]),
                   "wv": proj(k[2], (d, hkv), rms["v"]),
                   "wo": proj(k[3], (hq, d), rms["attn_out"])})
    return lp


@_cc.in_phase("startup/weights")
def init_params(cfg: Nemotron3Config, seed) -> Dict:
    """Seeded random weights, made where JAX computes (the device), in
    ``cfg.dtype``, one layer a call (one compile a KIND of layer). Each
    projection's deviation follows ``cfg.seed_rms`` (:data:`SEED_RMS` says
    why); Mamba-2's own seeds: the step ``softplus(dt_bias)`` log-uniform
    over ``cfg.dt_range`` a head, ``A = -exp(a_log)`` with ``exp(a_log)``
    uniform over ``cfg.a_range``, ``D`` ones, every gain ones; the router's
    weights and selecting bias float32."""
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.n_layer + 2)
    v, d, rms = cfg.vocab_size, cfg.d_model, cfg.seed_rms
    layer = jax.jit(lambda k, kind: _init_layer(cfg, k, kind),
                    static_argnums=1)
    matrix = jax.jit(_normal, static_argnums=(1, 2, 3))
    return {
        "tok_emb": matrix(keys[0], (v, d), rms["embedding"], cfg.dtype),
        "head": matrix(keys[1], (d, v), rms["head"] / math.sqrt(d),
                       cfg.dtype),
        "gf": jnp.ones((d,), cfg.dtype),
        "layers": [layer(keys[2 + i], kind)
                   for i, kind in enumerate(cfg.layer_kinds)]}


def head(params, cfg: Nemotron3Config, x):
    """The final RMSNorm and the untied head, the logits float32."""
    with jax.named_scope("lm_head"):
        return jnp.dot(rms_norm(x, params["gf"], cfg.rms_eps),
                       params["head"], preferred_element_type=jnp.float32)


# -- an M layer's ends (the recurrence between them is the cache's or the
# scan's) ---------------------------------------------------------------------

def _ssm_in(cfg, lp, u):
    """``(z [..., d_ssm], xBC [..., d_conv] in the served type (what the
    convolution reads and the tail keeps), dt pre-activation [..., H]
    float32)`` of the normed input."""
    p = jnp.dot(u, lp["w_in"], preferred_element_type=jnp.float32)
    z, xbc, dt = jnp.split(p, (cfg.d_ssm, cfg.d_ssm + cfg.d_conv), axis=-1)
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
    # time_step_limit is absent from the config: (0, inf), no clamp
    step = jax.nn.softplus(dt + lp["dt_bias"])
    return x, x * step[..., None], b, c, -jnp.exp(lp["a_log"]) * step


def _ssm_out(cfg, lp, y, x, z):
    """``W_out grouped_rmsnorm((y + D x) * silu(z))`` of the recurrence's
    ``y`` and the heads' inputs ``x`` [..., H, P] float32."""
    y = y + lp["dskip"][:, None] * x
    y = gated_group_norm(y.reshape(y.shape[:-2] + (cfg.d_ssm,)), z,
                         lp["gn"], cfg.ssm_groups, cfg.rms_eps)
    return y @ lp["w_out"]


def _mamba_prefill(cfg, lp, u, length):
    """One sequence's ``M`` part: ``u`` [S, d] normed, ``length`` its valid
    rows. Returns ``(m [S, d], state [H, N, P] float32, tail [taps - 1,
    d_conv])``; the bucket's padding is given a log-decay and an input of
    0, so it leaves the state as the last prompt token left it."""
    z, xbc, dt = _ssm_in(cfg, lp, u)
    conv, tail = causal_conv_prefill(xbc, lp["cw"], lp["cb"], length)
    x, xdt, b, c, a = _ssd_inputs(cfg, lp, conv, dt)
    valid = jnp.arange(u.shape[0]) < length
    with jax.named_scope("ssm_scan"):
        y, state = ssd_ops.ssd_chunk_scan(
            jnp.where(valid[:, None, None], xdt, 0.0), b, c,
            jnp.where(valid[:, None], a, 0.0), chunk=cfg.chunk)
    return _ssm_out(cfg, lp, y, x, z), state, tail


# -- an E layer -----------------------------------------------------------------

def _moe(cfg, lp, u, row_valid):
    """The sparse feed-forward over rows ``u`` [N, d]: the routed experts
    held here (sigmoid scores over ALL ``n_expert``, the ``top_k`` largest
    of score + bias, normalised, times ``routed_scale``) plus the shared
    expert, each ``relu(u W_up)^2 W_down``. Returns ``(y [N, d], stats)``."""
    idx, w = moe_ops.route_sigmoid_topk(u, lp["wr"], lp["br"], cfg.top_k,
                                        cfg.routed_scale)
    y, stats = moe_ops.expert_layer(
        u, idx, w, None, lp["wu"], lp["wd"], n_expert=cfg.n_expert,
        held=held_experts(cfg), row_valid=row_valid,
        activation=moe_ops.relu2, transposed_up=True)
    stats = dict(stats, held_pairs=moe_ops.held_pairs(
        idx, cfg.experts_held, cfg.n_expert, row_valid))
    with jax.named_scope("moe/shared"):
        shared = moe_ops.relu2(u @ lp["su"]) @ lp["sd"]
    return (y + shared.astype(jnp.float32)).astype(u.dtype), stats


# -- a * layer ------------------------------------------------------------------

def _qkv(cfg, lp, u):
    """``(q [..., Hq, D], k, v [..., Hkv, D])``: no position embedding."""
    lead = u.shape[:-1]
    return ((u @ lp["wq"]).reshape(lead + (cfg.n_head, cfg.d_head)),
            (u @ lp["wk"]).reshape(lead + (cfg.n_kv_head, cfg.d_head)),
            (u @ lp["wv"]).reshape(lead + (cfg.n_kv_head, cfg.d_head)))


def prefill_forward(params: Dict, cfg: Nemotron3Config, tokens, lengths):
    """Causal forward over bucket-padded prompts ``tokens`` [B, S]. Returns
    ``(x [B, S, d] before the final norm, kept)`` with ``kept`` a layer
    what its cache group takes: ``(state [B, H, N, P], tail [B, taps - 1,
    d_conv])`` of an ``M`` layer, None of an ``E`` layer, ``(k, v)`` [B, S,
    Hkv, D] of a ``*`` layer."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    kept = []
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        u = rms_norm(x, lp["g"], cfg.rms_eps)
        if kind == MAMBA:
            with jax.named_scope("nemotron/mamba"):
                ms, states, tails = zip(*(
                    _mamba_prefill(cfg, lp, u[j], lengths[j])
                    for j in range(b)))
                part = jnp.stack(ms)
            kept.append((jnp.stack(states), jnp.stack(tails)))
        elif kind == MOE:
            with jax.named_scope("nemotron/moe"):
                y, _ = _moe(cfg, lp, u.reshape(b * s, -1),
                            valid.reshape(b * s))
                part = y.reshape(b, s, -1)
            kept.append(None)
        else:
            with jax.named_scope("nemotron/attn"):
                q, k, v = _qkv(cfg, lp, u)
                o = jnp.stack([attention_ops.gqa_causal_attention(
                    q[j], k[j], v[j], cfg.sm_scale) for j in range(b)])
                part = o.reshape(b, s, -1) @ lp["wo"]
            kept.append((k, v))
        x = x + part
    return x, kept


def decode_forward(params: Dict, cfg: Nemotron3Config, cache, cache_ops,
                   tokens, pos, active):
    """One decode position a slot through ``cache_ops``: an ``M`` layer
    advances the slot's convolution tail and state, an ``E`` layer touches
    no cache, a ``*`` layer writes its K and V row and attends over its
    pages. Returns ``(logits [B, V] float32, cache, stats)``:
    ``state_slots_stepped``, the expert layers' ``moe_*`` counts and the
    cache's ``attn_rows_read.global``."""
    x = params["tok_emb"][tokens]
    expert_stats = []
    for i, (kind, lp) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        u = rms_norm(x, lp["g"], cfg.rms_eps)
        if kind == MAMBA:
            with jax.named_scope("nemotron/mamba"):
                z, xbc, dt = _ssm_in(cfg, lp, u)
                window, cache = cache_ops.tail_step(cache, i, xbc, active)
                conv = causal_conv_step(window, lp["cw"], lp["cb"])
                xh, xdt, b, c, a = _ssd_inputs(cfg, lp, conv, dt)
                with jax.named_scope("ssm_step"):
                    y, cache = cache_ops.state_step(cache, i, xdt, b, c, a,
                                                    active)
                part = _ssm_out(cfg, lp, y, xh, z)
        elif kind == MOE:
            with jax.named_scope("nemotron/moe"):
                part, stats = _moe(cfg, lp, u, active)
            expert_stats.append(stats)
        else:
            with jax.named_scope("nemotron/attn"):
                q, k, v = _qkv(cfg, lp, u)
                cache = cache_ops.write_token(cache, i, k, v, pos, active)
                o = cache_ops.decode_attention(cache, i, q, pos + 1, active,
                                               sm_scale=cfg.sm_scale)
                part = o.reshape(o.shape[0], -1) @ lp["wo"]
        x = x + part
    stats = {"state_slots_stepped": jnp.sum(active).astype(jnp.int32),
             **cache_ops.rows_read(pos + 1, active)}
    if expert_stats:
        stats.update(moe_stats(expert_stats))
    return head(params, cfg, x), cache, stats


class Nemotron3LM(ServedLM):
    """The serving contract over :class:`Nemotron3Config`."""

    init_params = staticmethod(init_params)
    prefill_forward = staticmethod(prefill_forward)
    decode_forward = staticmethod(decode_forward)
    head = staticmethod(head)

"""NVIDIA-Nemotron-3-Nano's decoder, plain (the model's public
``config.json``, ``model_type: nemotron_h``). A layer of
``hybrid_override_pattern`` is ONE part with its own pre-norm and residual;
with ``d`` the hidden size (2,688), eps 1e-5 and layer input ``h`` [d] at
position ``t``:

    h' = h + part(u),  u = RMSNorm(h; g)

    M, a Mamba-2 mixer: H = 64 heads of P = 64 channels (d_ssm = 4,096),
    G = 8 groups of N = 128 state lanes, a convolution of 4 taps over C =
    d_ssm + 2 G N = 6,144 channels:
    [z | xBC | dt] = W_in u                      widths d_ssm | C | H, no bias
    xBC = silu(sum_{j=0..3} w_j xBC_{t-3+j} + b_conv)
                                                 depth-wise, zeros before the
                                                 request's start
    dt = softplus(dt + dt_bias)                  a head; no clamp
    a  = exp(dt A),  A = -exp(A_log)             one number a head
    S_t = a_t S_{t-1} + B_t (dt_t x_t)^T         S [H, N, P] float32, zero at
                                                 the start; B_t, C_t of group
                                                 h // (H / G)
    y_t = S_t^T C_t + D x_t
    y  = RMSNorm_group(y * silu(z); gn)          the gate FIRST, then an RMS
                                                 norm over each of the G
                                                 groups' 512 channels
    part = W_out y

    E, a sparse feed-forward: s = sigmoid(W_r u) over all 128 experts in
    float32; the six largest of s + b are chosen (b selects, never weighs;
    n_group = topk_group = 1: no group limit); weights s[chosen] /
    sum(s[chosen]) times routed_scaling_factor 2.5;
    expert e: relu(W_up,e u)^2 W_down,e          UNGATED, width 1,856
    part = shared(u) + sum of the weighed chosen experts HELD here
                                                 one shared expert of the
                                                 same form, width 3,712

    *, attention: 32 query heads over 2 K/V heads of 128, no bias, NO
    position embedding, scale 128^-0.5, causal softmax; query head n reads
    K/V head n // 16; part = W_o o

and ``logits = W_head RMSNorm(h_L; gf)``, the head not tied to the
embedding. Float32 throughout at ``jax.default_matmul_precision("highest")``;
the recurrence token by token; no cache, no kernels, no chunks, no
batching. So that nine layers at ten thousand positions fit a chip beside
the served model, the weights are cast to float32 a matrix at a time (an
expert at a time), attention is computed in blocks of query rows, and the
head is applied to the rows asked for only.

The SHARE: ``model["experts_held"]`` names the routed experts whose weights
the tree holds, in that order; a pair routed to an absent expert adds
nothing (the other chip of the pair adds its part there), in the program
and here alike. ``published.n_routed_experts`` is the router's width.

Departures from the published forward, each a reading the config does not
settle (the configuration file lists them under ``assumed``):

* no position embedding in the ``*`` layers: the ``nemotron_h`` model code
  applies none (arXiv:2504.03624, section 2.1), and the config's
  ``rope_theta`` and ``partial_rotary_factor`` are not read by it;
* the group of SSM head ``h`` is ``h // (H / G)``; the gated norm is gate
  first, then the grouped norm; ``time_step_limit`` is absent: (0, inf);
* the state here is ``[H, N, P]`` (the served contract's order, the
  transpose of the text's ``[H, P, N]``), the same numbers; the SSM's three
  input segments are stored side by side as ``w_in`` (a concatenation); a
  routed expert's ``W_up`` is stored transposed, ``wu`` [E, f, d].

The parameter tree is the served one (``models/nemotron3.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How far below the reference's own best logit a served token may rank, in
# units of that row's standard deviation over the vocabulary: the rule of
# ``reference/falcon_h1.py`` with its two limits, on a request's WORST row
# and on the MEAN over its rows. What sets both here is not rounding as such
# but ROUTING FLIPS: the sixth and seventh of 128 sigmoid scores of a row
# lie within bfloat16's rounding of its input often enough (a few rows in a
# hundred a layer) that the served row goes to another expert than the
# reference's; the flipped expert's part is an eighth of the residual, and
# rows downstream of it (the SSM states integrate it) differ from then on.
# Kimi-K2's, Laguna's, Ling's and Motif's references say the same of theirs
# (margins 0.8-1.1, mean limits 0.0075-0.035). Readings on the chip at the
# published widths (PERF.md, Findings, PR 65, has every one): served in
# bf16 with float32 states, 20 requests of 10 runs (contexts 1,819-9,628)
# read 0.47-0.92 at their worst row and 0.0020-0.0116 at the mean; this
# reference with every matrix rounded to float8 e4m3, the nearest precision
# below the stated one (``control_nemotron3.py ref_fp8``), 1.04 and 1.29 at
# the worst row and 0.071 and 0.073 at the mean. The MEAN tells the two
# apart with room on both sides (2.2 times over, 2.8 under). The WORST row
# does not: it is one flip's size in both, and its readings spread like
# maxima (a Gumbel fit to the 20 puts 1 request in 30 over 0.93, the middle
# of the gap, 1 in 400 over 1.2 and 1 in 2,500 over 1.4): the limit is set
# where a fresh seed does not fail a sound tree, it refuses what a part
# left out or a wrong group reads, and fp8 fails by the mean and the states.
LOGIT_MARGIN = 1.4
MEAN_GAP_LIMIT = 0.025
# What NO rank sees well is the precision of the recurrent state (with
# bfloat16 states, ``control_nemotron3.py state_bf16``, the worst rows read
# 0.43-0.85 and the means 0.007-0.014: inside the served spread or at its
# edge). So a VALUE the cache keeps is held too: the float32 states of
# FIVE requests resident in slots at the run's end (spread over the
# residents by their decode steps, 128 to 1,900 and more) against this
# reference's after the same tokens, as a share of their length
# (Frobenius, a head). The FIRST ``M`` layer reads the embedding alone, no
# expert layer stands before it and no routing flip reaches it: its gap is
# the states' own precision, and it is held at its WORST head (the heads
# that forget slowest carry a rounding longest) of the WORST of the five.
# 65 residents of 23 runs read 0.0044-0.0081 there; bfloat16 states read
# 0.041 (five residents; one resident a run before: 0.034, 0.186) and the
# float8 referee 0.083 (0.060, 0.094): ``STATE_GAP_LIMIT`` is 2.5 times
# over the first and 2 times under the nearest.
# Behind an expert layer a request's routing flips are in its states too,
# a term that is the REQUEST's own, never negative and heavy-tailed, on
# top of what the states' and the weights' precision leave in every
# request: ONE resident's worst head of the three later layers read
# 0.03-0.64 over 50 residents (the driver's check of PR 65 met 0.454 in
# its traced run at seed 179068403, where this file held that number
# under 0.4; the same seed run again read 0.203, 0.148 and 0.118: the
# number is which token the run stopped at), its MEDIAN head at the worst
# of those layers 0.008-0.117, and the median of five residents still
# 0.011-0.066 (a seed whose router sits nearer its ties moves four of its
# five). What all requests share is the LEAST of the five: 0.0084-0.0143
# over eight runs, where the float8 referee, whose rounding is in every
# request alike, reads 0.148 (its five 0.148-0.179; 0.198 before) and
# bfloat16 states 0.014 (their fault is the first layer's to refuse). So
# ``STATE_GAP_DEEP_LIMIT`` holds the later layers' median head, at the
# worst layer, of the resident it reads LEAST in: 7 times over the largest
# served reading and 1.5 times under the referee's. It stands nearer the
# referee because the served side is the one with a tail: one resident in
# 50 read over 0.1 (0.117); were it one in five at a seed near its ties,
# five at once are one run in 3,000; at the geometric middle (0.046)
# a seed like 987654321 (four of five over 0.042) would fail one sound
# run in three. A wrong group, recurrence or layout reads 1.0 and more in
# every head of every request.
STATE_GAP_LIMIT = 0.02
STATE_GAP_DEEP_LIMIT = 0.1

Q_BLOCK = 128


def _f32(w):
    """A stored matrix as the float32 the reference multiplies by. The ONE
    place a precision control lowers (``benchmarks/control_nemotron3.py
    ref_fp8``)."""
    return w.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def pattern(model: Dict[str, Any]) -> str:
    """The letters of the layers HELD: the published pattern's first
    ``num_hidden_layers``."""
    return model["hybrid_override_pattern"][:int(model["num_hidden_layers"])]


def sizes(model: Dict[str, Any]) -> Dict[str, int]:
    """The layers' sizes from the published keys."""
    h, p = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    return {"H": h, "P": p, "G": int(model["n_groups"]),
            "N": int(model["ssm_state_size"]), "d_ssm": h * p,
            "taps": int(model["conv_kernel"]),
            "n_head": int(model["num_attention_heads"]),
            "n_kv": int(model["num_key_value_heads"]),
            "d_head": int(model["head_dim"]),
            "top_k": int(model["num_experts_per_tok"])}


def n_experts(model: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever is held."""
    return int(model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]))


def held(model: Dict[str, Any]) -> List[int]:
    """The global ids of the routed experts the tree holds, in its order."""
    return [int(e) for e in model.get(
        "experts_held", range(int(model["n_routed_experts"])))]


def _frozen(model: Dict[str, Any]):
    """The numbers a jitted layer closes over, hashable."""
    return (tuple(sorted(sizes(model).items())),
            float(model["layer_norm_epsilon"]),
            float(model["routed_scaling_factor"]), n_experts(model),
            tuple(held(model)))


def _mamba(lp, u, z, eps, length):
    """The ``M`` part's ``(m [S, d], S [H, N, P])`` of the normed input ``u``
    [S, d]: ``S`` is the state after the first ``length`` positions (the
    positions after them, a caller's padding, leave it as it is)."""
    f32 = jnp.float32
    s = u.shape[0]
    h, p, g, n, d_ssm = z["H"], z["P"], z["G"], z["N"], z["d_ssm"]
    proj = u @ _f32(lp["w_in"])
    gate = proj[:, :d_ssm]
    xbc = proj[:, d_ssm:2 * d_ssm + 2 * g * n]
    dt = jax.nn.softplus(proj[:, 2 * d_ssm + 2 * g * n:]
                         + lp["dt_bias"].astype(f32))        # [S, H]
    cw = lp["cw"].astype(f32)
    taps = cw.shape[0]
    up = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(cw[j] * up[j:j + s] for j in range(taps))
                       + lp["cb"].astype(f32))
    x = conv[:, :d_ssm].reshape(s, h, p)
    b = conv[:, d_ssm:d_ssm + g * n].reshape(s, g, n)
    c = conv[:, d_ssm + g * n:].reshape(s, g, n)
    a = jnp.exp(dt * -jnp.exp(lp["a_log"].astype(f32)))     # [S, H]
    per = h // g

    def step(carry, t):
        state, kept = carry
        xt, bt, ct, at, dtt, live = t
        bt, ct = (jnp.repeat(v, per, axis=0) for v in (bt, ct))   # [H, N]
        state = state * at[:, None, None] \
            + bt[:, :, None] * (dtt[:, None] * xt)[:, None, :]
        return (state, jnp.where(live, state, kept)), jnp.einsum(
            "hn,hnp->hp", ct, state)

    zero = jnp.zeros((h, n, p), f32)
    (_, kept), y = jax.lax.scan(step, (zero, zero), (
        x, b, c, a, dt, jnp.arange(s) < length))
    y = y + lp["dskip"].astype(f32)[:, None] * x
    y = (y.reshape(s, d_ssm) * jax.nn.silu(gate)).reshape(s, g, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(s, d_ssm) * lp["gn"].astype(f32)
    return y @ _f32(lp["w_out"]), kept


def route(lp, u, top_k: int, scale: float):
    """``(idx [S, k], w [S, k])``: the chosen experts (global ids of the
    router's outputs) and their weights."""
    s = jax.nn.sigmoid(u @ lp["wr"].astype(jnp.float32))
    _, idx = jax.lax.top_k(s + lp["br"].astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale


def _experts(lp, u, top_k, scale, n_expert, held_ids, shared: bool):
    """The ``E`` part of rows ``u`` [S, d]: an expert at a time over ALL
    rows, each row's result weighed by what the router gave that expert
    for it (0 where it was not chosen)."""
    f32 = jnp.float32
    idx, w = route(lp, u, top_k, scale)
    # the weight each held expert has in each row: [E_held, S]
    of_expert = jnp.zeros((u.shape[0], n_expert), f32).at[
        jnp.arange(u.shape[0])[:, None], idx].add(w)
    weight = of_expert[:, jnp.asarray(held_ids, jnp.int32)].T

    def one(y, ew):
        w_e, wu, wd = ew
        return y + w_e[:, None] * (_relu2(u @ _f32(wu).T) @ _f32(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (weight, lp["wu"], lp["wd"]))
    if shared:
        y = y + _relu2(u @ _f32(lp["su"])) @ _f32(lp["sd"])
    return y


def _attention(q, k, v, scale):
    """``q`` [S, Hq, D], ``k``/``v`` [S, Hkv, D]; causal, grouped."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    bq = Q_BLOCK
    while s % bq:
        bq //= 2
    cols = jnp.arange(s)[None, :]

    def block(bi, qi):
        rows = bi * bq + jnp.arange(bq)[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", qi.reshape(bq, hkv, hq // hkv, d),
                        k) * scale
        pr = jax.nn.softmax(jnp.where((cols <= rows)[None, None], sc,
                                      -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(bq, hq, d)

    out = jax.lax.map(lambda t: block(*t),
                      (jnp.arange(s // bq), q.reshape(s // bq, bq, hq, d)))
    return out.reshape(s, hq, d)


def _attn(lp, u, z):
    s = u.shape[0]
    q = (u @ _f32(lp["wq"])).reshape(s, z["n_head"], z["d_head"])
    k = (u @ _f32(lp["wk"])).reshape(s, z["n_kv"], z["d_head"])
    v = (u @ _f32(lp["wv"])).reshape(s, z["n_kv"], z["d_head"])
    o = _attention(q, k, v, z["d_head"] ** -0.5)      # no position embedding
    return o.reshape(s, -1) @ _f32(lp["wo"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(lp, x, kind, frozen, leave_out, length):
    """One layer of ``kind``; returns ``(x', (|part|, |x|) root mean
    squares, the SSM's state after ``length`` positions or None)``.
    ``leave_out`` names a kind of part a test leaves out."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        zs, eps, scale, n_expert, held_ids = frozen
        z = dict(zs)
        u = _rms(x, lp["g"].astype(f32), eps)
        state = None
        if kind == "M":
            part, state = _mamba(lp, u, z, eps, length)
        elif kind == "E":
            part = _experts(lp, u, z["top_k"], scale, n_expert, held_ids,
                            True)
        else:
            part = _attn(lp, u, z)
        if leave_out == kind:
            part = jnp.zeros_like(x)

        def rms(t):
            return jnp.sqrt(jnp.mean(jnp.square(t)))

        return x + part, jnp.stack([rms(part), rms(x)]), state


def expert_part(lp, u, model: Dict[str, Any],
                held_ids: Optional[Sequence[int]] = None,
                shared: bool = True):
    """The ``E`` part alone over normed rows ``u`` [S, d] float32, for the
    experts ``held_ids`` (default: the model's) whose weights ``lp`` holds
    in that order, with or without the shared expert: what the share test
    adds up."""
    with jax.default_matmul_precision("highest"):
        return _experts(lp, u.astype(jnp.float32),
                        int(model["num_experts_per_tok"]),
                        float(model["routed_scaling_factor"]),
                        n_experts(model),
                        tuple(held(model) if held_ids is None else held_ids),
                        shared)


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(gf, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, gf.astype(jnp.float32), eps) @ _f32(head)


HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnums=(4,))
def _gap_parts(gf, head, x, picked_ids, eps):
    """``(max, the picked token's logit, deviation)`` [R] of the logits of
    rows ``x`` [R, d], the head applied a BLOCK of the vocabulary at a
    time."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        v = head.shape[1]
        blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
        size = v // blocks
        xn = _rms(x, gf.astype(f32), eps)

        def block(i, acc):
            best, picked, total, squares = acc
            w = jax.lax.dynamic_slice_in_dim(head, i * size, size, axis=1)
            lg = xn @ _f32(w)                                # [R, size]
            local = picked_ids - i * size
            mine = (local >= 0) & (local < size)
            got = jnp.take_along_axis(
                lg, jnp.clip(local, 0, size - 1)[:, None], axis=1)[:, 0]
            return (jnp.maximum(best, lg.max(-1)),
                    jnp.where(mine, got, picked), total + lg.sum(-1),
                    squares + jnp.square(lg).sum(-1))

        r = x.shape[0]
        best, picked, total, squares = jax.lax.fori_loop(
            0, blocks, block, (jnp.full((r,), -jnp.inf, f32),
                               jnp.zeros((r,), f32), jnp.zeros((r,), f32),
                               jnp.zeros((r,), f32)))
        mean = total / v
        return best, picked, jnp.sqrt(jnp.maximum(squares / v - mean * mean,
                                                  0.0))


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens,
           leave_out: str = None, shares: List = None, states: List = None,
           length: int = None) -> jnp.ndarray:
    """``x`` [S, d] after the last layer of one sequence ``tokens`` [S].
    ``model`` gives the sizes under the published config's own keys.
    ``shares``, a list, is given a layer's ``(|part|, |x|)``: what the part
    adds beside the residual it is added to; ``states``, a list, each ``M``
    layer's SSM state ``[H, N, P]`` after the first ``length`` positions
    (default: all of them)."""
    frozen = _frozen(model)
    x = _f32(params["tok_emb"][tokens])
    length = jnp.asarray(tokens.shape[0] if length is None else length)
    for kind, lp in zip(pattern(model), params["layers"]):
        x, norms, state = _layer(lp, x, kind, frozen, leave_out, length)
        if shares is not None:
            shares.append(norms)
        if states is not None and state is not None:
            states.append(state)
    return x


def forward(params: Dict[str, Any], model: Dict[str, Any], tokens,
            rows=None, leave_out: str = None, shares: List = None
            ) -> jnp.ndarray:
    """Logits of one sequence: every row [S, V], or the ``rows`` asked
    for."""
    x = hidden(params, model, jnp.asarray(tokens), leave_out, shares)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _logits(params["gf"], params["head"], x,
                   float(model["layer_norm_epsilon"]))


def row_gaps(params, model: Dict[str, Any], prompt: Sequence[int],
             output: List[int], pad_to: int = 256, shares: List = None
             ) -> np.ndarray:
    """Teacher-forced in ONE forward over prompt + output (a causal model's
    row i depends on tokens <= i only, so row ``len(prompt) - 1 + j`` is
    the row from which the j-th output token was chosen): for each of the
    output's tokens, how far the served token ranks below the row's best
    logit, in row standard deviations (0 where it IS the best). The
    sequence is padded to a multiple of ``pad_to`` (causality keeps the
    padding out of every row that is read)."""
    seq = list(prompt) + list(output[:-1])
    size = -(-len(seq) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    first = len(prompt) - 1
    x = hidden(params, model, jnp.asarray(toks), shares=shares)[
        first:first + len(output)]
    best, picked, std = _gap_parts(
        params["gf"], params["head"], x, jnp.asarray(output, jnp.int32),
        float(model["layer_norm_epsilon"]))
    return np.asarray((best - picked) / std)


def final_states(params, model: Dict[str, Any], tokens: Sequence[int],
                 pad_to: int = 256) -> np.ndarray:
    """``[M layers, H, N, P]``: every ``M`` layer's SSM state after
    ``tokens``, what a served slot KEEPS once it has consumed them. The
    sequence is padded to a multiple of ``pad_to``; the state is taken
    before the padding."""
    size = -(-len(tokens) // pad_to) * pad_to
    toks = np.zeros((size,), np.int32)
    toks[:len(tokens)] = tokens
    states = []
    hidden(params, model, jnp.asarray(toks), states=states,
           length=len(tokens))
    return np.stack([np.asarray(s) for s in states])


def state_gaps(served: np.ndarray, want: np.ndarray) -> np.ndarray:
    """``|served - want| / |want|`` of each head's state (Frobenius), a
    value a (layer, head), ascending: the last is the worst head's."""
    axes = (-2, -1)
    return np.sort((np.linalg.norm(served - want, axis=axes)
                    / np.linalg.norm(want, axis=axes)).reshape(-1))


def first_layer_gap(by_layer: Sequence[np.ndarray]) -> float:
    """What ``STATE_GAP_LIMIT`` holds of one request, from
    :func:`state_gaps` an ``M`` layer: the first layer's WORST head."""
    return float(by_layer[0][-1])


def deep_layer_gap(by_layer: Sequence[np.ndarray]) -> float:
    """What ``STATE_GAP_DEEP_LIMIT`` holds of one request: the MEDIAN head
    of each ``M`` layer behind the first, at the worst of those layers (of
    the first where the model has one)."""
    return max(float(np.median(g)) for g in (by_layer[1:] or by_layer))

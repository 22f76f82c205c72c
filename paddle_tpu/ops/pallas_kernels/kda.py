"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692, section 3): a
linear-attention recurrence with a decay for every channel of every head
and a delta-rule write. For one head with state ``S`` [dk, dv], zero at a
request's start, and a token's ``q``, ``k`` [dk], ``v`` [dv], log-decay
``a`` [dk] (<= 0) and write strength ``beta``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of it live here:

* :func:`kda_recurrence`: the equations token by token under
  ``lax.scan``: the yardstick of the tests and of the float32 reference.
* :func:`kda_state_step`: ONE decode step for every live slot of one
  layer, a Pallas kernel (name ``kda_state_step`` in a device trace). The
  states ``[n_layer, slots, H, dk, dv]`` float32 are aliased in and out:
  a live slot's state streams through the chip once (read, decayed,
  corrected, read out, written back), all on the vector unit in float32
  (2 x H x dk x dv x 4 bytes a slot against some 7 operations a value:
  the HBM rate bounds it, not the arithmetic). The live slots are put
  first by a scalar-prefetched order and the steps past the last of them
  stay on its block, so a slot that is not ``active`` is neither read nor
  written. :func:`kda_state_step_xla` is the same step in plain XLA (the
  CPU tests' second path, and where the gate refuses a geometry).
* :func:`kda_chunk_scan`: the prefill's chunk-wise form (chunks of 64,
  the WY form of the paper's section 3): inside a chunk, with ``g_t`` the
  running sum of ``a`` and ``u_t`` the pseudo-value the delta rule writes,

      (I + A) u = beta (V - (K e^g) S_0),  A[t, i] = beta_t sum_c k_t k_i
                                           e^(g_t - g_i)   (i < t)
      o_t = (q_t e^(g_t))^T S_0 + sum_{i <= t} (sum_c q_t k_i e^(g_t - g_i))
            u_i
      S_C = e^(g_C) S_0 + sum_i (k_i e^(g_C - g_i)) u_i^T

  so S tokens cost S / 64 sequential steps of small matrix products. It
  is blocked ``jax.numpy`` under ``lax.scan`` (scope ``kda_chunk_scan``),
  not a Pallas kernel. The pairwise factors ``e^(g_t - g_i)`` are made as
  ``e^(g_t - r) e^(r - g_i)`` with ``r`` the running sum at the start of
  t's sub-block of 16: with a step's log-decay bounded below by
  ``LOWER_BOUND`` (-5: ``kda_lower_bound``) both exponents stay within
  +-80, inside float32, whatever the gates do. ``(I + A) u = b`` is
  solved by forward substitution in blocks of 16 (each diagonal block's
  inverse row by row, then the blocks in turn): NOT by the finite series
  ``(I - A)(I + A^2)(I + A^4)...``, whose terms grow like binomial
  coefficients and cancel where the keys of a chunk resemble each other
  (a served model's do: PERF.md, PR 41, read 5 row deviations from it).
  Every product is float32 at the highest matmul precision: the state is
  float32 and is only as good as what is written into it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_recurrence", "kda_chunk_scan", "kda_state_step",
           "kda_state_step_xla", "kda_state_step_gate", "KERNEL_NAME",
           "SCAN_NAME", "CHUNK", "LOWER_BOUND"]

KERNEL_NAME = "kda_state_step"
SCAN_NAME = "kda_chunk_scan"
CHUNK = 64
_SUB = 16            # the sub-block whose start is the exponents' reference
LOWER_BOUND = -5.0   # a step's log-decay lies in (LOWER_BOUND, 0)
_LANES = 128
_HEAD_BLOCK = 16     # heads a grid step of the decode kernel: 1 MiB of state
_HI = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, a, beta, s0=None):
    """The recurrence token by token, float32. ``q``/``k``/``a`` [T, H,
    dk], ``v`` [T, H, dv], ``beta`` [T, H]; ``s0`` [H, dk, dv] or zeros.
    Returns ``(o [T, H, dv], S [H, dk, dv])``."""
    f32 = jnp.float32
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    if s0 is None:
        s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)

    def step(s, x):
        qt, kt, vt, at, bt = x
        s = s * jnp.exp(at)[..., None]
        ks = jnp.einsum("hk,hkv->hv", kt, s, precision=_HI)
        s = s + kt[..., None] * (bt[:, None] * (vt - ks))[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=_HI)

    s, o = jax.lax.scan(step, s0.astype(f32), (q, k, v, a, beta))
    return o, s


def _solve_unit_lower(a, b, sub: int):
    """``x`` with ``(I + a) x = b``: ``a`` [H, C, C] strictly lower
    triangular, ``b`` [H, C, n]. Forward substitution in blocks of
    ``sub``: the inverse of each diagonal block ``I + a_II`` row by row
    (row r is ``e_r - a_II[r, :r] rows[:r]``), then ``x_I = inv_I (b_I -
    a[I, :I] x[:I])`` block after block: ``sub + C / sub`` sequential
    steps, each exact to round-off whatever ``a`` holds."""
    h, c, _ = a.shape
    nb = c // sub
    diag = jnp.stack([a[:, i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                      for i in range(nb)], axis=1)           # [H, nb, s, s]
    eye = jnp.eye(sub, dtype=a.dtype)
    rows = []
    for r in range(sub):
        row = eye[r]
        if r:
            row = row - jnp.einsum("hbi,hbij->hbj", diag[:, :, r, :r],
                                   jnp.stack(rows, axis=2), precision=_HI)
        rows.append(jnp.broadcast_to(row, (h, nb, sub)))
    inv = jnp.stack(rows, axis=2)                            # [H, nb, s, s]
    xs = []
    for i in range(nb):
        rhs = b[:, i * sub:(i + 1) * sub]
        if i:
            rhs = rhs - jnp.matmul(a[:, i * sub:(i + 1) * sub, :i * sub],
                                   jnp.concatenate(xs, axis=1),
                                   precision=_HI)
        xs.append(jnp.matmul(inv[:, i], rhs, precision=_HI))
    return jnp.concatenate(xs, axis=1)


def _chunk(s0, x, sub: int):
    """One chunk of :func:`kda_chunk_scan`: ``x`` = (q, k, v, a, beta)
    [C, H, .] in the caller's type, ``s0`` [H, dk, dv] float32."""
    f32 = jnp.float32
    q, k, v, a = (jnp.swapaxes(t.astype(f32), 0, 1) for t in x[:4])
    beta = x[4].astype(f32).T[..., None]                     # [H, C, 1]
    h, c, dk = k.shape
    nb = c // sub
    g = jnp.cumsum(a, axis=1)                                # [H, C, dk]
    # r: the running sum at the start of each sub-block; the rows of block
    # I see e^(g_t - r_I) (<= 1) and the columns e^(r_I - g_i), clamped at
    # the bound a row of the same block can reach (later columns are
    # masked, earlier ones lie under 1)
    r = g.reshape(h, nb, sub, dk)[:, :, :1]                  # [H, nb, 1, dk]
    row = jnp.exp(g.reshape(h, nb, sub, dk) - r)             # [H, nb, sub, dk]
    col = jnp.exp(jnp.minimum(r - g[:, None], -LOWER_BOUND * sub))
    kcol = k[:, None] * col                                  # [H, nb, C, dk]

    def pairs(x_rows):
        scaled = x_rows.reshape(h, nb, sub, dk) * row
        return jnp.einsum("hbtc,hbic->hbti", scaled, kcol,
                          precision=_HI).reshape(h, c, c)

    t_i = jnp.arange(c)[:, None] - jnp.arange(c)[None, :]    # t - i
    akk = jnp.where(t_i > 0, pairs(k), 0.0) * beta
    aqk = jnp.where(t_i >= 0, pairs(q), 0.0)
    eg = jnp.exp(g)
    dv = v.shape[-1]
    solved = _solve_unit_lower(
        akk, jnp.concatenate([beta * v, beta * k * eg], axis=-1), sub)
    w = solved[..., dv:]                                     # [H, C, dk]
    u = solved[..., :dv] - jnp.matmul(w, s0, precision=_HI)  # [H, C, dv]
    o = jnp.matmul(q * eg, s0, precision=_HI) \
        + jnp.matmul(aqk, u, precision=_HI)
    g_end = g[:, -1:]                                        # [H, 1, dk]
    s1 = s0 * jnp.swapaxes(jnp.exp(g_end), 1, 2) + jnp.einsum(
        "hic,hiv->hcv", k * jnp.exp(g_end - g), u, precision=_HI)
    return s1, jnp.swapaxes(o, 0, 1)


def kda_chunk_scan(q, k, v, a, beta, s0=None, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens in ``ceil(T / chunk)`` sequential
    steps. Arguments and results as :func:`kda_recurrence` (``o`` comes
    back float32); equal to it to float32 round-off (the tests pin 2e-5
    on outputs of order 1). A position that must not touch the state (a
    prompt's padding) is given ``a`` = 0 and ``beta`` = 0 by the caller;
    the tail this function pads to a whole chunk is made so here. ``a``
    must lie in (``LOWER_BOUND``, 0]."""
    t, h, dk = k.shape
    dv = v.shape[-1]
    sub = min(_SUB, chunk)
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError("chunk=%d must be a power of two and a multiple "
                         "of %d" % (chunk, sub))
    n = -(-t // chunk)
    pad = n * chunk - t

    def chunks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, chunk) + x.shape[1:])

    if s0 is None:
        s0 = jnp.zeros((h, dk, dv), jnp.float32)
    with jax.named_scope(SCAN_NAME):
        s, o = jax.lax.scan(
            functools.partial(_chunk, sub=sub), s0.astype(jnp.float32),
            tuple(chunks(x) for x in (q, k, v, a, beta)))
    return o.reshape(n * chunk, h, dv)[:t], s


def kda_state_step_xla(states, layer, q, k, v, a, beta, active):
    """:func:`kda_state_step` in plain XLA: every slot computed, the
    inactive ones put back as they were."""
    f32 = jnp.float32
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    s = states[layer] * jnp.exp(a)[..., None]
    ks = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HI)
    s = s + k[..., None] * (beta[..., None] * (v - ks))[:, :, None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HI)
    live = active[:, None, None, None]
    return (jnp.where(active[:, None, None], o, 0.0),
            states.at[layer].set(jnp.where(live, s, states[layer])))


def kda_state_step_gate(n_head: int, dk: int, dv: int,
                        interpret: bool = False) -> Optional[str]:
    """None when the compiled kernel takes this state geometry, else the
    rule that excludes it (the chip compiler's tiling; the interpreter is
    not bound by it)."""
    if interpret:
        return None
    if dk % 8 or dv % _LANES:
        return ("a head's state [%d, %d] must be whole (8, %d) float32 "
                "tiles" % (dk, dv, _LANES))
    hb = _head_block(n_head)
    if hb % 8:
        return ("%d heads do not divide into blocks of a multiple of 8 "
                "sublanes" % n_head)
    return None


def _head_block(n_head: int) -> int:
    hb = min(_HEAD_BLOCK, n_head)
    while n_head % hb:
        hb -= 1
    return hb


def _step_kernel(layer_ref, idx_ref, n_ref, cols_ref, v_ref, s_ref, o_ref,
                 s_out, *, hb):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        cols = cols_ref[...]                      # [dk, lanes]
        for h in range(hb):
            k = cols[:, h:h + 1]                  # [dk, 1] each
            qq = cols[:, hb + h:hb + h + 1]
            decay = cols[:, 2 * hb + h:2 * hb + h + 1]
            bk = cols[:, 3 * hb + h:3 * hb + h + 1]
            s = s_ref[h] * decay                  # [dk, dv]
            ks = jnp.sum(s * k, axis=0, keepdims=True)        # [1, dv]
            s = s + bk * (v_ref[h:h + 1, :] - ks)
            s_out[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * qq, axis=0, keepdims=True)

    # nobody live: every step sits on one block, which goes back as it came
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_state_step(states, layer, q, k, v, a, beta, active, *,
                   interpret: bool = False):
    """One decode step of layer ``layer`` for the slots marked ``active``.

    ``states`` [n_layer, B, H, dk, dv] float32, donated: the result's
    second part is the same buffer with the live slots' states advanced.
    ``q``/``k``/``a`` [B, H, dk], ``v`` [B, H, dv], ``beta`` [B, H], any
    float type (computed in float32); ``a`` is the log-decay. Returns
    ``(o [B, H, dv] float32, states)``; ``o`` of a slot that is not active
    is 0, its state untouched and unread."""
    f32 = jnp.float32
    n_layer, b, h, dk, dv = states.shape
    hb = _head_block(h)
    nj = h // hb
    lanes = -(-4 * hb // _LANES) * _LANES
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    # the vectors that scale S's ROWS ride as columns: for each block of
    # hb heads a [dk, 4 hb] tile [k | q | e^a | beta k], a head a lane
    cols = jnp.stack([k, q, jnp.exp(a), beta[..., None] * k], axis=1)
    cols = cols.reshape(b, 4, nj, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = jnp.pad(cols.reshape(b, nj, dk, 4 * hb),
                   ((0, 0), (0, 0), (0, 0), (0, lanes - 4 * hb)))
    # live slots first; the steps past the last stay on its last block
    n = jnp.sum(active).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    idx = order[jnp.minimum(jnp.arange(b), jnp.maximum(n - 1, 0))]

    def block(i, j, layer_ref, idx_ref, n_ref):
        return idx_ref[i], jnp.where(i < n_ref[0], j, nj - 1)

    def state_map(i, j, layer_ref, idx_ref, n_ref):
        slot, jj = block(i, j, layer_ref, idx_ref, n_ref)
        return layer_ref[0], slot, jj, 0, 0

    def col_map(i, j, *refs):
        slot, jj = block(i, j, *refs)
        return slot, jj, 0, 0

    def row_map(i, j, *refs):
        slot, jj = block(i, j, *refs)
        return slot, jj, 0

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), state_map)
    row_spec = pl.BlockSpec((None, hb, dv), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b, nj),
        in_specs=[pl.BlockSpec((None, None, dk, lanes), col_map),
                  row_spec, state_spec],
        out_specs=[row_spec, state_spec])
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        input_output_aliases={5: 1}, interpret=interpret, name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(jnp.asarray(layer, jnp.int32).reshape(1), idx.astype(jnp.int32),
      n.reshape(1), cols, v, states)
    return jnp.where(active[:, None, None], o, 0.0), states

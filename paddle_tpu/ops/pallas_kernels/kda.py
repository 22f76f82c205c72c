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

  so S tokens cost S / 64 sequential steps of small matrix products. On
  a TPU a chunk's work is ONE Pallas kernel (name ``kda_chunk_scan`` in a
  device trace) inside the loop over chunks, a block of eight heads a grid
  step: the chunk's ``q``, ``k``, ``v``, ``a``, ``beta`` and the state
  come into VMEM once and only ``o`` and the state go back
  (:func:`kda_chunk_scan_kernel`, where :func:`kda_chunk_scan_gate` takes
  the geometry); elsewhere it is blocked ``jax.numpy`` under ``lax.scan``
  (:func:`kda_chunk_scan_xla`, scope ``kda_chunk_scan``: the form the CPU
  tests and the precision controls patch). The pairwise factors
  ``e^(g_t - g_i)`` are made as ``e^(g_t - r) e^(r - g_i)`` with ``r``
  the running sum at the start of t's sub-block of 16: with a step's
  log-decay bounded below by ``LOWER_BOUND`` (-5: ``kda_lower_bound``)
  both exponents stay within +-80, inside float32, whatever the gates do.
  ``(I + A) u = b`` is solved by forward substitution in blocks of 16
  (each diagonal block's inverse first, then the blocks in turn): NOT by
  the finite series
  ``(I - A)(I + A^2)(I + A^4)...``, whose terms grow like binomial
  coefficients and cancel where the keys of a chunk resemble each other
  (a served model's do: PERF.md, PR 41, read 5 row deviations from it).
  Every product is float32 at the highest matmul precision: the state is
  float32 and is only as good as what is written into it. (The kernel
  writes that precision out: an operand is its three bfloat16 parts and a
  product the six passes ``Precision.HIGHEST`` makes of them on this chip,
  laid along the contracted axis so that the matrix unit adds them:
  :func:`_dot6`. It reads 2e-6 from the blocked form on the chip.)

The scan's two forms, and how one is chosen. :func:`kda_chunk_scan` asks
:func:`kda_chunk_scan_gate` on a TPU and nowhere else; the gate sees the
geometry only and names the rule it refuses by: chunks of 64 alone; ``dk``
and ``dv`` whole 128-lane tiles; the heads a multiple of the head block
(8); a head block's float32 states within 1 MiB of VMEM. Both served
geometries pass (32 and 64 heads of 128 x 128: four and eight head blocks
of the grid, no other parameter). ``kda/scan_calls.kernel`` and
``.blocked`` count the choice once a call of a traced program. The kernel's
calls (8 chunks each, a head block's state staying in VMEM between them
and aliased in and out) sit inside a ``lax.fori_loop`` whose carry is
``(state f32[H, dk, dv], o)``: ALL of a chunk's work is inside that
``while``, because the benchmark's reader tells the scan by a ``while``
of the prefill executable that carries the float32 state
(``grid/readers/hybrid.py`` ``_is_scan``). The half of a chunk that does
not depend on the state (the decay's sum, the pair products, the
inverses) is NOT batched in front of the loop for that reason.

What was tried on the chip, one layer at 64 heads and 8,192 rows, the
scan's whole call (PR 49's builder; PR 50 reads the end of the list again:
PERF.md, section 6): the blocked form 19.3 ms; a kernel a chunk with every
product a ``precision=HIGHEST`` einsum 10.9; the six passes laid along
the contracted axis (:func:`_dot6`) 9.8; eight chunks a call with the
state in VMEM 9.6, 8.7 as it was first timed in a layer; q, k, v, a and o
as the caller's ``[T, H, d]`` blocks with strided loads 6.8 (a ``[T, H
d]`` view cost three XLA layout copies in front of the loop and 5 ms a
layer of reshapes around it); the diagonal blocks' inverses packed a pair
of heads a vreg and the block solve as six-pass products of 128 contracted
lanes (:func:`_small6`) 6.1, the loop alone 4.6. Tried and NOT kept:
float32 operands straight to the matrix unit (no splitting, but twice the
row pushes: 7,785 bundles a grid step for 7,639); heads in pairs under a
``fori_loop`` (a third of the text, but the solve's chain is exposed:
8,910 bundles). The kernel is bound by its bundles (6,614 a grid step),
not by HBM and not by stalls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_recurrence", "kda_chunk_scan", "kda_chunk_scan_xla",
           "kda_chunk_scan_kernel", "kda_chunk_scan_gate", "kda_state_step",
           "kda_state_step_xla", "kda_state_step_gate", "KERNEL_NAME",
           "SCAN_NAME", "CHUNK", "LOWER_BOUND"]

KERNEL_NAME = "kda_state_step"
SCAN_NAME = "kda_chunk_scan"
CHUNK = 64
_SUB = 16            # the sub-block whose start is the exponents' reference
LOWER_BOUND = -5.0   # a step's log-decay lies in (LOWER_BOUND, 0)
_LANES = 128
_HEAD_BLOCK = 16     # heads a grid step of the decode kernel: 1 MiB of state
_SCAN_HEAD_BLOCK = 8  # heads a grid step of the chunk kernel
_SCAN_CALL_CHUNKS = 8  # chunks a call of it, the state kept in VMEM
_SCAN_STATE_BYTES = 1 << 20  # a head block's state there: twice the served
_HI = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    # asked through attention_ops, so that what steers its kernels onto a
    # described chip (tests/test_chip_compile.py) steers this one too
    from .. import attention_ops

    return attention_ops._on_tpu()


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
    must lie in (``LOWER_BOUND``, 0]. On a TPU the ``kda_chunk_scan``
    kernel does a chunk's work where :func:`kda_chunk_scan_gate` takes the
    geometry; elsewhere, and where it refuses, :func:`_chunk`'s blocked
    ``jax.numpy`` does. ``kda/scan_calls.kernel`` and ``.blocked`` count
    which."""
    _, h, dk = k.shape
    if _on_tpu() and kda_chunk_scan_gate(h, dk, v.shape[-1], chunk) is None:
        _count("kernel")
        return kda_chunk_scan_kernel(q, k, v, a, beta, s0, chunk=chunk)
    _count("blocked")
    return kda_chunk_scan_xla(q, k, v, a, beta, s0, chunk=chunk)


def _count(form: str) -> None:
    """One more chunk scan traced in ``form`` (trace-time: an executable's
    scans count once, when it is traced, as ``attention/sdpa_calls.*``
    do)."""
    from ...monitor import metrics

    metrics.counter(
        "kda/scan_calls." + form,
        help="kda_chunk_scan calls traced in the %s form (counted where "
             "kda_chunk_scan chooses: once a call of a traced program, not "
             "once a run)" % form).inc()


def _chunks(x, chunk: int):
    """``x`` [T, ...] as ``[ceil(T / chunk), chunk, ...]``, the tail
    zeros."""
    n = -(-x.shape[0] // chunk)
    x = jnp.pad(x, ((0, n * chunk - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((n, chunk) + x.shape[1:])


def kda_chunk_scan_xla(q, k, v, a, beta, s0=None, chunk: int = CHUNK):
    """:func:`kda_chunk_scan` as blocked ``jax.numpy`` under ``lax.scan``
    (scope ``kda_chunk_scan``): every intermediate of a chunk an XLA
    fusion's result."""
    t, h, dk = k.shape
    dv = v.shape[-1]
    sub = min(_SUB, chunk)
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError("chunk=%d must be a power of two and a multiple "
                         "of %d" % (chunk, sub))
    if s0 is None:
        s0 = jnp.zeros((h, dk, dv), jnp.float32)
    with jax.named_scope(SCAN_NAME):
        s, o = jax.lax.scan(
            functools.partial(_chunk, sub=sub), s0.astype(jnp.float32),
            tuple(_chunks(x, chunk) for x in (q, k, v, a, beta)))
    return o.reshape(-1, h, dv)[:t], s


def kda_chunk_scan_gate(n_head: int, dk: int, dv: int, chunk: int = CHUNK,
                        interpret: bool = False) -> Optional[str]:
    """None when the ``kda_chunk_scan`` kernel takes this geometry, else
    the rule that excludes it."""
    if chunk != CHUNK:
        return "the kernel is written for chunks of %d, not %d" % (CHUNK,
                                                                   chunk)
    if interpret:
        return None
    if dk % _LANES or dv % _LANES:
        return ("a head's q, k [., %d] and v [., %d] must be whole %d-lane "
                "tiles" % (dk, dv, _LANES))
    if n_head % _SCAN_HEAD_BLOCK:
        return ("%d heads do not divide into blocks of %d"
                % (n_head, _SCAN_HEAD_BLOCK))
    if _SCAN_HEAD_BLOCK * dk * dv * 4 > _SCAN_STATE_BYTES:
        return ("a block of %d heads' states [%d, %d] is more than %d KiB "
                "of VMEM" % (_SCAN_HEAD_BLOCK, dk, dv,
                             _SCAN_STATE_BYTES >> 10))
    return None


def _split3(x):
    """``x`` (float32) as three bfloat16 terms whose sum is ``x`` to its
    last bit or two: each the rounding of what the ones before left."""
    bf, f32 = jnp.bfloat16, jnp.float32
    hi = x.astype(bf)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf)
    return hi, mid, (rest - mid.astype(f32)).astype(bf)


# the six passes of a float32 product at ``Precision.HIGHEST`` on this
# chip, as (part of x, part of y): hi hi, hi mid, mid hi, hi lo, mid mid,
# lo hi, summed in float32
_SIX = ((0, 0), (0, 1), (1, 0), (0, 2), (1, 1), (2, 0))


def _dot6(eq, x, y):
    """A float32 product as ``Precision.HIGHEST`` computes it, the six
    passes of ``_SIX`` written as ONE product over six times the contracted
    length: the matrix unit adds the passes and hands the result over once,
    where six products of the compiler's hand it over six times and load
    the same operand for each. ``x`` and ``y`` are arrays or their three
    parts; ``eq`` is a batched ``einsum`` whose contracted letter comes
    last in ``x`` and second in ``y``, or second in both."""
    x, y = (t if isinstance(t, tuple) else _split3(t) for t in (x, y))
    lhs, rhs = eq.split("->")[0].split(",")
    k = (set(lhs) & set(rhs) - set(eq.split("->")[1])).pop()
    return jnp.einsum(
        eq, jnp.concatenate([x[i] for i, _ in _SIX], axis=lhs.index(k)),
        jnp.concatenate([y[j] for _, j in _SIX], axis=rhs.index(k)),
        preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _lane_gather(x, idx):
    """``x[..., r, idx[l]]`` of ``x`` [..., R, 128] with ``idx`` [8, 128]
    (every row the same): a gather a vreg, all the chip's compiler
    takes."""
    flat = x.reshape((-1, 8, _LANES))
    return jnp.stack([jnp.take_along_axis(flat[i], idx, axis=1)
                      for i in range(flat.shape[0])]).reshape(x.shape)


def _small6(x, w, y):
    """``x[..., :w] @ y`` a head, the six passes in ONE product of 128 (or
    256, 384) contracted lanes: ``x`` [H, 16, 128] float32 holds each
    head's [16, w] (w 16, 32 or 64) ``128 // w`` times side by side, so a
    lane tile takes that many passes; ``y`` is the three parts of a [H, w,
    n] float32."""
    bf = jnp.bfloat16
    reps = _LANES // w
    lane = _iota(x.shape, 2)
    xs = _split3(x)
    ls, rs = [], []
    for t in range(0, len(_SIX), reps):
        tile = _SIX[t:t + reps]
        side = jnp.zeros(x.shape, bf)
        for j, (i, _) in enumerate(tile):
            side = jnp.where(lane // w == j, xs[i], side)
        ls.append(side)
        rs.extend(y[j] for _, j in tile)
        if len(tile) < reps:
            rs.append(jnp.zeros((x.shape[0], (reps - len(tile)) * w,
                                 y[0].shape[2]), bf))
    return jnp.einsum("htk,hkv->htv", jnp.concatenate(ls, axis=2),
                      jnp.concatenate(rs, axis=1),
                      preferred_element_type=jnp.float32)


def _scan_kernel(i_ref, q_ref, k_ref, v_ref, a_ref, beta_ref, s_ref, o_in,
                 o_ref, s_out, q32, k32, v32, a32, o32, *, hb, sub):
    """One chunk of a block of ``hb`` heads, the module docstring's
    equations as :func:`_chunk` computes them, every product the six
    bfloat16 passes of ``Precision.HIGHEST``. The refs hold the chunk's
    rows ``[C, hb, d]``; the scratch the same as float32 rows ``(t,
    head)``, so that a head's ``[C, d]`` is a strided load; the state
    ``[hb, dk, dv]`` stays in ``s_out`` from a call's first chunk to its
    last."""
    del i_ref, o_in
    f32 = jnp.float32
    c = o_ref.shape[0]
    nb = c // sub
    _, dk, dv = s_out.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out[...] = s_ref[...]

    for src, dst in ((q_ref, q32), (k_ref, k32), (v_ref, v32), (a_ref, a32)):
        dst[...] = src[...].astype(f32).reshape(dst.shape)

    def heads(ref):                                 # [hb, C, d]
        return jnp.stack([ref[pl.ds(i, c, stride=hb), :] for i in range(hb)])

    def below(x, n):
        # ``x`` [hb, n, .] with zero rows after it, to c rows
        return x if n == c else jnp.concatenate(
            [x, jnp.zeros((hb, c - n) + x.shape[2:], x.dtype)], axis=1)

    q, k, v, a = heads(q32), heads(k32), heads(v32), heads(a32)
    t_i = _iota((c, c), 0) - _iota((c, c), 1)                # t - i
    # the running sum of the log-decay: a product with the lower triangle
    # of ones (bfloat16 holds them exactly: three passes add the three
    # parts of ``a``, the other three would add zeros)
    tri = jnp.broadcast_to((t_i >= 0).astype(jnp.bfloat16), (hb, c, c))
    g = jnp.einsum("hts,hsc->htc", jnp.concatenate([tri] * 3, axis=2),
                   jnp.concatenate(_split3(a), axis=1),
                   preferred_element_type=f32)
    mine = _iota(beta_ref.shape, 1) - pl.program_id(0) * hb
    beta = jnp.stack([jnp.sum(jnp.where(mine == i, beta_ref[...], 0.0),
                              axis=1, keepdims=True) for i in range(hb)])
    akk, aqk = [], []
    for b in range(nb):
        rows, n = slice(b * sub, (b + 1) * sub), (b + 1) * sub
        r = g[:, b * sub:b * sub + 1]                        # [hb, 1, dk]
        row = jnp.exp(g[:, rows] - r)
        # the columns after this block's are masked: left zero here
        kcol = k[:, :n] * jnp.exp(jnp.minimum(r - g[:, :n],
                                              -LOWER_BOUND * sub))
        pairs = _dot6("htc,hic->hti", jnp.concatenate(
            [k[:, rows] * row, q[:, rows] * row], axis=1),
            tuple(below(part, n) for part in _split3(kcol)))
        akk.append(pairs[:, :sub])
        aqk.append(pairs[:, sub:])
    akk = jnp.where(t_i > 0, jnp.concatenate(akk, axis=1), 0.0) * beta
    aqk = jnp.where(t_i >= 0, jnp.concatenate(aqk, axis=1), 0.0)
    # each diagonal block's inverse by forward substitution, a column a
    # step (row i is final once the columns before it are eliminated), the
    # nb blocks of a PAIR of heads side by side in a vreg's 128 lanes: the
    # step's column is then one gather a vreg, not one a block
    pairs_of = -(-hb // 2)
    lane8 = _iota((8, _LANES), 1)
    own = _iota((sub, c), 1) // sub
    diag = sum(jnp.where(own == b, akk[:, b * sub:(b + 1) * sub], 0.0)
               for b in range(nb))                           # [hb, sub, C]
    if hb % 2:
        diag = jnp.concatenate([diag, jnp.zeros_like(diag[:1])], axis=0)
    diag = diag.reshape(pairs_of, 2, sub, c)
    diag = jnp.concatenate([diag[:, 0], diag[:, 1]], axis=2)
    inv = jnp.broadcast_to((_iota((sub, _LANES), 1) % sub
                            == _iota((sub, _LANES), 0)).astype(f32),
                           diag.shape)
    for i in range(sub - 1):
        inv = inv - _lane_gather(diag, lane8 // sub * sub + i) \
            * inv[:, i:i + 1]
    # every head its pair's lanes, its own blocks from (head % 2) C on
    inv = jnp.stack([inv, inv], axis=1).reshape(2 * pairs_of, sub,
                                                _LANES)[:hb]
    odd = _iota((hb, 1, 1), 0) % 2 == 1
    wide = jnp.concatenate([akk, akk], axis=2)               # [hb, C, 128]
    # (I + akk) u = beta (v - (k e^g) S_0), the blocks in turn
    s0 = s_out[...]
    eg = jnp.exp(g)
    both = _dot6("htc,hcv->htv",
                 jnp.concatenate([beta * k * eg, q * eg], axis=1), s0)
    rhs = beta * v - both[:, :c]
    us = []                                     # a block's u: its 3 parts
    for b in range(nb):
        rows = slice(b * sub, (b + 1) * sub)
        x = rhs[:, rows]
        if b:
            # the blocks before, at a width that divides the lanes
            w = c if b * sub > c // 2 else b * sub
            y = tuple(jnp.concatenate(
                [blk[part] for blk in us]
                + [jnp.zeros((hb, w - b * sub, dv), jnp.bfloat16)] * (
                    w > b * sub), axis=1) for part in range(3))
            x = x - _small6(_lane_gather(wide[:, rows], lane8 % w), w, y)
        blk = jnp.where(odd,
                        _lane_gather(inv, (nb + b) * sub + lane8 % sub),
                        _lane_gather(inv, b * sub + lane8 % sub))
        us.append(_split3(_small6(blk, sub, _split3(x))))
    u = tuple(jnp.concatenate([blk[part] for blk in us], axis=1)
              for part in range(3))                          # [hb, C, dv]
    o = both[:, c:] + _dot6("hti,hiv->htv", aqk, u)
    for i in range(hb):
        o32[pl.ds(i, c, stride=hb), :] = o[i]
    o_ref[...] = o32[...].reshape(o_ref.shape)
    g_end = g[:, c - 1:]                                     # [hb, 1, dk]
    # e^(g_C) scales S's ROWS: the row vector laid down dv times, turned
    decay = jnp.swapaxes(jnp.broadcast_to(jnp.exp(g_end), (hb, dv, dk)),
                         1, 2)
    s_out[...] = s0 * decay + _dot6("hic,hiv->hcv",
                                    k * jnp.exp(g_end - g), u)


def _call_chunks(n: int) -> int:
    """Chunks a call of the kernel takes: the largest power of two up to
    ``_SCAN_CALL_CHUNKS`` that divides ``n`` and leaves the loop two
    turns."""
    m = _SCAN_CALL_CHUNKS
    while m > 1 and (n % m or n // m < 2):
        m //= 2
    return m


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_chunk_scan_kernel(q, k, v, a, beta, s0=None, chunk: int = CHUNK, *,
                          interpret: bool = False):
    """:func:`kda_chunk_scan` with a chunk's work in ONE Pallas kernel
    (name ``kda_chunk_scan`` in a device trace) under the loop over
    chunks. A grid step takes one chunk of ``_SCAN_HEAD_BLOCK`` heads:
    its ``q``, ``k``, ``v``, ``a``, ``beta`` come into VMEM once, as the
    caller laid them (found in the whole arrays by the scalar-prefetched
    index: no slice is copied, nothing is turned), and only ``o`` (into
    its rows of the whole result, aliased through the loop) goes back. A
    call takes up to ``_SCAN_CALL_CHUNKS`` chunks in turn, the heads'
    state staying in VMEM between them: it goes through HBM once a call,
    not once a chunk. Jitted, so that the layers of one executable trace
    and lower ONE kernel text."""
    f32 = jnp.float32
    t, h, dk = k.shape
    dv = v.shape[-1]
    why_not = kda_chunk_scan_gate(h, dk, dv, chunk, interpret)
    if why_not is not None:
        raise ValueError(why_not)
    hb = _head_block(h, _SCAN_HEAD_BLOCK)
    q, k, v, a, beta = (_chunks(x, chunk) for x in (
        q, k, v, a.astype(f32), beta.astype(f32)))
    n = q.shape[0]
    m = _call_chunks(n)
    if s0 is None:
        s0 = jnp.zeros((h, dk, dv), f32)

    def rows(d):
        return pl.BlockSpec((None, chunk, hb, d),
                            lambda j, c, i_ref: (i_ref[0] * m + c, 0, j, 0))

    state = pl.BlockSpec((hb, dk, dv), lambda j, c, i_ref: (j, 0, 0))
    call = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, sub=_SUB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h // hb, m),
            in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                      pl.BlockSpec((None, chunk, h),
                                   lambda j, c, i_ref: (i_ref[0] * m + c, 0,
                                                        0)),
                      state, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows(dv), state],
            scratch_shapes=[pltpu.VMEM((chunk * hb, d), f32)
                            for d in (dk, dk, dv, dk, dv)]),
        out_shape=[jax.ShapeDtypeStruct((n, chunk, h, dv), f32),
                   jax.ShapeDtypeStruct((h, dk, dv), f32)],
        input_output_aliases={7: 0, 6: 1}, interpret=interpret,
        name=SCAN_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")))

    def step(i, carry):
        s, o = carry
        o, s = call(jnp.reshape(i, (1,)).astype(jnp.int32), q, k, v, a, beta,
                    s, o)
        return s, o

    s, o = jax.lax.fori_loop(
        0, n // m, step,
        (s0.astype(f32), jnp.zeros((n, chunk, h, dv), f32)))
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


def _head_block(n_head: int, most: int = _HEAD_BLOCK) -> int:
    """The largest divisor of ``n_head`` up to ``most``."""
    hb = min(most, n_head)
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

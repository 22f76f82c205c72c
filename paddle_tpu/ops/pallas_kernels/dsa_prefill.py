"""The prefill's attention under a ROW's own mask (DeepSeek sparse
attention: ``attention_ops.dsa_causal_attention``): every query row of one
sequence reads the rows its indexer chose, so no two rows share a mask and
the vendored flash kernel (a causal triangle, segment ids) cannot say it.

:func:`dsa_prefill_attention` is ONE Pallas call a layer
(``dsa_prefill_attention`` in a device trace): an online softmax in
float32 over key tiles, the scores of one ``[block_q, block_k]`` tile of
one head in VMEM at a time, so that no ``[H, block_q, S]`` tensor reaches
HBM. The mask comes as data, ``int8 [S, S]`` (the selection repeated over
a block's rows AND the causal triangle: the caller's, letter for letter
what the blocked form applies), and a grid step takes a GROUP of heads, so
that a mask tile is fetched and unpacked once a group. The grid is a group
of heads by the (query block, key tile) PAIRS up to each block's causal
edge, a block's tiles one after another: the tiles past a query block's
last row are not steps at all (a step that only skips still costs the
pipeline 0.9 us: 3,840 of them were 3.4 ms of a 36 ms call at 8,192 rows;
PERF.md, PR 63). Where each step reads and what it does come as
scalar-prefetch tables made from the sequence's LENGTH (the prompt in its
bucket): the pairs of a query block past the length stay on the last live
block's last tiles (held in VMEM: nothing is copied), compute nothing, and
the block's rows of the result are written as ZEROS, never left as the
buffer was (they go on through the output projection into a cache, where a
NaN in a row that a later step masks would still poison ``0 x NaN``). The
block that holds the last live row is computed whole: its rows past the
length read what the mask says.
``q``, ``k``, ``v`` and the result are the caller's ``[S, H D]`` lanes:
nothing is turned.

:func:`dsa_prefill_gate` says from the shapes alone whether the chip's
compiler takes the call, and why not; ``dsa_causal_attention`` asks it on
a TPU and keeps its blocked XLA form elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dsa_prefill_attention", "dsa_prefill_gate", "KERNEL_NAME"]

KERNEL_NAME = "dsa_prefill_attention"
_LANES = 128
_BLOCK_Q = 512              # query rows a grid step: K and V stream once a
_BLOCK_K = 512              # ... query block, S / 1024 times in all
_HEADS = 4                  # heads a grid step at the most
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = 40 << 20     # what a grid step's blocks and tiles may take


def _tile(want: int, s: int, unit: int) -> int:
    """The largest multiple of ``unit`` up to ``want`` that divides ``s``
    (0 where none does)."""
    b = min(int(want), s) // unit * unit
    while b > 0 and s % b:
        b -= unit
    return b


def _head_group(n_head: int, want: int = _HEADS) -> int:
    g = min(want, n_head)
    while n_head % g:
        g -= 1
    return g


def _vmem_bytes(bq: int, bk: int, g: int, d: int, d_v: int,
                itemsize: int) -> int:
    """A grid step's VMEM: q, k, v and the result twice (the pipeline's
    two buffers), the mask tile twice, the float32 accumulator, maximum
    and sum of each head, and four float32 score tiles of temporaries."""
    blocks = (bq * g * d + bk * g * d + bk * g * d_v + bq * g * d_v) \
        * itemsize + bq * bk
    return 2 * blocks + g * bq * (d_v + 2 * _LANES) * 4 + 4 * bq * bk * 4


def dsa_prefill_gate(n_head: int, d: int, d_v: int, s: int, kpool: int,
                     itemsize: int = 2, interpret: bool = False
                     ) -> Optional[str]:
    """None when the ``dsa_prefill_attention`` kernel takes ``s`` rows of
    ``n_head`` heads of ``d`` (values ``d_v``) under a selection by blocks
    of ``kpool`` rows, else the rule that excludes it (the chip compiler's
    tiling; the interpreter is bound by the first alone)."""
    if s % kpool:
        return "%d rows are not whole blocks of %d" % (s, kpool)
    if interpret:
        return None
    if d % _LANES or d_v % _LANES:
        return ("a head's q, k [., %d] and v [., %d] must be whole %d-lane "
                "tiles" % (d, d_v, _LANES))
    if s % _LANES:
        return "%d rows are not whole %d-row tiles of the mask" % (s, _LANES)
    bq, bk = _tile(_BLOCK_Q, s, _LANES), _tile(_BLOCK_K, s, _LANES)
    need = _vmem_bytes(bq, bk, _head_group(n_head), d, d_v, itemsize)
    if need > _VMEM_BUDGET:
        return ("a grid step's blocks, %d KiB, are more than %d KiB of VMEM"
                % (need >> 10, _VMEM_BUDGET >> 10))
    return None


_LIVE, _FIRST, _LAST, _ZERO = 1, 2, 4, 8     # what a grid step does


def _pairs(s: int, bq: int, bk: int):
    """The (query block, key tile) pairs up to each block's causal edge, a
    block's tiles one after another: ``(block [T], tile [T], the tile is
    its block's last [T])`` as numpy."""
    block, tile = zip(*((i, j) for i in range(s // bq)
                        for j in range(((i + 1) * bq - 1) // bk + 1)))
    block, tile = np.asarray(block, np.int32), np.asarray(tile, np.int32)
    return block, tile, tile == ((block + 1) * bq - 1) // bk


def _attn_kernel(q_at, k_at, o_at, what, q_ref, k_ref, v_ref, mask_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, heads, d, d_v, sm_scale, low):
    """One key tile of one query block of a group of ``heads`` heads:
    ``what`` [T] the step's part (``_LIVE``: a tile of a block that holds a
    row of the sequence, ``_FIRST`` and ``_LAST`` of its block's; ``_ZERO``:
    the last pair of a block past the length), ``q_ref`` [bq, heads d],
    ``k_ref`` [bk, heads d], ``v_ref`` [bk, heads dv], ``mask_ref`` int8
    [bq, bk]; the running maximum and sum (a row's value in every lane) and
    the accumulator stay in VMEM over the block's key tiles. ``q_at``,
    ``k_at`` and ``o_at`` are the index maps'."""
    f32 = jnp.float32
    part = what[pl.program_id(1)]

    @pl.when((part & _FIRST) != 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, low, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    @pl.when((part & _LIVE) != 0)
    def _():
        ok = mask_ref[...].astype(jnp.int32) != 0
        for h in range(heads):
            sc = jax.lax.dot_general(
                q_ref[:, h * d:(h + 1) * d], k_ref[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=f32
            ) * sm_scale                                     # [bq, bk]
            sc = jnp.where(ok, sc, low)
            m_prev = m_scr[h]                                # [bq, 128]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(sc - m_next[:, :1])
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = alpha[:, :1] * acc_scr[h] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[:, h * d_v:(h + 1) * d_v],
                preferred_element_type=f32)
            m_scr[h] = m_next

    @pl.when((part & _LAST) != 0)
    def _():
        for h in range(heads):
            o_ref[:, h * d_v:(h + 1) * d_v] = (
                acc_scr[h] / l_scr[h][:, :1]).astype(o_ref.dtype)

    @pl.when((part & _ZERO) != 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "heads", "interpret"))
def dsa_prefill_attention(q, k, v, mask, length=None,
                          sm_scale: float = 1.0, *,
                          block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K,
                          heads: int = _HEADS, interpret: bool = False):
    """Attention of ONE sequence under ``mask``: ``q``/``k`` [S, H, D],
    ``v`` [S, H, Dv], ``mask`` int8 [S, S], nonzero where row ``t`` reads
    key ``u`` (the CALLER's causal triangle in it: the grid only leaves out
    the key tiles that lie wholly past a query block, which no row of a
    causal mask reads; every row under the length reads at least itself),
    ``length`` an int32 scalar, the rows that are the sequence's (None:
    all S). The softmax in float32 with ``attention_ops``' masking
    constant, the weights cast to ``v``'s type before the second product.
    Returns [S, H, Dv] in ``q``'s type: ZEROS in every query block of
    ``block_q`` rows that starts at or past ``length``, which is neither
    computed nor copied; finite everywhere (a row past the length in the
    last live block reads what ``mask`` gives it, the mean of the keys its
    tiles hold where that is nothing). Jitted, so that the layers of one
    executable lower ONE kernel text."""
    from ..attention_ops import neg_inf_value

    s, n_head, d = q.shape
    d_v = v.shape[-1]
    why_not = dsa_prefill_gate(n_head, d, d_v, s, 1, q.dtype.itemsize,
                               interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    unit = 1 if interpret else _LANES
    bq, bk = _tile(block_q, s, unit), _tile(block_k, s, unit)
    g = _head_group(n_head, heads)
    # where each step reads and what it does, from the length: a pair of a
    # block past it stays on the last live block's last tiles
    block, tile, closes = _pairs(s, bq, bk)
    last_live = jnp.maximum(
        jnp.asarray(s if length is None else length, jnp.int32) - 1, 0) // bq
    dead = block > last_live
    q_at = jnp.minimum(block, last_live)
    k_at = jnp.where(dead, ((last_live + 1) * bq - 1) // bk, tile)
    what = jnp.where(dead, _ZERO * closes,
                     _LIVE + _FIRST * (tile == 0) + _LAST * closes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_head // g, len(block)),
        in_specs=[
            pl.BlockSpec((bq, g * d), lambda hg, t, q, k, o, w: (q[t], hg)),
            pl.BlockSpec((bk, g * d), lambda hg, t, q, k, o, w: (k[t], hg)),
            pl.BlockSpec((bk, g * d_v), lambda hg, t, q, k, o, w: (k[t], hg)),
            pl.BlockSpec((bq, bk), lambda hg, t, q, k, o, w: (q[t], k[t]))],
        out_specs=pl.BlockSpec((bq, g * d_v),
                               lambda hg, t, q, k, o, w: (o[t], hg)),
        scratch_shapes=[pltpu.VMEM((g, bq, _LANES), jnp.float32),
                        pltpu.VMEM((g, bq, _LANES), jnp.float32),
                        pltpu.VMEM((g, bq, d_v), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, heads=g, d=d, d_v=d_v, sm_scale=float(sm_scale),
            low=neg_inf_value(jnp.float32)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_head * d_v), q.dtype),
        interpret=interpret, name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(q_at, k_at, jnp.asarray(block), what.astype(jnp.int32),
      q.reshape(s, n_head * d), k.reshape(s, n_head * d),
      v.reshape(s, n_head * d_v), mask)
    return out.reshape(s, n_head, d_v)

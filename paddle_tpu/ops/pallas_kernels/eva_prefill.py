"""The prefill's EVA attention (``models/evabyte._prefill_attention``):
positions fall into TUMBLING windows of ``window`` rows; a row reads the
exact keys of its own window up to itself and, for every window that has
CLOSED before it, that window's ``kept`` pooled (key, value) pairs, all
under ONE softmax.

:func:`eva_prefill_attention` is ONE Pallas call a layer
(``eva_prefill_attention`` in a device trace), after ``window_prefill.py``,
whose tile fold it shares (``_reset``, ``_fold``): an online softmax in
float32 over key tiles, the scores of one tile of one head in VMEM at a
time, so that no ``[H, block_q, keys]`` tensor reaches HBM. The key tiles of a query block
of window ``w`` are, in order, the ``w`` SUMMARY tiles of the windows
before it (a closed window's ``kept`` summaries are one tile, so the tile
index is the window index and no summary tile is partly seen) and the
tiles of its OWN window up to the one that holds the block's last row, and
no others: grid steps past that neither compute nor copy (they stay on the
tiles they hold). Only the own window's tiles that the diagonal crosses
take a mask, made from ``iota`` inside the kernel. One running maximum, one
running sum and one float32 accumulator serve both kinds of tile: the model
states one softmax, and the kernel computes one.

The kernel reads q, k, v and the summaries and writes the result with the
ROWS IN THE LANES, ``[H D, S]``: the layout the chip's compiler gives the
model's projections and rotation by itself (row-major ``[S, H D]`` operands
cost a transposing copy of q, k AND v a layer: the compiled text of the
served prefill, ``tests/test_chip_compile.py``, ``eva_prefill``), so that
the wrapper's transposes are views. A head's 128 lanes of width lie along
the sublanes, a grid step takes a GROUP of heads one after another (a head
a KV head: nothing is shared between them but the step), a row's running
maximum and sum are lane vectors, and only the K tile is turned, once a
head a tile.

:func:`eva_prefill_gate` says from the shapes alone whether the chip's
compiler takes the call, and why not; ``_prefill_attention`` asks it on a
TPU and keeps its blocked XLA form elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa_prefill import _head_group, _tile
from .window_prefill import (_LANES, _VMEM_BUDGET, _VMEM_LIMIT, _fold,
                             _reset)

__all__ = ["eva_prefill_attention", "eva_prefill_gate", "KERNEL_NAME"]

KERNEL_NAME = "eva_prefill_attention"
# Read on a v5e at the three served buckets (PERF.md, PR 59): query blocks
# of 1,024 rows against key tiles of 512, four heads a grid step, are 8-15%
# faster than 512 x 512 (a K tile is turned once a head for twice the
# rows, a summary tile serves twice the rows a grid step) though their
# diagonal tiles score a fifth more pairs; 1,024 x 1,024, 256 x 256 and
# eight heads a step read between the two.
_BLOCK_Q = 1024             # query rows a grid step
_BLOCK_K = 512              # rows of an own-window key tile
_HEADS = 4                  # heads a grid step at the most


def _tiles(window: int, block_q: int, block_k: int, unit: int):
    """``(block_q, block_k)`` cut to the window: a key tile of whole
    ``unit``s that divides it, a query block of whole key tiles that
    does."""
    bk = _tile(block_k, window, unit)
    return _tile(block_q, window, bk), bk


def _vmem_bytes(bq: int, bk: int, kept: int, g: int, d: int,
                itemsize: int) -> int:
    """A grid step's VMEM: q, a k and a v tile, a window's summaries and
    the result twice (the pipeline's two buffers), the float32
    accumulator, maximum and sum of each head, a K tile turned and four
    float32 score tiles of temporaries."""
    blocks = (2 * bq + 2 * bk + 2 * kept) * g * d * itemsize
    return 2 * blocks + g * bq * (d + 16) * 4 + bk * d * itemsize \
        + 4 * bq * bk * 4


def eva_prefill_gate(n_head: int, d: int, s: int, window: int, chunk: int,
                     itemsize: int = 2, interpret: bool = False
                     ) -> Optional[str]:
    """None when the ``eva_prefill_attention`` kernel takes ``s`` rows of
    ``n_head`` heads of ``d`` under tumbling windows of ``window`` rows
    summarised a ``chunk``, else the rule that excludes it (the chip
    compiler's tiling; the interpreter is bound by the first alone)."""
    if window % chunk or s % window:
        return ("%d rows are not whole windows of %d in whole chunks of %d"
                % (s, window, chunk))
    if interpret:
        return None
    sublanes = 32 // itemsize   # rows of one packed tile: 16 in bfloat16
    if d % sublanes:
        return ("a head's q, k and v [., %d] must be whole %d-row sublane "
                "tiles" % (d, sublanes))
    if window % _LANES:
        return "a window of %d rows is not whole %d-row tiles" % (
            window, _LANES)
    kept = window // chunk
    if kept % _LANES:
        return ("a closed window's %d summaries (%d / %d) are not whole "
                "%d-lane tiles" % (kept, window, chunk, _LANES))
    bq, bk = _tiles(window, _BLOCK_Q, _BLOCK_K, _LANES)
    need = _vmem_bytes(bq, bk, kept, _head_group(n_head, _HEADS), d,
                       itemsize)
    if need > _VMEM_BUDGET:
        return ("a grid step's blocks, %d KiB, are more than %d KiB of VMEM"
                % (need >> 10, _VMEM_BUDGET >> 10))
    return None


def _own(i, bq: int, bk: int, window: int):
    """Query block ``i``'s place: ``(w, first, last)``, the windows closed
    before it, its window's first key tile and the tile of that window
    that holds the block's last row (``i`` a Python int or a traced
    scalar)."""
    w = (i * bq) // window
    return w, w * (window // bk), ((i + 1) * bq - 1) // bk


def _attn_kernel(*refs, heads, d, sm_scale, low, block_q, block_k, window,
                 closed):
    """Grid step ``j`` of one query block of a group of ``heads`` heads,
    rows in the lanes: ``q_ref`` [heads d, bq], ``k_ref``/``v_ref``
    [heads d, bk] a tile of the block's own window and, where a window can
    have closed before a block (``closed``), ``ks_ref``/``vs_ref`` [heads
    d, kept] one closed window's summaries, ``o_ref`` [heads d, bq]; steps
    ``j < w`` fold window ``j``'s summaries, the next ones the own window's
    tiles up to the block's last row. A head's running maximum and sum
    ``[8, bq]`` (a row's value a lane, in every sublane) and accumulator
    ``[d, bq]`` stay in VMEM over both kinds."""
    f32 = jnp.float32
    if closed:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr \
            = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    i, j = pl.program_id(0), pl.program_id(2)
    w, first, last = _own(i, block_q, block_k, window)
    tile = first + j - w            # of the own window, from step w on
    row0, key0 = i * block_q, tile * block_k

    pl.when(j == 0)(lambda: _reset(low, m_scr, l_scr, acc_scr))

    def fold(k_tile, v_tile, ok=None):
        for h in range(heads):
            lanes = slice(h * d, (h + 1) * d)
            sc = jnp.dot(k_tile[lanes, :].T, q_ref[lanes, :],
                         preferred_element_type=f32) * sm_scale  # [bk, bq]
            if ok is not None:
                sc = jnp.where(ok, sc, low)
            _fold(sc, v_tile.at[lanes, :], m_scr.at[h], l_scr.at[h],
                  acc_scr.at[h])

    if closed:
        # every row of the block sees every summary of a closed window
        pl.when(j < w)(lambda: fold(ks_ref, vs_ref))

    # the diagonal crosses a tile that holds a key past the block's first
    # row; each row sees at least itself there
    own = (j >= w) & (tile <= last)
    on_edge = key0 + block_k - 1 > row0

    @pl.when(own & on_edge)
    def _():
        keys = key0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        fold(k_ref, v_ref, keys <= rows)

    pl.when(own & jnp.logical_not(on_edge))(lambda: fold(k_ref, v_ref))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            o_ref[h * d:(h + 1) * d, :] = (
                acc_scr[h] / l_scr[h][:1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "chunk", "sm_scale", "block_q", "block_k", "heads", "interpret"))
def eva_prefill_attention(q, k, v, ks, vs, window: int, chunk: int,
                          sm_scale: float = 1.0, *, block_q: int = _BLOCK_Q,
                          block_k: int = _BLOCK_K, heads: int = _HEADS,
                          interpret: bool = False):
    """EVA attention of ONE sequence of whole windows: ``q``/``k``/``v``
    [S, H, D] (k rotated), ``ks``/``vs`` [n, H, D] a summary a ``chunk``:
    window ``w``'s ``kept = window // chunk`` at rows ``[kept w, kept (w +
    1))``, for every window but the last at least (``S == window`` reads no
    summary and takes any ``n``). Row ``p`` reads the summaries of the
    windows before its own and its own window's keys up to itself, one
    softmax over both: float32 scores, maximum, exponent and sum, the
    weights cast to ``v``'s type before the second product, the division in
    float32. The kernel's operands and result are ``[H D, rows]`` (the
    module's note): the transposes here are views where the compiler lays
    the producers out that way. Returns [S, H, D] in ``q``'s type. Jitted,
    so that the layers of one executable lower ONE kernel text."""
    from ..attention_ops import neg_inf_value

    s, n_head, d = q.shape
    why_not = eva_prefill_gate(n_head, d, s, window, chunk,
                               q.dtype.itemsize, interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    closed = s // window - 1        # what the last window's rows see
    kept = window // chunk
    if ks.shape[0] < closed * kept:
        raise ValueError("%d summaries for %d closed windows of %d"
                         % (ks.shape[0], closed, kept))
    bq, bk = _tiles(window, block_q, block_k, 1 if interpret else _LANES)
    g = _head_group(n_head, heads)

    def rows(i, hg, j):
        return hg, i

    def own_tile(i, hg, j):     # held at the ends: no copy outside the band
        w, first, last = _own(i, bq, bk, window)
        return hg, jnp.clip(first + j - w, first, last)

    def summary_tile(i, hg, j):
        return hg, jnp.minimum(j, jnp.maximum(_own(i, bq, bk, window)[0] - 1,
                                              0))

    def lanes(t):               # [rows, H, D] with the rows in the lanes
        return t.reshape(t.shape[0], n_head * d).T

    operands = [lanes(q), lanes(k), lanes(v)]
    in_specs = [pl.BlockSpec((g * d, bq), rows),
                pl.BlockSpec((g * d, bk), own_tile),
                pl.BlockSpec((g * d, bk), own_tile)]
    if closed:
        operands += [lanes(ks), lanes(vs)]
        in_specs += [pl.BlockSpec((g * d, kept), summary_tile)] * 2
    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, heads=g, d=d, sm_scale=float(sm_scale),
            low=neg_inf_value(jnp.float32), block_q=bq, block_k=bk,
            window=int(window), closed=closed),
        grid=(s // bq, n_head // g, closed + window // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((g * d, bq), rows),
        out_shape=jax.ShapeDtypeStruct((n_head * d, s), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 8, bq), jnp.float32),
                        pltpu.VMEM((g, 8, bq), jnp.float32),
                        pltpu.VMEM((g, d, bq), jnp.float32)],
        interpret=interpret, name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(*operands)
    return out.T.reshape(s, n_head, d)

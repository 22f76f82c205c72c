"""The prefill's attention of a WINDOW layer (``attention_ops.
windowed_causal_attention``): row ``i`` of one sequence reads the keys ``j
<= i`` with ``i - j < window``, query head ``n`` the KV head ``n // G``.

:func:`window_prefill_attention` is ONE Pallas call a layer
(``window_prefill_attention`` in a device trace), after
``dsa_prefill.py``: an online softmax in float32 over key tiles, the scores
of one ``[block_k, G block_q]`` tile of one KV head's ``G`` query heads in
VMEM at a time, so that no ``[Hkv, G, block_q, window + block_q]`` tensor
reaches HBM. A grid step takes ONE KV head and its ``G`` query heads, so a
K and a V tile is fetched once a group, and the group's query blocks lie
side by side in the lanes: a tile is TWO products for the whole group. The
key tiles of a query block are those of its BAND and no others: from the
tile that holds the first row's oldest key to the tile that holds the last
row; grid steps past the band neither compute nor copy (they stay on the
band's last tile). The mask is made from ``iota`` inside the kernel, and
only on the tiles the band's two edges cross.

The kernel reads q, k, v and writes the result with the ROWS IN THE LANES,
``[H D, S]``: the layout the chip's compiler gives the three served models'
projections and rotations by itself (a head's 64-lane halves and 192-lane
widths waste no lane there), so that the wrapper's transposes are views
and nothing is copied, turned or repeated between a layer's products and
the call (row-major ``[S, H D]`` operands cost a transposing copy of q a
layer in two of the three models: PERF.md, PR 55). In that layout a
head's width lies along the sublanes, so 192 needs no padding, a row's
running maximum and sum are lane vectors, and only the K tile is turned,
once a grid step for the group.

The tiles follow the window and the group (:func:`_tiles`): a key tile of
half the window between 128 and 512 rows, a query block of up to two key
tiles that keeps the group's lanes in bounds, so that a 128-row window
reads three 128-row tiles a query block of 256 rows and a 4,096-row window
nine 512-row tiles a query block of 512.

:func:`window_prefill_gate` says from the shapes alone whether the chip's
compiler takes the call, and why not; ``windowed_causal_attention`` asks it
on a TPU and keeps its blocked XLA form elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa_prefill import _tile

__all__ = ["window_prefill_attention", "window_prefill_gate", "KERNEL_NAME"]

KERNEL_NAME = "window_prefill_attention"
_LANES = 128
_BLOCK_K = 512              # key rows a tile at the most
_GROUP_LANES = 3584         # (head, row) pairs a score tile at the most
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = 40 << 20     # what a grid step's blocks and tiles may take


def _tiles(s: int, window: int, g: int, unit: int = _LANES
           ) -> Tuple[int, int]:
    """``(block_q, block_k)`` for ``s`` rows of ``g`` query heads a KV
    head under ``window``: a key tile of HALF the window, between one lane
    tile and 512 rows (the band's two edge tiles are half masked: at a
    window of 512, tiles of 256 score 768 keys a query block of 256 rows
    where tiles of 512 score 1,024), and a query block of up to two key
    tiles that keeps the group's ``g block_q`` lanes within
    ``_GROUP_LANES``; each cut to the largest multiple of ``unit`` that
    divides ``s`` (0 where none does). Read on a v5e at 8,192 rows
    (PERF.md, PR 55): 512 x 512 at 7 heads a group under 4,096, 256 x 256
    at 9 under 512, 256 x 128 at 5 under 128 are each the best or within
    2% of the best of nine tilings."""
    bk = _tile(max(min(_BLOCK_K, window // 2), unit), s, unit)
    return _tile(max(min(2 * bk, _GROUP_LANES // g), unit), s, unit), bk


def _band(i, bq: int, bk: int, window: int, maximum=max):
    """The first and last key tile that query block ``i`` reads (``i`` a
    Python int, or a traced scalar with ``jnp.maximum``)."""
    return (maximum(i * bq - (window - 1), 0) // bk,
            ((i + 1) * bq - 1) // bk)


def _band_tiles(s: int, bq: int, bk: int, window: int) -> int:
    """The key tiles of the widest band: the grid's last dimension."""
    return max(hi - lo + 1 for lo, hi in
               (_band(i, bq, bk, window) for i in range(s // bq)))


def _vmem_bytes(bq: int, bk: int, g: int, d: int, d_v: int,
                itemsize: int) -> int:
    """A grid step's VMEM: q, k, v and the result twice (the pipeline's
    two buffers), the group's queries side by side and the K tile turned,
    the float32 accumulator, maximum and sum of the group, and four
    float32 score tiles of the whole group as temporaries."""
    blocks = (bq * g * d + bk * d + bk * d_v + bq * g * d_v) * itemsize
    return 2 * blocks + (bq * g * d + bk * d) * itemsize \
        + g * bq * (d_v + 16) * 4 + 4 * bk * g * bq * 4


def window_prefill_gate(n_head: int, n_kv_head: int, d: int, d_v: int,
                        s: int, window: int, itemsize: int = 2,
                        interpret: bool = False) -> Optional[str]:
    """None when the ``window_prefill_attention`` kernel takes ``s`` rows
    of ``n_head`` query heads of ``d`` over ``n_kv_head`` KV heads (values
    ``d_v``) under ``window``, else the rule that excludes it (the chip
    compiler's tiling; the interpreter is bound by the first alone)."""
    if n_head % n_kv_head:
        return "%d query heads are not whole groups of %d KV heads" % (
            n_head, n_kv_head)
    if interpret:
        return None
    sublanes = 32 // itemsize   # rows of one packed tile: 16 in bfloat16
    if d % sublanes or d_v % sublanes:
        return ("a head's q, k [., %d] and v [., %d] must be whole %d-row "
                "sublane tiles" % (d, d_v, sublanes))
    bq, bk = _tiles(s, window, n_head // n_kv_head)
    if not bk:
        return "%d rows are not whole tiles of %d rows" % (s, _LANES)
    need = _vmem_bytes(bq, bk, n_head // n_kv_head, d, d_v, itemsize)
    if need > _VMEM_BUDGET:
        return ("a grid step's blocks, %d KiB, are more than %d KiB of VMEM"
                % (need >> 10, _VMEM_BUDGET >> 10))
    return None


def _reset(low, m_scr, l_scr, acc_scr):
    """Before a query block's first key tile: nothing seen yet."""
    m_scr[...] = jnp.full(m_scr.shape, low, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _fold(sc, v_ref, m_ref, l_ref, acc_ref):
    """A key tile's (masked) float32 scores ``sc`` [bk, n], a (head, row)
    a lane, into the running softmax of those lanes: ``m_ref``, ``l_ref``
    [8, n] (the value in every sublane) and ``acc_ref`` [dv, n], with the
    tile's values ``v_ref`` [dv, bk]. Maximum, exponent and sum in
    float32, the weights cast to the values' type for the product.
    ``eva_prefill.py`` folds its tiles by it too, a head at a time, after
    the same :func:`_reset`."""
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(sc - m_next[:1])
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[...] = alpha[:1] * acc_ref[...] + jnp.dot(
        v_ref[...], p.astype(v_ref.dtype),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_next


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 group, d, d_v, sm_scale, low, block_q, block_k, window):
    """One key tile of one query block of ONE KV head and its ``group``
    query heads, rows in the lanes: ``q_ref`` [group d, bq], ``k_ref`` [d,
    bk], ``v_ref`` [dv, bk], ``o_ref`` [group dv, bq]. The group's heads
    share K and V, so their query blocks go SIDE BY SIDE in the lanes,
    ``[d, group bq]``, and a tile is two products for the whole group; the
    running maximum and sum ``[8, group bq]`` (a (head, row)'s value a
    lane, in every sublane) and the accumulator ``[dv, group bq]`` stay in
    VMEM over the band's key tiles."""
    f32 = jnp.float32
    i, j = pl.program_id(0), pl.program_id(2)
    lo, hi = _band(i, block_q, block_k, window, jnp.maximum)
    row0, key0 = i * block_q, (lo + j) * block_k

    pl.when(j == 0)(lambda: _reset(low, m_scr, l_scr, acc_scr))

    def tile(ok):
        """The band's tile ``lo + j`` into the group's running softmax,
        under ``ok`` [bk, bq] where an edge of the band crosses it. A row
        the tile holds no key of gathers weights of 1, which its own
        diagonal's tile, the band's last, multiplies by exp(low - m) = 0."""
        q = jnp.concatenate([q_ref[h * d:(h + 1) * d, :]
                             for h in range(group)], axis=1)
        sc = jnp.dot(k_ref[...].T, q,
                     preferred_element_type=f32) * sm_scale  # [bk, G bq]
        if ok is not None:
            sc = jnp.where(jnp.concatenate([ok] * group, axis=1), sc, low)
        _fold(sc, v_ref, m_scr, l_scr, acc_scr)

    # the band's two edges: the causal one crosses a tile that holds a key
    # past the block's first row, the window's one a tile whose first key
    # the block's last row no longer sees
    in_band = lo + j <= hi
    on_edge = (key0 + block_k - 1 > row0) \
        | (row0 + block_q - 1 - key0 >= window)

    @pl.when(in_band & on_edge)
    def _():
        keys = key0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        tile((keys <= rows) & (rows - keys < window))

    @pl.when(in_band & jnp.logical_not(on_edge))
    def _():
        tile(None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        out = (acc_scr[...] / l_scr[...][:1]).astype(o_ref.dtype)
        for h in range(group):
            o_ref[h * d_v:(h + 1) * d_v, :] = \
                out[:, h * block_q:(h + 1) * block_q]


@functools.partial(jax.jit, static_argnames=(
    "window", "sm_scale", "block_q", "block_k", "interpret"))
def window_prefill_attention(q, k, v, window: int, sm_scale: float = 1.0, *,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False):
    """Causal attention of ONE sequence under a window: ``q`` [S, Hq, D],
    ``k`` [S, Hkv, D], ``v`` [S, Hkv, Dv]; row ``i`` of query head ``n``
    reads the keys ``j <= i`` with ``i - j < window`` of KV head ``n //
    (Hq // Hkv)``. The softmax in float32 with ``attention_ops``' masking
    constant, the weights cast to ``v``'s type before the second product.
    The kernel's operands and result are ``[H D, S]`` (the module's note):
    the transposes here are views where the compiler lays the producers
    out that way. ``block_q`` and ``block_k`` default to :func:`_tiles`'
    choice. Returns [S, Hq, Dv] in ``q``'s type. Jitted, so that the
    layers of one executable lower ONE kernel text."""
    from ..attention_ops import neg_inf_value

    s, n_head, d = q.shape
    n_kv, d_v = k.shape[1], v.shape[-1]
    why_not = window_prefill_gate(n_head, n_kv, d, d_v, s, window,
                                  q.dtype.itemsize, interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    g = n_head // n_kv
    bq, bk = _tiles(s, window, g, 1 if interpret else _LANES)
    bq, bk = block_q or bq, block_k or bk
    if s % bq or s % bk:
        raise ValueError("%d rows are not whole blocks of %d and %d"
                         % (s, bq, bk))

    def key_tile(i, h, j):
        lo, hi = _band(i, bq, bk, window, jnp.maximum)
        return h, jnp.minimum(lo + j, hi)

    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, group=g, d=d, d_v=d_v, sm_scale=float(sm_scale),
            low=neg_inf_value(jnp.float32), block_q=bq, block_k=bk,
            window=int(window)),
        grid=(s // bq, n_kv, _band_tiles(s, bq, bk, window)),
        in_specs=[
            pl.BlockSpec((g * d, bq), lambda i, h, j: (h, i)),
            pl.BlockSpec((d, bk), key_tile),
            pl.BlockSpec((d_v, bk), key_tile)],
        out_specs=pl.BlockSpec((g * d_v, bq), lambda i, h, j: (h, i)),
        out_shape=jax.ShapeDtypeStruct((n_head * d_v, s), q.dtype),
        scratch_shapes=[pltpu.VMEM((8, g * bq), jnp.float32),
                        pltpu.VMEM((8, g * bq), jnp.float32),
                        pltpu.VMEM((d_v, g * bq), jnp.float32)],
        interpret=interpret, name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(q.reshape(s, n_head * d).T, k.reshape(s, n_kv * d).T,
      v.reshape(s, n_kv * d_v).T)
    return out.T.reshape(s, n_head, d_v)

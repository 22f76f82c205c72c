"""Latent (MLA) paged decode attention: the absorbed form of multi-head
latent attention over ONE shared row a token.

A latent cache keeps, for each context position of a layer, one row ``[c |
kr | 0]``: the compressed KV latent ``c`` (``rank`` lanes), the one rotary
key ``kr`` every head shares, and zero lanes up to a whole lane tile
(``kv_cache.LatentPagedCache``). With the up-projections absorbed into the
query and the output (``models/kimi_k2.py``), every one of the ``H`` query
heads scores against the SAME row over all its lanes, and the weighted sum
runs over the first ``rank`` lanes of the SAME row:

    s[h, j] = sm_scale * q[h, :] . row[j, :]
    o[h, :] = sum_j softmax_j(s[h, :]) row[j, :rank]

so a page is DMA'd once and feeds both products: about ``2 H (W + rank) /
(2 W)`` operations a cache byte (60 at H = 32, 121 at H = 64, 151 at H =
80, over 576 + 512 lanes in bf16: the served models' head counts), near
the chip's ridge, where the grouped kernel of ``paged_attention.py`` (a
few operations a byte) is far under it. ``H`` is any number: the heads are
padded to whole sublanes (8) and the score tile is ``[H, 512]`` float32.
Hence the differences from that kernel, whose page-table, scalar-prefetch
and ragged-length skeleton this one shares:

- both products ride the MXU in the POOL's type with float32 accumulation
  (bf16 rows are not widened first; a float32 pool, as in the CPU tests,
  multiplies at full precision); the softmax state is float32;
- the waves are double-buffered: wave ``w + 1``'s pages are in flight
  while wave ``w`` is folded, because here the fold is not free beside the
  DMA;
- no head-membership matmuls and no V pool.

A slot of length 0 writes zeros and ends its grid step there; rows at or
beyond the length are zeroed before use and masked with the package's one
masking constant, so stale rows contribute exactly 0.0. The kernel's name
in a device trace is ``mla_latent_decode``; a caller whose page table is a
RING of a few pages (a window layer: one wave a slot, a call whose cost is
its launches and DMA waits and not its bytes) names its calls
``mla_latent_decode_ring`` so that a trace's reader can tell the two.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_paged_decode", "mla_gather_reference", "mla_decode_gate",
           "KERNEL_NAME", "RING_KERNEL_NAME"]

KERNEL_NAME = "mla_latent_decode"
RING_KERNEL_NAME = "mla_latent_decode_ring"
_LANES = 128
_WAVE_ROWS = 512     # context rows a wave folds: [H, 512] f32 scores


def mla_decode_gate(dtype, width: int, rank: int, page_size: int,
                    interpret: bool = False) -> Optional[str]:
    """None when the compiled kernel takes this latent geometry, else the
    rule that excludes it: a row or a latent that is not whole lane tiles,
    a page that is not whole sublane tiles of the pool's type, a type that
    is neither float32 nor bfloat16. The number of heads is not among the
    rules (32, 64 and 80 are served). The shape rules are the chip
    compiler's tiling and do not bind the interpreter."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "latent dtype %s is not float32/bfloat16" % dt.name
    if interpret:
        return None
    if width % _LANES or rank % _LANES:
        return ("row width %d and latent rank %d must be multiples of %d"
                % (width, rank, _LANES))
    sublanes = 32 // dt.itemsize
    if page_size % sublanes:
        return ("page_size=%d is not a multiple of the %s tile's %d rows"
                % (page_size, dt.name, sublanes))
    return None


def _mla_kernel(pt_ref, len_ref, layer_ref, q_ref, pool, o_ref, scr, sems, *,
                block_pages, page_size, pages_per_slot, num_pages, rank,
                sm_scale, mask_value, precision):
    b = pl.program_id(0)  # out here: the interpreter has none in a branch
    ctx = len_ref[b]
    live = ctx > 0
    ps = page_size
    rows = block_pages * ps
    layer = layer_ref[0]

    def dma(w, i, buf):
        """The copy of wave ``w``'s page ``i`` into buffer ``buf``."""
        pidx = jnp.minimum(w * block_pages + i, pages_per_slot - 1)
        page = jnp.clip(pt_ref[b * pages_per_slot + pidx], 0, num_pages - 1)
        return pltpu.make_async_copy(
            pool.at[layer, pl.ds(page * ps, ps)],
            scr.at[buf, pl.ds(i * ps, ps)], sems.at[buf, i])

    def each_page(w, buf, act):
        def body(i, _):
            pidx = w * block_pages + i

            @pl.when((pidx < pages_per_slot) & (pidx * ps < ctx))
            def _():
                act(dma(w, i, buf))

            return 0

        jax.lax.fori_loop(0, block_pages, body, 0)

    @pl.when(live)
    def _():
        q = q_ref[0]                                  # [H, W], pool's type
        n_waves = -(-pages_per_slot // block_pages)
        live_waves = jnp.minimum((ctx + rows - 1) // rows, n_waves)
        each_page(0, 0, lambda c: c.start())

        def wave(w, carry):
            m, l, acc = carry
            buf = w % 2

            @pl.when(w + 1 < live_waves)
            def _():
                each_page(w + 1, 1 - buf, lambda c: c.start())

            each_page(w, buf, lambda c: c.wait())
            col = w * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            # rows past the length hold whatever the buffer last held
            kb = jnp.where(col < ctx, scr[buf], 0)    # [R, W]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * sm_scale   # [H, R]
            pos = w * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            s = jnp.where(pos < ctx, s, mask_value)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)    # masked rows underflow to exactly 0.0
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jnp.dot(
                p.astype(kb.dtype), kb[:, :rank], precision=precision,
                preferred_element_type=jnp.float32)              # [H, rank]
            return m_new, l_new, acc_new

        h = q.shape[0]
        init = (jnp.full((h, 1), mask_value, jnp.float32),
                jnp.zeros((h, 1), jnp.float32),
                jnp.zeros((h, rank), jnp.float32))
        # ctx >= 1 here, so every state has folded a valid row: l >= 1
        _, l, acc = jax.lax.fori_loop(0, live_waves, wave, init)
        o_ref[0] = (acc / l).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def mla_paged_decode(q, pool, page_table, ctx_len, *, page_size, rank,
                     layer=None, sm_scale=1.0, block_pages=None,
                     interpret: bool = False, name: str = KERNEL_NAME):
    """Absorbed latent decode attention over a paged pool.

    ``q`` [B, H, W]: each head's absorbed query over the row's lanes (its
    lanes beyond what the row really holds must be 0, as the row's are).
    ``pool`` [n_layer, num_pages * page_size, W] (the whole pool, of which
    layer ``layer`` is read), or one layer [rows, W] with ``layer`` None.
    ``page_table`` [B, pages_per_slot] int32; ``ctx_len`` [B] valid leading
    rows a slot (0: the slot holds nothing, its output is exactly 0.0 and
    it moves no page). ``name`` is the call's name in a device trace.
    Returns [B, H, rank] in ``q``'s type, matching
    :func:`mla_gather_reference` to the products' round-off."""
    b, h, width = q.shape
    if pool.ndim == 2 and layer is None:
        pool, layer = pool[None], 0
    if pool.ndim != 3 or layer is None or pool.shape[-1] != width \
            or not 0 < rank <= width:
        raise ValueError("pool must be [n_layer, rows, %d] with a layer, or "
                         "one layer without, rank <= the row: got %s "
                         "layer=%r rank=%d" % (width, pool.shape, layer, rank))
    slots, pages_per_slot = page_table.shape
    if slots != b:
        raise ValueError("page_table slots %d != q batch %d" % (slots, b))
    ps = int(page_size)
    n_layer, num_rows = pool.shape[:2]
    if num_rows % ps:
        raise ValueError("pool rows %d not a multiple of page_size %d"
                         % (num_rows, ps))
    if isinstance(layer, (int, np.integer)) and not 0 <= layer < n_layer:
        raise ValueError("layer %d outside a pool of %d" % (layer, n_layer))
    bp = int(block_pages) if block_pages else max(1, _WAVE_ROWS // ps)
    bp = max(1, min(bp, pages_per_slot))
    from ..attention_ops import neg_inf_value

    f32 = pool.dtype == jnp.float32
    hp = -(-h // 8) * 8     # whole sublanes of heads; the padding is zeros
    qk = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, hp - h), (0, 0)))
    kernel = functools.partial(
        _mla_kernel, block_pages=bp, page_size=ps,
        pages_per_slot=pages_per_slot, num_pages=num_rows // ps,
        rank=int(rank), sm_scale=float(sm_scale),
        mask_value=neg_inf_value(jnp.float32),
        precision=jax.lax.Precision.HIGHEST if f32 else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, bp * ps, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, bp))])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, rank), q.dtype),
        interpret=interpret, name=name,
    )(page_table.reshape(-1).astype(jnp.int32), ctx_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qk, pool)
    return out[:, :h]


def mla_gather_reference(q, pool, page_table, ctx_len, page_size, rank,
                         sm_scale=1.0):
    """The XLA path the kernel replaces, over ONE layer ``[rows, W]``: the
    page gather composed with ``attention_ops.mla_decode_attention``."""
    ps = int(page_size)
    rows = (page_table * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
    from ..attention_ops import mla_decode_attention

    return mla_decode_attention(q, pool[rows.reshape(q.shape[0], -1)],
                                ctx_len, rank, sm_scale=sm_scale)

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
- no head-membership matmuls and no V pool.

A wave's order of work (PR 44; what each part cost before and after is in
PERF.md section 6 and ``benchmarks/diag_latent_ring.py``). The waves are
double-buffered over the WHOLE sequential grid: two wave buffers, one DMA
semaphore each, and two SMEM words that one slot leaves the next (which
buffer the next live slot's wave 0 lands in, and whether it is already
started). For wave ``w`` of a slot, in buffer ``buf``, ONE loop body:

1. start what comes next into the other buffer: this slot's wave ``w + 1``
   or, behind the slot's last wave, wave 0 of the NEXT slot that holds a
   row (rowless slots are skipped over; the last live slot starts
   nothing), so that a slot's first wave, and the ONE wave of a ring call,
   is in flight while the slot before is folded;
2. wait for wave ``w``;
3. fold it into the online-softmax state ``(m, l, acc)``.

A copy moves a page RUN (``copy_pages``, static; PR 64). A page table is
one entry a ``page_size``-row page, but the pool of a latent group
(``serving/page_pool.py``) hands its pages out in aligned runs of R pages
side by side, and a reservation is whole runs: so entry ``R g`` of a
slot's table starts ``R * page_size`` consecutive pool rows that are all
the slot's own. THE POOL guarantees that (the engine checks it on the host
where it sets a slot's table); the caller that knows it
(``kv_cache.LatentPagedCache``, from the group's geometry:
:func:`run_pages`) says so with ``copy_pages=R`` and the kernel RELIES on
it: it reads the run's FIRST entry alone and moves the run with one
descriptor, ``block_pages / R`` copies a wave for ``block_pages``. With
``copy_pages=1`` (the default; the sparse read's 8-row tiles, whose second
table is made a step and knows no runs) the kernel is, to the letter, the
one it was. A corrupt entry is still clamped, to the last whole run: it
reads wrong rows, never out of bounds.

A wave is FULL when every one of its rows is below the slot's length and
every one of its pages is in the table. Its copies start
with no predicate, in unrolled runs of four, one loop a buffer (so a
copy's buffer and semaphore are constants of its descriptor); they are
waited for ONCE (all signal the buffer's one semaphore; the wait is for
their sum) and the buffer is folded as it lies. Only a slot's last wave
can be partial: it copies and awaits just the runs that hold a row below
the length (a loop of that many trips; the run that straddles the length
is copied whole, its pages being the slot's own) and ZEROES the rows at or
beyond the length in the buffer, where the exactly-0.0 probabilities would meet
them. The scores of such rows are replaced with the package's one masking
constant; that select runs on every wave (all true in a full one, at a
cost no chip run could read) so that the kernel holds one fold. So stale
rows (whatever the buffer last held, Inf and NaN included) contribute
exactly 0.0. A slot of length 0 writes zeros, moves no page and leaves the
two words as they are; a length past the table is read as the table's.

The kernel's TEXT is part of its cost: a decode executable holds one copy
a layer and the serve cells compile theirs at every start (0.2 s a copy
before PR 44). Every copy of a wave unrolled with a constant destination
folds a 512-row wave in 1.21 us (56% of the stream at 80 heads) and
compiles in 1.0-1.9 s a copy, 22 s more set-up in Kimi's cell; the runs of
four read 1.5 us (45%) and compile in 0.3 s. On a v5e at 80 heads the fold
alone is 0.80 us a wave, the copies alone 0.85, and a copy's descriptor
costs the scalar unit 13 ns (20 with a destination computed at run time)
that it does NOT overlap with the fold: what bounded a wave of single
pages was its 32 descriptors, not its 655 KB (0.80 us at the HBM rate).
With runs of 4 pages (8 descriptors a wave; ``benchmarks/diag_latent_ring
.py --copy-pages 1,4,8``, PR 64, ragged slots of 2,688 and 4,800 rows, us a
wave at R = 1 | 4 | 8): 32 heads 1.31 | 1.00 | 1.01, 64 heads 1.44 | 1.05 |
1.06, 80 heads 1.54 | 1.11 | 1.12, 128 heads 1.83 | 1.34 | 1.32; a ring
call of 64 slots 55.7 | 46.6 | 46.6 us. No reading tells 8 from 4, and 4
pads a reservation by three pages at the most: ``RUN_PAGES`` is 4. What is
left above the stream's 0.80 us is the fold itself (1.28 us of products at
128 heads) and a wave's own loop. The
kernel's name in a device trace is ``mla_latent_decode``; a caller whose
page table is a RING of a few pages (a window layer: one wave a slot, a
call whose cost is each slot's own chain and not its bytes) names its calls
``mla_latent_decode_ring`` so that a trace's reader can tell the two.

The SPARSE read (``dsa_sparse_decode``; a layer whose indexer chooses the
blocks of rows a query reads, ``kv_cache.LatentPagedCache
.sparse_decode_attention``) is the same kernel over a second, shorter table
a step: the "pages" are the 8-row TILES that hold a chosen block, and a
``row_valid`` operand [B, table rows] keeps the chosen blocks' rows, since
a tile may hold a block that was not chosen. Eight rows, not a block's
four: a bfloat16 pool lies in HBM in tiles of (8, 128) 32-bit words' worth
of rows and the chip's compiler refuses a copy that is not whole tiles
("Slice shape along dimension 1 must be aligned to tiling (8), but is 4"),
so two neighbouring blocks are ONE copy and ONE descriptor whether one of
them or both were chosen; the fold then runs over the tile's eight rows
and the mask drops the four that were not.
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
           "KERNEL_NAME", "RING_KERNEL_NAME", "SPARSE_KERNEL_NAME",
           "SPARSE_TILE", "RUN_PAGES", "run_pages"]

KERNEL_NAME = "mla_latent_decode"
RING_KERNEL_NAME = "mla_latent_decode_ring"
SPARSE_KERNEL_NAME = "dsa_sparse_decode"
SPARSE_TILE = 8     # rows the sparse read copies at a time: an HBM tile's
_LANES = 128
# pages of an aligned RUN: what the pool of a latent group hands out side
# by side (serving/page_pool.py) and what the kernels here and in
# dsa_index.py copy with one descriptor. Chosen once on the chip from {4,
# 8} (benchmarks/diag_latent_ring.py --copy-pages; PERF.md, PR 64): no
# reading tells them apart, and 4 pads a reservation by half as much
RUN_PAGES = 4
# context rows a wave folds: [H, 512] f32 scores. Read on the chip at 256,
# 512, 1,024 and 2,048 rows (PERF.md, PR 44): 1,024 folds 2 to 8% faster at
# the served lengths and costs each of a decode executable's kernels a
# tenth of a second more to compile, which every start pays
_WAVE_ROWS = 512


def mla_decode_gate(dtype, width: int, rank: int, page_size: int,
                    interpret: bool = False, sparse: bool = False
                    ) -> Optional[str]:
    """None when the compiled kernel takes this latent geometry, else the
    rule that excludes it: a row or a latent that is not whole lane tiles,
    a page that is not whole sublane tiles of the pool's type, a type that
    is neither float32 nor bfloat16. The number of heads is not among the
    rules (32, 64 and 80 are served). The shape rules are the chip
    compiler's tiling and do not bind the interpreter. ``sparse``: the
    "page" is the sparse read's tile, held to the 8 rows of an HBM tile
    (what the compiler takes of a copy), not to the pool type's sublanes."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "latent dtype %s is not float32/bfloat16" % dt.name
    if interpret:
        return None
    if width % _LANES or rank % _LANES:
        return ("row width %d and latent rank %d must be multiples of %d"
                % (width, rank, _LANES))
    sublanes = SPARSE_TILE if sparse else 32 // dt.itemsize
    if page_size % sublanes:
        return ("page_size=%d is not a multiple of the %s tile's %d rows"
                % (page_size, dt.name, sublanes))
    return None


def run_pages(page_size: int, pages_per_slot: int) -> int:
    """The run a latent group of this geometry takes: the largest of
    ``RUN_PAGES``, its half, ... that divides both a slot's table and the
    wave the kernel folds over it (1 if none does: single pages)."""
    wave = max(1, min(max(1, _WAVE_ROWS // int(page_size)), pages_per_slot))
    r = RUN_PAGES
    while r > 1 and (wave % r or pages_per_slot % r):
        r //= 2
    return r


def _mla_kernel(pt_ref, len_ref, layer_ref, q_ref, pool, *rest, block_pages,
                page_size, pages_per_slot, num_pages, rank, sm_scale,
                mask_value, precision, copy_pages=1):
    # the sparse read's row mask rides between the pool and the output
    valid_ref = rest[0] if len(rest) == 5 else None
    o_ref, scr, sems, ahead = rest[-4:]
    b = pl.program_id(0)  # out here: the interpreter has none in a branch
    slots = pl.num_programs(0)
    ps = page_size
    rows = block_pages * ps
    layer = layer_ref[0]
    n_waves = -(-pages_per_slot // block_pages)
    whole = pages_per_slot // block_pages   # waves with every page tabled
    # a copy moves a RUN of this many pages (the pool's: the table's entry
    # ``cp g`` starts ``cp`` pages side by side); 1: a page a copy
    cp = copy_pages
    copies = block_pages // cp      # of a full wave
    # a full wave's copies start in unrolled runs of this many (2 reads 6%
    # slower than 4 or 8 on the chip; a copy more in a run is 8 ms more of
    # compilation for each of an executable's kernels, at every start)
    unroll = max(d for d in range(1, 5) if copies % d == 0)

    def times(x, k):    # no product by 1 in the kernel's text
        return x if k == 1 else x * k

    def length(slot):
        """A slot's rows, as many as its table can hold."""
        return jnp.minimum(len_ref[slot], pages_per_slot * ps)

    ctx = length(b)

    def page(slot, w, i, buf):
        """The copy of ``slot``'s wave ``w``, run ``i`` (``cp`` pages from
        the run's FIRST table entry on), into ``buf``. The entry is
        clamped: a corrupt one reads a wrong run, never out of bounds."""
        entry = pt_ref[slot * pages_per_slot + w * block_pages + times(i, cp)]
        return pltpu.make_async_copy(
            pool.at[layer, pl.ds(jnp.clip(entry, 0, num_pages - cp) * ps,
                                 cp * ps)],
            scr.at[buf, pl.ds(pl.multiple_of(i * (cp * ps), cp * ps),
                              cp * ps)], sems.at[buf])

    def each_live_page(slot, length, w, buf, act):
        """``act`` on the copy of each run of a wave that holds a row
        below the length, one at a time; a run wholly at or past the
        length is not in the loop, one that straddles it is copied whole
        (its pages are the slot's own: a reservation is whole runs)."""
        live = jnp.minimum((length + ps - 1) // ps, pages_per_slot)

        def body(i, _):
            act(page(slot, w, i, buf))
            return 0

        pages = jnp.clip(live - w * block_pages, 0, block_pages)
        jax.lax.fori_loop(
            0, pages if cp == 1 else (pages + (cp - 1)) // cp, body, 0)

    def start(slot, length, w, buf):
        """Wave ``w`` of ``slot`` on its way into ``buf``. A FULL wave (no
        row at or past the length, no page past the table) is a straight
        run with no predicate, one a buffer, so that a copy's buffer and
        semaphore are constants of its descriptor."""
        full = ((w + 1) * rows <= length) & (w < whole)

        for const in (0, 1):
            @pl.when(full & (buf == const))
            def _(const=const):
                def some(g, _):
                    for j in range(unroll):
                        page(slot, w, g * unroll + j, const).start()
                    return 0

                if cp > 1 and copies == unroll:
                    some(0, 0)      # one run: every offset a constant
                else:
                    jax.lax.fori_loop(0, copies // unroll, some, 0)

        @pl.when(jnp.logical_not(full))
        def _():
            each_live_page(slot, length, w, buf, lambda c: c.start())

    @pl.when(b == 0)
    def _():
        ahead[0] = 0    # the buffer this slot's wave 0 lands in
        ahead[1] = 0    # 1: an earlier slot has started that wave

    @pl.when(ctx > 0)
    def _():
        q = q_ref[0]                                  # [H, W], pool's type
        first = ahead[0]
        live_waves = jnp.minimum((ctx + rows - 1) // rows, n_waves)
        full_waves = jnp.minimum(ctx // rows, whole)  # live_waves or 1 less
        # the next slot that holds a row: its wave 0 follows this slot's last
        nxt = jax.lax.while_loop(
            lambda s: (s < slots) & (len_ref[jnp.minimum(s, slots - 1)] <= 0),
            lambda s: s + 1, b + 1)
        follows = nxt < slots
        nxt = jnp.minimum(nxt, slots - 1)

        @pl.when(ahead[1] == 0)     # the call's first live slot: its own
        def _():
            each_live_page(b, ctx, 0, first, lambda c: c.start())

        def wave(w, carry):
            m, l, acc = carry
            buf = (first + w) % 2
            # in flight while this wave is folded, in the buffer the fold
            # does not read: this slot's wave w + 1 or, behind its last,
            # the next live slot's wave 0
            more = w + 1 < live_waves

            @pl.when(more | follows)
            def _():
                start(jnp.where(more, b, nxt),
                      jnp.where(more, ctx, length(nxt)),
                      jnp.where(more, w + 1, 0), 1 - buf)

            @pl.when(w < full_waves)
            def _():
                # the wave's copies signal ONE semaphore: one wait of
                # their sum, and the buffer is folded as it lies
                pltpu.make_async_copy(scr.at[buf], scr.at[buf],
                                      sems.at[buf]).wait()

            @pl.when(w >= full_waves)
            def _():
                # the slot's last wave, partial: rows at or past the
                # length hold whatever the buffer last held and are
                # zeroed where the exactly-0 probabilities meet them
                # (Inf/NaN * 0)
                each_live_page(b, ctx, w, buf, lambda c: c.wait())
                col = w * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0)
                scr[buf] = jnp.where(col < ctx, scr[buf], 0)

            kb = scr[buf]                             # [R, W]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * sm_scale   # [H, R]
            # all true in a full wave (which costs nothing a chip run
            # could read); a second fold without it doubles the kernel's
            # text, and its compilation is paid at every start
            pos = w * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            keep = pos < ctx
            if valid_ref is not None:
                keep = keep & (valid_ref[0, :, pl.ds(
                    pl.multiple_of(w * rows, rows), rows)] > 0)
            s = jnp.where(keep, s, mask_value)
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
        ahead[0] = (first + live_waves) % 2
        ahead[1] = follows.astype(jnp.int32)

    @pl.when(ctx <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def mla_paged_decode(q, pool, page_table, ctx_len, *, page_size, rank,
                     layer=None, sm_scale=1.0, block_pages=None,
                     interpret: bool = False, name: str = KERNEL_NAME,
                     row_valid=None, copy_pages: int = 1):
    """Absorbed latent decode attention over a paged pool.

    ``q`` [B, H, W]: each head's absorbed query over the row's lanes (its
    lanes beyond what the row really holds must be 0, as the row's are).
    ``pool`` [n_layer, num_pages * page_size, W] (the whole pool, of which
    layer ``layer`` is read), or one layer [rows, W] with ``layer`` None.
    ``page_table`` [B, pages_per_slot] int32; ``ctx_len`` [B] valid leading
    rows a slot (0: the slot holds nothing, its output is exactly 0.0 and
    it moves no page). ``name`` is the call's name in a device trace.
    ``row_valid`` [B, pages_per_slot * page_size] bool (the sparse read):
    of the rows below the length, those that count; a slot with a length
    has one at least. ``copy_pages`` (static): the pool's RUN, what the
    caller KNOWS of the table: entry ``copy_pages * g`` of a slot starts
    that many pages side by side in the pool, all the slot's own
    (``serving/page_pool.py`` hands them out so); the kernel then reads
    that entry alone and copies the run whole. Returns [B, H, rank] in
    ``q``'s type, matching :func:`mla_gather_reference` to the products'
    round-off."""
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
    cp = int(copy_pages)
    if cp < 1 or bp % cp or pages_per_slot % cp or num_rows // ps < cp:
        raise ValueError("runs of %d pages do not tile waves of %d pages of "
                         "a table of %d over a pool of %d"
                         % (cp, bp, pages_per_slot, num_rows // ps))
    from ..attention_ops import neg_inf_value

    f32 = pool.dtype == jnp.float32
    hp = -(-h // 8) * 8     # whole sublanes of heads; the padding is zeros
    qk = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, hp - h), (0, 0)))
    kernel = functools.partial(
        _mla_kernel, block_pages=bp, page_size=ps,
        pages_per_slot=pages_per_slot, num_pages=num_rows // ps,
        rank=int(rank), sm_scale=float(sm_scale),
        mask_value=neg_inf_value(jnp.float32),
        precision=jax.lax.Precision.HIGHEST if f32 else None, copy_pages=cp)
    masks = ()
    if row_valid is not None:
        table_rows = pages_per_slot * ps
        if row_valid.shape != (b, table_rows) or table_rows % (bp * ps):
            raise ValueError("row_valid must be [%d, %d], whole waves of %d "
                             "rows: got %s" % (b, table_rows, bp * ps,
                                               row_valid.shape))
        masks = (row_valid.astype(jnp.int32).reshape(b, 1, table_rows),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec((1, 1, m.shape[-1]), lambda i, *_: (i, 0, 0))
           for m in masks],
        out_specs=pl.BlockSpec((1, hp, rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, bp * ps, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # a slot leaves the next
        interpret=interpret, name=name,
    )(page_table.reshape(-1).astype(jnp.int32), ctx_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qk, pool, *masks)
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

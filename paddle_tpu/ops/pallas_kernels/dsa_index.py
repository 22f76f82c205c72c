"""The lightning indexer's scores of one decode position a slot, over an
index of ONE key a row kept in pages (``kv_cache.LatentPagedCache(index=(1,
lanes, topk))``: the pool ``[layers, pages, page_size, lanes]``, a page a
tile of its own, addressed through the slot's page table):

    I(b, s) = sum_j w[b, j] ReLU(q[b, j, :] . K[b, s, :])     s < ctx[b]

The XLA form (``attention_ops.dsa_index_scores`` over the gathered table)
copies a slot's WHOLE table of keys whatever its context holds and writes
the heads' float32 products ``[B, Hi, rows]`` to HBM before it weighs them:
five times the keys' bytes through HBM a layer. Here a slot's LIVE pages
are copied once, a wave of ``_WAVE_ROWS`` rows at a time into one of two
VMEM buffers (the next wave's copies in flight while this one is scored:
``mla_attention.py``'s skeleton without its cross-slot hand-over), the
heads' products ``[Hi, wave]`` stay in VMEM, the weighted ReLUs are summed
over the heads in float32 on the vector unit, and a slot writes ``[1,
rows]`` scores: the masking constant at and past its length, and in every
wave it holds no row of. The call's name in a device trace is
``dsa_index_scores``.

A copy moves a page RUN (``copy_pages``; PR 64): the pool of the latent
group hands its pages out in aligned runs of R pages side by side
(``serving/page_pool.py`` guarantees it, the engine checks it,
``mla_attention.py``'s docstring has the rule), so entry ``R g`` of a
slot's table starts ``[R, page_size, lanes]`` of consecutive keys, all the
slot's own, and one descriptor moves them where a page of 16 keys (4 KB)
paid one each; the wave buffer is ``[pages, page_size, lanes]`` on the
copy's side and is read as rows on end (a view: a bfloat16 page of 16 rows
is one tile). Read on the chip (``benchmarks/diag_dsv32_step.py --parts
index --copy-pages 1,4,8 --index-wave-rows 512,1024,2048``, PR 64; 32 slots
of 4,608-10,240 rows, us a layer): waves of 512 rows 392 at R = 1, 224 at
R = 4 or 8 (the kernel WAS its descriptors, and then is its loop a wave);
waves of 1,024 rows 170 | 153 (R = 4 | 8), of 2,048 rows **142** | 133:
``_WAVE_ROWS`` is 2,048 (52% of the keys' stream), R the latent kernel's.

:func:`dsa_index_gate` says from the geometry whether the chip's compiler
takes the call; the cache asks it and keeps the XLA form elsewhere.

:func:`dsa_index_scores_prefill` is the same scores for every row of ONE
sequence against every row before it (``attention_ops
.dsa_rows_causal_attention``'s masks are chosen from them): the XLA form
writes ``[block_q, Hi, S]`` float32 products a query block to HBM and reads
them back to weigh them (0.5 GB a block of 256 rows at S = 8,192 and 64
heads, 34 GB a layer); here a tile of ``_BLOCK_K`` keys by ``_BLOCK_Q``
queries accumulates the heads' weighted ReLUs in VMEM and ``[S, S]``
float32 is all that is written, keys down the sublanes and queries along
the lanes (a head's weight is then a row vector, as it lies), the tiles
wholly past a query block neither copied nor computed; nor are the query
blocks whose scores nobody reads, which the caller names by two rows
(``first``: the rows before it read their whole prefix; ``end``, a
scalar-prefetch operand: the rows from it on lie past the prompt in its
bucket). What a tile that was not computed holds is whatever the buffer
held. The call's name is ``dsa_index_scores_prefill``;
:func:`dsa_index_prefill_gate` is its gate.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dsa_prefill import _tile

__all__ = ["dsa_index_scores_paged", "dsa_index_gate", "KERNEL_NAME",
           "dsa_index_scores_prefill", "dsa_index_prefill_gate",
           "PREFILL_KERNEL_NAME"]

KERNEL_NAME = "dsa_index_scores"
PREFILL_KERNEL_NAME = "dsa_index_scores_prefill"
_LANES = 128
# rows a wave of the DECODE kernel scores: read on the chip at 512, 1,024
# and 2,048 rows (PERF.md, PR 64): once a wave's copies are a few runs what
# is left of it is the loop's own cost a wave, which 2,048 rows share
_WAVE_ROWS = 2048
_BLOCK_Q = 256              # query rows a grid step (the lanes of a tile)
_BLOCK_K = 512              # keys a grid step (its sublanes)
_VMEM_LIMIT = 48 << 20


def dsa_index_gate(dtype, lanes: int, page_size: int, table_rows: int,
                   interpret: bool = False) -> Optional[str]:
    """None when the compiled kernel takes an index of keys of ``lanes``
    lanes in pages of ``page_size`` rows, ``table_rows`` a slot, else the
    rule that excludes it (the chip compiler's tiling; the interpreter is
    bound by the type alone)."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "index dtype %s is not float32/bfloat16" % dt.name
    if interpret:
        return None
    if lanes % _LANES:
        return "a key of %d lanes is not whole %d-lane tiles" % (lanes,
                                                                 _LANES)
    sublanes = 32 // dt.itemsize
    if page_size % sublanes:
        return ("page_size=%d is not a multiple of the %s tile's %d rows"
                % (page_size, dt.name, sublanes))
    wave = min(_WAVE_ROWS, table_rows)
    if wave % page_size or table_rows % wave or wave % _LANES:
        return ("a slot's %d rows are not whole waves of %d rows of whole "
                "pages" % (table_rows, wave))
    return None


def _index_kernel(pt_ref, len_ref, layer_ref, q_ref, w_ref, pool, o_ref, scr,
                  sems, *, wave_pages, page_size, pages_per_slot, num_pages,
                  low, precision, copy_pages=1):
    b = pl.program_id(0)
    ps = page_size
    rows = wave_pages * ps
    layer = layer_ref[0]
    ctx = jnp.minimum(len_ref[b], pages_per_slot * ps)
    live_pages = (ctx + ps - 1) // ps
    live_waves = (ctx + rows - 1) // rows
    # a copy moves a RUN of this many pages, ``[cp, page_size, lanes]`` of
    # the pool from the run's FIRST table entry on (``mla_attention.py``
    # says who guarantees a run); the wave buffer is pages by rows a page
    cp = copy_pages
    copies = wave_pages // cp       # of a full wave
    # a full wave's copies start in unrolled runs of this many, with the
    # buffer a constant of the descriptor (``mla_attention.py`` has the
    # readings: a descriptor costs the scalar unit 13 ns so, 20 otherwise)
    unroll = max(d for d in range(1, 5) if copies % d == 0)
    o_ref[...] = jnp.full(o_ref.shape, low, o_ref.dtype)

    def page(w, i, buf):
        """The copy of wave ``w``'s run ``i`` into ``buf``. A table entry
        is clamped: a corrupt one reads a wrong run, never out of
        bounds."""
        entry = pt_ref[b * pages_per_slot + w * wave_pages + i * cp]
        return pltpu.make_async_copy(
            pool.at[layer, pl.ds(jnp.clip(entry, 0, num_pages - cp), cp)],
            scr.at[buf, pl.ds(pl.multiple_of(i * cp, cp), cp)], sems.at[buf])

    def each_live_page(w, buf, act):
        """``act`` on the copy of each run of a PARTIAL wave that holds a
        row below the length, one at a time (a run that straddles the
        length whole: its pages are the slot's own)."""
        def body(i, _):
            act(page(w, i, buf))
            return 0

        pages = jnp.clip(live_pages - w * wave_pages, 0, wave_pages)
        jax.lax.fori_loop(0, (pages + (cp - 1)) // cp, body, 0)

    def full(w):                # every row of the wave lies below the length
        return (w + 1) * rows <= ctx

    def start(w, buf):
        for const in (0, 1):
            @pl.when(full(w) & (buf == const))
            def _(const=const):
                def some(g, _):
                    for j in range(unroll):
                        page(w, g * unroll + j, const).start()
                    return 0

                if copies == unroll:    # one run: every offset a constant
                    some(0, 0)
                else:
                    jax.lax.fori_loop(0, copies // unroll, some, 0)

        @pl.when(jnp.logical_not(full(w)))
        def _():
            each_live_page(w, buf, lambda c: c.start())

    @pl.when(ctx > 0)
    def _():
        q = q_ref[0]                                  # [Hi, L], pool's type
        weight = w_ref[0][:, :1]                      # [Hi, 1] float32
        start(0, 0)

        def wave(w, _):
            buf = w % 2

            @pl.when(w + 1 < live_waves)
            def _():
                start(w + 1, 1 - buf)

            @pl.when(full(w))
            def _():
                # the wave's copies signal ONE semaphore: one wait of
                # their sum
                pltpu.make_async_copy(scr.at[buf], scr.at[buf],
                                      sems.at[buf]).wait()

            @pl.when(jnp.logical_not(full(w)))
            def _():
                each_live_page(w, buf, lambda c: c.wait())

            keys = scr[buf].reshape(rows, scr.shape[-1])    # pages on end
            sc = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)             # [Hi, R]
            score = jnp.sum(jnp.maximum(sc, 0.0) * weight, axis=0,
                            keepdims=True)                      # [1, R]
            # rows at or past the length hold whatever the buffer last
            # held (Inf and NaN included): the select drops them
            pos = w * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            o_ref[0, :, pl.ds(pl.multiple_of(w * rows, rows), rows)] = \
                jnp.where(pos < ctx, score, low)
            return 0

        jax.lax.fori_loop(0, live_waves, wave, 0)


def dsa_index_scores_paged(q_idx, w_idx, pool, page_table, ctx_len, *, layer,
                           interpret: bool = False, copy_pages: int = 1):
    """``q_idx`` [B, Hi, L] the index queries, ``w_idx`` [B, Hi] float32
    their weights, ``pool`` [n_layer, num_pages, page_size, L] of which
    layer ``layer`` is read, ``page_table`` [B, pages_per_slot] int32,
    ``ctx_len`` [B] the rows a slot may score (0: none, and no page is
    moved), ``copy_pages`` (static) the pool's run: entry ``copy_pages *
    g`` of a slot's table starts that many pages side by side in the pool,
    all the slot's own, and one copy moves them
    (``mla_attention.mla_paged_decode``'s rule). Returns [B,
    pages_per_slot * page_size] float32:
    ``attention_ops.dsa_index_scores``'s, to the products' round-off (the
    heads' weighted sum is float32 here)."""
    from ..attention_ops import neg_inf_value

    b, heads, lanes = q_idx.shape
    n_layer, num_pages, ps, pool_lanes = pool.shape
    slots, pages_per_slot = page_table.shape
    if slots != b or pool_lanes != lanes or w_idx.shape != (b, heads):
        raise ValueError("q_idx %s, w_idx %s, pool %s and page_table %s do "
                         "not agree" % (q_idx.shape, w_idx.shape, pool.shape,
                                        page_table.shape))
    table_rows = pages_per_slot * ps
    why_not = dsa_index_gate(pool.dtype, lanes, ps, table_rows,
                             interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    wave_pages = max(1, min(_WAVE_ROWS, table_rows) // ps)
    if pages_per_slot % wave_pages:
        raise ValueError("a slot's %d pages are not whole waves of %d"
                         % (pages_per_slot, wave_pages))
    cp = int(copy_pages)
    if cp < 1 or wave_pages % cp or num_pages < cp:
        raise ValueError("runs of %d pages do not tile waves of %d pages "
                         "over a pool of %d" % (cp, wave_pages, num_pages))
    hp = -(-heads // 8) * 8     # whole sublanes of heads; the padding is 0
    pad = ((0, 0), (0, hp - heads), (0, 0))
    q = jnp.pad(q_idx.astype(pool.dtype), pad)
    w = jnp.pad(jnp.broadcast_to(w_idx.astype(jnp.float32)[:, :, None],
                                 (b, heads, _LANES)), pad)
    f32 = pool.dtype == jnp.float32
    kernel = functools.partial(
        _index_kernel, wave_pages=wave_pages, page_size=ps,
        pages_per_slot=pages_per_slot, num_pages=num_pages,
        low=neg_inf_value(jnp.float32),
        precision=jax.lax.Precision.HIGHEST if f32 else None, copy_pages=cp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, lanes), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, hp, _LANES), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, table_rows), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, wave_pages, ps, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, table_rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=KERNEL_NAME,
    )(page_table.reshape(-1).astype(jnp.int32), ctx_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, w, pool)
    return out[:, 0]


def dsa_index_prefill_gate(heads: int, lanes: int, s: int,
                           interpret: bool = False) -> Optional[str]:
    """None when the ``dsa_index_scores_prefill`` kernel takes ``s`` rows
    of ``heads`` index heads of ``lanes`` lanes, else the rule that
    excludes it (the chip compiler's tiling)."""
    if interpret:
        return None
    if lanes % _LANES:
        return "a key of %d lanes is not whole %d-lane tiles" % (lanes,
                                                                 _LANES)
    if s % _LANES or heads % 8:
        return ("%d rows of %d heads are not whole %d-row tiles of whole "
                "sublanes of heads" % (s, heads, _LANES))
    return None


def _prefill_kernel(end_ref, q_ref, w_ref, k_ref, o_ref, acc, *, heads,
                    block_q, block_k, first):
    """One tile: ``end_ref`` [1] the first row nobody reads the scores of,
    ``q_ref`` [Hi, bq, L], ``w_ref`` [Hi, bq] float32, ``k_ref`` [bk, L];
    ``o_ref`` [bk, bq] float32, keys by queries."""
    i, j = pl.program_id(0), pl.program_id(1)
    read = ((i + 1) * block_q > first) & (i * block_q < end_ref[0])

    # a tile whose first key lies past the block's last row holds nothing
    # a row may choose: the caller's causal mask drops what is left there
    @pl.when(read & (j * block_k < (i + 1) * block_q))
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        k = k_ref[...]

        def head(h, _):
            sc = jax.lax.dot_general(
                k, q_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [bk, bq]
            acc[...] += jnp.maximum(sc, 0.0) * w_ref[pl.ds(h, 1), :]
            return 0

        jax.lax.fori_loop(0, heads, head, 0)
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("first", "block_q", "block_k",
                                             "interpret"))
def dsa_index_scores_prefill(q_idx, w_idx, k_idx, end=None, *,
                             first: int = 0, block_q: int = _BLOCK_Q,
                             block_k: int = _BLOCK_K,
                             interpret: bool = False):
    """``q_idx`` [S, Hi, L], ``w_idx`` [S, Hi] float32, ``k_idx`` [S, L]
    of ONE sequence; ``first`` (static) and ``end`` (an int32 scalar; None:
    S) the rows whose scores are read, ``first <= t < end``. Returns ``I``
    [S, S] float32, ``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])``
    wherever ``s <= t`` in every query block of ``block_q`` rows that holds
    such a row. The tiles wholly past a query block, and the query blocks
    that hold no such row (neither copied nor computed), hold whatever the
    buffer held: the caller masks by causality and reads no row outside
    the two. Jitted, so that the layers of one executable lower ONE kernel
    text."""
    s, heads, lanes = q_idx.shape
    why_not = dsa_index_prefill_gate(heads, lanes, s, interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    unit = 1 if interpret else _LANES
    bq, bk = _tile(block_q, s, unit), _tile(block_k, s, unit)
    first = min(int(first), s)

    def at(i, j, end_ref):
        """The (query block, key tile) a grid step reads: its own up to
        the block's causal edge, then the edge's; of a block nobody reads,
        the first tile of the first block that is read (before it) or the
        last tile of the last (after it): held in VMEM, nothing copied."""
        lo = min(first // bq, s // bq - 1)
        hi = jnp.maximum(jnp.maximum(end_ref[0] - 1, 0) // bq, lo)
        block = jnp.clip(i, lo, hi)
        edge = ((block + 1) * bq - 1) // bk
        return block, jnp.where(i < lo, 0, jnp.where(
            i > hi, edge, jnp.minimum(j, edge)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((heads, bq, lanes), lambda *st: (0, at(*st)[0], 0)),
            pl.BlockSpec((heads, bq), lambda *st: (0, at(*st)[0])),
            pl.BlockSpec((bk, lanes), lambda *st: (at(*st)[1], 0))],
        out_specs=pl.BlockSpec((bk, bq), lambda i, j, _: (j, i)),
        scratch_shapes=[pltpu.VMEM((bk, bq), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=heads, block_q=bq,
                          block_k=bk, first=first),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, s), jnp.float32),
        interpret=interpret, name=PREFILL_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.asarray(s if end is None else end, jnp.int32).reshape(1),
      q_idx.transpose(1, 0, 2), w_idx.astype(jnp.float32).T, k_idx)
    return out.T

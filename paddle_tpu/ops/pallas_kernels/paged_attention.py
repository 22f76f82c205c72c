"""Ragged paged-attention Pallas TPU kernel for the serving decode loop.

Motivation (ROADMAP item 1, "Ragged Paged Attention" in PAPERS.md): the
serving engine's hottest inner loop — one decode attention per layer per
fused step — runs as an XLA gather that materializes every slot's KV
context ``[B, max_ctx, H, D]`` in HBM (serving.kv_cache.PagedKVCache
.context) before ops.attention_ops.decode_attention reduces it. That
traffic is ``B * max_ctx * H * D`` elements per layer per step regardless
of how short the ragged sequences actually are. This kernel fuses the page
gather into the attention inner loop: K/V pages stream from the flat page
pool straight into VMEM scratch via per-page DMAs driven by the
device-resident page table, and an online-softmax accumulator reduces them
wave by wave — HBM traffic becomes ``sum_b ctx_len[b] * H * D`` (only the
LIVE rows move) and the ``[B, max_ctx, H, D]`` intermediate never exists.

Design:

- the pool is STORED as ``[n_layer, num_pages*page_size, H*D]``: one
  lane-dense row per context position. The chip's compiler tiles the last
  two dims of every buffer to (8, 128) and refuses a DMA slice that is not
  a whole number of tiles, so a ``[rows, H, D]`` pool only moves for ``D %
  128 == 0`` and ``H % 8 == 0`` (and XLA gives GPT-2 small's ``12 x 64``
  a rows-minor layout that every scatter and every slice converts, a copy
  of the whole pool each); the flat row moves for any ``H*D % 128 == 0``
  (GPT-2 small's 12 heads of 64 are six whole lane tiles) and is the
  default layout, so nothing converts. The static gate
  :func:`paged_attention_gate` states the rule;
- the kernel takes the WHOLE pool and the layer, and indexes the layer in
  its page DMA (``k_hbm.at[layer, pl.ds(row, ps)]``): nothing outside the
  kernel slices or reshapes a pool-sized array. The layer rides in SMEM
  beside the page table, so every layer's call shares one kernel body;
- HEADS THAT ARE NOT WHOLE LANE TILES (one query head a KV head, ``D %
  128 != 0``: GPT-2 small's heads of 64) cannot be sliced out of the flat
  row, so their per-head reductions over it ride the MXU whole: ``(k * q)
  @ seg`` sums each head's D lanes (``seg`` is the 0/1 head-membership
  matrix ``[H*D, 128]``), and ``p @ seg.T`` spreads each head's
  probability back over its lanes. That fold (``per_lane``) widens the
  wave to float32 and multiplies at full f32 precision, so for such heads
  the kernel agrees with the gather path to float round-off whatever the
  pool's type;
- HEADS OF WHOLE LANE TILES AND GROUPED QUERIES: ``q`` may carry ``G``
  query heads for each KV head (query head n reads KV head ``n // G``). It
  arrives in the kernel as ``[B, G, H*D]`` (row g holds, for every KV head,
  its g-th query head; G padded to whole sublanes with zero queries, one
  query head to 8 rows like any other count), each wave of pages is DMA'd
  ONCE and folded into all G online-softmax states. The per-head sums go
  straight to the MXU (the ``grouped`` fold): one ``[G, D] x [D,
  rows]`` product a KV head for the scores and one ``[G, rows] x [rows, D]``
  for the weighted sum, over the head's own lane slice of the row, which on
  the chip has to be whole lane tiles (``D % 128 == 0``; the gate says so
  for G > 1, and G = 1 takes this fold only there).
  Both products take the pool's rows in the POOL's type and accumulate in
  float32: a bf16 pool is not widened (bf16 x bf16 products are exact in
  the accumulator, so the scores are the float32 ones up to the order of
  a sum), its probabilities go in as their bf16 high and low halves (16
  bits), and scale, mask and the softmax state ``(m, l, acc)`` are float32;
  a float32 pool multiplies at full precision. So in this fold "to float
  round-off" holds for float32 pools only: a bf16 pool agrees with a
  float32 reference over the same bf16 values to 2**-16 of the largest V.
  One query head a KV head over heads that are not whole lane tiles keeps
  the head-membership matmuls above, which take any ``H*D % 128 == 0``.
  One kernel, one DMA loop, the fold chosen by the geometry
  (:func:`paged_attention_fold`: the head's width and G, nothing else)
  and its precision by the pool's type;
- grid is ``(slots,)``; the page table (flattened) and per-slot ``ctx_len``
  ride in SMEM via ``PrefetchScalarGridSpec`` scalar prefetch, so page
  addresses are known before the body runs;
- per slot, pages stream in waves of ``block_pages`` (the autotunable
  knob, table kernel key ``paged_attention``) into TWO K and two V
  buffers: a wave is ``2 * block_pages`` row-range DMAs started
  back-to-back (K and V per page), wave 0's before the loop and wave ``w +
  1``'s before wave ``w`` is waited for and folded into the online-softmax
  state ``(m, l, acc)``, so the copy of one wave hides behind the
  arithmetic of the one before (what a wave costs on the chip, fold and
  double buffer each alone and together: PERF.md, PR 35);
- the ragged bound: a slot of ``ctx_len`` 0 (one that holds no request:
  serving.kv_cache hands the kernel LIVE lengths) writes zeros and ends its
  grid step there; elsewhere only the waves that hold a position below
  ``ctx_len`` run, a page entirely at/after ``ctx_len`` starts no DMA and
  is not waited for, and the position mask uses attention_ops.neg_inf — the
  SAME masking constant as the gather path — with the rows beyond
  ``ctx_len`` zeroed before use where a product could see them (K and V in
  the per-lane fold; V in the grouped one, whose scores of such rows are
  REPLACED by the mask), so stale rows (retired requests, unreserved
  pages, whatever either buffer last held, Inf and NaN included)
  contribute exactly 0.0;
- page ids from the table are clamped to the pool, so a corrupt table row
  degrades to wrong-but-safe reads, never an OOB DMA.

``interpret=True`` runs the same kernel through the Pallas interpreter on
CPU — what tier-1 parity tests and the ``--selftest`` CLI use (the
interpreter has no tiling, so it takes any shape); tests/test_chip_compile
.py compiles it for a described v5e. The engine arms the kernel via
``FLAGS_paged_attention_kernel`` (auto = compiled on TPU only; on =
everywhere, interpreted off-TPU; interpret = force the interpreter; off =
gather), resolved by attention_ops.paged_kernel_mode and dispatched from
serving.kv_cache.PagedKVCache.decode_attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "paged_decode_attention",
    "gather_reference",
    "paged_attention_gate",
    "paged_attention_fold",
    "paged_attention_supported",
]

_LANES = 128
_MAX_ROW_WIDTH = 4096  # H*D: the two [H*D, 128] f32 head maps stay in VMEM
_VMEM_WAVE_BUDGET = 2 * 1024 * 1024  # bytes the K and V wave buffers may hold
_HIGHEST = jax.lax.Precision.HIGHEST


def paged_attention_gate(dtype, n_head: int, d_head: int, page_size: int,
                         interpret: bool = False, q_per_kv: int = 1
                         ) -> Optional[str]:
    """None when the kernel takes this cache geometry, else the rule that
    excludes it (what ``ServingEngine.decode_kernel_info`` reports when
    ``auto`` keeps the XLA gather path). The dtype rule always holds; the
    shape rules are the chip compiler's tiling and do not bind the
    interpreter."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "kv dtype %s is not float32/bfloat16" % dt.name
    if interpret:
        return None
    hd = int(n_head) * int(d_head)
    if hd % _LANES or hd > _MAX_ROW_WIDTH:
        return ("n_head*d_head=%d is not a multiple of %d up to %d"
                % (hd, _LANES, _MAX_ROW_WIDTH))
    if q_per_kv > 1 and int(d_head) % _LANES:
        return ("%d query heads a KV head need d_head=%d to be a multiple "
                "of %d (a head's lanes are sliced out of the row)"
                % (q_per_kv, d_head, _LANES))
    sublanes = 32 // dt.itemsize  # rows per tile: 8 for f32, 16 for bf16
    if page_size % sublanes:
        return ("page_size=%d is not a multiple of the %s tile's %d rows"
                % (page_size, dt.name, sublanes))
    return None


def paged_attention_fold(q_per_kv: int, d_head: int) -> str:
    """Which fold a geometry takes, from the head's width: ``"grouped"``
    (a head's lanes sliced out of the flat row, its sums small MXU
    products) where heads are whole lane tiles, and wherever several query
    heads share a KV head (which the gate holds to such heads on the chip);
    ``"per_lane"`` (the head-membership matmuls over the whole row) for
    one query head a KV head of any other width."""
    if int(q_per_kv) > 1 or int(d_head) % _LANES == 0:
        return "grouped"
    return "per_lane"


def paged_attention_supported(dtype, n_head: int, d_head: int,
                              page_size: int, interpret: bool = False,
                              q_per_kv: int = 1) -> bool:
    return paged_attention_gate(dtype, n_head, d_head, page_size,
                                interpret, q_per_kv) is None


def _wave_fits(page_size: int, hd: int, itemsize: int) -> int:
    """Most pages a wave may hold: what the kernel keeps in fast memory is
    two K and two V buffers of a wave each, in the pool's type."""
    return max(1, _VMEM_WAVE_BUDGET // (4 * page_size * hd * itemsize))


def _default_block_pages(page_size: int, pages_per_slot: int, hd: int,
                         itemsize: int = 4) -> int:
    """Largest power-of-two pages-per-wave, up to the slot's pages, whose
    buffers fit the wave budget — the untuned fallback the autotune sweep
    measures against."""
    bp = 1
    while bp * 2 <= min(pages_per_slot, _wave_fits(page_size, hd, itemsize)):
        bp *= 2
    return bp


def _block_pages(block, page_size: int, pages_per_slot: int, max_ctx: int,
                 hd: int, itemsize: int = 4) -> int:
    """Pages per DMA wave. ``block=None`` (the entry point's default)
    consults the tuned config table (paddle_tpu.tune: kernel
    ``paged_attention``, bucketed by (max_ctx, H*D) + device_kind, with the
    shipped v5e sweep) and falls back to the analytic VMEM-budget default —
    an explicit integer skips the table, which keeps the autotuner's own
    sweep from looping through the table it is writing. Either way the
    result is clamped to the slot's page count and to :func:`_wave_fits`:
    a table row is bucketed coarsely and must not hand a wide model more
    fast memory than the chip compiler grants. The lookup never raises; a
    corrupt table logs once inside tune.table and lands here as the
    default."""
    if block is None:
        block = _default_block_pages(page_size, pages_per_slot, hd, itemsize)
        try:
            from ...tune import table as _tt

            cfg, _src = _tt.lookup("paged_attention",
                                   _tt.bucket_ctx(max_ctx, hd))
            if cfg and int(cfg.get("block_pages", 0)) > 0:
                block = int(cfg["block_pages"])
        except Exception:
            pass
    return max(1, min(int(block), pages_per_slot,
                      _wave_fits(page_size, hd, itemsize)))


def _paged_attn_kernel(pt_ref, len_ref, layer_ref, q_ref, seg_ref, segt_ref,
                       k_hbm, v_hbm, o_ref, k_scr, v_scr, sems, **static):
    """One grid step, one slot. A slot that holds nothing (``ctx_len`` <= 0)
    writes a zero block and is done: no ``q`` cast, no wave, no epilogue
    (whose per-lane form is a matmul that ~30 rowless slots a layer would
    pay for nobody)."""
    b = pl.program_id(0)  # out here: the interpreter has none in a branch
    live = len_ref[b] > 0

    @pl.when(live)
    def _():
        _attend_slot(b, pt_ref, len_ref, layer_ref, q_ref, seg_ref, segt_ref,
                     k_hbm, v_hbm, o_ref, k_scr, v_scr, sems, **static)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _attend_slot(b, pt_ref, len_ref, layer_ref, q_ref, seg_ref, segt_ref,
                 k_hbm, v_hbm, o_ref, k_scr, v_scr, sems, *,
                 block_pages, page_size, pages_per_slot, num_pages,
                 sm_scale, mask_value, d_head, grouped):
    """Slot ``b`` over its ``ctx_len`` >= 1 leading rows: the shared
    double-buffered wave loop around the fold the geometry chose."""
    ps = page_size
    ctx = len_ref[b]
    layer = layer_ref[0]
    hd = k_scr.shape[-1]
    rows = block_pages * ps
    n_kv = hd // d_head
    # the scores of a float32 pool at full precision; a bf16 pool's
    # products are exact in the float32 accumulator as they are
    precision = _HIGHEST if k_scr.dtype == jnp.float32 else None

    live_pages = jnp.minimum((ctx + ps - 1) // ps, pages_per_slot)

    def each_page(w, buf, act):
        """``act`` on the K and the V copy of every page of wave ``w`` that
        holds a row below ``ctx``, into (or out of) buffer ``buf``: a page
        wholly at or past ``ctx`` is not in the loop. A table entry is
        clamped: a corrupt one reads a wrong page, never out of bounds."""
        def body(i, _):
            page = jnp.clip(pt_ref[b * pages_per_slot + w * block_pages + i],
                            0, num_pages - 1)
            for kv, (pool, scr) in enumerate(((k_hbm, k_scr),
                                              (v_hbm, v_scr))):
                act(pltpu.make_async_copy(
                    pool.at[layer, pl.ds(page * ps, ps)],
                    scr.at[buf, pl.ds(i * ps, ps)], sems.at[kv, buf, i]))
            return 0

        jax.lax.fori_loop(
            0, jnp.clip(live_pages - w * block_pages, 0, block_pages), body, 0)

    def below_ctx(w, axis):
        """Which of wave ``w``'s scratch rows, laid along ``axis`` of a 2-D
        tile, hold a context position below ``ctx``. The others hold
        whatever the buffer last held: a page that was never copied, the
        rows past the length in the last page, the slot before."""
        shape = (rows, 1) if axis == 0 else (1, rows)
        return w * rows + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                   axis) < ctx

    seg = seg_ref[...]    # [HD, HP]: lane j belongs to head seg[j].argmax()
    segt = segt_ref[...]  # [HP, HD]
    hp = seg.shape[1]

    def per_head(x):
        """[R, HD] -> [R, HP]: sum each head's lanes."""
        return jnp.dot(x, seg, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)

    def over_lanes(x):
        """[R, HP] -> [R, HD]: each head's value on all of its lanes."""
        return jnp.dot(x, segt, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)

    def row_over_lanes(x):
        """:func:`over_lanes` of one row, as a full-sublane matmul."""
        return over_lanes(jnp.broadcast_to(x, (8, hp)))[0:1]

    def fold_per_lane(w, buf, carry):
        """One query head a KV head of any width: one state a head over
        the flat row, the per-head sums through the head-membership
        matrices, in float32 at full precision whatever the pool's type."""
        valid = below_ctx(w, 0)
        # zeroed so the exactly-0 probabilities below cannot meet an
        # Inf/NaN residue
        kb = jnp.where(valid, k_scr[buf].astype(jnp.float32), 0.0)  # [R,HD]
        vb = jnp.where(valid, v_scr[buf].astype(jnp.float32), 0.0)
        m, l, acc = carry
        s = jnp.where(valid, per_head(kb * qs), mask_value)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))  # [1,HP]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)  # masked rows underflow to exactly 0.0
        l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_new = (acc * row_over_lanes(alpha)
                   + jnp.sum(over_lanes(p) * vb, axis=0, keepdims=True))
        return m_new, l_new, acc_new

    def weighted_sum(p, v):
        """``p @ v``, ``[G, R] x [R, D]``, into a float32 accumulator. A
        float32 pool multiplies at full precision. A bf16 pool is given
        ``p`` as its high and low halves in the pool's type: two MXU passes
        over operands that are bf16 as they are, 16 bits of ``p`` (on the
        chip two such products also run FASTER than one: PERF.md, PR 35)."""
        if v.dtype == jnp.float32:
            return jnp.dot(p, v, precision=_HIGHEST,
                           preferred_element_type=jnp.float32)
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        return (jnp.dot(hi, v, preferred_element_type=jnp.float32)
                + jnp.dot(lo, v, preferred_element_type=jnp.float32))

    def fold_grouped(w, buf, carry):
        """The wave folded into the G states of every KV head: scores
        ``[G, R]`` and weighted sums ``[G, D]`` on the MXU, a head's lanes
        sliced out of the flat row. Both products take the pool's rows in
        the POOL's type and accumulate in float32 (bf16 x bf16 products are
        exact there); scale, mask and the softmax state ``(m, l, acc)`` are
        float32."""
        ms, ls, accs = carry
        valid = below_ctx(w, 1)
        kb = k_scr[buf]  # a stale row's scores are REPLACED by the mask...
        # ...but its V would meet a probability of exactly 0.0: Inf/NaN * 0
        vb = jnp.where(below_ctx(w, 0), v_scr[buf], 0)
        out_m, out_l, out_acc = [], [], []
        for h in range(n_kv):
            lanes = slice(h * d_head, (h + 1) * d_head)
            s = jax.lax.dot_general(
                q[:, lanes], kb[:, lanes], (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32) * sm_scale   # [G,R]
            s = jnp.where(valid, s, mask_value)
            m_new = jnp.maximum(ms[h], jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(ms[h] - m_new)                        # [G,1]
            p = jnp.exp(s - m_new)  # masked rows underflow to exactly 0.0
            out_m.append(m_new)
            out_l.append(ls[h] * alpha + jnp.sum(p, axis=1, keepdims=True))
            out_acc.append(accs[h] * alpha
                           + weighted_sum(p, vb[:, lanes]))       # [G,D]
        return tuple(out_m), tuple(out_l), tuple(out_acc)

    if grouped:
        q = q_ref[0]      # [G, HD] in the pool's type
        gq = q.shape[0]
        fold = fold_grouped
        init = (tuple(jnp.full((gq, 1), mask_value, jnp.float32)
                      for _ in range(n_kv)),
                tuple(jnp.zeros((gq, 1), jnp.float32) for _ in range(n_kv)),
                tuple(jnp.zeros((gq, d_head), jnp.float32)
                      for _ in range(n_kv)))
    else:
        # the one-state-a-head fold scales its query once
        qs = q_ref[0].astype(jnp.float32) * sm_scale  # [1, HD]
        fold = fold_per_lane
        init = (jnp.full((1, hp), mask_value, jnp.float32),
                jnp.zeros((1, hp), jnp.float32),
                jnp.zeros((1, hd), jnp.float32))

    live_waves = (live_pages + block_pages - 1) // block_pages
    each_page(0, 0, lambda c: c.start())

    def wave(w, carry):
        """Wave ``w + 1``'s pages (none past the last wave) are in flight
        while wave ``w`` is folded."""
        buf = w % 2
        each_page(w + 1, 1 - buf, lambda c: c.start())
        each_page(w, buf, lambda c: c.wait())
        return fold(w, buf, carry)

    # ctx >= 1 here, so every state has folded a valid row: l >= 1
    _, l, acc = jax.lax.fori_loop(0, live_waves, wave, init)
    if grouped:
        out = jnp.concatenate([acc[h] / l[h] for h in range(n_kv)], axis=1)
    else:
        out = acc / row_over_lanes(l)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, ctx_len, *,
                           page_size, layer=None, sm_scale=1.0,
                           block_pages=None, interpret: bool = False):
    """Fused ragged paged decode attention.

    ``q`` [B,Hq,D] — current position's query per slot, ``Hq`` = ``G * H``
    query heads over the pool's ``H`` KV heads (G = 1: one each; G > 1:
    grouped queries, query head n on KV head ``n // G``); the fold is
    :func:`paged_attention_fold`'s for (G, D). ``k_pages``/
    ``v_pages`` [n_layer, num_pages*page_size, H*D] — the WHOLE paged KV
    pool (serving.kv_cache.PagedKVCache state), of which the kernel reads
    layer ``layer`` (an int or an int32 scalar); or ONE layer as
    [num_pages*page_size, H*D] with ``layer`` left None. ``page_table`` [B,
    pages_per_slot] int32 — each slot's ordered page ids. ``ctx_len`` [B] —
    valid leading rows per slot (must be >= 1 for slots whose output
    is consumed; 0 = the slot holds nothing: its rows of the result are
    exactly 0.0 and it costs a grid step, no DMA and no arithmetic).
    ``block_pages=None`` = tuned-table lookup with the
    analytic VMEM-budget fallback (see ``_block_pages``). Returns [B,Hq,D],
    matching ``gather_reference`` (the XLA gather + decode_attention path)
    on live rows to float32 round-off (the grouped fold, G > 1 or heads of
    whole lane tiles, over a bf16 pool: to 2**-16 of the largest V, against
    that path in float32 over the same bf16 values; one query head a KV
    head over heads that are not whole lane tiles: round-off whatever the
    pool's type) and EXACTLY ignoring garbage beyond ``ctx_len``. Compiled
    (``interpret=False``) it takes the shapes :func:`paged_attention_gate`
    admits; callers gate on it.
    """
    b, hq, d = q.shape
    slots, pages_per_slot = page_table.shape
    if slots != b:
        raise ValueError("page_table slots %d != q batch %d" % (slots, b))
    if k_pages.ndim == 2 and layer is None:
        # one layer is a pool of one: a leading 1 is free on tiled memory
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    hd = k_pages.shape[-1]
    h = hd // d
    if k_pages.ndim != 3 or layer is None or hd % d or hq % max(h, 1) \
            or v_pages.shape != k_pages.shape:
        raise ValueError(
            "pool must be [n_layer, rows, H*%d] with a layer, or one layer "
            "[rows, H*%d] without, H dividing q's %d heads: got k %s v %s "
            "layer=%r" % (d, d, hq, k_pages.shape, v_pages.shape, layer))
    n_layer, num_rows = k_pages.shape[:2]
    if isinstance(layer, (int, np.integer)) and not 0 <= layer < n_layer:
        raise ValueError("layer %d outside a pool of %d" % (layer, n_layer))
    ps = int(page_size)
    if num_rows % ps != 0:
        raise ValueError("pool rows %d not a multiple of page_size %d"
                         % (num_rows, ps))
    max_ctx = pages_per_slot * ps
    bp = _block_pages(block_pages, ps, pages_per_slot, max_ctx, hd,
                      jnp.dtype(k_pages.dtype).itemsize)
    # head-membership of the flat row's lanes, padded to a full lane tile
    # of heads: the padded heads own no lane, so they never reach the output
    hp = -(-h // _LANES) * _LANES
    seg = (np.arange(hd)[:, None] // d
           == np.arange(hp)[None, :]).astype(np.float32)
    return _kernel_call(
        q, k_pages, v_pages, page_table, ctx_len,
        jnp.asarray(layer, jnp.int32), jnp.asarray(seg), jnp.asarray(seg.T),
        page_size=ps, sm_scale=float(sm_scale), block_pages=bp,
        interpret=bool(interpret))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "page_size", "sm_scale", "block_pages", "interpret"))
def _kernel_call(q, k_pages, v_pages, page_table, ctx_len, layer, seg,
                 segt, *, page_size, sm_scale, block_pages, interpret):
    """:func:`paged_decode_attention` past its checks: the call itself over
    a whole pool, ``layer`` an int32 scalar, the head maps ``[H*D, 128]``
    and ``[128, H*D]`` the caller's constants. Under ``jit`` with
    ``inline=True`` so that a program's calls at ONE geometry (a model's
    layers: 48 in the looped model's decode step) share one trace of the
    kernel's body and one lowering, which the grouped fold's unrolled heads
    made seconds of a start-up (PERF.md, PR 57); inlined, the caller's
    program holds the same operations as if they were written there."""
    from ..attention_ops import neg_inf_value

    b, hq, d = q.shape
    pages_per_slot = page_table.shape[1]
    num_rows, hd = k_pages.shape[1:]
    h = hd // d
    g = hq // h
    ps, bp, hp = page_size, block_pages, seg.shape[1]
    grouped = paged_attention_fold(g, d) == "grouped"
    # row j of a slot: the j-th query head of every KV head
    qk = q.reshape(b, h, g, d).transpose(0, 2, 1, 3).reshape(b, g, hd) \
        if g > 1 else q.reshape(b, 1, hd)
    gp = g
    if grouped:
        # padded to whole sublanes with zero queries (uniform weights,
        # sliced away), in the pool's type: the fold's MXU operand
        gp = -(-g // 8) * 8
        qk = jnp.pad(qk, ((0, 0), (0, gp - g), (0, 0))).astype(k_pages.dtype)
    kernel = functools.partial(
        _paged_attn_kernel, block_pages=bp, page_size=ps,
        pages_per_slot=pages_per_slot, num_pages=num_rows // ps,
        sm_scale=sm_scale, mask_value=neg_inf_value(jnp.float32),
        d_head=d, grouped=grouped)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, gp, hd), lambda i, *_: (i, 0, 0)),  # q
            pl.BlockSpec((hd, hp), lambda i, *_: (0, 0)),       # seg
            pl.BlockSpec((hp, hd), lambda i, *_: (0, 0)),       # seg.T
            pl.BlockSpec(memory_space=pl.ANY),                  # K pool
            pl.BlockSpec(memory_space=pl.ANY),                  # V pool
        ],
        out_specs=pl.BlockSpec((1, gp, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bp * ps, hd), k_pages.dtype),  # two waves of K
            pltpu.VMEM((2, bp * ps, hd), v_pages.dtype),  # and of V
            pltpu.SemaphoreType.DMA((2, 2, bp)),  # K|V, buffer, page
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, gp, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.reshape(-1).astype(jnp.int32),
      ctx_len.astype(jnp.int32), layer.reshape(1),
      qk, seg, segt, k_pages, v_pages)
    if gp != g:
        out = out[:, :g]
    if g == 1:
        return out.reshape(b, hq, d)
    return out.reshape(b, g, h, d).transpose(0, 2, 1, 3).reshape(b, hq, d)


def gather_reference(q, k_pages, v_pages, page_table, ctx_len, page_size,
                     sm_scale=1.0):
    """The XLA path the kernel replaces, as a standalone reference over ONE
    layer ``[rows, H*D]``: the PagedKVCache.context gather (rows first,
    heads split after) composed with attention_ops.decode_attention (which
    supplies the SHARED neg_inf masking constant — the parity contract the
    selftest asserts)."""
    ps = int(page_size)
    b, _, d = q.shape
    h = k_pages.shape[-1] // d
    rows = (page_table * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
    rows = rows.reshape(b, -1)
    from ..attention_ops import decode_attention

    return decode_attention(q, k_pages[rows].reshape(b, -1, h, d),
                            v_pages[rows].reshape(b, -1, h, d), ctx_len,
                            sm_scale=sm_scale)


# -- selftest -----------------------------------------------------------------


def _selftest() -> int:
    """CPU interpret-mode parity vs the XLA gather path at mixed ragged
    lengths, including a garbage-page poisoning leg and a bf16 pool under
    both folds — the CI smoke next to sparse_adam --selftest."""
    import time

    t0 = time.time()
    rng = np.random.RandomState(0)
    slots, h, d, ps, pages_per_slot = 5, 2, 16, 8, 8
    num_pages = 24
    max_ctx = pages_per_slot * ps
    sm = 1.0 / float(d) ** 0.5

    # a shared pool with slots owning disjoint page sets, deliberately
    # scrambled so logical order != pool order
    perm = rng.permutation(num_pages)
    pt = np.zeros((slots, pages_per_slot), np.int32)
    for s_i in range(slots):
        pt[s_i] = np.resize(perm[s_i::slots], pages_per_slot)
    # ragged mixed lengths: 1 token, mid-page, page-exact, multi-page, full
    ctx_len = np.array([1, 7, 8, 33, max_ctx], np.int32)

    k_pool = rng.randn(num_pages * ps, h * d).astype(np.float32)
    v_pool = rng.randn(num_pages * ps, h * d).astype(np.float32)
    q = rng.randn(slots, h, d).astype(np.float32)

    def run(kp, vp, block):
        got = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(ctx_len), page_size=ps,
            sm_scale=sm, block_pages=block, interpret=True)
        want = gather_reference(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(ctx_len), ps, sm_scale=sm)
        return np.asarray(got), np.asarray(want)

    # clean pool, several wave widths (incl. a non-divisor and the tuned
    # default path)
    for block in (1, 3, 4, None):
        got, want = run(k_pool, v_pool, block)
        np.testing.assert_allclose(
            got, want, rtol=1e-6, atol=1e-6,
            err_msg="kernel vs gather mismatch at block_pages=%s" % block)

    # garbage-page poisoning: every pool row NOT covered by a slot's valid
    # prefix gets huge finite garbage (stale retired-request rows). Both
    # paths must be bit-unmoved: their masks zero those contributions
    # exactly. (NaN poisoning is out of contract: the gather path's
    # 0 * NaN would already break.)
    live = np.zeros(num_pages * ps, bool)
    for s_i in range(slots):
        n = int(ctx_len[s_i])
        flat = (pt[s_i].repeat(ps) * ps
                + np.tile(np.arange(ps), pages_per_slot))[:n]
        live[flat] = True
    k_poison = k_pool.copy()
    v_poison = v_pool.copy()
    k_poison[~live] = 1e4 * rng.randn((~live).sum(), h * d)
    v_poison[~live] = -1e4
    got_p, want_p = run(k_poison, v_poison, 2)
    np.testing.assert_allclose(got_p, want_p, rtol=1e-6, atol=1e-6,
                               err_msg="poisoned kernel vs gather mismatch")
    clean, _ = run(k_pool, v_pool, 2)
    np.testing.assert_array_equal(
        got_p, clean,
        err_msg="garbage beyond ctx_len leaked into the kernel output")

    # the engine's entry: the whole pool and a layer. The layer sits in the
    # middle of three with poisoned neighbours, and must come out bit-equal
    # to the single-layer call
    def pooled(x, fill):
        return jnp.asarray(np.stack([np.full_like(x, fill), x,
                                     np.full_like(x, -fill)]))

    got_l = np.asarray(paged_decode_attention(
        jnp.asarray(q), pooled(k_pool, 1e4), pooled(v_pool, -1e4),
        jnp.asarray(pt), jnp.asarray(ctx_len), page_size=ps, layer=1,
        sm_scale=sm, block_pages=2, interpret=True))
    np.testing.assert_array_equal(
        got_l, clean,
        err_msg="layer 1 of a pool differs from the same layer alone")

    # rowless slots (ctx_len 0: the slot holds no request) among live ones,
    # their table rows on a stretch of Inf and NaN: exactly 0.0 out, and
    # the live slots bit-equal to the call without them
    dead = np.array([False, True, False, True, False])
    pt3 = np.where(dead[:, None], pt + num_pages, pt + 2 * num_pages)
    bad = np.where(np.arange(num_pages * ps)[:, None] % 2, np.inf, np.nan)
    bad = np.broadcast_to(bad, k_pool.shape).astype(np.float32)

    def rowless(keep):
        return np.asarray(paged_decode_attention(
            jnp.asarray(q[keep]),
            jnp.asarray(np.concatenate([k_pool, bad, k_pool])),
            jnp.asarray(np.concatenate([v_pool, -bad, v_pool])),
            jnp.asarray(pt3[keep]),
            jnp.asarray(np.where(dead, 0, ctx_len)[keep]), page_size=ps,
            sm_scale=sm, block_pages=2, interpret=True))

    got_r = rowless(np.ones(slots, bool))
    np.testing.assert_array_equal(
        got_r[dead], np.zeros_like(got_r[dead]),
        err_msg="a slot of ctx_len 0 did not come back exactly 0.0")
    np.testing.assert_array_equal(
        got_r[~dead], rowless(~dead),
        err_msg="rowless slots moved the live slots' output")
    np.testing.assert_array_equal(got_r[~dead], clean[~dead])

    # a bf16 pool, 1 and 7 query heads a KV head, against the gather path
    # in float32 over the SAME bf16 values: the per-lane fold (one query
    # head of 16 lanes) widens the pool (1e-6), the grouped fold gives the
    # MXU p's two bf16 halves (2**-16 of the largest V). Waves of one
    # page: an odd count (ctx 33: five), an even one (64: eight) and a
    # single one, the two buffers alternating and each slot starting on
    # what the slot before left in them
    def stored(x):
        x = jnp.asarray(x).astype(jnp.bfloat16)
        return x, x.astype(jnp.float32)

    (k16, k32), (v16, v32) = stored(k_pool), stored(v_pool)
    for g, blocks in ((1, (1,)), (7, (1, 3))):
        qg = stored(rng.randn(slots, g * h, d))[1]
        want = np.asarray(gather_reference(
            qg, k32, v32, jnp.asarray(pt), jnp.asarray(ctx_len), ps,
            sm_scale=sm))
        tol = 1e-6 if g == 1 else 2.0 ** -16 * float(jnp.max(jnp.abs(v32)))
        for block in blocks:
            got = np.asarray(paged_decode_attention(
                qg, k16, v16, jnp.asarray(pt), jnp.asarray(ctx_len),
                page_size=ps, sm_scale=sm, block_pages=block,
                interpret=True))
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=tol,
                err_msg="bf16 pool, %d query heads a KV head, "
                        "block_pages=%d" % (g, block))

    print("paged_attention selftest OK (%.2fs): kernel == gather on %d "
          "ragged slots (ctx %s) in float32 and, with 1 and 7 query heads a "
          "KV head, over a bf16 pool at odd and even wave counts; garbage "
          "pages and neighbouring layers contribute exactly zero, slots of "
          "ctx_len 0 return 0.0 and read nothing"
          % (time.time() - t0, slots, list(map(int, ctx_len))))
    return 0


if __name__ == "__main__":
    import sys

    if "--selftest" in sys.argv:
        sys.exit(_selftest())
    print("usage: python -m paddle_tpu.ops.pallas_kernels.paged_attention "
          "--selftest")
    sys.exit(2)

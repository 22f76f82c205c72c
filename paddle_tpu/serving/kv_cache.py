"""KV-cache layouts for the decode driver: paged block-pool vs contiguous.

Two layouts behind ONE functional interface (`init_state` / `write_token` /
`write_prompt` / `context` / `decode_attention`), so the model's decode
loop is layout-blind and the two paths are bit-comparable:

* :class:`PagedKVCache` — the "Ragged Paged Attention" layout (PAPERS.md):
  KV rows live in a flat page pool ``[n_layer, num_pages*page_size, H*D]``
  (one lane-dense row per context position) and each slot owns an ordered
  page table ``[slots, pages_per_slot]``. Ragged sequence lengths cost only
  their pages; ``context`` gathers a slot's pages back into logical order
  (the XLA-gather path), and ``decode_attention`` dispatches between that
  gather and the fused ragged paged-attention Pallas kernel
  (ops/pallas_kernels/paged_attention.py) per
  ``FLAGS_paged_attention_kernel``. Why the heads are not a dimension of
  the pool: the chip tiles the last two dims of a buffer to (8, 128), a
  ``[rows, H, D]`` pool with GPT-2 small's ``12 x 64`` pads badly, so the
  compiler stored it rows-minor and converted the WHOLE pool before the
  first row scatter of a step and back after the last, and sliced and
  copied a layer of it for every kernel call (four fifths of a decode
  step). ``[rows, H*D]`` is whole lane tiles for every ``H*D % 128 == 0``,
  so writes scatter in place and the kernel indexes the layer in its own
  page DMA; the small ``[B, H, D]`` updates and the gathered contexts are
  reshaped, the pool never is.
* :class:`ContiguousKVCache` — the dense reference ``[n_layer, slots,
  max_ctx, H, D]`` every slot pays ``max_ctx`` for. The parity yardstick
  (tests/test_serving.py asserts bit-identical tokens/logits) and the
  padded-baseline cache.

Both write paths scatter with ``mode="drop"`` on out-of-bounds destination
rows, so inactive slots / padding positions are dropped INSIDE the compiled
step — no host-side branching, and unwritten rows stay zero in both
layouts, which is what makes the gathered contexts bit-identical.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache", "Int8PagedKVCache", "ContiguousKVCache"]

Cache = Dict[str, jnp.ndarray]


def _dtype_by_name(name: str) -> np.dtype:
    """Resolve a dtype by its ``.name`` — including the ml_dtypes extended
    set (bfloat16 etc.) that ``np.dtype(str)`` does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


class _KVCacheBase:
    """Shared geometry: ``max_ctx`` context positions per slot, over
    ``n_layer`` layers of ``n_head`` heads of ``d_head`` lanes."""

    layout = "base"

    def __init__(self, n_layer: int, n_head: int, d_head: int, slots: int,
                 max_ctx: int, dtype=jnp.float32):
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_head = int(d_head)
        self.slots = int(slots)
        self.max_ctx = int(max_ctx)
        self.dtype = jnp.dtype(dtype)

    def cache_bytes(self, state: Cache) -> int:
        return int(state["k"].nbytes + state["v"].nbytes)

    # -- page migration ------------------------------------------------------
    # Only paged layouts can ship pages; the dense layout refuses with a
    # typed error (there IS no page — a contiguous slot's KV is not an
    # addressable unit of state), which callers surface as "migration
    # unsupported" rather than a crash.
    def export_pages(self, state: Cache, pages):
        raise ValueError("layout %r has no pages to export" % self.layout)

    def import_pages(self, state: Cache, pages, meta: dict, blobs):
        raise ValueError("layout %r has no pages to import" % self.layout)


class PagedKVCache(_KVCacheBase):
    layout = "paged"

    def __init__(self, n_layer: int, n_head: int, d_head: int, slots: int,
                 max_ctx: int, page_size: int, num_pages: int,
                 dtype=jnp.float32):
        super().__init__(n_layer, n_head, d_head, slots, max_ctx, dtype)
        if max_ctx % page_size != 0:
            raise ValueError("max_ctx=%d must be a multiple of page_size=%d"
                             % (max_ctx, page_size))
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.pages_per_slot = self.max_ctx // self.page_size
        self.num_rows = self.num_pages * self.page_size  # flat KV rows
        self.row_width = self.n_head * self.d_head  # lanes of one KV row

    def _storage_dtype(self):
        """What a pool row is stored as (``self.dtype`` is what ``context``
        returns)."""
        return self.dtype

    def init_state(self) -> Cache:
        shp = (self.n_layer, self.num_rows, self.row_width)
        return {
            "k": jnp.zeros(shp, self._storage_dtype()),
            "v": jnp.zeros(shp, self._storage_dtype()),
            # page table: slot -> ordered page ids; rows beyond a slot's
            # reservation are whatever the allocator last left (reads are
            # masked by length, writes by the drop scatter)
            "pt": jnp.zeros((self.slots, self.pages_per_slot), jnp.int32),
        }

    # -- decode (one token per slot) -----------------------------------------
    def write_token(self, state: Cache, layer: int, k_new, v_new, pos,
                    active) -> Cache:
        """k_new/v_new [B,H,D] written at logical position ``pos[b]`` of
        slot b; inactive slots dropped via an OOB destination row."""
        ps = self.page_size
        pt = state["pt"]
        b_idx = jnp.arange(pt.shape[0])
        page = pt[b_idx, pos // ps]
        dest = page * ps + pos % ps
        dest = jnp.where(active, dest, self.num_rows)
        return self._write_rows(state, layer, dest, k_new, v_new)

    def _write_rows(self, state: Cache, layer: int, dest, k_new, v_new
                    ) -> Cache:
        """Scatter ``[N, H, D]`` updates into pool rows ``dest`` [N] of
        ``layer``; rows at ``num_rows`` are dropped."""
        return {
            **state,
            "k": state["k"].at[layer, dest].set(
                k_new.reshape(-1, self.row_width), mode="drop"),
            "v": state["v"].at[layer, dest].set(
                v_new.reshape(-1, self.row_width), mode="drop"),
        }

    def context(self, state: Cache, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Gather every slot's pages back into logical order:
        ``[slots, max_ctx, H, D]`` — the XLA-gather paged-attention path."""
        rows = self._context_rows(state["pt"])
        return (self._gather(state["k"], layer, rows),
                self._gather(state["v"], layer, rows))

    def _context_rows(self, pt) -> jnp.ndarray:
        """Pool row of every logical position: ``[slots, max_ctx]``."""
        ps = self.page_size
        rows = (pt * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
        return rows.reshape(pt.shape[0], self.max_ctx)

    def _gather(self, pool, layer: int, rows) -> jnp.ndarray:
        """``pool[layer, rows]`` with the heads split AFTER the gather:
        ``[slots, max_ctx, H, D]``."""
        return pool[layer, rows].reshape(rows.shape + (self.n_head,
                                                       self.d_head))

    def kernel_mode(self):
        """``(mode, why_not)``: ``mode`` is "compiled"/"interpret" when the
        ragged paged-attention Pallas kernel carries this cache's decode
        attention — ``FLAGS_paged_attention_kernel`` armed (see
        ops.attention_ops.paged_kernel_mode) AND the geometry inside the
        kernel's static gate — else None, with ``why_not`` "n/a" for a flag
        that is off and the gate's rule for an excluded shape. The one
        decision both decode paths and ``ServingEngine.decode_kernel_info``
        read."""
        from ..ops import attention_ops
        from ..ops.pallas_kernels.paged_attention import paged_attention_gate

        mode = attention_ops.paged_kernel_mode()
        if mode is None:
            return None, "n/a"
        why_not = paged_attention_gate(
            self.dtype, self.n_head, self.d_head, self.page_size,
            interpret=(mode == "interpret"))
        if why_not is not None:
            return None, "gate: " + why_not
        return mode, None

    def decode_attention(self, state: Cache, layer: int, q, ctx_len,
                         sm_scale: float = 1.0) -> jnp.ndarray:
        """One decode-attention step [B,H,D] over this layer's ragged
        contexts. Where :meth:`kernel_mode` arms it, the Pallas kernel
        takes the WHOLE pool and reads this layer's K/V pages straight from
        it via the device-resident page table — neither a layer slice nor
        the ``[B, max_ctx, H, D]`` gather ever materializes; otherwise the
        XLA gather + ops.attention_ops.decode_attention path runs. Both mask positions >= ctx_len with the SAME neg_inf
        constant, so the paths agree to float round-off (tier-1 parity
        tests pin it)."""
        from ..ops import attention_ops

        mode, _ = self.kernel_mode()
        if mode is not None:
            from ..ops.pallas_kernels import paged_attention as _pa

            return _pa.paged_decode_attention(
                q, state["k"], state["v"], state["pt"], ctx_len,
                page_size=self.page_size, layer=layer, sm_scale=sm_scale,
                interpret=(mode == "interpret"))
        ctx_k, ctx_v = self.context(state, layer)
        return attention_ops.decode_attention(q, ctx_k, ctx_v, ctx_len,
                                              sm_scale=sm_scale)

    def decode_verify(self, state: Cache, layer: int, q, ctx_len,
                      sm_scale: float = 1.0) -> jnp.ndarray:
        """Speculative verify-window attention [B,W,H,D] over this layer's
        ragged contexts (window position j = logical position ctx_len-1+j;
        the caller wrote all W positions' K/V first). Rides the SAME ragged
        Pallas kernel as ``decode_attention`` by flattening the window into
        B*W pseudo-slots — each window row replays its slot's page table
        with length ctx_len+j, which is exactly the per-slot raggedness the
        kernel already handles; no kernel change, one dispatch. The XLA
        gather + ops.attention_ops.verify_attention path stays the parity
        reference (one ``context`` gather serves all W rows)."""
        from ..ops import attention_ops

        b, w = q.shape[0], q.shape[1]
        mode, _ = self.kernel_mode()
        if mode is not None:
            from ..ops.pallas_kernels import paged_attention as _pa

            lens = ctx_len[:, None] + jnp.arange(w)[None, :]
            lens = jnp.clip(lens.reshape(b * w), 0, self.max_ctx)
            out = _pa.paged_decode_attention(
                q.reshape(b * w, self.n_head, self.d_head),
                state["k"], state["v"],
                jnp.repeat(state["pt"], w, axis=0), lens,
                page_size=self.page_size, layer=layer, sm_scale=sm_scale,
                interpret=(mode == "interpret"))
            return out.reshape(b, w, self.n_head, self.d_head)
        ctx_k, ctx_v = self.context(state, layer)
        return attention_ops.verify_attention(q, ctx_k, ctx_v, ctx_len,
                                              sm_scale=sm_scale)

    # -- prefill (one sequence) ----------------------------------------------
    def prompt_dest(self, pages) -> np.ndarray:
        """Host-side: the ``dest`` operand for ``write_prompt`` — a full
        page-table row (reserved pages first, rest parked on page 0;
        unused entries are never read or written)."""
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:len(pages)] = np.asarray(pages, np.int32)
        return row

    def write_prompt(self, state: Cache, layer: int, k_new, v_new, dest,
                     length) -> Cache:
        """k_new/v_new [S,H,D] for ONE sequence; ``dest`` is its page-table
        row [pages_per_slot]; positions >= length are dropped."""
        ps = self.page_size
        s = k_new.shape[0]
        j = jnp.arange(s)
        flat = dest[j // ps] * ps + j % ps
        flat = jnp.where(j < length, flat, self.num_rows)
        return self._write_rows(state, layer, flat, k_new, v_new)

    # -- page migration ------------------------------------------------------
    def _page_rows(self, pages) -> np.ndarray:
        p = np.asarray(pages, np.int64)
        return (p[:, None] * self.page_size
                + np.arange(self.page_size)[None, :]).reshape(-1)

    def page_meta(self) -> dict:
        """Geometry a page payload must match to be importable here —
        embedded in every export, checked on every import."""
        return {"layout": self.layout, "n_layer": self.n_layer,
                "n_head": self.n_head, "d_head": self.d_head,
                "page_size": self.page_size,
                "kv_dtype": jnp.dtype(self._storage_dtype()).name}

    def _check_meta(self, meta: dict, n_blobs: int, blobs) -> None:
        want = self.page_meta()
        got = {k: meta.get(k) for k in want}
        if got != want:
            raise ValueError("page payload geometry mismatch: %r != %r"
                             % (got, want))
        if len(blobs) != n_blobs:
            raise ValueError("page payload has %d blobs, expected %d"
                             % (len(blobs), n_blobs))

    def export_pages(self, state: Cache, pages):
        """Serialize ``pages`` (pool page ids) to ``(meta, blobs)``: raw
        C-order bytes of the K rows then the V rows, ``[n_layer,
        n_pages*page_size, H, D]`` each (which the ``[.., H*D]`` pool's
        rows are, byte for byte) — bit-exact, no float formatting."""
        rows = self._page_rows(pages)
        k = np.ascontiguousarray(np.asarray(state["k"][:, rows]))
        v = np.ascontiguousarray(np.asarray(state["v"][:, rows]))
        meta = self.page_meta()
        meta["n_pages"] = len(pages)
        return meta, [k.tobytes(), v.tobytes()]

    def import_pages(self, state: Cache, pages, meta: dict, blobs) -> Cache:
        """Write an exported payload into ``pages`` of THIS pool; raises
        ``ValueError`` (typed, caller frees its reservation) on any
        geometry/dtype/size mismatch. Row bytes land verbatim, so an
        export of the same pages round-trips bit-identical."""
        self._check_meta(meta, 2, blobs)
        n = int(meta.get("n_pages", -1))
        if n != len(pages):
            raise ValueError("page payload has %d pages, caller reserved %d"
                             % (n, len(pages)))
        rows = self._page_rows(pages)
        dt = _dtype_by_name(meta["kv_dtype"])
        shp = (self.n_layer, len(rows), self.row_width)
        want = int(np.prod(shp)) * dt.itemsize
        if len(blobs[0]) != want or len(blobs[1]) != want:
            raise ValueError("page payload blob bytes %d/%d != %d"
                             % (len(blobs[0]), len(blobs[1]), want))
        k = np.frombuffer(blobs[0], dtype=dt).reshape(shp)
        v = np.frombuffer(blobs[1], dtype=dt).reshape(shp)
        return {
            **state,
            "k": state["k"].at[:, rows].set(jnp.asarray(k)),
            "v": state["v"].at[:, rows].set(jnp.asarray(v)),
        }


class Int8PagedKVCache(PagedKVCache):
    """Paged layout with int8 KV pages: each pool row stores symmetric
    int8 quantized K/V, dequantized through per-page fp32 scale arrays
    (``"ks"``/``"vs"``, ``[n_layer, num_pages]`` — the scale rides the page
    metadata, so a page is self-describing wherever its id travels).

    The scales are FIXED at construction from a calibrated amax
    (``monitor.numerics.kv_scale``) — a write never rescales a page, which
    is exactly why this layout is gated behind calibration: without a
    trustworthy amax the fixed grid would silently clip. ``self.dtype``
    stays the COMPUTE dtype (`context` returns it), so the model's decode
    loop and the attention ops stay layout-blind; only the pool storage and
    ``cache_bytes`` see int8 — half the page bytes of bf16, a quarter of
    fp32, which under the PagePool's unchanged reservation math doubles
    (resp. quadruples) the page capacity of the same byte budget
    (tools/serve_bench.py asserts the capacity and decode-parity claims).

    ``decode_attention``/``decode_verify`` always take the gather path
    (``kernel_mode`` says so): the ragged Pallas kernel reads raw pool rows
    and has no dequant stage, so the kernel dispatch is bypassed rather
    than fed garbage — both decode paths (fused decode scan and
    prefill-side attention) dequantize through ``context``.
    """

    layout = "paged-int8"

    def __init__(self, n_layer: int, n_head: int, d_head: int, slots: int,
                 max_ctx: int, page_size: int, num_pages: int,
                 k_scale: float, v_scale: float, dtype=jnp.float32):
        super().__init__(n_layer, n_head, d_head, slots, max_ctx,
                         page_size, num_pages, dtype)
        if not (float(k_scale) > 0.0 and float(v_scale) > 0.0):
            raise ValueError(
                "Int8PagedKVCache needs calibrated positive scales, got "
                "k_scale=%r v_scale=%r — run a calibration pass "
                "(PADDLE_TPU_NUMERICS=2 / numerics.record_kv_calibration) "
                "first" % (k_scale, v_scale))
        self.k_scale = float(k_scale)
        self.v_scale = float(v_scale)

    def _storage_dtype(self):
        return jnp.int8

    def init_state(self) -> Cache:
        return {
            **super().init_state(),
            "ks": jnp.full((self.n_layer, self.num_pages), self.k_scale,
                           jnp.float32),
            "vs": jnp.full((self.n_layer, self.num_pages), self.v_scale,
                           jnp.float32),
        }

    def _quant(self, x, scale: float):
        return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                        -127, 127).astype(jnp.int8)

    def write_token(self, state: Cache, layer: int, k_new, v_new, pos,
                    active) -> Cache:
        return super().write_token(state, layer,
                                   self._quant(k_new, self.k_scale),
                                   self._quant(v_new, self.v_scale),
                                   pos, active)

    def write_prompt(self, state: Cache, layer: int, k_new, v_new, dest,
                     length) -> Cache:
        return super().write_prompt(state, layer,
                                    self._quant(k_new, self.k_scale),
                                    self._quant(v_new, self.v_scale),
                                    dest, length)

    def context(self, state: Cache, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        rows = self._context_rows(state["pt"])
        pages = rows // self.page_size  # page id per logical position
        ks = state["ks"][layer][pages][:, :, None, None].astype(self.dtype)
        vs = state["vs"][layer][pages][:, :, None, None].astype(self.dtype)
        return (self._gather(state["k"], layer, rows).astype(self.dtype) * ks,
                self._gather(state["v"], layer, rows).astype(self.dtype) * vs)

    def kernel_mode(self):
        return None, "gate: int8 pool (the kernel has no dequant stage)"

    def cache_bytes(self, state: Cache) -> int:
        return int(state["k"].nbytes + state["v"].nbytes
                   + state["ks"].nbytes + state["vs"].nbytes)

    # -- page migration ------------------------------------------------------
    def export_pages(self, state: Cache, pages):
        """int8 pages travel WITH their per-page fp32 scale columns
        (``ks``/``vs`` ``[n_layer]`` per page) — the payload is
        self-describing, so the importer dequantizes exactly as the
        exporter would even if its own constructor scales differ."""
        meta, blobs = super().export_pages(state, pages)
        p = np.asarray(pages, np.int64)
        ks = np.ascontiguousarray(np.asarray(state["ks"][:, p], np.float32))
        vs = np.ascontiguousarray(np.asarray(state["vs"][:, p], np.float32))
        return meta, blobs + [ks.tobytes(), vs.tobytes()]

    def import_pages(self, state: Cache, pages, meta: dict, blobs) -> Cache:
        self._check_meta(meta, 4, blobs)
        sshp = (self.n_layer, len(pages))
        want = int(np.prod(sshp)) * 4
        if len(blobs[2]) != want or len(blobs[3]) != want:
            raise ValueError("page payload scale bytes %d/%d != %d"
                             % (len(blobs[2]), len(blobs[3]), want))
        state = super().import_pages(state, pages, meta, blobs[:2])
        p = np.asarray(pages, np.int64)
        ks = np.frombuffer(blobs[2], dtype=np.float32).reshape(sshp)
        vs = np.frombuffer(blobs[3], dtype=np.float32).reshape(sshp)
        return {
            **state,
            "ks": state["ks"].at[:, p].set(jnp.asarray(ks)),
            "vs": state["vs"].at[:, p].set(jnp.asarray(vs)),
        }


class ContiguousKVCache(_KVCacheBase):
    layout = "contiguous"

    def init_state(self) -> Cache:
        shp = (self.n_layer, self.slots, self.max_ctx, self.n_head, self.d_head)
        return {"k": jnp.zeros(shp, self.dtype),
                "v": jnp.zeros(shp, self.dtype)}

    def write_token(self, state: Cache, layer: int, k_new, v_new, pos,
                    active) -> Cache:
        b_idx = jnp.arange(pos.shape[0])
        pos_c = jnp.where(active, pos, self.max_ctx)  # OOB -> dropped
        return {
            **state,
            "k": state["k"].at[layer, b_idx, pos_c].set(k_new, mode="drop"),
            "v": state["v"].at[layer, b_idx, pos_c].set(v_new, mode="drop"),
        }

    def context(self, state: Cache, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return state["k"][layer], state["v"][layer]

    def decode_attention(self, state: Cache, layer: int, q, ctx_len,
                         sm_scale: float = 1.0) -> jnp.ndarray:
        """Dense layout has no gather to fuse away — always the XLA path
        (the parity yardstick the paged kernel is measured against)."""
        from ..ops import attention_ops

        ctx_k, ctx_v = self.context(state, layer)
        return attention_ops.decode_attention(q, ctx_k, ctx_v, ctx_len,
                                              sm_scale=sm_scale)

    def decode_verify(self, state: Cache, layer: int, q, ctx_len,
                      sm_scale: float = 1.0) -> jnp.ndarray:
        from ..ops import attention_ops

        ctx_k, ctx_v = self.context(state, layer)
        return attention_ops.verify_attention(q, ctx_k, ctx_v, ctx_len,
                                              sm_scale=sm_scale)

    def prompt_dest(self, slot: int) -> np.int32:
        return np.int32(slot)

    def write_prompt(self, state: Cache, layer: int, k_new, v_new, dest,
                     length) -> Cache:
        s = k_new.shape[0]
        j = jnp.arange(s)
        pos_c = jnp.where(j < length, j, self.max_ctx)
        return {
            **state,
            "k": state["k"].at[layer, dest, pos_c].set(k_new, mode="drop"),
            "v": state["v"].at[layer, dest, pos_c].set(v_new, mode="drop"),
        }

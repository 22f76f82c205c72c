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

Cache GROUPS (:class:`CacheGroup`): a model whose layers do not all keep
the same positions names, for each group of layers, the window it keeps
(none: every position; W: the last W). A group has its own pool
``[layers_in_group, rows, H*D]``, its own page table a slot and its own
free list (serving.page_pool.PagePool, one a group, held by the engine). A
window group's slot uses its pages as a RING: position p lives at row ``p
mod W`` of the ring, so a slot never holds more than ``W / page_size``
pages there however long its context grows; K is stored after any rotary
rotation, so the order of a ring's rows does not matter to the softmax and
attention needs only ``min(ctx, W)`` as its length. A model with one kind
of layer (GPT-2) is ONE global group: the same class, the same code.

``n_head`` counts the heads of K and V, the same in every group. A model
with grouped queries hands ``decode_attention`` a ``q`` of ``G * n_head``
heads (query head n reads KV head ``n // G``), and G belongs to the GROUP:
layers of different kinds may put different numbers of query heads over the
same KV heads. ``q_per_kv`` names G, one value for every group or a mapping
by group name, and the kernel's gate is asked for each.

A group has a KIND (``CacheGroup.kind``), and one cache may hold groups of
different kinds side by side:

* ``KV``: rows of K and V a token, as above.
* ``LATENT`` (:class:`LatentPagedCache`): the layers of a model with latent
  (MLA) attention keep ONE row a token a layer, ``[c | kr]`` (the
  compressed KV latent and the one rotary key every head shares), and no V
  pool: decode attention, absorbed, scores every query head against that
  row and sums over its first ``rank`` lanes. Pages, page tables, the
  pool's free list and the drop scatter are the paged cache's own. A
  group covers the layers it names: all of them (Kimi-K2), one in six (a
  hybrid whose other layers keep a state), or one in four beside a second
  LATENT group with a ``window`` (a model whose other latent layers see
  the last W positions only): that group's slots keep their rows in a
  RING by the rule above, the row being stored with its rotary key
  already rotated. A latent cache may keep an INDEX beside its rows
  (``index``; a model whose latent layers choose the rows a query reads):
  index keys in a second pool ``"ik"`` addressed through the group's own
  page table (no second allocator, no second free list), a page's keys
  together (pooled: side by side in ONE pool row; a key a row: a page a
  ``[page_size, lanes]`` tile), so that the page table gathers them as
  it stands. Two shapes of it, ONE code path with a short branch where they
  part (a block of one row is a case of ``index=``, not a second class:
  the pool, its addressing, the prompt's write and the scores are the
  same lines):

  - POOLED BLOCKS (``index = (kpool > 1, lanes, blocks a query reads)``):
    one key a block of ``kpool`` rows, the MEAN of the block's keys,
    written when the block's last row is (a page of 16 rows owns 4 of
    them), and, bound to the SLOT as a state group's convolution tail is,
    the raw keys of the block still open (``"it"``). A query reads its own
    block and the best closed ones; the read goes by 8-row tiles through a
    second, shorter table a step (the least the chip copies).
  - ROWS (``index = (1, lanes, rows a query reads)``): one key a ROW,
    written with the row at every step and with a prompt's rows at a
    prefill; no pooling, no open block, no ``"it"``, no slot entry after
    the page table. A query scores every row of its context, its own
    among them (``index_scores``: the ``dsa_index_scores`` kernel over
    its live pages of keys), and reads the best ``topk`` single rows by
    the latent kernel's wave over the slot's pages as they stand with the
    choice as its row mask (``rows_decode_attention``: a one-row copy is
    not a thing the chip's compiler gives a Pallas kernel out of HBM, of
    32-bit words or of bfloat16: 8 rows at the least, and 8-row tiles
    hold a chosen row almost everywhere once a few thousand single rows
    are chosen; XLA's own gather of the chosen rows costs a descriptor a
    row and is the off-chip path).

  A latent group's pages come in RUNS (:meth:`LatentPagedCache
  .group_run_pages`; PR 64): its free list (``page_pool.PagePool``, built
  by the engine with the run the cache names) hands out R pages side by
  side in the pool, the first a multiple of R, so entry ``R g`` of a
  slot's table starts ``R * page_size`` consecutive pool rows, all the
  slot's own. The POOL guarantees it and the engine checks it where it
  sets a slot's table; the two kernels that walk the table
  (``mla_latent_decode`` under its three names, ``dsa_index_scores``) rely
  on it and are handed R as ``copy_pages``: one copy a run where they paid
  one a page. R is the latent kernel's ``RUN_PAGES`` where it divides the
  slot's table and the kernel's wave (a ring takes the largest half that
  divides its pages, 1 if none: the geometry decides, not a model's
  name). Nothing else reads the table differently: it is still one entry
  a page, and the pooled blocks' 8-row tiles go by their own table a step
  with single copies. ``KV`` groups keep single pages.

  Side by side, what a paged group's slot holds: every position in pages
  (``window`` None); the last W positions as a RING (``window`` W); a
  window's rows and then a summary a chunk of it, COMPACTING (``chunk``,
  below, ``KV`` groups); and, beside a latent group of pages, an index of
  pooled blocks or of rows.
* ``STATE``: the layers of a linear-attention recurrence keep nothing a
  token. What they keep belongs to the SLOT, has a fixed size and is
  rewritten whole at every step: a ``[H, dk, dv]`` float32 state and the
  last few inputs of a short causal convolution (``slot_state``). Such a
  group has no pages, no page table and no pool, so the scheduler admits
  by the other groups' pages alone; its one entry of a slot's ``dest`` row
  is the slot's own index. ``set_page_table`` zeroes the slot's state (the
  prefill executable arms the slot with it), ``write_prompt`` takes a
  layer's final state and convolution tail, ``tail_step`` and
  ``state_step`` advance them by one decode step for the slots that are
  ``active`` and leave every other slot's unread and unwritten. The
  group's RECURRENCE (``recurrence``: ``"kda"``, the delta rule of
  ops/pallas_kernels/kda.py, or ``"ssd"``, Mamba-2's of ssd.py) says
  which step ``state_step`` runs and what its inputs are.

A layer stands in ONE group of a kind, and may stand in one PAGED group
(``KV`` or ``LATENT``) and one ``STATE`` group at once: a block whose two
mixers read the same input, one over a page pool's rows and one over a
recurrent state, keeps both. ``write_token``, ``context`` and
``decode_attention`` then mean the layer's pages, ``tail_step``,
``state_step`` and ``write_slot_state`` its state, and ``write_prompt``
its pages (its state where it has no pages).

Cache STEPS (``cache_steps``): a model that runs its layers T times over a
token (a looped decoder: the same weights at every step) keeps, a layer,
what EACH step wrote at every position, since step t of a layer attends
over step t's rows alone. The pools then hold ``cache_steps`` layers a
layer of the model, layer-major: layer ``li`` of a group at step ``t`` is
the pool's layer ``li * cache_steps + t``. ``write_token``,
``write_prompt``, ``context`` and ``decode_attention`` take ``step=`` beside
``layer``: an int, or an int32 scalar traced inside the model's device loop
over its steps (the layer stays static: the group and the place in it come
from ``_where`` as ever). A group's ONE page table serves all its pool
layers, so pages, admission and page sharing count what they counted and a
page is ``cache_steps`` times the bytes. With ``cache_steps`` 1 (every other
model) ``step`` is not passed and the pool's layer is the Python int it
always was.

COMPACTING groups (``CacheGroup.chunk``): a ``KV`` group with a ``window``
W AND a ``chunk`` c keeps a position's row only while the position's
TUMBLING window (positions ``[W w, W w + W)``) is open; when the window
closes, its W rows are REPLACED, for good, by one pooled (key, value) pair
a chunk of c positions, ``W / c`` summaries, which the MODEL computes (the
cache owns where rows live, not what a summary is). A slot's view, in the
order attention reads it, is::

    [ summaries of windows 0..w-1 : (W/c) w rows ]
    [ the open window's exact rows : up to W rows ]    <- the length ends here
    [ the open window's finished summaries : W/c ]     <- unread until the close

so position p lives at view row ``(W/c) (p // W) + p mod W`` and a query at
p attends over that many rows plus one: neither the identity nor a ring. K
is stored rotated and the summaries are pooled from rotated keys, so the
order of a view's rows does not matter to the softmax and
``decode_attention`` is the paged kernel given a length, as for a ring. The
open window's summaries WAIT in the ``W / (c page_size)`` pages that follow
the window's in the slot's page table; closing a window is a ROTATION of
that stretch of the slot's page-table row (the summary pages come to stand
where the window began, the window's pages, whose rows are dead from that
instant, become the next window's and its summaries'): a few hundred int32
a slot, no row of the pool is copied, and nothing is staged beside the
pool. The table is the device's from admission on (the host keeps a
request's pages as a set, to free them), so nothing else sees the
rotation. A model calls, a decode step: ``write_token`` (the map above),
``open_chunk`` (the c rows of the chunk p is in, out), ``write_summary``
(the chunk's pair in; dropped unless ``(p + 1) % c == 0``),
``decode_attention``, and, after its last layer, ``close_windows`` (the
rotation where ``(p + 1) % W == 0``; every layer of the group shares the
table). A prompt hands ``write_prompt`` the OPEN window's rows and a
summary a chunk (``(k_open, v_open, k_sum, v_sum)``, the layer's ``kept``;
:func:`open_window_start` says which rows).
A request of n positions needs ``ceil(((W/c) (n // W) + min(n, W)) /
page_size) + W / (c page_size)`` pages (:meth:`PagedKVCache.pages_needed`),
not ``n / page_size``. What cannot work over such a group is refused as
over a ring, and for one more reason: a compacted window cannot be rolled
back, and a page no longer holds the positions its place says (the prefix
cache, the int8 pool, the contiguous layout, page export and import).

Both write paths scatter with ``mode="drop"`` on out-of-bounds destination
rows, so inactive slots / padding positions are dropped INSIDE the compiled
step — no host-side branching, and unwritten rows stay zero in both
layouts, which is what makes the gathered contexts bit-identical.
"""

from __future__ import annotations

import functools
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CacheGroup", "PagedKVCache", "Int8PagedKVCache",
           "LatentPagedCache", "ContiguousKVCache", "KV", "LATENT", "STATE",
           "open_window_start"]

KV, LATENT, STATE = "kv", "latent", "state"
# a state group's recurrence: a module of that name under
# ops/pallas_kernels has ``<name>_state_step``, ``<name>_state_step_xla``
# and ``<name>_state_step_gate``
_RECURRENCES = ("kda", "ssd")


class CacheGroup(NamedTuple):
    """Layers that keep the same thing: ``kind`` ``KV`` or ``LATENT`` keeps
    rows a token in pages (``window`` None keeps every position of a slot's
    context, W keeps the last W as a ring); ``STATE`` keeps a fixed-size
    state a slot and has no pages (``window`` None, ``num_pages`` 0). A
    ``KV`` group with a ``chunk`` c is COMPACTING: ``window`` is then a
    tumbling window whose rows are replaced by one summary a chunk when it
    closes (the module docstring has the map)."""

    name: str
    layers: Tuple[int, ...]
    window: Optional[int]
    num_pages: int
    kind: str = KV
    chunk: Optional[int] = None

Cache = Dict[str, jnp.ndarray]


def _live_len(ctx_len, active):
    """The lengths a decode step's attention is given: ``ctx_len`` [B]
    where a slot is ``active``, and 0 elsewhere. The engine leaves a retired
    slot's length where its request ended, so without this every layer of
    every step would stream that request's whole context for nobody."""
    return jnp.where(active, ctx_len, 0)


def open_window_start(length, rows: int, window: int):
    """First position of the stretch of a prompt's rows that a compacting
    group is handed: the ``min(rows, window)`` positions from here hold the
    window that ``length`` positions leave open (the positions from
    ``window (length // window)`` on), clipped to the bucket's ``rows``.
    The model slices by it, ``write_prompt`` places by it."""
    return jnp.clip(window * (length // window), 0,
                    rows - min(rows, window))


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
                 max_ctx: int, dtype=jnp.float32, cache_steps: int = 1):
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_head = int(d_head)
        self.slots = int(slots)
        self.max_ctx = int(max_ctx)
        self.dtype = jnp.dtype(dtype)
        self.cache_steps = int(cache_steps)
        if self.cache_steps < 1:
            raise ValueError("cache_steps=%d: a layer keeps at least one "
                             "cache layer" % self.cache_steps)

    def _pool_layer(self, li: int, step):
        """The pool's layer of a model layer's place ``li`` at loop step
        ``step`` (None: step 0), layer-major: ``li * cache_steps + step``.
        ``step`` may be an int32 scalar traced in a device loop. With ONE
        cache layer a layer it is ``li`` itself, a Python int."""
        if self.cache_steps == 1:
            return li
        return li * self.cache_steps + (0 if step is None else step)

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
                 dtype=jnp.float32,
                 groups: Optional[Sequence[CacheGroup]] = None,
                 q_per_kv: Union[int, Mapping[str, int]] = 1,
                 slot_state: Optional[Sequence[int]] = None,
                 recurrence: str = "kda", cache_steps: int = 1):
        super().__init__(n_layer, n_head, d_head, slots, max_ctx, dtype,
                         cache_steps)
        if max_ctx % page_size != 0:
            raise ValueError("max_ctx=%d must be a multiple of page_size=%d"
                             % (max_ctx, page_size))
        self.page_size = int(page_size)
        self.row_width = self.n_head * self.d_head  # lanes of one KV row
        if groups is None:
            groups = [CacheGroup("global", tuple(range(self.n_layer)), None,
                                 int(num_pages))]
        self.groups: List[CacheGroup] = [
            CacheGroup(g.name, tuple(g.layers),
                       None if g.window is None
                       else int(g.window) if g.chunk is not None
                       else min(int(g.window), self.max_ctx),
                       int(g.num_pages), g.kind,
                       None if g.chunk is None else int(g.chunk))
            for g in groups]
        # a STATE group's geometry: (heads, dk, dv, tail rows, tail width)
        self.slot_state = (None if slot_state is None
                           else tuple(int(n) for n in slot_state))
        kinds = [g.kind for g in self.groups]
        if STATE in kinds and (
                self.slot_state is None
                or STATE in kinds[:len(kinds) - kinds.count(STATE)]):
            raise ValueError(
                "a state group needs slot_state=(heads, dk, dv, tail rows, "
                "tail width) and comes after every paged group (the "
                "engine's pools are the paged groups', in order): %s"
                % kinds)
        if recurrence not in _RECURRENCES:
            raise ValueError("recurrence=%r is not one of %s"
                             % (recurrence, sorted(_RECURRENCES)))
        self.recurrence = recurrence
        # layer -> (its group's index, its index inside that group's pool
        # or state buffer), a map a KIND of group: the paged groups', the
        # state groups'. A layer may be in one of each
        self._where: Dict[int, Tuple[int, int]] = {}
        self._where_state: Dict[int, Tuple[int, int]] = {}
        for gi, g in enumerate(self.groups):
            if g.window is not None and g.window % self.page_size:
                raise ValueError("group %r: window=%d must be a multiple of "
                                 "page_size=%d" % (g.name, g.window,
                                                   self.page_size))
            if g.chunk is not None and (
                    g.kind != KV or g.window is None or self.cache_steps > 1
                    or self.page_size % g.chunk
                    or g.window % (g.chunk * self.page_size)):
                raise ValueError(
                    "group %r: a compacting group is a KV group with a "
                    "window, whole chunks a page and whole pages of "
                    "summaries a window, one cache layer a layer; got kind "
                    "%s, window=%s, chunk=%d, page_size=%d, cache_steps=%d"
                    % (g.name, g.kind, g.window, g.chunk, self.page_size,
                       self.cache_steps))
            where = self._where_state if g.kind == STATE else self._where
            for li, layer in enumerate(g.layers):
                if layer in where:
                    raise ValueError(
                        "layer %d is in two %s cache groups"
                        % (layer, "state" if g.kind == STATE else "paged"))
                where[layer] = (gi, li)
        # a layer may stand in NO group: it keeps nothing (a feed-forward
        # that is a layer of its own)
        covered = sorted(set(self._where) | set(self._where_state))
        if not covered or covered[0] < 0 or covered[-1] >= self.n_layer:
            raise ValueError("the cache groups name layers of 0..%d, each "
                             "at most once a kind, got %s"
                             % (self.n_layer - 1, covered))
        # query heads a KV head, by group name
        if not isinstance(q_per_kv, Mapping):
            q_per_kv = {g.name: q_per_kv for g in self.groups}
        if set(q_per_kv) != {g.name for g in self.groups}:
            raise ValueError("q_per_kv names %s; the cache groups are %s"
                             % (sorted(q_per_kv),
                                [g.name for g in self.groups]))
        self.q_per_kv: Dict[str, int] = {
            g.name: int(q_per_kv[g.name]) for g in self.groups}
        # the first group's geometry under the names a one-group cache
        # always had
        self.num_pages = self.groups[0].num_pages
        self.pages_per_slot = self.group_pages_per_slot(0)
        # where each group's row starts in a slot's page-table rows laid on
        # end (prompt_dest_groups); the last entry is their whole length
        self._pt_start = [0]
        for gi in range(len(self.groups)):
            self._pt_start.append(self._pt_start[-1]
                                  + self.group_pages_per_slot(gi))
        self.num_rows = self.num_pages * self.page_size  # flat KV rows

    # -- groups ---------------------------------------------------------------
    def group_rows(self, gi: int) -> int:
        """Context rows a slot can hold in group ``gi``."""
        g = self.groups[gi]
        if g.chunk is not None:
            return self.pages_needed(gi, self.max_ctx) * self.page_size
        return self.max_ctx if g.window is None else g.window

    def _summaries(self, gi: int) -> int:
        """Summaries a closed window of compacting group ``gi`` leaves:
        view rows it keeps."""
        return self.groups[gi].window // self.groups[gi].chunk

    def _view_row(self, gi: int, pos):
        """Where position ``pos`` lives in a slot's view of compacting
        group ``gi`` while its window is open: after a summary a chunk of
        every closed window."""
        w = self.groups[gi].window
        return self._summaries(gi) * (pos // w) + pos % w

    def _pool_rows(self, table, view_rows):
        """Pool rows of ``view_rows`` of a slot's view through its
        page-table entries ``table`` (one table for all the rows, or one a
        row)."""
        ps = self.page_size
        page = (table[view_rows // ps] if table.ndim == 1
                else table[jnp.arange(table.shape[0]), view_rows // ps])
        return page * ps + view_rows % ps

    def group_pages_per_slot(self, gi: int) -> int:
        """Entries of group ``gi`` in a slot's page-table rows: its pages,
        or for a state group the one entry that names the slot."""
        if self.groups[gi].kind == STATE:
            return 1
        if self.groups[gi].chunk is not None:
            return self.pages_needed(gi, self.max_ctx)
        return self.group_rows(gi) // self.page_size

    def group_run_pages(self, gi: int) -> int:
        """Pages side by side in the pool that group ``gi``'s free list
        hands out as ONE aligned run (``page_pool.py``): 1, single pages,
        for every group of K and V rows (a prefix is shared page by
        page)."""
        return 1

    @property
    def page_table_len(self) -> int:
        """Entries of one slot's page-table rows, every group's on end:
        the length of :meth:`prompt_dest_groups`'s row."""
        return self._pt_start[-1]

    def pages_needed(self, gi: int, total_tokens: int) -> int:
        """Pages group ``gi`` reserves for a request of ``total_tokens``
        positions: all of them, or the whole ring where it is shorter; in
        a compacting group a summary a chunk of every window the request
        can close, a whole window's rows (or the request's, if fewer) and
        the pages where the open window's summaries wait."""
        n, g = int(total_tokens), self.groups[gi]
        if g.chunk is not None:
            rows = self._summaries(gi) * (n // g.window) + min(n, g.window)
            return (-(-rows // self.page_size)
                    + self._summaries(gi) // self.page_size)
        need = -(-n // self.page_size)
        return min(need, self.group_pages_per_slot(gi))

    def _key(self, gi: int, what: str) -> str:
        """State key of group ``gi``'s ``k``/``v``/``pt``: the first group
        keeps the plain names a one-group cache always had."""
        return what if gi == 0 else "%s.%s" % (what, self.groups[gi].name)

    def _group_len(self, gi: int, ctx_len):
        """Rows of a slot's context that group ``gi`` holds: what a query
        at position ``ctx_len - 1`` attends over."""
        g = self.groups[gi]
        if g.chunk is not None:
            return jnp.where(ctx_len > 0,
                             self._view_row(gi, ctx_len - 1) + 1, 0)
        return ctx_len if g.window is None else jnp.minimum(ctx_len,
                                                            g.window)

    def _single_group(self, what: str) -> None:
        """What needs ONE group of K and V rows, a position's row where
        the position says (the int8 pool, page copies, export and import),
        is refused elsewhere."""
        if self.groups[0].chunk is not None:
            raise ValueError(
                "%s is not supported over a compacting group (%s: a closed "
                "window's rows are replaced by a summary a chunk of %d, so "
                "a page no longer holds the positions its place says and "
                "nothing can be rolled back)"
                % (what, self.groups[0].name, self.groups[0].chunk))
        if len(self.groups) > 1 or self.groups[0].kind != KV:
            raise ValueError(
                "%s is not supported over a cache with %d groups (%s)"
                % (what, len(self.groups),
                   ["%s: %s" % (g.name, g.kind) for g in self.groups]))

    def _storage_dtype(self):
        """What a pool row is stored as (``self.dtype`` is what ``context``
        returns)."""
        return self.dtype

    _POOLS = ("k", "v")     # a paged group's pools, by state key

    def init_state(self) -> Cache:
        state = {}
        for gi, g in enumerate(self.groups):
            if g.kind == STATE:
                _, _, _, taps, width = self.slot_state
                state[self._key(gi, "s")] = jnp.zeros(
                    (len(g.layers), self.slots) + self.state_shape(),
                    jnp.float32)
                state[self._key(gi, "tail")] = jnp.zeros(
                    (len(g.layers), self.slots, taps, width), self.dtype)
                continue
            shp = (len(g.layers) * self.cache_steps,
                   g.num_pages * self.page_size, self.row_width)
            for what in self._POOLS:
                state[self._key(gi, what)] = jnp.zeros(
                    shp, self._storage_dtype())
            # page table: slot -> ordered page ids; rows beyond a slot's
            # reservation are whatever the allocator last left (reads are
            # masked by length, writes by the drop scatter)
            state[self._key(gi, "pt")] = jnp.zeros(
                (self.slots, self.group_pages_per_slot(gi)), jnp.int32)
        return state

    def cache_bytes(self, state: Cache) -> int:
        """Every pool and state as stored (a latent row's padding lanes
        and an int8 pool's scales count); the page tables do not."""
        return int(sum(x.nbytes for key, x in state.items()
                       if key.partition(".")[0] != "pt"))

    def state_bytes(self, state: Cache) -> int:
        """The per-slot states and convolution tails of the state groups."""
        return int(sum(x.nbytes for key, x in state.items()
                       if key.partition(".")[0] in ("s", "tail")))

    def set_page_table(self, state: Cache, slot: int, dest) -> Cache:
        """Point ``slot`` at the pages of ``dest`` (what
        :meth:`prompt_dest_groups` made) in every paged group, and zero
        its state in every state group: a request starts from nothing."""
        out = dict(state)
        for gi, g in enumerate(self.groups):
            if g.kind == STATE:
                for what in ("s", "tail"):
                    key = self._key(gi, what)
                    out[key] = state[key].at[:, slot].set(0)
                continue
            key = self._key(gi, "pt")
            out[key] = state[key].at[slot].set(
                dest[self._pt_start[gi]:self._pt_start[gi + 1]])
        return out

    def copy_pages(self, state: Cache, src, dst) -> Cache:
        """Every layer's K and V rows of pool pages ``src`` written to pages
        ``dst`` (int32 vectors of one length; a pair with ``src == dst``
        leaves its page as it is, which is how a caller pads them)."""
        self._single_group("page copy")
        rows = jnp.arange(self.page_size, dtype=jnp.int32)
        s = (src[:, None] * self.page_size + rows).reshape(-1)
        d = (dst[:, None] * self.page_size + rows).reshape(-1)
        return {**state,
                "k": state["k"].at[:, d].set(state["k"][:, s]),
                "v": state["v"].at[:, d].set(state["v"][:, s])}

    # -- decode (one token per slot) -----------------------------------------
    def write_token(self, state: Cache, layer: int, k_new, v_new, pos,
                    active, step=None) -> Cache:
        """k_new/v_new [B,H,D] written at logical position ``pos[b]`` of
        slot b (in a window group: at its place in the ring); inactive
        slots dropped via an OOB destination row. ``step``: the loop step
        whose cache layer of ``layer`` is written (:meth:`_pool_layer`)."""
        ps = self.page_size
        gi, _ = self._where[layer]
        pt = state[self._key(gi, "pt")]
        if self.groups[gi].chunk is not None:
            dest = self._pool_rows(pt, self._view_row(gi, pos))
        else:
            b_idx = jnp.arange(pt.shape[0])
            idx = pos // ps
            if self.groups[gi].window is not None:
                idx = idx % self.group_pages_per_slot(gi)
            dest = pt[b_idx, idx] * ps + pos % ps
        dest = jnp.where(active, dest, self._drop_row(gi))
        return self._write_rows(state, layer, dest, k_new, v_new, step)

    # -- a compacting group's decode step ------------------------------------
    def open_chunk(self, state: Cache, layer: int, pos
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``(k, v)`` [B, chunk, H, D]: the rows of the chunk that position
        ``pos[b]`` is in, as the pool keeps them (``write_token`` first:
        the row at ``pos`` is among them; rows past it are whatever the
        pages held, which a caller uses only once the chunk is whole). A
        chunk's rows lie in ONE page, so this is a slice a slot of the
        group's whole pool, no layer of it is copied."""
        gi, li = self._where[layer]
        c = self.groups[gi].chunk
        row = self._view_row(gi, pos)
        first = self._pool_rows(state[self._key(gi, "pt")],
                                row - row % c)

        def rows(pool):
            out = jax.vmap(lambda f: jax.lax.dynamic_slice(
                pool, (li, f, 0), (1, c, self.row_width))[0])(first)
            return out.reshape(out.shape[:2] + (self.n_head, self.d_head))

        return (rows(state[self._key(gi, "k")]),
                rows(state[self._key(gi, "v")]))

    def write_summary(self, state: Cache, layer: int, k_sum, v_sum, pos,
                      active) -> Cache:
        """``k_sum``/``v_sum`` [B, H, D], the summary of the chunk that
        ``pos[b]`` ENDS, written where the open window's summaries wait
        (after the window's rows in the slot's view: not read until
        :meth:`close_windows` brings the pages forward). Dropped for a slot
        that is not ``active`` or whose ``pos`` is not its chunk's last."""
        gi, _ = self._where[layer]
        g = self.groups[gi]
        view = (self._summaries(gi) * (pos // g.window) + g.window
                + (pos % g.window) // g.chunk)
        dest = self._pool_rows(state[self._key(gi, "pt")], view)
        dest = jnp.where(active & ((pos + 1) % g.chunk == 0), dest,
                         self._drop_row(gi))
        return self._write_rows(state, layer, dest, k_sum, v_sum)

    def close_windows(self, state: Cache, pos, active) -> Cache:
        """The compaction, after a decode step's LAST layer: in every
        compacting group, a slot that is ``active`` and whose ``pos`` is
        its window's last has the stretch of its page-table row that holds
        the window and its summaries rotated by the summaries' pages, so
        that they stand where the window began and the window's own pages
        (their rows dead from now on) follow them, as the next window's and
        its summaries'. No pool row moves."""
        out = dict(state)
        for gi, g in enumerate(self.groups):
            if g.chunk is None:
                continue
            key = self._key(gi, "pt")
            pt = state[key]
            ps = self.page_size
            kept = self._summaries(gi) // ps        # summary pages a window
            span = g.window // ps + kept            # window + its summaries
            entry = jnp.arange(pt.shape[1])[None, :]
            rel = entry - (kept * (pos // g.window))[:, None]
            src = jnp.where((rel >= 0) & (rel < span),
                            entry - rel + (rel - kept) % span, entry)
            closing = active & ((pos + 1) % g.window == 0)
            out[key] = jnp.where(closing[:, None],
                                 jnp.take_along_axis(pt, src, axis=1), pt)
        return out

    def _drop_row(self, gi: int) -> int:
        """One past group ``gi``'s last pool row: a scatter to it drops."""
        return self.groups[gi].num_pages * self.page_size

    def _write_rows(self, state: Cache, layer: int, dest, k_new, v_new,
                    step=None) -> Cache:
        """Scatter ``[N, H, D]`` updates into rows ``dest`` [N] of
        ``layer`` (at ``step``) in its group's pool; rows at
        :meth:`_drop_row` are dropped."""
        gi, li = self._where[layer]
        li = self._pool_layer(li, step)
        kk, vk = self._key(gi, "k"), self._key(gi, "v")
        return {
            **state,
            kk: state[kk].at[li, dest].set(
                k_new.reshape(-1, self.row_width), mode="drop"),
            vk: state[vk].at[li, dest].set(
                v_new.reshape(-1, self.row_width), mode="drop"),
        }

    def context(self, state: Cache, layer: int, step=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Gather every slot's pages of ``layer``'s group (the cache layer
        of ``step``) back into page-table order: ``[slots, rows, H, D]``
        with ``rows`` = ``max_ctx``, or the ring's length in a window group
        (ring order, not position order) — the XLA-gather paged-attention
        path."""
        gi, li = self._where[layer]
        li = self._pool_layer(li, step)
        rows = self._context_rows(state[self._key(gi, "pt")])
        return (self._gather(state[self._key(gi, "k")], li, rows),
                self._gather(state[self._key(gi, "v")], li, rows))

    def _context_rows(self, pt) -> jnp.ndarray:
        """Pool row of every row of a slot's page table: ``[slots,
        pages * page_size]``."""
        ps = self.page_size
        rows = (pt * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
        return rows.reshape(pt.shape[0], pt.shape[1] * ps)

    def _gather(self, pool, li, rows) -> jnp.ndarray:
        """``pool[li, rows]`` with the heads split AFTER the gather:
        ``[slots, rows, H, D]``."""
        return pool[li, rows].reshape(rows.shape + (self.n_head,
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

        mode = attention_ops.paged_kernel_mode()
        if mode is None:
            return None, "n/a"
        why_not = self._kernel_gate(interpret=(mode == "interpret"))
        if why_not is not None:
            return None, "gate: " + why_not
        return mode, None

    def _kernel_gate(self, interpret: bool) -> Optional[str]:
        """The kernel's static gate over this cache's geometry, asked for
        each group's query heads a KV head: the first rule that excludes
        one keeps every group on the gather path."""
        from ..ops.pallas_kernels.paged_attention import paged_attention_gate

        for g in sorted(set(self.q_per_kv.values())):
            why_not = paged_attention_gate(
                self.dtype, self.n_head, self.d_head, self.page_size,
                interpret=interpret, q_per_kv=g)
            if why_not is not None:
                return why_not
        return None

    def kernel_folds(self) -> Dict[str, str]:
        """``{group: "grouped" | "per_lane"}``: the fold the Pallas kernel
        takes for each paged group's geometry (its query heads a KV head
        and the head's width: paged_attention.paged_attention_fold, what
        the call itself asks); empty where :meth:`kernel_mode` keeps the
        gather path."""
        from ..ops.pallas_kernels.paged_attention import paged_attention_fold

        if self.kernel_mode()[0] is None:
            return {}
        return {g.name: paged_attention_fold(self.q_per_kv[g.name],
                                             self.d_head)
                for g in self.groups if g.kind != STATE}

    def rows_read(self, ctx_len, active) -> Dict[str, jnp.ndarray]:
        """``{"attn_rows_read.<group>": rows}``: the context rows ONE layer
        of each group reads in a decode step at ``ctx_len`` [B], summed
        over the slots (the lengths :meth:`decode_attention` attends over:
        0 for a slot that is not ``active``). A model hands them back among
        its decode ``stats`` for ``serving/attn_rows_read.<group>``."""
        live = _live_len(ctx_len, active)
        out = {}
        for gi, g in enumerate(self.groups):
            if g.kind == STATE:
                continue
            rows = jnp.sum(self._group_len(gi, live)).astype(jnp.int32)
            if g.chunk is None:
                out["attn_rows_read." + g.name] = rows
                continue
            # a compacting group reads two kinds of row: a summary a chunk
            # of the closed windows, and the open window's exact rows
            pooled = jnp.sum(self._summaries(gi) * (
                jnp.maximum(live - 1, 0) // g.window)).astype(jnp.int32)
            out["attn_rows_read.%s_summary" % g.name] = pooled
            out["attn_rows_read.%s_exact" % g.name] = rows - pooled
        return out

    def decode_attention(self, state: Cache, layer: int, q, ctx_len,
                         active, sm_scale: float = 1.0, step=None
                         ) -> jnp.ndarray:
        """One decode-attention step over this layer's ragged contexts (at
        loop step ``step``: the rows that step wrote, no other's):
        ``q`` [B, G*H, D] (G = 1: as many query heads as KV heads) in,
        [B, G*H, D] out. A slot attends over its LIVE length
        (:func:`_live_len`: 0 where ``active`` [B] is false, so a slot that
        holds no request streams none of the rows its last one left), a
        window group over ``min(that, window)`` rows of its ring. Where
        :meth:`kernel_mode` arms it, the Pallas kernel takes the group's
        WHOLE pool and reads this layer's K/V pages straight from it via
        the device-resident page table, once for all G query heads of a KV
        head — neither a layer slice nor the ``[B, rows, H, D]`` gather
        ever materializes, and a slot of length 0 costs a grid step and
        nothing else; otherwise the XLA gather +
        ops.attention_ops.decode_attention path runs. Both mask rows >= the
        length with the SAME neg_inf constant, so over a float32 pool the
        paths agree to float round-off (tier-1 parity tests pin it, at one
        query head a KV head for heads that are not whole lane tiles and
        for heads that are); over a bfloat16 pool they differ by the
        rounding of what each keeps between its steps (the kernel's
        ``grouped`` fold, :meth:`kernel_folds`, meets V with 16 bits of
        each probability; its fold for heads that are not whole lane tiles
        widens the pool to float32), inside the margins the models' tests
        state. A slot of length 0 comes back finite from both and is
        nobody's to read: exactly 0.0 from the kernel, the mean of its
        table's V rows from the gather."""
        from ..ops import attention_ops

        gi, li = self._where[layer]
        length = self._group_len(gi, _live_len(ctx_len, active))
        mode, _ = self.kernel_mode()
        if mode is not None:
            from ..ops.pallas_kernels import paged_attention as _pa

            return _pa.paged_decode_attention(
                q, state[self._key(gi, "k")], state[self._key(gi, "v")],
                state[self._key(gi, "pt")], length,
                page_size=self.page_size, layer=self._pool_layer(li, step),
                sm_scale=sm_scale, interpret=(mode == "interpret"))
        ctx_k, ctx_v = self.context(state, layer, step)
        return attention_ops.decode_attention(q, ctx_k, ctx_v, length,
                                              sm_scale=sm_scale)

    # -- a state group's decode step -----------------------------------------
    def state_kernel_mode(self):
        """:meth:`kernel_mode`'s twin for the state groups' decode step:
        ``(mode, why_not)`` by the same flag and the recurrence's own
        ``*_state_step_gate`` over ``slot_state``."""
        from ..ops import attention_ops

        mode = attention_ops.paged_kernel_mode()
        if mode is None:
            return None, "n/a"
        gate, _, _ = self._state_step_forms()
        why_not = gate(interpret=(mode == "interpret"))
        if why_not is not None:
            return None, "gate: " + why_not
        return mode, None

    def _ssd_groups(self) -> int:
        """The groups that share ``B`` and ``C`` in Mamba-2's recurrence,
        which the tail's width tells (``heads x dv`` channels of ``x`` and
        ``2 x groups x dk`` of ``B`` and ``C``)."""
        h, dk, dv, _, width = self.slot_state
        return (width - h * dv) // (2 * dk)

    def state_shape(self) -> Tuple[int, int, int]:
        """A slot's state in one layer as the pool keeps it: ``[H, dk,
        dv]``, or Mamba-2's heads narrower than a lane tile side by side
        (``ops/pallas_kernels/ssd.state_shape``: 64 x [128, 64] is kept
        as 32 x [128, 128], 2 MiB and no padding lanes)."""
        h, dk, dv, _, _ = self.slot_state
        if self.recurrence == "ssd":
            from ..ops.pallas_kernels import ssd

            return ssd.state_shape(h, dk, dv, self._ssd_groups())
        return (h, dk, dv)

    def slot_states(self, state: Cache, gi: int, slot: int):
        """``[layers, H, dk, dv]`` float32: what ``slot`` keeps in state
        group ``gi``, in the MODEL's order whatever the pool's."""
        kept = state[self._key(gi, "s")][:, slot]
        if self.recurrence == "ssd":
            from ..ops.pallas_kernels import ssd

            return ssd.unpack_state(
                kept, self.slot_state[0] // kept.shape[-3])
        return kept

    def _state_step_forms(self):
        """``(gate, XLA form, kernel)`` of the state groups' recurrence.
        The gate takes ``interpret`` alone: Mamba-2's also needs the groups
        that share ``B`` and ``C``."""
        h, dk, dv, _, width = self.slot_state
        if self.recurrence == "ssd":
            from ..ops.pallas_kernels import ssd

            return (functools.partial(ssd.ssd_state_step_gate, h, dk, dv,
                                      self._ssd_groups()),
                    ssd.ssd_state_step_xla, ssd.ssd_state_step)
        from ..ops.pallas_kernels import kda

        return (functools.partial(kda.kda_state_step_gate, h, dk, dv),
                kda.kda_state_step_xla, kda.kda_state_step)

    def tail_step(self, state: Cache, layer: int, u, active):
        """One decode step of a state layer's convolution tail: ``u`` [B,
        width], this step's inputs. Returns ``(window [B, rows + 1, width],
        state)``: each slot's kept rows with ``u`` after them, oldest first
        (what a causal convolution of ``rows + 1`` taps reads), and the
        tails advanced by one row where ``active``; elsewhere as they
        were."""
        gi, li = self._where_state[layer]
        key = self._key(gi, "tail")
        tail = state[key][li]
        window = jnp.concatenate([tail, u[:, None].astype(tail.dtype)],
                                 axis=1)
        new = jnp.where(active[:, None, None], window[:, 1:], tail)
        return window, {**state, key: state[key].at[li].set(new)}

    def state_step(self, state: Cache, layer: int, *inputs_active):
        """One step of a state layer's recurrence for the slots that are
        ``active``, the last argument. ``"kda"``
        (ops/pallas_kernels/kda.py): ``q``/``k``/``a`` [B, H, dk], ``v``
        [B, H, dv], ``beta`` [B, H]. ``"ssd"`` (ssd.py): ``x`` [B, H, dv],
        ``b``/``c`` [B, G, dk], ``a`` [B, H]. Returns ``(o [B, H, dv]
        float32, state)``. By the kernel where :meth:`state_kernel_mode`
        arms it (the group's whole state buffer aliased in and out, an
        inactive slot's neither read nor written), else in plain XLA
        (computed for all, kept where active)."""
        gi, li = self._where_state[layer]
        key = self._key(gi, "s")
        mode, _ = self.state_kernel_mode()
        _, step_xla, step_kernel = self._state_step_forms()
        from ..ops import attention_ops

        # ``<recurrence>/step_calls.kernel|xla``: the form, once a traced call
        attention_ops._count("xla" if mode is None else "kernel",
                             self.recurrence + "/step_calls",
                             "cache_ops.state_step")
        if mode is None:
            o, s = step_xla(state[key], li, *inputs_active)
        else:
            o, s = step_kernel(state[key], li, *inputs_active,
                               interpret=(mode == "interpret"))
        return o, {**state, key: s}

    # -- prefill (one sequence) ----------------------------------------------
    def prompt_dest(self, pages) -> np.ndarray:
        """:meth:`prompt_dest_groups` of a one-group cache."""
        return self.prompt_dest_groups([pages])

    def prompt_dest_groups(self, group_pages, slot: int = 0) -> np.ndarray:
        """Host-side: the ``dest`` operand for ``write_prompt`` and
        ``set_page_table`` — every paged group's full page-table row, one
        after another (reserved pages first, rest parked on page 0; unused
        entries are never read or written), then ``slot`` for each state
        group: what a state group has of a slot is the slot itself."""
        paged = [gi for gi, g in enumerate(self.groups) if g.kind != STATE]
        if len(group_pages) != len(paged):
            raise ValueError("pages for %d groups, the cache has %d paged"
                             % (len(group_pages), len(paged)))
        rows = []
        for gi, pages in zip(paged, group_pages):
            row = np.zeros(self.group_pages_per_slot(gi), np.int32)
            row[:len(pages)] = np.asarray(pages, np.int32)
            if self.groups[gi].chunk is not None:
                # the first window's summaries wait after a WHOLE window's
                # pages: a request shorter than a window holds fewer
                kept = self._summaries(gi) // self.page_size
                first = self.groups[gi].window // self.page_size
                if len(pages) < first + kept:
                    row[len(pages) - kept:len(pages)] = 0
                    row[first:first + kept] = np.asarray(
                        pages[len(pages) - kept:], np.int32)
            rows.append(row)
        rows.append(np.full(len(self.groups) - len(paged), slot, np.int32))
        return np.concatenate(rows)

    def write_prompt(self, state: Cache, layer: int, *new_dest_length,
                     step=None) -> Cache:
        """What a model's prefill ``kept`` of ONE sequence for ``layer``,
        then ``dest`` (:meth:`prompt_dest_groups`'s row) and ``length``;
        the layer's group says what was kept. ``(k_new, v_new)`` [S,H,D]:
        positions >= length are dropped, and in a window group the
        positions that have already left the window (< length - window)
        too: the last ``min(length, window)`` land at their places in the
        ring. ``step``: the loop step that made these rows (a model with
        ``cache_steps`` hands a prompt's K and V a step, and the engine
        writes each). For a layer that has a state and NO pages ``(state,
        tail)``: :meth:`write_slot_state`'s. In a COMPACTING group
        ``(k_open, v_open, k_sum, v_sum)``: the ``min(S, window)`` rows
        from :func:`open_window_start` on (the window ``length`` leaves
        open is among them) and a pair a chunk of the bucket [S / chunk,
        H, D]: the closed windows' summaries go where attention reads
        them, the open window's finished ones where they wait, the open
        window's rows after the closed windows' summaries."""
        *new, dest, length = new_dest_length
        ps = self.page_size
        if layer not in self._where:
            return self.write_slot_state(state, layer, *new, dest)
        gi, li = self._where[layer]
        off = self._pt_start[gi]
        g = self.groups[gi]
        if g.chunk is not None:
            k_new, v_new, *summaries = new
            table = dest[off:self._pt_start[gi + 1]]
            kept, closed = self._summaries(gi), length // g.window
            i = jnp.arange(summaries[0].shape[0])
            view = jnp.where(i < kept * closed, i, i + g.window)
            flat = jnp.where(i < length // g.chunk,
                             self._pool_rows(table, view),
                             self._drop_row(gi))
            state = self._write_rows(state, layer, flat, *summaries)
            j = jnp.arange(k_new.shape[0]) + open_window_start(
                length, summaries[0].shape[0] * g.chunk, g.window)
            flat = jnp.where(
                (j >= closed * g.window) & (j < length),
                self._pool_rows(table, self._view_row(gi, j)),
                self._drop_row(gi))
            return self._write_rows(state, layer, flat, k_new, v_new)
        k_new, v_new = new
        s = k_new.shape[0]
        j = jnp.arange(s)
        keep = j < length
        idx = j // ps
        if self.groups[gi].window is not None:
            keep = keep & (j >= length - self.groups[gi].window)
            idx = idx % self.group_pages_per_slot(gi)
        flat = dest[off + idx] * ps + j % ps
        flat = jnp.where(keep, flat, self._drop_row(gi))
        return self._write_rows(state, layer, flat, k_new, v_new, step)

    def write_slot_state(self, state: Cache, layer: int, s_new, tail_new,
                         dest) -> Cache:
        """What a prompt LEAVES in a layer of a state group: its state
        ``s_new`` [H, dk, dv] and its convolution tail ``tail_new`` [rows,
        width], written whole to the slot ``dest``
        (:meth:`prompt_dest_groups`'s row) names."""
        gi, li = self._where_state[layer]
        off = self._pt_start[gi]
        sk, tk = self._key(gi, "s"), self._key(gi, "tail")
        s_new = s_new.astype(jnp.float32)
        if self.recurrence == "ssd":
            from ..ops.pallas_kernels import ssd

            s_new = ssd.pack_state(
                s_new, s_new.shape[0] // state[sk].shape[-3])
        return {**state,
                sk: state[sk].at[li, dest[off]].set(s_new),
                tk: state[tk].at[li, dest[off]].set(
                    tail_new.astype(state[tk].dtype))}

    # -- page migration ------------------------------------------------------
    def _page_rows(self, pages) -> np.ndarray:
        p = np.asarray(pages, np.int64)
        return (p[:, None] * self.page_size
                + np.arange(self.page_size)[None, :]).reshape(-1)

    def page_meta(self) -> dict:
        """Geometry a page payload must match to be importable here —
        embedded in every export, checked on every import."""
        return {"layout": self.layout, "n_layer": self.n_layer,
                "cache_steps": self.cache_steps,
                "n_head": self.n_head, "d_head": self.d_head,
                "page_size": self.page_size,
                "kv_dtype": jnp.dtype(self._storage_dtype()).name}

    def _check_meta(self, meta: dict, n_blobs: int, blobs) -> None:
        want = self.page_meta()
        got = {k: meta.get(k) for k in want}
        # a payload from before ``cache_steps`` has one cache layer a layer
        got["cache_steps"] = meta.get("cache_steps", 1)
        if got != want:
            raise ValueError("page payload geometry mismatch: %r != %r"
                             % (got, want))
        if len(blobs) != n_blobs:
            raise ValueError("page payload has %d blobs, expected %d"
                             % (len(blobs), n_blobs))

    def export_pages(self, state: Cache, pages):
        """Serialize ``pages`` (pool page ids) to ``(meta, blobs)``: raw
        C-order bytes of the K rows then the V rows, ``[n_layer *
        cache_steps, n_pages*page_size, H, D]`` each (which the ``[..,
        H*D]`` pool's rows are, byte for byte) — bit-exact, no float
        formatting."""
        self._single_group("page export")
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
        self._single_group("page import")
        self._check_meta(meta, 2, blobs)
        n = int(meta.get("n_pages", -1))
        if n != len(pages):
            raise ValueError("page payload has %d pages, caller reserved %d"
                             % (n, len(pages)))
        rows = self._page_rows(pages)
        dt = _dtype_by_name(meta["kv_dtype"])
        shp = (self.n_layer * self.cache_steps, len(rows), self.row_width)
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
    (resp. quadruples) the page capacity of the same byte budget.

    ``decode_attention`` always takes the gather path (``kernel_mode``
    says so): the ragged Pallas kernel reads raw pool rows and has no
    dequant stage, so the kernel dispatch is bypassed rather than fed
    garbage — both decode paths (fused decode scan and prefill-side
    attention) dequantize through ``context``.
    """

    layout = "paged-int8"

    def __init__(self, n_layer: int, n_head: int, d_head: int, slots: int,
                 max_ctx: int, page_size: int, num_pages: int,
                 k_scale: float, v_scale: float, dtype=jnp.float32,
                 groups: Optional[Sequence[CacheGroup]] = None,
                 q_per_kv: Union[int, Mapping[str, int]] = 1):
        super().__init__(n_layer, n_head, d_head, slots, max_ctx,
                         page_size, num_pages, dtype, groups=groups,
                         q_per_kv=q_per_kv)
        self._single_group("the int8 KV pool")
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

    def context(self, state: Cache, layer: int, step=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        # one cache layer a layer: the scales are [n_layer, num_pages]
        rows = self._context_rows(state["pt"])
        pages = rows // self.page_size  # page id per logical position
        ks = state["ks"][layer][pages][:, :, None, None].astype(self.dtype)
        vs = state["vs"][layer][pages][:, :, None, None].astype(self.dtype)
        return (self._gather(state["k"], layer, rows).astype(self.dtype) * ks,
                self._gather(state["v"], layer, rows).astype(self.dtype) * vs)

    def kernel_mode(self):
        return None, "gate: int8 pool (the kernel has no dequant stage)"

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


class LatentPagedCache(PagedKVCache):
    """The paged layout whose paged groups keep latent rows: a pool a
    group, ``"c"`` ``[layers of the group, num_pages*page_size,
    row_width]``, and no V pool. ONE LATENT group a ``window`` (None: pages
    for every position; W: a ring of the last W, the paged cache's own
    rule), each with its page table and its free list; after them there
    may be a state group (``groups``, ``slot_state``). Without
    ``groups`` the one latent group is every layer (Kimi-K2: the one-group
    case).

    A token's row is ``[c (rank) | kr (rope) | 0...]``: ``rank + rope``
    values (512 + 64 at DeepSeek-V3's sizes, against 64 heads x (192 + 128)
    of unabsorbed K and V), zero-padded to ``row_width``, the next whole
    lane tile (640), because the chip moves whole (8, 128) tiles and a
    buffer's last dimension is padded to them in memory whatever its
    declared size: the padding is stated here and not hidden in the
    layout. ``write_token``/``write_prompt`` take the unpadded row;
    ``decode_attention`` takes the ABSORBED query ``[B, H, rank + rope]``
    and returns ``[B, H, rank]`` (ops.attention_ops.mla_decode_attention or
    the kernel of ops/pallas_kernels/mla_attention.py, by the same flag
    as the paged kernel; a window group's call carries the kernel name
    ``mla_latent_decode_ring`` in a device trace). What needs a K and a V
    row (the int8 pool, page export and import, with them the prefix
    cache) is refused by the paged cache's own rule:
    nobody needs it yet."""

    layout = "paged-latent"
    _POOLS = ("c",)

    def __init__(self, n_layer: int, rank: int, rope: int, slots: int,
                 max_ctx: int, page_size: int, num_pages: int,
                 dtype=jnp.float32,
                 groups: Optional[Sequence[CacheGroup]] = None,
                 slot_state: Optional[Sequence[int]] = None,
                 index: Optional[Sequence[int]] = None):
        self.rank, self.rope = int(rank), int(rope)
        # (rows a block, lanes of an index key, blocks a query reads)
        self.index = None if index is None else tuple(int(n) for n in index)
        self.row_values = self.rank + self.rope
        width = -(-self.row_values // 128) * 128
        if groups is None:
            groups = [CacheGroup("latent", tuple(range(int(n_layer))), None,
                                 int(num_pages), LATENT)]
        paged = [g for g in groups if g.kind != STATE]
        if {g.kind for g in paged} != {LATENT} \
                or len({g.window for g in paged}) != len(paged):
            raise ValueError(
                "a latent cache has ONE latent group a window (None: pages "
                "for every position; W: a ring of the last W), and state "
                "groups after them, got %s"
                % [(g.name, g.kind, g.window) for g in groups])
        super().__init__(n_layer, 1, width, slots, max_ctx, page_size,
                         groups[0].num_pages, dtype, groups=groups,
                         slot_state=slot_state)
        if self.index is not None:
            kpool = self.index[0]
            if len(paged) != 1 or paged[0].window is not None \
                    or self.page_size % (2 * kpool) or 8 % kpool:
                raise ValueError(
                    "an index is kept beside ONE latent group of pages: a "
                    "key a ROW (blocks of 1: whole 8-row tiles a page), or "
                    "a pooled key a block of rows, whole blocks a page and "
                    "whole blocks an 8-row tile (the least the chip copies "
                    "of the rows a block chooses), pairs of tiles a page: "
                    "got groups %s, page_size=%d, blocks of %d rows"
                    % ([(g.name, g.window) for g in paged], self.page_size,
                       kpool))

    # -- the index beside a latent group's rows -------------------------------
    @property
    def page_table_len(self) -> int:
        """With an index, a slot's ``dest`` row ends with the slot itself:
        the open block's keys are the slot's, not a page's."""
        return self._pt_start[-1] + self._open_block

    @property
    def _open_block(self) -> bool:
        """Whether a slot keeps the raw keys of a block still open: an
        index of POOLED keys does, an index of a key a row has no open
        block."""
        return self.index is not None and self.index[0] > 1

    def group_run_pages(self, gi: int) -> int:
        """A latent group's pages come in aligned RUNS, as long as its
        geometry lets the kernels copy them whole: the largest of the
        latent kernel's ``RUN_PAGES``, its half, ... that divides the
        slot's table (a window group's ring) and the kernel's wave; 1 if
        none does. Every kernel call over the group's table is handed it
        as ``copy_pages``."""
        from ..ops.pallas_kernels.mla_attention import run_pages

        if self.groups[gi].kind != LATENT:
            return 1
        return run_pages(self.page_size, self.group_pages_per_slot(gi))

    def prompt_dest_groups(self, group_pages, slot: int = 0) -> np.ndarray:
        dest = super().prompt_dest_groups(group_pages, slot)
        if not self._open_block:
            return dest
        return np.concatenate([dest, np.full(1, slot, np.int32)])

    def init_state(self) -> Cache:
        state = super().init_state()
        if self.index is not None:
            kpool, lanes, _ = self.index
            g = self.groups[0]
            # a PAGE's pooled keys side by side in one row (4 x 128 lanes
            # at a page of 16 rows): the page table gathers them as it
            # stands, a row a page
            # (a key a ROW keeps the page's rows as an axis, a page one
            # tile of its own: the row is then written where its latent
            # row is, and a page is a thing a kernel can copy)
            state["ik"] = jnp.zeros(
                (len(g.layers), g.num_pages, self.page_size, lanes)
                if kpool == 1 else
                (len(g.layers), g.num_pages,
                 self.page_size // kpool * lanes), self.dtype)
            if self._open_block:
                state["it"] = jnp.zeros(
                    (len(g.layers), self.slots, kpool - 1, lanes),
                    self.dtype)
        return state

    def index_bytes(self, state: Cache) -> int:
        """The pooled index keys and the open blocks' raw keys as stored."""
        return int(sum(state[k].nbytes for k in ("ik", "it") if k in state))

    def write_index(self, state: Cache, layer: int, key_new, pos, active
                    ) -> Cache:
        """One decode step of a layer's index: ``key_new`` [B, lanes], the
        index key of position ``pos[b]``. With a key a ROW it is written
        to its row's lanes of its page's row in ``"ik"`` as it is. With
        pooled blocks: a row that is not its block's
        last joins the slot's open block (``"it"``); the block's LAST row
        closes it: the mean of the block's keys, in float32, is written to
        the block's lanes of its page's row in ``"ik"`` (through the page
        table) and the raw keys are dropped (overwritten as the next block
        opens). Inactive slots write nothing."""
        kpool, lanes, _ = self.index
        _, li = self._where[layer]
        pt = state["pt"]
        b_idx = jnp.arange(pt.shape[0])
        if kpool == 1:
            # a key a ROW: no block is ever open, the key goes to its
            # row's place in its page as it is
            page = jnp.where(active, pt[b_idx, pos // self.page_size],
                             state["ik"].shape[1])
            return {**state, "ik": state["ik"].at[
                li, page, pos % self.page_size].set(
                    key_new.astype(self.dtype), mode="drop")}
        j = pos % kpool
        tail = state["it"][li]                           # [B, kpool - 1, L]
        pooled = ((jnp.sum(tail.astype(jnp.float32), axis=1)
                   + key_new.astype(jnp.float32)) / kpool).astype(self.dtype)
        per_page = self.page_size // kpool
        page = pt[b_idx, pos // self.page_size]
        row = state["ik"][li, page]                      # [B, per_page * L]
        mine = (jnp.arange(per_page * lanes) // lanes)[None, :] \
            == ((pos % self.page_size) // kpool)[:, None]
        row = jnp.where(mine, jnp.tile(pooled, (1, per_page)), row)
        dest = jnp.where(active & (j == kpool - 1), page,
                         state["ik"].shape[1])
        opened = jnp.where(
            (active & (j < kpool - 1))[:, None, None]
            & (jnp.arange(kpool - 1)[None, :, None] == j[:, None, None]),
            key_new[:, None].astype(tail.dtype), tail)
        return {**state,
                "ik": state["ik"].at[li, dest].set(row, mode="drop"),
                "it": state["it"].at[li].set(opened)}

    def _write_index_prompt(self, state: Cache, layer: int, pooled, tail,
                            dest, length) -> Cache:
        """A prompt's index: ``pooled`` [S / kpool, lanes], a key a block,
        written a page's row at a time to every page that holds a closed
        block (a block of such a page that is still open holds whatever
        the bucket's padding pooled to: it is not scored before it closes,
        and closing writes it), and ``tail`` [kpool - 1, lanes], the raw
        keys of the block ``length`` leaves open, written whole to the
        slot ``dest`` ends with."""
        kpool, lanes, _ = self.index
        _, li = self._where[layer]
        per_page = self.page_size // kpool
        rows = pooled.astype(self.dtype).reshape(
            (-1,) + state["ik"].shape[2:])      # a page's keys together
        p = jnp.arange(rows.shape[0])
        flat = jnp.where(p * self.page_size + kpool <= length, dest[p],
                         state["ik"].shape[1])
        state = {**state,
                 "ik": state["ik"].at[li, flat].set(rows, mode="drop")}
        if tail is None:        # a key a row: no block is left open
            return state
        return {**state, "it": state["it"].at[li, dest[-1]].set(
            tail.astype(self.dtype))}

    def index_scores(self, state: Cache, layer: int, q_idx, w_idx, ctx_len,
                     active, score_dtype=jnp.float32):
        """The index scores of one decode step: ``q_idx`` [B, Hi, lanes]
        the index queries, ``w_idx`` [B, Hi] float32 their weights.
        Returns ``(scores [B, blocks a slot] float32, closed [B])``: ``I(t,
        b) = sum_j w_j ReLU(q_j . K_b)`` for each of the slot's CLOSED
        blocks before the one position ``ctx_len - 1`` lies in (with a key
        a ROW: for every row of the context, that position's among them),
        the masking constant elsewhere (and everywhere in a slot that is
        not ``active``); ``closed`` counts them. The keys are gathered a
        PAGE at a time, by the page table as it stands; a key a row at
        float32 scores by the ``dsa_index_scores`` kernel where
        :meth:`index_kernel_mode` arms it (the live pages alone, the
        heads' products never in HBM). ``score_dtype``:
        ``dsa_index_scores``'s."""
        from ..ops import attention_ops

        kpool = self.index[0]
        _, li = self._where[layer]
        # a key a ROW scores every row of the context, the position's own
        # among them: nothing is forced in, so it has to earn its place
        closed = jnp.where(active, ctx_len if kpool == 1
                           else (ctx_len - 1) // kpool, 0)
        if kpool == 1 and jnp.dtype(score_dtype) == jnp.float32:
            mode, _ = self.index_kernel_mode()
            if mode is not None:
                from ..ops.pallas_kernels import dsa_index

                return dsa_index.dsa_index_scores_paged(
                    q_idx, w_idx, state["ik"], state["pt"], closed, layer=li,
                    interpret=(mode == "interpret"),
                    copy_pages=self.group_run_pages(0)), closed
        keys = state["ik"][li, state["pt"]]
        if kpool == 1:          # [B, pages, rows a page, L]: a key a row
            keys = keys.reshape(keys.shape[0], -1, keys.shape[-1])
        return attention_ops.dsa_index_scores(q_idx, w_idx, keys, closed,
                                              score_dtype), closed

    def index_kernel_mode(self):
        """:meth:`kernel_mode`'s twin for the index scores of a key a row:
        the same flag, ``dsa_index_gate`` over the index's geometry (an
        index of pooled blocks is scored in XLA: a page owns too few of
        its keys for the chip to copy them a page at a time)."""
        from ..ops import attention_ops
        from ..ops.pallas_kernels.dsa_index import dsa_index_gate

        mode = attention_ops.paged_kernel_mode()
        if mode is None or self.index is None or self.index[0] != 1:
            return None, "n/a"
        why_not = dsa_index_gate(self.dtype, self.index[1], self.page_size,
                                 self.max_ctx,
                                 interpret=(mode == "interpret"))
        if why_not is not None:
            return None, "gate: " + why_not
        return mode, None

    def sparse_kernel_mode(self):
        """:meth:`kernel_mode`'s twin for the sparse read of pooled
        blocks: the same flag, the latent kernel's gate at the 8-row tile
        it copies (a choice of single rows goes by the pages as they
        stand: :meth:`rows_decode_attention` asks :meth:`kernel_mode`)."""
        from ..ops import attention_ops
        from ..ops.pallas_kernels.mla_attention import (SPARSE_TILE,
                                                        mla_decode_gate)

        mode = attention_ops.paged_kernel_mode()
        if mode is None:
            return None, "n/a"
        why_not = mla_decode_gate(self.dtype, self.row_width, self.rank,
                                  SPARSE_TILE, interpret=(mode == "interpret"),
                                  sparse=True)
        if why_not is not None:
            return None, "gate: " + why_not
        return mode, None

    def sparse_decode_attention(self, state: Cache, layer: int, q, chosen,
                                ctx_len, active, sm_scale: float = 1.0):
        """Decode attention over the CHOSEN blocks only: ``q`` [B, H,
        rank + rope] absorbed, ``chosen`` [B, blocks a slot] bool (the
        selection: closed blocks, and the block position ``ctx_len - 1``
        lies in; single rows are read by :meth:`rows_decode_attention`).
        Returns ``(o [B, H, rank], rows_read [B])``. The chip
        copies 8 rows at the least (a bfloat16 tile's rows in HBM), so the
        read goes by 8-row TILES: the tiles that hold a chosen block, in
        ascending order, through a second, shorter table a step, and a row
        mask that keeps the chosen blocks' rows at or before the position.
        By the latent kernel under the name ``dsa_sparse_decode`` where
        :meth:`sparse_kernel_mode` arms it, else by an XLA gather of the
        same tiles."""
        from ..ops import attention_ops
        from ..ops.pallas_kernels import mla_attention as _mla

        kpool, _, topk = self.index
        _, li = self._where[layer]
        pt = state["pt"]
        b = pt.shape[0]
        tile = _mla.SPARSE_TILE
        per_tile, per_page = tile // kpool, self.page_size // tile
        chosen = chosen & active[:, None]
        by_tile = chosen.reshape(b, -1, per_tile)
        n_tiles = by_tile.shape[1]
        big = jnp.int32(n_tiles)
        # a query reads at most ``topk`` blocks: so many tiles at the most
        order = jnp.sort(jnp.where(jnp.any(by_tile, axis=-1),
                                   jnp.arange(n_tiles, dtype=jnp.int32),
                                   big), axis=-1)[:, :topk]
        held = order < big
        tiles = jnp.where(held, order, 0)
        table = jnp.where(
            held, pt[jnp.arange(b)[:, None], tiles // per_page] * per_page
            + tiles % per_page, 0)
        rows = tiles[:, :, None] * tile + jnp.arange(tile)
        valid = jnp.repeat(
            jnp.take_along_axis(by_tile, tiles[:, :, None], axis=1),
            kpool, axis=-1) & held[:, :, None] \
            & (rows < ctx_len[:, None, None])
        valid = valid.reshape(b, -1)
        length = jnp.sum(held, axis=-1).astype(jnp.int32) * tile
        q = jnp.pad(q, ((0, 0), (0, 0), (0, self.row_width - q.shape[-1])))
        mode, _ = self.sparse_kernel_mode()
        read = jnp.sum(valid, axis=-1).astype(jnp.int32)
        if mode is not None:
            return _mla.mla_paged_decode(
                q, state["c"], table, length, page_size=tile, rank=self.rank,
                layer=li, sm_scale=sm_scale, row_valid=valid,
                interpret=(mode == "interpret"),
                name=_mla.SPARSE_KERNEL_NAME), read
        pool_rows = (table[:, :, None] * tile + jnp.arange(tile)
                     ).reshape(b, -1)
        return attention_ops.mla_decode_attention(
            q, state["c"][li, pool_rows], length, self.rank,
            sm_scale=sm_scale, row_valid=valid), read

    def rows_decode_attention(self, state: Cache, layer: int, q, chosen,
                              ctx_len, active, sm_scale: float = 1.0):
        """Decode attention over the CHOSEN ROWS only (an index of a key a
        row): ``q`` [B, H, rank + rope] absorbed, ``chosen`` [B, rows a
        slot] bool (``ops.attention_ops.dsa_select_rows``: at most ``topk``
        a slot, every one below the slot's length). Returns ``(o [B, H,
        rank], rows_read [B])``; ``rows_read`` counts the rows CHOSEN,
        whatever was copied. Where :meth:`kernel_mode` arms it: the latent
        kernel's wave over the slot's WHOLE context through the page table
        as it stands, the choice as its ``row_valid`` mask, under the name
        ``dsa_sparse_decode``: every page is copied, a row that was not
        chosen meets the masking constant. That is form (b) of the two
        exact ones PERF.md section 6 (PR 62) measured: 873 us a layer at
        32 slots of 7.4k rows and 128 heads, its products and not its
        bytes the bound (3.7 ns a row of context). Form (a), a read of the
        chosen rows alone, has no Pallas kernel: the chip's compiler takes
        no copy of fewer than 8 rows out of HBM, of 32-bit words as of
        bfloat16, and 8-row tiles hold a chosen row almost everywhere once
        a few thousand single rows are chosen; as XLA's gather it costs a
        descriptor a row (15 ns: 1.0 ms a layer for 65,536 rows before
        the attention over them) and wins only past some 9k rows a slot,
        the edge of this envelope. Off the kernel the chosen rows ARE
        gathered (``dsa_chosen_rows`` makes their table): the CPU tests'
        path."""
        from ..ops import attention_ops

        topk = self.index[2]
        _, li = self._where[layer]
        live = _live_len(ctx_len, active)
        chosen = chosen & (jnp.arange(chosen.shape[1])[None, :]
                           < live[:, None])
        read = jnp.sum(chosen, axis=-1).astype(jnp.int32)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, self.row_width - q.shape[-1])))
        mode, _ = self.kernel_mode()
        if mode is not None:
            from ..ops.pallas_kernels import mla_attention as _mla

            return _mla.mla_paged_decode(
                q, state["c"], state["pt"], live, page_size=self.page_size,
                rank=self.rank, layer=li, sm_scale=sm_scale,
                row_valid=chosen, interpret=(mode == "interpret"),
                name=_mla.SPARSE_KERNEL_NAME,
                copy_pages=self.group_run_pages(0)), read
        rows, held = attention_ops.dsa_chosen_rows(chosen, topk)
        pool_rows = self._pool_rows(state["pt"], rows.T).T
        return attention_ops.mla_rows_attention(
            q, state["c"][li, pool_rows], held, self.rank,
            sm_scale=sm_scale), read

    def write_token(self, state: Cache, layer: int, row_new, pos, active
                    ) -> Cache:
        """``row_new`` [B, rank + rope] written at position ``pos[b]`` of
        slot b; inactive slots dropped."""
        return super().write_token(state, layer, row_new, None, pos, active)

    def write_prompt(self, state: Cache, layer: int, *new_dest_length
                     ) -> Cache:
        """A latent layer: ``(row_new [S, rank + rope], dest, length)`` of
        ONE sequence, positions >= ``length`` dropped; with an index,
        ``(row_new, pooled keys [S / kpool, lanes], open block's keys
        [kpool - 1, lanes], dest, length)``, or, of a key a row,
        ``(row_new, index keys [S, lanes], dest, length)``. A state layer:
        ``(state, tail, dest, length)``, the paged cache's."""
        if len(new_dest_length) == 5:    # a latent layer with an index
            row_new, pooled, tail, dest, length = new_dest_length
            state = self._write_index_prompt(state, layer, pooled, tail,
                                             dest, length)
            new_dest_length = (row_new, dest, length)
        elif len(new_dest_length) == 4 and layer in self._where \
                and self.index is not None:    # ... of a key a row
            row_new, keys, dest, length = new_dest_length
            state = self._write_index_prompt(state, layer, keys, None, dest,
                                             length)
            new_dest_length = (row_new, dest, length)
        if len(new_dest_length) == 3:
            row_new, dest, length = new_dest_length
            new_dest_length = (row_new, None, dest, length)
        return super().write_prompt(state, layer, *new_dest_length)

    def _write_rows(self, state: Cache, layer: int, dest, row_new, _v,
                    _step=None) -> Cache:
        """The paged cache's destinations, one padded row each."""
        gi, li = self._where[layer]
        key = self._key(gi, "c")
        rows = row_new.reshape(-1, self.row_values).astype(self.dtype)
        rows = jnp.pad(rows, ((0, 0), (0, self.row_width - self.row_values)))
        return {**state,
                key: state[key].at[li, dest].set(rows, mode="drop")}

    def context(self, state: Cache, layer: int) -> jnp.ndarray:
        """Every slot's rows of ``layer`` in page-table order: ``[slots,
        rows, row_width]`` with ``rows`` = ``max_ctx``, or the ring's
        length in a window group (the XLA-gather path)."""
        gi, li = self._where[layer]
        return state[self._key(gi, "c")][
            li, self._context_rows(state[self._key(gi, "pt")])]

    def ring_bytes(self, state: Cache) -> int:
        """The pools of the window groups as stored: what the rings hold
        whatever the contexts' lengths."""
        return int(sum(state[self._key(gi, "c")].nbytes
                       for gi, g in enumerate(self.groups)
                       if g.kind == LATENT and g.window is not None))

    def _kernel_gate(self, interpret: bool) -> Optional[str]:
        from ..ops.pallas_kernels.mla_attention import mla_decode_gate

        return mla_decode_gate(self.dtype, self.row_width, self.rank,
                               self.page_size, interpret=interpret)

    def kernel_folds(self) -> Dict[str, str]:
        return {}   # the latent kernels have one fold

    def decode_attention(self, state: Cache, layer: int, q, ctx_len,
                         active, sm_scale: float = 1.0) -> jnp.ndarray:
        """``q`` [B, H, rank + rope], absorbed; [B, H, rank] out, over each
        slot's LIVE length (:func:`_live_len`), in a window group over
        ``min(that, window)`` rows of its ring."""
        from ..ops import attention_ops

        gi, li = self._where[layer]
        length = self._group_len(gi, _live_len(ctx_len, active))
        q = jnp.pad(q, ((0, 0), (0, 0), (0, self.row_width - q.shape[-1])))
        mode, _ = self.kernel_mode()
        if mode is not None:
            from ..ops.pallas_kernels import mla_attention as _mla

            return _mla.mla_paged_decode(
                q, state[self._key(gi, "c")], state[self._key(gi, "pt")],
                length, page_size=self.page_size, rank=self.rank, layer=li,
                sm_scale=sm_scale, interpret=(mode == "interpret"),
                name=(_mla.KERNEL_NAME if self.groups[gi].window is None
                      else _mla.RING_KERNEL_NAME),
                copy_pages=self.group_run_pages(gi))
        return attention_ops.mla_decode_attention(
            q, self.context(state, layer), length, self.rank,
            sm_scale=sm_scale)


class ContiguousKVCache(_KVCacheBase):
    layout = "contiguous"

    def init_state(self) -> Cache:
        shp = (self.n_layer * self.cache_steps, self.slots, self.max_ctx,
               self.n_head, self.d_head)
        return {"k": jnp.zeros(shp, self.dtype),
                "v": jnp.zeros(shp, self.dtype)}

    def write_token(self, state: Cache, layer: int, k_new, v_new, pos,
                    active, step=None) -> Cache:
        b_idx = jnp.arange(pos.shape[0])
        pos_c = jnp.where(active, pos, self.max_ctx)  # OOB -> dropped
        layer = self._pool_layer(layer, step)
        return {
            **state,
            "k": state["k"].at[layer, b_idx, pos_c].set(k_new, mode="drop"),
            "v": state["v"].at[layer, b_idx, pos_c].set(v_new, mode="drop"),
        }

    def context(self, state: Cache, layer: int, step=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        layer = self._pool_layer(layer, step)
        return state["k"][layer], state["v"][layer]

    def decode_attention(self, state: Cache, layer: int, q, ctx_len,
                         active, sm_scale: float = 1.0, step=None
                         ) -> jnp.ndarray:
        """Dense layout has no gather to fuse away — always the XLA path
        (the parity yardstick the paged kernel is measured against), over
        the same live lengths as the paged layout."""
        from ..ops import attention_ops

        ctx_k, ctx_v = self.context(state, layer, step)
        return attention_ops.decode_attention(
            q, ctx_k, ctx_v, _live_len(ctx_len, active), sm_scale=sm_scale)

    def rows_read(self, ctx_len, active) -> Dict[str, jnp.ndarray]:
        """The paged layout's count, for the one group of every position
        that this layout is."""
        return {"attn_rows_read.global": jnp.sum(
            _live_len(ctx_len, active)).astype(jnp.int32)}

    def prompt_dest(self, slot: int) -> np.int32:
        return np.int32(slot)

    def write_prompt(self, state: Cache, layer: int, k_new, v_new, dest,
                     length, step=None) -> Cache:
        s = k_new.shape[0]
        j = jnp.arange(s)
        pos_c = jnp.where(j < length, j, self.max_ctx)
        layer = self._pool_layer(layer, step)
        return {
            **state,
            "k": state["k"].at[layer, dest, pos_c].set(k_new, mode="drop"),
            "v": state["v"].at[layer, dest, pos_c].set(v_new, mode="drop"),
        }

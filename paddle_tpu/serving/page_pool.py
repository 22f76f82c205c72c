"""Fixed-size KV-cache page allocator (the vLLM/"Ragged Paged Attention"
block pool, host side).

HBM for the KV cache is carved into ``num_pages`` pages of ``page_size``
token positions each (every page spans all layers/heads — the device
arrays carry those axes). The pool hands out page INDICES; the device-side
arrays never move. Allocation is all-or-nothing: a request either gets its
full reservation or a :class:`PagePoolExhausted` (a
:class:`~.request.BackpressureError`) and the scheduler keeps it queued —
exhaustion degrades to queueing, never to a crash or a mid-decode OOM.

The engine reserves a request's WORST-CASE need (prompt + max_new_tokens)
at admission, so a running request can never hit exhaustion mid-decode —
the same preallocation posture as watermark-based vLLM scheduling, chosen
here over on-demand growth because it keeps the decode step free of
allocation control flow.

A page RUN (``run_pages`` R; PR 64). Because a reservation is taken whole
and given back whole, the pool of a LATENT cache group hands its pages out
in aligned runs: R pages side by side in the pool, the first a multiple of
R, ascending. ``alloc(n)`` gives ``ceil(n / R)`` whole runs (at most R - 1
pages more than were asked for, the mechanism's whole cost:
``serving/pages_padding.<group>``), in table order, so a slot's page-table
entry ``R g`` starts ``R * page_size`` consecutive pool rows whatever was
admitted and retired before. THE POOL guarantees that (and the engine
checks it where it sets a slot's table); the kernels that walk a latent
table (``ops/pallas_kernels/mla_attention.py``, ``dsa_index.py``) RELY on
it and copy a run with one descriptor where they paid one a page. The
page table is still one entry a page and nothing else reads it
differently. R = 1 is the pool of single pages every KV group keeps (it
shares prefixes page by page: ``prefix_cache.py``): the same class, one
page a run, call for call what it was. Pages past the last whole run of a
pool whose size is no multiple of R are never handed out.
"""

from __future__ import annotations

from typing import Dict, List

from . import metrics as _sm
from .request import BackpressureError

__all__ = ["PagePool", "PagePoolExhausted"]


class PagePoolExhausted(BackpressureError):
    """Not enough free pages for the requested reservation."""


class PagePool:
    """One free list of runs of ``run_pages`` pages (1: single pages). An
    engine holds one a cache group (``name``: the group's; every group
    publishes ``serving/pages_used.<name>``, ``serving/page_run_pages
    .<name>`` and ``serving/pages_padding.<name>``, the first also the two
    gauges a one-group engine always had)."""

    def __init__(self, num_pages: int, page_size: int,
                 name: str = "global", primary: bool = True,
                 run_pages: int = 1):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be >= 1")
        if not 1 <= int(run_pages) <= num_pages:
            raise ValueError("a run of %d pages in a pool of %d"
                             % (run_pages, num_pages))
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.run_pages = int(run_pages)
        self.name = str(name)
        self._primary = bool(primary)
        self._used_gauge = _sm.pages_used(self.name)
        self._padding_gauge = _sm.pages_padding(self.name)
        _sm.page_run_pages(self.name).set(self.run_pages)
        r = self.run_pages
        # LIFO free list of runs, each by its first page: recently-freed
        # (cache-warm) pages are reused first
        self._free: List[int] = list(range(
            (self.num_pages // r - 1) * r, -1, -r))
        self._free_set = set(self._free)
        # pages handed out beyond those asked for, by the first page of
        # the run that holds them (an allocation's last)
        self._padding: Dict[int, int] = {}
        self._update_gauges()

    # -- accounting -----------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Pages the pool can hand out: its whole runs."""
        return self.num_pages // self.run_pages * self.run_pages

    @property
    def num_free(self) -> int:
        return len(self._free) * self.run_pages

    @property
    def num_used(self) -> int:
        return self.capacity - self.num_free

    @property
    def num_padding(self) -> int:
        """Of the pages in use, those nobody asked for: what rounding
        reservations up to whole runs costs right now."""
        return sum(self._padding.values())

    @property
    def utilization(self) -> float:
        return self.num_used / self.num_pages

    def rounded(self, n: int) -> int:
        """Pages :meth:`alloc` hands out for ``n``: whole runs."""
        return -(-int(n) // self.run_pages) * self.run_pages

    def whole_runs(self, pages: List[int]) -> bool:
        """Whether ``pages`` are whole aligned ascending runs on end: what
        :meth:`alloc` hands out, and what a kernel that copies a run from
        its first table entry has to be given."""
        r = self.run_pages
        return len(pages) % r == 0 and all(
            pages[at] % r == 0
            and list(pages[at:at + r]) == list(range(pages[at], pages[at] + r))
            for at in range(0, len(pages), r))

    def pages_needed(self, total_tokens: int) -> int:
        """Pages a reservation covering ``total_tokens`` cache positions
        takes: whole runs."""
        return self.rounded(-(-int(total_tokens) // self.page_size))

    def _update_gauges(self):
        self._used_gauge.set(self.num_used)
        self._padding_gauge.set(self.num_padding)
        if self._primary:
            _sm.PAGES_IN_USE.set(self.num_used)
            _sm.PAGE_POOL_UTILIZATION.set(self.utilization)

    # -- alloc/free -----------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Reserve ``n`` pages atomically, as the whole runs that cover
        them, their pages in table order; raises
        :class:`PagePoolExhausted` (leaving the pool untouched) when fewer
        are free."""
        n = int(n)
        if n < 0:
            raise ValueError("cannot allocate %d pages" % n)
        from ..reliability import faults as _faults

        spec = _faults.fire("page_pool.alloc")
        if spec is not None and spec.kind == "exhausted":
            # chaos drill: behave exactly like a real exhaustion — the
            # caller's backpressure path must absorb it
            raise PagePoolExhausted(
                "page pool exhausted (injected): need %d pages of %d — "
                "request stays queued until pages retire"
                % (n, self.num_pages))
        r, take = self.run_pages, self.rounded(n)
        if take > self.num_free:
            raise PagePoolExhausted(
                "page pool exhausted: need %d pages, %d free of %d "
                "(page_size=%d) — request stays queued until pages retire"
                % (take, self.num_free, self.num_pages, self.page_size))
        runs = [self._free.pop() for _ in range(take // r)]
        self._free_set.difference_update(runs)
        if take > n:
            self._padding[runs[-1]] = take - n
        self._update_gauges()
        return [first + i for first in runs for i in range(r)]

    def free(self, pages: List[int]) -> None:
        """Take whole runs back, each as its pages in order; a page outside
        the pool, a partial or misaligned run and a double free are
        refused."""
        r = self.run_pages
        pages = [int(p) for p in pages]
        for at in range(0, len(pages), r):
            run = pages[at:at + r]
            first = run[0]
            if not 0 <= first < self.capacity:
                raise ValueError("freeing page %d outside pool of %d"
                                 % (first, self.num_pages))
            if not self.whole_runs(run):
                raise ValueError(
                    "freeing %s: not a whole aligned run of %d pages"
                    % (run, r))
            if first in self._free_set:
                raise ValueError("double free of page %d" % first)
            self._free.append(first)
            self._free_set.add(first)
            self._padding.pop(first, None)
        self._update_gauges()

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
"""

from __future__ import annotations

from typing import List

from . import metrics as _sm
from .request import BackpressureError

__all__ = ["PagePool", "PagePoolExhausted"]


class PagePoolExhausted(BackpressureError):
    """Not enough free pages for the requested reservation."""


class PagePool:
    """One free list. An engine holds one a cache group (``name``: the
    group's; every group publishes ``serving/pages_used.<name>``, the
    first also the two gauges a one-group engine always had)."""

    def __init__(self, num_pages: int, page_size: int,
                 name: str = "global", primary: bool = True):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.name = str(name)
        self._primary = bool(primary)
        self._used_gauge = _sm.pages_used(self.name)
        # LIFO free list: recently-freed (cache-warm) pages are reused first
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._free_set = set(self._free)
        self._update_gauges()

    # -- accounting -----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def utilization(self) -> float:
        return self.num_used / self.num_pages

    def pages_needed(self, total_tokens: int) -> int:
        """Pages covering ``total_tokens`` cache positions."""
        return -(-int(total_tokens) // self.page_size)

    def _update_gauges(self):
        self._used_gauge.set(self.num_used)
        if self._primary:
            _sm.PAGES_IN_USE.set(self.num_used)
            _sm.PAGE_POOL_UTILIZATION.set(self.utilization)

    # -- alloc/free -----------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Reserve ``n`` pages atomically; raises :class:`PagePoolExhausted`
        (leaving the pool untouched) when fewer than ``n`` are free."""
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
        if n > len(self._free):
            raise PagePoolExhausted(
                "page pool exhausted: need %d pages, %d free of %d "
                "(page_size=%d) — request stays queued until pages retire"
                % (n, len(self._free), self.num_pages, self.page_size))
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        self._update_gauges()
        return pages

    def free(self, pages: List[int]) -> None:
        for p in pages:
            p = int(p)
            if not 0 <= p < self.num_pages:
                raise ValueError("freeing page %d outside pool of %d"
                                 % (p, self.num_pages))
            if p in self._free_set:
                raise ValueError("double free of page %d" % p)
            self._free.append(p)
            self._free_set.add(p)
        self._update_gauges()

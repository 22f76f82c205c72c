"""Autoregressive decode driver: continuous batching over a paged KV-cache.

The device-resident serving loop the ROADMAP's item-1 gap called for. One
:class:`ServingEngine` owns:

* a :class:`~.scheduler.Scheduler` (bounded queue → fixed batch slots,
  continuous in-flight admission),
* a :class:`~.page_pool.PagePool` + :class:`~.kv_cache.PagedKVCache` (or
  the :class:`~.kv_cache.ContiguousKVCache` reference layout),
* AOT-compiled step functions built through ``executor.aot_compile`` —
  ONE prefill executable per prompt bucket (power-of-two padded, so a
  ragged prompt stream compiles O(log max_seq) programs, the same
  bounded-specialization idea as the Predictor's batch buckets) and ONE
  decode executable per fuse length whose state (KV pages, page tables,
  slot occupancy, lengths) never leaves the device between steps — the
  serving twin of ``Executor.run_steps``'s stack-and-scan fusion, with
  retirement/admission decisions surfacing only at chunk boundaries.

Observability rides PR 1/5's monitor: ``serving/*`` counters + latency
histograms (serving.metrics), and the crash flight recorder captures the
in-flight batch spec on any decode failure (``PADDLE_TPU_FLIGHT_DIR``).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile_cache as _cc
from ..executor import _safe_flight_dump, aot_compile
from ..monitor import (device as _dev, slo as _slo, telemetry as _telemetry,
                       tracer as _tr)
from ..reliability import faults as _faults
from . import metrics as _sm
from . import trace as _trace
from .kv_cache import (KV, LATENT, STATE, CacheGroup, ContiguousKVCache,
                       Int8PagedKVCache, LatentPagedCache, PagedKVCache)
from .page_pool import PagePool, PagePoolExhausted
from .prefix_cache import PrefixCache
from .request import (FAILED, FINISHED, REJECTED, TIMEOUT, DrainingError,
                      Request)
from .scheduler import Scheduler


def _span(name: str, **args) -> _tr.span:
    """A span on the engine's thread (README: the program's spans). The
    category keeps it apart from the per-request tracks of serving/trace.py,
    which read the same list by ``cat``."""
    return _tr.span(name, cat="engine", args=args or None)

__all__ = ["ServingConfig", "ServingEngine"]


def _pow2_buckets(lo: int, hi: int) -> tuple:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


def _sample_tokens(logits, temp, top_k, seed, position, live=None):
    """Device-side per-slot token selection, shared by the prefill
    executable, the fused decode scan and the resume scan.

    ``logits`` [B,V]; ``temp``/``top_k``/``seed``/``position`` [B];
    ``live`` [B] bool, every row when None.
    ``temp[b] == 0`` returns EXACTLY ``argmax(logits[b])`` — the greedy
    path's own computation, selected by ``where``, so greedy requests are
    bit-identical whether or not sampling requests share the batch.
    ``temp[b] > 0`` draws via the Gumbel-argmax trick over the
    temperature-scaled logits, restricted to the ``top_k[b]`` largest when
    positive (threshold at the k-th sorted logit; ties below it are kept,
    matching the usual top-k convention of "never a logit SMALLER than the
    k-th"). The draw is keyed ``fold_in(PRNGKey(seed[b]), position[b])`` —
    a pure function of the request's own seed and the absolute context
    position of the token being consumed, so the stream is reproducible
    across ``decode_fuse`` widths and a slot re-admitted to a new request
    (new seed) can never replay the previous tenant's draws.

    A step pays only for what its LIVE rows ask for, read from the
    arguments on the device (one ``lax.switch`` of three tiers in one
    executable): (greedy) no live row with ``temp > 0`` — the argmax and
    nothing else; (draw) some, none of them with ``top_k > 0`` — the
    scaling and the Gumbel draw, no sort (the threshold at ``k = V`` is
    the row's minimum and masks nothing); (sort) one with ``top_k > 0`` —
    the sort too, for every row as before. ``live`` belongs to the
    predicate because a retired slot keeps its last tenant's
    ``temp``/``top_k``. A live row's token is the same in whichever tier
    the step takes; a dead row's may be its argmax where a stale tenant's
    draw used to be, and every caller discards it."""
    from ..ops.attention_ops import neg_inf

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    draws = temp > 0 if live is None else live & (temp > 0)
    cuts = draws & (top_k > 0)

    def sampled(cut):
        scaled = logits.astype(jnp.float32) / jnp.maximum(
            temp.astype(jnp.float32), 1e-6)[:, None]
        if cut:
            k = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
            srt = jax.lax.sort(scaled, dimension=-1)[:, ::-1]  # descending
            kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
            scaled = jnp.where(scaled >= kth, scaled, neg_inf(jnp.float32))

        def draw(seed_b, pos_b):
            key = jax.random.fold_in(jax.random.PRNGKey(seed_b), pos_b)
            return jax.random.gumbel(key, (v,), jnp.float32)

        tok = jnp.argmax(scaled + jax.vmap(draw)(seed, position),
                         axis=-1).astype(jnp.int32)
        return jnp.where(temp > 0, tok, greedy)

    # cuts implies draws, so the sum is the tier: 0, 1 or 2
    return jax.lax.switch(
        jnp.any(draws).astype(jnp.int32) + jnp.any(cuts).astype(jnp.int32),
        (lambda: greedy, lambda: sampled(False), lambda: sampled(True)))


def _sampler_tier(requests) -> int:
    """The tier of :func:`_sample_tokens` that a dispatch over these
    requests (None: an empty slot) selects at launch, told on the host:
    0 greedy, 1 draw, 2 sort."""
    tier = 0
    for r in requests:
        if r is not None and r.temperature > 0:
            if r.top_k > 0:
                return 2
            tier = 1
    return tier


def _expert_matmul_form(mcfg, n_tokens: int) -> Optional[str]:
    """The form of the experts' grouped product (``ops.moe_ops.matmul_form``)
    that an executable over ``n_tokens`` rows a forward selects, told on
    the host from the model's static geometry as the expert layer tells it
    from its pass's rows; None for a model with no expert layer."""
    held = getattr(mcfg, "experts_held", None)    # the contract's
    if held is None:
        return None
    from ..ops import moe_ops

    return moe_ops.matmul_form(moe_ops.pass_rows(
        n_tokens * mcfg.top_k, len(held), mcfg.n_expert), len(held))


# the per-slot state every decode executable carries from step to step, in
# the order the executables take it
_SLOT_STATE = ("_len", "_tok", "_active", "_gen", "_maxnew", "_temp",
               "_topk", "_seed")


def _arm_slot(state, slot, length, tok, maxnew, temp, topk, seed, eos):
    """``state`` (the arrays of ``_SLOT_STATE``) with ``slot`` armed for a
    request whose prompt of ``length`` tokens gave ``tok``: traced inside
    the prefill and resume executables, which know the token before the
    host does. A request that ends at its first token (``eos``, or
    ``maxnew == 1``) is armed not live, so no dispatch decodes a ghost of
    it."""
    ln, tk, ac, gc, mn, tp, kk, sd = state
    live = maxnew > 1
    if eos is not None:
        live = live & (tok != eos)
    return (ln.at[slot].set(length), tk.at[slot].set(tok),
            ac.at[slot].set(live), gc.at[slot].set(1),
            mn.at[slot].set(maxnew), tp.at[slot].set(temp),
            kk.at[slot].set(topk), sd.at[slot].set(seed))


def _request_scalars(req: Request, slot: int, start: int = 0):
    """What an admission's executable is told of its request, as the two
    host arrays that cross with it: ``(slot, prompt_len, max_new_tokens,
    top_k, seed, start)`` int32 (``start``: the prompt tokens a resume
    finds cached) and the temperature float32."""
    return (np.array([slot, req.prompt_len, req.max_new_tokens, req.top_k,
                      req.seed, start], np.int32),
            np.asarray(req.temperature, np.float32))


_SCALARS_ABS = (jax.ShapeDtypeStruct((6,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32))


class _Dispatch(NamedTuple):
    """One decode dispatch from its launch to the read of its outputs."""

    snap: tuple         # the per-slot state it was launched on: what a
                        # failure rolls back to
    tenants: list       # the requests that held the slots then: whom its
                        # tokens are for
    steps: int          # its fused steps
    outs: list          # its outputs, still on the device
    t0: float           # the instant its launch began


class ServingConfig:
    """Engine geometry + policy knobs.

    ``slots``: fixed decode batch width. ``max_seq``: per-request context
    budget (prompt + generated), a multiple of ``page_size``. ``num_pages``
    defaults to full-occupancy worst case (``slots * max_seq/page_size``);
    size it SMALLER to oversubscribe — admission then backpressures on the
    pool instead of the slots. It sizes the model's FIRST cache group (the
    only one of a model whose layers all keep every position);
    ``group_pages`` ``{group name: pages}`` sizes any group by name, each
    defaulting to its own worst case (``slots`` times the group's pages a
    slot: for a window group its ring). ``decode_fuse`` fuses that many decode steps
    into one dispatched scan (admission/retirement happen at chunk
    boundaries — latency trades against host dispatch overhead);
    ``decode_fuse="auto"`` consults the autotuned config table
    (paddle_tpu.tune, kernel key ``serving.decode_fuse``, bucketed by slot
    count + device kind) and falls back to 1 when no tuned entry exists —
    ``decode_fuse_source`` records which layer answered
    (tuned/shipped/default vs "explicit" for a literal int).
    ``paged=False`` swaps in the contiguous reference cache. ``eos_id=None``
    disables EOS stopping (generation runs to ``max_new_tokens``).
    ``kv_dtype="int8"`` requests quantized KV pages
    (:class:`~.kv_cache.Int8PagedKVCache` — half the bf16 page bytes, so
    the same HBM budget holds 2× the pages); it engages only when a
    calibrated scale for this model's KV fingerprint exists
    (``paddle_tpu.monitor.numerics``, ``PADDLE_TPU_NUMERICS=2``), and
    falls back to the fp cache otherwise — serving must come up even with
    no calibration table on disk.

    Failure policy: ``decode_retries`` bounds in-place retries of a decode
    dispatch whose failure classifies as transient
    (:func:`paddle_tpu.reliability.faults.classify`); past the budget — or
    on a fatal failure — the in-flight batch is FAILED, its pages return to
    the pool, and the engine keeps serving the queue. ``fail_fast=True``
    restores the old raise-through behavior (debugging).

    Telemetry: ``slos`` is an optional sequence of
    :class:`paddle_tpu.monitor.slo.SLO` specs evaluated on every telemetry
    export tick (``PADDLE_TPU_TELEMETRY_DIR`` arms the exporter; the
    engine starts/stops it with its own lifetime). A breached spec with
    ``degrade=True`` flips :meth:`ServingEngine.health` to ``degraded``
    until a clean tick — slow-death becomes visible to the same recovery
    ladder that sees exceptions. ``PADDLE_TPU_SLO`` (see
    :func:`paddle_tpu.monitor.slo.parse_slos`) appends env-declared specs.
    """

    def __init__(self, slots: int = 8, page_size: int = 16,
                 max_seq: int = 128, num_pages: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 max_queue: int = 1024, eos_id: Optional[int] = None,
                 decode_fuse=1, paged: bool = True,
                 collect_logits: bool = False,
                 pad_id: int = 0, decode_retries: int = 2,
                 fail_fast: bool = False,
                 slos: Optional[Sequence] = None,
                 drain_timeout_s: float = 30.0,
                 kv_dtype: Optional[str] = None,
                 prefix_cache_pages: int = 0,
                 group_pages: Optional[Dict[str, int]] = None):
        if kv_dtype not in (None, "int8"):
            raise ValueError("kv_dtype must be None or 'int8', got %r"
                             % (kv_dtype,))
        if max_seq % page_size != 0:
            raise ValueError("max_seq=%d must be a multiple of page_size=%d"
                             % (max_seq, page_size))
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.max_seq = int(max_seq)
        self.num_pages = (self.slots * (self.max_seq // self.page_size)
                          if num_pages is None else int(num_pages))
        self.group_pages = dict(group_pages or {})
        self.prompt_buckets = tuple(sorted(
            prompt_buckets if prompt_buckets is not None
            else _pow2_buckets(min(8, max_seq), max_seq)))
        if self.prompt_buckets[-1] > self.max_seq:
            raise ValueError("prompt bucket %d exceeds max_seq %d"
                             % (self.prompt_buckets[-1], self.max_seq))
        self.max_queue = int(max_queue)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.decode_fuse_source = "explicit"
        if decode_fuse is None or decode_fuse == "auto":
            decode_fuse, self.decode_fuse_source = self._tuned_decode_fuse()
        self.decode_fuse = max(1, int(decode_fuse))
        self.paged = bool(paged)
        self.collect_logits = bool(collect_logits)
        self.pad_id = int(pad_id)
        self.decode_retries = max(0, int(decode_retries))
        self.fail_fast = bool(fail_fast)
        self.slos = list(slos) if slos else []
        self.drain_timeout_s = float(drain_timeout_s)
        # "int8": quantized KV pages — honored only when paged AND a
        # calibrated scale exists for this model's KV fingerprint
        # (monitor.numerics.kv_scale); otherwise the engine falls back to
        # the fp cache with a vlog warning instead of refusing to serve
        self.kv_dtype = kv_dtype
        # >0 arms the fleet prefix cache (paged layout only): that many
        # pool pages may be pinned by cached prompt-prefix KV, LRU-evicted
        # under pressure. A hit skips the shared prefix's prefill compute
        # (pages are row-copied, the remainder runs the resume executable).
        self.prefix_cache_pages = max(0, int(prefix_cache_pages))
        if self.prefix_cache_pages >= self.num_pages:
            raise ValueError(
                "prefix_cache_pages=%d must leave serving pages free "
                "(num_pages=%d)" % (self.prefix_cache_pages, self.num_pages))

    def _tuned_decode_fuse(self):
        """(value, source) from the autotuned config table; (1, "default")
        when no entry (or any table failure — serving must come up even
        with a corrupt table on disk)."""
        from .. import tune

        return tune.resolve_decode_fuse(self.slots)


def _layer_groups(mcfg):
    """A model config's cache groups as ``(name, layers, window, kind,
    chunk)``: ``cache_groups`` (an entry without a kind is K and V rows,
    one without a chunk is not compacting; with ``latent_row`` and no
    ``cache_groups``, one latent group of every layer), else one group of
    every layer that keeps every position (the contract's defaults:
    ``models.blocks.ServedLM``)."""
    latent = getattr(mcfg, "latent_row", None)
    groups = getattr(mcfg, "cache_groups", None) or [
        ("latent" if latent else "global", tuple(range(mcfg.n_layer)), None,
         LATENT if latent else KV)]
    return [tuple(g) + (KV, None)[len(g) - 3:] for g in groups]


def _query_groups(mcfg, layer_groups):
    """``(n_kv, {group: G})`` of a model config: the KV heads, and for each
    cache group the query heads a KV head of its layers. ``n_head`` is one
    number (every layer; with no ``n_kv_head``, G = 1) or one a layer; the
    layers of a group must agree, since a group's decode attention is one
    kernel shape."""
    heads = mcfg.n_head
    if isinstance(heads, int):
        heads = (heads,) * mcfg.n_layer
    n_kv = getattr(mcfg, "n_kv_head", heads[0])
    q_per_kv = {}
    for name, layers, _window, _kind, _chunk in layer_groups:
        of_group = sorted({int(heads[l]) for l in layers})
        if len(of_group) != 1 or of_group[0] % n_kv:
            raise ValueError(
                "cache group %r: its layers have %s query heads over %d KV "
                "heads; a group has one number, a multiple of the KV heads"
                % (name, of_group, n_kv))
        q_per_kv[name] = of_group[0] // n_kv
    return n_kv, q_per_kv


class ServingEngine:
    """Drives a model under the serving contract, which is written ONCE,
    in ``models.blocks.ServedLM``'s docstring: the methods the engine calls
    (``prefill`` or, where the model has it, ``prefill_last``; ``decode``)
    and what it reads of ``model.cfg`` (``n_layer``, ``n_head``,
    ``d_head``, ``max_seq``, ``dtype`` and, by ``getattr``, ``n_kv_head``,
    ``cache_groups``, ``cache_steps``, ``latent_row``, ``slot_state``,
    ``state_recurrence``, ``index_row``, ``experts_held``), each with what
    its absence means.
    Every ``getattr``/``hasattr`` on a model or its config in this module
    is one of those. The cache groups' kinds (``KV``, ``LATENT``,
    ``STATE``) are ``serving.kv_cache``'s; a model's decode ``stats`` go to
    the ``serving/*`` histograms of their names (``metrics.model_stat``).

    Over a cache of more than one group, over a latent cache (with or
    without a state group) and over a COMPACTING group
    (``serving.kv_cache``), the engine refuses, at construction, what
    cannot work there: the int8 KV pool, the prefix cache (a state has no
    snapshot at a page boundary; a compacted page no longer holds the
    positions its place says) and the contiguous layout; page
    export/import raise when called.
    """

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 params=None):
        self.model = model
        self.cfg = config or ServingConfig()
        mcfg = model.cfg
        if mcfg.max_seq < self.cfg.max_seq:
            raise ValueError(
                "model max_seq %d < serving max_seq %d (position table too "
                "small for the context budget)" % (mcfg.max_seq, self.cfg.max_seq))
        self.params = params if params is not None else model.params
        layer_groups = _layer_groups(mcfg)
        latent = getattr(mcfg, "latent_row", None)
        if len(layer_groups) > 1 or latent \
                or layer_groups[0][4] is not None:
            self._refuse_over_groups(layer_groups, latent)
        # cache layers a layer of a paged group (a looped model: one a step)
        steps = int(getattr(mcfg, "cache_steps", 1))
        if steps > 1 and (latent or self.cfg.kv_dtype == "int8"):
            raise ValueError(
                "%s is not supported over a cache of %d cache layers a "
                "layer (cache_steps)" % (
                    "a latent cache" if latent else "the int8 KV pool",
                    steps))
        self.pools: List[PagePool] = []
        groups = []
        if self.cfg.paged:
            ps = self.cfg.page_size
            for gi, (name, layers, window, kind, chunk) in enumerate(
                    layer_groups):
                # a compacting group's window is no ring: a slot's rows
                # follow max_seq through the cache's own map, and the
                # worst case of every position a row covers it
                rows = self.cfg.max_seq if window is None or chunk \
                    else min(int(window), self.cfg.max_seq)
                pages = 0 if kind == STATE else self.cfg.group_pages.get(
                    name, self.cfg.num_pages if gi == 0
                    else self.cfg.slots * (rows // ps))
                groups.append(CacheGroup(name, tuple(layers), window, pages,
                                         kind, chunk))
            unknown = set(self.cfg.group_pages) - {
                g.name for g in groups if g.kind != STATE}
            if unknown:
                raise ValueError("group_pages names %s; the model's paged "
                                 "cache groups are %s"
                                 % (sorted(unknown), [
                                     g.name for g in groups
                                     if g.kind != STATE]))
        if latent:
            self.cache_ops = LatentPagedCache(
                mcfg.n_layer, latent[0], latent[1], self.cfg.slots,
                self.cfg.max_seq, self.cfg.page_size, groups[0].num_pages,
                dtype=mcfg.dtype, groups=groups,
                slot_state=getattr(mcfg, "slot_state", None),
                index=getattr(mcfg, "index_row", None))
        elif self.cfg.paged:
            n_kv, q_per_kv = _query_groups(mcfg, layer_groups)
            kv_scales = None
            if self.cfg.kv_dtype == "int8":
                kv_scales = self._calibrated_kv_scales(mcfg)
            geometry = dict(dtype=mcfg.dtype, groups=groups,
                            q_per_kv=q_per_kv)
            if steps > 1:
                geometry["cache_steps"] = steps
            slot_state = getattr(mcfg, "slot_state", None)
            if slot_state is not None:   # K and V pages BESIDE a state
                geometry.update(
                    slot_state=slot_state,
                    recurrence=getattr(mcfg, "state_recurrence", "kda"))
            if kv_scales is not None:
                self.cache_ops = Int8PagedKVCache(
                    mcfg.n_layer, n_kv, mcfg.d_head, self.cfg.slots,
                    self.cfg.max_seq, ps, groups[0].num_pages,
                    k_scale=kv_scales[0], v_scale=kv_scales[1], **geometry)
            else:
                self.cache_ops = PagedKVCache(
                    mcfg.n_layer, n_kv, mcfg.d_head, self.cfg.slots,
                    self.cfg.max_seq, ps, groups[0].num_pages, **geometry)
        else:
            n_kv, _ = _query_groups(mcfg, layer_groups)
            self.cache_ops = ContiguousKVCache(
                mcfg.n_layer, n_kv, mcfg.d_head, self.cfg.slots,
                self.cfg.max_seq, dtype=mcfg.dtype, cache_steps=steps)
        # the third start-up phase (compile_cache.phases()): first pool
        # allocated to last, so pages, states and the per-slot tables
        with _cc.phase("startup/pools"):
            if self.cfg.paged:      # one free list a PAGED cache group
                self.pools = [PagePool(
                    g.num_pages, self.cfg.page_size, name=g.name,
                    primary=(gi == 0),
                    run_pages=self.cache_ops.group_run_pages(gi))
                    for gi, g in enumerate(self.cache_ops.groups)
                    if g.kind != STATE]
            # the first group's pool, under the name a one-group engine's
            # only pool always had
            self.pool: Optional[PagePool] = \
                self.pools[0] if self.pools else None
            self.scheduler = Scheduler(self.cfg.slots, self.cfg.max_queue)
            self._cache = self.cache_ops.init_state()
            if self.cfg.paged:
                _sm.STATE_POOL_BYTES.set(
                    self.cache_ops.state_bytes(self._cache))
                if latent:
                    _sm.LATENT_RING_BYTES.set(
                        self.cache_ops.ring_bytes(self._cache))
                    _sm.INDEX_POOL_BYTES.set(
                        self.cache_ops.index_bytes(self._cache))
            self._reset_slot_state()
        self._prefill_exe: Dict[int, Any] = {}   # bucket -> AOT executable
        self._decode_exe: Dict[int, Any] = {}    # fuse length -> executable
        self._resume_exe: Dict[int, Any] = {}    # remainder bucket -> exe
        self.last_decode_stats = None   # (tenants, stats) of the newest read
        # prefix cache: host-side index of donated prompt-prefix KV pages
        # (paged layout only; see serving/prefix_cache.py)
        self.prefix_cache = None
        if self.cfg.paged and self.cfg.prefix_cache_pages > 0:
            self.prefix_cache = PrefixCache(self.cfg.prefix_cache_pages,
                                            self.cfg.page_size)
        self._captured_logits: Dict[int, List[np.ndarray]] = {}
        # the decode dispatch that was launched and is not read yet
        self._unread: Optional[_Dispatch] = None
        self._consecutive_failures = 0
        self._faults_absorbed = 0
        # per-ENGINE prefill accounting (the registry counters are shared
        # process-wide; a fleet replica's health doc needs its own)
        self._prefills = 0
        self._resumes = 0
        self._cycles = 0
        # the prefill clock (:meth:`prefill_clock`): seconds inside the
        # ``serving/prefill`` spans that have closed, and the start of the
        # one that is open
        self._prefill_closed_s = 0.0
        self._prefill_open_t0: Optional[float] = None
        self._last_error: Optional[str] = None
        self._closed = False
        self._draining = False
        self.last_drain: Optional[dict] = None
        # drain re-entrancy latch: a nested drain (signal handler firing
        # mid-drain, monitor thread) must observe, not re-enter
        self._drain_active = False
        self._drain_summary: Optional[dict] = None
        # continuous telemetry: refcounted process exporter (None when
        # PADDLE_TPU_TELEMETRY_DIR is unset — that check is one env read)
        self._telemetry = _telemetry.acquire()
        self._slo_breach: Optional[_slo.Breach] = None
        self._slo_monitor: Optional[_slo.SLOMonitor] = None
        specs = list(self.cfg.slos)
        env_slos = os.environ.get("PADDLE_TPU_SLO", "").strip()
        if env_slos:
            specs.extend(_slo.parse_slos(env_slos))
        if specs:
            self._slo_monitor = _slo.SLOMonitor(
                specs, on_breach=self._on_slo_breach,
                on_clear=self._on_slo_clear)
            if self._telemetry is not None:
                self._telemetry.add_listener(self._slo_monitor.on_sample)
            else:
                # SLOs only evaluate on export ticks: without the exporter
                # they would be silently dead — say so once, loudly
                import logging

                logging.getLogger("paddle_tpu").warning(
                    "ServingEngine: %d SLO spec(s) configured but "
                    "PADDLE_TPU_TELEMETRY_DIR is unset — no export ticks "
                    "will run, so the SLOs are inert (health() cannot "
                    "degrade on them)", len(specs))

    def _reset_slot_state(self) -> None:
        """Every slot empty: nobody live, nothing generated."""
        b = self.cfg.slots
        self._len = jnp.zeros((b,), jnp.int32)
        self._tok = jnp.zeros((b,), jnp.int32)
        self._active = jnp.zeros((b,), jnp.bool_)
        self._gen = jnp.zeros((b,), jnp.int32)
        self._maxnew = jnp.ones((b,), jnp.int32)
        # per-slot sampling params (ride the decode dispatch as plain
        # arguments; 0-temperature slots run the exact greedy path)
        self._temp = jnp.zeros((b,), jnp.float32)
        self._topk = jnp.zeros((b,), jnp.int32)
        self._seed = jnp.zeros((b,), jnp.int32)

    def _slot_state(self) -> tuple:
        return tuple(getattr(self, name) for name in _SLOT_STATE)

    def _refuse_over_groups(self, layer_groups, latent=None) -> None:
        """What cannot work over a cache of more than one group, or over a
        latent one, said at construction rather than computed wrong."""
        cfg = self.cfg
        kinds = [g[3] for g in layer_groups]
        over = ("a latent cache (one [c | kr] row a token, no V pool%s%s)"
                % (", %d latent groups %s of which the windowed keep rings"
                   % (kinds.count(LATENT),
                      [g[0] for g in layer_groups if g[3] == LATENT])
                   if kinds.count(LATENT) > 1 else "",
                   ", beside a state a slot that has no pages"
                   if STATE in kinds else "")
                if latent else "a cache with %d groups %s"
                % (len(layer_groups), [g[0] for g in layer_groups]))
        compacting = [g[0] for g in layer_groups if g[4] is not None]
        if compacting:
            over += (" of which %s compact: a closed window's rows are "
                     "replaced by a summary a chunk, so nothing can be "
                     "rolled back and a page no longer holds the positions "
                     "its place says" % compacting)
        for on, what in (
                (not cfg.paged, "the contiguous layout (paged=False)"),
                (cfg.kv_dtype == "int8", "the int8 KV pool"),
                (cfg.prefix_cache_pages > 0, "the prefix cache")):
            if on:
                raise ValueError("%s is not supported over %s" % (what, over))

    @staticmethod
    def _calibrated_kv_scales(mcfg):
        """(k_scale, v_scale) for this model's KV fingerprint, or None when
        no calibration exists (or ANY lookup failure — the int8 request
        then degrades to the fp cache, because serving must come up even
        with a missing/corrupt calibration table)."""
        from ..log import vlog
        from ..monitor import numerics as _num

        try:
            fp = _num.kv_fingerprint(mcfg.n_layer, mcfg.n_head, mcfg.d_head,
                                     mcfg.dtype)
            scales = _num.kv_scale(fp)
        except Exception:
            scales = None
        if scales is None:
            vlog(1, "ServingEngine: kv_dtype='int8' requested but no "
                    "calibrated KV scale found (run a calibration pass: "
                    "PADDLE_TPU_NUMERICS=2 or numerics."
                    "record_kv_calibration) — falling back to fp pages")
        return scales

    # -- public API -----------------------------------------------------------
    def close(self) -> None:
        """Release the engine's telemetry resources: unhook the SLO
        monitor and drop the exporter reference (the LAST engine or
        supervisor releasing it stops the thread and flushes the final
        partial interval). A decode dispatch still unread is read first:
        its tokens reach their requests. Idempotent; compiled executables
        stay usable."""
        if self._closed:
            return
        self._closed = True
        self._read_unread()
        if self._telemetry is not None:
            if self._slo_monitor is not None:
                self._telemetry.remove_listener(self._slo_monitor.on_sample)
            _telemetry.release(self._telemetry)
            self._telemetry = None

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _on_slo_breach(self, breach) -> None:
        self._slo_breach = breach

    def _on_slo_clear(self) -> None:
        self._slo_breach = None

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               deadline_s: Optional[float] = None,
               temperature: float = 0.0, top_k: int = 0,
               seed: Optional[int] = None,
               trace_id: Optional[str] = None,
               attempt: int = 0) -> Request:
        """Queue a request. Raises ``ValueError`` for a request that can
        NEVER be served at this geometry, and ``BackpressureError`` when
        the bounded queue is full (shed/retry — transient). ``deadline_s``
        bounds the request's wall-clock life from submission: past it the
        request is retired with TIMEOUT status (queued or running) so it
        stops pinning a slot and KV pages. ``temperature``/``top_k``/
        ``seed`` select device-side sampled decoding for THIS request (see
        :class:`~.request.Request`); the default is exact greedy."""
        if self._draining:
            _sm.DRAIN_REJECTED.inc()
            raise DrainingError(
                "engine is draining (graceful shutdown): not admitting new "
                "requests — re-route to a peer")
        req = Request(prompt, max_new_tokens, deadline_s=deadline_s,
                      temperature=temperature, top_k=top_k, seed=seed,
                      trace_id=trace_id, attempt=attempt)
        if req.prompt_len > self.cfg.prompt_buckets[-1]:
            raise ValueError(
                "prompt length %d exceeds the largest prefill bucket %d"
                % (req.prompt_len, self.cfg.prompt_buckets[-1]))
        total = req.prompt_len + req.max_new_tokens
        if total > self.cfg.max_seq:
            raise ValueError(
                "prompt+max_new_tokens=%d exceeds max_seq=%d" %
                (total, self.cfg.max_seq))
        for gi, pool in enumerate(self.pools):
            need = pool.rounded(self.cache_ops.pages_needed(gi, total))
            if need > pool.capacity:
                raise ValueError(
                    "request needs %d pages but the %s pool only has %d"
                    % (need, pool.name, pool.capacity))
        req = self.scheduler.submit(req)
        _trace.on_submitted(req)
        return req

    def step(self) -> List[Request]:
        """One multiplexer cycle: expire deadlines, retire/admit into free
        slots, prefill the admissions, launch one fused decode dispatch
        and read the one the cycle before launched (the device runs the
        new one meanwhile: :meth:`_decode_dispatch`). A token that
        dispatch N computed is handed over by the ``step()`` that launches
        N+1. Returns requests that reached a terminal state during the
        cycle (FINISHED, TIMEOUT or FAILED — check ``req.state``)."""
        self._cycles += 1
        _sm.CYCLES.inc()
        sched = self.scheduler
        with _span("serving/step", cycle=self._cycles,
                   occupancy=sched.occupancy, queue=sched.queue_depth):
            with _span("serving/expire"):
                finished = self._expire_deadlines()
            with _span("serving/admit"):
                finished.extend(self._admit())
            if sched.occupancy or self._unread is not None:
                finished.extend(self._decode_dispatch())
        return finished

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive :meth:`step` until queue and slots drain (or ``max_steps``).
        A :meth:`request_drain` arriving mid-drive (a SIGTERM handler) flips
        the loop into :meth:`drain`: in-flight requests finish, queued
        ones are shed, the engine closes."""
        done: List[Request] = []
        steps = 0
        while not self.scheduler.idle():
            if self._draining:
                self.drain()
                break
            if max_steps is not None and steps >= max_steps:
                break
            done.extend(self.step())
            steps += 1
        done.extend(self._read_unread())    # a cut drive leaves none behind
        return done

    def request_drain(self) -> None:
        """Signal-handler-safe drain request: new submissions start
        rejecting typed (:class:`~.request.DrainingError`) immediately;
        the driving loop (:meth:`run`) performs the actual drain at the
        next cycle boundary instead of tearing down mid-decode."""
        self._draining = True

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful shutdown: stop admitting (queued requests are shed
        with terminal state REJECTED — they never held slots or pages),
        FINISH the in-flight requests (continuing the normal decode loop,
        bounded by ``timeout_s``; stragglers past it retire TIMEOUT with
        their pages reclaimed), then :meth:`close`. Returns and stores
        (``engine.last_drain``) a summary dict; ticks ``serving/drains``
        and ``serving/drained_requests``.

        Idempotent AND re-entrant: a second drain on a drained engine
        returns the recorded summary untouched, and a nested call (a
        SIGTERM handler or monitor thread firing while a drain is already
        running its decode loop) returns a snapshot of the in-progress
        summary instead of re-entering the loop — the fleet router's
        respawn paths call drain from exactly those contexts."""
        if self.last_drain is not None:
            return self.last_drain
        if self._drain_active:
            return dict(self._drain_summary or {})
        self._drain_active = True
        summary = {"finished": 0, "timed_out": 0, "failed": 0,
                   "rejected": 0}
        self._drain_summary = summary
        try:
            if timeout_s is None:
                timeout_s = self.cfg.drain_timeout_s
            self._draining = True
            _sm.DRAINS.inc()
            now = time.perf_counter()
            for req in self.scheduler.drain_queue():
                req.finished_t = now
                _trace.on_terminal(req, REJECTED, None)
                summary["rejected"] += 1

            def tally(reqs):
                for req in reqs:
                    key = {FINISHED: "finished", TIMEOUT: "timed_out",
                           FAILED: "failed"}.get(req.state)
                    if key is not None:
                        summary[key] += 1

            deadline = time.monotonic() + timeout_s
            while self.scheduler.occupancy and time.monotonic() < deadline:
                tally(self.step())
            # past the budget with a dispatch unread: its tokens are
            # computed, and some of its requests may have ended in it
            tally(self._read_unread())
            for slot in range(self.cfg.slots):
                if self.scheduler.slot_request(slot) is not None:
                    # past the drain budget: cut the straggler loose —
                    # TIMEOUT is its terminal state, pages go to the pool
                    self._retire(slot, state=TIMEOUT)
                    summary["timed_out"] += 1
            if self.prefix_cache is not None and self.pool is not None:
                # cached prefix pages are engine-lifetime pins: a drained
                # engine returns them so accounting ends at zero used
                self.pool.free(self.prefix_cache.flush())
            _sm.DRAINED_REQUESTS.inc(summary["finished"])
            self.last_drain = summary
            self.close()
            return summary
        finally:
            self._drain_active = False

    def captured_logits(self, req: Request) -> List[np.ndarray]:
        """Per-emitted-token logits rows (``collect_logits=True`` only)."""
        return self._captured_logits.get(req.id, [])

    def decode_kernel_info(self) -> tuple:
        """``(kernel, source)`` of the decode-attention inner loop as THIS
        engine resolves it: ``("paged", "<tuned|shipped|default>; fold:
        <grouped|per_lane>")`` when the ragged paged-attention Pallas
        kernel is armed (``FLAGS_paged_attention_kernel``, paged layout,
        geometry inside the kernel's static gate) — source is the
        tune-table layer answering its ``block_pages`` lookup, i.e. the
        provenance the compiled trace saw, and the fold the one the cache
        groups' geometry takes (``PagedKVCache.kernel_folds`` says it a
        group) — else
        ``("gather", why)``: "n/a" with the flag off or a dense layout,
        ``"gate: <rule>"`` for a cache geometry or dtype the kernel's gate
        excludes."""
        if not self.cfg.paged:
            return "gather", "n/a"
        mode, why_not = self.cache_ops.kernel_mode()
        if mode is None:
            return "gather", why_not
        if isinstance(self.cache_ops, LatentPagedCache):
            return "mla_paged", "default"     # no tuned table of its own
        try:
            from .. import tune

            _c, src = tune.lookup(
                "paged_attention",
                tune.bucket_ctx(self.cfg.max_seq, self.cache_ops.row_width))
        except Exception:
            src = "default"
        folds = sorted(set(self.cache_ops.kernel_folds().values()))
        return "paged", "%s; fold: %s" % (src, "/".join(folds))

    def stats(self) -> dict:
        kern, kern_src = self.decode_kernel_info()
        out = {
            "layout": self.cache_ops.layout,
            "queued": self.scheduler.queue_depth,
            "running": self.scheduler.occupancy,
            "cache_bytes": self.cache_ops.cache_bytes(self._cache),
            "decode_fuse": self.cfg.decode_fuse,
            "decode_fuse_source": getattr(self.cfg, "decode_fuse_source",
                                          "explicit"),
            "decode_kernel": kern,
            "decode_kernel_source": kern_src,
            # the layout actually serving (int8 requests silently fall back
            # to fp when uncalibrated — this is where that shows)
            "kv_layout": self.cache_ops.layout,
            "kv_dtype": ("int8" if isinstance(self.cache_ops,
                                              Int8PagedKVCache)
                         else str(self.cache_ops.dtype)),
        }
        if self.pool is not None:
            out["pages_in_use"] = self.pool.num_used
            out["page_pool_utilization"] = round(self.pool.utilization, 4)
            out["pages_by_group"] = {p.name: [p.num_used, p.num_pages]
                                     for p in self.pools}
            # serving/page_run_pages.<group> and serving/pages_padding
            # .<group>: the run a group's pool hands out, what it costs now
            out["page_run_pages"] = {p.name: p.run_pages for p in self.pools}
            out["pages_padding"] = {p.name: p.num_padding
                                    for p in self.pools}
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out

    def health(self) -> dict:
        """Liveness/degradation snapshot for an external health checker:
        ``status`` is ``"ok"`` until a decode failure is absorbed and back
        to ``"ok"`` after the next clean dispatch (``"degraded"`` in
        between) — and, with SLO specs configured, while the most recent
        telemetry tick breached a ``degrade=True`` spec (slow-death
        detection, cleared by the next healthy tick). Counters are
        lifetime totals for THIS engine."""
        degraded = bool(self._consecutive_failures) or \
            self._slo_breach is not None
        out = {
            "status": "degraded" if degraded else "ok",
            "queued": self.scheduler.queue_depth,
            "running": self.scheduler.occupancy,
            "consecutive_failures": self._consecutive_failures,
            "faults_absorbed": self._faults_absorbed,
            "last_error": self._last_error,
            "page_accounting_ok": self.page_accounting_ok(),
            # full prefills vs prefix-resume ingests, THIS engine only —
            # what a router reads to prove a migrated prefix skipped work
            "prefills": self._prefills,
            "resumes": self._resumes,
        }
        if self._slo_breach is not None:
            out["slo_breach"] = self._slo_breach.to_doc()
        if self._slo_monitor is not None:
            out["slo_breaches_total"] = self._slo_monitor.breaches_total
        if self.pool is not None:
            out["pages_free"] = self.pool.num_free
            out["pages_total"] = self.pool.num_pages
        return out

    def prefill_clock(self, now: float) -> float:
        """The seconds this engine has spent inside ``serving/prefill``
        spans up to ``now`` (a ``time.perf_counter`` instant not before the
        last span's start): the closed spans' own ``t1 - t0``, and an open
        one as far as ``now``. Each entry of a request's ``timeline``
        carries it, so what the request lost behind admissions between two
        hand-overs is the difference of two stamps. An upper bound by up to
        one decode step an admission: a prefill queues on the device behind
        the decode dispatch in flight and ``serving/prefill.sync`` drains
        that too, which the other slots would have waited for anyway."""
        if self._prefill_open_t0 is None:
            return self._prefill_closed_s
        return self._prefill_closed_s + (now - self._prefill_open_t0)

    def page_accounting_ok(self) -> bool:
        """The no-leak invariant every retirement path must preserve, in
        every cache group: pages the group's pool counts as used == pages
        its running requests hold there."""
        for gi, pool in enumerate(self.pools):
            held = sum(len(r.group_pages[gi])
                       for r in self.scheduler.running())
            if gi == 0 and self.prefix_cache is not None:
                held += self.prefix_cache.pages_held
            if pool.num_used != held:
                return False
        return True

    # -- cross-replica page migration -----------------------------------------
    # The shippable unit of state is a prefix-cache entry: page-aligned
    # prompt KV pages + the exact tokens they cover. Export COPIES bytes
    # (ownership never crosses a process boundary); import is atomic from
    # the pool's point of view — alloc, write, insert, and any failure
    # frees the reservation before returning, so ``page_accounting_ok``
    # holds on both sides of every migration outcome.
    def export_prefix_pages(self, tokens: Sequence[int]):
        """Serialize the prefix-cache entry exactly covering ``tokens`` to
        ``(meta, blobs)``; None when absent (evicted, never donated) or
        when this engine has no page concept (contiguous layout)."""
        if self.prefix_cache is None:
            return None
        entry = self.prefix_cache.get(tokens)
        if entry is None:
            return None
        return self.cache_ops.export_pages(self._cache, entry.pages)

    def ingest_prefix_pages(self, tokens: Sequence[int], meta: dict,
                            blobs) -> bool:
        """Land an exported prefix into THIS engine's pool + prefix cache.
        Returns False (never raises) when it cannot: no paged pool, no
        prefix cache, geometry mismatch, pool exhausted, or the cache
        refuses the insert — in every refusal the reservation is freed
        first. Re-ingesting an already-held prefix is a no-op success."""
        if self.pool is None or self.prefix_cache is None or self._closed:
            return False
        tokens = [int(t) for t in tokens]
        n = int(meta.get("n_pages", 0))
        if n < 1 or len(tokens) != n * self.cfg.page_size:
            return False
        if self.prefix_cache.contains(tokens):
            return True
        try:
            pages = self.pool.alloc(n)
        except PagePoolExhausted:
            return False
        try:
            self._cache = self.cache_ops.import_pages(
                self._cache, pages, meta, blobs)
        except ValueError:
            self.pool.free(pages)
            return False
        accepted, evicted = self.prefix_cache.insert(tokens, pages)
        if evicted:
            self.pool.free(evicted)
        if not accepted:
            self.pool.free(pages)
            return False
        return True

    def evict_prefix(self, tokens: Sequence[int]) -> int:
        """Drop one prefix entry and free its pages; returns pages freed.
        With :meth:`export_prefix_pages` on the other side this is the
        MOVE half of a rebalance: ship, then evict on the source."""
        if self.pool is None or self.prefix_cache is None:
            return 0
        pages = self.prefix_cache.evict(tokens)
        if pages:
            self.pool.free(pages)
        return len(pages)

    def export_request_prefix(self, req: Request):
        """Copy (never move) a live request's page-aligned PROMPT prefix —
        those rows are immutable once prefilled, whatever decode is doing
        — as ``(tokens, meta, blobs)``; None when there is less than one
        full page or no paged pool. The scale-down path ships these so a
        requeued request resumes from its prefill instead of redoing it."""
        if self.pool is None or not req.pages:
            return None
        ps = self.cfg.page_size
        n_tok = ((req.prompt_len - 1) // ps) * ps
        npages = n_tok // ps
        if npages < 1 or len(req.pages) < npages:
            return None
        meta, blobs = self.cache_ops.export_pages(
            self._cache, req.pages[:npages])
        return [int(t) for t in req.prompt[:n_tok]], meta, blobs

    # -- admission + prefill --------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.prompt_buckets:
            if n <= b:
                return b
        raise ValueError("no prefill bucket covers prompt length %d" % n)

    def _admit(self) -> List[Request]:
        finished: List[Request] = []
        slots = self.scheduler.admissible_slots()
        if not slots or self.scheduler.peek() is None:
            return finished
        for slot in slots:
            req = self.scheduler.peek()
            if req is None:
                break
            total = req.prompt_len + req.max_new_tokens
            group_pages = self._reserve(req, total)
            if group_pages is None:
                break
            req = self.scheduler.admit(slot)
            req.admitted_t = time.perf_counter()
            req.group_pages = group_pages
            req.pages = group_pages[0] if group_pages else []
            _trace.on_admitted(req, slot)
            done = self._prefill(req, slot,
                                 self._bucket_for(req.prompt_len))
            if done is not None:
                finished.append(done)
        return finished

    def _reserve(self, req: Request, total: int) -> Optional[List[List[int]]]:
        """A request's pages in EVERY cache group (its worst case there:
        all its positions, or the whole ring where that is shorter), all or
        nothing. None where a group is short: the request stays at the
        queue head (graceful backpressure; retirements will free pages),
        what the other groups gave goes back, and the flight recorder and
        the trace (``serving/admit.blocked``, args ``group``) say which
        group it was."""
        got: List[List[int]] = []
        for gi, pool in enumerate(self.pools):
            need = self.cache_ops.pages_needed(gi, total)
            try:
                got.append(pool.alloc(need))
            except PagePoolExhausted:
                for gj, pages in enumerate(got):
                    self.pools[gj].free(pages)
                self.scheduler.requeue_head_blocked()
                with _span("serving/admit.blocked", group=pool.name,
                           need_pages=need, free_pages=pool.num_free):
                    fr = _dev.flight_recorder()
                    if fr is not None:
                        fr.record_event(
                            "serving_admission_blocked",
                            request_id=req.id, need_pages=need,
                            free_pages=pool.num_free, group=pool.name,
                            batch=self._batch_spec())
                return None
        return got

    def _check_runs(self, group_pages: List[List[int]]) -> None:
        """What the kernels that walk a latent page table rely on, checked
        on the host where a slot's table is set: a group whose pool hands
        out runs is given whole aligned ascending runs (a table that broke
        this would read another request's rows, silently)."""
        for pool, pages in zip(self.pools, group_pages):
            if not pool.whole_runs(pages):
                raise ValueError(
                    "cache group %r is read by whole aligned runs of %d "
                    "pages, a slot's table was handed %s"
                    % (pool.name, pool.run_pages, list(pages)))

    def _prefill(self, req: Request, slot: int, bucket: int
                 ) -> Optional[Request]:
        """Run the per-bucket compiled prefill; returns the request if it
        finished immediately (EOS first token / max_new_tokens == 1). With
        a prefix cache armed, a prompt whose page-aligned prefix is cached
        skips the full prefill: its pages are row-copied and only the
        remainder runs (the resume executable). Either way the admission
        is ONE device program (``serving/admission_programs`` counts them)
        and one read: the executable points the slot's page table at the
        request's pages, writes the prompt's rows, draws the first token
        and arms the slot's per-slot state with it (:func:`_arm_slot`), so
        the host launches nothing after the token is read. And it is ONE
        ``serving/prefill`` span, launch to slot armed: its length is the
        request's ``prefill_s`` (``serving/admission_ms``) and what the
        prefill clock advances by."""
        entry = None
        if self.prefix_cache is not None:
            entry = self.prefix_cache.lookup(req.prompt)
        _sm.SAMPLER_DISPATCHES[_sampler_tier((req,))].inc()
        # a resume runs the remainder through the decode contract: a row a slot
        self._count_expert_matmul(bucket if entry is None else self.cfg.slots)
        admission = _span("serving/prefill", trace_id=req.trace_id, slot=slot,
                          bucket=bucket,
                          cause="local" if entry is None else "resume")
        try:
            with admission:
                self._prefill_open_t0 = admission.t0
                if entry is not None:
                    return self._prefill_from_prefix(req, slot, entry)
                return self._prefill_cold(req, slot, bucket)
        finally:
            # the whole admission on the span's own two clock reads: launch,
            # sync and the host's bookkeeping (serving/prefill_ms starts at
            # the executable's argument transfers and stops when the token
            # is on the host, and keeps its meaning)
            req.prefill_s = admission.t1 - admission.t0
            _sm.ADMISSION_MS.observe(req.prefill_s * 1e3)
            self._prefill_closed_s += req.prefill_s
            self._prefill_open_t0 = None

    def _prefill_cold(self, req: Request, slot: int, bucket: int
                      ) -> Optional[Request]:
        """The per-bucket compiled prefill of a whole prompt, inside
        :meth:`_prefill`'s ``serving/prefill`` span."""
        cfg = self.cfg
        with _span("serving/prefill.launch"):
            prompt = np.full((bucket,), cfg.pad_id, np.int32)
            prompt[:req.prompt_len] = req.prompt
            if cfg.paged:
                self._check_runs(req.group_pages)
            dest = (self.cache_ops.prompt_dest_groups(req.group_pages, slot)
                    if cfg.paged else self.cache_ops.prompt_dest(slot))
            exe = self._get_prefill_exe(bucket)
            # serving/prefill_ms starts here, as it always has: at the
            # transfers of the executable's own arguments
            t0 = time.perf_counter()
            first_tok, last_logits = self._admit_on_device(
                exe, dest, prompt, *_request_scalars(req, slot))
        t1, tok = self._first_token(first_tok)
        _trace.on_prefill(req, slot, bucket, t0, t1, cause="local")
        _sm.PREFILL_MS.observe((t1 - t0) * 1e3)
        _sm.PREFILL_COUNT.inc()
        _sm.PREFILL_ROWS_PROMPT.inc(req.prompt_len)
        _sm.PREFILL_ROWS_BUCKET.inc(bucket)
        self._prefills += 1
        return self._finish_prefill(req, slot, tok, last_logits)

    def _prefill_from_prefix(self, req: Request, slot: int, entry
                             ) -> Optional[Request]:
        """Serve admission from a prefix-cache hit: the resume executable
        points this slot's page table at the request's pages, row-copies
        the cached prefix KV into them, then runs ONLY the prompt remainder
        (teacher-forced decode over the model's own serving
        contract — model-agnostic, no second prefill trace). The first
        sampled token is keyed (seed, prompt_len-1), identical to the cold
        prefill path, so hit and miss generate the same stream."""
        n = entry.n_tokens
        npages = len(entry.pages)
        with _span("serving/prefill.launch"):
            dest = self.cache_ops.prompt_dest(req.pages)
            # the cached pages onto the request's first ones; the pairs
            # left over copy the request's next page onto itself (int8
            # layout: per-page scales are fixed constants, rows copy 1:1)
            src = np.full_like(dest, req.pages[npages])
            src[:npages] = entry.pages
            dst = src.copy()
            dst[:npages] = dest[:npages]
            t0 = time.perf_counter()
            rbucket = self._bucket_for(req.prompt_len - n)
            remainder = np.full((rbucket,), self.cfg.pad_id, np.int32)
            remainder[:req.prompt_len - n] = req.prompt[n:]
            exe = self._get_resume_exe(rbucket)
            first_tok, last_logits = self._admit_on_device(
                exe, dest, remainder, *_request_scalars(req, slot, n), src,
                dst)
        t1, tok = self._first_token(first_tok)
        _trace.on_prefill(req, slot, rbucket, t0, t1, cause="resume")
        _sm.PREFILL_MS.observe((t1 - t0) * 1e3)
        # deliberately NOT PREFILL_COUNT: the bench's "reduced prefill
        # dispatches vs cold" assertion reads that counter
        self._resumes += 1
        return self._finish_prefill(req, slot, tok, last_logits)

    def _admit_on_device(self, exe, *args):
        """Call an admission's executable (prefill or resume) on the cache
        and the per-slot state as they stand (the outputs of the decode
        dispatch in flight, read or not) and take both back armed. The
        cache is donated; the eight state arrays are not, and are
        reassigned only here, after the call returned: a call that raises
        leaves them, and the page table, as they were. Returns the first
        token and the last row's logits, still on the device."""
        out = exe(self.params, self._cache, self._slot_state(), *args)
        _sm.ADMISSION_PROGRAMS.inc()
        self._cache, state, first_tok, last_logits = out
        for name, x in zip(_SLOT_STATE, state):
            setattr(self, name, x)
        first_tok.copy_to_host_async()
        return first_tok, last_logits

    @staticmethod
    def _first_token(first_tok):
        """The admission's one read: ``(the instant it ended, the token)``.
        It drains the pipe: the executable queued behind the decode
        dispatch in flight."""
        with _span("serving/prefill.sync") as sync:
            tok = int(np.asarray(first_tok))
        return sync.t1, tok

    def _finish_prefill(self, req: Request, slot: int, tok: int,
                        last_logits) -> Optional[Request]:
        """What is the host's of an admission, shared by the cold and
        prefix-hit paths: TTFT, the first token and its stamp, immediate
        retirement. It launches nothing: the executable armed the slot on
        the device (:func:`_arm_slot`), not live where the request ends
        here."""
        cfg = self.cfg
        _sm.TOKENS_GENERATED.inc()
        now = time.perf_counter()
        req.first_token_t = now
        _sm.TTFT_MS.observe((now - req.submitted_t) * 1e3)
        req.tokens_out.append(tok)
        # its own admission is open: what is left of it counts as its own
        # stall, since its second token waits for it
        req.timeline.append((now, 1, self.prefill_clock(now)))
        if cfg.collect_logits:
            self._captured_logits.setdefault(req.id, []).append(
                np.asarray(last_logits))
        if (cfg.eos_id is not None and tok == cfg.eos_id) \
                or req.max_new_tokens == 1:
            return self._retire(slot)
        return None

    # -- decode ---------------------------------------------------------------
    def _cache_lost(self) -> bool:
        """True when a failed dispatch already consumed the donated cache
        buffers (``donate_argnums=(1,)``) — retrying would feed deleted
        arrays, so recovery must re-init the cache instead."""
        lost = False

        def probe(v):
            nonlocal lost
            deleted = getattr(v, "is_deleted", None)
            if deleted is not None and deleted():
                lost = True

        jax.tree_util.tree_map(probe, self._cache)
        return lost

    def _decode_dispatch(self) -> List[Request]:
        """What one ``step()`` does about decoding. A dispatch carries
        every per-slot state on the device and decides there who finishes,
        so dispatch N+1 needs nothing of N that the host has to read first:
        it is launched on N's outputs while N is unread, and the host reads
        N's tokens while the device runs N+1 (:meth:`_decode_cycle`). N is
        read BEFORE anything is launched where the engine can tell that
        N+1 would be empty (:meth:`_launches_ahead`)."""
        finished: List[Request] = []
        if self._unread is not None and not self._launches_ahead(self._unread):
            finished = self._decode_cycle(launch=False)
        if self.scheduler.occupancy:
            finished.extend(self._decode_cycle(launch=True))
        return finished

    def _read_unread(self) -> List[Request]:
        """Read the dispatch left unread, if any, and launch nothing: what
        ``run()``, ``drain()`` and ``close()`` end with."""
        return self._decode_cycle(launch=False) \
            if self._unread is not None else []

    def _launches_ahead(self, prev: _Dispatch) -> bool:
        """Whether the dispatch after ``prev`` is launched before ``prev``
        is read. Not where, by the host's own counts, every running
        request's budget (``max_new_tokens``, ``max_seq``) ends in
        ``prev``: the next dispatch would be empty. (An EOS the host cannot
        foresee may still leave one dispatch with no live slot; its outputs
        are dropped.)"""
        for slot, was in enumerate(prev.tenants):
            req = self.scheduler.slot_request(slot)
            if req is None:
                continue
            gen = len(req.tokens_out) + prev.steps
            # a request armed after prev's launch has its whole budget left
            if req is not was or (
                    gen < req.max_new_tokens
                    and req.prompt_len + gen - 1 < self.cfg.max_seq):
                return True
        return False

    def _launch(self, exe, steps: int) -> _Dispatch:
        """Call ``exe`` on the per-slot state as it stands (the outputs of
        the dispatch before it, read or not; the cache stays donated) and
        start the host copies of its small outputs, so that a cycle later
        they have landed. On a failure nothing is reassigned."""
        snap = (self._cache, self._len, self._tok, self._active, self._gen)
        tenants = [self.scheduler.slot_request(s)
                   for s in range(self.cfg.slots)]
        with _span("serving/decode.launch") as launch:
            spec = _faults.fire("serving.decode")  # chaos drills
            if spec is not None and spec.kind == "exhausted":
                raise PagePoolExhausted(
                    "injected pool exhaustion at serving.decode")
            out = exe(self.params, self._cache, self._len, self._tok,
                      self._active, self._gen, self._maxnew, self._temp,
                      self._topk, self._seed)
            (self._cache, self._len, self._tok, self._active, self._gen,
             *outs) = out
            for x in jax.tree_util.tree_leaves(outs):
                x.copy_to_host_async()
        _sm.SAMPLER_DISPATCHES[_sampler_tier(tenants)].inc()
        self._count_expert_matmul(self.cfg.slots)
        return _Dispatch(snap, tenants, steps, outs, launch.t0)

    def _count_expert_matmul(self, n_tokens: int) -> None:
        form = _expert_matmul_form(self.model.cfg, n_tokens)
        if form is not None:
            _sm.EXPERT_MATMUL_DISPATCHES[form].inc()

    def _sync(self, d: _Dispatch):
        """``d``'s outputs on the host: ``(toks, emitted, fin, logits or
        None, the model's counters or None)``. The one host sync of a
        cycle: the retire/admit decision needs the emitted tokens (the
        serving analog of run_steps' fetch)."""
        with _span("serving/decode.sync"):
            toks, emitted, fin, *rest = jax.tree_util.tree_map(
                np.asarray, d.outs)
        logseq = rest.pop(0) if self.cfg.collect_logits else None
        return toks, emitted, fin, logseq, rest[0] if rest else None

    def _roll_back(self, d: _Dispatch) -> bool:
        """Put the per-slot state back to what ``d`` was launched on: a
        failed dispatch often surfaces at host materialization, AFTER the
        ``self._*`` slots were reassigned to its outputs (and the next
        dispatch launched on them) — a retry from those half-advanced
        values would double-step every in-flight request. Returns whether
        ``d`` can be launched again from there: not where the donated
        cache is gone (recovery must re-init it), nor where a slot was
        vacated or armed since (the snapshot knows nothing of it)."""
        (self._cache, self._len, self._tok, self._active,
         self._gen) = d.snap
        return not self._cache_lost() and all(
            self.scheduler.slot_request(slot) is was
            for slot, was in enumerate(d.tenants))

    def _decode_cycle(self, launch: bool) -> List[Request]:
        """One ``serving/decode`` span, the interval
        ``serving/decode_step_ms`` observes: the launch of a dispatch
        (``launch``; one more for each retry), then the sync that reads
        the dispatch launched a cycle ago, while the device runs the new
        one; ``serving/retire`` hands its tokens over. With nothing unread
        the new dispatch is left unread and the span ends with the launch.
        With ``launch`` false the span is the sync alone.

        The recovery ladder: transient failures retry in place (bounded by
        ``decode_retries``); a failure that exhausts the budget — or
        classifies fatal — FAILS the in-flight batch (pages reclaimed,
        requests marked FAILED, device slot state reset) and the engine
        keeps serving the queue. The flight recorder captures the batch
        spec either way. A failure of the launch leaves the state as it
        was; one that surfaces at the sync rolls it back to what the
        dispatch being read was launched on (:meth:`_roll_back`), the
        dispatch launched ahead of it is abandoned unread, and a retry
        launches the failed one again and reads it at once."""
        prev, self._unread = self._unread, None
        steps = self.cfg.decode_fuse
        exe = self._get_decode_exe(steps)
        read_now = False
        attempt = 0
        with _span("serving/decode", steps=steps) as window:
            while True:
                cur = read = None
                try:
                    if launch:
                        cur = self._launch(exe, steps)
                        if prev is not None:
                            _sm.DECODE_LAUNCHED_AHEAD.inc()
                    read = prev if prev is not None \
                        else cur if read_now else None
                    host = None if read is None else self._sync(read)
                    break
                except Exception as e:
                    if read is None:    # the launch: nothing was reassigned
                        again = not self._cache_lost()
                    else:
                        again = self._roll_back(read)
                        prev, launch, read_now = None, True, True
                    if (again and _faults.classify(e) == "transient"
                            and attempt < self.cfg.decode_retries):
                        attempt += 1
                        _sm.RETRIES.inc()
                        continue
                    fr = _dev.flight_recorder()
                    if fr is not None:
                        fr.record_event("serving_inflight_batch",
                                        **self._batch_spec())
                    _safe_flight_dump(fr, "serving.decode", e)
                    # a launch that gives up with a dispatch unread: that
                    # one's tokens are whole, and go to their requests
                    # before the batch fails
                    whole = self._hand_over_whole(prev, window.t0) \
                        if prev is not None else []
                    if self.cfg.fail_fast:
                        raise
                    return whole + self._fail_inflight_batch(e)
        if cur is not read:
            self._unread = cur
        if read is None:
            return []
        self._consecutive_failures = 0
        finished = self._hand_over(read, host, window.t0, window.t1)
        if not self.scheduler.occupancy:
            # an EOS the host could not foresee: the dispatch launched
            # ahead has no live slot, and nobody to give its outputs to
            self._unread = None
        return finished

    def _hand_over_whole(self, d: _Dispatch, t0: float) -> List[Request]:
        """Read ``d`` and hand its tokens over on the way to failing its
        batch; a read that fails too loses them with the batch."""
        try:
            return self._hand_over(d, self._sync(d), t0, time.perf_counter())
        except Exception:
            return []

    def _hand_over(self, d: _Dispatch, host, t0: float, t1: float
                   ) -> List[Request]:
        """Dispatch ``d``'s tokens, read, to the requests that held the
        slots when it was launched — and only to one that still runs in
        its slot: a request retired since (a deadline, a failed batch)
        gets none of them. ``t0``..``t1`` is the cycle's ``serving/decode``
        interval, which the requests' tracks show; ``serving/decode_step_ms``
        observes ``d``'s launch to ``t1``, its tokens on the host."""
        toks, emitted, fin, logseq, stats = host
        steps = d.steps
        live = [req if req is not None
                and self.scheduler.slot_request(slot) is req else None
                for slot, req in enumerate(d.tenants)]
        _trace.on_decode_chunk(live, steps, t0, t1)
        # the dispatch's own latency, not the cycle's interval: a host-bound
        # loop launches in a fraction of its cycle, and what a caller paces
        # itself by (an SLO, a load generator's lateness) is how long a
        # dispatch takes to give its tokens
        _sm.DECODE_STEP_MS.observe((t1 - d.t0) * 1e3)
        _sm.DECODE_DISPATCHES.inc()
        _sm.DECODE_STEPS.inc(steps)
        if stats is not None:
            # the newest dispatch's stats as read, with who held its slots:
            # what a caller may look at beside the histograms (a name the
            # histograms do not know, such as a probe, is only here)
            self.last_decode_stats = (d.tenants, stats)
            for name, xs in stats.items():
                hist = _sm.model_stat(name)
                if hist is not None:
                    for x in xs.reshape(-1):
                        hist.observe(float(x))
        finished: List[Request] = []
        handed = 0
        clock = self.prefill_clock(t1)
        with _span("serving/retire"):
            for slot, req in enumerate(live):
                if req is None:
                    continue
                had = len(req.tokens_out)
                done = False
                for f in range(steps):
                    if emitted[f, slot]:
                        req.tokens_out.append(int(toks[f, slot]))
                        if logseq is not None:
                            self._captured_logits.setdefault(
                                req.id, []).append(logseq[f, slot])
                    if fin[f, slot]:
                        done = True
                        break
                n = len(req.tokens_out)
                if n > had:
                    # ONE entry however many tokens the dispatch brought,
                    # at the instant serving/decode_step_ms observes
                    req.timeline.append((t1, n, clock))
                    handed += n - had
                if done:
                    finished.append(self._retire(slot))
        _sm.TOKENS_GENERATED.inc(handed)
        return finished

    def _retire(self, slot: int, state: str = FINISHED,
                clear_slot: bool = True) -> Request:
        """EVERY slot-vacating path funnels through here — EOS/max_new
        (FINISHED), deadline (TIMEOUT), decode failure (FAILED) — so page
        reclamation can't be forgotten on a new path. ``clear_slot=False``
        is for callers about to reset ALL device slot state wholesale
        (``_fail_inflight_batch``) — no point in per-slot updates first."""
        req = self.scheduler.retire(slot, state)
        if self.pool is not None and req.pages:
            donated = 0
            if self.prefix_cache is not None:
                donated = self._donate_prefix_pages(req, state)
            if donated < len(req.pages):
                self.pool.free(req.pages[donated:])
            for gi in range(1, len(self.pools)):
                self.pools[gi].free(req.group_pages[gi])
            req.pages = []
            req.group_pages = [[] for _ in self.pools]
        req.finished_t = time.perf_counter()
        _trace.on_terminal(req, state, slot)
        if state == FINISHED:
            _sm.REQUEST_LATENCY_MS.observe(
                (req.finished_t - req.submitted_t) * 1e3)
            (t0, n0, c0), (t1, n1, c1) = req.timeline[0], req.timeline[-1]
            if n1 > n0:     # two tokens or more: it has a gap
                _sm.TPOT_MS.observe((t1 - t0) * 1e3 / (n1 - n0))
                _sm.PREFILL_STALL_MS_PER_TOKEN.observe(
                    (c1 - c0) * 1e3 / (n1 - n0))
        elif state == TIMEOUT:
            _sm.TIMEOUTS.inc()
        elif state == FAILED:
            _sm.REQUESTS_FAILED.inc()
        if state != FINISHED and clear_slot:
            # the decode loop only deactivates slots it finished itself;
            # an out-of-band retirement must clear the device-side flag or
            # the next dispatch decodes a ghost
            self._active = self._active.at[slot].set(False)
        return req

    def _donate_prefix_pages(self, req: Request, state: str) -> int:
        """Zero-copy prefix-cache insert at retirement: a FINISHED
        request's leading full-prompt pages transfer ownership to the
        cache instead of returning to the pool. Returns how many of
        ``req.pages`` the cache now owns (a prefix of the list — the
        caller frees the rest). A request that did NOT finish never
        donates: its pages may hold garbage from the failed dispatch, and
        poisoned prefixes must be structurally unservable."""
        cache = self.prefix_cache
        n = cache.cacheable_len(req.prompt_len)
        if n <= 0:
            return 0
        if state != FINISHED:
            _sm.PREFIX_POISONED_SKIPPED.inc()
            return 0
        tokens = req.prompt[:n]
        if cache.contains(tokens):
            return 0
        npages = n // self.cfg.page_size
        accepted, evicted = cache.insert(tokens, req.pages[:npages])
        if evicted:
            self.pool.free(evicted)
        return npages if accepted else 0

    def _expire_deadlines(self) -> List[Request]:
        """Retire requests past their deadline — queued ones leave the
        queue (no pages to reclaim), running ones vacate slot + pages."""
        now = time.perf_counter()
        out: List[Request] = []
        for req in self.scheduler.drop_expired(now):
            req.finished_t = now
            _trace.on_terminal(req, TIMEOUT, None)
            _sm.TIMEOUTS.inc()
            out.append(req)
        for slot in range(self.cfg.slots):
            req = self.scheduler.slot_request(slot)
            if req is not None and req.expired(now):
                out.append(self._retire(slot, state=TIMEOUT))
        return out

    def _fail_inflight_batch(self, exc: BaseException) -> List[Request]:
        """Decode-failure recovery: mark every in-flight request FAILED,
        reclaim its pages, reset device slot state (re-init the cache if
        the failed dispatch consumed the donated buffers), and leave the
        engine serving. The queue is untouched — queued requests admit
        into the freed slots on the next cycle."""
        self._consecutive_failures += 1
        self._faults_absorbed += 1
        self._last_error = "%s: %s" % (type(exc).__name__, exc)
        _sm.FAULTS.inc()
        failed: List[Request] = []
        for slot in range(self.cfg.slots):
            req = self.scheduler.slot_request(slot)
            if req is None:
                continue
            req.error = self._last_error
            failed.append(self._retire(slot, state=FAILED,
                                       clear_slot=False))
        self._reset_slot_state()
        if self._cache_lost():
            self._cache = self.cache_ops.init_state()
            if self.prefix_cache is not None and self.pool is not None:
                # the rows backing every cached prefix died with the
                # donated buffers — the entries are lies now; drop them
                self.pool.free(self.prefix_cache.flush())
        return failed

    def _batch_spec(self) -> dict:
        """The in-flight batch, host view — what the flight recorder keeps
        when a decode dispatch fails or admission backpressures."""
        rows = []
        for slot in range(self.cfg.slots):
            req = self.scheduler.slot_request(slot)
            if req is None:
                continue
            rows.append({"slot": slot, "request_id": req.id,
                         "trace_id": req.trace_id,
                         "prompt_len": req.prompt_len,
                         "generated": len(req.tokens_out),
                         "max_new_tokens": req.max_new_tokens,
                         "pages": list(req.pages)})
        kern, kern_src = self.decode_kernel_info()
        return {"layout": self.cache_ops.layout, "slots": rows,
                "queue_depth": self.scheduler.queue_depth,
                "decode_fuse": self.cfg.decode_fuse,
                "decode_fuse_source": getattr(self.cfg, "decode_fuse_source",
                                              "explicit"),
                "decode_kernel": kern,
                "decode_kernel_source": kern_src}

    # -- AOT compilation ------------------------------------------------------
    def _get_prefill_exe(self, bucket: int):
        exe = self._prefill_exe.get(bucket)
        if exe is not None:
            return exe
        model, ops, cfg = self.model, self.cache_ops, self.cfg

        last_only = hasattr(model, "prefill_last")   # optional: contract
        steps = ops.cache_steps

        def prefill(params, cache, state, dest, prompt, ints, temp):
            slot, length, maxnew, topk, seed, _ = ints
            if cfg.paged:
                cache = ops.set_page_table(cache, slot, dest)
            if last_only:
                logits, kvs = model.prefill_last(params, prompt[None],
                                                 length[None])
                last = logits[0]
            else:
                logits, kvs = model.prefill(params, prompt[None],
                                            length[None])
                last = logits[0, length - 1]
            for i, kv in enumerate(kvs):
                if kv is None:      # a layer in no cache group keeps nothing
                    continue
                if isinstance(kv[0], tuple):
                    # a layer in a paged AND a state group: its rows, then
                    # what the prompt leaves in its slot
                    kv, left = kv
                    cache = ops.write_slot_state(
                        cache, i, *(t[0] for t in left), dest)
                if steps > 1:
                    # a looped model: K and V a step, [steps, B, S, H, D]
                    for t in range(steps):
                        cache = ops.write_prompt(
                            cache, i, *(x[t, 0] for x in kv), dest, length,
                            step=t)
                    continue
                cache = ops.write_prompt(cache, i, *(t[0] for t in kv), dest,
                                         length)
            # first generated token: same sampler as the decode scan, keyed
            # by the last PROMPT position (decode steps then key length,
            # length+1, ... — the streams can't collide)
            tok = _sample_tokens(last[None], temp[None], topk[None],
                                 seed[None], (length - 1)[None])[0]
            state = _arm_slot(state, slot, length, tok, maxnew, temp, topk,
                              seed, cfg.eos_id)
            return cache, state, tok, last

        dest_abs = (jax.ShapeDtypeStruct((ops.page_table_len,), jnp.int32)
                    if cfg.paged else jax.ShapeDtypeStruct((), jnp.int32))
        exe = aot_compile(
            prefill,
            (self.params, self._cache, self._slot_state(), dest_abs,
             jax.ShapeDtypeStruct((bucket,), jnp.int32)) + _SCALARS_ABS,
            donate_argnums=(1,), label="prefill[%d]" % bucket)
        self._prefill_exe[bucket] = exe
        return exe

    def _get_decode_exe(self, fuse: int):
        exe = self._decode_exe.get(fuse)
        if exe is not None:
            return exe
        model, ops, cfg = self.model, self.cache_ops, self.cfg
        eos = -1 if cfg.eos_id is None else cfg.eos_id
        max_ctx = cfg.max_seq
        collect = cfg.collect_logits

        def chunk(params, cache, lengths, tokens, active, gen, maxnew,
                  temp, topk, seed):
            def body(carry, _):
                cache, ln, tk, ac, gc = carry
                logits, cache, *stats = model.decode(params, cache, ops, tk,
                                                     ln, ac)
                # device-side sampling: keyed by ln (the consumed token's
                # absolute position), which advances per STEP not per
                # dispatch — fuse=1 and fuse=4 draw identical streams
                nxt = _sample_tokens(logits, temp, topk, seed, ln, ac)
                nxt = jnp.where(ac, nxt, tk)
                emitted = ac
                gc = gc + ac
                ln = ln + ac
                fin = ac & ((nxt == eos) | (gc >= maxnew) | (ln >= max_ctx))
                ac = ac & ~fin
                out = (nxt, emitted, fin, logits) if collect \
                    else (nxt, emitted, fin)
                # a model's own per-step counts ride last, stacked [fuse,..]
                return (cache, ln, nxt, ac, gc), out + tuple(stats)

            (cache, lengths, tokens, active, gen), outs = jax.lax.scan(
                body, (cache, lengths, tokens, active, gen), None,
                length=fuse)
            return (cache, lengths, tokens, active, gen) + tuple(outs)

        exe = aot_compile(
            chunk,
            (self.params, self._cache, self._len, self._tok, self._active,
             self._gen, self._maxnew, self._temp, self._topk, self._seed),
            donate_argnums=(1,), label="chunk[fuse=%d]" % fuse)
        self._decode_exe[fuse] = exe
        return exe

    def _get_resume_exe(self, rbucket: int):
        """Teacher-forced prompt-remainder ingest for a prefix-cache hit:
        consume the uncached prompt tail token by token through the
        model's own decode contract (each step writes KV at its absolute
        position), then sample the first generated token from the final
        step's logits, keyed (seed, prompt_len-1) — exactly the cold
        prefill's keying, so the sampled stream is path-independent. Before
        that it points the slot's page table at the request's pages and
        copies the cached prefix's pages onto them (``src`` onto ``dst``);
        after it, it arms the slot as the prefill does. Compiled once per
        remainder bucket, cache donated like every other step function."""
        exe = self._resume_exe.get(rbucket)
        if exe is not None:
            return exe
        model, ops, cfg = self.model, self.cache_ops, self.cfg
        b = cfg.slots
        vocab = self.model.cfg.vocab_size

        def resume(params, cache, state, dest, toks, ints, temp, src, dst):
            slot, length, maxnew, topk, seed, start = ints
            cache = ops.copy_pages(ops.set_page_table(cache, slot, dest),
                                   src, dst)
            slotmask = jnp.arange(b, dtype=jnp.int32) == slot
            tempv = jnp.where(slotmask, temp, 0.0).astype(jnp.float32)
            topkv = jnp.where(slotmask, topk, 0).astype(jnp.int32)
            seedv = jnp.where(slotmask, seed, 0).astype(jnp.int32)

            def body(carry, i):
                cache, tok_acc, log_acc = carry
                pos = start + i
                ac = slotmask & (pos < length)
                tkb = jnp.where(slotmask, toks[i], 0).astype(jnp.int32)
                posb = jnp.full((b,), pos, jnp.int32)
                logits, cache, *_ = model.decode(params, cache, ops, tkb,
                                                 posb, ac)
                is_last = ac & (pos == length - 1)
                # only the last prompt position's draw is kept
                cand = _sample_tokens(logits, tempv, topkv, seedv, posb,
                                      is_last)
                tok_acc = tok_acc + jnp.sum(
                    jnp.where(is_last, cand, 0).astype(jnp.int32))
                log_acc = log_acc + jnp.sum(
                    jnp.where(is_last[:, None],
                              logits.astype(jnp.float32), 0.0), axis=0)
                return (cache, tok_acc, log_acc), None

            init = (cache, jnp.zeros((), jnp.int32),
                    jnp.zeros((vocab,), jnp.float32))
            (cache, tok, last), _ = jax.lax.scan(
                body, init, jnp.arange(rbucket, dtype=jnp.int32))
            state = _arm_slot(state, slot, length, tok, maxnew, temp, topk,
                              seed, cfg.eos_id)
            return cache, state, tok, last

        pages_abs = jax.ShapeDtypeStruct((ops.page_table_len,), jnp.int32)
        exe = aot_compile(
            resume,
            (self.params, self._cache, self._slot_state(), pages_abs,
             jax.ShapeDtypeStruct((rbucket,), jnp.int32)) + _SCALARS_ABS
            + (pages_abs, pages_abs),
            donate_argnums=(1,), label="resume[%d]" % rbucket)
        self._resume_exe[rbucket] = exe
        return exe

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> None:
        """Pre-compile the decode chunk + the given (default: all) prefill
        buckets — this both warms the process and persists the
        executables in the compile cache before traffic arrives."""
        for b in (buckets or self.cfg.prompt_buckets):
            self._get_prefill_exe(self._bucket_for(b))
        self._get_decode_exe(self.cfg.decode_fuse)

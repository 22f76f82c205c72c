"""serving/* instruments: the monitor-registry face of the serving stack.

One module owns every ``serving/*`` name so the scheduler, page pool and
decode driver never race a get-or-create, and tools
(``tools/dump_metrics --selftest``) can assert the full set exists by
importing this module alone. Same hot-path contract as the executor
instruments: module-level handles, a single disabled-branch per call.

``serving/prefill_rows.prompt`` and ``serving/prefill_rows.bucket`` count,
a cold prefill, the rows that were the prompt's and the rows of the bucket
it ran in: their ratio is the share of a prefill's rows that a token can
read, which is what the passes that stop at the prompt's end (the routed
experts, the sparse-attention prefill) still compute.
"""

from __future__ import annotations

from ..monitor import metrics as _mx

__all__ = [
    "REQUESTS_SUBMITTED", "REQUESTS_ADMITTED", "REQUESTS_RETIRED",
    "REQUESTS_REJECTED", "QUEUE_DEPTH", "SLOT_OCCUPANCY",
    "PAGES_IN_USE", "PAGE_POOL_UTILIZATION", "ADMISSION_BLOCKED",
    "PREFILL_COUNT", "PREFILL_ROWS_PROMPT", "PREFILL_ROWS_BUCKET",
    "DECODE_STEPS", "DECODE_DISPATCHES",
    "DECODE_LAUNCHED_AHEAD",
    "TOKENS_GENERATED", "CYCLES", "SAMPLER_DISPATCHES",
    "REQUEST_LATENCY_MS", "TTFT_MS", "DECODE_STEP_MS", "PREFILL_MS",
    "ADMISSION_MS", "ADMISSION_PROGRAMS",
    "TPOT_MS", "PREFILL_STALL_MS_PER_TOKEN",
    "FAULTS", "RETRIES", "TIMEOUTS", "REQUESTS_FAILED",
    "DRAINS", "DRAINED_REQUESTS", "DRAIN_REJECTED",
    "MOE_EXPERTS_TOUCHED", "MOE_MAX_EXPERT_ROWS", "MOE_HELD_PAIRS",
    "MOE_GROUPS_KEPT_WITH_HELD", "INDEX_ROWS_SCORED",
    "STATE_SLOTS_STEPPED", "STATE_POOL_BYTES", "LATENT_RING_BYTES",
    "EVA_CHUNKS_CLOSED", "EVA_WINDOWS_CLOSED",
    "PREFIX_HITS", "PREFIX_MISSES", "PREFIX_INSERTS", "PREFIX_EVICTIONS",
    "PREFIX_ENTRIES", "PREFIX_PAGES", "PREFIX_TOKENS_REUSED",
    "PREFIX_POISONED_SKIPPED",
    "pages_used", "page_run_pages", "pages_padding", "attn_rows_read",
    "model_stat",
]

REQUESTS_SUBMITTED = _mx.counter(
    "serving/requests_submitted", help="requests accepted into the queue")
REQUESTS_ADMITTED = _mx.counter(
    "serving/requests_admitted", help="requests admitted into a batch slot")
REQUESTS_RETIRED = _mx.counter(
    "serving/requests_retired", help="requests finished and retired")
REQUESTS_REJECTED = _mx.counter(
    "serving/requests_rejected",
    help="submissions rejected with BackpressureError (queue full)")
QUEUE_DEPTH = _mx.gauge(
    "serving/queue_depth", help="requests waiting for a slot")
SLOT_OCCUPANCY = _mx.gauge(
    "serving/slot_occupancy", help="batch slots currently running a request")
PAGES_IN_USE = _mx.gauge(
    "serving/page_pool_pages_in_use", help="KV-cache pages currently allocated")
PAGE_POOL_UTILIZATION = _mx.gauge(
    "serving/page_pool_utilization", help="pages_in_use / num_pages, 0..1")
ADMISSION_BLOCKED = _mx.counter(
    "serving/admission_blocked_on_pages",
    help="admission attempts deferred because the page pool could not "
         "cover the request's worst-case page need (backpressure, not crash)")
PREFILL_COUNT = _mx.counter(
    "serving/prefills", help="compiled prefill invocations")
PREFILL_ROWS_PROMPT = _mx.counter(
    "serving/prefill_rows.prompt",
    help="rows of the prompts that the compiled prefills ran "
         "(Request.prompt_len, a cold prefill)")
PREFILL_ROWS_BUCKET = _mx.counter(
    "serving/prefill_rows.bucket",
    help="rows of the buckets those prefills ran in: "
         "serving/prefill_rows.prompt over it is the share of a bucket's "
         "rows that are a prompt's, the most that a pass which stops at the "
         "prompt's end (the routed experts, the sparse-attention prefill) "
         "computes of what a pass over the whole bucket does")
DECODE_STEPS = _mx.counter(
    "serving/decode_steps", help="decode steps executed (all slots at once)")
DECODE_DISPATCHES = _mx.counter(
    "serving/decode_dispatches",
    help="decode dispatches issued (each fuses >=1 decode steps)")
DECODE_LAUNCHED_AHEAD = _mx.counter(
    "serving/decode_launched_ahead",
    help="decode dispatches launched while the one before was unread: "
         "over serving/decode_dispatches, how often the host reads one "
         "dispatch's tokens while the device runs the next")
TOKENS_GENERATED = _mx.counter(
    "serving/tokens_generated", help="tokens emitted to finished+running requests")
CYCLES = _mx.counter(
    "serving/cycles",
    help="engine.step() calls: the base of every per-cycle ratio "
         "(prefills a cycle, tokens a cycle)")
# indexed by the tier engine._sampler_tier returns
SAMPLER_DISPATCHES = tuple(
    _mx.counter("serving/sampler_dispatches.%s" % tier, help=what)
    for tier, what in (
        ("greedy", "decode dispatches and prefills launched with no request "
                   "that samples: the sampler is the argmax alone"),
        ("draw", "decode dispatches and prefills launched with a request "
                 "of temperature > 0 and none of those with top_k > 0: the "
                 "sampler scales and draws, and does not sort"),
        ("sort", "decode dispatches and prefills launched with a request of "
                 "temperature > 0 and top_k > 0: the sampler sorts the "
                 "vocabulary")))
# keyed by what ops.moe_ops.matmul_form returns
EXPERT_MATMUL_DISPATCHES = {
    form: _mx.counter("serving/expert_matmul_dispatches.%s" % form, help=what)
    for form, what in (
        ("stream", "decode dispatches and prefills of a model with an expert "
                   "layer whose passes of the grouped product take the fused "
                   "stream kernel (few rows an expert: the weights bound it)"),
        ("grouped", "decode dispatches and prefills of a model with an "
                    "expert layer whose passes take the compiler's grouped "
                    "matmul (ragged_dot x 3: many rows, or no TPU)"))}
REQUEST_LATENCY_MS = _mx.histogram(
    "serving/request_latency_ms",
    help="submit -> finish wall time per retired request")
TTFT_MS = _mx.histogram(
    "serving/ttft_ms", help="submit -> first token wall time per request")
DECODE_STEP_MS = _mx.histogram(
    "serving/decode_step_ms",
    help="host wall time of one decode dispatch, from its launch to its "
         "tokens on the host: the end of the sync that reads it, which is "
         "a cycle later where the next dispatch was launched ahead of the "
         "read (all its fused steps; one observation a dispatch read)")
PREFILL_MS = _mx.histogram(
    "serving/prefill_ms", help="host wall time of one compiled prefill call")
ADMISSION_MS = _mx.histogram(
    "serving/admission_ms",
    help="host wall time of one admission whole, the length of its "
         "serving/prefill span (Request.prefill_s): the launch, the "
         "executable and the read of its first token, and the host's "
         "bookkeeping around them. Over serving/prefill_ms, which starts at "
         "the executable's argument transfers and ends with the token on "
         "the host: what an admission costs the host beyond its executable")
ADMISSION_PROGRAMS = _mx.counter(
    "serving/admission_programs",
    help="device programs the engine launched inside serving/prefill "
         "spans: over the admissions (serving/prefills and the prefix "
         "resumes) it reads 1, the prefill or resume executable, which "
         "writes the slot's page table and arms its per-slot state itself")
TPOT_MS = _mx.histogram(
    "serving/tpot_ms",
    help="mean gap between a finished request's output tokens: the time "
         "from its first token to its last hand-over over the tokens "
         "between (Request.timeline; one observation a request that "
         "finished with two tokens or more): what a streaming user feels")
PREFILL_STALL_MS_PER_TOKEN = _mx.histogram(
    "serving/prefill_stall_ms_per_token",
    help="the part of serving/tpot_ms the request spent behind admissions: "
         "the engine's prefill clock (seconds inside serving/prefill spans) "
         "at its last hand-over minus at its first token, over the tokens "
         "between; the rest of its own admission included. An upper bound by "
         "up to one decode step an admission (the prefill's sync drains "
         "the decode dispatch in flight too)")
FAULTS = _mx.counter(
    "serving/faults",
    help="decode dispatch failures absorbed by the recovery path (the "
         "in-flight batch was failed, the engine kept serving)")
RETRIES = _mx.counter(
    "serving/retries",
    help="decode dispatches retried after a transient-classified failure")
TIMEOUTS = _mx.counter(
    "serving/timeouts",
    help="requests retired with TIMEOUT status at their deadline (queued "
         "or running; slots and pages reclaimed)")
REQUESTS_FAILED = _mx.counter(
    "serving/requests_failed",
    help="requests retired as FAILED when their in-flight batch was lost "
         "to a decode failure")
DRAINS = _mx.counter(
    "serving/drains",
    help="graceful drains performed (stop admitting, finish in-flight, "
         "close) — SIGTERM/rollout shutdowns, not crashes")
DRAINED_REQUESTS = _mx.counter(
    "serving/drained_requests",
    help="in-flight requests that FINISHED during a graceful drain")
DRAIN_REJECTED = _mx.counter(
    "serving/drain_rejected",
    help="requests rejected because the engine was draining (typed "
         "DrainingError at submit, plus queued requests shed at drain "
         "start)")
MOE_EXPERTS_TOUCHED = _mx.histogram(
    "serving/moe_experts_touched",
    help="experts that received at least one live row, one observation a "
         "layer a decode step (a model whose decode returns the count)")
MOE_MAX_EXPERT_ROWS = _mx.histogram(
    "serving/moe_max_expert_rows",
    help="rows of the fullest expert, one observation a layer a decode step")
MOE_HELD_PAIRS = _mx.histogram(
    "serving/moe_held_pairs",
    help="(token, expert) pairs routed to an expert this chip holds, one "
         "observation a layer a decode step: the load of a share of a "
         "wider expert-parallel deployment")
STATE_SLOTS_STEPPED = _mx.histogram(
    "serving/state_slots_stepped",
    help="live slots whose recurrent state a decode step advanced, one "
         "observation a step (a model with linear-attention layers)")
UT_EXPECTED_EXIT_STEP = _mx.histogram(
    "serving/ut_expected_exit_step",
    help="the loop step a looped model's exit gate expects to leave at, "
         "sum_t (t + 1) p_t x 100, mean over the live slots, one "
         "observation a decode step (a model that runs its layers several "
         "times a token; with a threshold of 1 every step runs whatever "
         "this reads)")
EVA_CHUNKS_CLOSED = _mx.histogram(
    "serving/eva_chunks_closed",
    help="live slots whose decode step ended a chunk of a compacting cache "
         "group (its summary written where the open window's wait), one "
         "observation a step")
EVA_WINDOWS_CLOSED = _mx.histogram(
    "serving/eva_windows_closed",
    help="live slots whose decode step closed a window of a compacting "
         "cache group (the page-table rotation that puts a summary a chunk "
         "in place of the window's rows), one observation a step")
STATE_POOL_BYTES = _mx.gauge(
    "serving/state_pool_bytes",
    help="bytes of the per-slot recurrent states and convolution tails "
         "the cache holds (0 for a cache without a state group)")
LATENT_RING_BYTES = _mx.gauge(
    "serving/latent_ring_bytes",
    help="bytes of the latent pools whose slots keep the last W rows as a "
         "ring (0 for a latent cache without a window group): what those "
         "layers hold whatever the contexts' lengths")

INDEX_BLOCKS_SCORED = _mx.histogram(
    "serving/index_blocks_scored",
    help="closed blocks of index keys a decode step scored in one sparse "
         "latent layer, over the live slots, one observation a step (a "
         "model whose indexer chooses the rows a query reads)")
INDEX_ROWS_SCORED = _mx.histogram(
    "serving/index_rows_scored",
    help="context rows whose index key a decode step scored in one sparse "
         "latent layer, over the live slots, one observation a step (a "
         "model whose indexer keeps a key a ROW and chooses single rows)")
MOE_GROUPS_KEPT_WITH_HELD = _mx.histogram(
    "serving/moe_groups_kept_with_held",
    help="live rows of which a group that a group-limited router kept "
         "holds an expert this chip holds, one observation a layer a "
         "decode step: the rows that CAN send this share a pair")
INDEX_POOL_BYTES = _mx.gauge(
    "serving/index_pool_bytes",
    help="bytes of the pooled index keys and the open blocks' raw keys a "
         "latent cache keeps beside its rows (0 for a cache without an "
         "index)")

# the engine's prefix cache (serving/prefix_cache.py). The names say
# ``fleet/``: the cache was the fleet's before it was the engine's, and
# operators and tools/dump_metrics.py read these strings
PREFIX_HITS = _mx.counter(
    "fleet/prefix_cache/hits",
    help="prefill requests served from cached prefix KV pages (prefill "
         "compute skipped for the shared prefix)")
PREFIX_MISSES = _mx.counter(
    "fleet/prefix_cache/misses",
    help="prefill lookups that found no cached prefix")
PREFIX_INSERTS = _mx.counter(
    "fleet/prefix_cache/inserts",
    help="prefix entries inserted (pages donated by a FINISHED request)")
PREFIX_EVICTIONS = _mx.counter(
    "fleet/prefix_cache/evictions",
    help="LRU evictions under page-budget pressure")
PREFIX_ENTRIES = _mx.gauge(
    "fleet/prefix_cache/entries", help="live prefix entries")
PREFIX_PAGES = _mx.gauge(
    "fleet/prefix_cache/pages_held",
    help="KV pages owned by the prefix cache (counted by the engine's "
         "page-accounting invariant)")
PREFIX_TOKENS_REUSED = _mx.counter(
    "fleet/prefix_cache/tokens_reused",
    help="prompt tokens whose prefill compute was skipped via a cached "
         "prefix")
PREFIX_POISONED_SKIPPED = _mx.counter(
    "fleet/prefix_cache/poisoned_skipped",
    help="cacheable prefixes NOT inserted because their request did not "
         "FINISH (failed/timed-out pages are never served to a later "
         "request)")

# a model's decode ``stats`` by name (:func:`model_stat`)
_MODEL_STATS = {"moe_experts_touched": MOE_EXPERTS_TOUCHED,
                "moe_max_expert_rows": MOE_MAX_EXPERT_ROWS,
                "moe_held_pairs": MOE_HELD_PAIRS,
                "state_slots_stepped": STATE_SLOTS_STEPPED,
                "index_blocks_scored": INDEX_BLOCKS_SCORED,
                "index_rows_scored": INDEX_ROWS_SCORED,
                "moe_groups_kept_with_held": MOE_GROUPS_KEPT_WITH_HELD,
                "ut_expected_exit_step": UT_EXPECTED_EXIT_STEP,
                "eva_chunks_closed": EVA_CHUNKS_CLOSED,
                "eva_windows_closed": EVA_WINDOWS_CLOSED}


def pages_used(group: str):
    """``serving/pages_used.<group>``: pages a cache group has allocated
    (a gauge a group; get-or-create, so engines of one process share it)."""
    return _mx.gauge("serving/pages_used.%s" % group,
                     help="KV-cache pages allocated in cache group %r" % group)


def page_run_pages(group: str):
    """``serving/page_run_pages.<group>``: pages side by side in the pool
    that the group's free list hands out as one aligned run (1 where the
    group keeps single pages: ``page_pool.py``)."""
    return _mx.gauge("serving/page_run_pages.%s" % group,
                     help="pages an aligned run of cache group %r holds"
                     % group)


def pages_padding(group: str):
    """``serving/pages_padding.<group>``: of ``serving/pages_used
    .<group>``, the pages handed out beyond those asked for (reservations
    rounded up to whole runs: the whole cost of runs)."""
    return _mx.gauge("serving/pages_padding.%s" % group,
                     help="pages allocated in cache group %r beyond those "
                     "asked for" % group)


def attn_rows_read(group: str):
    """``serving/attn_rows_read.<group>``: context rows one layer of a
    cache group read in a decode step, summed over the live slots (a
    histogram a group, one observation a step; get-or-create)."""
    return _mx.histogram(
        "serving/attn_rows_read.%s" % group,
        help="context rows a layer of cache group %r read in a decode "
             "step, over the live slots (a model whose decode returns "
             "the cache's rows_read)" % group)


def attn_rows_context(group: str):
    """``serving/attn_rows_context.<group>``: the whole contexts of the
    slots whose rows :func:`attn_rows_read` counts, where a layer reads
    only the rows it chose: what a dense layer would have read."""
    return _mx.histogram(
        "serving/attn_rows_context.%s" % group,
        help="context rows the live slots HOLD in cache group %r, of which "
             "a sparse layer read serving/attn_rows_read.%s, one "
             "observation a decode step" % (group, group))


def model_stat(name: str):
    """The histogram the engine feeds a model's decode ``stats[name]`` to
    (an observation a value a step), or None for a name it does not know:
    the three ``moe_*``, ``state_slots_stepped``,
    ``ut_expected_exit_step`` and the two ``eva_*_closed`` above and
    ``attn_rows_read.<group>``, ``index_blocks_scored`` and
    ``attn_rows_context.<group>``, looked up once a name."""
    hist = _MODEL_STATS.get(name)
    if hist is None:
        kind, _, group = name.partition(".")
        by_group = {"attn_rows_read": attn_rows_read,
                    "attn_rows_context": attn_rows_context}.get(kind)
        if by_group is not None and group:
            hist = _MODEL_STATS[name] = by_group(group)
    return hist

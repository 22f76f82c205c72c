"""fleet/* instruments: the monitor-registry face of the fleet router.

One module owns every ``fleet/*`` name so the router, replicas and the
prefix cache never race a get-or-create, and tools
(``tools/dump_metrics --selftest``) can assert the full set exists by
importing this module alone. Same hot-path contract as serving.metrics:
module-level handles, a single disabled-branch per call.
"""

from __future__ import annotations

from ..monitor import metrics as _mx
from ..serving import phases as _phases

__all__ = [
    "PHASE_MS",
    "SUBMITTED", "ROUTED", "REQUEUED", "COMPLETED", "REJECTED",
    "DUPLICATE_RESULTS", "QUEUE_DEPTH", "REPLICAS_ALIVE",
    "REPLICA_RESTARTS", "ROLLING_RESTARTS", "NO_HEALTHY_REPLICA",
    "REROUTED",
    "PREFIX_HITS", "PREFIX_MISSES", "PREFIX_INSERTS", "PREFIX_EVICTIONS",
    "PREFIX_ENTRIES", "PREFIX_PAGES", "PREFIX_TOKENS_REUSED",
    "PREFIX_POISONED_SKIPPED",
    "MIGRATIONS_STARTED", "MIGRATIONS_COMPLETED", "MIGRATIONS_FAILED",
    "MIGRATED_PAGES", "MIGRATION_MS",
    "REMOTE_HITS", "REMOTE_MISSES", "REMOTE_SHIPS",
]

SUBMITTED = _mx.counter(
    "fleet/submitted", help="requests accepted into the router's queue")
ROUTED = _mx.counter(
    "fleet/routed", help="request dispatches to a replica (re-dispatches "
                         "after a requeue count again)")
REQUEUED = _mx.counter(
    "fleet/requeued",
    help="in-flight requests re-queued after their replica was lost "
         "(crash/SIGKILL) — replayed idempotently by request id")
COMPLETED = _mx.counter(
    "fleet/completed", help="requests that reached exactly one terminal "
                            "state at the router")
REJECTED = _mx.counter(
    "fleet/rejected",
    help="submissions refused at the router (bounded queue full, or the "
         "router is draining) — typed backpressure, never a silent drop")
DUPLICATE_RESULTS = _mx.counter(
    "fleet/duplicate_results",
    help="late results for an already-terminal request id, ignored "
         "(the exactly-once accounting absorbed a replay race)")
QUEUE_DEPTH = _mx.gauge(
    "fleet/queue_depth", help="requests waiting in the router's queue")
REPLICAS_ALIVE = _mx.gauge(
    "fleet/replicas_alive", help="replicas currently alive")
REPLICA_RESTARTS = _mx.counter(
    "fleet/replica_restarts",
    help="replica respawns (after a crash or a rolling-restart drain)")
ROLLING_RESTARTS = _mx.counter(
    "fleet/rolling_restarts",
    help="completed rolling restarts of the whole fleet")
NO_HEALTHY_REPLICA = _mx.counter(
    "fleet/no_healthy_replica",
    help="dispatch attempts deferred because no healthy replica was "
         "accepting traffic (requests stay queued — degraded replicas "
         "are drained of NEW traffic, not fed)")
REROUTED = _mx.counter(
    "fleet/rerouted",
    help="requests re-routed to a peer after a replica-side typed "
         "rejection (draining/backpressure) — never surfaced as a "
         "terminal rejection")

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

MIGRATIONS_STARTED = _mx.counter(
    "fleet/migrations_started",
    help="cross-replica KV-page migrations begun (disaggregated "
         "prefill->decode handoff, fleet prefix-cache ship, rebalance, "
         "scale-down)")
MIGRATIONS_COMPLETED = _mx.counter(
    "fleet/migrations_completed",
    help="migrations whose pages landed on the destination replica")
MIGRATIONS_FAILED = _mx.counter(
    "fleet/migrations_failed",
    help="migrations aborted (replica died / export miss / import "
         "refused / timeout) — the carried request falls back to a cold "
         "dispatch, never to a loss")
MIGRATED_PAGES = _mx.counter(
    "fleet/migrated_pages",
    help="KV pages shipped across replicas over the binary page frame")
MIGRATION_MS = _mx.histogram(
    "fleet/migration_ms",
    help="end-to-end migration latency (export op sent -> import ack)")

REMOTE_HITS = _mx.counter(
    "fleet/prefix_cache/remote_hits",
    help="requests served on one replica from prefix pages prefilled on "
         "ANOTHER (the fleet-wide prefix cache paid off)")
REMOTE_MISSES = _mx.counter(
    "fleet/prefix_cache/remote_misses",
    help="fleet prefix-index probes whose owner could no longer produce "
         "the entry (evicted/restarted) — the request prefills cold")
REMOTE_SHIPS = _mx.counter(
    "fleet/prefix_cache/remote_ships",
    help="prefix entries shipped between replicas' prefix caches")

# Per-phase latency budgets (the request-autopsy plane): one histogram
# per phase of the serving/phases.py taxonomy, observed per REQUEST from
# the span-derived phase ledger when the router closes a traced run —
# fleet/phase/<name>/ms explains where serving/request_latency_ms went.
PHASE_MS = {
    name: _mx.histogram(
        "fleet/phase/%s/ms" % name,
        help="per-request milliseconds attributed to the %r phase by the "
             "span-derived phase ledger (serving/phases.py)" % name)
    for name in _phases.PHASES
}

"""fleet/* instruments: the monitor-registry face of the fleet router.

One module owns every ``fleet/*`` name the fleet bumps, so the router and
the replicas never race a get-or-create, and tools
(``tools/dump_metrics --selftest``) can assert the full set exists by
importing this module alone. The eight ``fleet/prefix_cache/*`` instruments
of an engine's OWN prefix cache are ``serving.metrics``'s (the engine and
``serving/prefix_cache.py`` bump them; importing this module imports
``serving`` and so registers them too); the ``remote_*`` three below are
the router's. Same hot-path contract as serving.metrics: module-level
handles, a single disabled-branch per call.
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

"""paddle_tpu.fleet — fleet serving: a health-aware router over N engine
replicas.

The layer above one ``serving.ServingEngine``: a bounded-queue router
(:class:`~.router.Router`) dispatching over N replicas — in-process
engines for tests/benches, ``python -m paddle_tpu.fleet.worker``
subprocesses speaking the length-prefixed frame protocol in production
shape — with health-aware routing, session/prefix affinity, a bounded
LRU prefix cache of prefilled KV pages, kill-tolerant exactly-once
request accounting, and per-replica telemetry aggregated into one fleet
snapshot — plus the fleet observability plane: cross-process
distributed tracing with clock-aligned merge (:mod:`.trace`,
tools/fleet_trace.py), two-scope SLO evaluation over the telemetry
rings (:mod:`.slo`), the run-stamped fleet event journal
(:mod:`.events`), and the request autopsy plane (:mod:`.autopsy` +
``serving.phases``): per-request phase ledgers derived from the merged
span stream, ``fleet/phase/*`` latency budgets, and automatic
SLO-breach root-cause verdicts (tools/fleet_autopsy.py). See ROADMAP
item 2 and tools/fleet_top.py.
"""

from . import metrics  # registers every fleet/* instrument
from .autopsy import (BreachAutopsy, autopsy_breaches, build_ledgers,
                      phase_stats, run_autopsy)
from .events import FleetEventLog, read_events
from ..serving.prefix_cache import PrefixCache, PrefixEntry, prefix_key
from .protocol import FrameReader, read_frame, send_frame
from .replica import (InProcessReplica, ProcessReplica, SimConfig,
                      SimEngine, sim_token)
from .router import (FleetBackpressure, FleetConfig, FleetRequest, Router,
                     aggregate_telemetry)
from .slo import FleetSLO, fleet_slos_from_env, merge_fleet_docs
from .trace import (close_orphans, fleet_request_spans, load_fragments,
                    validate_fleet_spans)

__all__ = [
    "Router", "FleetConfig", "FleetRequest", "FleetBackpressure",
    "aggregate_telemetry",
    "PrefixCache", "PrefixEntry", "prefix_key",
    "InProcessReplica", "ProcessReplica", "SimConfig", "SimEngine",
    "sim_token",
    "FrameReader", "read_frame", "send_frame",
    "FleetEventLog", "read_events",
    "FleetSLO", "fleet_slos_from_env", "merge_fleet_docs",
    "close_orphans", "fleet_request_spans", "load_fragments",
    "validate_fleet_spans",
    "BreachAutopsy", "autopsy_breaches", "build_ledgers", "phase_stats",
    "run_autopsy",
    "metrics",
]

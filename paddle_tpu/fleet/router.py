"""Health-aware fleet router: one bounded queue over N engine replicas.

The front door of the serving fleet. The router owns a bounded request
queue and a set of replicas (:class:`~.replica.InProcessReplica` for
tests/benches, :class:`~.replica.ProcessReplica` workers in production
shape) and guarantees, through every failure mode it knows about:

* **exactly-once terminal accounting** — every accepted request reaches
  exactly ONE terminal state (finished/failed/timeout/rejected), recorded
  on its :class:`FleetRequest`. Late/duplicate results after a requeue
  race are absorbed (``fleet/duplicate_results``), never double-counted;
* **crash tolerance** — a replica that dies (SIGKILL, OOM) is detected via
  its pipe/exit status; its in-flight requests requeue idempotently by
  fleet id (``fleet/requeued``) and replay bit-identically: the router
  pins every request's seed at submission, and sampling is keyed (seed,
  absolute position), so a retried stream equals the unkilled twin's;
* **health-aware dispatch** — replicas whose ``health()`` reports
  ``degraded`` (SLO breach, absorbed faults) are drained of NEW traffic
  but not killed; with no healthy replica accepting, requests stay queued
  (``fleet/no_healthy_replica``) rather than failing;
* **graceful rollout** — :meth:`rolling_restart` = per replica
  ``drain(timeout_s)`` → respawn. Requests the drain sheds come back as
  typed ``draining`` rejections and are re-routed to peers — zero
  rejected-by-bug.

Affinity: ``affinity="prefix"`` routes by a stable hash of the first
``affinity_tokens`` prompt tokens, so one conversation/system-prompt
cohort lands on one replica and its KV pages (and prefix-cache entries)
stay hot there; ``"round_robin"`` is the reference spread.

Disaggregation (``roles="P:D"``): the fleet splits into prefill-heavy
and decode-heavy replicas. A request prefills on a prefill replica (an
internal one-token job), its KV pages ship to a decode replica over the
binary page frame (fleet.protocol), and decoding resumes there through
the engine's prefix-resume path — so prefill bursts never interleave
with (and stall) in-flight decode steps. The same page-migration
primitive powers the fleet-wide prefix cache (a prefix cached on
replica A serves a request routed to B), pool-pressure rebalancing, and
live :meth:`Router.scale_down`. Migrated streams are bit-identical to
their unmigrated twins (sampling is keyed (seed, position), and KV
pages are exact byte copies); a failed migration falls back to a cold
dispatch, never to a loss.

The router is single-threaded by design: :meth:`pump` is the event loop
tick (poll replicas → account results → detect deaths → dispatch), and
everything else composes on it. No locks, no callback hell — the same
drive-loop shape as ``ServingEngine.step``.
"""

from __future__ import annotations

import itertools
import json
import os
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence

from ..monitor import runlog as _runlog
from ..monitor import tracer as _tr
from ..serving.prefix_cache import prefix_key
from ..serving.request import FAILED, FINISHED, REJECTED, TIMEOUT
from . import autopsy as _autopsy
from . import metrics as _fm
from . import trace as _ftr
from .events import KIND_BREACH_AUTOPSY, FleetEventLog
from .replica import InProcessReplica, ProcessReplica
from .slo import FleetSLO, fleet_slos_from_env

__all__ = ["FleetConfig", "FleetRequest", "FleetBackpressure", "Router",
           "aggregate_telemetry"]

# distinguishes trace ids of two Routers in one process (the chaos
# drill's replay twin must never collide with the original's ids)
_ROUTER_SEQ = itertools.count()

_TERMINAL = (FINISHED, FAILED, TIMEOUT, REJECTED)


class FleetBackpressure(RuntimeError):
    """The router's bounded queue is full (or it is draining): typed
    shed-or-retry, mirroring serving.BackpressureError one level up."""


class FleetRequest:
    """One request as the ROUTER accounts it. The id is router-assigned
    and stable across requeues (the idempotency key); the seed is ALWAYS
    pinned at submission — derived deterministically from the id when the
    caller passes None — so a replay after a replica loss regenerates the
    identical sampled stream."""

    __slots__ = ("id", "prompt", "max_new_tokens", "deadline_s",
                 "temperature", "top_k", "seed", "state", "tokens", "error",
                 "attempts", "last_replica", "submitted_t", "finished_t",
                 "trace_id", "dispatches", "dispatched_t", "queued_since",
                 "internal", "pin_replica", "no_migrate")

    def __init__(self, rid: int, prompt: Sequence[int], max_new_tokens: int,
                 deadline_s: Optional[float] = None, temperature: float = 0.0,
                 top_k: int = 0, seed: Optional[int] = None,
                 trace_id: Optional[str] = None):
        self.id = int(rid)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.deadline_s = deadline_s
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # never let a replica pick an id-derived seed: engine-local request
        # ids differ between the first attempt and a requeued replay
        self.seed = (int(seed) if seed is not None
                     else (self.id * 1000003 + 0x5EED) & 0x7FFFFFFF)
        self.state = "queued"
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.attempts = 0
        self.last_replica: Optional[int] = None
        self.submitted_t = time.perf_counter()
        self.finished_t: Optional[float] = None
        # tracing: one trace_id across every attempt of this request;
        # ``dispatches`` is the 1-based attempt number the spans carry
        self.trace_id = trace_id if trace_id else "fr-%d" % self.id
        self.dispatches = 0
        self.dispatched_t: Optional[float] = None   # open attempt start
        self.queued_since: Optional[float] = self.submitted_t
        # router-side flags (never on the wire): ``internal`` marks the
        # scaffolding prefill jobs of a disaggregated handoff (excluded
        # from user accounting); ``pin_replica`` targets a dispatch at the
        # replica a migration warmed; ``no_migrate`` is the one-shot fuse
        # that sends a request cold after its migration failed
        self.internal = False
        self.pin_replica: Optional[int] = None
        self.no_migrate = False

    @property
    def terminal(self) -> bool:
        return self.state in _TERMINAL

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_t is None:
            return None
        return self.finished_t - self.submitted_t

    def doc(self) -> dict:
        """The wire/replica form of this request. ``attempt`` is the
        current dispatch count, so the replica's engine stamps its spans
        with the attempt they belong to (a requeued replay is attempt 2
        of the SAME trace_id)."""
        return {"id": self.id, "prompt": self.prompt,
                "max_new_tokens": self.max_new_tokens,
                "deadline_s": self.deadline_s,
                "temperature": self.temperature, "top_k": self.top_k,
                "seed": self.seed, "trace_id": self.trace_id,
                "attempt": self.dispatches}

    def __repr__(self):
        return ("FleetRequest(id=%d, state=%s, out=%d, attempts=%d)"
                % (self.id, self.state, len(self.tokens), self.attempts))


class FleetConfig:
    """Router geometry + policy.

    ``replicas``: replica count, or ``"auto"`` to consult the autotuned
    config table (tune kernel ``fleet.router``; falls back to 2).
    ``mode``: ``"inprocess"`` (requires ``engine_factory``, a callable
    ``index -> engine``) or ``"process"`` (requires ``engine_spec``, the
    worker spec dict — see fleet.worker). ``affinity``: ``"prefix"`` or
    ``"round_robin"``; ``affinity_tokens`` is the prefix-hash window.
    ``max_outstanding`` caps dispatched-but-unresolved requests per
    replica (bounds the requeue set a crash can strand). ``requeue_limit``
    bounds replays per request before it terminally FAILs ("replica
    lost"). ``telemetry_base``: per-replica telemetry ring dirs are
    created under it (``replica_<i>/``) in process mode.

    Observability plane (all default-off; env fallbacks make every tool
    armable without code changes):

    * ``trace_dir`` (env ``PADDLE_TPU_FLEET_TRACE_DIR``) — distributed
      tracing: the router runs the host tracer, workers get per-spawn
      fragment files + a clock-offset handshake, and ``close()`` writes
      the fragments manifest ``tools/fleet_trace.py`` merges;
    * ``slos`` (env ``PADDLE_TPU_FLEET_SLO``, ``monitor.slo`` grammar) —
      evaluated per replica AND fleet-aggregate over the telemetry rings
      (needs ``telemetry_base``); a replica in breach is drained of new
      traffic like any degraded replica;
    * ``event_log`` (env ``PADDLE_TPU_FLEET_EVENTS``) — JSONL fleet
      lifecycle journal (fleet.events);
    * ``spec_overrides`` — {replica index: spec keys merged over
      ``engine_spec`` for that replica} (process mode), e.g. a per-replica
      ``fault_plan`` for chaos drills.

    Disaggregation / migration plane (see the migration section of
    :class:`Router`):

    * ``roles`` (env ``PADDLE_TPU_FLEET_ROLES``) — ``None`` keeps every
      replica uniform; ``"P:D"`` (or ``{"prefill": P, "decode": D}``)
      splits the fleet into P prefill-heavy + D decode-heavy replicas
      (overrides ``replicas`` to P+D); ``"auto"`` consults the tune table
      (kernel ``fleet.roles``, fallback 1:1);
    * ``page_size`` — granularity of the fleet prefix index; MUST match
      the replica engines' KV page size for migrated prefixes to resume;
    * ``migrate_min_tokens`` (env ``PADDLE_TPU_FLEET_MIGRATE_MIN``) —
      prompts whose page-aligned prefix is shorter dispatch cold (a ship
      costs a round trip; tiny prefixes are not worth it);
    * ``migration_timeout_s`` (env ``PADDLE_TPU_FLEET_MIGRATION_TIMEOUT_S``)
      — a migration not acknowledged in time fails and its requests fall
      back to a cold dispatch (never lost);
    * ``fleet_prefix`` (env ``PADDLE_TPU_FLEET_PREFIX``) — arm the
      fleet-wide prefix index in a uniform fleet (role-split fleets arm
      it implicitly: the handoff rides the same index);
    * ``rebalance_util`` (env ``PADDLE_TPU_FLEET_REBALANCE_UTIL``) — KV
      page-pool utilization above which a replica's prefix entries are
      migrated (shipped + evicted) to the least-loaded peer; 0 disables.
    """

    def __init__(self, replicas=2, mode: str = "inprocess",
                 affinity: str = "prefix", affinity_tokens: int = 16,
                 max_queue: int = 1024, max_outstanding: int = 16,
                 requeue_limit: int = 2, drain_timeout_s: float = 30.0,
                 engine_factory: Optional[Callable] = None,
                 engine_spec: Optional[dict] = None,
                 auto_restart: bool = True,
                 telemetry_base: Optional[str] = None,
                 health_every: int = 16,
                 trace_dir: Optional[str] = None,
                 slos: Optional[Sequence] = None,
                 event_log: Optional[str] = None,
                 spec_overrides: Optional[Dict[int, dict]] = None,
                 roles=None, page_size: int = 16,
                 migrate_min_tokens: Optional[int] = None,
                 migration_timeout_s: Optional[float] = None,
                 fleet_prefix: Optional[bool] = None,
                 rebalance_util: Optional[float] = None):
        if mode not in ("inprocess", "process"):
            raise ValueError("mode must be 'inprocess' or 'process'")
        if affinity not in ("prefix", "round_robin"):
            raise ValueError("affinity must be 'prefix' or 'round_robin'")
        self.replicas_source = "explicit"
        if replicas in (None, "auto"):
            replicas, affinity_cfg, self.replicas_source = \
                self._tuned_router(affinity)
            affinity = affinity_cfg
        self.replicas = max(1, int(replicas))
        if roles is None:
            roles = os.environ.get("PADDLE_TPU_FLEET_ROLES") or None
        self.roles: Optional[Dict[str, int]] = None
        self.roles_source = "none"
        if roles:
            self.roles, self.roles_source = self._parse_roles(roles)
            self.replicas = self.roles["prefill"] + self.roles["decode"]
        self.page_size = max(1, int(page_size))
        if migrate_min_tokens is None:
            migrate_min_tokens = int(os.environ.get(
                "PADDLE_TPU_FLEET_MIGRATE_MIN", self.page_size))
        self.migrate_min_tokens = max(1, int(migrate_min_tokens))
        if migration_timeout_s is None:
            migration_timeout_s = float(os.environ.get(
                "PADDLE_TPU_FLEET_MIGRATION_TIMEOUT_S", "10.0"))
        self.migration_timeout_s = float(migration_timeout_s)
        if fleet_prefix is None:
            env = os.environ.get("PADDLE_TPU_FLEET_PREFIX")
            fleet_prefix = None if env is None else \
                env.strip().lower() in ("1", "true", "yes", "on")
        self.fleet_prefix = fleet_prefix
        if rebalance_util is None:
            rebalance_util = float(os.environ.get(
                "PADDLE_TPU_FLEET_REBALANCE_UTIL", "0.85"))
        self.rebalance_util = float(rebalance_util)
        self.mode = mode
        self.affinity = affinity
        self.affinity_tokens = max(1, int(affinity_tokens))
        self.max_queue = int(max_queue)
        self.max_outstanding = max(1, int(max_outstanding))
        self.requeue_limit = max(0, int(requeue_limit))
        self.drain_timeout_s = float(drain_timeout_s)
        self.engine_factory = engine_factory
        self.engine_spec = engine_spec
        self.auto_restart = bool(auto_restart)
        self.telemetry_base = telemetry_base
        self.health_every = max(1, int(health_every))
        if trace_dir is None:
            trace_dir = os.environ.get("PADDLE_TPU_FLEET_TRACE_DIR") or None
        self.trace_dir = trace_dir
        self.slos = list(slos) if slos is not None else fleet_slos_from_env()
        if event_log is None:
            event_log = os.environ.get("PADDLE_TPU_FLEET_EVENTS") or None
        self.event_log = event_log
        self.spec_overrides = dict(spec_overrides or {})
        if mode == "inprocess" and engine_factory is None:
            raise ValueError("inprocess mode needs engine_factory")
        if mode == "process" and engine_spec is None:
            raise ValueError("process mode needs engine_spec")

    @staticmethod
    def _parse_roles(spec):
        """(roles dict, source) from ``"P:D"`` / dict / ``"auto"``. The
        tune-table path never raises — a role-split fleet must come up
        with no table on disk (1:1 fallback)."""
        if spec == "auto":
            try:
                from .. import tune

                cfg, src = tune.resolve_fleet_roles()
                return ({"prefill": max(1, int(cfg.get("prefill", 1))),
                         "decode": max(1, int(cfg.get("decode", 1)))}, src)
            except Exception:
                return {"prefill": 1, "decode": 1}, "default"
        if isinstance(spec, str):
            p_str, _, d_str = spec.partition(":")
            try:
                spec = {"prefill": int(p_str), "decode": int(d_str)}
            except ValueError:
                raise ValueError(
                    "roles spec must be 'P:D', 'auto' or a dict; got %r"
                    % (spec,))
        p = int(spec.get("prefill", 0))
        d = int(spec.get("decode", 0))
        if p < 1 or d < 1:
            raise ValueError(
                "roles needs >= 1 prefill and >= 1 decode replica, got "
                "prefill=%d decode=%d" % (p, d))
        return {"prefill": p, "decode": d}, "explicit"

    @staticmethod
    def _tuned_router(affinity_default: str):
        """(replicas, affinity, source) from the tune table; a safe
        (2, default-affinity, "default") on any failure — the fleet must
        come up with no table on disk."""
        try:
            from .. import tune

            cfg, src = tune.resolve_fleet_router()
            return (int(cfg.get("replicas", 2)),
                    cfg.get("affinity", affinity_default), src)
        except Exception:
            return 2, affinity_default, "default"


class _Migration:
    """One in-flight cross-replica KV-page ship, whatever its purpose:

    * ``disagg`` — prefill/decode handoff: an internal prefill job warms
      ``src`` (a prefill replica), the donated pages ship to ``dst`` (a
      decode replica), the user request dispatches pinned to ``dst``;
    * ``remote_hit`` — the fleet prefix index says another replica owns
      this prompt's prefix: ship it to the picked replica first;
    * ``rebalance`` — pool-pressure relief: ship one prefix entry to the
      least-loaded peer, then evict it at the source (ship+evict = move);
    * ``scale_down`` — a retiring replica exports its running requests'
      immutable prompt-prefix pages so their re-dispatch lands warm.

    Stages: ``prefill`` (disagg only: waiting on the internal job) →
    ``export`` (export op sent to src) → ``import`` (binary page frame
    sent to dst, waiting for the ack). ANY failure — export miss, import
    refusal, replica death, timeout — fails the migration and every
    carried request falls back to a cold dispatch with its ``no_migrate``
    fuse blown; a migration can delay a request, never lose one."""

    __slots__ = ("xid", "purpose", "key", "tokens", "src", "dst", "fr",
                 "waiters", "stage", "t0", "prefill_id", "n_pages")

    def __init__(self, xid: int, purpose: str, tokens, fr):
        self.xid = int(xid)
        self.purpose = purpose
        self.tokens = tuple(int(t) for t in tokens)
        self.key = prefix_key(self.tokens)
        self.fr = fr                       # user request carried (or None)
        self.waiters: List[FleetRequest] = []
        self.src: Optional[int] = None
        self.dst: Optional[int] = None
        self.stage = "start"
        self.t0 = time.perf_counter()
        self.prefill_id: Optional[int] = None  # disagg internal job id
        self.n_pages = 0

    def requests(self) -> List["FleetRequest"]:
        out = [self.fr] if self.fr is not None else []
        out.extend(self.waiters)
        return out

    def __repr__(self):
        return ("_Migration(xid=%d, %s, stage=%s, src=%s, dst=%s)"
                % (self.xid, self.purpose, self.stage, self.src, self.dst))


class Router:
    """See module docstring. Lifecycle: construct (spawns replicas) →
    ``submit``/``pump`` (or ``wait_all``) → ``drain``/``close``."""

    def __init__(self, config: FleetConfig):
        self.cfg = config
        self._seq = next(_ROUTER_SEQ)
        self._queue: Deque[FleetRequest] = deque()
        self._requests: Dict[int, FleetRequest] = {}
        self._next_id = 0
        self._rr = 0          # round-robin cursor
        self._ticks = 0
        self._draining = False
        self._closed = False
        self._t0 = time.perf_counter()
        self._health: Dict[int, dict] = {}       # replica index -> last doc
        self._rep_done: Dict[int, int] = {}      # replica index -> completed
        self._rep_lat: Dict[int, List[float]] = {}
        # -- observability plane --------------------------------------------
        self._trace = bool(config.trace_dir)
        self._own_tracer = False
        self._spawn_gen: Dict[int, int] = {}     # replica -> spawn count
        self._worker_frags: List[dict] = []      # manifest worker entries
        if self._trace:
            os.makedirs(config.trace_dir, exist_ok=True)
            if not _tr.active():
                _tr.start_tracing()
                self._own_tracer = True
        self._events = (FleetEventLog(config.event_log)
                        if config.event_log else None)
        self._slo_breached: Dict[int, dict] = {}  # replica -> last breach doc
        self._fleet_breach: Optional[dict] = None
        self._fleet_breach_count = 0
        # every breach this run, scope-tagged: the close-time autopsy's
        # input (bounded by dedup inside autopsy_breaches)
        self._breach_log: List[dict] = []
        self._phase_stats: Optional[dict] = None  # set by _run_autopsy
        self._autopsies: List[dict] = []
        self._slo: Optional[FleetSLO] = None
        if config.slos and config.telemetry_base:
            self._slo = FleetSLO(
                config.slos,
                on_replica_breach=self._on_replica_slo_breach,
                on_replica_clear=self._on_replica_slo_clear,
                on_fleet_breach=self._on_fleet_slo_breach,
                on_fleet_clear=self._on_fleet_slo_clear)
        self._last_obs_t = 0.0   # throttles ring reads + snapshot writes
        # -- migration / disaggregation plane -------------------------------
        # fleet prefix index: prefix key -> {"tokens", "owners"} — which
        # replicas (probably) hold this prefix in their LOCAL prefix
        # cache. Ownership is optimistic (recorded at FINISH, confirmed
        # or corrected by the export op), so the index is a routing hint,
        # never a correctness dependency.
        self._fleet_prefix = (config.fleet_prefix
                              if config.fleet_prefix is not None
                              else config.roles is not None)
        self._prefix_index: Dict[str, dict] = {}
        self._migrations: Dict[int, _Migration] = {}
        self._mig_seq = itertools.count(1)
        self._retired: set = set()   # scale-down'd indices: never respawn
        self._replicas = [self._spawn(i) for i in range(self.cfg.replicas)]
        _fm.REPLICAS_ALIVE.set(len(self._replicas))
        self._emit_event("fleet_start", replicas=self.cfg.replicas,
                         mode=self.cfg.mode, roles=self.cfg.roles,
                         trace_dir=self.cfg.trace_dir,
                         telemetry_base=self.cfg.telemetry_base)

    # -- observability callbacks/sinks ----------------------------------------
    def _emit_event(self, kind: str, **fields) -> None:
        if self._events is not None:
            self._events.emit(kind, **fields)

    def _on_replica_slo_breach(self, index: int, breach) -> None:
        doc = breach.to_doc()
        self._slo_breached[index] = doc
        self._breach_log.append(dict(doc, scope="replica", replica=index))
        self._emit_event("slo_breach", scope="replica", replica=index, **doc)

    def _on_replica_slo_clear(self, index: int) -> None:
        if self._slo_breached.pop(index, None) is not None:
            self._emit_event("slo_clear", scope="replica", replica=index)

    def _on_fleet_slo_breach(self, breach) -> None:
        self._fleet_breach = breach.to_doc()
        self._fleet_breach_count += 1
        self._breach_log.append(dict(self._fleet_breach, scope="fleet"))
        self._emit_event("slo_breach", scope="fleet", **self._fleet_breach)

    def _on_fleet_slo_clear(self) -> None:
        if self._fleet_breach is not None:
            self._fleet_breach = None
            self._emit_event("slo_clear", scope="fleet")

    # -- replica lifecycle ----------------------------------------------------
    def _spawn(self, index: int):
        self._health[index] = {"status": "ok"}
        self._rep_done.setdefault(index, 0)
        self._rep_lat.setdefault(index, [])
        gen = self._spawn_gen.get(index, 0) + 1
        self._spawn_gen[index] = gen
        if self.cfg.mode == "inprocess":
            rep = InProcessReplica(self.cfg.engine_factory(index), index)
            rep.role = self._role_for(index)
            self._emit_event("spawn", replica=index, gen=gen,
                             mode="inprocess", role=rep.role)
            return rep
        tdir = None
        if self.cfg.telemetry_base:
            tdir = os.path.join(self.cfg.telemetry_base,
                                "replica_%d" % index)
        tfile = None
        if self._trace:
            # one fragment file per SPAWN: a respawned replica must not
            # clobber its predecessor's (possibly never-flushed) fragment
            tfile = os.path.join(self.cfg.trace_dir,
                                 "worker_r%d_g%d.json" % (index, gen))
        spec = dict(self.cfg.engine_spec)
        spec.update(self.cfg.spec_overrides.get(index, {}))
        rep = ProcessReplica(spec, index, telemetry_dir=tdir,
                             trace_file=tfile)
        rep.role = self._role_for(index)
        if tfile:
            self._worker_frags.append({
                "file": os.path.basename(tfile), "replica": index,
                "gen": gen, "pid": rep.pid,
                "offset_us": rep.clock_offset_us,
                "rtt_us": rep.clock_rtt_us})
        if self._trace:
            _ftr.on_lifecycle_instant(
                "spawn replica %d" % index,
                args={"replica": index, "gen": gen, "pid": rep.pid})
        self._emit_event("spawn", replica=index, gen=gen, pid=rep.pid,
                         role=rep.role,
                         clock_offset_us=rep.clock_offset_us,
                         clock_rtt_us=rep.clock_rtt_us)
        return rep

    def _role_for(self, index: int) -> str:
        """Replica role under the configured split: the first P indices
        are prefill-heavy, the rest decode-heavy; no split = uniform."""
        r = self.cfg.roles
        if not r:
            return "uniform"
        return "prefill" if index < r["prefill"] else "decode"

    def _respawn(self, index: int) -> None:
        # a respawned replica starts with empty caches: whatever prefixes
        # the index credited to it are gone
        self._drop_owner_everywhere(index)
        self._replicas[index] = self._spawn(index)
        _fm.REPLICA_RESTARTS.inc()
        self._emit_event("restart", replica=index,
                         gen=self._spawn_gen.get(index))

    # -- submission -----------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               deadline_s: Optional[float] = None, temperature: float = 0.0,
               top_k: int = 0, seed: Optional[int] = None) -> FleetRequest:
        """Accept a request into the bounded queue. Raises
        :class:`FleetBackpressure` (typed, accounted) when full or
        draining — the router never silently drops."""
        if self._closed or self._draining:
            _fm.REJECTED.inc()
            raise FleetBackpressure("router is draining/closed")
        if len(self._queue) >= self.cfg.max_queue:
            _fm.REJECTED.inc()
            raise FleetBackpressure(
                "fleet queue full (%d)" % self.cfg.max_queue)
        fr = FleetRequest(self._next_id, prompt, max_new_tokens,
                          deadline_s=deadline_s, temperature=temperature,
                          top_k=top_k, seed=seed,
                          trace_id="fr%d-%d" % (self._seq, self._next_id))
        self._next_id += 1
        self._requests[fr.id] = fr
        self._queue.append(fr)
        _fm.SUBMITTED.inc()
        _fm.QUEUE_DEPTH.set(len(self._queue))
        if self._trace:
            _ftr.on_submitted(fr)
        return fr

    # -- accounting -----------------------------------------------------------
    def _finalize(self, fr: FleetRequest, state: str,
                  tokens: Optional[List[int]] = None,
                  error: Optional[str] = None) -> None:
        """THE exactly-once funnel: every terminal outcome lands here, and
        an already-terminal request absorbs the duplicate instead of
        flipping state (a SIGKILL race can produce both a late result and
        a requeued completion — first one wins, deterministically)."""
        if fr.terminal:
            _fm.DUPLICATE_RESULTS.inc()
            return
        fr.state = state
        if tokens is not None:
            fr.tokens = list(tokens)
        fr.error = error
        fr.finished_t = time.perf_counter()
        fr.queued_since = None
        if fr.internal:
            # scaffolding (disagg prefill job): no user-facing accounting,
            # no trace spans — but its outcome advances (or fails) the
            # migration that spawned it
            if state == FINISHED and self._fleet_prefix \
                    and fr.last_replica is not None:
                self._record_prefix(fr.prompt, fr.last_replica)
            self._on_internal_done(fr)
            return
        _fm.COMPLETED.inc()
        if self._trace:
            _ftr.on_terminal(fr)   # also closes a never-dispatched wait
        if fr.last_replica is not None:
            self._rep_done[fr.last_replica] = \
                self._rep_done.get(fr.last_replica, 0) + 1
            self._rep_lat.setdefault(fr.last_replica, []).append(
                (fr.finished_t - fr.submitted_t) * 1e3)
        if state == FINISHED and self._fleet_prefix \
                and fr.last_replica is not None:
            # its engine (probably) cached the aligned prefix at retire:
            # record optimistic ownership in the fleet index
            self._record_prefix(fr.prompt, fr.last_replica)

    def _requeue(self, fr: FleetRequest, why: str) -> None:
        if fr.terminal:
            return
        fr.attempts += 1
        if fr.attempts > self.cfg.requeue_limit:
            self._finalize(fr, FAILED,
                           error="replica lost %d times (%s)"
                                 % (fr.attempts, why))
            return
        fr.state = "queued"
        fr.queued_since = time.perf_counter()  # second queued span opens
        self._queue.appendleft(fr)  # retries go to the head: oldest first
        self._emit_event("requeue", trace_id=fr.trace_id, id=fr.id,
                         attempts=fr.attempts, why=why)

    def _handle_event(self, rep, ev: dict) -> None:
        kind = ev.get("ev")
        if kind == "health":
            self._health[rep.index] = ev.get("health", {"status": "ok"})
            return
        if kind == "pages":
            self._on_pages(rep, ev)
            return
        if kind == "imported":
            self._on_imported(rep, ev)
            return
        if kind == "evicted":
            self._emit_event("prefix_evicted", replica=rep.index,
                             xid=ev.get("xid"), pages=ev.get("pages"))
            return
        if kind != "result":
            return
        fr = self._requests.get(ev.get("id"))
        if fr is None:
            return
        state = ev.get("state")
        if state == REJECTED and ev.get("kind") in ("draining",
                                                    "backpressure"):
            # replica-side typed shed: route to a peer, never terminal
            _fm.REROUTED.inc()
            if self._trace and not fr.terminal and not fr.internal:
                _ftr.on_attempt_end(fr, rep.index, "rerouted")
            fr.dispatched_t = None
            self._emit_event("reroute", trace_id=fr.trace_id, id=fr.id,
                             replica=rep.index, why=ev.get("kind"))
            self._requeue_reroute(fr)
            return
        if self._trace and not fr.terminal and not fr.internal:
            _ftr.on_attempt_end(fr, rep.index, state)
        fr.dispatched_t = None
        self._finalize(fr, state, ev.get("tokens"), ev.get("error"))

    def _requeue_reroute(self, fr: FleetRequest) -> None:
        """A typed reroute (peer draining/backpressured) does not count
        against the requeue budget — nothing was lost, only refused."""
        if fr.terminal:
            return
        fr.state = "queued"
        fr.queued_since = time.perf_counter()
        self._queue.appendleft(fr)

    def _lose(self, fr: FleetRequest, replica_index: int, why: str,
              tag: str = "killed") -> None:
        """One lost in-flight request, accounted by kind: user requests
        requeue idempotently; internal prefill jobs terminate FAILED
        (their migration fails and its user request falls back cold —
        re-running scaffolding on a respawned replica buys nothing)."""
        if fr.internal:
            self._finalize(fr, FAILED, error=why)
            return
        _fm.REQUEUED.inc()
        if self._trace:
            # the worker never reported: close its attempt at detection
            # time, tagged killed+synthetic
            _ftr.on_attempt_end(fr, replica_index, tag, killed=True)
        fr.dispatched_t = None
        self._requeue(fr, why)

    # -- the event-loop tick --------------------------------------------------
    def pump(self) -> int:
        """One router cycle: poll replicas (pumps in-process engines one
        step), account events, detect/recover deaths, dispatch the queue.
        Returns the number of requests still unresolved."""
        self._ticks += 1
        for rep in list(self._replicas):
            for ev in rep.poll():
                self._handle_event(rep, ev)
        for i, rep in enumerate(self._replicas):
            if not rep.alive:
                lost = list(rep.inflight.values())
                rep.inflight.clear()
                if lost or rep.accepting:
                    # accepting distinguishes a detected death from an
                    # already-accounted drain (accepting was lowered)
                    self._emit_event("kill_detected", replica=i,
                                     pid=getattr(rep, "pid", None),
                                     lost=len(lost))
                    if self._trace:
                        _ftr.on_lifecycle_instant(
                            "replica %d died" % i,
                            args={"replica": i, "lost": len(lost)})
                rep.accepting = False
                for rdoc in lost:
                    fr = self._requests.get(rdoc["id"])
                    if fr is not None and not fr.terminal:
                        self._lose(fr, i, "replica %d died" % i)
                # the dead replica's caches died with it; any migration
                # touching it can never complete — fail them now so their
                # requests fall back immediately instead of timing out
                self._drop_owner_everywhere(i)
                self._fail_migrations_for(i, "replica %d died" % i)
                if self.cfg.auto_restart and not self._draining \
                        and not self._closed and i not in self._retired:
                    self._respawn(i)
        if self._migrations:
            now = time.perf_counter()
            for m in list(self._migrations.values()):
                if now - m.t0 > self.cfg.migration_timeout_s:
                    self._fail_migration(m, "timeout after %.1fs"
                                         % (now - m.t0))
        if self.cfg.mode == "process" \
                and self._ticks % self.cfg.health_every == 0:
            for rep in self._replicas:
                if rep.alive:
                    rep.health()  # answer arrives as a health event
        if (self._slo is not None or self.cfg.telemetry_base) \
                and self._ticks % self.cfg.health_every == 0:
            now = time.monotonic()
            if now - self._last_obs_t >= 0.5:  # ring reads are file I/O
                self._last_obs_t = now
                if self._slo is not None:
                    self.evaluate_slos()
                self._write_snapshot()
        if self._fleet_prefix and self.cfg.rebalance_util > 0 \
                and self._ticks % self.cfg.health_every == 0:
            self._auto_rebalance()
        self._dispatch()
        _fm.QUEUE_DEPTH.set(len(self._queue))
        _fm.REPLICAS_ALIVE.set(sum(1 for r in self._replicas if r.alive))
        return sum(1 for fr in self._requests.values() if not fr.terminal)

    def _replica_healthy(self, rep) -> bool:
        if not rep.alive or not rep.accepting:
            return False
        if rep.index in self._slo_breached:
            return False   # SLO breach == degraded: drained, not killed
        if rep.kind == "inprocess":
            h = rep.health()
        else:
            h = self._health.get(rep.index, {"status": "ok"})
        return h.get("status", "ok") == "ok"

    def _role_ok(self, rep, fr: FleetRequest) -> bool:
        """Role gate in a split fleet: user requests decode on
        decode-heavy replicas; internal prefill jobs run on
        prefill-heavy ones; uniform replicas take anything."""
        if fr.internal:
            return rep.role in ("prefill", "uniform")
        return rep.role in ("decode", "uniform")

    def _dispatchable(self, rep, fr: FleetRequest) -> bool:
        return (self._replica_healthy(rep)
                and rep.index not in self._retired
                and self._role_ok(rep, fr)
                and len(rep.inflight) < self.cfg.max_outstanding)

    def _pick_replica(self, fr: FleetRequest):
        if fr.pin_replica is not None:
            # a migration warmed (or a disagg handoff targets) exactly one
            # replica: dispatch there or wait for it — unless it is gone,
            # in which case the pin dissolves into a cold pick
            pin = fr.pin_replica
            if 0 <= pin < len(self._replicas):
                rep = self._replicas[pin]
                if self._dispatchable(rep, fr):
                    return rep
                if rep.alive and rep.accepting \
                        and pin not in self._retired:
                    return None   # busy/degraded, not gone: stay queued
            fr.pin_replica = None
        n = len(self._replicas)
        if self.cfg.affinity == "prefix":
            window = fr.prompt[:self.cfg.affinity_tokens]
            start = int(prefix_key(window)[:8], 16) % n
        else:
            start = self._rr % n
            self._rr += 1
        for off in range(n):
            rep = self._replicas[(start + off) % n]
            if self._dispatchable(rep, fr):
                return rep
        return None

    def _dispatch_to(self, fr: FleetRequest, rep) -> None:
        fr.state = "dispatched"
        fr.last_replica = rep.index
        fr.dispatches += 1
        if self._trace and not fr.internal:
            _ftr.on_dispatch(fr, rep.index)  # closes the queued span
        fr.queued_since = None
        fr.dispatched_t = time.perf_counter()
        rep.submit(fr.doc())
        if not fr.internal:
            _fm.ROUTED.inc()

    def _dispatch(self) -> None:
        # one pass over the queue: each request either dispatches, starts
        # (or joins) a migration, or goes back where it was. A pinned or
        # internal request whose one target is busy must not block the
        # unpinned traffic behind it, so it is skipped, not a barrier.
        skipped: List[FleetRequest] = []
        while self._queue:
            fr = self._queue.popleft()
            if fr.terminal:  # finalized while queued (router drain race)
                continue
            if self._maybe_migrate(fr):
                continue
            rep = self._pick_replica(fr)
            if rep is None:
                _fm.NO_HEALTHY_REPLICA.inc()
                skipped.append(fr)
                if fr.pin_replica is None and not fr.internal:
                    # nothing can take an unconstrained request: peers
                    # will not take the rest of the queue either
                    break
                continue
            self._dispatch_to(fr, rep)
        for fr in reversed(skipped):
            self._queue.appendleft(fr)

    # -- cross-replica KV-page migration --------------------------------------
    # One primitive — ship a prefix's KV pages over the binary page frame
    # from the replica that has them to the replica that needs them —
    # bought four ways: the disaggregated prefill->decode handoff, the
    # fleet-wide prefix cache, pool-pressure rebalancing, and live
    # scale-down. Pages are COPIED, never moved, across the wire: the
    # source keeps (or explicitly evicts) its entry, the destination
    # allocates from its own pool inside the engine's atomic ingest, and
    # a process death on either side therefore cannot strand a page.

    def _aligned_len(self, prompt_len: int) -> int:
        ps = self.cfg.page_size
        return ((int(prompt_len) - 1) // ps) * ps

    def _record_prefix(self, prompt: Sequence[int], owner: int) -> None:
        n = self._aligned_len(len(prompt))
        if n < self.cfg.migrate_min_tokens:
            return
        tokens = tuple(int(t) for t in prompt[:n])
        self._add_owner(prefix_key(tokens), tokens, owner)

    def _add_owner(self, key: str, tokens, owner: int) -> None:
        ent = self._prefix_index.get(key)
        tokens = tuple(int(t) for t in tokens)
        if ent is None or ent["tokens"] != tokens:
            ent = {"tokens": tokens, "owners": set()}
            self._prefix_index[key] = ent
        ent["owners"].add(int(owner))

    def _drop_owner(self, key: str, owner: int) -> None:
        ent = self._prefix_index.get(key)
        if ent is None:
            return
        ent["owners"].discard(owner)
        if not ent["owners"]:
            del self._prefix_index[key]

    def _drop_owner_everywhere(self, owner: int) -> None:
        for key in [k for k, e in self._prefix_index.items()
                    if owner in e["owners"]]:
            self._drop_owner(key, owner)

    def _rep_or_none(self, index: Optional[int]):
        if index is None or not (0 <= index < len(self._replicas)):
            return None
        return self._replicas[index]

    def _owner_usable(self, index: int) -> bool:
        """Can this index answer an export op? (Alive is enough — a
        replica drained of NEW traffic still ships its cached pages.)"""
        rep = self._rep_or_none(index)
        return rep is not None and rep.alive and index not in self._retired

    def _pick_prefill(self):
        """Least-loaded prefill-heavy replica, for internal prefill jobs."""
        best = None
        for rep in self._replicas:
            if rep.role != "prefill" or rep.index in self._retired \
                    or not self._replica_healthy(rep) \
                    or len(rep.inflight) >= self.cfg.max_outstanding:
                continue
            if best is None or len(rep.inflight) < len(best.inflight):
                best = rep
        return best

    def _least_loaded_peer(self, exclude: int):
        """Least-loaded replica that can take user traffic (migration
        destination for rebalance/scale-down shipments)."""
        best = None
        for rep in self._replicas:
            if rep.index == exclude or rep.index in self._retired \
                    or rep.role == "prefill" \
                    or not self._replica_healthy(rep):
                continue
            if best is None or len(rep.inflight) < len(best.inflight):
                best = rep
        return best

    def _maybe_migrate(self, fr: FleetRequest) -> bool:
        """Dispatch-time migration decision for one queued request. True
        when the request was captured (held by a migration, or dispatched
        pinned at an owner) — False sends it down the cold path."""
        if fr.internal or fr.no_migrate or fr.pin_replica is not None \
                or not self._fleet_prefix:
            return False
        n_max = self._aligned_len(len(fr.prompt))
        if n_max < self.cfg.migrate_min_tokens:
            return False
        ps = self.cfg.page_size
        for n in range(n_max, self.cfg.migrate_min_tokens - 1, -ps):
            tokens = tuple(fr.prompt[:n])
            key = prefix_key(tokens)
            for m in self._migrations.values():
                if m.key == key and m.purpose in ("disagg", "remote_hit"):
                    # the same prefix is already in flight: piggyback —
                    # one ship serves every waiter
                    m.waiters.append(fr)
                    fr.state = "migrating"
                    fr.queued_since = None
                    return True
            ent = self._prefix_index.get(key)
            if ent is None or ent["tokens"] != tokens:
                continue
            owners = [i for i in sorted(ent["owners"])
                      if self._owner_usable(i)]
            if not owners:
                del self._prefix_index[key]   # every owner is gone
                continue
            for i in owners:
                rep = self._replicas[i]
                if self._dispatchable(rep, fr):
                    # an owner can serve directly: a LOCAL prefix-cache
                    # hit there, no ship needed
                    self._dispatch_to(fr, rep)
                    return True
            dst = self._pick_replica(fr)
            if dst is None:
                return False   # nowhere to ship to; retry next pump
            src = self._replicas[owners[0]]
            purpose = ("disagg" if src.role == "prefill" else "remote_hit")
            self._start_ship(purpose, tokens, src, dst, fr)
            return True
        if self.cfg.roles:
            # no cached prefix anywhere: in a role-split fleet, warm it on
            # a prefill replica and ship; uniform fleets dispatch cold
            return self._start_disagg(fr, n_max)
        return False

    def _new_migration(self, purpose: str, tokens, fr) -> _Migration:
        m = _Migration(next(self._mig_seq), purpose, tokens, fr)
        self._migrations[m.xid] = m
        _fm.MIGRATIONS_STARTED.inc()
        return m

    def _hold(self, fr: Optional[FleetRequest]) -> None:
        if fr is not None:
            fr.state = "migrating"
            fr.queued_since = None

    def _start_ship(self, purpose: str, tokens, src, dst,
                    fr: Optional[FleetRequest]) -> None:
        m = self._new_migration(purpose, tokens, fr)
        m.src, m.dst = src.index, dst.index
        m.stage = "export"
        self._hold(fr)
        self._emit_event("migration_start", xid=m.xid, purpose=purpose,
                         key=m.key, src=m.src, dst=m.dst,
                         id=(fr.id if fr is not None else None),
                         tokens=len(m.tokens))
        src.request_export_prefix(m.xid, list(m.tokens))

    def _start_disagg(self, fr: FleetRequest, n_aligned: int) -> bool:
        src = self._pick_prefill()
        if src is None:
            return False   # no prefill capacity right now: stay queued
        # the internal prefill job: the aligned prefix + one remainder
        # token, ONE generated token — the engine prefills the prompt,
        # FINISHES immediately, and retirement donates the aligned
        # prefix's pages to its local prefix cache, where the export op
        # finds them. Temperature 0 keeps it cheap and deterministic;
        # the KV pages depend only on the prompt tokens anyway.
        ifr = FleetRequest(self._next_id, fr.prompt[:n_aligned + 1], 1,
                           temperature=0.0, top_k=0, seed=fr.seed,
                           trace_id="fr%d-%d-prefill"
                                    % (self._seq, self._next_id))
        self._next_id += 1
        ifr.internal = True
        ifr.pin_replica = src.index
        self._requests[ifr.id] = ifr
        m = self._new_migration("disagg", fr.prompt[:n_aligned], fr)
        m.src = src.index
        m.prefill_id = ifr.id
        m.stage = "prefill"
        self._hold(fr)
        self._emit_event("migration_start", xid=m.xid, purpose="disagg",
                         key=m.key, src=m.src, dst=None, id=fr.id,
                         tokens=len(m.tokens), prefill_id=ifr.id)
        self._queue.append(ifr)   # dispatches this same pass, pinned
        return True

    def _on_internal_done(self, ifr: FleetRequest) -> None:
        for m in list(self._migrations.values()):
            if m.prefill_id != ifr.id:
                continue
            if ifr.state != FINISHED:
                self._fail_migration(m, "prefill job %s: %s"
                                     % (ifr.state, ifr.error))
            else:
                self._advance_export(m)

    def _advance_export(self, m: _Migration) -> None:
        src = self._rep_or_none(m.src)
        if src is None or not src.alive:
            self._fail_migration(m, "source replica lost")
            return
        if m.dst is None:
            dst = self._pick_replica(m.fr) if m.fr is not None else None
            if dst is None:
                self._fail_migration(m, "no destination replica")
                return
            m.dst = dst.index
        m.stage = "export"
        src.request_export_prefix(m.xid, list(m.tokens))

    def _on_pages(self, rep, ev: dict) -> None:
        """The export answer: a binary page payload (ok) or a typed miss.
        Forward the pages to the destination's import, or fail over."""
        m = self._migrations.get(ev.get("xid"))
        if m is None or rep.index != m.src or m.stage != "export":
            return   # late/alien answer: the migration already resolved
        if not ev.get("ok"):
            if m.purpose == "remote_hit":
                _fm.REMOTE_MISSES.inc()
            self._drop_owner(m.key, m.src)   # the hint was stale
            self._fail_migration(m, "export miss at replica %d" % m.src)
            return
        if ev.get("tokens") and not m.tokens:
            # scale-down exports name their own prefix (the router did
            # not know the aligned length of a running request's prompt)
            m.tokens = tuple(int(t) for t in ev["tokens"])
            m.key = prefix_key(m.tokens)
        dst = self._rep_or_none(m.dst)
        if dst is None or not dst.alive:
            self._fail_migration(m, "destination replica lost")
            return
        meta = {k: v for k, v in ev.items()
                if k not in ("ev", "xid", "ok", "tokens", "_blobs")}
        m.n_pages = int(meta.get("n_pages", 0))
        m.stage = "import"
        _fm.REMOTE_SHIPS.inc()
        dst.request_import_prefix(m.xid, list(m.tokens), meta,
                                  ev.get("_blobs", []))

    def _on_imported(self, rep, ev: dict) -> None:
        m = self._migrations.get(ev.get("xid"))
        if m is None or rep.index != m.dst or m.stage != "import":
            return
        if not ev.get("ok"):
            self._fail_migration(m, "import refused at replica %d" % m.dst)
            return
        self._complete_migration(m, int(ev.get("pages", m.n_pages)))

    def _complete_migration(self, m: _Migration, pages: int) -> None:
        self._migrations.pop(m.xid, None)
        dt_ms = (time.perf_counter() - m.t0) * 1e3
        _fm.MIGRATIONS_COMPLETED.inc()
        _fm.MIGRATED_PAGES.inc(pages)
        _fm.MIGRATION_MS.observe(dt_ms)
        if m.tokens:
            self._add_owner(m.key, m.tokens, m.dst)
        served = [fr for fr in m.requests() if not fr.terminal]
        if m.purpose == "remote_hit" and served:
            _fm.REMOTE_HITS.inc(len(served))
        if self._trace:
            # phase-ledger tags: the ledger joins this window in as a
            # ``ship`` interval of every request the migration served
            _ftr.on_lifecycle_span(
                "migrate %s" % m.purpose, m.t0, time.perf_counter(),
                args={"xid": m.xid, "src": m.src, "dst": m.dst,
                      "pages": pages, "served": len(served),
                      "phase": "ship", "cause": m.purpose,
                      "trace_ids": [fr.trace_id for fr in served][:8]})
        self._emit_event("migration_done", xid=m.xid, purpose=m.purpose,
                         key=m.key, src=m.src, dst=m.dst, pages=pages,
                         ms=round(dt_ms, 3), served=len(served))
        if m.purpose == "rebalance":
            # ship + evict = move: the source frees its copy, and the
            # index forgets it owned one, only AFTER the import landed
            src = self._rep_or_none(m.src)
            if src is not None and src.alive:
                src.request_evict_prefix(m.xid, list(m.tokens))
            self._drop_owner(m.key, m.src)
        for fr in served:
            # dispatch pinned at the replica that now holds the prefix:
            # its local prefix cache turns the dispatch into a resume
            fr.pin_replica = m.dst
            fr.state = "queued"
            fr.queued_since = time.perf_counter()
            self._queue.appendleft(fr)

    def _fail_migration(self, m: _Migration, why: str) -> None:
        """ANY failure funnels here, idempotently: the migration is
        forgotten and every carried request falls back to an ordinary
        cold dispatch — a migration can delay a request, never lose one."""
        if self._migrations.pop(m.xid, None) is None:
            return
        _fm.MIGRATIONS_FAILED.inc()
        self._emit_event("migration_failed", xid=m.xid, purpose=m.purpose,
                         key=m.key, src=m.src, dst=m.dst, why=why)
        if self._trace:
            _ftr.on_lifecycle_instant(
                "migration %d failed" % m.xid,
                args={"purpose": m.purpose, "src": m.src, "dst": m.dst,
                      "why": why})
        for fr in m.requests():
            if fr.terminal:
                continue
            fr.no_migrate = True
            fr.pin_replica = None
            fr.state = "queued"
            fr.queued_since = time.perf_counter()
            self._queue.appendleft(fr)

    def _fail_migrations_for(self, index: int, why: str) -> None:
        for m in list(self._migrations.values()):
            if m.src == index or m.dst == index:
                self._fail_migration(m, why)

    def _auto_rebalance(self) -> None:
        """Pool-pressure relief: when a replica's KV page pool runs above
        ``rebalance_util``, move ONE of its solely-owned prefix entries
        to the least-loaded peer (at most one ship per evaluation — the
        next pass sees the post-move utilization, so relief converges
        instead of oscillating)."""
        for rep in self._replicas:
            i = rep.index
            if not rep.alive or i in self._retired:
                continue
            h = (rep.health() if rep.kind == "inprocess"
                 else self._health.get(i, {}))
            total = h.get("pages_total") or 0
            if not total:
                continue
            util = 1.0 - float(h.get("pages_free", total)) / total
            if util < self.cfg.rebalance_util:
                continue
            for key, ent in self._prefix_index.items():
                if ent["owners"] != {i}:
                    continue
                if any(m.key == key for m in self._migrations.values()):
                    continue
                dst = self._least_loaded_peer(i)
                if dst is None:
                    return
                self._start_ship("rebalance", ent["tokens"], rep, dst,
                                 None)
                return

    def rebalance(self, src_index: int, dst_index: int,
                  tokens: Sequence[int]) -> Optional[int]:
        """Manually move one prefix entry ``src -> dst`` (ship + evict).
        Returns the migration id, or None when either side cannot serve.
        The move resolves through ``pump()`` like any migration."""
        src = self._rep_or_none(src_index)
        dst = self._rep_or_none(dst_index)
        if src is None or dst is None or not src.alive or not dst.alive:
            return None
        m = self._new_migration("rebalance",
                                tuple(int(t) for t in tokens), None)
        m.src, m.dst = src.index, dst.index
        m.stage = "export"
        self._emit_event("migration_start", xid=m.xid, purpose="rebalance",
                         key=m.key, src=m.src, dst=m.dst,
                         tokens=len(m.tokens))
        src.request_export_prefix(m.xid, list(m.tokens))
        return m.xid

    def scale_down(self, index: int,
                   timeout_s: Optional[float] = None) -> dict:
        """Retire one replica WITHOUT losing its in-flight work: stop its
        new traffic, export each running request's immutable prompt-prefix
        pages to the least-loaded peer, requeue those requests (typed
        reroute — no requeue-budget hit, nothing was lost), and close the
        replica. The re-dispatch probes the fleet prefix index, finds the
        shipped prefix at the peer, and resumes warm there. pump() will
        not respawn a retired index; the fleet is permanently one smaller."""
        if timeout_s is None:
            timeout_s = self.cfg.drain_timeout_s
        rep = self._replicas[index]
        self._retired.add(index)
        rep.accepting = False
        t0 = time.perf_counter()
        xids: List[int] = []
        for fid in list(rep.inflight):
            fr = self._requests.get(fid)
            if fr is None or fr.terminal or fr.internal:
                continue
            dst = self._least_loaded_peer(index)
            if dst is None:
                break   # nowhere to ship: plain requeue still holds
            m = self._new_migration("scale_down", (), None)
            m.src, m.dst = index, dst.index
            m.stage = "export"
            self._emit_event("migration_start", xid=m.xid,
                             purpose="scale_down", src=index,
                             dst=dst.index, id=fid)
            rep.request_export_request(m.xid, fid)
            xids.append(m.xid)
        # let the ships settle (complete/fail) before the replica goes —
        # a request may also simply FINISH here, which wins outright
        deadline = time.monotonic() + max(0.1, float(timeout_s))
        while any(x in self._migrations for x in xids) \
                and time.monotonic() < deadline:
            self.pump()
            if self.cfg.mode == "process":
                time.sleep(0.002)
        for x in xids:
            m = self._migrations.get(x)
            if m is not None:
                self._fail_migration(m, "scale-down budget exhausted")
        requeued = 0
        lost = list(rep.inflight.values())
        rep.inflight.clear()
        for rdoc in lost:
            fr = self._requests.get(rdoc["id"])
            if fr is None or fr.terminal:
                continue
            if fr.internal:
                self._finalize(fr, FAILED,
                               error="replica %d retired" % index)
                continue
            if self._trace:
                _ftr.on_attempt_end(fr, index, "migrated", killed=True)
            fr.dispatched_t = None
            self._requeue_reroute(fr)
            requeued += 1
        self._drop_owner_everywhere(index)
        self._fail_migrations_for(index, "replica %d retired" % index)
        try:
            rep.close()
        except Exception:
            pass
        out = {"replica": index, "migrations": len(xids),
               "requeued": requeued,
               "duration_s": round(time.perf_counter() - t0, 6)}
        self._emit_event("scale_down", **out)
        if self._trace:
            _ftr.on_lifecycle_span("scale_down replica %d" % index, t0,
                                   time.perf_counter(), args=dict(out))
        self.pump()   # the rerouted work lands on the warmed peers
        return out

    def wait_all(self, timeout_s: float = 60.0,
                 idle_sleep_s: float = 0.002) -> bool:
        """Pump until every accepted request is terminal (True) or the
        timeout passes (False)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.pump() == 0:
                return True
            if self.cfg.mode == "process":
                time.sleep(idle_sleep_s)
        return self.pump() == 0

    # -- lifecycle ------------------------------------------------------------
    def rolling_restart(self, timeout_s: Optional[float] = None) -> dict:
        """Zero-downtime rollout: one replica at a time, stop its new
        traffic, ``drain(timeout_s)`` (in-flight finishes; engine-queued
        work is shed as typed ``draining`` rejections that re-route to
        peers), respawn, move on. Traffic keeps flowing through the
        others for the whole pass."""
        if timeout_s is None:
            timeout_s = self.cfg.drain_timeout_s
        t_pass = time.perf_counter()
        summaries = {}
        for i in range(len(self._replicas)):
            rep = self._replicas[i]
            rep.accepting = False
            t_leg = time.perf_counter()
            if rep.alive:
                summaries[rep.name] = rep.drain(timeout_s)
            for ev in rep.poll():  # drain's result events (incl. sheds)
                self._handle_event(rep, ev)
            # anything the drain could not resolve is a lost in-flight set
            lost = list(rep.inflight.values())
            rep.inflight.clear()
            for rdoc in lost:
                fr = self._requests.get(rdoc["id"])
                if fr is not None and not fr.terminal:
                    self._lose(fr, i, "rolling restart of replica %d" % i,
                               tag="lost_in_drain")
            self._fail_migrations_for(i, "rolling restart of replica %d"
                                      % i)
            if self._trace:
                _ftr.on_lifecycle_span(
                    "drain replica %d" % i, t_leg, time.perf_counter(),
                    args=dict(summaries.get(rep.name) or {}, replica=i))
            self._emit_event("drain", replica=i,
                             summary=summaries.get(rep.name),
                             lost=len(lost))
            self._respawn(i)
            self.pump()  # rerouted work lands on peers before the next leg
        _fm.ROLLING_RESTARTS.inc()
        if self._trace:
            _ftr.on_lifecycle_span("rolling_restart", t_pass,
                                   time.perf_counter(),
                                   args={"replicas": len(self._replicas)})
        self._emit_event("rolling_restart", replicas=len(self._replicas),
                         duration_s=round(time.perf_counter() - t_pass, 6))
        return summaries

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Fleet-wide graceful stop: no new submissions, finish what can
        finish within the budget, account everything else (queued work
        sheds as terminal REJECTED — typed, counted, never silent)."""
        if timeout_s is None:
            timeout_s = self.cfg.drain_timeout_s
        t0 = time.perf_counter()
        self._draining = True
        self.wait_all(timeout_s)
        for rep in self._replicas:
            if rep.alive:
                rep.drain(timeout_s)
            for ev in rep.poll():
                self._handle_event(rep, ev)
        out = {"finished": 0, "failed": 0, "timeout": 0, "rejected": 0}
        for fr in list(self._requests.values()):
            if fr.internal:
                if not fr.terminal:
                    self._finalize(fr, REJECTED, error="router drained")
                continue
            if not fr.terminal:
                _fm.REJECTED.inc()
                if self._trace and fr.dispatched_t is not None:
                    _ftr.on_attempt_end(fr, fr.last_replica or 0, "shed",
                                        killed=True)
                    fr.dispatched_t = None
                self._finalize(fr, REJECTED, error="router drained")
            out[fr.state] = out.get(fr.state, 0) + 1
        self._queue.clear()
        _fm.QUEUE_DEPTH.set(0)
        if self._trace:
            _ftr.on_lifecycle_span("drain", t0, time.perf_counter(),
                                   args=dict(out))
        self._emit_event("drain", scope="fleet", summary=out)
        self.close()
        return out

    def close(self) -> None:
        """Stop the fleet. Idempotent; replicas still alive are shut down
        (process workers get a graceful shutdown op, then SIGKILL)."""
        if self._closed:
            return
        self._closed = True
        # outstanding migrations can never resolve once the replicas are
        # gone; their held requests stay accounted through _requests (a
        # drain() sweep finalizes them as REJECTED before reaching here)
        self._migrations.clear()
        for rep in self._replicas:
            try:
                rep.close()
            except Exception:
                pass
        _fm.REPLICAS_ALIVE.set(0)
        if self._slo is not None:
            # closing the workers flushed their final telemetry samples;
            # evaluate them now, while the event log is still open, so a
            # breach in the last interval is journaled, not lost
            try:
                self.evaluate_slos()
            except Exception:
                pass
        self._emit_event("fleet_stop",
                         requests=len(self._requests),
                         states=dict(self._request_states()))
        # workers flushed their fragments on close (atexit); now the
        # router's own fragment + the merge manifest complete the set
        self._write_trace()
        # the merged fragments exist and the event log is still open:
        # replay the run through the phase ledger and autopsy any breach
        self._run_autopsy()
        self._write_snapshot()
        if self._events is not None:
            self._events.close()

    def _write_trace(self) -> None:
        if not self._trace:
            return
        try:
            _tr.save_chrome_trace(
                os.path.join(self.cfg.trace_dir, "router.json"),
                process_names={os.getpid(): "fleet router"})
            _ftr.write_manifest(
                self.cfg.trace_dir,
                {"file": "router.json", "pid": os.getpid(), "offset_us": 0},
                self._worker_frags, _runlog.run_id())
        except OSError:
            pass
        if self._own_tracer:
            _tr.stop_tracing()
            self._own_tracer = False

    def _run_autopsy(self) -> None:
        """Close-time request autopsy over the just-written trace: build
        the phase ledgers from the merged fragments, feed the
        ``fleet/phase/*`` histograms + snapshot stats, and — when this
        run recorded SLO breaches — journal one typed ``breach_autopsy``
        verdict per distinct breach in the event log (and the flight
        ring). Best-effort: an autopsy failure must never take down
        close()."""
        if not self._trace:
            return
        try:
            spans, manifest, _problems = _ftr.load_fragments(
                self.cfg.trace_dir)
            ledgers = _autopsy.build_ledgers(spans, manifest)
            if not ledgers:
                return
            _autopsy.observe_phase_histograms(ledgers)
            self._phase_stats = _autopsy.phase_stats(ledgers)
            if not self._breach_log:
                return
            verdicts = _autopsy.autopsy_breaches(
                self._breach_log, ledgers,
                telemetry_base=self.cfg.telemetry_base)
            self._autopsies = [v.to_doc() for v in verdicts]
            from ..monitor import device as _dev

            ring = _dev.flight_recorder()
            for doc in self._autopsies:
                self._emit_event(KIND_BREACH_AUTOPSY, **doc)
                if ring is not None:
                    ring.record_event(KIND_BREACH_AUTOPSY, **doc)
        except Exception:
            import logging

            logging.getLogger("paddle_tpu").exception(
                "breach autopsy failed (run artifacts are intact)")

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- introspection --------------------------------------------------------
    def accounting(self) -> Dict[int, str]:
        """fleet id -> state for every USER request ever accepted — the
        drill's zero-silent-drops ledger. Internal prefill jobs (disagg
        scaffolding) are router bookkeeping, not accepted work, and are
        excluded."""
        return {fid: fr.state for fid, fr in self._requests.items()
                if not fr.internal}

    def request(self, fid: int) -> Optional[FleetRequest]:
        return self._requests.get(fid)

    @staticmethod
    def _p99(lat_ms: List[float]) -> Optional[float]:
        if not lat_ms:
            return None
        s = sorted(lat_ms)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    def _request_states(self) -> Dict[str, int]:
        states: Dict[str, int] = {}
        for fr in self._requests.values():
            if fr.internal:
                continue
            states[fr.state] = states.get(fr.state, 0) + 1
        return states

    def evaluate_slos(self) -> dict:
        """One fleet-SLO evaluation pass (per-replica + aggregate) over
        the telemetry base. The pump calls this periodically; drills call
        it synchronously after workers flushed their final samples."""
        if self._slo is None or not self.cfg.telemetry_base:
            return {"replica": {}, "fleet": []}
        return self._slo.evaluate(self.cfg.telemetry_base,
                                  [rep.index for rep in self._replicas])

    def snapshot(self) -> dict:
        """One fleet-wide observability document: router counters,
        per-replica liveness/health/throughput (with SLO-breach overlay),
        the active breach set, joinable ids (run_id) and artifact paths
        (trace dir, event log), and (process mode with a telemetry base)
        the merged last-sample view of every replica's telemetry ring."""
        now = time.perf_counter()
        dt = max(now - self._t0, 1e-9)
        reps = []
        for rep in self._replicas:
            idx = rep.index
            lat = self._rep_lat.get(idx, [])
            health = (rep.health() if rep.kind == "inprocess" and rep.alive
                      else self._health.get(idx, {"status": "ok"}))
            breach = self._slo_breached.get(idx)
            if breach is not None:
                health = dict(health, status="degraded", slo_breached=True,
                              slo=breach.get("slo"))
            row = {
                "name": rep.name, "alive": rep.alive,
                "accepting": rep.accepting,
                "role": rep.role,
                "retired": idx in self._retired,
                "health": health,
                "inflight": len(rep.inflight),
                "completed": self._rep_done.get(idx, 0),
                "qps": round(self._rep_done.get(idx, 0) / dt, 3),
                "p99_ms": self._p99(lat),
            }
            if self._phase_stats is not None:
                row["phases"] = self._phase_stats.get(
                    "replicas", {}).get(idx, {})
            reps.append(row)
        out = {"queue_depth": len(self._queue),
               "requests": sum(1 for fr in self._requests.values()
                               if not fr.internal),
               "states": self._request_states(),
               "replicas": reps,
               "uptime_s": round(dt, 3),
               "run_id": _runlog.run_id()}
        if self.cfg.roles:
            out["roles"] = dict(self.cfg.roles,
                                source=self.cfg.roles_source)
        if self._fleet_prefix:
            out["migration"] = {
                "active": len(self._migrations),
                "prefix_index_entries": len(self._prefix_index)}
        if self.cfg.trace_dir:
            out["trace_dir"] = self.cfg.trace_dir
        if self._phase_stats is not None:
            out["phases"] = self._phase_stats.get("fleet", {})
        if self._autopsies:
            out["autopsies"] = self._autopsies
        if self._events is not None and self._events.armed:
            out["event_log"] = self._events.path
        if self._slo is not None:
            out["slo"] = {
                "specs": [s.name for s in self.cfg.slos],
                "breached_replicas": sorted(self._slo_breached),
                "fleet_breaches": self._fleet_breach_count,
                "fleet_breach": self._fleet_breach,
            }
        if self.cfg.telemetry_base:
            out["telemetry"] = aggregate_telemetry(
                self.cfg.telemetry_base,
                expected=[rep.index for rep in self._replicas])
        return out

    def _write_snapshot(self) -> None:
        """Drop ``snapshot.json`` under the telemetry base (atomically) so
        out-of-process viewers (tools/fleet_top.py --watch) can render the
        router's live view without a control channel."""
        base = self.cfg.telemetry_base
        if not base:
            return
        try:
            os.makedirs(base, exist_ok=True)
            path = os.path.join(base, "snapshot.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.snapshot(), f, default=repr)
            os.replace(tmp, path)
        except OSError:
            pass


def _replica_index(name: str) -> int:
    """Numeric index from a ``replica_<i>`` dir name; unparsable names
    sort last (after replica_9 comes replica_10, not replica_1)."""
    try:
        return int(name.split("_", 1)[1])
    except (IndexError, ValueError):
        return 1 << 30


def aggregate_telemetry(base_dir: str,
                        expected: Optional[Sequence[int]] = None) -> dict:
    """Merge N replicas' telemetry rings (``<base>/replica_<i>/``, each an
    exporter dir of JSONL ring files) into one fleet view: per replica,
    the LAST sample of each of its processes, in NUMERIC replica order.
    The same files ``tools/dump_metrics --watch dir1,dir2,...`` tails
    live.

    Degenerate rings never throw — a freshly spawned replica that has not
    ticked yet, a SIGKILLed one that left only a torn tail, or a ring dir
    that never appeared (pass ``expected`` indices to detect that) each
    yield an entry with a ``flag`` explaining the gap, so the aggregate
    stays healthy and the hole stays visible."""
    from ..monitor import telemetry as _telemetry

    out: Dict[str, dict] = {}
    if not base_dir or not os.path.isdir(base_dir):
        if expected:
            for idx in expected:
                out["replica_%d" % idx] = {"samples": 0,
                                           "flag": "ring dir missing"}
        return out
    names = [n for n in os.listdir(base_dir)
             if n.startswith("replica_")
             and os.path.isdir(os.path.join(base_dir, n))]
    for name in sorted(names, key=_replica_index):
        sub = os.path.join(base_dir, name)
        try:
            series = _telemetry.read_series(sub)
        except Exception as e:
            out[name] = {"samples": 0, "flag": "unreadable: %s" % e}
            continue
        if series:
            out[name] = {"samples": len(series), "last": series[-1]}
        else:
            out[name] = {"samples": 0, "flag": "no complete samples"}
    for idx in (expected or ()):
        name = "replica_%d" % idx
        if name not in out:
            out[name] = {"samples": 0, "flag": "ring dir missing"}
    return out

"""Replica wrappers: the uniform surface the router dispatches over.

Three concrete replicas behind one duck-typed contract (``submit(rdoc)``
/ ``poll() -> events`` / ``health()`` / ``drain()`` / ``kill()`` /
``alive``):

* :class:`InProcessReplica` — wraps an engine object living in the
  router's process (a real ``serving.ServingEngine`` or a
  :class:`SimEngine`). The test/bench mode: no pipes, no pickling,
  deterministic pumping.
* :class:`ProcessReplica` — a ``python -m paddle_tpu.fleet.worker``
  subprocess speaking the length-prefixed frame protocol over its
  stdin/stdout. The production shape: SIGKILLing it is a real kill, and
  the router's only view of its death is EOF/exit — exactly what the
  crash-tolerance drill needs to exercise.
* :class:`SimEngine` — a device-bound engine model: each step sleeps
  ``step_ms`` (the host-blocks-on-accelerator regime — on a TPU replica
  the host waits on the device, it does not compute) and advances every
  running slot one deterministic token. Sim tokens are a pure function
  of (seed, absolute position) like the real engine's sampler, so
  requeue-replay bit-identity holds by the same mechanism. This is what
  makes router/protocol QPS scaling honestly measurable on a 1-core CI
  host: replicas overlap their device waits, not Python compute.

Events (worker -> router), all plain dicts with an ``ev`` key:
``ready``/``result``/``health``/``drained``/``stats``. A ``result``
carries the fleet request id, terminal ``state`` (finished/failed/
timeout — or ``rejected`` with a ``kind`` of draining/backpressure,
which the router treats as re-routable, never terminal), ``tokens`` and
``error``.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from ..monitor import tracer as _tracer
from ..reliability import faults as _faults
from ..serving import metrics as _sm
from ..serving import trace as _sv
from ..serving.request import (FAILED, FINISHED, REJECTED, BackpressureError,
                               DrainingError, Request)
from .protocol import (Binary, FrameReader, pack_pages, send_binary_frame,
                       send_frame, unpack_pages)

__all__ = ["SimConfig", "SimEngine", "InProcessReplica", "ProcessReplica",
           "sim_token"]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sim_token(seed: int, pos: int, vocab: int) -> int:
    """The sim decoder's next token: a stable hash of (seed, absolute
    position) — the same keying shape as the real engine's device-side
    sampler (fold_in(PRNGKey(seed), position)), so a replayed request
    regenerates the identical stream on any replica, by construction."""
    h = hashlib.sha1(b"%d:%d" % (int(seed), int(pos))).digest()
    return int.from_bytes(h[:4], "big") % max(1, int(vocab))


class SimConfig:
    """Geometry + the modeled device latency of one sim replica.

    The prefill cost model (default off): admission of a prompt blocks
    ``prefill_ms_per_token`` per token NOT covered by a known prefix —
    prefill is compute-bound and stalls the whole engine, exactly the
    contention continuous batching suffers.
    ``page_size`` is the prefix granularity for the migration surface."""

    def __init__(self, slots: int = 4, step_ms: float = 0.0,
                 vocab: int = 256, max_queue: int = 1024,
                 drain_timeout_s: float = 30.0, page_size: int = 16,
                 prefill_ms_per_token: float = 0.0,
                 serving_spans: bool = False):
        self.slots = int(slots)
        self.step_ms = float(step_ms)
        self.vocab = int(vocab)
        self.max_queue = int(max_queue)
        self.drain_timeout_s = float(drain_timeout_s)
        self.page_size = max(1, int(page_size))
        self.prefill_ms_per_token = float(prefill_ms_per_token)
        # Emit the serving-cat request-lifecycle spans (serving.trace) when
        # the host tracer is armed. Default OFF: serving spans ride virtual
        # tracks keyed by track NAME, so two in-process sims would collide
        # on "serving slot k" — only the fleet WORKER (one engine per
        # process) flips this on, giving the phase ledger the same span
        # vocabulary the real engine emits.
        self.serving_spans = bool(serving_spans)


class SimEngine:
    """Engine-shaped simulator: the ServingEngine slice the fleet layer
    drives (submit/step/idle/health/drain/request_drain/close), minus the
    device. Used in-process for router unit tests and as the worker's
    ``"engine": "sim"`` mode for protocol-scaling benches."""

    def __init__(self, config: Optional[SimConfig] = None):
        self.cfg = config or SimConfig()
        self._queue: List[Request] = []
        self._running: List[Request] = []
        self._free_slots: List[int] = list(range(self.cfg.slots))
        self._draining = False
        self._closed = False
        self._drain_active = False
        self.last_drain: Optional[dict] = None
        self.force_degraded = False  # tests flip this to exercise routing
        self.steps = 0
        # known prefixes (token tuple -> True): the sim analog of the real
        # engine's prefix cache — a covered prefix skips its prefill stall
        self._prefixes: Dict[tuple, bool] = {}
        self._prefills = 0
        self._resumes = 0

    # -- the engine contract --------------------------------------------------
    def submit(self, prompt, max_new_tokens, deadline_s=None,
               temperature=0.0, top_k=0, seed=None, trace_id=None,
               attempt=0) -> Request:
        if self._draining:
            raise DrainingError("sim engine is draining")
        if len(self._queue) >= self.cfg.max_queue:
            raise BackpressureError("sim queue full")
        req = Request(prompt, max_new_tokens, deadline_s=deadline_s,
                      temperature=temperature, top_k=top_k, seed=seed,
                      trace_id=trace_id, attempt=attempt)
        self._queue.append(req)
        _sm.REQUESTS_SUBMITTED.inc()
        if self.cfg.serving_spans:
            _sv.on_submitted(req)
        return req

    def idle(self) -> bool:
        return not self._queue and not self._running

    def _emit(self, req: Request) -> None:
        pos = req.prompt_len - 1 + len(req.tokens_out)
        req.tokens_out.append(sim_token(req.seed, pos, self.cfg.vocab))

    def _cacheable_len(self, n: int) -> int:
        # same alignment rule as serving.prefix_cache: longest page-aligned
        # prefix STRICTLY shorter than the prompt
        return ((int(n) - 1) // self.cfg.page_size) * self.cfg.page_size

    def _known_prefix_len(self, prompt) -> int:
        ps = self.cfg.page_size
        prompt = [int(t) for t in prompt]
        for n in range(self._cacheable_len(len(prompt)), 0, -ps):
            if tuple(prompt[:n]) in self._prefixes:
                return n
        return 0

    def _prefill_stall(self, req: Request) -> int:
        """The modeled prefill cost of admitting ``req``: per uncovered
        token. Returns the known-prefix length (the phase
        ledger's local/resume cause attribution)."""
        if self.cfg.prefill_ms_per_token <= 0:
            if self.cfg.serving_spans:
                return self._known_prefix_len(req.prompt)
            return 0
        known = self._known_prefix_len(req.prompt)
        if known:
            self._resumes += 1
        else:
            self._prefills += 1
        ms = (req.prompt_len - known) * self.cfg.prefill_ms_per_token
        if ms > 0:
            time.sleep(ms / 1e3)
        return known

    def _retire(self, req: Request, state: str) -> None:
        """Terminal bookkeeping shared by step() and drain(): emit the
        lifecycle spans (when armed) and free the request's slot."""
        if self.cfg.serving_spans:
            _sv.on_terminal(req, state, req.slot)
        if req.slot is not None:
            self._free_slots.append(req.slot)
            self._free_slots.sort()

    def step(self) -> List[Request]:
        """One sim cycle: admit into free slots (first token emitted at
        admission, like prefill — paying the modeled prefill stall first),
        block ``step_ms`` on the modeled device, advance every running
        request one token."""
        finished: List[Request] = []
        while self._queue and len(self._running) < self.cfg.slots:
            req = self._queue.pop(0)
            req.state = "running"
            req.slot = self._free_slots.pop(0)
            req.admitted_t = time.perf_counter()
            known = self._prefill_stall(req)
            n = self._cacheable_len(req.prompt_len)
            if n >= self.cfg.page_size:
                # the sim donates at admission (prefilled rows exist now)
                self._prefixes[tuple(int(t) for t in req.prompt[:n])] = True
            self._emit(req)
            # the +epsilon floor keeps the prefill span strictly inside
            # the lifetime span even when the modeled stall is zero (the
            # nesting validator treats equal-start spans as a partial
            # overlap, and sub-µs windows truncate to equal starts)
            req.first_token_t = max(time.perf_counter(),
                                    req.admitted_t + 4e-6)
            self._running.append(req)
            _sm.REQUESTS_ADMITTED.inc()
            if self.cfg.serving_spans:
                _sv.on_admitted(req, req.slot)
                _sv.on_prefill(req, req.slot, req.prompt_len,
                               req.admitted_t + 2e-6, req.first_token_t,
                               cause="resume" if known else "local")
                _sm.TTFT_MS.observe(
                    (req.first_token_t - req.submitted_t) * 1e3)
                _sm.PREFILL_MS.observe(
                    (req.first_token_t - req.admitted_t) * 1e3)
        _sm.QUEUE_DEPTH.set(len(self._queue))
        if not self._running:
            return finished
        # same chaos chokepoint as the real decode loop: a ``latency``
        # fault sleeps here, so per-replica fault plans can degrade one
        # sim replica's tail without touching its peers. The decode span
        # window opens BEFORE the fault fires — injected decode latency
        # lands inside the decode phase, where the autopsy should find it.
        t0d = time.perf_counter()
        if self.cfg.serving_spans:
            # the epsilon-floored first_token_t of a just-admitted request
            # can sit ahead of the wall clock; open the decode window at
            # or after every prefill close so slot tracks stay well-nested
            for req in self._running:
                if req.first_token_t is not None:
                    t0d = max(t0d, req.first_token_t)
        _faults.fire("serving.decode")
        if self.cfg.step_ms > 0:
            time.sleep(self.cfg.step_ms / 1e3)
        self.steps += 1
        still: List[Request] = []
        done: List[Request] = []
        for req in self._running:
            if len(req.tokens_out) < req.max_new_tokens:
                self._emit(req)
            if len(req.tokens_out) >= req.max_new_tokens:
                done.append(req)
            else:
                still.append(req)
        t1d = max(time.perf_counter(), t0d)
        if self.cfg.serving_spans:
            by_slot: List[Optional[Request]] = [None] * self.cfg.slots
            for req in self._running:
                if req.slot is not None:
                    by_slot[req.slot] = req
            _sv.on_decode_chunk(by_slot, 1, t0d, t1d)
            _sm.DECODE_STEP_MS.observe((t1d - t0d) * 1e3)
        for req in done:
            req.state = FINISHED
            req.finished_t = max(time.perf_counter(), t1d)
            finished.append(req)
            _sm.REQUESTS_RETIRED.inc()
            _sm.REQUEST_LATENCY_MS.observe(
                (req.finished_t - req.submitted_t) * 1e3)
            self._retire(req, FINISHED)
        self._running = still
        return finished

    def health(self) -> dict:
        return {"status": "degraded" if self.force_degraded else "ok",
                "queued": len(self._queue), "running": len(self._running),
                "consecutive_failures": 0, "faults_absorbed": 0,
                "last_error": None, "page_accounting_ok": True,
                "prefills": self._prefills, "resumes": self._resumes}

    # -- migration surface (same duck type as ServingEngine) ------------------
    def export_prefix_pages(self, tokens):
        tokens = tuple(int(t) for t in tokens)
        if tokens not in self._prefixes:
            return None
        return {"layout": "sim", "page_size": self.cfg.page_size,
                "n_pages": len(tokens) // self.cfg.page_size}, []

    def ingest_prefix_pages(self, tokens, meta: dict, blobs) -> bool:
        if self._closed or meta.get("layout") != "sim":
            return False  # a real-engine payload is not importable here
        tokens = tuple(int(t) for t in tokens)
        if not tokens or len(tokens) % self.cfg.page_size:
            return False
        self._prefixes[tokens] = True
        return True

    def evict_prefix(self, tokens) -> int:
        if self._prefixes.pop(tuple(int(t) for t in tokens), None):
            return max(1, len(tokens) // self.cfg.page_size)
        return 0

    def export_request_prefix(self, req: Request):
        n = self._cacheable_len(req.prompt_len)
        if n < self.cfg.page_size:
            return None
        tokens = [int(t) for t in req.prompt[:n]]
        self._prefixes[tuple(tokens)] = True  # prefilled rows exist
        return tokens, {"layout": "sim", "page_size": self.cfg.page_size,
                        "n_pages": n // self.cfg.page_size}, []

    def request_drain(self) -> None:
        self._draining = True

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Same contract (and re-entrancy discipline) as the real engine's
        drain: shed queued as REJECTED, finish running, idempotent."""
        if self.last_drain is not None:
            return self.last_drain
        if self._drain_active:
            return {"finished": 0, "timed_out": 0, "failed": 0,
                    "rejected": 0, "nested": True}
        self._drain_active = True
        try:
            if timeout_s is None:
                timeout_s = self.cfg.drain_timeout_s
            self._draining = True
            summary = {"finished": 0, "timed_out": 0, "failed": 0,
                       "rejected": 0}
            for req in self._queue:
                req.state = REJECTED
                req.finished_t = time.perf_counter()
                summary["rejected"] += 1
                if self.cfg.serving_spans:
                    _sv.on_terminal(req, REJECTED, None)
            self._queue = []
            deadline = time.monotonic() + timeout_s
            while self._running and time.monotonic() < deadline:
                summary["finished"] += len(self.step())
            for req in self._running:
                req.state = "timeout"
                req.finished_t = time.perf_counter()
                summary["timed_out"] += 1
                self._retire(req, "timeout")
            self._running = []
            self.last_drain = summary
            self.close()
            return summary
        finally:
            self._drain_active = False

    def close(self) -> None:
        self._closed = True

    def stats(self) -> dict:
        return {"layout": "sim", "queued": len(self._queue),
                "running": len(self._running), "steps": self.steps,
                "step_ms": self.cfg.step_ms, "slots": self.cfg.slots}


def _engine_idle(engine) -> bool:
    if hasattr(engine, "idle"):
        return engine.idle()
    return engine.scheduler.idle()


def _decode_frames(frames) -> List[dict]:
    """Normalize a frame batch: binary page frames unpack to their meta
    dict with the blobs attached under ``"_blobs"`` (a foreign/garbled
    payload is dropped — same tolerance as a torn JSON line in the event
    log); JSON frames pass through."""
    out: List[dict] = []
    for fr in frames:
        if isinstance(fr, Binary):
            try:
                meta, blobs = unpack_pages(fr.payload)
            except ValueError:
                continue
            meta["_blobs"] = blobs
            out.append(meta)
        else:
            out.append(fr)
    return out


class InProcessReplica:
    """A replica living in the router's process. ``poll()`` pumps the
    engine one step when it has work — the router's pump loop IS the
    engine's drive loop in this mode."""

    kind = "inprocess"

    def __init__(self, engine, index: int = 0):
        self.engine = engine
        self.index = int(index)
        self.name = "replica-%d" % self.index
        self.role = "uniform"  # the router stamps prefill/decode roles
        self.accepting = True
        self.alive = True
        self.inflight: Dict[int, dict] = {}   # fleet id -> request doc
        self._by_req: Dict[int, int] = {}     # engine Request.id -> fleet id
        self._requests: Dict[int, Request] = {}  # engine Request.id -> obj
        self._events: List[dict] = []

    def submit(self, rdoc: dict) -> None:
        try:
            req = self.engine.submit(
                rdoc["prompt"], rdoc["max_new_tokens"],
                deadline_s=rdoc.get("deadline_s"),
                temperature=rdoc.get("temperature", 0.0),
                top_k=rdoc.get("top_k", 0), seed=rdoc.get("seed"),
                trace_id=rdoc.get("trace_id"),
                attempt=int(rdoc.get("attempt", 0)))
        except DrainingError:
            self._events.append({"ev": "result", "id": rdoc["id"],
                                 "state": REJECTED, "kind": "draining"})
            return
        except BackpressureError:
            self._events.append({"ev": "result", "id": rdoc["id"],
                                 "state": REJECTED, "kind": "backpressure"})
            return
        except ValueError as e:  # never servable at this geometry: terminal
            self._events.append({"ev": "result", "id": rdoc["id"],
                                 "state": FAILED, "tokens": [],
                                 "error": str(e)})
            return
        self.inflight[rdoc["id"]] = rdoc
        self._by_req[req.id] = rdoc["id"]
        self._requests[req.id] = req

    def _result(self, req: Request) -> Optional[dict]:
        fid = self._by_req.pop(req.id, None)
        self._requests.pop(req.id, None)
        if fid is None:
            return None
        self.inflight.pop(fid, None)
        return {"ev": "result", "id": fid, "state": req.state,
                "tokens": list(req.tokens_out), "error": req.error}

    def poll(self) -> List[dict]:
        evs, self._events = self._events, []  # drain events outlive alive
        if self.alive and not _engine_idle(self.engine):
            for req in self.engine.step():
                r = self._result(req)
                if r is not None:
                    evs.append(r)
        return evs

    def health(self) -> dict:
        if not self.alive:
            return {"status": "dead"}
        return self.engine.health()

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful stop: the engine finishes in-flight work and sheds its
        queue; every tracked request's terminal state is reported as a
        normal result event (shed ones come back ``rejected`` so the
        router re-routes them — never a terminal rejection)."""
        summary = self.engine.drain(timeout_s)
        # every still-tracked request now has a terminal state on the
        # Request object the engine handed back at submit; report each as
        # a normal result event. Shed ones surface ``rejected`` with
        # kind=draining so the router re-routes them (never terminal).
        for rid in list(self._by_req):
            fid = self._by_req.pop(rid)
            req = self._requests.pop(rid, None)
            self.inflight.pop(fid, None)
            if req is None:
                continue
            state = req.state if req.state != "running" else "timeout"
            ev = {"ev": "result", "id": fid, "state": state,
                  "tokens": list(req.tokens_out), "error": req.error}
            if state == REJECTED:
                ev["kind"] = "draining"
            self._events.append(ev)
        self.accepting = False
        self.alive = False  # a drained engine is closed; respawn to reuse
        return summary

    # -- migration ops (answers surface as events, like the wire mode) --------
    def request_export_prefix(self, xid: int, tokens) -> None:
        res = None
        if self.alive and hasattr(self.engine, "export_prefix_pages"):
            try:
                res = self.engine.export_prefix_pages(tokens)
            except ValueError:
                res = None  # layout refuses pages: an honest export miss
        if res is None:
            self._events.append({"ev": "pages", "xid": xid, "ok": False})
            return
        meta, blobs = res
        head = dict(meta, ev="pages", xid=xid, ok=True,
                    tokens=[int(t) for t in tokens])
        # round-trip the wire encoding even in-process, so every mode
        # exercises the same serialization the binary frame carries
        meta2, blobs2 = unpack_pages(pack_pages(head, blobs))
        meta2["_blobs"] = blobs2
        self._events.append(meta2)

    def request_export_request(self, xid: int, fid: int) -> None:
        res = None
        rid = next((r for r, f in self._by_req.items() if f == fid), None)
        req = self._requests.get(rid) if rid is not None else None
        if self.alive and req is not None \
                and hasattr(self.engine, "export_request_prefix"):
            try:
                res = self.engine.export_request_prefix(req)
            except ValueError:
                res = None
        if res is None:
            self._events.append({"ev": "pages", "xid": xid, "ok": False})
            return
        tokens, meta, blobs = res
        head = dict(meta, ev="pages", xid=xid, ok=True, tokens=tokens)
        meta2, blobs2 = unpack_pages(pack_pages(head, blobs))
        meta2["_blobs"] = blobs2
        self._events.append(meta2)

    def request_import_prefix(self, xid: int, tokens, meta: dict,
                              blobs) -> None:
        ok = False
        if self.alive and hasattr(self.engine, "ingest_prefix_pages"):
            try:
                ok = bool(self.engine.ingest_prefix_pages(tokens, meta,
                                                          blobs))
            except Exception:
                ok = False
        self._events.append(
            {"ev": "imported", "xid": xid, "ok": ok,
             "pages": int(meta.get("n_pages", 0)) if ok else 0})

    def request_evict_prefix(self, xid: int, tokens) -> None:
        n = 0
        if self.alive and hasattr(self.engine, "evict_prefix"):
            try:
                n = int(self.engine.evict_prefix(tokens))
            except Exception:
                n = 0
        self._events.append({"ev": "evicted", "xid": xid, "pages": n})

    def kill(self) -> None:
        """The in-process analog of SIGKILL: the engine vanishes with its
        in-flight work. ``inflight`` keeps the lost request docs for the
        router's requeue path."""
        self.alive = False
        self.accepting = False
        try:
            self.engine.close()
        except Exception:
            pass

    def close(self) -> None:
        if self.alive:
            try:
                self.engine.close()
            except Exception:
                pass
        self.alive = False


class ProcessReplica:
    """One ``python -m paddle_tpu.fleet.worker`` subprocess. The router
    writes op frames to its stdin and tails event frames from its stdout
    (non-blocking; pumped by ``poll()``). Death — clean exit or SIGKILL —
    surfaces as EOF/exit, flips ``alive`` False, and leaves ``inflight``
    holding exactly the request docs the router must requeue."""

    kind = "process"

    def __init__(self, spec: dict, index: int = 0,
                 telemetry_dir: Optional[str] = None,
                 trace_file: Optional[str] = None,
                 ready_timeout_s: float = 120.0):
        self.spec = dict(spec)
        self.index = int(index)
        self.name = "replica-%d" % self.index
        self.role = "uniform"  # the router stamps prefill/decode roles
        self.accepting = True
        self.inflight: Dict[int, dict] = {}
        self._events: List[dict] = []
        self._dead = False
        self.pid: Optional[int] = None
        self.trace_file = trace_file
        self.clock_offset_us = 0   # worker span clock − router span clock
        self.clock_rtt_us = 0      # min handshake round trip (error bound)
        # the worker inherits the parent's platform: a CPU fleet is one
        # whose router was started with JAX_PLATFORMS=cpu. On a TPU host a
        # chip belongs to one process, and workers cannot yet be handed a
        # device each (ROADMAP R6), so process fleets are a CPU path today
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        if telemetry_dir:
            os.makedirs(telemetry_dir, exist_ok=True)
            env["PADDLE_TPU_TELEMETRY_DIR"] = telemetry_dir
        else:
            # never let N workers share the parent's ring dir by accident
            env.pop("PADDLE_TPU_TELEMETRY_DIR", None)
        if trace_file:
            d = os.path.dirname(os.path.abspath(trace_file))
            if d:
                os.makedirs(d, exist_ok=True)
            env["PADDLE_TPU_TRACE_FILE"] = trace_file
        else:
            # N workers inheriting the parent's trace file would clobber
            # each other's fragment — arm per-replica or not at all
            env.pop("PADDLE_TPU_TRACE_FILE", None)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.fleet.worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        os.set_blocking(self.proc.stdout.fileno(), False)
        self.reader = FrameReader(self.proc.stdout.fileno())
        send_frame(self.proc.stdin, {"op": "spec", "spec": self.spec})
        self._wait_ready(ready_timeout_s)
        self._clock_sync()

    def _drain_frames(self) -> List[dict]:
        return _decode_frames(self.reader.drain())

    def _wait_ready(self, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            for ev in self._drain_frames():
                if ev.get("ev") == "ready":
                    self.pid = ev.get("pid")
                    return
                self._events.append(ev)
            if self.proc.poll() is not None:
                raise RuntimeError(
                    "fleet worker %d died during startup (rc=%s)"
                    % (self.index, self.proc.returncode))
            time.sleep(0.01)
        self.kill()
        raise RuntimeError("fleet worker %d not ready after %.0fs"
                           % (self.index, timeout_s))

    def _clock_sync(self, probes: int = 3, timeout_s: float = 5.0) -> None:
        """Measure this worker's span-clock offset with an NTP-style
        midpoint handshake: offset = worker_t − (t0+t1)/2, keeping the
        probe with the smallest round trip (its midpoint estimate has the
        tightest error bound, ±rtt/2). Runs AFTER ready — probing during
        engine build would fold warmup time into the midpoint. The
        offsets land in the trace-dir manifest so the merge can move
        every worker fragment onto the router's clock."""
        best_rtt = None
        best_off = 0
        for _ in range(probes):
            t0 = _tracer.now_us()
            if not self._send({"op": "clock"}):
                break
            reply = None
            t1 = t0
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                evs = self._drain_frames()
                t1 = _tracer.now_us()
                for ev in evs:
                    if ev.get("ev") == "clock" and reply is None:
                        reply = ev
                    else:
                        self._events.append(ev)
                if reply is not None:
                    break
                if self.reader.eof or self.proc.poll() is not None:
                    break
                time.sleep(0.001)
            if reply is None:
                break
            rtt = max(1, t1 - t0)
            off = int(reply.get("t_us", 0)) - (t0 + t1) // 2
            if best_rtt is None or rtt < best_rtt:
                best_rtt, best_off = rtt, off
        self.clock_offset_us = int(best_off)
        self.clock_rtt_us = int(best_rtt or 0)

    @property
    def alive(self) -> bool:
        return not self._dead

    def _send(self, op: dict) -> bool:
        if self._dead:
            return False
        try:
            send_frame(self.proc.stdin, op)
            return True
        except (BrokenPipeError, OSError):
            return False  # poll() will observe the death and requeue

    def submit(self, rdoc: dict) -> None:
        # track BEFORE sending: if the pipe breaks mid-write the request
        # is in inflight and the death path requeues it — never dropped
        self.inflight[rdoc["id"]] = rdoc
        self._send(dict(rdoc, op="submit"))

    def poll(self) -> List[dict]:
        evs, self._events = self._events, []  # drain events outlive alive
        if self._dead:
            return evs
        evs.extend(self._drain_frames())
        for ev in evs:
            if ev.get("ev") == "result":
                self.inflight.pop(ev.get("id"), None)
        if self.reader.eof or self.proc.poll() is not None:
            # peer gone: any frames already buffered were just returned;
            # what remains in inflight is the router's requeue set
            self._dead = True
            try:
                self.proc.wait(timeout=5)
            except Exception:
                pass
        return evs

    def health(self) -> dict:
        """Last health event wins; this just asks for a fresh one (the
        answer arrives on a later poll). Returns nothing synchronous —
        the router caches health from the event stream."""
        self._send({"op": "health"})
        return {}

    # -- migration ops: answers arrive as pages/imported/evicted events -------
    def request_export_prefix(self, xid: int, tokens) -> None:
        self._send({"op": "export_prefix", "xid": xid,
                    "tokens": [int(t) for t in tokens]})

    def request_export_request(self, xid: int, fid: int) -> None:
        self._send({"op": "export_request", "xid": xid, "id": fid})

    def request_import_prefix(self, xid: int, tokens, meta: dict,
                              blobs) -> None:
        head = {k: v for k, v in meta.items() if k != "_blobs"}
        head.update(op="import_prefix", xid=xid,
                    tokens=[int(t) for t in tokens])
        if self._dead:
            return
        try:
            send_binary_frame(self.proc.stdin, pack_pages(head, blobs))
        except (BrokenPipeError, OSError):
            pass  # poll() observes the death; the migration times out
        except ValueError:
            # oversize payload: the import can never be delivered —
            # synthesize the refusal so the router falls back immediately
            self._events.append({"ev": "imported", "xid": xid,
                                 "ok": False, "pages": 0})

    def request_evict_prefix(self, xid: int, tokens) -> None:
        self._send({"op": "evict_prefix", "xid": xid,
                    "tokens": [int(t) for t in tokens]})

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful stop: the worker drains its engine, reports every
        tracked request's terminal state, emits ``drained`` and exits.
        Result events collected here surface through the next poll()."""
        self.accepting = False
        if not self._send({"op": "drain", "timeout_s": timeout_s}):
            return {}
        summary: dict = {}
        deadline = time.monotonic() + (timeout_s or 30.0) + 10.0
        while time.monotonic() < deadline:
            for ev in self._drain_frames():
                if ev.get("ev") == "drained":
                    summary = ev.get("summary", {})
                else:
                    if ev.get("ev") == "result":
                        self.inflight.pop(ev.get("id"), None)
                    self._events.append(ev)
            if summary:
                break
            if self.proc.poll() is not None and self.reader.eof:
                break
            time.sleep(0.005)
        try:
            self.proc.wait(timeout=10)
        except Exception:
            self.proc.kill()
        self._dead = True
        return summary

    def kill(self) -> None:
        """SIGKILL — the crash drill's hammer. No goodbye frames: the
        router finds out the same way it would in production (EOF)."""
        try:
            self.proc.kill()
        except Exception:
            pass
        try:
            self.proc.wait(timeout=10)
        except Exception:
            pass

    def close(self) -> None:
        if not self._dead:
            self._send({"op": "shutdown"})
            try:
                self.proc.wait(timeout=10)
            except Exception:
                self.kill()
            self._dead = True

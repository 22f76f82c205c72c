"""Replica worker: one engine in one process, driven over stdin/stdout.

``python -m paddle_tpu.fleet.worker`` is what a :class:`ProcessReplica`
spawns. The first frame on stdin is the engine spec; everything the
router needs afterwards rides the frame protocol (protocol.py):

ops (router -> worker)::

    {"op": "spec", "spec": {...}}            # first frame only
    {"op": "submit", "id": <fleet id>, "prompt": [...],
     "max_new_tokens": n, "temperature": t, "top_k": k, "seed": s,
     "deadline_s": d}
    {"op": "health"}                         # answered by a health event
    {"op": "clock"}                          # answered by a clock event
    {"op": "export_prefix", "xid", "tokens"}   # -> pages (binary) | miss
    {"op": "export_request", "xid", "id"}      # -> pages (binary) | miss
    {"op": "evict_prefix", "xid", "tokens"}    # -> evicted
    <binary frame: pack_pages({"op": "import_prefix", "xid", "tokens",
     ...geometry...}, blobs)>                  # -> imported
    {"op": "drain", "timeout_s": t}          # graceful stop, then exit
    {"op": "shutdown"}                       # immediate close, then exit

events (worker -> router)::

    {"ev": "ready", "pid": ...}              # spec accepted, engine warm
    {"ev": "clock", "t_us": ...}             # tracer.now_us() snapshot
    {"ev": "result", "id", "state", "tokens", "error"[, "kind"]}
    {"ev": "health", "health": {...}}
    <binary frame: pack_pages({"ev": "pages", "xid", "ok": true, "tokens",
     ...geometry...}, blobs)>                # a KV-page export answer
    {"ev": "pages", "xid", "ok": false}      # export miss/refusal
    {"ev": "imported", "xid", "ok", "pages"} # import ack
    {"ev": "evicted", "xid", "pages"}        # evict ack
    {"ev": "drained", "summary": {...}}      # last frame before exit

Tracing: submits carry the fleet ``trace_id`` + ``attempt``, threaded
into the engine's Request so a real engine's serving spans join the
cross-process tree; the worker additionally emits one fleet-cat
``serve`` span per request (frame received → result sent) so sim-engine
workers are joinable too. The fragment file itself is the ordinary
``PADDLE_TPU_TRACE_FILE`` autostart (armed per-replica by the router);
the ``clock`` op is the router's offset handshake. An optional spec key
``"fault_plan"`` installs a ``reliability.faults`` plan process-wide —
per-replica chaos (e.g. a latency fault degrading one replica's tail).

The spec is the ISSUE's "engine handle extraction": the serving engine's
construction knobs, serialized. ``{"engine": "real", "model": {DecoderConfig
kwargs}, "model_seed": n, "serving": {ServingConfig kwargs}, "warmup": true}``
builds a DecoderLM + ServingEngine; ``{"engine": "sim", "sim": {SimConfig
kwargs}}`` builds the device-latency simulator (protocol/scaling benches on
hosts with no parallel compute to give).

fd hygiene: the frame channel is a dup of fd 1 taken at startup, after
which fd 1 is pointed at stderr — a stray ``print`` inside jax or user
code can then never corrupt the frame stream.

Request accounting mirrors InProcessReplica: every submitted id gets
exactly one result event — typed rejections (draining/backpressure)
carry ``kind`` so the router re-routes instead of terminating them, and a
drain reports the terminal state of everything still tracked before the
``drained`` frame.
"""

from __future__ import annotations

import os
import select
import sys
import time
from typing import Dict, Optional

from ..monitor import tracer as _tracer
from ..serving.request import (FAILED, REJECTED, BackpressureError,
                               DrainingError, Request)
from . import trace as _ftrace
from .protocol import (Binary, FrameReader, pack_pages, send_binary_frame,
                       send_frame, unpack_pages)

__all__ = ["main"]


def _build_engine(spec: dict):
    if spec.get("engine", "real") == "sim":
        from .replica import SimConfig, SimEngine

        cfg = SimConfig(**spec.get("sim", {}))
        # one engine per worker process: serving-slot virtual tracks are
        # collision-free here, so the sim emits the full serving-cat
        # request lifecycle the phase ledger decomposes
        cfg.serving_spans = True
        return SimEngine(cfg)
    from ..models.decoder_lm import DecoderConfig, DecoderLM
    from ..serving.engine import ServingConfig, ServingEngine

    mcfg = DecoderConfig(**spec.get("model", {}))
    model = DecoderLM(mcfg, seed=int(spec.get("model_seed", 0)))
    engine = ServingEngine(model, ServingConfig(**spec.get("serving", {})))
    if spec.get("warmup"):
        engine.warmup()
    return engine


class _Worker:
    def __init__(self, chan, engine):
        self.chan = chan
        self.engine = engine
        self._by_req: Dict[int, int] = {}      # engine Request.id -> fleet id
        self._requests: Dict[int, Request] = {}
        # engine Request.id -> (trace_id, attempt, frame-received time);
        # feeds the per-request ``serve`` span on the worker's own track
        self._meta: Dict[int, tuple] = {}

    def emit(self, ev: dict) -> None:
        send_frame(self.chan, ev)

    def _result(self, req: Request) -> None:
        fid = self._by_req.pop(req.id, None)
        self._requests.pop(req.id, None)
        meta = self._meta.pop(req.id, None)
        if fid is None:
            return
        if meta is not None:
            _ftrace.on_worker_serve(meta[0], meta[1], req.state, meta[2],
                                    time.perf_counter())
        self.emit({"ev": "result", "id": fid, "state": req.state,
                   "tokens": list(req.tokens_out), "error": req.error})

    def submit(self, op: dict) -> None:
        t_recv = time.perf_counter()
        try:
            req = self.engine.submit(
                op["prompt"], op["max_new_tokens"],
                deadline_s=op.get("deadline_s"),
                temperature=op.get("temperature", 0.0),
                top_k=op.get("top_k", 0), seed=op.get("seed"),
                trace_id=op.get("trace_id"),
                attempt=int(op.get("attempt", 0)))
        except DrainingError:
            self.emit({"ev": "result", "id": op["id"], "state": REJECTED,
                       "kind": "draining"})
            return
        except BackpressureError:
            self.emit({"ev": "result", "id": op["id"], "state": REJECTED,
                       "kind": "backpressure"})
            return
        except ValueError as e:
            self.emit({"ev": "result", "id": op["id"], "state": FAILED,
                       "tokens": [], "error": str(e)})
            return
        self._by_req[req.id] = op["id"]
        self._requests[req.id] = req
        self._meta[req.id] = (op.get("trace_id"), int(op.get("attempt", 0)),
                              t_recv)

    def pump(self) -> None:
        for req in self.engine.step():
            self._result(req)

    # -- KV-page migration ops ------------------------------------------------
    # export answers ride ONE binary frame (meta envelope + raw page
    # blobs, see protocol.pack_pages); misses and import acks are plain
    # JSON events. Engines without the migration surface (or layouts
    # without pages) answer honest misses/refusals, never crash.
    def _emit_pages(self, xid, res, tokens=None) -> None:
        if res is None:
            self.emit({"ev": "pages", "xid": xid, "ok": False})
            return
        if tokens is None:
            tokens, meta, blobs = res
        else:
            meta, blobs = res
        head = dict(meta, ev="pages", xid=xid, ok=True,
                    tokens=[int(t) for t in tokens])
        send_binary_frame(self.chan, pack_pages(head, blobs))

    def export_prefix(self, op: dict) -> None:
        res = None
        if hasattr(self.engine, "export_prefix_pages"):
            try:
                res = self.engine.export_prefix_pages(op.get("tokens") or [])
            except ValueError:
                res = None
        self._emit_pages(op.get("xid"), res, tokens=op.get("tokens") or [])

    def export_request(self, op: dict) -> None:
        res = None
        fid = op.get("id")
        rid = next((r for r, f in self._by_req.items() if f == fid), None)
        req = self._requests.get(rid) if rid is not None else None
        if req is not None and hasattr(self.engine, "export_request_prefix"):
            try:
                res = self.engine.export_request_prefix(req)
            except ValueError:
                res = None
        self._emit_pages(op.get("xid"), res)

    def import_prefix(self, meta: dict, blobs) -> None:
        ok = False
        if hasattr(self.engine, "ingest_prefix_pages"):
            try:
                ok = bool(self.engine.ingest_prefix_pages(
                    meta.get("tokens") or [], meta, blobs))
            except Exception:
                ok = False
        self.emit({"ev": "imported", "xid": meta.get("xid"), "ok": ok,
                   "pages": int(meta.get("n_pages", 0)) if ok else 0})

    def evict_prefix(self, op: dict) -> None:
        n = 0
        if hasattr(self.engine, "evict_prefix"):
            try:
                n = int(self.engine.evict_prefix(op.get("tokens") or []))
            except Exception:
                n = 0
        self.emit({"ev": "evicted", "xid": op.get("xid"), "pages": n})

    def busy(self) -> bool:
        if hasattr(self.engine, "idle"):
            return not self.engine.idle()
        return not self.engine.scheduler.idle()

    def drain(self, timeout_s: Optional[float]) -> None:
        summary = self.engine.drain(timeout_s)
        for rid in list(self._by_req):
            req = self._requests.pop(rid, None)
            fid = self._by_req.pop(rid)
            meta = self._meta.pop(rid, None)
            if req is None:
                continue
            state = req.state if req.state != "running" else "timeout"
            if meta is not None:
                _ftrace.on_worker_serve(meta[0], meta[1], state, meta[2],
                                        time.perf_counter())
            ev = {"ev": "result", "id": fid, "state": state,
                  "tokens": list(req.tokens_out), "error": req.error}
            if state == REJECTED:
                # shed by the drain, not refused by policy: the router
                # re-routes these to a peer — zero rejected-by-bug
                ev["kind"] = "draining"
            self.emit(ev)
        self.emit({"ev": "drained", "summary": summary})


def main() -> int:
    # claim the frame channel, then point fd 1 at stderr so stray prints
    # (jax warnings, user hooks) can never tear a frame
    chan = os.fdopen(os.dup(1), "wb", buffering=0)
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    stdin_fd = sys.stdin.fileno()
    os.set_blocking(stdin_fd, False)
    reader = FrameReader(stdin_fd)

    spec = None
    deadline = time.monotonic() + 60.0
    while spec is None and time.monotonic() < deadline:
        select.select([stdin_fd], [], [], 1.0)
        for frame in reader.drain():
            if isinstance(frame, Binary):
                continue
            if frame.get("op") == "spec":
                spec = frame.get("spec", {})
                break
        if reader.eof:
            return 1
    if spec is None:
        return 1

    # the worker process owns its telemetry ring (PADDLE_TPU_TELEMETRY_DIR
    # is set per-replica by ProcessReplica): sim engines get a series too,
    # and release() flushes a final partial sample even for short lives
    from ..monitor import telemetry as _telemetry

    if spec.get("fault_plan"):
        # per-replica chaos: the router passes a plan for THIS replica
        # only (FleetConfig.spec_overrides), e.g. a latency fault that
        # degrades one replica's tail for the fleet-SLO drill
        from ..reliability import faults as _faults

        _faults.install(_faults.FaultPlan.parse(str(spec["fault_plan"])))

    tele = _telemetry.acquire()
    try:
        worker = _Worker(chan, _build_engine(spec))
        worker.emit({"ev": "ready", "pid": os.getpid()})

        while True:
            timeout = 0.0 if worker.busy() else 0.05
            select.select([stdin_fd], [], [], timeout)
            for op in reader.drain():
                if isinstance(op, Binary):
                    # the bulk lane: one self-describing page payload
                    try:
                        meta, blobs = unpack_pages(op.payload)
                    except ValueError:
                        continue  # foreign/garbled payload: drop
                    if meta.get("op") == "import_prefix":
                        worker.import_prefix(meta, blobs)
                    continue
                kind = op.get("op")
                if kind == "submit":
                    worker.submit(op)
                elif kind == "export_prefix":
                    worker.export_prefix(op)
                elif kind == "export_request":
                    worker.export_request(op)
                elif kind == "evict_prefix":
                    worker.evict_prefix(op)
                elif kind == "health":
                    worker.emit({"ev": "health",
                                 "health": worker.engine.health()})
                elif kind == "clock":
                    # offset handshake: one span-clock sample, answered
                    # immediately (the router brackets it with its own
                    # now_us() reads and takes the midpoint)
                    worker.emit({"ev": "clock", "t_us": _tracer.now_us()})
                elif kind == "drain":
                    worker.drain(op.get("timeout_s"))
                    return 0
                elif kind == "shutdown":
                    worker.engine.close()
                    return 0
            if reader.eof:
                # router gone: nothing to report results to — close + exit
                worker.engine.close()
                return 0
            if worker.busy():
                worker.pump()
    finally:
        _telemetry.release(tele)


if __name__ == "__main__":
    sys.exit(main())

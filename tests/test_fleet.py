"""Fleet subsystem tests: prefix-cache hashing/LRU/poisoning, the frame
protocol (JSON + binary page-payload frames), router exactly-once
accounting under kill/restart, cross-process telemetry aggregation
(ISSUE 15 tentpole coverage), and the disaggregation plane — KV-page
serialization round-trips on every cache layout, cross-replica prefix
shipping, and scale-down migration (ISSUE 18). Router tests run on
in-process sim engines — the process-worker path is covered by
tools/chaos_drill (a smoke gate)."""

import io
import os
import subprocess
import sys

import pytest

from paddle_tpu.fleet import (FleetBackpressure, FleetConfig, FleetRequest,
                              PrefixCache, Router, SimConfig, SimEngine,
                              aggregate_telemetry, prefix_key)
from paddle_tpu.fleet import metrics as fm
from paddle_tpu.fleet.protocol import (MAX_FRAME, Binary, FrameReader,
                                       pack_pages, read_frame, send_frame,
                                       send_binary_frame, unpack_pages)
from paddle_tpu.serving import metrics as sm

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- prefix_key ---------------------------------------------------------------
class TestPrefixKey:
    def test_deterministic_and_order_sensitive(self):
        assert prefix_key([1, 2, 3]) == prefix_key([1, 2, 3])
        assert prefix_key([1, 2, 3]) != prefix_key([3, 2, 1])
        assert prefix_key([1, 2]) != prefix_key([1, 2, 3])
        # numpy ints and Python ints hash identically
        import numpy as np

        assert prefix_key(np.array([5, 6, 7])) == prefix_key([5, 6, 7])

    def test_stable_across_processes(self):
        """The router and its worker replicas MUST derive the same key
        from the same tokens — Python hash() is salted per process, so
        this would fail if prefix_key ever leaned on it."""
        toks = list(range(40, 72))
        out = subprocess.run(
            [sys.executable, "-c",
             "from paddle_tpu.serving.prefix_cache import prefix_key;"
             "print(prefix_key(range(40, 72)))"],
            cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONHASHSEED="12345"),
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == prefix_key(toks)


# -- PrefixCache (host bookkeeping) -------------------------------------------
class TestPrefixCache:
    def test_cacheable_len_keeps_a_remainder_token(self):
        c = PrefixCache(page_budget=8, page_size=8)
        # a prompt that exactly fills pages still leaves >= 1 token out
        assert c.cacheable_len(16) == 8
        assert c.cacheable_len(17) == 16
        assert c.cacheable_len(8) == 0
        assert c.cacheable_len(3) == 0

    def test_insert_lookup_longest_match(self):
        c = PrefixCache(page_budget=8, page_size=4)
        base = list(range(100, 112))  # 12 tokens = 3 pages
        ok, evicted = c.insert(base[:4], [0])
        assert ok and not evicted
        ok, _ = c.insert(base[:8], [1, 2])
        assert ok
        # longest page-aligned prefix wins: 12-token prompt -> 8-token hit
        hit = c.lookup(base + [999])
        assert hit is not None and hit.tokens == tuple(base[:8])
        assert hit.pages == [1, 2]
        # shorter prompt falls back to the 4-token entry
        hit = c.lookup(base[:6])
        assert hit is not None and hit.tokens == tuple(base[:4])
        # different tokens with the same length miss entirely
        assert c.lookup([7] * 12) is None

    def test_refusals_keep_ownership_with_caller(self):
        c = PrefixCache(page_budget=2, page_size=4)
        assert c.insert([1, 2, 3, 4], [10]) == (True, [])
        # duplicate: refused, nothing evicted
        assert c.insert([1, 2, 3, 4], [11]) == (False, [])
        # token/page length mismatch: refused
        assert c.insert([1, 2, 3], [12]) == (False, [])
        # larger than the whole budget: refused even against an empty LRU
        assert c.insert(list(range(12)), [13, 14, 15]) == (False, [])
        assert c.pages_held == 1

    def test_lru_eviction_returns_pages(self):
        c = PrefixCache(page_budget=2, page_size=4)
        c.insert([1, 2, 3, 4], [10])
        c.insert([5, 6, 7, 8], [11])
        # touch the first entry so the SECOND is LRU
        assert c.lookup([1, 2, 3, 4, 9]) is not None
        ok, evicted = c.insert([9, 10, 11, 12], [12])
        assert ok and evicted == [11], "LRU order ignored recency"
        assert c.pages_held == 2 and len(c) == 2

    def test_flush_returns_every_owned_page(self):
        c = PrefixCache(page_budget=4, page_size=4)
        c.insert([1, 2, 3, 4], [10])
        c.insert([5, 6, 7, 8], [11, 12][:1])
        assert sorted(c.flush()) == [10, 11]
        assert c.pages_held == 0 and len(c) == 0 and c.flush() == []

    def test_counters_tick(self):
        h0, m0 = sm.PREFIX_HITS.value, sm.PREFIX_MISSES.value
        i0, e0 = sm.PREFIX_INSERTS.value, sm.PREFIX_EVICTIONS.value
        c = PrefixCache(page_budget=1, page_size=4)
        c.insert([1, 2, 3, 4], [0])
        assert c.lookup([1, 2, 3, 4, 5]) is not None
        assert c.lookup([9, 9, 9, 9, 9]) is None
        c.insert([5, 6, 7, 8], [1])  # evicts the first
        assert sm.PREFIX_HITS.value == h0 + 1
        assert sm.PREFIX_MISSES.value == m0 + 1
        assert sm.PREFIX_INSERTS.value == i0 + 2
        assert sm.PREFIX_EVICTIONS.value == e0 + 1


# -- frame protocol -----------------------------------------------------------
class TestProtocol:
    def test_round_trip(self):
        buf = io.BytesIO()
        docs = [{"op": "submit", "id": 3, "prompt": [1, 2, 3]},
                {"ev": "result", "tokens": list(range(100)),
                 "error": None, "unicode": "påge"}]
        for d in docs:
            send_frame(buf, d)
        buf.seek(0)
        assert [read_frame(buf) for _ in docs] == docs
        assert read_frame(buf) is None  # clean EOF

    def test_torn_frame_is_eof_not_garbage(self):
        buf = io.BytesIO()
        send_frame(buf, {"a": 1})
        data = buf.getvalue()
        for cut in (1, 3, 5, len(data) - 1):  # mid-header and mid-payload
            assert read_frame(io.BytesIO(data[:cut])) is None

    def test_oversized_frame_rejected(self):
        buf = io.BytesIO((MAX_FRAME + 1).to_bytes(4, "big") + b"x")
        with pytest.raises(ValueError):
            read_frame(buf)

    def test_reader_reassembles_split_writes(self):
        r, w = os.pipe()
        try:
            os.set_blocking(r, False)
            reader = FrameReader(r)
            buf = io.BytesIO()
            send_frame(buf, {"n": 1})
            send_frame(buf, {"n": 2})
            data = buf.getvalue()
            got = []
            for i in range(0, len(data), 3):  # drip 3 bytes at a time
                os.write(w, data[i:i + 3])
                got.extend(reader.drain())
            assert got == [{"n": 1}, {"n": 2}]
            os.close(w)
            assert reader.drain() == [] and reader.eof
        finally:
            os.close(r)


# -- binary page-payload frames (ISSUE 18) ------------------------------------
class TestBinaryFrames:
    def test_mixed_json_and_binary_round_trip(self):
        """Binary frames interleave with JSON on the same stream; the
        length-word top bit tells them apart, bytes come back verbatim."""
        buf = io.BytesIO()
        payload = bytes(range(256)) * 7
        send_frame(buf, {"op": "submit", "id": 1})
        send_binary_frame(buf, payload)
        send_frame(buf, {"ev": "result", "id": 1})
        buf.seek(0)
        assert read_frame(buf) == {"op": "submit", "id": 1}
        got = read_frame(buf)
        assert isinstance(got, Binary) and got.payload == payload
        assert read_frame(buf) == {"ev": "result", "id": 1}
        assert read_frame(buf) is None

    def test_torn_binary_frame_is_eof_not_garbage(self):
        buf = io.BytesIO()
        send_binary_frame(buf, b"kvpagebytes" * 100)
        data = buf.getvalue()
        for cut in (1, 3, 4, 10, len(data) - 1):
            assert read_frame(io.BytesIO(data[:cut])) is None

    def test_oversized_binary_rejected_both_ends(self):
        """The sender refuses before poisoning the stream; a reader that
        sees an oversize binary length word raises the same typed error
        (both sides treat it as a corrupt stream, not a big payload)."""
        with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
            send_binary_frame(io.BytesIO(), b"\0" * (MAX_FRAME + 1))
        word = (0x80000000 | (MAX_FRAME + 1)).to_bytes(4, "big")
        with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
            read_frame(io.BytesIO(word + b"x"))
        r, w = os.pipe()
        try:
            os.set_blocking(r, False)
            reader = FrameReader(r)
            os.write(w, word + b"x")
            with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
                reader.drain()
        finally:
            os.close(r)
            os.close(w)

    def test_reader_reassembles_split_binary_writes(self):
        """A binary frame dripped through a pipe in small chunks (the
        kernel tears large page payloads across reads) reassembles into
        one Binary; the torn tail stays buffered between drains."""
        r, w = os.pipe()
        try:
            os.set_blocking(r, False)
            reader = FrameReader(r)
            buf = io.BytesIO()
            send_frame(buf, {"n": 1})
            send_binary_frame(buf, bytes(range(251)) * 5)
            send_frame(buf, {"n": 2})
            data = buf.getvalue()
            got = []
            for i in range(0, len(data), 7):
                os.write(w, data[i:i + 7])
                got.extend(reader.drain())
            assert len(got) == 3
            assert got[0] == {"n": 1} and got[2] == {"n": 2}
            assert isinstance(got[1], Binary)
            assert got[1].payload == bytes(range(251)) * 5
            os.close(w)
            assert reader.drain() == [] and reader.eof
        finally:
            os.close(r)

    def test_pack_unpack_pages_round_trip(self):
        meta = {"ev": "pages", "xid": 7, "layout": "paged", "n_pages": 2}
        blobs = [b"k" * 1000, b"v" * 1000, b"", b"\x00\xff" * 8]
        meta2, blobs2 = unpack_pages(pack_pages(meta, blobs))
        assert blobs2 == blobs
        assert {k: meta2[k] for k in meta} == meta
        assert meta2["blob_lens"] == [1000, 1000, 0, 16]

    def test_torn_page_payload_raises_typed_error(self):
        payload = pack_pages({"ev": "pages", "xid": 1}, [b"abc" * 64])
        for cut in (2, 6, len(payload) - 1):
            with pytest.raises(ValueError, match="torn page payload"):
                unpack_pages(payload[:cut])
        with pytest.raises(ValueError, match="torn page payload"):
            unpack_pages(payload + b"extra")


# -- router over in-process sims ----------------------------------------------
def _sim_router(n=2, slots=2, **kw):
    kw.setdefault("affinity", "round_robin")
    return Router(FleetConfig(
        replicas=n, mode="inprocess",
        engine_factory=lambda i: SimEngine(SimConfig(slots=slots)), **kw))


class TestRouter:
    def test_exactly_once_and_seed_pinning(self):
        router = _sim_router()
        frs = [router.submit([1, i], 4) for i in range(8)]
        assert all(f.seed is not None for f in frs), \
            "unseeded requests cannot replay deterministically"
        assert router.wait_all(20.0)
        acc = router.accounting()
        assert len(acc) == 8 and set(acc.values()) == {"finished"}
        assert all(len(f.tokens) == 4 for f in frs)
        router.close()

    def test_backpressure_is_typed_not_silent(self):
        router = _sim_router(n=1, max_queue=2, max_outstanding=1)
        router.submit([1], 4)
        router.submit([2], 4)
        with pytest.raises(FleetBackpressure):
            router.submit([3], 4)
        assert router.wait_all(20.0)
        router.close()
        with pytest.raises(FleetBackpressure):
            router.submit([4], 4)  # closed router rejects loudly too

    def test_kill_requeues_and_replays_bit_identical(self):
        req0 = fm.REQUEUED.value
        router = _sim_router(n=2, slots=1)
        frs = [router.submit([3, 3, i], 6, temperature=0.9)
               for i in range(6)]
        for _ in range(2):
            router.pump()
        router._replicas[1].kill()
        assert router.wait_all(20.0)
        assert set(router.accounting().values()) == {"finished"}
        assert fm.REQUEUED.value > req0
        twin = _sim_router(n=1, slots=1)
        frs_t = [twin.submit([3, 3, i], 6, temperature=0.9)
                 for i in range(6)]
        assert twin.wait_all(20.0)
        assert [f.tokens for f in frs] == [f.tokens for f in frs_t]
        router.close()
        twin.close()

    def test_requeue_limit_fails_loudly(self):
        """A request that keeps landing on dying replicas must become
        FAILED — never retry forever, never vanish."""
        router = _sim_router(n=1, slots=1, requeue_limit=1,
                             auto_restart=False)
        fr = router.submit([1, 2], 4)
        router.pump()
        router._replicas[0].kill()
        # manual respawn/kill cycle: each pump requeues, each kill burns
        # one attempt
        for _ in range(4):
            router.pump()
            if fr.terminal:
                break
            router._respawn(0)
            router.pump()
            router._replicas[0].kill()
        assert fr.state == "failed", fr.state
        assert router.accounting()[fr.id] == "failed"
        router.close()

    def test_rolling_restart_rejects_nothing(self):
        router = _sim_router(n=2)
        frs = [router.submit([2, i], 5) for i in range(6)]
        for _ in range(2):
            router.pump()
        router.rolling_restart(10.0)
        assert router.wait_all(20.0)
        acc = router.accounting()
        assert "rejected" not in acc.values(), acc
        assert all(f.state == "finished" for f in frs)
        router.close()

    def test_degraded_replica_gets_no_new_traffic(self):
        engines = {}

        def factory(i):
            engines[i] = SimEngine(SimConfig(slots=2))
            return engines[i]

        router = Router(FleetConfig(replicas=2, mode="inprocess",
                                    affinity="round_robin",
                                    engine_factory=factory))
        engines[0].force_degraded = True
        frs = [router.submit([4, i], 3) for i in range(6)]
        assert router.wait_all(20.0)
        assert all(f.state == "finished" for f in frs)
        assert all(f.last_replica == 1 for f in frs), \
            [f.last_replica for f in frs]
        router.close()

    def test_drain_terminates_everything_exactly_once(self):
        router = _sim_router(n=2)
        frs = [router.submit([6, i], 4) for i in range(5)]
        router.drain()
        states = {f.state for f in frs}
        assert states <= {"finished", "rejected"}, states
        acc = router.accounting()
        assert len(acc) == 5 and all(v in ("finished", "rejected")
                                     for v in acc.values())

    def test_fleet_request_doc_round_trips_the_wire_fields(self):
        fr = FleetRequest(7, [1, 2, 3], 5, temperature=0.5, top_k=3,
                          seed=42)
        d = fr.doc()
        assert d["id"] == 7 and d["prompt"] == [1, 2, 3]
        assert d["max_new_tokens"] == 5 and d["seed"] == 42
        import json

        assert json.loads(json.dumps(d)) == d  # frame-protocol safe

    def test_a_frame_key_no_replica_reads_is_ignored(self):
        """A submit frame from an older router may still carry
        ``speculation``: the replica serves it as a frame without the key."""
        from paddle_tpu.fleet.replica import InProcessReplica

        rep = InProcessReplica(SimEngine(SimConfig(slots=2)))
        doc = FleetRequest(3, [5, 5, 5], 4, seed=9).doc()
        rep.submit(dict(doc, speculation=4))
        rep.submit(dict(doc, id=4))
        results = {}
        for _ in range(50):
            results.update((e["id"], e) for e in rep.poll()
                           if e["ev"] == "result")
            if len(results) == 2:
                break
        assert results[3]["state"] == results[4]["state"] == "finished"
        assert results[3]["tokens"] == results[4]["tokens"]


class TestAggregateTelemetry:
    def test_merges_replica_rings(self, tmp_path):
        from paddle_tpu.monitor import metrics as mx
        from paddle_tpu.monitor import telemetry

        mx.enable()
        base = str(tmp_path / "fleet")
        for i in range(3):
            d = os.path.join(base, "replica_%d" % i)
            os.makedirs(d)
            exp = telemetry.TelemetryExporter(d, interval_s=999.0)
            mx.counter("test/fleet_agg").inc(i + 1)
            exp.tick()
            exp.stop()
        agg = aggregate_telemetry(base)
        assert sorted(agg) == ["replica_0", "replica_1", "replica_2"]
        for v in agg.values():
            assert v["samples"] >= 1 and "last" in v

    def test_empty_base_is_empty_not_fatal(self, tmp_path):
        assert aggregate_telemetry(str(tmp_path)) == {}
        assert aggregate_telemetry(str(tmp_path / "nonexistent")) == {}

    def test_degenerate_rings_flag_not_throw(self, tmp_path):
        """The three ways a replica's ring goes wrong — never ticked,
        crashed mid-append, never appeared — each yield a flagged entry,
        never an exception, never a silent hole."""
        base = str(tmp_path / "fleet")
        os.makedirs(os.path.join(base, "replica_0"))  # spawned, no tick yet
        d1 = os.path.join(base, "replica_1")          # torn tail only
        os.makedirs(d1)
        with open(os.path.join(d1, "telemetry_123_0.jsonl"), "w") as f:
            f.write('{"schema": "paddle_tpu.telemetry/v1", "seq": 1, "tr')
        agg = aggregate_telemetry(base, expected=[0, 1, 2])
        assert agg["replica_0"]["flag"] == "no complete samples"
        assert agg["replica_1"]["flag"] == "no complete samples"
        assert agg["replica_2"]["flag"] == "ring dir missing"
        assert all(v["samples"] == 0 for v in agg.values())

    def test_missing_base_with_expected_flags_every_replica(self, tmp_path):
        agg = aggregate_telemetry(str(tmp_path / "never_made"), expected=[0, 1])
        assert sorted(agg) == ["replica_0", "replica_1"]
        assert all(v["flag"] == "ring dir missing" for v in agg.values())

    def test_numeric_replica_order(self, tmp_path):
        base = str(tmp_path / "fleet")
        for i in (0, 1, 2, 10):
            os.makedirs(os.path.join(base, "replica_%d" % i))
        assert list(aggregate_telemetry(base)) == [
            "replica_0", "replica_1", "replica_2", "replica_10"]


# -- fleet event log ----------------------------------------------------------
class TestFleetEventLog:
    def test_round_trip_skips_torn_tail(self, tmp_path):
        from paddle_tpu.fleet.events import FleetEventLog, read_events

        p = str(tmp_path / "events.jsonl")
        log = FleetEventLog(p)
        assert log.armed
        log.emit("spawn", replica=0)
        log.emit("kill_detected", replica=0, lost=2)
        log.close()
        with open(p, "a") as f:
            f.write('{"kind": "torn')  # crash mid-append
        evs = read_events(p)
        assert [e["kind"] for e in evs] == ["spawn", "kill_detected"]
        assert len({e["run_id"] for e in evs}) == 1
        kills = read_events(p, kind="kill_detected")
        assert len(kills) == 1 and kills[0]["lost"] == 2

    def test_unwritable_path_disarms_never_raises(self, tmp_path):
        from paddle_tpu.fleet.events import FleetEventLog

        bad = os.path.join(str(tmp_path / "file_not_dir"), "x", "e.jsonl")
        with open(str(tmp_path / "file_not_dir"), "w") as f:
            f.write("occupied")
        log = FleetEventLog(bad)
        assert not log.armed
        assert log.emit("spawn", replica=0) is None  # no-op, no raise


# -- fleet SLO plane ----------------------------------------------------------
class TestFleetSLO:
    def test_merge_fleet_docs_sums_deltas(self):
        from paddle_tpu.fleet.slo import merge_fleet_docs

        docs = [
            {"t": 10.0, "dt_s": 2.0,
             "metrics": {"g": {"type": "gauge", "value": 2.0}},
             "deltas": {"counters": {"c": 1.0}, "gauges": {"g": 2.0},
                        "histograms": {"h": {"count": 2, "sum": 10.0,
                                             "buckets": {"5": 2}}}}},
            {"t": 11.0, "dt_s": 3.0,
             "metrics": {"g": {"type": "gauge", "value": 3.0}},
             "deltas": {"counters": {"c": 2.0}, "gauges": {"g": 3.0},
                        "histograms": {"h": {"count": 1, "sum": 7.0,
                                             "buckets": {"10": 1}}}}},
        ]
        s = merge_fleet_docs(docs, seq=1)
        assert s.counter_delta("c") == 3.0
        assert s.gauge_value("g") == 5.0  # queue depths ADD across a fleet
        h = s.histogram_delta("h")
        assert h["count"] == 3 and h["sum"] == 17.0
        assert h["buckets"] == {"5": 2, "10": 1}
        assert s.dt_s == 3.0  # widest window, not the sum

    def test_breach_fires_both_scopes_and_cursor_dedupes(self, tmp_path):
        import json

        from paddle_tpu.fleet.slo import FleetSLO
        from paddle_tpu.monitor.slo import parse_slos

        base = str(tmp_path)
        d = os.path.join(base, "replica_0")
        os.makedirs(d)
        doc = {"schema": "paddle_tpu.telemetry/v1", "seq": 1, "pid": 1,
               "t": 1.0, "dt_s": 1.0,
               "metrics": {"fleet/queue_depth": {"type": "gauge",
                                                 "value": 9.0}},
               "deltas": {"counters": {}, "histograms": {},
                          "gauges": {"fleet/queue_depth": 9.0}}}
        with open(os.path.join(d, "telemetry_1_0.jsonl"), "w") as f:
            f.write(json.dumps(doc) + "\n")
        hits = []
        slo = FleetSLO(
            parse_slos("fleet/queue_depth<=5"),
            on_replica_breach=lambda i, b: hits.append(("replica", i)),
            on_fleet_breach=lambda b: hits.append(("fleet",)))
        out = slo.evaluate(base, [0])
        assert out["replica"].get(0) and out["fleet"]
        assert ("replica", 0) in hits and ("fleet",) in hits
        # per-(replica, pid) seq cursor: the same sample never
        # re-evaluates on the next pass
        hits.clear()
        assert slo.evaluate(base, [0]) == {"replica": {}, "fleet": []}
        assert not hits


# -- fleet trace: orphan closure + in-process round trip ----------------------
class TestFleetTrace:
    def test_close_orphans_synthesizes_tagged_closures(self):
        from paddle_tpu.fleet import trace as ftrace

        spans = [
            {"name": "submitted", "cat": "fleet", "ts_us": 0, "dur_us": 0,
             "pid": 1, "tid": -1, "track": ftrace.QUEUE_TRACK,
             "args": {"trace_id": "t1"}},
            {"name": "queued", "cat": "fleet", "ts_us": 0, "dur_us": 5,
             "pid": 1, "tid": -1, "track": ftrace.QUEUE_TRACK,
             "args": {"trace_id": "t1", "attempt": 1}},
            # a dispatch whose attempt never closed and a request with no
            # terminal: what a SIGKILLed ROUTER would leave behind
            {"name": "dispatch", "cat": "fleet", "ts_us": 5, "dur_us": 0,
             "pid": 1, "tid": -2, "track": "replica 0",
             "args": {"trace_id": "t1", "attempt": 1}},
            {"name": "drain", "cat": "fleet", "ts_us": 0, "dur_us": 100,
             "pid": 1, "tid": -3, "track": ftrace.LIFECYCLE_TRACK,
             "args": {}},
        ]
        out, n = ftrace.close_orphans(spans)
        assert n == 2
        synth = [s for s in out if (s.get("args") or {}).get("synthetic")]
        att = next(s for s in synth if s["name"] == "attempt 1")
        assert att["args"]["killed"] and att["dur_us"] >= 1
        term = next(s for s in synth if s["name"] == "failed")
        assert term["dur_us"] == 0
        # the validator runs the same closure pass itself on raw spans
        digests = ftrace.validate_fleet_spans(spans)
        assert digests["t1"]["synthetic"]
        assert digests["t1"]["state"] == "failed"
        assert digests["_meta"]["synthetic_closures"] == 2

    def test_inprocess_router_trace_validates(self, tmp_path):
        """A traced in-process fleet round trip: the router's own spans
        alone form a validating request tree (submitted -> queued ->
        dispatch -> attempt 1 -> terminal), zero synthetic closures."""
        from paddle_tpu.fleet import trace as ftrace

        trace_dir = str(tmp_path / "trace")
        router = Router(FleetConfig(
            replicas=2, mode="inprocess", affinity="round_robin",
            engine_factory=lambda i: SimEngine(SimConfig(slots=2)),
            trace_dir=trace_dir))
        frs = [router.submit([1, i], 4) for i in range(5)]
        assert router.wait_all(20.0)
        router.close()
        spans, manifest, problems = ftrace.load_fragments(trace_dir)
        assert not problems and manifest.get("run_id")
        digests = ftrace.validate_fleet_spans(spans)
        meta = digests.pop("_meta")
        assert meta["requests"] == 5
        assert meta["synthetic_closures"] == 0
        assert all(d["state"] == "finished" and d["attempts"] == [1]
                   for d in digests.values())
        trace_ids = {f.trace_id for f in frs}
        assert set(digests) == trace_ids


# -- engine-level prefix cache (real model) -----------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=1, d_model=16,
                                   n_head=2, max_seq=64)
    return decoder_lm.DecoderLM(cfg, seed=3)


def _prefix_engine(model, pages=8):
    from paddle_tpu import serving

    return serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, num_pages=32,
        prefix_cache_pages=pages))


class TestEnginePrefixCache:
    def test_config_validates_budget(self, tiny_model):
        from paddle_tpu import serving

        with pytest.raises(ValueError):
            serving.ServingConfig(slots=2, page_size=8, max_seq=64,
                                  num_pages=16, prefix_cache_pages=16)

    def test_hit_skips_prefill_and_matches_cold_stream(self, tiny_model):
        sys_prompt = list(range(1, 18))  # 17 tokens: 2 full pages cached
        eng = _prefix_engine(tiny_model)
        p0 = sm.PREFILL_COUNT.value
        h0 = sm.PREFIX_HITS.value
        r1 = eng.submit(sys_prompt + [30], 5, temperature=0.8, seed=11)
        eng.run()
        r2 = eng.submit(sys_prompt + [30], 5, temperature=0.8, seed=11)
        eng.run()
        assert r1.state == r2.state == "finished"
        assert list(r2.tokens_out) == list(r1.tokens_out), \
            "a prefix hit changed the sampled stream"
        assert sm.PREFIX_HITS.value == h0 + 1
        assert sm.PREFILL_COUNT.value == p0 + 1, \
            "the warm request still dispatched a full prefill"
        assert eng.page_accounting_ok()
        eng.drain(10.0)
        assert eng.pool.num_used == 0, "prefix pages leaked through drain"

    def test_failed_request_never_donates(self, tiny_model):
        from paddle_tpu.reliability import FaultPlan, faults

        eng = _prefix_engine(tiny_model)
        pk0 = sm.PREFIX_POISONED_SKIPPED.value
        plan = FaultPlan([faults.FaultSpec("serving.decode", "fatal",
                                           at=1, times=1)])
        with plan:
            bad = eng.submit(list(range(1, 18)), 5)
            eng.run(max_steps=50)
        assert bad.state == "failed"
        assert sm.PREFIX_POISONED_SKIPPED.value > pk0
        assert len(eng.prefix_cache) == 0, \
            "a FAILED request's pages entered the prefix cache"
        assert eng.page_accounting_ok() and eng.pool.num_used == 0
        # the poisoned prefix is structurally unservable: a fresh request
        # with the same prompt misses and re-prefills cleanly
        h0 = sm.PREFIX_HITS.value
        good = eng.submit(list(range(1, 18)), 3, seed=5)
        eng.run()
        assert good.state == "finished" and sm.PREFIX_HITS.value == h0
        eng.drain(10.0)

    def test_accounting_includes_cache_owned_pages(self, tiny_model):
        eng = _prefix_engine(tiny_model)
        r = eng.submit(list(range(1, 18)), 3, seed=9)
        eng.run()
        assert r.state == "finished"
        assert eng.prefix_cache.pages_held == 2
        assert eng.pool.num_used == 2, "donated pages were double-freed"
        assert eng.page_accounting_ok()
        eng.drain(10.0)
        assert eng.pool.num_used == 0


# -- KV-page serialization (ISSUE 18: every layout round-trips or refuses) ----
class TestPagePayloadLayouts:
    @staticmethod
    def _fp_cache():
        import jax.numpy as jnp

        from paddle_tpu.serving.kv_cache import PagedKVCache

        return PagedKVCache(n_layer=2, n_head=2, d_head=4, slots=2,
                            max_ctx=32, page_size=8, num_pages=6,
                            dtype=jnp.float32)

    @staticmethod
    def _fill(cache, state, seed):
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.RandomState(seed)
        shp = state["k"].shape
        return {**state,
                "k": jnp.asarray(rng.randn(*shp).astype(state["k"].dtype)
                                 if state["k"].dtype != np.int8 else
                                 rng.randint(-127, 128, shp, np.int8)),
                "v": jnp.asarray(rng.randn(*shp).astype(state["v"].dtype)
                                 if state["v"].dtype != np.int8 else
                                 rng.randint(-127, 128, shp, np.int8))}

    def test_fp_paged_round_trip_bit_exact(self):
        """fp pages exported from one pool and imported into DIFFERENT
        page ids of another re-export the exact same bytes — raw C-order
        rows, no float formatting anywhere in the path."""
        src = self._fp_cache()
        s_state = self._fill(src, src.init_state(), seed=1)
        meta, blobs = src.export_pages(s_state, [1, 3])
        assert meta["n_pages"] == 2 and len(blobs) == 2
        dst = self._fp_cache()
        d_state = self._fill(dst, dst.init_state(), seed=2)  # noisy pool
        d_state = dst.import_pages(d_state, [4, 2], meta, blobs)
        meta2, blobs2 = dst.export_pages(d_state, [4, 2])
        assert blobs2 == blobs, "fp page bytes mutated in transit"
        assert {k: meta2[k] for k in meta} == meta

    def test_int8_paged_round_trip_carries_scales(self):
        """int8 pages travel with their per-page fp32 scale columns; the
        importer's own constructor scales never touch imported pages, so
        the re-export is bit-exact including the scales."""
        import jax.numpy as jnp
        import numpy as np

        from paddle_tpu.serving.kv_cache import Int8PagedKVCache

        def mk(ks, vs):
            return Int8PagedKVCache(n_layer=2, n_head=2, d_head=4, slots=2,
                                    max_ctx=32, page_size=8, num_pages=6,
                                    k_scale=ks, v_scale=vs)

        src = mk(0.125, 0.25)
        s_state = self._fill(src, src.init_state(), seed=3)
        # vary the per-page scale columns so the test catches a payload
        # that ships the constructor scalar instead of the page columns
        rng = np.random.RandomState(4)
        s_state = {**s_state,
                   "ks": jnp.asarray(rng.rand(2, 6).astype(np.float32) + .1),
                   "vs": jnp.asarray(rng.rand(2, 6).astype(np.float32) + .1)}
        meta, blobs = src.export_pages(s_state, [0, 5])
        assert len(blobs) == 4, "int8 payload must carry ks/vs columns"
        dst = mk(1.0, 1.0)  # different calibration on purpose
        d_state = dst.import_pages(dst.init_state(), [2, 4], meta, blobs)
        meta2, blobs2 = dst.export_pages(d_state, [2, 4])
        assert blobs2 == blobs, "int8 pages or scale columns mutated"
        got_ks = np.asarray(d_state["ks"][:, [2, 4]])
        want_ks = np.asarray(s_state["ks"][:, [0, 5]])
        assert np.array_equal(got_ks, want_ks), \
            "imported pages dequantize with the wrong scales"

    @pytest.mark.parametrize("kind", ["bf16", "int8"])
    def test_flat_pool_ships_the_head_split_wire_format(self, kind):
        """The pool is stored ``[n_layer, rows, H*D]``; the wire format is
        C-order bytes of ``[n_layer, n_pages*page_size, H, D]``. The two
        are the same bytes: an export equals ``np.ascontiguousarray`` of
        the head-split view's rows, and a payload built from that view
        alone imports into the flat pool and re-exports bit for bit."""
        import jax.numpy as jnp
        import numpy as np

        from paddle_tpu.serving.kv_cache import (Int8PagedKVCache,
                                                 PagedKVCache)

        geom = dict(n_layer=3, n_head=2, d_head=4, slots=2, max_ctx=32,
                    page_size=8, num_pages=6)
        cache = (PagedKVCache(dtype=jnp.bfloat16, **geom) if kind == "bf16"
                 else Int8PagedKVCache(k_scale=0.5, v_scale=0.25, **geom))
        state = self._fill(cache, cache.init_state(), seed=7)
        assert state["k"].shape == (3, 6 * 8, 2 * 4)
        pages = [4, 1]
        rows = np.concatenate([np.arange(p * 8, p * 8 + 8) for p in pages])
        meta, blobs = cache.export_pages(state, pages)
        for name, blob in zip("kv", blobs):
            old = np.asarray(state[name]).reshape(3, 6 * 8, 2, 4)
            assert blob == np.ascontiguousarray(old[:, rows]).tobytes(), name
        assert (meta["n_head"], meta["d_head"]) == (2, 4)
        assert meta["kv_dtype"] == ("bfloat16" if kind == "bf16" else "int8")
        dst = cache.import_pages(cache.init_state(), [0, 5], meta, blobs)
        assert cache.export_pages(dst, [0, 5])[1] == blobs
        got = np.asarray(dst["k"]).reshape(3, 6 * 8, 2, 4)
        want = np.asarray(state["k"]).reshape(3, 6 * 8, 2, 4)[:, rows]
        dst_rows = np.concatenate([np.arange(p * 8, p * 8 + 8)
                                   for p in [0, 5]])
        assert np.array_equal(got[:, dst_rows], want)

    def test_contiguous_layout_refuses_typed(self):
        """The dense layout has no addressable page unit: both directions
        refuse with ValueError — callers surface 'migration unsupported',
        never a crash or a silent wrong-shape blob."""
        from paddle_tpu.serving.kv_cache import ContiguousKVCache

        cache = ContiguousKVCache(n_layer=1, n_head=2, d_head=4, slots=2,
                                  max_ctx=16)
        state = cache.init_state()
        with pytest.raises(ValueError, match="no pages to export"):
            cache.export_pages(state, [0])
        with pytest.raises(ValueError, match="no pages to import"):
            cache.import_pages(state, [0], {"layout": "contiguous"}, [b""])

    def test_import_refuses_geometry_mismatch(self):
        """Every mismatch is a typed ValueError BEFORE any pool write:
        wrong page_size, wrong blob count, wrong page count, short blobs."""
        import jax.numpy as jnp

        from paddle_tpu.serving.kv_cache import PagedKVCache

        src = self._fp_cache()
        state = self._fill(src, src.init_state(), seed=5)
        meta, blobs = src.export_pages(state, [1, 3])
        other = PagedKVCache(n_layer=2, n_head=2, d_head=4, slots=2,
                             max_ctx=32, page_size=16, num_pages=3,
                             dtype=jnp.float32)
        with pytest.raises(ValueError, match="geometry mismatch"):
            other.import_pages(other.init_state(), [1], meta, blobs)
        dst = self._fp_cache()
        with pytest.raises(ValueError, match="blobs"):
            dst.import_pages(dst.init_state(), [1, 3], meta, blobs[:1])
        with pytest.raises(ValueError, match="pages"):
            dst.import_pages(dst.init_state(), [1], meta, blobs)
        with pytest.raises(ValueError, match="bytes"):
            dst.import_pages(dst.init_state(), [1, 3], meta,
                             [blobs[0][:-4], blobs[1]])

    def test_engine_export_ingest_serves_bit_identical(self, tiny_model):
        """Engine-level round trip: a prefix prefilled on engine A,
        shipped as (meta, blobs), and ingested by engine B serves the
        same request on B as a RESUME — zero prefill dispatches, the
        sampled stream bit-identical, page accounting intact on both."""
        prompt = list(range(1, 18))  # 16 cached tokens = 2 pages of 8
        eng_a = _prefix_engine(tiny_model)
        eng_b = _prefix_engine(tiny_model)
        r1 = eng_a.submit(prompt, 5, temperature=0.8, seed=11)
        eng_a.run()
        assert r1.state == "finished"
        exported = eng_a.export_prefix_pages(prompt[:16])
        assert exported is not None, "donated prefix not exportable"
        meta, blobs = exported
        assert eng_b.ingest_prefix_pages(prompt[:16], meta, blobs)
        assert eng_b.page_accounting_ok()
        p0 = sm.PREFILL_COUNT.value
        r2 = eng_b.submit(prompt, 5, temperature=0.8, seed=11)
        eng_b.run()
        assert r2.state == "finished"
        assert list(r2.tokens_out) == list(r1.tokens_out), \
            "shipped pages changed the sampled stream"
        assert sm.PREFILL_COUNT.value == p0, \
            "the ingested prefix did not spare the prefill dispatch"
        # re-export from B: the bytes survived the hop bit-exact
        meta_b, blobs_b = eng_b.export_prefix_pages(prompt[:16])
        assert blobs_b == blobs
        for eng in (eng_a, eng_b):
            assert eng.page_accounting_ok()
            eng.drain(10.0)
            assert eng.pool.num_used == 0, "pages leaked through drain"

    def test_ingest_refusals_leak_nothing(self, tiny_model):
        """Every ingest refusal frees its reservation first: bad token
        count, geometry mismatch, duplicate ingest — pool usage is
        unchanged and page accounting holds after each."""
        prompt = list(range(1, 18))
        eng_a = _prefix_engine(tiny_model)
        eng_b = _prefix_engine(tiny_model)
        r = eng_a.submit(prompt, 3, seed=2)
        eng_a.run()
        assert r.state == "finished"
        meta, blobs = eng_a.export_prefix_pages(prompt[:16])
        used0 = eng_b.pool.num_used
        # token count disagrees with n_pages * page_size
        assert not eng_b.ingest_prefix_pages(prompt[:12], meta, blobs)
        # geometry lie: n_pages beyond the payload
        bad = dict(meta, n_pages=3)
        assert not eng_b.ingest_prefix_pages(prompt[:16] + [77] * 8,
                                             bad, blobs)
        assert eng_b.pool.num_used == used0 and eng_b.page_accounting_ok()
        assert eng_b.ingest_prefix_pages(prompt[:16], meta, blobs)
        # duplicate ingest: no-op success, no second reservation
        used1 = eng_b.pool.num_used
        assert eng_b.ingest_prefix_pages(prompt[:16], meta, blobs)
        assert eng_b.pool.num_used == used1
        eng_a.drain(10.0)
        eng_b.drain(10.0)


# -- disaggregation plane over in-process sims (ISSUE 18) ---------------------
def _disagg_router(roles, n_decode_slots=2, **kw):
    kw.setdefault("affinity", "round_robin")
    return Router(FleetConfig(
        roles=roles, mode="inprocess", page_size=16,
        engine_factory=lambda i: SimEngine(
            SimConfig(slots=n_decode_slots, page_size=16)), **kw))


class TestDisaggRouter:
    def test_prefill_replicas_serve_no_user_requests(self):
        """1-prefill/2-decode fleet: long prompts prefill on the prefill
        replica and decode elsewhere; the streams match a uniform twin
        bit-for-bit and the pages actually migrated."""
        mc0 = fm.MIGRATIONS_COMPLETED.value
        router = _disagg_router("1:2")
        prompts = [[100 + i * 50 + t for t in range(33)] for i in range(4)]
        frs = [router.submit(p, 4, temperature=0.5, seed=40 + i)
               for i, p in enumerate(prompts)]
        assert router.wait_all(30.0)
        acc = router.accounting()
        assert len(acc) == 4 and set(acc.values()) == {"finished"}, acc
        assert all(f.last_replica != 0 for f in frs), \
            "a user request decoded on the prefill replica"
        assert fm.MIGRATIONS_COMPLETED.value > mc0, "nothing migrated"
        snap = router.snapshot()
        assert snap["roles"]["prefill"] == 1
        assert snap["migration"]["active"] == 0
        router.close()
        twin = _sim_router(n=1)
        frs_t = [twin.submit(p, 4, temperature=0.5, seed=40 + i)
                 for i, p in enumerate(prompts)]
        assert twin.wait_all(30.0)
        twin.close()
        assert [f.tokens for f in frs] == [f.tokens for f in frs_t], \
            "disaggregated decode diverged from the uniform twin"

    def test_remote_prefix_hit_ships_across_replicas(self):
        """Uniform fleet with the fleet-wide index armed: a prefix owned
        by replica A serves an identical request forced onto replica B by
        shipping the pages — remote hit counted, stream unchanged."""
        h0 = fm.REMOTE_HITS.value
        router = Router(FleetConfig(
            replicas=2, mode="inprocess", affinity="round_robin",
            page_size=16, fleet_prefix=True,
            engine_factory=lambda i: SimEngine(
                SimConfig(slots=2, page_size=16))))
        prompt = [7 * t % 97 for t in range(33)]
        f1 = router.submit(prompt, 4, temperature=0.5, seed=9)
        assert router.wait_all(30.0)
        owner = f1.last_replica
        router._replicas[owner].accepting = False
        f2 = router.submit(prompt, 4, temperature=0.5, seed=9)
        assert router.wait_all(30.0)
        assert f2.state == "finished" and f2.last_replica == 1 - owner
        assert f2.tokens == f1.tokens, \
            "the remote prefix hit changed the stream"
        assert fm.REMOTE_HITS.value > h0
        router.close()

    def test_failed_migration_falls_back_cold_exactly_once(self):
        """Kill the DESTINATION while pages are in flight toward it: the
        migration fails closed, the carried request blows its no-migrate
        fuse and re-prefills cold — one terminal outcome, same stream."""
        mf0 = fm.MIGRATIONS_FAILED.value
        router = Router(FleetConfig(
            replicas=2, mode="inprocess", affinity="round_robin",
            page_size=16, fleet_prefix=True,
            engine_factory=lambda i: SimEngine(
                SimConfig(slots=2, page_size=16))))
        prompt = [5 * t % 89 for t in range(33)]
        f1 = router.submit(prompt, 4, temperature=0.5, seed=13)
        assert router.wait_all(30.0)
        owner = f1.last_replica
        router._replicas[owner].accepting = False
        f2 = router.submit(prompt, 4, temperature=0.5, seed=13)
        deadline = 200
        while not router._migrations and deadline:
            router.pump()
            deadline -= 1
        assert router._migrations, "no migration started"
        router._replicas[1 - owner].kill()
        assert router.wait_all(30.0)
        acc = router.accounting()
        assert acc[f2.id] == "finished", acc
        assert list(acc.values()).count("finished") == len(acc), acc
        assert fm.MIGRATIONS_FAILED.value > mf0, \
            "the dead owner's migration did not fail closed"
        assert f2.no_migrate, "the failed request can retry migration"
        assert f2.tokens == f1.tokens, "the cold fallback changed tokens"
        router.close()

    def test_manual_rebalance_moves_ownership(self):
        """rebalance() is a MOVE: the pages ship to the destination and
        are evicted at the source, and the fleet index re-points the
        prefix at its new owner."""
        mc0 = fm.MIGRATIONS_COMPLETED.value
        router = Router(FleetConfig(
            replicas=2, mode="inprocess", affinity="round_robin",
            page_size=16, fleet_prefix=True,
            engine_factory=lambda i: SimEngine(
                SimConfig(slots=2, page_size=16))))
        prompt = [3 * t % 83 for t in range(33)]
        f1 = router.submit(prompt, 3, temperature=0.5, seed=21)
        assert router.wait_all(30.0)
        owner = f1.last_replica
        key, ent = next(iter(router._prefix_index.items()))
        assert ent["owners"] == {owner}
        xid = router.rebalance(owner, 1 - owner, ent["tokens"])
        assert xid is not None
        for _ in range(200):
            if not router._migrations:
                break
            router.pump()
        assert not router._migrations, "rebalance never resolved"
        assert fm.MIGRATIONS_COMPLETED.value > mc0
        assert router._prefix_index[key]["owners"] == {1 - owner}, \
            "ownership did not move with the pages"
        router.close()

    def test_scale_down_migrates_and_retires(self):
        """scale_down drains a live replica: in-flight work re-lands
        elsewhere, every request reaches one terminal outcome, and the
        victim takes no further traffic."""
        router = _sim_router(n=3)
        frs = [router.submit([9, 9, 9, i], 6, temperature=0.4, seed=60 + i)
               for i in range(6)]
        for _ in range(2):
            router.pump()
        out = router.scale_down(1)
        assert out["replica"] == 1
        assert router.wait_all(30.0)
        acc = router.accounting()
        assert len(acc) == 6 and set(acc.values()) == {"finished"}, acc
        snap = router.snapshot()
        victim = next(r for r in snap["replicas"]
                      if r["name"] == "replica-1")
        assert victim["retired"] and not victim["alive"]
        router.close()
        # the retired slot stays down: nothing respawns it afterwards
        assert not router._replicas[1].alive

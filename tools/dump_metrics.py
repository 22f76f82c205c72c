"""Inspect paddle_tpu.monitor artifacts from the command line.

The tools/timeline.py of this stack, plus a metrics pretty-printer:

    python -m tools.dump_metrics snapshot.json
        Pretty-print a metrics snapshot (the ``monitor.to_json()`` /
        bench-JSON ``metrics`` format) as an aligned table.

    python -m tools.dump_metrics --to-chrome spans.json trace.json
        Convert a raw host-span file (``monitor.tracer.save_spans``) to a
        chrome://tracing / Perfetto-loadable Chrome trace. Accepts an
        existing Chrome trace too (idempotent), so the conversion
        round-trips.

    python -m tools.dump_metrics --watch <interval_s>
        Tail the LIVE in-process registry as interval deltas: every tick
        print counters that moved (as +delta and rate/s), gauges that
        changed, and histogram activity. Ctrl-C exits. (Most useful from
        code: ``from tools.dump_metrics import watch; watch(1.0)`` in a
        thread next to a running engine — a separate process sees its own
        registry, so there it tails a telemetry ring dir instead:
        ``--watch <interval_s> <PADDLE_TPU_TELEMETRY_DIR>``.) Multiple
        dirs — ``--watch 1 dir1 dir2`` or ``dir1,dir2`` — tail N rings
        into one merged view (lines labeled by source dir); a fleet
        ``telemetry_base`` holding ``replica_*/`` subdirs expands to all
        of its replicas' rings.

    python -m tools.dump_metrics --selftest
        Exercise registry + tracer + the Chrome-trace round-trip +
        telemetry ring write/rotate/read-back + SLO counters in-process
        and exit 0/1. Needs no TPU (run under ``JAX_PLATFORMS=cpu``); the
        CI smoke check.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from paddle_tpu.monitor import metrics, tracer  # noqa: E402


def format_snapshot(snap: dict) -> str:
    """Aligned table for a ``monitor.snapshot()``-format dict."""
    lines = ["%-40s %-9s %s" % ("metric", "type", "value"),
             "-" * 72]
    for name in sorted(snap):
        s = snap[name]
        t = s.get("type", "?")
        if t == "histogram":
            detail = ("count=%d mean=%.3f p50=%.3f p95=%.3f p99=%.3f "
                      "min=%.3f max=%.3f"
                      % (s.get("count", 0), s.get("mean", 0.0),
                         s.get("p50", 0.0), s.get("p95", 0.0),
                         s.get("p99", 0.0),
                         s.get("min", 0.0), s.get("max", 0.0)))
        else:
            v = s.get("value", 0)
            detail = ("%d" % v) if float(v).is_integer() else ("%.6g" % v)
        lines.append("%-40s %-9s %s" % (name, t, detail))
    return "\n".join(lines)


def dump_snapshot(path: str) -> int:
    with open(path) as f:
        doc = json.load(f)
    # accept a whole bench JSON ({"detail": ..., "metrics": {...}}) too
    if "metrics" in doc and all(
            not isinstance(v, dict) or "type" not in v for v in doc.values()):
        doc = doc["metrics"]
    print(format_snapshot(doc))
    return 0


def to_chrome(src: str, dst: str) -> int:
    spans = tracer.load_spans(src)
    tracer.save_chrome_trace(dst, spans)
    print("wrote %d span(s) -> %s" % (len(spans), dst))
    return 0


def _delta_lines(sample) -> list:
    """One human line per instrument that moved this interval (the
    exporter's own ``telemetry/*`` bookkeeping is excluded — every tick
    moves it, which would bury real deltas and make idle look busy)."""
    lines = []
    for name, d in sorted(sample.deltas.get("counters", {}).items()):
        if name.startswith("telemetry/"):
            continue
        lines.append("%-44s +%-10g %8.2f/s"
                     % (name, d, d / sample.dt_s if sample.dt_s else 0.0))
    for name, v in sorted(sample.deltas.get("gauges", {}).items()):
        if name.startswith("telemetry/"):
            continue
        lines.append("%-44s -> %g" % (name, v))
    for name, h in sorted(sample.deltas.get("histograms", {}).items()):
        p99 = sample.histogram_interval_percentile(name, 99) or 0.0
        lines.append("%-44s n=%-6d mean=%.3f p99=%.3f"
                     % (name, h["count"],
                        (h["sum"] / h["count"]) if h["count"] else 0.0, p99))
    return lines


def _expand_watch_dirs(telemetry_dir) -> list:
    """Normalize the --watch dir argument: a single dir, a comma-joined
    list, or a Python list — plus one level of fleet expansion: a dir
    containing ``replica_*/`` subdirs (the router's ``telemetry_base``)
    tails every replica's ring, merged — in NUMERIC replica order
    (replica_10 after replica_9, not between replica_1 and replica_2)."""
    from paddle_tpu.fleet.router import _replica_index

    if telemetry_dir is None:
        return []
    dirs = (list(telemetry_dir) if isinstance(telemetry_dir, (list, tuple))
            else [d for d in str(telemetry_dir).split(",") if d])
    out = []
    for d in dirs:
        subs = sorted(
            (name for name in
             (os.listdir(d) if os.path.isdir(d) else [])
             if name.startswith("replica_")
             and os.path.isdir(os.path.join(d, name))),
            key=_replica_index)
        out.extend([os.path.join(d, name) for name in subs] or [d])
    return out


def watch(interval_s: float, telemetry_dir=None,
          max_ticks: int = None) -> int:
    """Print interval deltas every ``interval_s``. With ``telemetry_dir``
    set, tail other processes' JSONL telemetry rings (exporter output
    dirs) instead of the local registry; otherwise run a private
    in-process exporter with no disk ring. ``telemetry_dir`` may be one
    dir, a comma-joined list ("dir1,dir2"), a Python list, or a fleet
    ``telemetry_base`` containing ``replica_*/`` subdirs — N rings tail
    into one merged view, each line group labeled by its source dir.
    ``max_ticks`` bounds the loop (tests); None = until KeyboardInterrupt.
    The ring tail re-parses the whole (bounded: rotate × keep samples)
    ring each interval and filters by per-(dir, writer) seq — simple over
    fast, this is an ops tool."""
    import time

    from paddle_tpu.monitor import telemetry
    from paddle_tpu.monitor.telemetry import TelemetrySample

    ticks = 0
    dirs = _expand_watch_dirs(telemetry_dir)
    try:
        if dirs:
            # track the monotone per-writer seq, NOT the list index: a
            # ring rotation prunes old files, shrinking the list without
            # un-publishing samples (index tracking would go blind for a
            # whole rotation's worth of samples after each prune). Keyed
            # (dir, pid): two replicas' rings never shadow each other.
            last_seq = {}
            label = len(dirs) > 1
            while max_ticks is None or ticks < max_ticks:
                for d in dirs:
                    try:
                        series = telemetry.read_series(d)
                    except Exception:
                        continue
                    for doc in series:
                        key = (d, doc.get("pid", 0))
                        if doc.get("seq", 0) <= last_seq.get(key, -1):
                            continue
                        last_seq[key] = doc.get("seq", 0)
                        sample = TelemetrySample(
                            doc.get("seq", 0), doc.get("t", 0.0),
                            doc.get("dt_s", 0.0), doc.get("metrics", {}),
                            doc.get("deltas", {}))
                        body = _delta_lines(sample)
                        src = (" [%s]" % os.path.basename(d.rstrip("/"))
                               if label else "")
                        print("-- seq %d (dt %.2fs)%s"
                              % (sample.seq, sample.dt_s, src))
                        for line in body:
                            print(line)
                ticks += 1
                time.sleep(interval_s)
            return 0
        exp = telemetry.TelemetryExporter(
            "", interval_s=interval_s, prometheus_file=False)
        exp.disabled = True  # live tail only — never writes a ring
        while max_ticks is None or ticks < max_ticks:
            time.sleep(interval_s)
            sample = exp.tick()
            body = _delta_lines(sample)
            print("-- %s (dt %.2fs)"
                  % (time.strftime("%H:%M:%S"), sample.dt_s))
            for line in (body or ["(no activity)"]):
                print(line)
            ticks += 1
    except KeyboardInterrupt:
        pass
    return 0


def validate_chrome_trace(doc: dict) -> None:
    """Raise AssertionError unless ``doc`` is a loadable Chrome trace."""
    assert isinstance(doc, dict) and "traceEvents" in doc, "missing traceEvents"
    assert isinstance(doc["traceEvents"], list), "traceEvents must be a list"
    for ev in doc["traceEvents"]:
        assert "ph" in ev and "pid" in ev, "event missing ph/pid: %r" % (ev,)
        if ev["ph"] == "X":
            assert {"name", "ts", "dur", "tid"} <= set(ev), \
                "complete event missing fields: %r" % (ev,)


def selftest() -> int:
    # 1. registry: counter/gauge/histogram + snapshot/reset
    metrics.enable()
    c = metrics.counter("selftest/count")
    c.inc(3)
    metrics.gauge("selftest/gauge").set(1.5)
    h = metrics.histogram("selftest/hist")
    for v in (0.2, 2.0, 40.0):
        h.observe(v)
    snap = metrics.snapshot()
    assert snap["selftest/count"]["value"] == 3
    assert snap["selftest/hist"]["count"] == 3
    assert "p95" in snap["selftest/hist"]
    assert "p99" in snap["selftest/hist"]
    assert "p99=" in format_snapshot(snap)  # table carries the P99 column
    # disabled = inert
    metrics.disable()
    c.inc(100)
    metrics.enable()
    assert c.value == 3
    # 2. tracer: nested spans -> raw file -> CLI conversion -> valid Chrome
    tracer.start_tracing()
    with tracer.span("selftest/outer"):
        with tracer.span("selftest/inner", args={"k": 1}):
            pass
    spans = tracer.stop_tracing()
    mine = [s for s in spans if s["name"].startswith("selftest/")]
    assert {s["name"] for s in mine} == {"selftest/outer", "selftest/inner"}
    inner = next(s for s in mine if s["name"] == "selftest/inner")
    outer = next(s for s in mine if s["name"] == "selftest/outer")
    assert inner["depth"] == outer["depth"] + 1, "span nesting lost"
    with tempfile.TemporaryDirectory() as td:
        raw = os.path.join(td, "spans.json")
        chrome = os.path.join(td, "trace.json")
        tracer.save_spans(raw, mine)
        to_chrome(raw, chrome)
        with open(chrome) as f:
            doc = json.load(f)
        validate_chrome_trace(doc)
        # round-trip: chrome trace back to spans, names/durations preserved
        back = tracer.load_spans(chrome)
        assert {s["name"] for s in back} == {s["name"] for s in mine}
        assert sorted(s["dur_us"] for s in back) == sorted(
            s["dur_us"] for s in mine)
    # 3. async pipeline: the compile-cache counter pair must exist and a
    #    tiny fused run_steps loop must execute + instrument (CPU, ~1s)
    import numpy as np

    import paddle_tpu as fluid

    snap = metrics.snapshot()
    assert "compile_cache/hit" in snap, "compile-cache counters not registered"
    assert "compile_cache/miss" in snap
    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup):
                x = fluid.layers.data("x", shape=[4])
                y = fluid.layers.data("y", shape=[1], dtype="int64")
                logits = fluid.layers.fc(x, size=2)
                loss = fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(logits, y))
                fluid.optimizer.SGD(0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(0)
            feeds = ({"x": rng.randn(2, 4).astype("float32"),
                      "y": rng.randint(0, 2, (2, 1)).astype("int64")}
                     for _ in range(4))
            rows = exe.run_steps(main_prog, feeds, steps=4,
                                 fetch_list=[loss], fetch_every=2)
            assert len(rows) == 4 and np.isfinite(rows[-1][0]).all()
            snap = metrics.snapshot()
            assert snap["executor/run_steps_dispatches"]["value"] == 2
            assert snap["executor/run_steps_steps"]["value"] == 4
            # 4a. device-profile gauges: prepare() AOT-compiles and must
            #     mirror the XLA cost/memory analyses into the gauges
            exe.prepare(main_prog,
                        feed={"x": ((2, 4), "float32"),
                              "y": ((2, 1), "int64")},
                        fetch_list=[loss])
            snap = metrics.snapshot()
            assert snap["device_profile/flops"]["value"] > 0, \
                "prepare() did not publish cost_analysis"
            assert snap["device_profile/peak_hbm_bytes"]["value"] > 0
    # 4b. numerics watchdog packed-mask path: PADDLE_TPU_CHECK_NUMERICS=2
    #     compiles the guarded step variant; a planted NaN must be
    #     attributed to the ORIGINATING op by <slot>:<type>, not a fetch
    from paddle_tpu.core.enforce import EnforceNotMet

    prev = os.environ.get("PADDLE_TPU_CHECK_NUMERICS")
    os.environ["PADDLE_TPU_CHECK_NUMERICS"] = "2"
    try:
        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                m2, s2 = fluid.Program(), fluid.Program()
                with fluid.program_guard(m2, s2):
                    x = fluid.layers.data("x", shape=[4])
                    bad = fluid.layers.log(x)  # log(0) -> -inf at THIS op
                    out = fluid.layers.mean(bad)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(s2)
                try:
                    exe.run(m2, feed={"x": np.zeros((2, 4), "float32")},
                            fetch_list=[out])
                    raise AssertionError("watchdog missed the planted NaN")
                except EnforceNotMet as e:
                    assert ":log" in str(e), str(e)
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_CHECK_NUMERICS", None)
        else:
            os.environ["PADDLE_TPU_CHECK_NUMERICS"] = prev
    # 5. serving/* counters: the multiplexer's host-side bookkeeping
    #    (scheduler + page pool) must feed the registry; the full compiled
    #    prefill->decode->retire path is tests/test_serving.py's
    from paddle_tpu.serving import (PagePool, PagePoolExhausted, Request,
                                    Scheduler)

    metrics.reset()
    sched = Scheduler(n_slots=2, max_queue=4)
    pool = PagePool(num_pages=4, page_size=8)
    r1 = sched.submit(Request([1, 2, 3], max_new_tokens=4))
    r2 = sched.submit(Request([4, 5], max_new_tokens=2))
    r1.pages = pool.alloc(pool.pages_needed(3 + 4))
    sched.admit(0)
    try:
        pool.alloc(99)
        raise AssertionError("page pool did not backpressure")
    except PagePoolExhausted:
        sched.requeue_head_blocked()
    snap = metrics.snapshot()
    assert snap["serving/requests_submitted"]["value"] == 2
    assert snap["serving/requests_admitted"]["value"] == 1
    assert snap["serving/queue_depth"]["value"] == 1
    assert snap["serving/slot_occupancy"]["value"] == 1
    assert snap["serving/page_pool_pages_in_use"]["value"] == 1
    assert snap["serving/admission_blocked_on_pages"]["value"] == 1
    pool.free(r1.pages)
    sched.retire(0)
    snap = metrics.snapshot()
    assert snap["serving/requests_retired"]["value"] == 1
    assert snap["serving/slot_occupancy"]["value"] == 0
    assert snap["serving/page_pool_utilization"]["value"] == 0
    assert r2.state == "queued"  # blocked head stays FIFO-first
    metrics.reset()

    # 6. reliability instruments + the fault framework's registry feed:
    #    an armed plan firing must tick reliability/faults_injected (the
    #    full recovery drills have their own gate, tools/chaos_drill
    #    --selftest)
    from paddle_tpu.reliability import (FaultPlan, TransientFault, faults,
                                        run_supervised)  # noqa: F401
    # (run_supervised imported for its side effect: loading the supervisor
    # registers the reliability/preemptions|checkpoints|... instruments)

    with FaultPlan.parse("executor.compile@1=transient"):
        try:
            faults.fire("executor.compile")
            raise AssertionError("armed fault did not fire")
        except TransientFault:
            pass
    snap = metrics.snapshot()
    assert snap["reliability/faults_injected"]["value"] == 1
    for name in ("reliability/preemptions", "reliability/retries",
                 "reliability/checkpoints_written", "reliability/resumes",
                 "reliability/feed_errors",
                 "serving/faults", "serving/retries", "serving/timeouts",
                 "serving/requests_failed", "serving/drains",
                 "serving/drained_requests", "serving/drain_rejected"):
        assert name in snap, "missing instrument %s" % name
    metrics.reset()

    # 6b. data/* + sentinel/* registries: the ingestion pipeline's counters
    #     must feed the registry from a real (tiny) reader pass — one good
    #     record, one corrupt, one quarantine-skip on the second epoch —
    #     and loading the sentinel registers its trip/rollback instruments
    #     (the full self-heal/exactly-once recovery drills have their own
    #     gate, tools/chaos_drill --selftest)
    import numpy as np

    from paddle_tpu import data as pdata
    from paddle_tpu.reliability import sentinel as _sentinel  # noqa: F401

    metrics.reset()
    with tempfile.TemporaryDirectory() as td:
        shard = os.path.join(td, "rows.txt")
        with open(shard, "w") as f:
            f.write("1.0 2.0\nbad record\n3.0 4.0\n")
        qfile = os.path.join(td, "quarantine.jsonl")

        def parse(line):
            vals = [float(t) for t in line.split()]
            return {"x": np.asarray(vals, np.float32)}

        reader = pdata.CheckpointableReader(
            [shard], parse, batch_size=2,
            schema=[pdata.FieldSpec("x", (2,), np.float32)],
            epochs=2, quarantine_path=qfile,
            max_corrupt_rate=0.9, corrupt_check_min=1)
        batches = list(reader)
        assert len(batches) == 2 and batches[0]["x"].shape == (2, 2)
        qrows = [json.loads(ln) for ln in open(qfile)]
        assert len(qrows) == 1 and qrows[0]["id"] == "rows.txt#1", qrows
        assert "parse" in qrows[0]["reason"]
        snap = metrics.snapshot()
        assert snap["data/records_read"]["value"] == 4
        assert snap["data/records_corrupt"]["value"] == 1
        assert snap["data/records_quarantined"]["value"] == 1
        assert snap["data/records_skipped"]["value"] == 1  # epoch-2 skip
        assert snap["data/batches"]["value"] == 2
        assert snap["data/epochs_completed"]["value"] == 2
        assert snap["data/bytes_read"]["value"] > 0
        for name in ("data/prefetch_depth", "data/prefetch_wait_ms",
                     "sentinel/trips", "sentinel/rollbacks",
                     "sentinel/records_quarantined", "sentinel/lr_backoffs",
                     "sentinel/fatals", "sentinel/trips_nan",
                     "sentinel/trips_spike", "sentinel/trips_plateau",
                     "sentinel/trips_grad_norm", "sentinel/trips_drift"):
            assert name in snap, "missing instrument %s" % name
    metrics.reset()

    # 6c. numerics/* registry: the streaming-stats layer must feed per-op
    #     gauges, the chunks counter and the LOG-BUCKETED absmax histogram
    #     from a real armed step, render through the table/Prometheus/
    #     --watch formatters, and leave zero registry residue when off
    from paddle_tpu.monitor import numerics as _numerics
    from paddle_tpu.monitor import telemetry as _tele

    metrics.reset()
    _numerics.reset()
    prev_num = os.environ.get("PADDLE_TPU_NUMERICS")
    os.environ["PADDLE_TPU_NUMERICS"] = "1"
    try:
        exp = _tele.TelemetryExporter("", interval_s=999.0,
                                      prometheus_file=False)
        exp.disabled = True
        exp.tick()  # baseline so the next tick's deltas cover the run
        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                m3, s3 = fluid.Program(), fluid.Program()
                with fluid.program_guard(m3, s3):
                    x = fluid.layers.data("x", shape=[4])
                    h3 = fluid.layers.fc(x, size=4, act="relu")
                    out3 = fluid.layers.mean(h3)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(s3)
                exe.run(m3, feed={"x": np.ones((2, 4), "float32")},
                        fetch_list=[out3])
        snap = metrics.snapshot()
        assert snap["numerics/chunks"]["value"] >= 1, "no stats chunk landed"
        assert any(k.startswith("numerics/") and k.endswith("/absmax")
                   for k in snap), "per-op numerics gauges missing"
        hsnap = snap["numerics/absmax"]
        assert hsnap["type"] == "histogram" and hsnap["count"] >= 1
        assert "le_1e-08" in hsnap["buckets"], "log buckets missing"
        assert _numerics.snapshot(), "host per-op registry empty"
        # the log-bucketed histogram must survive every renderer
        assert "numerics/absmax" in format_snapshot(snap)
        assert 'numerics_absmax_bucket{le="1e-08"}' in metrics.to_prometheus()
        sample = exp.tick()
        assert any(line.startswith("numerics/absmax")
                   for line in _delta_lines(sample)), \
            "--watch formatter dropped the log-bucketed histogram"
        exp.stop()
    finally:
        if prev_num is None:
            os.environ.pop("PADDLE_TPU_NUMERICS", None)
        else:
            os.environ["PADDLE_TPU_NUMERICS"] = prev_num
    _numerics.reset()
    metrics.reset()

    # 7. continuous telemetry: JSONL ring write/rotate/read-back, interval
    #    deltas, the watch formatter, Prometheus rendering, and the slo/*
    #    counters breaching + clearing on synthetic ticks
    from paddle_tpu.monitor import slo, telemetry

    metrics.reset()
    with tempfile.TemporaryDirectory() as td:
        exp = telemetry.TelemetryExporter(td, interval_s=999.0,
                                          rotate_samples=2, keep_files=2)
        h = metrics.histogram("selftest/lat_ms")
        mon = slo.SLOMonitor([slo.SLO("selftest/lat_ms", p=99, max_ms=10.0)])
        exp.add_listener(mon.on_sample)
        for i in range(5):
            h.observe(100.0 if i < 2 else 1.0)  # breach 2 ticks, then clear
            sample = exp.tick()
            assert sample.histogram_delta("selftest/lat_ms")["count"] == 1
            assert _delta_lines(sample)  # the --watch formatter must render
        exp.stop()  # final flush = one more (empty-delta) sample
        series = telemetry.read_series(td, pid=os.getpid())
        assert len(series) >= 2, "ring rotation lost everything: %d" % len(series)
        assert all(s["schema"] == telemetry.SAMPLE_SCHEMA for s in series)
        seqs = [s["seq"] for s in series]
        assert seqs == sorted(seqs) and seqs[-1] == 6, seqs
        files = [f for f in os.listdir(td) if f.endswith(".jsonl")]
        assert len(files) <= 2, "rotation did not prune: %s" % files
        assert os.path.exists(os.path.join(td, "metrics.prom"))
        snap = metrics.snapshot()
        assert snap["slo/breaches"]["value"] == 2, snap["slo/breaches"]
        assert snap["telemetry/samples"]["value"] == 6
        assert snap["telemetry/rotations"]["value"] >= 1
        assert "slo/selftest/lat_ms:p99/breaches" in snap
    # prometheus exposition must carry the histogram triplet, sanitized
    prom = metrics.to_prometheus()
    assert "selftest_lat_ms_bucket{le=\"+Inf\"}" in prom, prom[-400:]
    assert "selftest_lat_ms_count 5" in prom
    assert "selftest_lat_ms_sum" in prom
    metrics.reset()

    # 8. autotune/* counters + the tuned-config lookup ladder (the sweep
    #    mechanism has its own gate, tools/autotune --selftest). Point the
    #    runtime table at a guaranteed-absent file so a developer's own
    #    tuned table can't change what this CI assertion sees.
    from paddle_tpu import tune

    prev_tbl = os.environ.get("PADDLE_TPU_TUNE_TABLE")
    with tempfile.TemporaryDirectory() as td:
        os.environ["PADDLE_TPU_TUNE_TABLE"] = os.path.join(td, "none.json")
        try:
            cfg, src = tune.lookup("flash_attention",
                                   tune.bucket_seq(8192, 8192),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block_q"] == 512, (cfg, src)
            cfg, src = tune.lookup("sparse_adam", tune.bucket_rows(1024, 64),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block"] == 128, (cfg, src)
            cfg, src = tune.lookup("flash_attention",
                                   tune.bucket_seq(128, 128),
                                   device="made-up-chip")
            assert cfg is None and src == "default"
        finally:
            if prev_tbl is None:
                os.environ.pop("PADDLE_TPU_TUNE_TABLE", None)
            else:
                os.environ["PADDLE_TPU_TUNE_TABLE"] = prev_tbl
    snap = metrics.snapshot()
    assert snap["autotune/lookups"]["value"] >= 3
    assert snap["autotune/lookup_shipped"]["value"] >= 2
    assert snap["autotune/lookup_default"]["value"] >= 1
    for name in ("autotune/sweeps", "autotune/candidates_timed",
                 "autotune/candidates_pruned", "autotune/candidates_failed",
                 "autotune/table_writes", "autotune/table_errors",
                 "autotune/measure_ms"):
        assert name in snap, "missing instrument %s" % name
    metrics.reset()

    # 9. fleet/* registry + multi-dir watch aggregation: importing the
    #    fleet metrics module must register the full router + prefix-cache
    #    instrument set, and --watch must merge N replica ring dirs with
    #    per-(dir, pid) cursors (the fleet's N-replica tail view)
    import contextlib
    import io

    import paddle_tpu.fleet.metrics  # noqa: F401  (registers fleet/*)

    snap = metrics.snapshot()
    for name in ("fleet/submitted", "fleet/routed", "fleet/requeued",
                 "fleet/completed", "fleet/rejected",
                 "fleet/duplicate_results", "fleet/queue_depth",
                 "fleet/replicas_alive", "fleet/replica_restarts",
                 "fleet/rolling_restarts", "fleet/no_healthy_replica",
                 "fleet/rerouted",
                 "fleet/prefix_cache/hits", "fleet/prefix_cache/misses",
                 "fleet/prefix_cache/inserts",
                 "fleet/prefix_cache/evictions",
                 "fleet/prefix_cache/entries",
                 "fleet/prefix_cache/pages_held",
                 "fleet/prefix_cache/tokens_reused",
                 "fleet/prefix_cache/poisoned_skipped",
                 "fleet/migrations_started", "fleet/migrations_completed",
                 "fleet/migrations_failed", "fleet/migrated_pages",
                 "fleet/migration_ms",
                 "fleet/prefix_cache/remote_hits",
                 "fleet/prefix_cache/remote_misses",
                 "fleet/prefix_cache/remote_ships"):
        assert name in snap, "missing fleet instrument %s" % name
    with tempfile.TemporaryDirectory() as td:
        base = os.path.join(td, "fleet")
        for i in range(2):
            d = os.path.join(base, "replica_%d" % i)
            os.makedirs(d)
            exp = telemetry.TelemetryExporter(d, interval_s=999.0)
            metrics.counter("selftest/fleet_tick").inc(i + 1)
            exp.tick()
            exp.stop()
        assert _expand_watch_dirs(base) == [
            os.path.join(base, "replica_0"), os.path.join(base, "replica_1")]
        # numeric, not lexicographic: replica_10 tails AFTER replica_2
        for i in (2, 10):
            os.makedirs(os.path.join(base, "replica_%d" % i))
        assert _expand_watch_dirs(base) == [
            os.path.join(base, "replica_%d" % i) for i in (0, 1, 2, 10)]
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            watch(0.0, base, max_ticks=1)
        out = buf.getvalue()
        assert "[replica_0]" in out and "[replica_1]" in out, out
    metrics.reset()
    print("dump_metrics selftest: OK")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0 if argv else 2
    if argv[0] == "--selftest":
        return selftest()
    if argv[0] == "--to-chrome":
        if len(argv) != 3:
            print("usage: dump_metrics --to-chrome spans.json trace.json",
                  file=sys.stderr)
            return 2
        return to_chrome(argv[1], argv[2])
    if argv[0] == "--watch":
        if len(argv) < 2:
            print("usage: dump_metrics --watch <interval_s> "
                  "[telemetry_dir ...]", file=sys.stderr)
            return 2
        return watch(float(argv[1]), argv[2:] if len(argv) > 2 else None)
    if len(argv) != 1:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    return dump_snapshot(argv[0])


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # e.g. piped into head
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)

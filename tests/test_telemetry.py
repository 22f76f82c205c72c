"""Production telemetry: Prometheus export, the continuous JSONL exporter,
SLO monitoring, per-request serving traces, and collective-traffic budgets.

The PR-8 surface: ``monitor.to_prometheus()`` round-trips under a
promtool-style parser; ``monitor.telemetry.TelemetryExporter`` writes a
bounded crash-safe JSONL ring wired into the serving-engine and supervisor
lifecycles; ``monitor.slo`` evaluates declarative specs per tick (an
injected decode-latency fault must trip the p99 SLO, hit the flight
recorder, and flip ``engine.health()`` to degraded); the serving request
tracer reconstructs the continuous-batching schedule; and the checked-in
collective budgets reject traffic regressions.
"""

import json
import logging
import os
import re
import threading
import time

import numpy as np
import pytest

from paddle_tpu.monitor import budgets, metrics, slo, telemetry, tracer
from paddle_tpu.monitor.telemetry import TelemetryExporter, TelemetrySample


@pytest.fixture(autouse=True)
def _clean_metrics():
    metrics.enable()
    metrics.reset()
    yield
    metrics.reset()


def _tiny_engine(slots=3, **cfg_kw):
    from paddle_tpu import serving
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                   n_head=2, max_seq=64)
    model = decoder_lm.DecoderLM(cfg, seed=0)
    return serving.ServingEngine(model, serving.ServingConfig(
        slots=slots, page_size=8, max_seq=64, **cfg_kw))


# -- Prometheus text exposition ----------------------------------------------

_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_][a-zA-Z0-9_]*)(\{le="([^"]+)"\})? ([0-9eE.+-]+|\+Inf|NaN)$')


def _parse_prometheus(text):
    """Minimal promtool-style validation: TYPE lines, legal names, legal
    sample lines, cumulative monotone histogram buckets ending in +Inf.
    Returns {name: value} for scalars and {name: {...}} for histograms."""
    types, scalars, hists = {}, {}, {}
    for line in text.splitlines():
        if not line or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            types[name] = kind
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, "unparseable exposition line: %r" % line
        name, _, le, val = m.groups()
        val = float(val) if val != "+Inf" else float("inf")
        if le is not None:
            assert name.endswith("_bucket"), line
            base = name[:-len("_bucket")]
            assert types.get(base) == "histogram", "untyped bucket %r" % line
            hists.setdefault(base, {"buckets": []})["buckets"].append(
                (float("inf") if le == "+Inf" else float(le), val))
        elif name.endswith("_sum") and types.get(name[:-4]) == "histogram":
            hists.setdefault(name[:-4], {"buckets": []})["sum"] = val
        elif name.endswith("_count") and types.get(name[:-6]) == "histogram":
            hists.setdefault(name[:-6], {"buckets": []})["count"] = val
        else:
            assert name in types, "sample before TYPE: %r" % line
            scalars[name] = val
    for name, h in hists.items():
        bounds = [b for b, _ in h["buckets"]]
        counts = [c for _, c in h["buckets"]]
        assert bounds == sorted(bounds) and bounds[-1] == float("inf"), name
        assert counts == sorted(counts), "non-cumulative buckets: %s" % name
        assert counts[-1] == h["count"], name
    return scalars, hists


def test_to_prometheus_roundtrip():
    c = metrics.counter("promtest/reqs", help="help text with \\ and\nnewline")
    c.inc(7)
    metrics.gauge("promtest/depth:q").set(3.5)
    h = metrics.histogram("promtest/lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    scalars, hists = _parse_prometheus(metrics.to_prometheus())
    # names sanitized: '/' and ':' -> '_'
    assert scalars["promtest_reqs"] == 7
    assert scalars["promtest_depth_q"] == 3.5
    hh = hists["promtest_lat_ms"]
    assert hh["count"] == 4 and abs(hh["sum"] - 555.5) < 1e-9
    # cumulative: 1 obs <=1, 2 <=10, 3 <=100, 4 <=+Inf
    assert [c for _, c in hh["buckets"]] == [1, 2, 3, 4]


def test_prometheus_name_sanitization():
    assert metrics.prometheus_name("serving/ttft_ms") == "serving_ttft_ms"
    assert metrics.prometheus_name("a:b/c-d.e") == "a_b_c_d_e"
    assert metrics.prometheus_name("9lives") == "_9lives"


# -- telemetry exporter -------------------------------------------------------

def test_exporter_ring_write_rotate_readback(tmp_path):
    exp = TelemetryExporter(str(tmp_path), interval_s=999.0,
                            rotate_samples=3, keep_files=2)
    c = metrics.counter("texp/ticks")
    for _ in range(8):
        c.inc()
        exp.tick()
    exp.stop()  # + final flush sample
    series = telemetry.read_series(str(tmp_path), pid=os.getpid())
    seqs = [s["seq"] for s in series]
    assert seqs == sorted(seqs) and seqs[-1] == 9
    files = [f for f in os.listdir(str(tmp_path)) if f.endswith(".jsonl")]
    assert len(files) <= 2
    # interval deltas: each live tick saw exactly +1
    live = [s for s in series if s["seq"] <= 8]
    assert all(s["deltas"]["counters"].get("texp/ticks") == 1 for s in live)
    # the prometheus textfile rides along
    assert (tmp_path / "metrics.prom").exists()


def test_exporter_thread_final_partial_interval_flush(tmp_path):
    exp = TelemetryExporter(str(tmp_path), interval_s=60.0)  # never ticks
    exp.start()
    c = metrics.counter("texp/final")
    c.inc(5)
    exp.stop()  # must flush the partial interval
    series = telemetry.read_series(str(tmp_path), pid=os.getpid())
    assert series, "final partial interval lost"
    assert series[-1]["deltas"]["counters"].get("texp/final") == 5
    assert exp.closed


def test_exporter_unwritable_dir_logs_once_and_disables(tmp_path, caplog):
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where a dir must go")
    bad = str(blocker / "sub")  # makedirs under a file -> OSError
    exp = TelemetryExporter(bad, interval_s=999.0)
    hits = []
    mon = slo.SLOMonitor([slo.SLO("texp/g", max_value=1.0)])
    exp.add_listener(lambda s: hits.append(s))
    exp.add_listener(mon.on_sample)
    metrics.gauge("texp/g").set(5.0)
    with caplog.at_level(logging.ERROR, logger="paddle_tpu"):
        exp.tick()
        exp.tick()
        exp.tick()
    errors = [r for r in caplog.records
              if "PADDLE_TPU_TELEMETRY_DIR" in r.getMessage()]
    assert len(errors) == 1, "must log exactly once, got %d" % len(errors)
    assert exp.disabled
    # the run is not masked and LISTENERS kept working disk-free
    assert len(hits) == 3
    assert mon.breaches_total == 3  # gauge ceiling kept evaluating
    exp.stop()


def test_two_engines_share_one_exporter_thread(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_INTERVAL_S", "60")

    def _threads():
        return [t for t in threading.enumerate()
                if t.name == "tpu-telemetry" and t.is_alive()]

    assert not _threads()
    eng1 = _tiny_engine(slots=2)
    eng2 = _tiny_engine(slots=2)
    try:
        assert len(_threads()) == 1, "exporter thread double-started"
        assert eng1._telemetry is eng2._telemetry
        eng1.close()
        assert len(_threads()) == 1, "refcounted exporter died early"
    finally:
        eng2.close()
        eng1.close()
    time.sleep(0.05)
    assert not _threads(), "last release did not stop the exporter"
    # the shutdown flushed a final sample
    assert telemetry.read_series(str(tmp_path), pid=os.getpid())


def test_engine_without_env_has_no_exporter(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    eng = _tiny_engine(slots=2)
    try:
        assert eng._telemetry is None
    finally:
        eng.close()


def test_supervisor_telemetry_lifecycle(tmp_path, monkeypatch):
    import paddle_tpu as fluid
    from paddle_tpu.reliability import run_supervised

    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "tele"))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_INTERVAL_S", "60")
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)

    def feed_source(start):
        def gen():
            for _ in range(start, 4):
                yield {"x": rng.randn(2, 4).astype("float32")}
        return gen()

    res = run_supervised(exe, main_prog, feed_source, total_steps=4,
                         fetch_list=[loss],
                         checkpoint_dir=str(tmp_path / "ckpt"),
                         exit_on_preempt=False)
    assert res.steps_done == 4
    series = telemetry.read_series(str(tmp_path / "tele"), pid=os.getpid())
    assert series, "supervisor did not flush the final partial interval"
    last = series[-1]
    assert last["deltas"]["counters"].get(
        "executor/run_steps_steps", 0) >= 4
    # the supervised run released its reference: no thread left behind
    assert not [t for t in threading.enumerate()
                if t.name == "tpu-telemetry" and t.is_alive()]


def test_tick_counter_reset_never_emits_negative_deltas(tmp_path):
    exp = TelemetryExporter(str(tmp_path), interval_s=999.0)
    c = metrics.counter("texp/reset")
    h = metrics.histogram("texp/reset_h")
    c.inc(5)
    h.observe(1.0)
    exp.tick()
    metrics.reset()  # mid-run reset (bench/selftest code does this)
    c.inc(2)
    h.observe(3.0)
    sample = exp.tick()
    # Prometheus rate() semantics: the post-reset value IS the increment
    assert sample.counter_delta("texp/reset") == 2
    hd = sample.histogram_delta("texp/reset_h")
    assert hd["count"] == 1 and hd["sum"] == 3.0
    assert all(v >= 0 for v in sample.deltas["counters"].values())
    exp.stop()


def test_interval_percentile_overflow_bucket_reports_largest_bound():
    """Observations past the last finite bound must NOT be understated:
    an SLO ceiling below that bound has to breach (the slow-death case)."""
    exp = TelemetryExporter("", interval_s=999.0)
    exp.disabled = True
    h = metrics.histogram("texp/slow_ms", buckets=(1.0, 10.0, 100.0))
    h.observe(0.5)            # one fast request
    for _ in range(5):
        h.observe(30000.0)    # five stalled past every bound
    sample = exp.tick()
    p99 = sample.histogram_interval_percentile("texp/slow_ms", 99)
    assert p99 == 100.0, p99  # the largest finite bound, not ~0.5
    assert slo.SLO("texp/slow_ms", p=99, max_ms=50.0).evaluate(sample)
    exp.stop()


def test_watch_ring_tail_survives_rotation(tmp_path, capsys):
    """The tail keys on per-writer seq, not list index: rotation prunes
    shrink the doc list mid-watch, and an index cursor would go blind for
    a whole rotation's worth of samples."""
    from tools.dump_metrics import watch

    exp = TelemetryExporter(str(tmp_path), interval_s=999.0,
                            rotate_samples=2, keep_files=2)
    c = metrics.counter("watchtest/rot")
    for _ in range(3):
        c.inc()
        exp.tick()
    done = threading.Event()

    def feeder():
        for _ in range(6):  # drives several prunes under the live tail
            c.inc()
            exp.tick()
            time.sleep(0.02)
        done.set()

    t = threading.Thread(target=feeder)
    t.start()
    watch(0.01, telemetry_dir=str(tmp_path), max_ticks=40)
    t.join()
    exp.stop()
    out = capsys.readouterr().out
    assert done.is_set()
    # the final sample (seq 9) printed even though the pruned ring holds
    # fewer docs than the tail had already consumed
    assert "-- seq 9" in out, out[-600:]
    assert "watchtest/rot" in out


def test_track_labels_survive_cross_process_conversion(tmp_path, monkeypatch):
    tracer.clear_spans()
    tracer.start_tracing()
    tracer.record_span("work", 100, 50, cat="serving", track="serving slot 1")
    spans = tracer.stop_tracing()
    raw = tmp_path / "spans.json"
    tracer.save_spans(str(raw), spans)
    # simulate the converter running in a fresh process: no in-memory
    # virtual-track registry
    monkeypatch.setattr(tracer, "_track_names", {})
    monkeypatch.setattr(tracer, "_track_ids", {})
    loaded = tracer.load_spans(str(raw))
    doc = tracer.to_chrome_trace(loaded)
    labels = [e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert "serving slot 1" in labels, labels
    # chrome -> spans -> chrome keeps the label too (second generation)
    chrome2 = tmp_path / "trace2.json"
    tracer.save_chrome_trace(str(chrome2), loaded)
    again = tracer.load_spans(str(chrome2))
    doc2 = tracer.to_chrome_trace(again)
    labels2 = [e["args"]["name"] for e in doc2["traceEvents"]
               if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert "serving slot 1" in labels2, labels2


def test_validate_digest_reports_real_slot_index(rng):
    from paddle_tpu.serving import trace as strace

    tracer.clear_spans()
    tracer.start_tracing()
    eng = _tiny_engine(slots=2)
    try:
        req = eng.submit(list(rng.randint(0, 64, 4)), 3)
        eng.run()
    finally:
        eng.close()
        spans = tracer.stop_tracing()
    digest = strace.validate_request_spans(spans, [req])[req.trace_id]
    assert digest["slot"] in (0, 1), digest  # a real slot, not a track tid


# -- SLO specs ----------------------------------------------------------------

def _sample(seq=1, dt=1.0, counters=None, hists=None, gauges=None):
    snap = {}
    for n, v in (gauges or {}).items():
        snap[n] = {"type": "gauge", "value": v}
    deltas = {"counters": counters or {}, "histograms": hists or {}}
    return TelemetrySample(seq, time.time(), dt, snap, deltas)


def test_slo_modes():
    lat = slo.SLO("m/lat_ms", p=99, max_ms=100.0)
    hit = _sample(hists={"m/lat_ms": {
        "count": 10, "sum": 2500.0,
        "buckets": {"le_50": 1, "le_500": 9}}})
    b = lat.evaluate(hit)
    assert b is not None and b.value > 100.0
    ok = _sample(hists={"m/lat_ms": {
        "count": 10, "sum": 100.0, "buckets": {"le_50": 10}}})
    assert lat.evaluate(ok) is None
    assert lat.evaluate(_sample()) is None  # no observations -> no verdict

    depth = slo.SLO("m/depth", max_value=8)
    assert depth.evaluate(_sample(gauges={"m/depth": 9})) is not None
    assert depth.evaluate(_sample(gauges={"m/depth": 8})) is None

    qps = slo.SLO("m/done", min_rate=10.0)
    assert qps.evaluate(_sample(counters={"m/done": 5}, dt=1.0)) is not None
    assert qps.evaluate(_sample(counters={"m/done": 20}, dt=1.0)) is None
    assert qps.evaluate(_sample(counters={}, dt=1.0)) is None  # idle != slow

    err = slo.SLO("m/fail", max_ratio=0.01, over="m/done")
    assert err.evaluate(_sample(
        counters={"m/fail": 2, "m/done": 100})) is not None
    assert err.evaluate(_sample(
        counters={"m/fail": 0, "m/done": 100})) is None
    assert err.evaluate(_sample(counters={"m/fail": 2})) is None  # den 0


def test_slo_constructor_validation():
    with pytest.raises(ValueError):
        slo.SLO("m/x")  # no mode
    with pytest.raises(ValueError):
        slo.SLO("m/x", max_ms=5, max_value=5)  # two modes
    with pytest.raises(ValueError):
        slo.SLO("m/x", max_ms=5)  # percentile without p
    with pytest.raises(ValueError):
        slo.SLO("m/x", max_ratio=0.1)  # error rate without denominator


def test_parse_slos_env_grammar():
    specs = slo.parse_slos(
        "serving/request_latency_ms:p99<=250; serving/queue_depth<=512;"
        "serving/requests_retired>=10/s;"
        "serving/requests_failed<=0.01 over serving/requests_retired")
    kinds = [s.kind for s in specs]
    assert kinds == ["percentile", "ceiling", "rate_floor", "error_rate"]
    assert specs[0].p == 99 and specs[0].threshold == 250
    assert specs[3].over == "serving/requests_retired"
    with pytest.raises(ValueError):
        slo.parse_slos("serving/queue_depth=512")
    with pytest.raises(ValueError):
        # 'over' + rate floor is a malformed error-rate spec, not a
        # silently-different rate-floor SLO
        slo.parse_slos("serving/requests_failed>=0.01/s "
                       "over serving/requests_retired")


def test_slo_monitor_counters_and_flight_recorder(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    from paddle_tpu.monitor import device as dev

    mon = slo.SLOMonitor([slo.SLO("m/depth", max_value=1.0, name="depthcap")])
    mon.on_sample(_sample(gauges={"m/depth": 5}))
    snap = metrics.snapshot()
    assert snap["slo/breaches"]["value"] == 1
    assert snap["slo/depthcap/breaches"]["value"] == 1
    fr = dev.flight_recorder()
    assert any(e.get("event") == "slo_breach" and e.get("slo") == "depthcap"
               for e in fr._entries)
    # a healthy tick clears
    cleared = []
    mon.on_clear = lambda: cleared.append(1)
    mon.on_sample(_sample(gauges={"m/depth": 0}))
    assert cleared


def test_observational_breach_does_not_block_recovery():
    """A breaching degrade=False spec must not pin health 'degraded'."""
    state = {"degraded": False}
    mon = slo.SLOMonitor(
        [slo.SLO("m/lat", p=99, max_ms=10.0, name="lat"),
         slo.SLO("m/watch_only", max_value=1.0, degrade=False, name="obs")],
        on_breach=lambda b: state.update(degraded=True),
        on_clear=lambda: state.update(degraded=False))
    slow = {"m/lat": {"count": 5, "sum": 500.0, "buckets": {"le_500": 5}}}
    mon.on_sample(_sample(hists=slow, gauges={"m/watch_only": 9}))
    assert state["degraded"]
    # latency healthy again, observational spec still breaching
    mon.on_sample(_sample(gauges={"m/watch_only": 9}))
    assert not state["degraded"], \
        "observational breach blocked health recovery"
    assert mon.breaches_total == 3  # both ticks still counted obs breaches


def test_ceiling_slo_on_counter_is_inert_and_warns_once(caplog):
    metrics.counter("sloct/c").inc(100)
    spec = slo.SLO("sloct/c", max_value=10.0)
    exp = TelemetryExporter("", interval_s=999.0)
    exp.disabled = True
    sample = exp.tick()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        assert spec.evaluate(sample) is None  # lifetime total != gauge
        assert spec.evaluate(sample) is None
    warns = [r for r in caplog.records if "gauge ceiling" in r.getMessage()]
    assert len(warns) == 1
    exp.stop()


def test_gauge_changes_ride_sample_deltas(tmp_path):
    exp = TelemetryExporter(str(tmp_path), interval_s=999.0)
    g = metrics.gauge("texp/depth")
    g.set(3.0)
    s1 = exp.tick()
    assert s1.deltas["gauges"].get("texp/depth") == 3.0
    s2 = exp.tick()  # unchanged -> not flagged
    assert "texp/depth" not in s2.deltas["gauges"]
    g.set(7.0)
    s3 = exp.tick()
    assert s3.deltas["gauges"].get("texp/depth") == 7.0
    exp.stop()


def test_dir_change_keeps_old_exporter_alive_for_holders(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "a"))
    h1 = telemetry.acquire()
    h2 = telemetry.acquire()
    assert h1 is h2 and h1._refs == 2
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "b"))
    h3 = telemetry.acquire()
    assert h3 is not h1
    telemetry.release(h1)
    assert not h1.closed, "dir change + one release killed a held exporter"
    telemetry.release(h2)
    assert h1.closed  # last holder released the superseded exporter
    telemetry.release(h3)
    assert h3.closed


# -- serving traces -----------------------------------------------------------

def test_serving_trace_reconstructs_schedule(rng):
    from paddle_tpu.serving import trace as strace

    tracer.clear_spans()
    tracer.start_tracing()
    eng = _tiny_engine(slots=3)
    base = metrics.snapshot()
    reqs = []
    try:
        for _ in range(8):
            p = list(rng.randint(0, 64, int(rng.randint(3, 20))))
            reqs.append(eng.submit(p, int(rng.randint(2, 8))))
        done = eng.run()
    finally:
        eng.close()
        spans = tracer.stop_tracing()
    assert len(done) == 8
    digests = strace.validate_request_spans(spans, reqs)
    assert len(digests) == 8

    def delta(name):
        return (metrics.snapshot()[name]["value"]
                - base.get(name, {}).get("value", 0))

    # slot occupancy from spans == the serving/* counters
    by_slot = strace.slot_assignments_from_spans(spans)
    assert sum(len(v) for v in by_slot.values()) == delta(
        "serving/requests_admitted") == 8
    assert len(by_slot) <= 3  # never more tracks than slots
    prefills = [s for s in spans if s["name"].startswith("prefill(")]
    assert len(prefills) == delta("serving/prefills")
    decode_windows = {s["ts_us"] for s in spans if s["name"] == "decode"}
    assert len(decode_windows) == delta("serving/decode_dispatches")
    # every request's span chain is causally ordered
    for req in reqs:
        mine = sorted((s for s in spans
                       if (s.get("args") or {}).get("trace_id") == req.trace_id
                       and s["name"] != "queued"),
                      key=lambda s: s["ts_us"])
        assert mine[0]["name"] == "submitted"
        assert mine[-1]["name"] == "retired"
    # no ghost slots: at no time do lifetime spans on one track overlap
    for tid, ids in by_slot.items():
        assert len(ids) == len(set(ids))


def test_trace_ids_link_flight_recorder_to_spans(tmp_path, monkeypatch, rng):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    from paddle_tpu.monitor import device as dev
    from paddle_tpu.reliability import FaultPlan

    tracer.clear_spans()
    tracer.start_tracing()
    eng = _tiny_engine(slots=2)
    try:
        req = eng.submit(list(rng.randint(0, 64, 6)), 8)
        with FaultPlan.parse("serving.decode@1=fatal"):
            eng.run(max_steps=10)
    finally:
        eng.close()
        spans = tracer.stop_tracing()
    assert req.state == "failed"
    fr = dev.flight_recorder()
    batch_events = [e for e in fr._entries
                    if e.get("event") == "serving_inflight_batch"]
    assert batch_events, "no in-flight batch captured"
    traced_ids = {(s.get("args") or {}).get("trace_id") for s in spans}
    for ev in batch_events:
        for row in ev["slots"]:
            assert row["trace_id"] in traced_ids, \
                "flight recorder row not linkable to the trace: %r" % row


def test_untraced_engine_emits_no_serving_spans(rng):
    tracer.clear_spans()
    assert not tracer.active()
    eng = _tiny_engine(slots=2)
    try:
        eng.submit(list(rng.randint(0, 64, 4)), 3)
        eng.run()
    finally:
        eng.close()
    assert not [s for s in tracer.get_spans() if s.get("cat") == "serving"]


# -- the acceptance drill: latency fault -> SLO -> degraded health ------------

def test_latency_fault_trips_p99_slo_and_degrades_health(
        tmp_path, monkeypatch, rng):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "tele"))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_INTERVAL_S", "60")  # manual ticks
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    from paddle_tpu.monitor import device as dev
    from paddle_tpu.reliability import FaultPlan

    # a warm decode step of this engine is about 1 ms here; the SLO stands
    # 50x above it and the fault 10x above the SLO, so a loaded host can
    # close neither gap
    eng = _tiny_engine(slots=2, slos=[
        slo.SLO("serving/decode_step_ms", p=99, max_ms=50.0)])
    try:
        # the engine's first request pays the first dispatch of every
        # executable: its tick is the warm-up's, not the healthy baseline
        eng.submit(list(rng.randint(0, 64, 4)), 3)
        eng.run()
        telemetry.force_tick()
        # healthy traffic on the warm engine, healthy tick
        eng.submit(list(rng.randint(0, 64, 4)), 3)
        eng.run()
        telemetry.force_tick()
        assert eng.health()["status"] == "ok"
        breaches0 = metrics.snapshot()["slo/breaches"]["value"]
        # inject a 500ms decode latency fault: dispatches stay successful
        # but slow — the crash-free degradation SLOs exist to catch
        with FaultPlan.parse("serving.decode@1=latency:3:500"):
            eng.submit(list(rng.randint(0, 64, 4)), 4)
            eng.run()
        sample = telemetry.force_tick()
        assert sample.histogram_interval_percentile(
            "serving/decode_step_ms", 99) > 20.0
        snap = metrics.snapshot()
        assert snap["slo/breaches"]["value"] > breaches0
        health = eng.health()
        assert health["status"] == "degraded", health
        assert health["slo_breach"]["metric"] == "serving/decode_step_ms"
        fr = dev.flight_recorder()
        assert any(e.get("event") == "slo_breach" for e in fr._entries)
        # healthy tick (no new observations) clears the degradation
        telemetry.force_tick()
        assert eng.health()["status"] == "ok"
    finally:
        eng.close()
    # the JSONL series caught all of it: >= 3 manual ticks + final flush
    series = telemetry.read_series(str(tmp_path / "tele"), pid=os.getpid())
    assert len(series) >= 4
    assert any(s["deltas"]["histograms"].get("serving/decode_step_ms")
               for s in series)


def test_env_declared_slos_apply(monkeypatch, rng, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_INTERVAL_S", "60")
    monkeypatch.setenv("PADDLE_TPU_SLO", "serving/queue_depth<=0.5")
    eng = _tiny_engine(slots=2)
    try:
        assert eng._slo_monitor is not None
        eng.submit(list(rng.randint(0, 64, 4)), 3)  # queue_depth -> 1
        telemetry.force_tick()
        assert eng.health()["status"] == "degraded"
        eng.run()
    finally:
        eng.close()


# -- collective budgets -------------------------------------------------------

def test_budget_formulas_closed_forms():
    # gpipe: M=4 over S=4, A bytes -> 2*(4-1) + 4+4-2 = 12 hops
    assert budgets.budget_bytes("gpipe.fwd", microbatches=4, stages=4,
                                activation_bytes=128) == 12 * 128
    # ragged M pads up to a stage multiple first
    assert budgets.budget_bytes("gpipe.fwd", microbatches=3, stages=4,
                                activation_bytes=10) == \
        budgets.budget_bytes("gpipe.fwd", microbatches=4, stages=4,
                             activation_bytes=10)
    assert budgets.budget_bytes("ring_attention.fwd", n_devices=4,
                                block_bytes=1024) == 8192
    assert budgets.budget_bytes("ring_attention.bwd", n_devices=4,
                                block_bytes=1024, block_elems=256) == \
        2 * 4 * 1024 + 2 * 4 * 256 * 4
    assert budgets.budget_bytes("ctr.row_routing", n_shards=8, n_local=16,
                                dim=8, id_itemsize=4, row_itemsize=4) == \
        8 * 16 * (4 + 8 * 4)


def test_check_budget_pass_and_tightened_failure():
    rec = budgets.check_budget("ring_attention.fwd", 8192, n_devices=4,
                               block_bytes=1024)
    assert rec["utilization"] == 1.0
    with pytest.raises(budgets.CollectiveBudgetExceeded) as ei:
        budgets.check_budget("ring_attention.fwd", 8192, budget=8191)
    assert "ring_attention.fwd" in str(ei.value)
    with pytest.raises(KeyError):
        budgets.budget_bytes("no.such.leg")


def test_measured_ring_bytes_within_budget(rng):
    """The in-process twin of tools/check_budgets --selftest's ring leg
    (the full three-leg sweep including gpipe + CTR routing runs there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.parallel import ring_attention

    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q = jnp.asarray(rng.randn(2, 2, 8 * sp, 8).astype("float32"))
    before = metrics.snapshot().get(
        "collectives/ppermute/bytes", {}).get("value", 0)
    with mesh:
        ring_attention(q, q + .1, q + .2, mesh=mesh, axis_name="sp")
    measured = metrics.snapshot()["collectives/ppermute/bytes"]["value"] \
        - before
    rec = budgets.check_budget("ring_attention.fwd", measured,
                               n_devices=sp, block_bytes=q.size // sp * 4)
    assert rec["measured_bytes"] == rec["budget_bytes"]


# -- watch formatter ----------------------------------------------------------

def test_dump_metrics_watch_formatter_and_ring_tail(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from tools.dump_metrics import watch

    exp = TelemetryExporter(str(tmp_path), interval_s=999.0)
    metrics.counter("watchtest/c").inc(3)
    metrics.histogram("watchtest/h").observe(2.0)
    exp.tick()
    exp.stop()
    rc = watch(0.01, telemetry_dir=str(tmp_path), max_ticks=1)
    assert rc == 0
    out = capsys.readouterr().out
    assert "watchtest/c" in out and "+3" in out
    assert "watchtest/h" in out

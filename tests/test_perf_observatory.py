"""Performance observatory tests: run ledger (monitor.runlog), step-time
attribution (monitor.stepstats), and the P99 satellite columns. All series are seeded
and synthetic — no wall-clock timing in any assertion."""

import json
import os

import pytest

from paddle_tpu.monitor import metrics as mx
from paddle_tpu.monitor import runlog, stepstats


@pytest.fixture(autouse=True)
def _metrics_on():
    mx.enable()
    mx.reset()
    yield


@pytest.fixture
def ledger_env(tmp_path, monkeypatch):
    path = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("PADDLE_TPU_RUN_LEDGER", path)
    monkeypatch.setattr(runlog, "_ledger", None)
    yield path
    runlog._ledger = None


def _rec(config, metrics, seq, kind="bench"):
    return {"schema": runlog.RUN_SCHEMA, "run_id": "rtest-%d" % seq,
            "t": float(seq), "kind": kind, "configs": {config: metrics}}


# -- run ledger ---------------------------------------------------------------

def test_record_run_round_trips_provenance(ledger_env):
    rec = runlog.record_run("bench", {"cfg": {"step_ms_p50": 12.5}},
                            extra={"note": "t"})
    assert rec["ledger_path"] == ledger_env
    back = runlog.read_ledger(ledger_env)
    assert len(back) == 1
    got = back[0]
    assert got["run_id"] == runlog.run_id() == rec["run_id"]
    assert got["kind"] == "bench"
    assert got["configs"] == {"cfg": {"step_ms_p50": 12.5}}
    assert got["extra"] == {"note": "t"}
    prov = got["provenance"]
    # every provenance section present (values may degrade to None)
    for key in ("git", "device_kind", "opt_level", "jax", "env"):
        assert key in prov, key
    assert "sha" in prov["git"]
    assert prov["env"].get("PADDLE_TPU_RUN_LEDGER") == ledger_env
    assert mx.snapshot()["runlog/records"]["value"] >= 1


def test_ledger_rotation_keeps_bounded_files(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = runlog.RunLedger(path, rotate_records=2, keep_files=2)
    for i in range(7):
        led.append(_rec("c", {"step_ms_p50": float(i)}, i))
    # rotate@2 keep@2 (live + 1 shard): bounded on disk, newest preserved
    names = sorted(os.listdir(str(tmp_path)))
    assert len(names) == 2, names
    back = runlog.read_ledger(path)
    assert [r["configs"]["c"]["step_ms_p50"] for r in back] == [4.0, 5.0, 6.0]
    assert mx.snapshot()["runlog/rotations"]["value"] >= 1


def test_read_ledger_skips_torn_tail_and_foreign_schema(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = runlog.RunLedger(path)
    led.append(_rec("c", {"step_ms_p50": 1.0}, 0))
    led.append(_rec("c", {"step_ms_p50": 2.0}, 1))
    with open(path, "a") as f:
        f.write(json.dumps({"schema": "someone_else/v1", "x": 1}) + "\n")
        f.write('{"schema": "paddle_tpu.runlog/v1", "run_id": "torn')
    back = runlog.read_ledger(path)
    assert [r["run_id"] for r in back] == ["rtest-0", "rtest-1"]


def test_ledger_write_error_disables_once(tmp_path):
    led = runlog.RunLedger(str(tmp_path / "noexist" / "x" / "led.jsonl"))
    # make the parent un-creatable by occupying it with a FILE
    blocker = str(tmp_path / "noexist")
    with open(blocker, "w") as f:
        f.write("x")
    assert led.append(_rec("c", {}, 0)) is None
    assert led.disabled
    assert led.append(_rec("c", {}, 1)) is None  # no raise, stays disabled
    assert mx.snapshot()["runlog/write_errors"]["value"] >= 1


def test_record_run_without_ledger_still_returns_record(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_RUN_LEDGER", raising=False)
    monkeypatch.setattr(runlog, "_ledger", None)
    rec = runlog.record_run("bench", {"cfg": {"eps": 1.0}})
    assert rec["ledger_path"] is None and rec["run_id"] == runlog.run_id()
    info = runlog.tail_info()
    assert info == {"run_id": runlog.run_id()}


# -- step-time attribution ----------------------------------------------------

def test_attribute_labels_input_bound_with_feed_wait_dominant():
    bd = stepstats.attribute(
        {"host_ms": 1.0, "input_ms": 8.0, "compute_ms": 2.0},
        step_ms=11.0)
    assert bd["bound"] == "input" and bd["dominant"] == "input_ms"
    assert "prefetch" in bd["hint"]
    assert stepstats.render(bd, "probe").startswith("probe: input-bound")


def test_attribute_residual_compute_on_peakless_hardware():
    bd = stepstats.attribute({"host_ms": 1.0, "input_ms": 2.0}, step_ms=10.0)
    assert bd["compute_is_residual"] and bd["terms"]["compute_ms"] == 7.0
    assert bd["bound"] == "compute"


def test_collect_terms_from_snapshot_with_peaks():
    snap = {
        "device_profile/flops": {"type": "gauge", "value": 1e9},
        "device_profile/bytes_accessed": {"type": "gauge", "value": 8e6},
        "collectives/ppermute/bytes": {"type": "counter", "value": 4e6},
        "collectives/ppermute/calls": {"type": "counter", "value": 2},
        "collectives/ppermute/sp/bytes": {"type": "counter", "value": 4e6},
        "data/prefetch_wait_ms": {"type": "histogram", "count": 4,
                                  "sum": 2.0},
    }
    peaks = {"flops": 1e12, "hbm_gbps": 8.0, "ici_gbps": 4.0}
    terms = stepstats.collect_terms(snap, host_ms=0.25, peaks=peaks)
    assert terms["compute_ms"] == pytest.approx(1.0)
    assert terms["memory_ms"] == pytest.approx(1.0)
    # axis-qualified collectives counters must not double count
    assert terms["comms_ms"] == pytest.approx(1.0)
    assert terms["input_ms"] == pytest.approx(0.5)
    assert terms["host_ms"] == 0.25
    bd = stepstats.attribute(terms, step_ms=4.0)
    assert bd["bound"] in ("compute", "comms")
    assert "attributed_frac" in bd


def test_attribute_with_nothing_measured():
    bd = stepstats.attribute({})
    assert bd["bound"] == "unknown" and bd["dominant"] is None


# -- P99 satellites -----------------------------------------------------------

def test_histogram_snapshot_has_p99():
    h = mx.histogram("perf_obs/p99_hist")
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["p95"] <= snap["p99"] <= snap["max"]
    assert "p99=" in mx.to_text()


def test_step_profiler_table_has_p99_column():
    from paddle_tpu.profiler import StepProfiler

    prof = StepProfiler()
    for _ in range(5):
        with prof.step("train"):
            pass
    table = prof.summary()
    header, row = table.splitlines()[0], table.splitlines()[1]
    assert "P99(ms)" in header
    # alignment: header columns and row columns line up count-wise
    assert len(header.split()) == len(row.split())


def test_step_logger_summary_has_p99(monkeypatch):
    from paddle_tpu.monitor.step_logger import StepLogger

    sl = StepLogger(every_n=1000)
    t = [0.0]

    def fake_clock():
        t[0] += 0.01
        return t[0]

    monkeypatch.setattr("paddle_tpu.monitor.step_logger.time.perf_counter",
                        fake_clock)
    for _ in range(10):
        sl.step(examples=4)
    s = sl.summary()
    assert "p99" in s["step_time_ms"]
    assert s["step_time_ms"]["p99"] >= s["step_time_ms"]["p50"]


def test_dump_metrics_table_renders_p99():
    from tools.dump_metrics import format_snapshot

    h = mx.histogram("perf_obs/fmt_hist")
    h.observe(5.0)
    out = format_snapshot(mx.snapshot())
    assert "p99=" in out


# -- flight-dump join keys ----------------------------------------------------

def test_flight_dump_embeds_run_id_and_telemetry_delta(tmp_path, monkeypatch):
    from paddle_tpu.monitor import telemetry
    from paddle_tpu.monitor.device import FlightRecorder

    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "tel"))
    handle = telemetry.acquire()
    try:
        mx.counter("perf_obs/flight_evt").inc(3)
        telemetry.force_tick()
        fr = FlightRecorder(str(tmp_path / "flight"))
        fr.record_event("test_evt", detail=1)
        path = fr.dump("test")
        with open(path) as f:
            doc = json.load(f)
        assert doc["run_id"] == runlog.run_id()
        assert doc["telemetry_last"]["seq"] >= 1
        assert doc["telemetry_last"]["deltas"]["counters"][
            "perf_obs/flight_evt"] == 3
    finally:
        telemetry.release(handle)

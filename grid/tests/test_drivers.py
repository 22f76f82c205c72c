"""The rehearsal: ``grid.run.main`` and the drivers under it, end to end on
the CPU at toy widths, with the device check stubbed HERE (the command has
no option for it), on one device and on four virtual ones. A CPU run
proves control flow, counts and the last line's form; it is never a time."""

import json

import pytest

from grid import flops, manifest, runtime
from grid import run as grid_run

FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 2 ** 34}


def _run(monkeypatch, capsys, toy_root, workload, trace, seconds="1.5"):
    cell_chips = manifest.Cell(workload, toy_root).chips
    monkeypatch.setattr(manifest, "ROOT", toy_root)
    monkeypatch.setattr(
        runtime, "require_chips",
        lambda chips: {"platform": "cpu-rehearsal", "kind": "toy",
                       "count": cell_chips})
    monkeypatch.setattr(flops, "device_peaks", lambda kind: FAKE_PEAKS)
    # the CPU keeps no memory statistics; the executables' scratch is read
    monkeypatch.setattr(runtime, "held_bytes", lambda: 1)
    monkeypatch.setattr(grid_run, "TRACE_SECONDS", 0.7)
    rc = grid_run.main(["--workload", workload, "--seed", str(2 ** 31 + 5),
                        "--seconds", seconds, "--trace", str(trace)])
    lines = capsys.readouterr().out.strip().splitlines()
    notes = [json.loads(x)["note"] for x in lines[:-1]]
    return rc, json.loads(lines[-1]), notes


def _well_formed(last, cell, traced):
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["memory_peak_bytes"] > 1    # held 1 + scratch
    allowed = set(cell.reported(traced))
    assert set(last["metrics"]) <= allowed
    for name, m in last["metrics"].items():
        assert m["unit"] == cell.metrics[name]["unit"]
        assert isinstance(m["value"], float)


@pytest.mark.parametrize("workload", ["gpt2s-chat-sat", "gpt2s-doc-steady"])
def test_serve_cells_end_to_end(monkeypatch, capsys, toy_root, workload):
    rc, last, notes = _run(monkeypatch, capsys, toy_root, workload, 0)
    cell = manifest.Cell(workload, toy_root)
    assert rc == 0
    _well_formed(last, cell, False)
    assert last["correct"], notes
    assert set(last["metrics"]) == set(cell.reported(False))
    summary = notes[-1]
    assert summary["requests_due_in_window"] == last["attempted"]
    assert summary["requests_with_a_gap"] > 0


def test_serve_traced_run_reports_per_layer_metrics(monkeypatch, capsys,
                                                    toy_root):
    rc, last, notes = _run(monkeypatch, capsys, toy_root,
                           "gpt2s-doc-steady", 1)
    cell = manifest.Cell("gpt2s-doc-steady", toy_root)
    assert rc == 0 and last["correct"], notes
    _well_formed(last, cell, True)
    # host-side readers answer; the device's find nothing to read in a CPU
    # trace (it has no /device:TPU plane) and are left out of the line
    assert {"tpot_p95_ms", "queue_wait_ms_p50", "prefill_ms_mean",
            "decode_dispatch_ms_mean", "engine_host_ms_per_step"} \
        <= set(last["metrics"])
    assert "device_idle_share.serve" not in last["metrics"]
    assert "paged_attn_roofline" not in last["metrics"]
    assert last["device"]["window_s"] > 0
    assert "grid/engine.step" in json.dumps(last["breakdown"]) \
        or last["breakdown"]["idle_gaps"] == []


@pytest.mark.parametrize("workload", ["tfbase-train-1chip",
                                      "tfbase-train-dp4"])
def test_train_cells_end_to_end(monkeypatch, capsys, toy_root, workload):
    import jax

    if workload.endswith("dp4") and len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    rc, last, notes = _run(monkeypatch, capsys, toy_root, workload, 0,
                           seconds="1.0")
    cell = manifest.Cell(workload, toy_root)
    assert rc == 0
    _well_formed(last, cell, False)
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    verdict = next(n for n in notes if "problems" in n)
    # toy widths start above ln V; every other check must hold
    assert all("ln V" in p for p in verdict["problems"]), verdict
    assert verdict["compiles"] == 0
    assert verdict["loss"]["probe_after"] < verdict["loss"]["probe_before"]


def test_a_compile_in_the_window_is_incorrect(monkeypatch, toy_root):
    from grid.drivers import serve

    monkeypatch.setattr(manifest, "ROOT", toy_root)
    cell = manifest.Cell("gpt2s-chat-sat", toy_root)
    args = type("A", (), {"seed": 1, "seconds": 0.8})()
    job = grid_run.Job(cell, args, runtime.CompileMeter(),
                       runtime.Profiler(None))
    engine = serve.build(job)
    with engine:
        serve.warm(engine, 97)
        from grid import generate

        plan = generate.serve_plan(cell.traffic, 97, 1, 0.8)
        record = serve.drive(engine, plan, 0.8, 0.3, 0.0, job.profiler,
                             lambda doc: None, job.meter)
        verdict = serve.check(engine, record, job, compiles_in_window=1)
    assert not verdict["correct"]
    assert any("compilations inside the window" in p
               for p in verdict["problems"])


def test_sweep_finds_a_capacity(monkeypatch, capsys, toy_root):
    from grid import sweep

    monkeypatch.setattr(manifest, "ROOT", toy_root)
    monkeypatch.setattr(runtime, "require_chips", lambda chips: {})
    rc = sweep.main(["--workload", "gpt2s-doc-steady", "--seconds", "1",
                     "--fractions", "0.5"])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0 and [r.get("window") for r in rows] \
        == ["backlog", None, "0.50 of capacity"]
    assert rows[1]["capacity_requests_per_s"] > 0
    assert rows[2]["rate_per_s"] == pytest.approx(
        0.5 * rows[1]["capacity_requests_per_s"], abs=1e-3)

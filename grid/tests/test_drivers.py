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
    cap = capsys.readouterr()
    lines = cap.out.strip().splitlines()
    notes = [json.loads(x)["note"] for x in lines[:-1]]
    last = json.loads(lines[-1])
    # each number compared beside its limit: the last lines on standard
    # error, one a number, as the last line's ``compared`` has them
    said = cap.err.strip().splitlines()[-len(last["compared"]):]
    assert [x.split()[2] for x in said] == list(last["compared"]), cap.err
    return rc, last, notes


def _well_formed(last, cell, traced):
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert list(last)[-1] == "compared" and last["compared"]
    assert all(len(pair) == 2 for pair in last["compared"].values())
    assert last["compared"]["compiles"] == [0, 0]
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
            "decode_dispatch_ms_mean"} \
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


@pytest.mark.parametrize("due, submitted, want", [
    (1.005, 1.0215, 0.0015),   # due inside a cycle: counted from its end
    (1.030, 1.0304, 0.0004),   # due while nothing ran: from the due instant
    (0.500, 0.5000, 0.0),      # before the first cycle, on time
    (1.045, 1.0600, 0.0),      # due inside the last cycle, submitted at its end
])
def test_lateness_is_counted_from_the_end_of_the_cycle_in_progress(
        due, submitted, want):
    """The loop submits between engine cycles, so a request due inside one
    waits for its end whatever the harness does: that wait is the
    engine's, and ``check`` holds only the rest under a decode dispatch."""
    from types import SimpleNamespace as NS

    from grid.drivers import serve

    cycles = [serve.Cycle(1.00, 1.02, 4, 0, 0, 4),
              serve.Cycle(1.04, 1.06, 4, 0, 0, 4)]
    tr = NS(due=due, req=NS(submitted_t=submitted))
    got = serve.harness_lateness(cycles, [c.start for c in cycles], tr)
    assert got == pytest.approx(want, abs=1e-12)

"""The eight ``startup_*`` readers over a made record (the identity is
exact, an entry's seconds leave the phase it began in, an executable built
under the pre-roll or after the window's opening is left out, parts over
``setup_s`` raise, a program without the log gives nothing); the rehearsal
of ``falcon-h1-chat-steady`` end to end on the CPU at ``test_serve_ssm.py``'s
toy widths, traced (the eight printed, adding up to ``setup_s``) and
untraced; and that the benchmark gained entries and files only. A CPU run
proves control flow, counts and the last line's form only."""

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from grid import manifest
from grid import run as grid_run
from grid.readers import startup as readers
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed
from grid.tests.test_serve_ssm import CONFIG, TOY, _toy_limits

CELL = "falcon-h1-chat-steady"
SECONDS = ("startup_import_s", "startup_weights_s", "startup_trace_lower_s",
           "startup_backend_compile_s", "startup_cache_load_s",
           "startup_unattributed_s")
COUNTS = ("startup_executables_compiled", "startup_executables_from_cache")
NAMES = SECONDS[:-1] + COUNTS + SECONDS[-1:]


def _entry(name, t, t_last, labelled=False, count=1, trace=0.0, lower=0.0,
           backend=0.0, cache="none", retrieval=0.0, saved=0.0, phase=None):
    return {"name": name, "labelled": labelled, "t": t, "t_last": t_last,
            "count": count, "trace_s": trace, "lower_s": lower,
            "backend_s": backend, "cache": cache, "retrieval_s": retrieval,
            "saved_s": saved, "phase": phase}


def _made():
    """A start of 100 s that opens its window at t = 200: 10 s of the
    harness, an import of 5 s holding a 1 s compile, weights in 6 s holding
    a burst of three small programs, pools in 2 s, a warm-up with two
    executables (one compiled and written, one loaded), the warm-up
    traffic's one-operation programs, and a pre-roll of 20 s holding a
    late compile; then one more inside the window."""
    entries = [
        _entry("early", 112, 113, backend=1.0, phase="startup/import"),
        _entry("_normal", 116, 117.5, count=3, trace=0.5, lower=0.5,
               backend=0.5, phase="startup/weights"),
        _entry("prefill[1024]", 123, 150, labelled=True, trace=4.5,
               lower=1.5, backend=19.0, cache="miss"),
        _entry("chunk[fuse=1]", 150, 163, labelled=True, trace=4.0,
               lower=1.0, backend=0.1, cache="hit", retrieval=6.9,
               saved=14.0),
        _entry("dynamic_update_slice", 164, 166, count=7, trace=0.2,
               lower=0.3, backend=1.0),
        # under the pre-roll's traffic: inside preroll_s, named in the note
        _entry("prefill[2048]", 185, 186, labelled=True, backend=1.0),
        # after the window's opening: no part of the start at all
        _entry("resume[64]", 210, 211, labelled=True, backend=1.0),
    ]
    phases = [{"name": "startup/import", "t0": 110.0, "t1": 115.0},
              {"name": "startup/weights", "t0": 115.0, "t1": 121.0},
              {"name": "startup/pools", "t0": 121.0, "t1": 123.0}]
    plan = SimpleNamespace(due_s=0.5)
    record = {"kind": "serve", "setup_s": 100.0,
              "marks": {"open": 200.0, "close": 240.0},
              "tracked": [SimpleNamespace(due=180.5, planned=plan)]}
    return record, entries, phases


def test_the_identity_is_exact_and_an_entry_leaves_the_phase_it_began_in():
    record, entries, phases = _made()
    got = readers.split(record, entries, phases)
    assert got["startup_import_s"] == pytest.approx(4.0)      # 5 less 1
    assert got["startup_weights_s"] == pytest.approx(6.5)     # 6 + 2 - 1.5
    assert got["startup_trace_lower_s"] == pytest.approx(
        1.0 + 6.0 + 5.0 + 0.5)
    assert got["startup_backend_compile_s"] == pytest.approx(
        1.0 + 0.5 + 19.0 + 1.0)
    assert got["startup_cache_load_s"] == pytest.approx(6.9 + 0.1)
    # labelled, before the window opened: the late compile counts too
    assert got["startup_executables_compiled"] == 2.0
    assert got["startup_executables_from_cache"] == 1.0
    note = got["note"]
    assert note["preroll_s"] == pytest.approx(20.0)
    assert sum(got[n] for n in SECONDS) + note["preroll_s"] \
        == pytest.approx(100.0, abs=1e-9)
    assert got["startup_unattributed_s"] == pytest.approx(
        100 - 20 - 4 - 6.5 - 12.5 - 21.5 - 7)
    assert [r[0] for r in note["labelled"]] == [
        "prefill[1024]", "chunk[fuse=1]", "prefill[2048]"]
    assert [r[0] for r in note["entries_in_preroll"]] == ["prefill[2048]"]
    assert [r[0] for r in note["entries_in_window"]] == ["resume[64]"]
    assert (note["entries_before_window"], note["entries_after_window"]) \
        == (6, 0)
    assert (note["unlabelled_entries"], note["unlabelled_executables"]) \
        == (3, 11)


def test_a_run_without_a_schedule_has_no_preroll():
    record, entries, phases = _made()
    del record["tracked"]
    got = readers.split(record, entries, phases)
    assert got["note"]["preroll_s"] == 0.0
    # the late compile now lies before the window's opening: the start's
    assert got["startup_backend_compile_s"] == pytest.approx(22.5)
    assert got["note"]["entries_in_preroll"] == []


def test_what_is_older_than_the_harness_first_line_is_left_out():
    record, entries, phases = _made()
    entries.insert(0, _entry("an_earlier_cell", 20, 90, backend=70.0))
    phases.insert(0, {"name": "startup/weights", "t0": 20.0, "t1": 95.0})
    got = readers.split(record, entries, phases)
    assert got["startup_backend_compile_s"] == pytest.approx(21.5)
    assert got["startup_weights_s"] == pytest.approx(6.5)


def test_parts_over_setup_s_raise():
    record, entries, phases = _made()
    # a second thread's compile over the same instants as the first's
    entries.append(_entry("other_thread", 123, 150, backend=45.0))
    with pytest.raises(ValueError, match="do not fit in setup_s"):
        readers.split(record, entries, phases)
    record, entries, phases = _made()
    entries[1]["backend_s"] = 9.0       # more than the phase it began in
    with pytest.raises(ValueError, match="do not fit in setup_s"):
        readers.split(record, entries, phases)


def test_a_program_without_the_log_returns_nothing(monkeypatch, capsys):
    from paddle_tpu import compile_cache

    record, _, _ = _made()
    monkeypatch.delattr(compile_cache, "log")      # the parent
    assert [getattr(readers, n)(record) for n in NAMES] == [None] * 8
    assert capsys.readouterr().out == ""


def test_the_readers_share_one_split_and_print_one_note(monkeypatch, capsys):
    from paddle_tpu import compile_cache

    record, entries, phases = _made()
    monkeypatch.setattr(compile_cache, "log", lambda: entries)
    monkeypatch.setattr(compile_cache, "phases", lambda: phases)
    values = {n: getattr(readers, n)(record) for n in NAMES}
    assert all(isinstance(v, float) for v in values.values())
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    note = json.loads(lines[0])["note"]
    assert note["phase"] == "startup"
    assert note["parts"]["startup_backend_compile_s"] == 21.5
    assert set(note["listeners"]) == {"calls", "seconds", "entries",
                                      "dropped"}


# -- the cell -----------------------------------------------------------------


@pytest.fixture
def steady_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["model"].update(dtype="float32", max_seq=128)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=128,
                             max_queue=4096, group_pages={"global": 64})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[32, 64], preroll_s=0.3)
        # the toy slots stay full (test_serve_ssm.py says why): the check
        # wants a request resident at the run's end
        doc["arrivals"]["rate_per_s"] = 400.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "h1chat-steady.json"),
             mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, steady_root, trace):
    _toy_limits(monkeypatch)
    # the harness's first line, as a new process has it: after nothing
    monkeypatch.setattr(grid_run, "_T_START", time.perf_counter())
    rc, last, notes = _run(monkeypatch, capsys, steady_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, steady_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    assert last["correct"], [n["problems"] for n in notes if "problems" in n]
    got = last["metrics"]
    startup = [n for n in notes if n.get("phase") == "startup"]
    if not trace:
        assert set(got) == {"tpot_p50_ms", "setup_s"} and not startup
        return
    assert set(NAMES) <= set(got)
    (note,) = startup
    setup = [n for n in notes if "setup_s" in n and "phase" not in n][0][
        "setup_s"]
    seconds = [got[n]["value"] for n in SECONDS]
    assert min(seconds) >= 0
    assert sum(seconds) + note["preroll_s"] == pytest.approx(setup, abs=1e-6)
    assert 0.3 <= note["preroll_s"] < 2.0
    # this process warmed two prefill buckets and the decode step by name;
    # the package's import lies before the harness's line
    assert [r[0] for r in note["labelled"]] == [
        "prefill[32]", "prefill[64]", "chunk[fuse=1]"]
    # (a compile of a second or more is written, and the untraced case's
    # may be loaded here: the two counts share the three)
    assert got["startup_executables_compiled"]["value"] \
        + got["startup_executables_from_cache"]["value"] == 3.0
    assert got["startup_executables_compiled"]["unit"] == "executables"
    assert got["startup_trace_lower_s"]["value"] > 0
    assert got["startup_backend_compile_s"]["value"] > 0
    assert got["startup_weights_s"]["value"] > 0
    assert got["startup_import_s"]["value"] == 0
    assert [p[0] for p in note["phases"]] == ["startup/weights",
                                              "startup/pools"]
    assert note["entries_in_window"] == [] and note["listeners"]["calls"] > 0
    assert {"tpot_engine_p50_ms", "queue_wait_ms_p50", "ttft_p95_ms.steady",
            "ssd_state_slots_stepped_mean"} <= set(got)


def test_the_traffic_is_the_sat_cells_at_the_issues_rate():
    sat = json.load(open(os.path.join(ROOT, "grid", "traffic",
                                      "h1chat-sat.json")))
    steady = json.load(open(os.path.join(ROOT, "grid", "traffic",
                                         "h1chat-steady.json")))
    same = set(sat) - {"why", "who", "arrivals"}
    assert {k: steady[k] for k in same} == {k: sat[k] for k in same}
    assert set(steady) == set(sat)
    arrivals = steady["arrivals"]
    assert arrivals["process"] == sat["arrivals"]["process"] == "poisson"
    assert arrivals["rate_per_s"] in (round(0.8 * 5.3917, 3),
                                      round(0.7 * 5.3917, 3))
    assert arrivals["schedule_seed"] == arrivals["order_seed"] == 54054
    assert set(arrivals) == set(sat["arrivals"])


def test_the_cell_reports_what_the_sat_cell_reports_and_ten_more():
    """All of ``falcon-h1-chat-sat``'s metrics but ``serve_tokens_per_s``
    and the three per-layer ones that move it: a cell may report a
    per-layer metric only beside the end-to-end metric it moves (the
    driver refuses ``BENCHMARK.json`` otherwise, as it did PR 54's first
    hand-in)."""
    bench = manifest.benchmark()
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    sat = manifest.Cell("falcon-h1-chat-sat").cell["reports"]
    steady = manifest.Cell(CELL).cell["reports"]
    assert len(steady) == 41 and "serve_tokens_per_s" not in steady
    assert set(steady) - set(sat) == set(NAMES) | {"queue_wait_ms_p50",
                                                   "ttft_p95_ms.steady"}
    assert set(sat) - set(steady) == {"serve_tokens_per_s"} | {
        m for m in sat if moves.get(m) == "serve_tokens_per_s"}
    for m in bench["per_layer"]:
        assert (CELL in m.get("workloads", ())) == (m["name"] in steady)
        if m["name"] in steady:
            assert m["moves"] in steady, m["name"]
    for name in NAMES:
        spec = manifest.Cell(CELL).metrics[name]
        assert spec["layer"] == "start-up" and spec["moves"] == "setup_s"
        assert callable(manifest.reader(spec["reader"]))


def test_the_benchmark_gained_entries_and_files_only():
    """Against the parent commit: no file under ``grid/`` that was there
    is edited, and ``BENCHMARK.json`` differs by one cell, this cell's
    name at the END of ``workloads`` lists and eight per-layer metrics at
    the end, each moving ``setup_s``."""
    def git(*args):
        return subprocess.run(("git",) + args, cwd=ROOT, check=True,
                              capture_output=True, text=True).stdout

    try:
        parent = json.loads(git("show", "HEAD:BENCHMARK.json"))
        changed = git("status", "--porcelain", "--", "grid").splitlines()
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD already holds the cell: nothing to compare with")
    assert [ln for ln in changed if ln[0] not in "?A"] == []
    now = manifest.benchmark()
    for key in ("command", "paths", "run_seconds", "configs"):
        assert now[key] == parent[key]
    assert now["workloads"][:-1] == parent["workloads"]
    assert now["workloads"][-1]["name"] == CELL
    assert now["workloads"][-1]["chips"] == 1
    reports = manifest.Cell(CELL).cell["reports"]
    for kind in ("end_to_end", "per_layer"):
        old = parent[kind]
        for was, is_ in zip(old, now[kind]):
            stripped = dict(is_)
            if is_.get("workloads", [None])[-1] == CELL:
                stripped["workloads"] = is_["workloads"][:-1]
                assert is_["name"] in reports
            assert stripped == was, was["name"]
        added = now[kind][len(old):]
        assert [m["name"] for m in added] == (
            list(NAMES) if kind == "per_layer" else [])
        for m in added:
            spec = manifest.Cell(CELL).metrics[m["name"]]
            assert m["workloads"] == [CELL] and m["moves"] == "setup_s" \
                and m["layer"] == "start-up"
            assert all(spec[k] == m[k] for k in ("unit", "better", "source",
                                                 "layer", "moves"))
    # what the cell reports and a list names it for are the same metrics
    listed = {m["name"] for m in now["end_to_end"] + now["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == set(reports)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))

"""``grid/readers/requests.py`` on hand-made records where every answer can
be worked out on paper (the window's edges, ``min_tokens_for_gap``, a
request with one entry, a program that keeps no timeline), and the cell
that prints its metrics (``laguna-s-code-steady``) end to end on the CPU at
toy widths. A CPU run proves the arithmetic and the last line's form only."""

import json
import os
import types

import pytest

from grid import manifest
from grid.readers import requests
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed
from grid.tests.test_serve_mixed_gqa import gqa_root  # noqa: F401

CELL = "laguna-s-code-steady"
NEW = ("tpot_engine_p50_ms", "prefill_stall_ms_per_token_p50",
       "prefill_stall_ms_per_token_p95", "longest_handover_gap_ms_p50",
       "admission_ms_mean")
READERS = [getattr(requests, name) for name in NEW]


def _tracked(timeline, admitted_t=None, prefill_s=None, keeps=True):
    req = types.SimpleNamespace(admitted_t=admitted_t)
    if keeps:
        req.timeline, req.prefill_s = timeline, prefill_s
    return types.SimpleNamespace(req=req)


def _steps(t0, n, gap, clock=0.0):
    """A first token at ``t0`` and ``n - 1`` single tokens ``gap`` apart,
    nothing admitted meanwhile."""
    return [(t0 + i * gap, i + 1, clock) for i in range(n)]


def _record(tracked, open_t=10.0, close_t=50.0, min_tokens=4):
    return {"tracked": tracked, "min_tokens_for_gap": min_tokens,
            "marks": {"open": open_t, "close": close_t}}


# the window is 10..50 s, min_tokens_for_gap 4
A = [(11.0, 1, 0.20), (11.1, 2, 0.25), (11.2, 4, 0.25), (12.0, 5, 0.95),
     (12.1, 9, 0.95)]       # 1.1 s and 0.75 s of stall over 8 tokens
B = _steps(20.0, 5, 0.010, clock=2.0)             # 10 ms a token, no stall
C = [(30.0, 1, 3.0), (30.5, 2, 3.4), (30.6, 5, 3.4)]   # 0.6 s, 0.4 s, 4 tokens


def test_the_arithmetic_of_one_request_and_of_the_medians():
    assert requests.mean_gap_ms(A) == pytest.approx(1100.0 / 8)
    assert requests.stall_ms_per_token(A) == pytest.approx(750.0 / 8)
    assert requests.longest_gap_ms(A) == pytest.approx(800.0)
    record = _record([_tracked(A, 10.5, 0.30), _tracked(B, 19.9, 0.10),
                      _tracked(C, 29.7, 0.35)])
    assert requests.tpot_engine_p50_ms(record) == pytest.approx(1100.0 / 8)
    assert requests.prefill_stall_ms_per_token_p50(record) == \
        pytest.approx(750.0 / 8)
    # linear between the order statistics 93.75 and 100: 0.9 of the way
    assert requests.prefill_stall_ms_per_token_p95(record) == \
        pytest.approx(93.75 + 0.9 * 6.25)
    assert requests.longest_handover_gap_ms_p50(record) == \
        pytest.approx(500.0)
    assert requests.admission_ms_mean(record) == pytest.approx(250.0)


def test_the_windows_edges_and_min_tokens_for_gap():
    early = _steps(9.5, 8, 0.2)          # first token before the opening
    late = _steps(49.0, 30, 0.1)         # 11 entries by the close at 50.0
    after = _steps(50.5, 8, 0.1)         # first token after the close
    few = _steps(15.0, 3, 0.1)           # under min_tokens_for_gap
    one = [(16.0, 1, 0.0)]               # ended with its first token
    picked = requests.in_window([early, late, after, few, one, [], B],
                                10.0, 50.0, 4)
    assert [len(p) for p in picked] == [11, 5]
    assert picked[0][-1] == pytest.approx((50.0, 11, 0.0))
    # ... and a request with exactly min_tokens tokens by the close counts
    assert len(requests.in_window([_steps(49.7, 9, 0.1)], 10.0, 50.0, 4)) == 1
    assert requests.in_window([_steps(49.8, 9, 0.1)], 10.0, 50.0, 4) == []
    # a fused chunk brings min_tokens in two entries: one gap, counted
    assert len(requests.in_window([[(12.0, 1, 0.0), (12.5, 5, 0.0)]],
                                  10.0, 50.0, 4)) == 1
    record = _record([_tracked(t) for t in (early, after, few, one)])
    assert [r(record) for r in READERS] == [None] * 5
    # admitted in the window: the opening counts, the close does not; a
    # request still queued has no admitted_t
    record = _record([_tracked(B, 10.0, 0.2), _tracked(B, 50.0, 0.9),
                      _tracked(B, 9.9, 0.9), _tracked([], None, None),
                      _tracked(B, 30.0, 0.4)])
    assert requests.admission_ms_mean(record) == pytest.approx(300.0)


def test_a_program_that_keeps_no_timeline_gives_nothing_and_does_not_raise():
    """The parent of the PR that brought the reader: ``Request`` has
    neither ``timeline`` nor ``prefill_s``."""
    record = _record([_tracked(None, 12.0, keeps=False),
                      types.SimpleNamespace(req=None)])     # one refused
    assert [r(record, None) for r in READERS] == [None] * 5
    assert [r(_record([])) for r in READERS] == [None] * 5


@pytest.mark.parametrize("timeline, match", [
    # 0.5 s behind prefills in 0.1 s of wall time
    ([(11.0, 1, 0.0), (11.1, 5, 0.5)], "stalled 0.500000 s"),
    ([(11.0, 1, 0.5), (11.1, 5, 0.4)], "stalled -0.100000 s"),
    ([(11.0, 1, 0.0), (11.0, 5, 0.0)], "does not rise"),
    ([(11.0, 1, 0.0), (11.1, 1, 0.0), (11.2, 5, 0.0)], "does not rise"),
])
def test_a_timeline_that_breaks_its_own_arithmetic_fails_the_run(timeline,
                                                                match):
    with pytest.raises(ValueError, match=match):
        requests.tpot_engine_p50_ms(_record([_tracked(timeline)]))
    # a whole span of stall is allowed: the clock may run with the wall
    ok = [(11.0, 1, 0.0), (11.5, 5, 0.5)]
    assert requests.prefill_stall_ms_per_token_p50(
        _record([_tracked(ok)])) == pytest.approx(125.0)


def test_the_cell_is_code_sat_at_another_rate():
    """The traffic differs from ``code-sat`` in the rate and the three
    texts about it; the cell reports what moves ``tpot_p50_ms`` and is not
    judged on tokens a second."""
    def traffic(name):
        with open(os.path.join(ROOT, "grid", "traffic", name + ".json")) as f:
            return json.load(f)

    sat, steady = traffic("code-sat"), traffic("code-steady")
    differs = {k for k in sat if sat[k] != steady[k]}
    assert differs == {"arrivals", "why", "who"} and set(sat) == set(steady)
    assert {k for k in sat["arrivals"]
            if sat["arrivals"][k] != steady["arrivals"][k]} == {
                "rate_per_s", "rate_from"}
    assert steady["arrivals"]["rate_per_s"] == 1.090 == round(0.6 * 1.8170, 3)
    cell, full = manifest.Cell(CELL), manifest.Cell("laguna-s-code-sat")
    assert (cell.kind, cell.chips, cell.config) == (
        full.kind, 1, full.config)
    bench = manifest.benchmark()
    declared = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert cell.reported(False) == ["tpot_p50_ms", "setup_s"]
    moved = [m for m in full.reported(True)
             if declared[m]["moves"] == "tpot_p50_ms"]
    assert cell.reported(True) == moved + [
        "queue_wait_ms_p50", "ttft_p95_ms.steady"] + list(NEW)
    for name in cell.cell["reports"]:
        m = declared[name]
        assert CELL in m.get("workloads", [CELL]), name
        if name != "setup_s":
            assert m.get("moves", "tpot_p50_ms") == "tpot_p50_ms", name
    for name in NEW:
        assert declared[name]["workloads"] == [CELL]
        spec = cell.metrics[name]
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: declared[name][k] for k in ("unit", "better", "source",
                                           "layer", "moves")}
        assert manifest.reader(spec["reader"]) is getattr(requests, name)


@pytest.fixture
def steady_root(gqa_root):  # noqa: F811
    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 12.0

    _rewrite(os.path.join(gqa_root, "grid", "traffic", "code-steady.json"),
             mix)
    return gqa_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, steady_root, trace):
    from grid.drivers import serve_mixed_gqa

    monkeypatch.setattr(serve_mixed_gqa, "LONG_CONTEXT", 8)
    rc, last, notes = _run(monkeypatch, capsys, steady_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, steady_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    # off the chip the flag's "auto" keeps the gather, which the check
    # reports; nothing else may be wrong
    assert all("paged kernel is not armed" in p for ps in problems
               for p in ps), problems
    got = {k: v["value"] for k, v in last["metrics"].items()}
    if not trace:
        assert set(got) == {"tpot_p50_ms", "setup_s"}
        return
    assert set(NEW) | {"queue_wait_ms_p50", "ttft_p95_ms.steady",
                       "tpot_p95_ms", "prefill_ms_mean"} <= set(got)
    assert not {"serve_tokens_per_s", "slot_occupancy_mean"} & set(got)
    # the engine's clock and the harness's time the same requests: the
    # harness reads after step() returns, a little later each time
    assert 0.0 < got["prefill_stall_ms_per_token_p50"] \
        <= got["prefill_stall_ms_per_token_p95"] < got["tpot_engine_p50_ms"]
    assert got["longest_handover_gap_ms_p50"] >= got["tpot_engine_p50_ms"]
    assert got["admission_ms_mean"] >= got["prefill_ms_mean"]

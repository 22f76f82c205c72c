"""``grid/readers/spans.py`` on a hand-built trace where every answer can be
worked out on paper, on a profile captured here, and the cell that prints
its metrics (``gpt2s-chat-steady``)."""

import collections
import os

import pytest

from grid import generate, manifest, reduce
from grid.readers import spans
from grid.reduce import Op, Trace

WIN = (0.0, 10.0)
# two cycles: the first admits one request, the second only decodes
SPANS = sorted([
    ("serving/step", 1.0, 5.0),
    ("serving/expire", 1.0, 1.1),
    ("serving/admit", 1.1, 2.6),
    ("serving/prefill", 1.2, 2.5),
    ("serving/prefill.launch", 1.2, 1.5),
    ("serving/prefill.sync", 1.5, 2.4),
    ("serving/decode", 2.7, 4.5),
    ("serving/decode.launch", 2.7, 3.0),
    ("serving/decode.sync", 3.0, 4.5),
    ("serving/retire", 4.6, 4.9),
    ("serving/step", 6.0, 9.0),
    ("serving/expire", 6.0, 6.1),
    ("serving/admit", 6.1, 6.2),
    ("serving/decode", 6.3, 8.5),
    ("serving/decode.launch", 6.3, 6.5),
    ("serving/decode.sync", 6.5, 8.5),
    ("serving/retire", 8.6, 8.9),
], key=lambda s: s[1])
BUSY = [(1.4, 2.3), (2.9, 4.4), (6.45, 8.4)]     # idle: 5.65 s of 10


def _trace(*chips):
    ops = {c: [Op("fusion.%d" % i, "jit_chunk", s, e, "fusion", "", "fusion")
               for i, (s, e) in enumerate(busy)]
           for c, busy in enumerate(chips)}
    return Trace(ops, {}, [("grid/engine.step", 0.5, 9.5)])


def test_innermost_span_wins_and_the_idle_split_sums_to_the_idle_seconds():
    trace = _trace(BUSY)
    idle = spans.idle_by_span(trace, SPANS, WIN)
    assert idle == pytest.approx({
        "serving/prefill.launch": 0.2, "serving/decode.launch": 0.35,
        "serving/prefill.sync": 0.1, "serving/decode.sync": 0.2,
        "serving/expire": 0.2, "serving/admit": 0.3, "serving/prefill": 0.1,
        "serving/retire": 0.6, "serving/step": 0.6, spans.NO_SPAN: 3.0})
    # the same split reduce.py makes for the grid's own spans
    same = reduce.idle_gaps_by_span(trace._replace(spans=SPANS), WIN)
    assert idle == pytest.approx(same)
    split = spans.idle_split(idle)
    assert split == pytest.approx({"launch": 0.55, "sync": 0.3,
                                   "bookkeeping": 1.8, "outside": 3.0})
    assert sum(split.values()) == pytest.approx(
        reduce.idle_share(trace, WIN) * (WIN[1] - WIN[0]))
    got = spans.serve_metrics(trace, SPANS, WIN)
    assert got["steps"] == 2 and "problem" not in got
    assert got["idle_launch_ms_per_step"] == pytest.approx(275.0)
    assert got["idle_sync_ms_per_step"] == pytest.approx(150.0)
    assert got["idle_bookkeeping_ms_per_step"] == pytest.approx(900.0)
    assert got["idle_outside_step_ms_per_step"] == pytest.approx(1500.0)
    four = sum(v for k, v in got.items() if k.startswith("idle_"))
    assert four * got["steps"] / 1e3 == pytest.approx(5.65)


def test_self_time_is_duration_minus_children_and_the_step_metrics():
    assert spans.self_seconds(SPANS, "serving/admit") == pytest.approx(0.3)
    assert spans.self_seconds(SPANS, "serving/prefill") == pytest.approx(0.1)
    assert spans.self_seconds(SPANS, "serving/retire") == pytest.approx(0.6)
    got = spans.serve_metrics(_trace(BUSY), SPANS, WIN)
    assert got["engine_self_ms_per_step"] == pytest.approx(850.0)
    assert got["scheduler_ms_per_step"] == pytest.approx(250.0)
    assert got["retire_ms_per_step"] == pytest.approx(300.0)
    assert got["decode_launch_ms_mean"] == pytest.approx(250.0)
    assert got["prefills_per_step"] == 0.5
    rows = spans.table(_trace(BUSY), SPANS, WIN)
    assert rows["serving/admit"] == pytest.approx(
        {"n": 2, "s": 1.6, "self_s": 0.3, "idle_s": 0.3})
    # a span cut by the window's edge is not counted, nor are its children
    late = spans.serve_metrics(_trace(BUSY), SPANS, (0.0, 8.0))
    assert late["steps"] == 1 and late["prefills_per_step"] == 1.0


def test_idle_metrics_are_left_out_where_they_do_not_add_up():
    """Idle instants are the first chip's, the stretch's idle share is the
    chips' mean: where the two part by over 2% the four say nothing."""
    got = spans.serve_metrics(_trace(BUSY, [(0.0, 10.0)]), SPANS, WIN)
    assert "over 2% apart" in got["problem"]
    assert not [k for k in got if k.startswith("idle_")]
    assert got["retire_ms_per_step"] == pytest.approx(300.0)


def test_a_trace_without_program_spans_gives_nothing_and_raises_nothing(
        monkeypatch, tmp_path):
    trace = _trace(BUSY)
    assert spans.serve_metrics(trace, [], WIN) is None
    assert spans.serve_metrics(trace, [("executor/run", 1.0, 2.0)],
                               WIN) is None
    record = {"trace_window": WIN}
    cell = manifest.Cell("gpt2s-chat-steady")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))   # no grid_out
    assert spans.newest_xplane() is None
    for name in cell.reported(True):
        spec = cell.metrics[name]
        if spec["reader"].startswith("spans."):
            reader = manifest.reader(spec["reader"])
            assert reader(record, trace) is None
            assert reader(record, None) is None


def test_a_captured_profile_gives_the_program_spans_on_the_traces_clock(
        tmp_path):
    import jax

    root = tmp_path / "grid_out" / "cell" / "trace"
    with jax.profiler.trace(str(root)):
        with jax.profiler.TraceAnnotation("grid/engine.step"):
            with jax.profiler.TraceAnnotation("serving/step", cycle=3):
                with jax.profiler.TraceAnnotation("serving/decode"):
                    jax.numpy.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("other/span"):
            pass
    path = spans.newest_xplane(str(tmp_path))
    assert path == reduce.find_xplane(str(root))
    got = spans.load(path)
    # the plain names (the annotation's arguments are the event's stats)
    assert [s[0] for s in got] == ["serving/step", "serving/decode"]
    (grid_span,) = reduce.load(path).spans
    assert grid_span[1] <= got[0][1] <= got[1][1] \
        and got[1][2] <= got[0][2] <= grid_span[2]


def test_the_cell_loads_and_offers_the_same_lengths_for_every_seed():
    cell = manifest.Cell("gpt2s-chat-steady")
    assert cell.kind == "serve" and cell.chips == 1
    assert cell.reported(False) == ["tpot_p50_ms", "setup_s"]
    traced = cell.reported(True)
    assert len(traced) == 19 and "slot_occupancy_mean" not in traced
    bench = manifest.benchmark()
    for name in traced:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["moves"] == "tpot_p50_ms" and cell.name in \
            entry["workloads"], name
        assert callable(manifest.reader(cell.metrics[name]["reader"]))
    new = [n for n in traced
           if cell.metrics[n]["reader"].startswith("spans.")]
    assert len(new) == 9 and all(
        cell.metrics[n]["source"] == "program_span" for n in new)
    assert cell.traffic["arrivals"]["rate_per_s"] == 20.127

    def offered(seed):
        plan = generate.serve_plan(cell.traffic, 50257, seed, 40.0, 4.0)
        main = [p for p in plan if p.due_s < 45.0]
        return ([p.due_s for p in plan],
                collections.Counter(len(p.prompt) for p in main),
                collections.Counter(p.max_new_tokens for p in main),
                [p.prompt[:4] for p in main[:3]])

    a, b = offered(101), offered(2147483747)
    assert a[:3] == b[:3] and a[3] != b[3]
    in_window = sum(1 for t in a[0] if 5.0 <= t < 45.0)
    assert in_window == 785 and str(in_window) in cell.cell["why"]
    assert 32 <= min(a[1]) and max(a[1]) <= 256
    assert 64 <= min(a[2]) and max(a[2]) <= 192

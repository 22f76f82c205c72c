"""``grid/sweep.py`` on the CPU at toy widths: it finds the driver of every
serve kind as ``grid/run.py`` does and takes that driver's ``plan`` where it
has one; a backlog window whose server was not full raises the rate, and at
the queue's ceiling the sweep fails instead of printing a capacity. A CPU
run proves control flow and the lines' form only."""

import functools
import json
import os

import pytest

from grid import generate, manifest, runtime, sweep
from grid.tests.conftest import _rewrite
from grid.tests.test_serve_mixed_gqa import gqa_root  # noqa: F401
from grid.tests.test_serve_mla import mla_root  # noqa: F401
from grid.tests.test_serve_moe import moe_root  # noqa: F401

# kind -> (a cell of that kind, the fixture that cuts it to toy widths,
#          the module whose plan the sweep has to take)
KINDS = {
    "serve": ("gpt2s-doc-steady", "toy_root", generate, "serve_plan"),
    "serve_moe": ("smallthinker-mixed-sat", "moe_root", None, "plan"),
    "serve_mla": ("kimi-k2-longctx-sat", "mla_root", None, "plan"),
    "serve_mixed_gqa": ("laguna-s-code-sat", "gqa_root", None, "plan"),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_sweep_goes_through_the_driver_of_the_kind(kind, request,
                                                   monkeypatch, capsys):
    cell_name, fixture, owner, fn = KINDS[kind]
    root = request.getfixturevalue(fixture)
    bench = manifest.benchmark(root)
    entry = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    # a queue with room for an offer far above a toy engine on the CPU
    _rewrite(os.path.join(root, config["file"]),
             lambda doc: doc["engine"].update(max_queue=4096))
    monkeypatch.setattr(manifest, "ROOT", root)
    monkeypatch.setattr(runtime, "require_chips", lambda chips: {})
    monkeypatch.setattr(sweep, "BACKLOG_RATE", 400.0)
    driver = manifest.driver(kind)
    assert manifest.Cell(cell_name, root).kind == kind
    owner = owner or driver
    planned = []
    real = getattr(owner, fn)

    @functools.wraps(real)
    def recording(traffic, *a, **kw):
        planned.append(traffic["arrivals"]["rate_per_s"])
        return real(traffic, *a, **kw)

    monkeypatch.setattr(owner, fn, recording)
    rc = sweep.main(["--workload", cell_name, "--seed", "2147483747",
                     "--seconds", "1", "--fractions", "0.5"])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0 and [r.get("window") for r in rows] \
        == ["backlog", None, "0.50 of capacity"]
    full, found, half = rows
    assert sweep.standing(full) and full["refused"] == 0
    assert {"slot_occupancy_mean", "queue_depth_at_open",
            "queue_depth_at_close", "queue_empty_cycle_share",
            "serve_tokens_per_s", "tpot_p50_ms"} <= set(full)
    assert found["driver"] == "grid.drivers." + kind
    assert found["capacity_requests_per_s"] == pytest.approx(
        full["serve_tokens_per_s"] / found["mean_output_tokens"])
    assert half["rate_per_s"] == pytest.approx(
        0.5 * found["capacity_requests_per_s"], abs=1e-3)
    # every window's plan was the driver's own, where it has one (with the
    # order of lengths it owns); serve_moe.plan draws through serve_plan
    # with the same traffic, so each window shows once or twice
    assert found["plan"].endswith("." + fn)
    assert sorted(set(planned)) == sorted({full["rate_per_s"],
                                           half["rate_per_s"]})


def _line(rate, empty_share, at_open, at_close, served, refused=0):
    return {"rate_per_s": rate, "queue_empty_cycle_share": empty_share,
            "queue_depth_at_open": at_open, "queue_depth_at_close": at_close,
            "refused": refused, "serve_tokens_per_s": served * 100.0}


def test_a_window_that_was_not_full_raises_the_rate():
    """The first offer is what an idle server's queue has room for over 45
    s (20.48/s, under ``BACKLOG_RATE``) and is all served (the queue is
    empty after most cycles), 40.96/s still is (a queue that does not
    grow), 61.44/s leaves a backlog: the capacity is the last window's, and
    each rate is the lower of twice the one before and what was served
    plus the queue's room."""
    offered = []

    def window(rate, label):
        offered.append(rate)
        assert label == "backlog"
        if rate <= 30.0:
            return _line(rate, 0.8, 0, 0, rate)
        if rate < 60.0:
            return _line(rate, 0.02, 3, 3, rate)
        return _line(rate, 0.0, 40, 300, 64.0)

    capacity, line = sweep.find_capacity(window, 100.0, 1024, 45.0)
    room = sweep.QUEUE_ROOM * 1024 / 45.0
    assert sweep.BACKLOG_RATE > room
    assert offered == [round(room, 3), round(2 * room, 3),
                       round(3 * room, 3)]
    assert capacity == 64.0 and line["queue_depth_at_close"] == 300


def test_the_first_rate_keeps_inside_the_queue():
    def window(rate, label):
        return _line(rate, 0.0, 10, 200, 1.3)

    capacity, line = sweep.find_capacity(window, 100.0, 1024, 55.0)
    assert line["rate_per_s"] == round(sweep.QUEUE_ROOM * 1024 / 55.0, 3)
    assert capacity == 1.3


@pytest.mark.parametrize("line", [
    # a queue that empties now and then under an offer three times what is
    # served, and no room in the queue for more
    dict(empty_share=0.2, at_open=5, at_close=400, served=6.0),
    # a request refused: the queue hit max_queue
    dict(empty_share=0.0, at_open=5, at_close=1024, served=6.0, refused=3),
    # a queue that did not grow
    dict(empty_share=0.0, at_open=50, at_close=50, served=6.0),
])
def test_at_the_queues_ceiling_it_fails_and_prints_no_capacity(line):
    def window(rate, label):
        return _line(rate, **line)

    with pytest.raises(sweep.NotFull, match="not full.*max_queue 1024"):
        sweep.find_capacity(window, 100.0, 1024, 45.0)


def test_main_fails_where_the_server_was_never_full(monkeypatch, capsys,
                                                    toy_root):
    """The toy's queue of 64 has no room for an offer its engine cannot
    serve: exit code 1, the message on standard error, no capacity line."""
    monkeypatch.setattr(manifest, "ROOT", toy_root)
    monkeypatch.setattr(runtime, "require_chips", lambda chips: {})
    monkeypatch.setattr(sweep, "standing", lambda out: False)
    rc = sweep.main(["--workload", "gpt2s-chat-sat", "--seconds", "0.6"])
    got = capsys.readouterr()
    rows = [json.loads(x) for x in got.out.splitlines()]
    assert rc == 1 and rows and all(r.get("window") == "backlog"
                                    for r in rows)
    assert "not full" in got.err and "no capacity to print" in got.err


def test_a_train_cell_has_no_rate_to_find(monkeypatch, toy_root):
    monkeypatch.setattr(manifest, "ROOT", toy_root)
    monkeypatch.setattr(runtime, "require_chips", lambda chips: {})
    with pytest.raises(SystemExit, match="tfbase-train-1chip"):
        sweep.main(["--workload", "tfbase-train-1chip"])

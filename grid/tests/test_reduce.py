"""``grid/reduce.py`` on a small recorded trace (two decode cycles of GPT-2
small on a TPU v5 lite, cut from PR 24's first probe on the chip and
stored reduced: ``data/serve_decode_2cycles.json.gz``), and on hand-made
intervals where the answer can be worked out on paper."""

import gzip
import os

import pytest

from grid import reduce
from grid.readers import device
from grid.reduce import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "serve_decode_2cycles.json.gz"),
                   "rt") as f:
        return Trace.from_json(f.read())


def test_recorded_trace_busy_union_and_idle_share(recorded):
    assert sorted(recorded.ops) == [0] and len(recorded.ops[0]) == 2460
    runs = recorded.modules[0]
    assert [m.name for m in runs] == ["jit_chunk", "jit_chunk"]
    assert [s[0] for s in recorded.spans] == ["grid/engine.step"] * 2
    win = (runs[0].start, runs[1].end)
    busy = reduce.busy_seconds(recorded, win)
    # 78.4 ms busy of 81.8: the gap is the host between two dispatches
    assert busy == pytest.approx(0.078403, abs=1e-5)
    assert reduce.idle_share(recorded, win) == pytest.approx(0.04104,
                                                             abs=1e-4)
    # the union is not the sum: nested and abutting events count once
    assert busy <= sum(o.end - o.start for o in recorded.ops[0])
    assert busy <= sum(m.end - m.start for m in runs) + 1e-9
    assert reduce.module_runs(recorded, "jit_chunk", win) \
        == [(m.start, m.end) for m in runs]
    assert reduce.module_runs(recorded, "jit_step", win) == []
    # cut() is how this file's data was taken from a longer trace
    first = recorded.cut(runs[0].start, runs[0].end)
    assert len(first.modules[0]) == 1 and 0 < len(first.ops[0]) < 2460


def test_recorded_trace_names_and_shares(recorded):
    runs = recorded.modules[0]
    win = (runs[0].start, runs[1].end)
    top = reduce.breakdown(recorded, win)
    assert top["device_ops"][0][0] == "jit_chunk:copy_bf16[12,32768,12,64]"
    assert top["device_ops"][0][1] == pytest.approx(0.02833, abs=1e-5)
    assert len(top["device_ops"]) == 10
    assert top["idle_gaps"][0][0] == "grid/engine.step"
    record = {"trace_window": win, "pool_rows": 32768}
    # copies and slices over the whole KV pool: 78% of a decode step
    assert device.pool_copy_time_share(record, recorded) \
        == pytest.approx(78.35, abs=0.01)
    # the one Pallas kernel of the decode executable: 24 calls, 5.1 ms
    pallas = [o for o in recorded.ops[0] if device.PALLAS in o.text]
    assert len(pallas) == 24 and {o.opcode for o in pallas} == {"custom-call"}
    assert device.pallas_time_share(record, recorded) \
        == pytest.approx(6.54, abs=0.01)


def test_hlo_text_is_parsed():
    text = ('%copy.112 = bf16[12,32768,12,64]{3,2,1,0:T(8,128)(2,1)} '
            'copy(bf16[12,32768,12,64]{1,3,2,0:T(8,128)(2,1)} %x)')
    assert reduce.parse_hlo(text) == ("copy.112", "copy",
                                      "bf16[12,32768,12,64]")
    tup = ('%sort.6 = (f32[32,50257]{1,0:T(8,128)S(1)}, s32[32,50257]{1,0}) '
           'sort(f32[32,50257]{1,0} %a, s32[32,50257]{1,0} %b)')
    assert reduce.parse_hlo(tup) == ("sort.6", "sort", "f32[32,50257]")
    assert reduce.parse_hlo("dot_general.1") == ("dot_general.1",
                                                 "dot_general", "")


def _ops(*rows):
    return [Op(name, "jit_step", s, e, opcode, "", name)
            for name, opcode, s, e in rows]


def test_interval_arithmetic():
    assert reduce.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) \
        == [(0, 3), (5, 7)]
    assert reduce.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) \
        == [(0, 1), (2, 4), (6, 9)]
    assert reduce.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert reduce.total([(0, 3), (5, 7)]) == 5


def test_exposed_collective_is_what_no_compute_hides():
    # chip 0: compute 0-4 and 6-10; all-reduce 3-7 (exposed 4-6 = 2 s)
    # chip 1: compute 0-10; all-reduce 3-7 wholly hidden
    chip0 = _ops(("fusion.1", "fusion", 0, 4),
                 ("all-reduce.1", "all-reduce", 3, 7),
                 ("fusion.2", "fusion", 6, 10))
    chip1 = _ops(("fusion.1", "fusion", 0, 10),
                 ("all-reduce-start.1", "all-reduce-start", 3, 7))
    trace = Trace({0: chip0, 1: chip1}, {}, [])
    assert reduce.exposed_collective_seconds(trace, (0, 10)) \
        == pytest.approx(1.0)          # (2 + 0) / 2 chips
    assert reduce.busy_seconds(trace, (0, 10)) == pytest.approx(10.0)
    assert reduce.idle_share(trace, (0, 20)) == pytest.approx(0.5)
    assert not reduce.is_collective(chip0[0])
    assert reduce.is_collective(chip1[1])


def test_idle_gaps_go_to_the_innermost_span():
    ops = {0: _ops(("fusion.1", "fusion", 0, 2), ("fusion.2", "fusion", 8, 10))}
    spans = [("grid/exe.run", 0.0, 9.0), ("grid/loss_fetch", 3.0, 5.0)]
    gaps = reduce.idle_gaps_by_span(Trace(ops, {}, spans), (0, 12))
    assert gaps == {"grid/loss_fetch": pytest.approx(2.0),
                    "grid/exe.run": pytest.approx(4.0),
                    "(no span)": pytest.approx(2.0)}

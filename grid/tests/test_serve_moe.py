"""The rehearsal of the sparse decoder's cell: ``grid.run.main`` through
``drivers/serve_moe.py`` end to end on the CPU at toy widths (device check
stubbed here, as in ``test_drivers.py``), traced and untraced, and the
arithmetic of ``flops_moe.py`` and ``readers/moe.py`` on hand-made records.
A CPU run proves control flow, counts and the last line's form only."""

import os

import pytest

from grid import flops_moe, manifest, reduce
from grid.readers import moe
from grid.tests.conftest import _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "smallthinker-mixed-sat"
TOY = dict(hidden_size=32, head_dim=8, num_attention_heads=4,
           num_key_value_heads=2, num_hidden_layers=4, vocab_size=97,
           moe_num_primary_experts=8, moe_num_active_primary_experts=3,
           moe_ffn_hidden_size=16, sliding_window_size=16)


@pytest.fixture
def moe_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["model"] = dict(dtype="float32", max_seq=64)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64,
                             group_pages={"global": 20, "window": 8})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs",
                          "smallthinker-21b-a3b-serve.json"), config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "mixed-sat.json"), mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, moe_root, trace):
    rc, last, notes = _run(monkeypatch, capsys, moe_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, moe_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"experts_touched_per_layer_mean", "kv_pages_used_share.global",
            "kv_pages_used_share.window", "admit_blocked_on_pages_share",
            "slot_occupancy_mean"} <= got
    assert 3 <= last["metrics"]["experts_touched_per_layer_mean"]["value"] <= 8
    assert 0 < last["metrics"]["kv_pages_used_share.window"]["value"] <= 100
    assert not {"moe_time_share.serve", "moe_expert_stream_roofline",
                "gqa_paged_attn_roofline"} & got
    margins = [n for n in notes if "reference_margins" in n][0]
    assert max(m["context"] for m in margins["reference_margins"]) > 16


@pytest.mark.parametrize("tail_s", [0.0, 4.0])
def test_every_seed_offers_the_same_lengths_in_the_same_order(tail_s):
    """The traffic file owns the instants AND which arrival gets which
    length; ``--seed`` (large ones too) draws the token ids alone."""
    from grid.drivers import serve_moe

    traffic = manifest.Cell(CELL).traffic
    plans = [serve_moe.plan(traffic, 151936, seed, 40.0, tail_s)
             for seed in (7, 7, 2147494005, 3999999999)]
    shapes = [[(p.due_s, len(p.prompt), p.max_new_tokens) for p in plan]
              for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2] == shapes[3]
    assert plans[0] == plans[1] and plans[0] != plans[2] != plans[3]
    # the generator's own multiset of lengths, as the traffic file gives it
    from grid import generate

    theirs = generate.serve_plan(traffic, 2, 1, 40.0, tail_s)
    assert sorted(len(p.prompt) for p in theirs) == \
        sorted(n for _, n, _ in shapes[0])
    assert sorted(p.max_new_tokens for p in theirs) == \
        sorted(o for _, _, o in shapes[0])
    assert max(max(p.prompt) for p in plans[3]) > 100000


def test_the_bytes_the_rooflines_divide():
    m = dict(hidden_size=2560, moe_ffn_hidden_size=768, num_hidden_layers=12,
             sliding_window_layout=[0, 1, 1, 1] * 13, num_key_value_heads=4,
             head_dim=128)
    assert flops_moe.expert_weight_bytes(m) == 3 * 2560 * 768 * 2
    assert flops_moe.expert_stream_bytes(50 * 12, m) == 600 * 11796480
    # a slot at 6,000 positions: 3 global layers read 6,000 rows, 9 window
    # layers 4,096, of 2 x 4 x 128 x 2 bytes
    assert flops_moe.grouped_kv_bytes(6000, 4096, m) == \
        (3 * 6000 + 9 * 4096) * 2048


def _op(text, start, end, module="jit_chunk"):
    name, opcode, shape = reduce.parse_hlo(text)
    return reduce.Op(name, module, start, end, opcode, shape, text)


def test_the_trace_readers_on_a_hand_made_trace():
    """One decode step: 6 ms of grouped matmuls told by name, 1 ms of an
    op told by its scope, 2 ms of the paged kernel, 1 ms of something
    else; another executable's kernel is not the decode step's."""
    call = 'custom-call(%%a), custom_call_target="tpu_custom_call", ' \
           'metadata={op_name="%s"}'
    ops = [
        _op("%ragged-dot-none.1 = bf16[96,768]{1,0} " + call
            % "ragged-dot-none", 0.000, 0.006),
        _op('%fusion.7 = f32[16,2560]{1,0} fusion(%b), metadata={op_name='
            '"jit(chunk)/moe/experts/reduce_sum"}', 0.006, 0.007),
        _op("%paged_attention.3 = bf16[16,8,512]{2,1,0} " + call
            % "jit(chunk)/attn/window/paged_attention", 0.007, 0.009),
        _op("%sort.1 = f32[16,151936]{1,0} sort(%c)", 0.009, 0.010),
        _op("%paged_attention.9 = bf16[16,8,512]{2,1,0} " + call % "x",
            0.010, 0.011, module="jit_prefill"),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    model = dict(hidden_size=2560, moe_ffn_hidden_size=768,
                 moe_num_primary_experts=64, num_hidden_layers=12,
                 sliding_window_layout=[0, 1, 1, 1] * 3,
                 num_key_value_heads=4, head_dim=128)
    from grid.drivers.serve import Cycle
    from grid.drivers.serve_moe import Sample

    samples = [Sample(-1.0, {"global": 10, "window": 4}, 0, 0.0, 0, 0),
               Sample(0.5, {"global": 30, "window": 8}, 1, 600.0, 12, 40000)]
    record = {"trace_window": (0.0, 0.011), "model": model,
              "peaks": {"hbm_bytes_per_s": 819e9}, "samples": samples,
              "pools": {"global": 60, "window": 16},
              "marks": {"tail_open": 0.0, "tail_close": 1.0, "open": 0.0,
                        "close": 1.0},
              "cycles": [Cycle(0.0, 0.5, 16, 0, 60016, 16)]}
    assert moe.moe_time_share(record, trace) == pytest.approx(100 * 7 / 11)
    need = 600 * 11796480 / 819e9
    assert moe.moe_expert_stream_roofline(record, trace) == \
        pytest.approx(100 * need / 0.007)
    kv = (3 * 60000 + 9 * 40000) * 2048 / 819e9
    assert moe.gqa_paged_attn_roofline(record, trace) == \
        pytest.approx(100 * kv / 0.002)
    assert moe.experts_touched_per_layer_mean(record) == 50.0
    assert moe.kv_pages_used_share_global(record) == 50.0
    assert moe.kv_pages_used_share_window(record) == 50.0
    assert moe.admit_blocked_on_pages_share(record) == 100.0
    # nothing to read: nothing returned, never 0
    empty = reduce.Trace({0: [ops[3]]}, {0: []}, [])
    for reader in (moe.moe_time_share, moe.moe_expert_stream_roofline,
                   moe.gqa_paged_attn_roofline):
        assert reader(record, empty) is None
        assert reader({"trace_window": (0, 1), "model": {}}, trace) is None \
            or reader is moe.moe_time_share
    bare = {"marks": record["marks"]}
    for reader in (moe.experts_touched_per_layer_mean,
                   moe.kv_pages_used_share_global,
                   moe.admit_blocked_on_pages_share):
        assert reader(bare) is None

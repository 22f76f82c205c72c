"""The rehearsal of the cell whose decoder has query heads by layer type:
``grid.run.main`` through ``drivers/serve_mixed_gqa.py`` end to end on the
CPU at toy widths (device check stubbed here, as in ``test_drivers.py``),
traced and untraced, and the arithmetic of ``flops_laguna.py`` and
``readers/mixed_gqa.py`` on hand-made records. A CPU run proves control
flow, counts and the last line's form only."""

import json
import os

import pytest

from grid import flops_laguna, manifest, reduce
from grid.readers import mixed_gqa, moe
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "laguna-s-code-sat"
CONFIG = "laguna-s-ep2-serve"
FULL, SLIDING = "full_attention", "sliding_attention"
TOY = dict(hidden_size=32, num_key_value_heads=2, head_dim=8,
           intermediate_size=64, moe_intermediate_size=16,
           shared_expert_intermediate_size=16, num_hidden_layers=5,
           vocab_size=97, num_experts=4, num_experts_per_tok=3,
           experts_held=[0, 1, 2, 3], sliding_window=8)


@pytest.fixture
def gqa_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["num_experts"] = 8
        doc["num_attention_heads_per_layer"] = [4, 6, 6, 6] * 12
        doc["rope_parameters"][FULL]["original_max_position_embeddings"] = 32
        doc["model"] = dict(dtype="float32", max_seq=64)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64,
                             group_pages={"global": 32, "window": 4})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "code-sat.json"), mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, gqa_root, trace):
    # a toy context never passes the driver's 4,096: one past the toy
    # window stands in for the long request
    from grid.drivers import serve_mixed_gqa

    monkeypatch.setattr(serve_mixed_gqa, "LONG_CONTEXT", 8)
    rc, last, notes = _run(monkeypatch, capsys, gqa_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, gqa_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    # off the chip the flag's "auto" keeps the gather, which the check
    # reports; nothing else may be wrong
    assert all("paged kernel is not armed" in p for ps in problems
               for p in ps), problems
    margins = [n["reference_margins"] for n in notes
               if "reference_margins" in n][0]
    assert len(margins) == 2 and margins[0]["context"] > 8
    assert all(m["margin"] < 1e-3 for m in margins)   # float32 both sides
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer, the page-share readers of readers/moe.py
    # read the new record unchanged; the device's find no TPU plane to read
    assert {"half_share_experts_touched_per_layer_mean",
            "attn_rows_read_per_step.global", "attn_rows_read_per_step.window",
            "kv_pages_used_share.global", "kv_pages_used_share.window",
            "admit_blocked_on_pages_share", "slot_occupancy_mean",
            "decode_dispatch_ms_mean"} <= got
    value = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0 < value["half_share_experts_touched_per_layer_mean"] <= 4
    assert 0 < value["attn_rows_read_per_step.window"] <= 4 * 8
    assert value["attn_rows_read_per_step.window"] \
        <= value["attn_rows_read_per_step.global"] <= 4 * 64
    assert 0 < value["kv_pages_used_share.window"] <= 100
    assert not {"mixed_gqa_attn_roofline.global",
                "mixed_gqa_attn_roofline.window",
                "global_attn_time_share.serve",
                "routed_block_time_share.serve",
                "half_share_expert_stream_roofline"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"global": 32, "window": 4}
    assert built["q_per_kv"] == {"global": 2, "window": 3}
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["held_pairs_mean"] <= 4 * 3
    assert window["rows_read_window_mean"] <= window["rows_read_global_mean"]


def test_the_configuration_is_the_catalog_entry_cut_as_it_says():
    """Every number of the published config under its own key, but for the
    keys ``reduced`` names; no width among them; inside the floors."""
    cfg = manifest.Cell(CELL).config
    bench = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cfg["reduced"] == bench["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["source"] == bench["source"]
    published = dict(
        model_type="laguna", hidden_size=3072, intermediate_size=12288,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=1048576, attention_bias=False,
        rms_norm_eps=1e-06, num_experts_per_tok=10,
        moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[0],
        tie_word_embeddings=False, gating="per-head", sliding_window=512,
        moe_apply_router_weight_on_input=False,
        moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0)
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}
    assert cfg["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING] * 12
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["gating_types"] == ["per_head"] * 48
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    # the floors: the dense layer and a whole period (>= 4) after it, >= 8
    # routed experts, >= 1/8 of the vocabulary; the experts held are named
    n = cfg["num_hidden_layers"]
    assert n - len(cfg["mlp_only_layers"]) >= 4
    assert cfg["layer_types"][1:n] == [SLIDING, SLIDING, SLIDING, FULL]
    assert cfg["num_experts"] == len(cfg["experts_held"]) == 128
    assert cfg["experts_held"] == list(range(128))
    assert cfg["vocab_size"] * 2 == cfg["published"]["vocab_size"]
    assert "2 chips share each layer" in cfg["deployment"]
    for key in ("hidden_act", "router", "shared_expert", "qk_norm", "gate",
                "rope_pairing", "yarn"):
        assert key in cfg["assumed"], key
    # every slot's worst case of the traffic fits the global pool, and
    # every slot's whole ring the window pool
    e, t = cfg["engine"], manifest.Cell(CELL).traffic
    worst = t["prompt_len"]["hi"] + t["output_len"]["hi"]
    assert e["slots"] * -(-worst // e["page_size"]) <= \
        e["group_pages"]["global"]
    assert e["slots"] * cfg["sliding_window"] // e["page_size"] == \
        e["group_pages"]["window"]
    assert worst <= e["max_seq"] and max(t["prompt_buckets"]) \
        >= t["prompt_len"]["hi"]
    assert t["prompt_len"]["lo"] > cfg["sliding_window"]


def test_the_driver_builds_the_share_the_file_states():
    from grid.drivers import serve_mixed_gqa

    config = manifest.Cell(CELL).config
    cfg = serve_mixed_gqa.model_config(config)
    assert (cfg.n_expert, len(cfg.experts_held), cfg.top_k) == (256, 128, 10)
    assert cfg.n_head == (48, 72, 72, 72, 48) and cfg.n_kv_head == 8
    assert cfg.dense_layers == (0,) and cfg.vocab_size == 50176
    assert cfg.cache_groups == [("global", (0, 4), None),
                                ("window", (1, 2, 3), 512)]
    assert (len(cfg.rope[FULL][0]), len(cfg.rope[SLIDING][0])) == (32, 64)
    assert cfg.rope[FULL][1] == 1.4852030263919618
    assert cfg.routed_scale == 2.5
    with pytest.raises(ValueError, match="experts_held names 2"):
        serve_mixed_gqa.model_config(dict(config, experts_held=[0, 1]))
    # a config that states another layer than the one written is refused
    with pytest.raises(ValueError, match="written for"):
        serve_mixed_gqa.model_config(dict(config, gating="none"))


def test_every_seed_offers_the_same_lengths_in_the_same_order():
    """``plan`` is ``serve_moe``'s: the traffic file owns the instants AND
    which arrival gets which length; ``--seed`` draws the token ids, from
    the vocabulary slice."""
    from grid.drivers import serve_mixed_gqa

    traffic = manifest.Cell(CELL).traffic
    plans = [serve_mixed_gqa.plan(traffic, 50176, seed, 40.0, 4.0)
             for seed in (7, 7, 3999999999)]
    shapes = [[(p.due_s, len(p.prompt), p.max_new_tokens) for p in plan]
              for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert plans[0] == plans[1] and plans[0] != plans[2]
    assert max(max(p.prompt) for p in plans[2]) < 50176
    assert traffic["prompt_len"]["lo"] <= min(n for _, n, _ in shapes[0]) \
        and max(n for _, n, _ in shapes[0]) <= traffic["prompt_len"]["hi"]


def test_the_operations_and_bytes_the_rooflines_divide():
    m = manifest.Cell(CELL).config
    assert (flops_laguna.group_layers(m, "global"),
            flops_laguna.group_layers(m, "window")) == (2, 3)
    assert (flops_laguna.q_per_kv(m, "global"),
            flops_laguna.q_per_kv(m, "window")) == (6, 9)
    assert [flops_laguna.kernel_query_rows(g) for g in (1, 6, 7, 8, 9)] == [
        1, 8, 8, 8, 16]
    assert flops_laguna.kv_row_bytes(m) == 4096
    # 5,000 rows a full layer: 2 layers x K and V of 8 heads of 128 in
    # bf16; 48 heads x 128 lanes x (score + sum) x 2 a row a layer
    assert flops_laguna.attn_kv_bytes(5000, m, "global") == 5000 * 2 * 4096
    assert flops_laguna.attn_flops(5000, m, "global") == \
        5000 * 2 * 48 * 128 * 4
    assert flops_laguna.attn_kv_bytes(8192, m, "window") == 8192 * 3 * 4096
    assert flops_laguna.attn_flops(8192, m, "window") == \
        8192 * 3 * 72 * 128 * 4
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # 6 and 9 operations a byte against a ridge of 240: the bytes bound it
    assert flops_laguna.attn_need_s(5000, m, "global", peaks) == \
        pytest.approx(5000 * 2 * 4096 / 819e9)
    assert flops_laguna.attn_need_s(
        8192, m, "window", dict(peaks, bf16_flops_per_s=1e12)) == \
        pytest.approx(8192 * 3 * 72 * 128 * 4 / 1e12)
    assert flops_laguna.expert_weight_bytes(m) == 3 * 3072 * 1024 * 2 \
        == 18874368
    assert flops_laguna.held_expert_stream_bytes(240, m) == 240 * 18874368


def _op(text, start, end, module="jit_chunk"):
    name, opcode, shape = reduce.parse_hlo(text)
    return reduce.Op(name, module, start, end, opcode, shape, text)


def test_the_trace_readers_on_a_hand_made_trace():
    """One decode step: 3 ms of the paged kernel at the full layers' query
    shape (result [16, 8, 1024]) and 1 ms at the window layers' ([16, 16,
    1024]), told apart by shape; 4 ms of the share's loop (told by the held
    experts' weights it carries) with 3.5 ms of grouped matmuls inside it
    (nested, so counted once); 2 ms of something else; another
    executable's kernel is not the decode step's."""
    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    ops = [
        _op("%paged_attention.3 = bf16[16,8,1024]{2,1,0} " + call,
            0.000, 0.003),
        _op("%paged_attention.5 = bf16[16,16,1024]{2,1,0} " + call,
            0.003, 0.004),
        _op("%while.37 = (s32[], f32[16,3072]{1,0}, "
            "bf16[128,3072,1024]{2,1,0}) while(%t), condition=%c, body=%b",
            0.004, 0.008),
        _op("%ragged-dot-none.1 = bf16[160,1024]{1,0} " + call, 0.0042,
            0.0077),
        _op("%fusion.11 = bf16[16,50176]{1,0} fusion(%e)", 0.008, 0.010),
        _op("%paged_attention.9 = bf16[16,8,1024]{2,1,0} " + call,
            0.010, 0.011, module="jit_prefill"),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    from grid.drivers.serve_mixed_gqa import Sample

    samples = [
        Sample(-1.0, {"global": 100, "window": 500}, 0.0, 0.0, 0, 0.0,
               0.0, 0.0, 0),
        Sample(0.5, {"global": 300, "window": 512}, 1.0, 240.0, 4, 320.0,
               80000.0, 8192.0, 1)]
    record = {"trace_window": (0.0, 0.011),
              "model": manifest.Cell(CELL).config, "slots": 16,
              "q_per_kv": {"global": 6, "window": 9},
              "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              "samples": samples, "pools": {"global": 400, "window": 512},
              "marks": {"tail_open": 0.0, "tail_close": 1.0, "open": 0.0,
                        "close": 1.0}}
    assert mixed_gqa.mixed_gqa_attn_roofline_global(record, trace) == \
        pytest.approx(100 * (80000 * 2 * 4096 / 819e9) / 0.003)
    assert mixed_gqa.mixed_gqa_attn_roofline_window(record, trace) == \
        pytest.approx(100 * (8192 * 3 * 4096 / 819e9) / 0.001)
    assert mixed_gqa.global_attn_time_share(record, trace) == \
        pytest.approx(100 * 3 / 11)
    assert mixed_gqa.routed_block_time_share(record, trace) == \
        pytest.approx(100 * 4 / 11)
    assert mixed_gqa.half_share_expert_stream_roofline(record, trace) == \
        pytest.approx(100 * (240 * 18874368 / 819e9) / 0.004)
    assert mixed_gqa.half_share_experts_touched_per_layer_mean(record) == 60.0
    assert mixed_gqa.attn_rows_read_per_step_global(record) == 80000.0
    assert mixed_gqa.attn_rows_read_per_step_window(record) == 8192.0
    # the readers that were there read the new record unchanged
    assert moe.kv_pages_used_share_global(record) == 75.0
    assert moe.kv_pages_used_share_window(record) == 100.0
    assert moe.admit_blocked_on_pages_share(record) == 100.0
    # nothing to read: nothing returned, never 0 (the parent of this PR has
    # neither the model nor the counters; another model's record neither)
    empty = reduce.Trace({0: [ops[4]]}, {0: []}, [])
    traced = (mixed_gqa.mixed_gqa_attn_roofline_global,
              mixed_gqa.mixed_gqa_attn_roofline_window,
              mixed_gqa.global_attn_time_share,
              mixed_gqa.routed_block_time_share,
              mixed_gqa.half_share_expert_stream_roofline)
    for reader in traced:
        assert reader(record, empty) is None
        assert reader(record, None) is None
        assert reader({"trace_window": (0, 1), "model": {"n_layer": 12},
                       "marks": {}}, trace) is None
    bare = {"marks": record["marks"]}
    for reader in (mixed_gqa.half_share_experts_touched_per_layer_mean,
                   mixed_gqa.attn_rows_read_per_step_global,
                   mixed_gqa.attn_rows_read_per_step_window):
        assert reader(bare) is None


def test_the_benchmark_gained_entries_and_files_only():
    """The cell's name is appended to the ``workloads`` of the metrics it
    shares, and its own eight name it alone."""
    bench = manifest.benchmark()
    cell = manifest.Cell(CELL)
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in cell.cell["reports"]:
        if name == "setup_s":
            continue
        assert CELL in by_name[name]["workloads"], name
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(own) == sorted([
        "mixed_gqa_attn_roofline.global", "mixed_gqa_attn_roofline.window",
        "global_attn_time_share.serve", "routed_block_time_share.serve",
        "half_share_expert_stream_roofline",
        "half_share_experts_touched_per_layer_mean",
        "attn_rows_read_per_step.global", "attn_rows_read_per_step.window"])
    assert all(by_name[n]["moves"] == "tpot_p50_ms" for n in own)
    # appended after what was there (a later PR appends after these: no
    # count and no "last" is pinned here)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 7 and bench["configs"][4]["name"] == CONFIG
    every = [m["name"] for m in bench["per_layer"]]
    assert every[every.index(own[0]):][:8] == own
    with open(os.path.join(ROOT, "grid", "traffic", "code-sat.json")) as f:
        arrivals = json.load(f)["arrivals"]
    assert arrivals["order_seed"] is not None
    assert "1.25 x" in arrivals["rate_from"]

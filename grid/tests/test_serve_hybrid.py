"""The rehearsal of the hybrid decoder's cell: ``grid.run.main`` through
``drivers/serve_hybrid.py`` end to end on the CPU at toy widths (device
check stubbed here, as in ``test_drivers.py``), traced and untraced, and
the arithmetic of ``flops_hybrid.py`` and ``readers/hybrid.py`` at the
published sizes and on hand-made records. A CPU run proves control flow,
counts and the last line's form only."""

import os

import pytest

from grid import flops_hybrid, manifest, reduce
from grid.readers import hybrid
from grid.tests.conftest import _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "ling3-flash-reason-sat"
CONFIG = "ling-3-flash-ep4-serve"
TOY = dict(hidden_size=32, num_attention_heads=4, head_dim=8,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
           moe_shared_expert_intermediate_size=16, num_hidden_layers=4,
           vocab_size=97, num_experts=4, num_experts_per_tok=4, n_group=4,
           topk_group=2, experts_held=[0, 1, 2, 3],
           layer_types=["kda", "kda", "mla", "kda"],
           published_layer_indices=[1, 6, 11, 7], dense_layers_held=[0])
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def hybrid_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["num_experts"] = 16
        doc["model"] = dict(dtype="float32", max_seq=64,
                            selection_bias_std=0.1,
                            half_life_tokens=[2, 64])
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64,
                             group_pages={"latent": 32})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "reason-sat.json"),
             mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, hybrid_root, trace):
    from grid.drivers import serve_hybrid

    # the toy's longest context is 54; a sixteenth of it served is enough
    monkeypatch.setattr(serve_hybrid, "LONG_CONTEXT", 30)
    monkeypatch.setattr(serve_hybrid, "MIN_TOKENS", 34)
    rc, last, notes = _run(monkeypatch, capsys, hybrid_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, hybrid_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap"}
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"quarter_share_experts_touched_per_layer_mean",
            "state_slots_stepped_mean", "hybrid_latent_pages_used_share",
            "slot_occupancy_mean", "decode_dispatch_ms_mean"} <= got
    assert 0 < last["metrics"]["state_slots_stepped_mean"]["value"] <= 4
    assert 0 < last["metrics"][
        "quarter_share_experts_touched_per_layer_mean"]["value"] <= 4
    assert not {"kda_state_step_roofline", "kda_chunk_scan_roofline",
                "kda_time_share.serve", "hybrid_latent_attn_roofline",
                "quarter_share_expert_stream_roofline"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"latent": 32}
    # 3 KDA layers x 4 slots x (4 x 8 x 8 float32 + 3 x 96 float32)
    assert built["state_bytes"] == 3 * 4 * (256 + 288) * 4
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["state_slots_stepped_mean"] <= 4
    assert window["rows_read_latent_mean"] > 0


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
        hidden_size=2560, intermediate_size=6144, kv_lora_rank=512,
        q_lora_rank=None, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, head_dim=128, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, num_attention_heads=32,
        num_key_value_heads=32, num_experts_per_tok=8, n_group=8,
        topk_group=4, first_k_dense_replace=2, routed_scaling_factor=2.5,
        rope_theta=6000000, max_position_embeddings=131072,
        layer_group_size=6, short_conv_kernel_size=4, kda_lower_bound=-5,
        kda_safe_gate=True, score_function="sigmoid")
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["expert_swiglu_limit_list"]) == 42
    assert cfg["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                "vocab_size": 157184}
    # one leading dense layer, then a whole period (five KDA, one MLA) of
    # the layers that follow, as the published pattern places them
    assert cfg["layer_types"] == ["kda"] * 6 + ["mla"]
    assert cfg["published_layer_indices"] == [1, 6, 7, 8, 9, 10, 11]
    assert [(i + 1) % cfg["layer_group_size"] == 0
            for i in cfg["published_layer_indices"]] == [False] * 6 + [True]
    assert cfg["dense_layers_held"] == [0] and \
        cfg["published_layer_indices"][0] < cfg["first_k_dense_replace"]
    assert cfg["num_experts"] == len(cfg["experts_held"]) == 128
    assert cfg["experts_held"] == list(range(128))     # groups 0 and 1
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert "4 chips share each layer" in cfg["deployment"]
    for key in ("safe_gate", "no_rope_in_kda", "qk_norm", "output_gate",
                "rope_pairing", "state_precision", "text_only", "weights",
                "decay_seeding", "selection_bias"):
        assert cfg["assumed"][key]
    # every slot's worst case of the traffic fits the latent pool
    e, t = cfg["engine"], manifest.Cell(CELL).traffic
    worst = t["prompt_len"]["hi"] + t["output_len"]["hi"]
    assert e["slots"] * -(-worst // e["page_size"]) <= \
        e["group_pages"]["latent"]
    assert worst <= e["max_seq"] and max(t["prompt_buckets"]) \
        >= t["prompt_len"]["hi"]
    assert t["arrivals"]["order_seed"] == t["arrivals"]["schedule_seed"] \
        == 41041


def test_the_driver_builds_the_share_the_file_states():
    from grid.drivers import serve_hybrid

    config = manifest.Cell(CELL).config
    cfg = serve_hybrid.model_config(config)
    assert (cfg.n_expert, len(cfg.experts_held), cfg.top_k) == (512, 128, 8)
    assert (cfg.n_group, cfg.topk_group) == (8, 4)
    assert cfg.latent_row == (512, 64) and cfg.vocab_size == 39296
    assert cfg.slot_state == (32, 128, 128, 3, 3 * 4096)
    assert [g[0] for g in cfg.cache_groups] == ["latent", "state"]
    assert cfg.cache_groups[0][1] == (6,) and len(cfg.cache_groups[1][1]) == 6
    assert abs(cfg.sm_scale - 192 ** -0.5) < 1e-12
    with pytest.raises(ValueError, match="experts_held names 2"):
        serve_hybrid.model_config(dict(config, experts_held=[0, 1]))
    with pytest.raises(ValueError, match="written for"):
        serve_hybrid.model_config(dict(config, kda_safe_gate=False))


def test_the_operations_and_bytes_the_rooflines_divide():
    """The numbers of ISSUE 41 at the published sizes."""
    m = manifest.Cell(CELL).config
    assert flops_hybrid.layers_of(m, "kda") == 6
    assert flops_hybrid.layers_of(m, "mla") == 1
    assert flops_hybrid.state_values(m) == 32 * 128 * 128
    # 2 MB of float32 state a slot a layer, read and written, and 82 KB of
    # the step's vectors
    assert flops_hybrid.kda_step_bytes(m) == 4 * (2 * 524288 + 5 * 4096 + 32)
    # 64 slots x 6 layers: 1.6 GB a step, 2.0 ms at 819 GB/s
    need = flops_hybrid.kda_step_need_s(64, m, PEAKS)
    assert need == pytest.approx(64 * 6 * 4276352 / 819e9)
    assert 1.9e-3 < need < 2.1e-3
    # the scan: 57 KB a token a layer against 3.7 MFLOP: the bytes bound it
    assert flops_hybrid.kda_scan_bytes(1, 0, m) == 4096 * 14 + 128
    assert flops_hybrid.kda_scan_flops(1, m) == 7 * 524288
    assert flops_hybrid.kda_scan_need_s(8192, 1, m, PEAKS) == pytest.approx(
        6 * (8192 * 57472 + 2097152) / 819e9)
    assert flops_hybrid.kda_scan_need_s(
        8192, 1, m, dict(PEAKS, bf16_flops_per_s=1e12)) == pytest.approx(
        6 * 8192 * 7 * 524288 / 1e12)
    # ONE latent layer: 390k rows x 1,152 bytes, 0.45 GB
    assert flops_hybrid.latent_decode_need_s(390000, m, PEAKS) == \
        pytest.approx(390000 * 1152 / 819e9)


def _op(text, start, end, module="jit_chunk"):
    name, opcode, shape = reduce.parse_hlo(text)
    return reduce.Op(name, module, start, end, opcode, shape, text)


def test_the_trace_readers_on_a_hand_made_trace():
    """One decode step: 3 ms of the state kernel, 1 ms of the fused q, k,
    v product (told by its 12,288 columns), 0.7 ms of output projections
    (six sevenths are KDA's), 0.5 ms of the latent kernel, 2 ms of the
    share's loop, 1 ms of something else; and 10 ms of a prefill's scan."""
    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    ops = [
        _op("%kda_state_step.3 = (f32[64,32,128]{2,1,0}, "
            "f32[6,64,32,128,128]{4,3,2,1,0}) " + call, 0.000, 0.003),
        _op("%fusion.5 = bf16[64,12288]{1,0} fusion(bf16[64,2560]{1,0} %x, "
            "bf16[2560,12288]{1,0} %w)", 0.003, 0.004),
        _op("%fusion.6 = bf16[64,2560]{1,0} fusion(bf16[64,4096]{1,0} %x, "
            "bf16[4096,2560]{1,0} %w)", 0.004, 0.0047),
        _op("%mla_latent_decode.3 = bf16[64,32,512]{2,1,0} " + call,
            0.0047, 0.0052),
        _op("%while.37 = (s32[], f32[64,2560]{1,0}, "
            "bf16[128,2560,768]{2,1,0}) while(%t), condition=%c, body=%b",
            0.0052, 0.0072),
        _op("%fusion.11 = bf16[64,39296]{1,0} fusion(%e)", 0.0072, 0.0082),
        _op("%while.40 = (s32[], f32[32,128,128]{2,1,0}, "
            "bf16[128,64,32,128]{3,2,1,0}) while(%t), condition=%c, "
            "body=%b", 0.010, 0.020, module="jit_prefill"),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    from grid.drivers.serve_hybrid import Sample

    class Req:
        prompt_len, admitted_t = 4000, 0.5

    class Tracked:
        req, refused = Req, False

    samples = [Sample(-1.0, 100, 0.0, 0, 0.0, 0.0, 0, 0.0),
               Sample(0.5, 300, 480.0, 6, 768.0, 64.0, 1, 390000.0)]
    record = {"trace_window": (0.0, 0.020),
              "model": manifest.Cell(CELL).config, "slots": 64,
              "peaks": PEAKS, "samples": samples, "tracked": [Tracked],
              "pools": {"latent": 400},
              "marks": {"tail_open": 0.0, "tail_close": 1.0, "open": 0.0,
                        "close": 1.0}}
    busy = 0.0182
    assert hybrid.kda_state_step_roofline(record, trace) == pytest.approx(
        100 * (64 * 6 * 4276352 / 819e9) / 0.003)
    assert hybrid.kda_chunk_scan_roofline(record, trace) == pytest.approx(
        100 * (6 * (4000 * 57472 + 2097152) / 819e9) / 0.010)
    assert hybrid.kda_time_share(record, trace) == pytest.approx(
        100 * (0.004 + 0.0007 * 6 / 7) / busy)
    assert hybrid.hybrid_latent_attn_roofline(record, trace) == \
        pytest.approx(100 * (390000 * 1152 / 819e9) / 0.0005)
    assert hybrid.quarter_share_expert_stream_roofline(record, trace) == \
        pytest.approx(100 * (480 * 3 * 2560 * 768 * 2 / 819e9) / 0.002)
    assert hybrid.state_slots_stepped_mean(record) == 64.0
    # nothing to read: nothing returned, never 0 (the parent of this PR has
    # neither the kernels nor the counters; another model's record neither)
    empty = reduce.Trace({0: [ops[5]]}, {0: []}, [])
    for reader in (hybrid.kda_state_step_roofline,
                   hybrid.kda_chunk_scan_roofline, hybrid.kda_time_share,
                   hybrid.hybrid_latent_attn_roofline,
                   hybrid.quarter_share_expert_stream_roofline):
        assert reader(record, empty) is None
        assert reader(record, None) is None
        assert reader({"trace_window": (0, 1), "model": {"n_layer": 12},
                       "marks": {}}, trace) is None
    assert hybrid.state_slots_stepped_mean({"marks": record["marks"]}) is None


def test_the_benchmark_gained_entries_and_files_only():
    """The cell's name is appended to the ``workloads`` of the metrics it
    shares, and its own eight name it first. Written so that a later PR's
    cell, appended after this one, leaves it passing: this file is the
    benchmark's and that PR may not edit it."""
    bench = manifest.benchmark()
    cell = manifest.Cell(CELL)
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in cell.cell["reports"]:
        if name != "setup_s":
            assert CELL in by_name[name]["workloads"], name
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads", [None])[0] == CELL]
    assert sorted(own) == sorted([
        "kda_state_step_roofline", "kda_chunk_scan_roofline",
        "kda_time_share.serve", "hybrid_latent_attn_roofline",
        "quarter_share_expert_stream_roofline",
        "quarter_share_experts_touched_per_layer_mean",
        "state_slots_stepped_mean", "hybrid_latent_pages_used_share"])
    assert all(by_name[n]["moves"] == "tpot_p50_ms" for n in own)
    assert [w["chips"] for w in bench["workloads"] if w["name"] == CELL] \
        == [1]
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert "mla_paged_attn_roofline" not in cell.cell["reports"]

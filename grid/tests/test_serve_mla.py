"""The rehearsal of the latent-attention decoder's cell: ``grid.run.main``
through ``drivers/serve_mla.py`` end to end on the CPU at toy widths
(device check stubbed here, as in ``test_drivers.py``), traced and
untraced, and the arithmetic of ``flops_mla.py`` and ``readers/mla.py`` on
hand-made records. A CPU run proves control flow, counts and the last
line's form only."""

import json
import os

import pytest

from grid import flops_mla, manifest, reduce
from grid.readers import mla
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "kimi-k2-longctx-sat"
CONFIG = "kimi-k2-ep32-serve"
TOY = dict(hidden_size=32, num_attention_heads=4, q_lora_rank=16,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
           num_hidden_layers=3, vocab_size=97, n_routed_experts=4,
           num_experts_per_tok=4, experts_held=[0, 1, 2, 3])


@pytest.fixture
def mla_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["n_routed_experts"] = 16
        doc["rope_scaling"]["original_max_position_embeddings"] = 32
        doc["model"] = dict(dtype="float32", max_seq=64,
                            selection_bias_std=0.1)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64,
                             group_pages={"latent": 32})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "longctx-sat.json"),
             mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, mla_root, trace):
    rc, last, notes = _run(monkeypatch, capsys, mla_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, mla_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"held_experts_touched_per_layer_mean", "latent_pages_used_share",
            "slot_occupancy_mean", "decode_dispatch_ms_mean"} <= got
    assert 0 < last["metrics"]["held_experts_touched_per_layer_mean"][
        "value"] <= 4
    assert 0 < last["metrics"]["latent_pages_used_share"]["value"] <= 100
    assert not {"mla_paged_attn_roofline", "mla_attn_time_share.serve",
                "held_expert_stream_roofline",
                "sparse_block_time_share.serve"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"latent": 32}
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["held_pairs_mean"] <= 4 * 4


def test_the_configuration_is_the_catalog_entry_cut_as_it_says():
    """Every number of the published config under its own key, but for the
    keys ``reduced`` names; no width among them; inside the floors."""
    cfg = manifest.Cell(CELL).config
    bench = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cfg["reduced"] == bench["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["source"] == bench["source"]
    published = dict(
        hidden_size=7168, intermediate_size=18432, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_intermediate_size=2048, num_attention_heads=64,
        num_key_value_heads=64, num_experts_per_tok=8, n_shared_experts=1,
        first_k_dense_replace=1, routed_scaling_factor=2.827,
        rope_theta=50000, max_position_embeddings=131072, n_group=1,
        topk_group=1, scoring_func="sigmoid")
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"]["factor"] == 32
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 384,
                                "vocab_size": 163840}
    # the floors: the dense layer and >= 4 that follow, >= 8 routed experts,
    # >= 1/8 of the vocabulary; the experts held are named
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] == len(cfg["experts_held"]) >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert "32 chips share each layer" in cfg["deployment"]
    # every slot's worst case of the traffic fits the pool
    e, t = cfg["engine"], manifest.Cell(CELL).traffic
    worst = t["prompt_len"]["hi"] + t["output_len"]["hi"]
    assert e["slots"] * -(-worst // e["page_size"]) <= \
        e["group_pages"]["latent"]
    assert worst <= e["max_seq"] and max(t["prompt_buckets"]) \
        >= t["prompt_len"]["hi"]


def test_the_driver_builds_the_share_the_file_states():
    from grid.drivers import serve_mla

    cfg = serve_mla.model_config(manifest.Cell(CELL).config)
    assert (cfg.n_expert, len(cfg.experts_held), cfg.top_k) == (384, 12, 8)
    assert cfg.latent_row == (512, 64) and cfg.vocab_size == 20480
    assert abs(cfg.sm_scale - 0.1309) < 5e-5
    bad = dict(manifest.Cell(CELL).config, experts_held=[0, 1])
    with pytest.raises(ValueError, match="experts_held names 2"):
        serve_mla.model_config(bad)


def test_every_seed_offers_the_same_lengths_in_the_same_order():
    """``plan`` is ``serve_moe``'s: the traffic file owns the instants AND
    which arrival gets which length; ``--seed`` draws the token ids, from
    the vocabulary slice."""
    from grid.drivers import serve_mla

    traffic = manifest.Cell(CELL).traffic
    plans = [serve_mla.plan(traffic, 20480, seed, 40.0, 4.0)
             for seed in (7, 7, 3999999999)]
    shapes = [[(p.due_s, len(p.prompt), p.max_new_tokens) for p in plan]
              for plan in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert plans[0] == plans[1] and plans[0] != plans[2]
    assert max(max(p.prompt) for p in plans[2]) < 20480
    assert 1024 <= min(n for _, n, _ in shapes[0]) \
        and max(n for _, n, _ in shapes[0]) <= 8192


def test_the_operations_and_bytes_the_rooflines_divide():
    m = manifest.Cell(CELL).config
    assert flops_mla.latent_row_values(m) == 576
    # one slot at 4,000 rows, 7 layers: 576 values of 2 bytes a row; 64
    # heads x (576 + 512) multiply-adds a row
    assert flops_mla.mla_decode_bytes(4000, m) == 4000 * 7 * 1152
    assert flops_mla.mla_decode_flops(4000, m) == 4000 * 7 * 64 * 1088 * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # 121 operations a byte against a ridge of 240: the bytes bound it
    assert flops_mla.mla_decode_need_s(4000, m, peaks) == \
        pytest.approx(4000 * 7 * 1152 / 819e9)
    assert flops_mla.mla_decode_need_s(
        4000, m, dict(peaks, bf16_flops_per_s=50e12)) == \
        pytest.approx(4000 * 7 * 64 * 1088 * 2 / 50e12)
    assert flops_mla.held_expert_weight_bytes(m) == 3 * 7168 * 2048 * 2
    assert flops_mla.held_expert_stream_bytes(36, m) == 36 * 88080384


def _op(text, start, end, module="jit_chunk"):
    name, opcode, shape = reduce.parse_hlo(text)
    return reduce.Op(name, module, start, end, opcode, shape, text)


def test_the_trace_readers_on_a_hand_made_trace():
    """One decode step: 4 ms of the latent kernel, 3 ms of the share's loop
    (told by the held experts' weights it carries) with 2.5 ms of grouped
    matmuls inside it (told by name; nested, so counted once), 1 ms of the
    shared expert and 0.5 ms of the router told by their shapes, 1.5 ms of
    something else; another executable's kernel is not the decode step's."""
    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    ops = [
        _op("%mla_latent_decode.3 = bf16[32,64,512]{2,1,0} " + call,
            0.000, 0.004),
        _op("%while.37 = (s32[], f32[32,7168]{1,0}, "
            "bf16[12,7168,2048]{2,1,0}) while(%t), condition=%c, body=%b",
            0.004, 0.007),
        _op("%ragged-dot-none.1 = bf16[256,2048]{1,0} " + call, 0.0042,
            0.0067),
        _op("%fusion.7 = bf16[32,2048]{1,0} fusion(%b, %c)", 0.007, 0.008),
        _op("%fusion.9 = f32[32,384]{1,0} fusion(%b, %d)", 0.008, 0.0085),
        _op("%fusion.11 = bf16[32,20480]{1,0} fusion(%e)", 0.0085, 0.010),
        _op("%mla_latent_decode.9 = bf16[32,64,512]{2,1,0} " + call,
            0.010, 0.011, module="jit_prefill"),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    from grid.drivers.serve import Cycle
    from grid.drivers.serve_mla import Sample

    samples = [Sample(-1.0, 100, 0.0, 0, 0.0),
               Sample(0.5, 300, 36.0, 6, 48.0)]
    record = {"trace_window": (0.0, 0.011),
              "model": manifest.Cell(CELL).config, "slots": 32,
              "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              "samples": samples, "pools": {"latent": 400},
              "marks": {"tail_open": 0.0, "tail_close": 1.0, "open": 0.0,
                        "close": 1.0},
              "cycles": [Cycle(0.0, 0.5, 32, 0, 120032, 32)]}
    need = 120000 * 7 * 1152 / 819e9
    assert mla.mla_paged_attn_roofline(record, trace) == \
        pytest.approx(100 * need / 0.004)
    assert mla.mla_attn_time_share(record, trace) == \
        pytest.approx(100 * 4 / 11)
    stream = 36 * 88080384 / 819e9
    assert mla.held_expert_stream_roofline(record, trace) == \
        pytest.approx(100 * stream / 0.003)
    assert mla.sparse_block_time_share(record, trace) == \
        pytest.approx(100 * 4.5 / 11)
    assert mla.held_experts_touched_per_layer_mean(record) == 6.0
    assert mla.latent_pages_used_share(record) == 75.0
    # nothing to read: nothing returned, never 0 (the parent of this PR has
    # neither the kernel nor the counters; another model's record neither)
    empty = reduce.Trace({0: [ops[5]]}, {0: []}, [])
    for reader in (mla.mla_paged_attn_roofline, mla.mla_attn_time_share,
                   mla.held_expert_stream_roofline,
                   mla.sparse_block_time_share):
        assert reader(record, empty) is None
        assert reader(record, None) is None
        assert reader({"trace_window": (0, 1), "model": {"n_layer": 12},
                       "marks": {}}, trace) is None
    bare = {"marks": record["marks"]}
    for reader in (mla.held_experts_touched_per_layer_mean,
                   mla.latent_pages_used_share):
        assert reader(bare) is None


def test_the_benchmark_gained_entries_and_files_only():
    """The cell's name is appended to the ``workloads`` of the metrics it
    shares, and its own six name it alone."""
    bench = manifest.benchmark()
    cell = manifest.Cell(CELL)
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in cell.cell["reports"]:
        if name == "setup_s":
            continue
        assert by_name[name]["workloads"][-1] == CELL or \
            CELL in by_name[name]["workloads"], name
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(own) == sorted([
        "mla_paged_attn_roofline", "mla_attn_time_share.serve",
        "held_expert_stream_roofline", "held_experts_touched_per_layer_mean",
        "sparse_block_time_share.serve", "latent_pages_used_share"])
    assert all(by_name[n]["moves"] == "tpot_p50_ms" for n in own)
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert json.load(open(os.path.join(ROOT, "grid", "traffic",
                                       "longctx-sat.json")))["arrivals"][
        "order_seed"] is not None

"""The rehearsal of the sparse latent hybrid's cell: ``grid.run.main``
through ``drivers/serve_dsa.py`` end to end on the CPU at toy widths
(device check stubbed here, as in ``test_drivers.py``), traced and
untraced, and the arithmetic of ``flops_dsa.py`` at the published sizes. A
CPU run proves control flow, counts and the last line's form only."""

import os

import pytest

from grid import flops_dsa, manifest
from grid.tests.conftest import _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "glm53-flash-longdoc-sat"
CONFIG = "glm-5.3-flash-ep8-serve"
KDA, DSA = "linear_attention", "deepseek_sparse_attention"
TOY = dict(hidden_size=32, num_attention_heads=4, q_lora_rank=16,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_head_dim=8, v_head_dim=8,
           index_n_heads=2, index_head_dim=8, index_topk=16, index_kpool=4,
           intermediate_size=64, moe_intermediate_size=16,
           num_hidden_layers=3, vocab_size=97, n_routed_experts=4,
           num_experts_per_tok=4, experts_held=[0, 1, 2, 3],
           layer_types_held=[KDA, DSA, KDA],
           indexer_types_held=["full"] * 3, published_layer_indices=[2, 3, 4],
           dense_layers_held=[0],
           linear_attn_config=dict(num_heads=4, head_dim=8,
                                   gate_lower_bound=-5,
                                   short_conv_kernel_size=4))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def dsa_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["n_routed_experts"] = 16
        doc["model"].update(dtype="float32", max_seq=128, index_rope_dim=4,
                            kda_decay_rank=4, half_life_tokens=[2, 64])
        doc["engine"] = dict(slots=4, page_size=8, max_seq=128, max_queue=64,
                             group_pages={"latent_sparse": 64})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[32, 64], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "longdoc-sat.json"),
             mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, dsa_root, trace):
    from grid.drivers import serve_dsa

    # the toy's longest context is 90; 4 blocks of 4 rows are read of it
    monkeypatch.setattr(serve_dsa, "LONG_CONTEXT", 40)
    monkeypatch.setattr(serve_dsa, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_dsa, "MIN_PROBED", 8)
    rc, last, notes = _run(monkeypatch, capsys, dsa_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, dsa_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap",
                                     "selection_overlap", "selection_mass",
                                     "forced_gap", "stream_norm_gap",
                                     "row_gap", "key_gap"}
    # float32 on the CPU: what the cache keeps IS the reference's
    for name in ("stream_norm_gap", "row_gap", "key_gap"):
        assert 0 <= last["compared"][name][0] < 1e-4, name
    # float32 on the CPU: the served selection IS the reference's
    assert last["compared"]["selection_overlap"][0] == 1.0
    assert last["compared"]["selection_mass"][0] == pytest.approx(1.0)
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"eighth_share_experts_touched_per_layer_mean",
            "attn_rows_read_per_step.latent_sparse", "dsa_rows_kept_share",
            "index_blocks_scored_per_step", "dsa_latent_pages_used_share",
            "state_slots_stepped_mean", "slot_occupancy_mean",
            "decode_dispatch_ms_mean", "tpot_engine_p50_ms",
            "admission_ms_mean"} <= got
    kept = last["metrics"]["dsa_rows_kept_share"]["value"]
    assert 0 < kept < 100        # the selection bites
    assert 0 < last["metrics"]["attn_rows_read_per_step.latent_sparse"][
        "value"] <= 4 * 16
    assert not {"dsa_sparse_attn_roofline", "dsa_index_roofline",
                "dsa_time_share.serve", "glm_kda_state_step_roofline"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"latent_sparse": 64}
    # 64 pages x 2 blocks + 4 slots x 3 raw keys, 8 lanes, float32
    assert built["index_bytes"] == (64 * 2 + 4 * 3) * 8 * 4
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["rows_read_mean"] < window["rows_context_mean"]


def test_the_needs_at_the_published_sizes():
    model = manifest.Cell(CELL).config
    assert flops_dsa.layers_of(model, flops_dsa.KDA) == 4
    assert flops_dsa.layers_of(model, flops_dsa.DSA) == 1
    # a row is 1,024 B, and the bytes bound the sparse read (128 ops/byte)
    assert flops_dsa.sparse_read_need_s(819e9 / 1024, model, PEAKS) \
        == pytest.approx(1.0)
    # a pooled key is 256 B
    assert flops_dsa.index_score_need_s(819e9 / 256, model, PEAKS) \
        == pytest.approx(1.0)
    # a slot-layer's state: 64 x 128 x 128 float32 read and written
    assert flops_dsa.kda_step_bytes(model) == 4 * (
        2 * 64 * 128 * 128 + 5 * 64 * 128 + 64)
    assert flops_dsa.expert_stream_bytes(1, model) == 3 * 4096 * 2048 * 2


@pytest.mark.parametrize("name,fails", [
    ("newest", {"selection_overlap", "selection_mass"}),
    ("wrong_pool", {"selection_overlap"}),
    ("dense", set())])
def test_a_wrong_selection_fails_the_selections_own_limits(
        monkeypatch, capsys, dsa_root, name, fails):
    """``benchmarks/control_glm5_flash.py`` through the harness's own
    comparison at toy widths: the newest blocks in place of the best, or
    keys pooled over the wrong rows, read under the overlap's limit (and
    the first under the mass's); every closed block read is more blocks
    than ``index_topk`` allows."""
    import importlib.util

    from grid.drivers import serve_dsa
    from paddle_tpu.models import glm5_flash
    from paddle_tpu.ops import attention_ops

    spec = importlib.util.spec_from_file_location(
        "control_glm5_flash", os.path.join(manifest.ROOT, "benchmarks",
                                           "control_glm5_flash.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    monkeypatch.setattr(serve_dsa, "LONG_CONTEXT", 40)
    monkeypatch.setattr(serve_dsa, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_dsa, "MIN_PROBED", 8)
    # what a control replaces is put back after the test
    monkeypatch.setattr(serve_dsa, "model_config", serve_dsa.model_config)
    monkeypatch.setattr(attention_ops, "dsa_select", attention_ops.dsa_select)
    monkeypatch.setattr(glm5_flash, "_pooled_keys", glm5_flash._pooled_keys)
    monkeypatch.setattr(manifest, "ROOT", dsa_root)   # dense reads max_seq
    control.CONTROLS[name]()
    rc, last, notes = _run(monkeypatch, capsys, dsa_root, CELL, 0,
                           seconds="2.5")
    problems = [p for n in notes for p in n.get("problems", [])]
    assert not last["correct"] and problems
    below = {k for k in ("selection_overlap", "selection_mass")
             if last["compared"][k][0] < last["compared"][k][1]}
    assert fails <= below, (last["compared"], problems)
    if name == "dense":
        assert any("more than index_topk allows" in p for p in problems)

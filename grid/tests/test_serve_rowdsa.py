"""The rehearsal of the row-choosing latent decoder's cell: ``grid.run.main``
through ``drivers/serve_rowdsa.py`` end to end on the CPU at toy widths
(device check stubbed here, as in ``test_drivers.py``), traced and
untraced, the controls of ``benchmarks/control_deepseek_v32.py`` through
the harness's own comparison, and the arithmetic of ``flops_rowdsa.py`` at
the published sizes. A CPU run proves control flow, counts and the last
line's form only."""

import importlib.util
import os

import pytest

from grid import flops_rowdsa, manifest
from grid.tests.conftest import _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "dsv32-sparsedoc-sat"
CONFIG = "deepseek-v32-ep16-serve"
TOY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
           q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
           index_head_dim=8, index_topk=16, intermediate_size=64,
           moe_intermediate_size=16, num_hidden_layers=3, vocab_size=97,
           n_routed_experts=4, num_experts_per_tok=4, n_group=4,
           topk_group=2, experts_held=[0, 1, 2, 3],
           published_layer_indices=[2, 3, 4], dense_layers_held=[0],
           layer_types_held=["deepseek_sparse_attention"] * 3)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def rowdsa_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["n_routed_experts"] = 16
        doc["rope_scaling"]["original_max_position_embeddings"] = 32
        doc["model"].update(dtype="float32", max_seq=128)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=128, max_queue=64,
                             group_pages={"latent_sparse": 64})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[64], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "sparsedoc-sat.json"),
             mix)
    return toy_root


def _toy_limits(monkeypatch):
    from grid.drivers import serve_rowdsa

    # the toy's longest context is 90; 16 rows are read of it
    monkeypatch.setattr(serve_rowdsa, "LONG_CONTEXT", 40)
    monkeypatch.setattr(serve_rowdsa, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_rowdsa, "MIN_PROBED", 8)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, rowdsa_root, trace):
    _toy_limits(monkeypatch)
    rc, last, notes = _run(monkeypatch, capsys, rowdsa_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, rowdsa_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap",
                                     "selection_overlap", "selection_mass",
                                     "forced_gap", "row_gap", "key_gap"}
    # float32 on the CPU: what the cache keeps IS the reference's, and the
    # served selection IS the reference's
    for name in ("row_gap", "key_gap"):
        assert 0 <= last["compared"][name][0] < 1e-4, name
    assert last["compared"]["selection_overlap"][0] == 1.0
    assert last["compared"]["selection_mass"][0] == pytest.approx(1.0)
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer, the accepted ones under the field
    # names this driver samples; the device's find no TPU plane to read
    assert {"held_experts_touched_per_layer_mean",
            "attn_rows_read_per_step.latent_sparse", "dsa_rows_kept_share",
            "index_rows_scored_per_step", "dsa_latent_pages_used_share",
            "moe_groups_kept_with_held_share", "slot_occupancy_mean",
            "decode_dispatch_ms_mean", "tpot_engine_p50_ms",
            "admission_ms_mean"} <= got
    kept = last["metrics"]["dsa_rows_kept_share"]["value"]
    assert 0 < kept < 100        # the selection bites
    assert 0 < last["metrics"]["attn_rows_read_per_step.latent_sparse"][
        "value"] <= 4 * 16
    scored = last["metrics"]["index_rows_scored_per_step"]["value"]
    assert last["metrics"]["attn_rows_read_per_step.latent_sparse"][
        "value"] < scored <= 4 * 90
    # the toy holds group 0 of 4, of which the router keeps 2 a row
    assert 20 < last["metrics"]["moe_groups_kept_with_held_share"][
        "value"] < 90
    assert not {"rowdsa_sparse_attn_roofline", "rowdsa_index_roofline",
                "dsv32_step_mfu.serve", "dsa_time_share.serve"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"latent_sparse": 64}
    # 64 pages x 8 keys of 8 lanes, float32, three layers: no open block
    assert built["index_bytes"] == 3 * 64 * 8 * 8 * 4
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["rows_read_mean"] < window["rows_context_mean"]
    assert window["rows_scored_mean"] == pytest.approx(
        window["rows_context_mean"])


def test_the_needs_at_the_published_sizes():
    model = manifest.Cell(CELL).config
    # a row is 576 values: 1,152 B, and at 128 heads the operations bound
    # the sparse read by a hair (242 operations a byte, the ridge 240)
    ops = 128 * (576 + 512) * 2
    assert flops_rowdsa.sparse_read_need_s(197e12 / ops, model, PEAKS) \
        == pytest.approx(5.0)
    assert ops / 1152 > 197e12 / 819e9
    # a key is 256 B, in five layers
    assert flops_rowdsa.index_score_need_s(819e9 / 256, model, PEAKS) \
        == pytest.approx(5.0)
    # a decoded row with nothing read, scored or routed: the products
    layer = 2 * (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                 + 128 * 128 * 512 + 128 * 512 * 128 + 128 * 128 * 7168
                 + 1536 * 64 * 128 + 7168 * 128 + 7168 * 64)
    assert flops_rowdsa.layer_product_flops(model) == layer
    assert flops_rowdsa.step_flops(1, 0, 0, 0, model) == (
        5 * layer + 6 * 7168 * 18432 + 4 * (2 * 7168 * 256 + 6 * 7168 * 2048)
        + 2 * 7168 * 16160)
    assert flops_rowdsa.step_flops(0, 1, 0, 0, model) == 5 * ops
    assert flops_rowdsa.step_flops(0, 0, 0, 1, model) == 6 * 7168 * 2048


@pytest.mark.parametrize("name,fails", [
    ("newest_2048", {"selection_overlap", "selection_mass"}),
    ("dense", set())])
def test_a_wrong_selection_fails_the_selections_own_limits(
        monkeypatch, capsys, rowdsa_root, name, fails):
    """``benchmarks/control_deepseek_v32.py`` through the harness's own
    comparison at toy widths: the newest rows in place of the best read
    under the overlap's limit and the mass's; every row read is more rows
    than ``index_topk`` allows."""
    from grid.drivers import serve_rowdsa
    from paddle_tpu.ops import attention_ops

    spec = importlib.util.spec_from_file_location(
        "control_deepseek_v32", os.path.join(
            manifest.ROOT, "benchmarks", "control_deepseek_v32.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    _toy_limits(monkeypatch)
    # what a control replaces is put back after the test
    monkeypatch.setattr(serve_rowdsa, "model_config",
                        serve_rowdsa.model_config)
    monkeypatch.setattr(attention_ops, "dsa_select_rows",
                        attention_ops.dsa_select_rows)
    monkeypatch.setattr(manifest, "ROOT", rowdsa_root)  # dense reads max_seq
    control.CONTROLS[name]()
    rc, last, notes = _run(monkeypatch, capsys, rowdsa_root, CELL, 0,
                           seconds="2.5")
    problems = [p for n in notes for p in n.get("problems", [])]
    assert not last["correct"] and problems
    below = {k for k in ("selection_overlap", "selection_mass")
             if last["compared"][k][0] < last["compared"][k][1]}
    assert fails <= below, (last["compared"], problems)
    if name == "dense":
        assert any("more than index_topk allows" in p for p in problems)

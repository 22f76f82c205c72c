"""The rehearsal of the cell whose cache group COMPACTS: ``grid.run.main``
through ``drivers/serve_eva.py`` end to end on the CPU at toy widths (2
layers, 4 heads of 8, windows of 32 in chunks of 4, three prediction heads;
device check stubbed here, as in ``test_drivers.py``), traced and untraced;
the arithmetic of ``flops_eva.py`` at the published sizes against a hand
count; the readers on a recorded sample; the controls through the harness's
own comparison; and that the benchmark gained entries and files only. A CPU
run proves control flow, counts and the last line's form only."""

import importlib.util
import json
import os
import subprocess
from types import SimpleNamespace

import pytest

from grid import flops_eva, manifest, reduce
from grid.readers import eva as readers
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "evabyte-file-sat"
CONFIG = "evabyte-6.5b-serve"
TOY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
           vocab_size=40, intermediate_size=48, num_hidden_layers=2,
           window_size=32, chunk_size=4, num_pred_heads=3)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def eva_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["model"].update(dtype="float32", max_seq=160)
        doc["engine"] = dict(slots=4, page_size=4, max_seq=160,
                             max_queue=4096, group_pages={"eva": 64})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 100},
                   output_len={"dist": "uniform", "lo": 17, "hi": 40},
                   prompt_buckets=[32, 64, 128], preroll_s=0.3)
        # several times what the toy pool takes on a CPU: it stays full
        doc["arrivals"]["rate_per_s"] = 400.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "file-sat.json"), mix)
    return toy_root


def _toy_limits(monkeypatch):
    from grid.drivers import serve_eva

    monkeypatch.setattr(serve_eva, "MIN_TOKENS", 34)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, eva_root, trace):
    _toy_limits(monkeypatch)
    rc, last, notes = _run(monkeypatch, capsys, eva_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, eva_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap"}
    # float32 on the CPU: the served logits ARE the reference's, every
    # head's, across the window a compared request closed while decoding
    assert last["compared"]["logit_margin"][0] < 1e-4
    assert last["compared"]["mean_gap"][0] < 1e-5
    margins = [n for n in notes if "reference_margins" in n][0][
        "reference_margins"]
    assert len(margins) == 2
    assert any(m["closed_a_window"] for m in margins)
    assert all(len(m["head_gaps"]) == 3 and m["argmax_agree"] == 1.0
               for m in margins)
    assert margins[0]["context"] >= margins[1]["context"]
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"eva": 64}
    # 160 positions: 5 windows' 8 summaries and a window's 32 rows, and
    # the 2 pages where the open window's summaries wait
    assert built["pages_a_slot"] == (5 * 8 + 32) // 4 + 2
    # 2 layers of 64 pages x 4 rows x 32 lanes, K and V, float32
    assert built["cache_bytes"] == 2 * 256 * 32 * 2 * 4
    warm = [n for n in notes if n.get("phase") == "warm"][0]
    assert {"chunk[fuse=1]", "prefill[32]", "prefill[64]",
            "prefill[128]"} <= {row[0] for row in warm["executables"]}
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert window["windows_closed"] > 0 and window["chunks_closed"] > 0
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"attn_rows_read_per_step.eva_exact",
            "attn_rows_read_per_step.eva_summary", "eva_rows_kept_share",
            "eva_pages_used_share", "admit_blocked_on_pages_share",
            "slot_occupancy_mean", "decode_dispatch_ms_mean",
            "tpot_engine_p50_ms", "admission_ms_mean"} <= got
    exact = last["metrics"]["attn_rows_read_per_step.eva_exact"]["value"]
    pooled = last["metrics"]["attn_rows_read_per_step.eva_summary"]["value"]
    assert 0 < exact <= 4 * 32 and 0 < pooled <= 4 * 4 * 8
    kept = last["metrics"]["eva_rows_kept_share"]["value"]
    assert kept == pytest.approx(
        (exact + pooled) / window["rows_context_mean"], rel=1e-2)
    assert 0.2 < kept < 1.0
    assert not {"eva_weight_stream_roofline", "eva_paged_attn_roofline",
                "eva_attn_time_share.serve", "evabyte_step_mfu.serve",
                "eva_products_time_share.serve"} & got


def test_the_needs_at_the_published_sizes_against_a_hand_count():
    model = manifest.Cell(CELL).config
    assert flops_eva.layer_matmul_params(model) == \
        4 * 4096 ** 2 + 3 * 4096 * 11008
    # ISSUE 58: 202,391,552 parameters a layer, 404.8 MB in bfloat16
    assert flops_eva.layer_params(model) == 202391552
    assert flops_eva.head_params(model) == 4096 * 8 * 320
    assert flops_eva.weight_bytes_per_step(model) == \
        2 * (12 * 202391552 + 10485760)
    assert flops_eva.weight_bytes_per_step(model) / 1e9 \
        == pytest.approx(4.878, abs=0.001)
    assert flops_eva.weight_need_s(1, model, PEAKS) == pytest.approx(
        0.005956, abs=1e-5)
    # 16 KiB a row a layer; twelve slots of 1,550 rows: 3.66 GB, 4.5 ms
    assert flops_eva.kv_row_bytes(model) == 16384
    assert flops_eva.kv_need_s(12 * 1550, model, PEAKS) == pytest.approx(
        12 * 1550 * 12 * 16384 / 819e9)
    per_row = flops_eva.row_flops(model)
    assert per_row == 12 * 2 * 202375168
    pool = 12 * 6 * 4096
    assert flops_eva.step_flops(8, 8 * 1500, [], model) == \
        8 * (per_row + 2 * 4096 * 2560 + 16 * pool) \
        + 8 * 1500 * 12 * 4 * 4096
    # a prompt of two windows: each window's causal pairs, and the second
    # window's 2,048 queries against the first's 128 summaries
    assert flops_eva.prefill_pairs(4096, model) == \
        2 * 2048 * 2049 / 2 + 2048 * 128
    assert flops_eva.prefill_pairs(1000, model) == 1000 * 1001 / 2
    assert flops_eva.step_flops(0, 0, [4096], model) == \
        4096 * (per_row + pool) \
        + 12 * 4 * 4096 * flops_eva.prefill_pairs(4096, model) \
        + 2 * 4096 * 2560


def _op(module, name, opcode, text, start, end):
    return reduce.Op(name, module, start, end, opcode, "", text)


def test_the_readers_on_a_recorded_sample():
    """A hand-made trace of one decode step and one prefill: each reader
    finds its operations by the rule its docstring states (a custom call
    outside ``attn/eva/`` is no attention; the weights' stream is held
    against the whole decode executable), and a record without the samples or of another model
    reads nothing."""
    from grid.drivers.serve import Cycle
    from grid.drivers.serve_eva import Sample

    model = manifest.Cell(CELL).config
    pallas = 'custom_call_target="tpu_custom_call"'
    ops = [
        _op("jit_chunk", "fusion.7", "fusion", "%fusion.7 = ...", 0.000,
            0.006),
        _op("jit_chunk", "paged_attention.2", "custom-call",
            "%paged_attention.2 = bf16[12,32,128] custom-call(...), "
            + pallas, 0.006, 0.010),
        _op("jit_chunk", "fusion.8", "fusion", "%fusion.8 = ...", 0.010,
            0.011),
        _op("jit_chunk", "fusion.9", "fusion", "%fusion.9 = ...", 0.011,
            0.017),
        _op("jit_chunk", "other.3", "custom-call",
            "%other.3 = bf16[12,32,128] custom-call(...), " + pallas,
            0.017, 0.018),
        _op("jit_chunk", "fusion.11", "fusion", "%fusion.11 = ...", 0.018,
            0.019),
        _op("jit_prefill", "fusion.20", "fusion", "%fusion.20 = ...", 0.019,
            0.030),
        _op("jit_prefill", "fusion.21", "fusion", "%fusion.21 = ...", 0.030,
            0.040),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    samples = [Sample(0.0, {"eva": 100}, 0.0, 0, 0.0, 0.0, 0.0, 0.0, 0.0),
               Sample(0.5, {"eva": 120}, 0.0, 1, 9000.0, 3000.0, 60000.0,
                      1.0, 0.0)]
    req = SimpleNamespace(prompt_len=3000, admitted_t=0.25)
    record = {
        "samples": samples, "model": model, "peaks": PEAKS,
        "pools": {"eva": 2048},
        "trace_window": (0.0, 0.040), "prompt_buckets": [4096, 8192, 16384],
        "marks": {"tail_open": 0.1, "tail_close": 1.0, "open": 0.1,
                  "close": 1.0},
        "tracked": [SimpleNamespace(req=req, refused=False)],
        "cycles": [Cycle(0.2, 0.5, 8, 0, 60008, 9)],
        "scoped_ops": {
            "jit_chunk": {"attn/eva/": ["paged_attention.2"],
                          "attn/eva_pool/": ["fusion.8"],
                          "attn/eva_close/": [], "attn/eva_prefill/": [],
                          "attn/proj/": ["fusion.7"], "mlp/": ["fusion.9"],
                          "head/multibyte/": ["fusion.11"]},
            "jit_prefill": {"attn/eva_prefill/": ["fusion.21"],
                            "attn/eva_pool/": [], "attn/proj/": ["fusion.20"]}}}
    rows = 12000.0
    assert readers.eva_paged_attn_roofline(record, trace) == pytest.approx(
        100 * (rows * 12 * 16384 / 819e9) / 0.004)
    # against the WHOLE decode executable's 19 ms, not its products' 13
    assert readers.eva_weight_stream_roofline(record, trace) \
        == pytest.approx(100 * flops_eva.weight_need_s(1, model, PEAKS)
                         / 0.019)
    # the products' 6 + 6 + 1 ms of the decode executable's 19
    assert readers.eva_products_time_share(record, trace) \
        == pytest.approx(100 * 0.013 / 0.019)
    # the kernel's 4 ms, the pooling's 1 and the prefill attention's 10
    assert readers.eva_attn_time_share(record, trace) \
        == pytest.approx(100 * 0.015 / 0.040)
    # 9 tokens of which one the prefill's: 8 decoded rows
    assert readers.evabyte_step_mfu(record, trace) == pytest.approx(
        100 * flops_eva.step_flops(8, rows, [4096], model)
        / (0.040 * 197e12))
    assert readers.eva_exact_rows_per_step(record) == 9000.0
    assert readers.eva_summary_rows_per_step(record) == 3000.0
    assert readers.eva_rows_kept_share(record) == pytest.approx(0.2)
    assert readers.eva_pages_used_share(record) == pytest.approx(
        100 * 120 / 2048)
    every = (readers.eva_paged_attn_roofline,
             readers.eva_weight_stream_roofline,
             readers.eva_products_time_share,
             readers.eva_attn_time_share, readers.evabyte_step_mfu,
             readers.eva_exact_rows_per_step,
             readers.eva_summary_rows_per_step, readers.eva_rows_kept_share,
             readers.eva_pages_used_share)
    for other in (dict(record, model={"total_ut_steps": 4}),
                  {k: v for k, v in record.items() if k != "samples"}):
        for read in every:
            assert read(other, trace) is None
    for read in every[:5]:
        assert read(record, None) is None


def _control(monkeypatch, name):
    """``benchmarks/control_evabyte.py``'s control ``name`` applied; what
    it replaces is put back after the test."""
    from paddle_tpu.models import evabyte
    from paddle_tpu.serving.kv_cache import PagedKVCache

    spec = importlib.util.spec_from_file_location(
        "control_evabyte", os.path.join(manifest.ROOT, "benchmarks",
                                        "control_evabyte.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    for name_ in ("_group_len", "decode_attention"):
        monkeypatch.setattr(PagedKVCache, name_,
                            getattr(PagedKVCache, name_))
    for name_ in ("summarize", "_prefill_attention", "_attn_out", "_mlp"):
        monkeypatch.setattr(evabyte, name_, getattr(evabyte, name_))
    control.CONTROLS[name]()


@pytest.mark.parametrize("name", ["no_summaries", "uniform_pool", "no_mu",
                                  "bf16"])
def test_a_control_fails_the_comparison(monkeypatch, capsys, eva_root, name):
    """The controls through the harness's own comparison at toy widths, in
    float32: the run as stated reads a mean gap under 1e-5 (the test
    above), so whatever a control reads is the control's. The three
    structural ones read past limits that lie between the CHIP's readings
    at the published widths (PERF.md, PR 58). A precision below the stated
    one moves two toy layers' logits less than twelve layers': it reads a
    hundred times the stated run's mean gap here, under the chip's limit
    (there it reads 0.0090 of 0.006)."""
    _toy_limits(monkeypatch)
    _control(monkeypatch, name)
    rc, last, notes = _run(monkeypatch, capsys, eva_root, CELL, 0,
                           seconds="2.5")
    problems = [p for n in notes for p in n.get("problems", [])]
    if name == "bf16":
        assert last["compared"]["mean_gap"][0] > 1e-3, last["compared"]
        return
    assert not last["correct"] and problems, last["compared"]
    over = {k for k in ("logit_margin", "mean_gap")
            if last["compared"][k][0] > last["compared"][k][1]}
    assert over, last["compared"]


def test_the_parent_cannot_build_the_cell():
    """What the driver tries on the parent first: this PR's benchmark files
    over a program without ``paddle_tpu.models.evabyte`` must fail at once,
    in ``build``'s import, before anything is placed on the device."""
    import inspect

    from grid.drivers import serve_eva

    source = inspect.getsource(serve_eva.build)
    assert source.index("from paddle_tpu.models.evabyte import") \
        < source.index("ServingEngine(")


def test_the_benchmark_gained_entries_and_files_only():
    """Against the parent commit: no file under ``grid/`` that was there
    is edited, and ``BENCHMARK.json`` differs by one configuration, one
    cell, this cell's name at the END of ``workloads`` lists and nine new
    per-layer metrics at the end."""
    def git(*args):
        return subprocess.run(("git",) + args, cwd=ROOT, check=True,
                              capture_output=True, text=True).stdout

    try:
        parent = json.loads(git("show", "HEAD:BENCHMARK.json"))
        changed = git("status", "--porcelain", "--", "grid").splitlines()
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD already holds the cell: nothing to compare with")
    # untracked or added (and perhaps edited since it was staged): new
    assert [ln for ln in changed if ln[0] not in "?A"] == []
    now = manifest.benchmark()
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == parent[key]
    assert now["configs"][:-1] == parent["configs"]
    assert now["configs"][-1]["name"] == CONFIG
    assert now["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert now["workloads"][:-1] == parent["workloads"]
    assert now["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in now["workloads"]) == 1
    assert len(now["workloads"]) == 16
    for kind in ("end_to_end", "per_layer"):
        old = parent[kind]
        for was, is_ in zip(old, now[kind]):
            stripped = dict(is_)
            if is_.get("workloads", [None])[-1] == CELL:
                stripped["workloads"] = is_["workloads"][:-1]
            assert stripped == was, was["name"]
        added = now[kind][len(old):]
        assert all(m["workloads"] == [CELL] for m in added)
    assert len(now["end_to_end"]) == len(parent["end_to_end"])
    assert [m["name"] for m in now["per_layer"][len(parent["per_layer"]):]
            ] == ["attn_rows_read_per_step.eva_exact",
                  "attn_rows_read_per_step.eva_summary",
                  "eva_rows_kept_share", "eva_pages_used_share",
                  "eva_paged_attn_roofline", "eva_weight_stream_roofline",
                  "eva_attn_time_share.serve", "evabyte_step_mfu.serve",
                  "eva_products_time_share.serve"]
    cell = manifest.Cell(CELL)
    named = {m["name"] for m in now["end_to_end"] + now["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert set(cell.cell["reports"]) == named


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    """Every number of the published config under its own key, the depth
    alone reduced with the published 32 beside the 12, every assumed item
    stated."""
    doc = manifest.Cell(CELL).config
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 32}
    assert doc["kind"] == "serve_eva"
    published = dict(hidden_size=4096, num_attention_heads=32,
                     num_key_value_heads=32, intermediate_size=11008,
                     window_size=2048, chunk_size=16, num_pred_heads=8,
                     vocab_size=320, rms_norm_eps=1e-05, rope_theta=100000,
                     max_position_embeddings=32768, max_seq_length=32768,
                     init_std=0.01275, attention_class="eva",
                     fp32_skip_add=True, fp32_logits=True, mixedp_attn=True,
                     fp32_ln=False, norm_add_unit_offset=True,
                     attention_bias=False, rope_scaling=None,
                     num_chunks=None, init_cutoff_factor=None)
    assert {k: doc[k] for k in published} == published
    assert doc["num_hidden_layers"] == 12
    assert set(doc["assumed"]) >= {
        "rope_pairing", "phi_and_mu", "summaries_visible_after_close",
        "heads", "precision", "seeded_scales", "weights", "serving"}
    assert doc["engine"]["group_pages"] == {"eva": 2048}
    assert doc["engine"]["slots"] == 12
    assert "twelve consecutive layers" in doc["deployment"]

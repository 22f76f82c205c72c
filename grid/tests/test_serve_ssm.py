"""The rehearsal of the parallel hybrid's cell: ``grid.run.main`` through
``drivers/serve_ssm.py`` end to end on the CPU at toy widths that keep
every ratio (5 query heads a KV head, 2 groups, ``N = 2 P``; device check
stubbed here, as in ``test_drivers.py``), traced and untraced; the
arithmetic of ``flops_ssm.py`` at the published sizes against a hand
count; the readers on a recorded sample; the controls through the
harness's own comparison; and that the benchmark gained entries and files
only. A CPU run proves control flow, counts and the last line's form
only."""

import importlib.util
import json
import os
import subprocess
from types import SimpleNamespace

import pytest

from grid import flops_ssm, manifest, reduce
from grid.readers import ssm as readers
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "falcon-h1-chat-sat"
CONFIG = "falcon-h1-34b-serve"
TOY = dict(hidden_size=64, num_attention_heads=10, num_key_value_heads=2,
           head_dim=16, vocab_size=96, intermediate_size=128,
           mamba_n_heads=4, mamba_d_head=8, mamba_n_groups=2,
           mamba_d_state=16, mamba_d_ssm=32, num_hidden_layers=2)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def ssm_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["model"].update(dtype="float32", max_seq=128)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=128,
                             max_queue=4096, group_pages={"global": 64})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[32, 64], preroll_s=0.3)
        # several times what four toy slots take on a CPU (about 100/s
        # warm): the slots stay full, so a request is resident at the run's
        # end with steps behind it
        doc["arrivals"]["rate_per_s"] = 400.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "h1chat-sat.json"),
             mix)
    return toy_root


def _toy_limits(monkeypatch):
    from grid.drivers import serve_ssm

    monkeypatch.setattr(serve_ssm, "LONG_CONTEXT", 40)
    monkeypatch.setattr(serve_ssm, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_ssm, "MIN_STATE_STEPS", 4)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, ssm_root, trace):
    _toy_limits(monkeypatch)
    rc, last, notes = _run(monkeypatch, capsys, ssm_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, ssm_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap",
                                     "state_gap"}
    # float32 on the CPU: the served tokens ARE the reference's, and so
    # are the states a resident slot keeps
    assert last["compared"]["mean_gap"][0] < 1e-3
    assert 0 <= last["compared"]["state_gap"][0] < 1e-4
    margins = [n for n in notes if "reference_margins" in n][0][
        "reference_margins"]
    # the three branches each add a share of the residual the comparison
    # can see: none under a tenth of it, at the published multipliers
    for layer in margins[0]["branch_rms"]:
        ssm, attn, mlp, resid = layer
        assert min(ssm, attn, mlp) > 0.1 * resid, layer
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"ssd_state_slots_stepped_mean",
            "attn_rows_read_per_step.parallel_gqa",
            "kv_pages_used_share.global", "admit_blocked_on_pages_share",
            "slot_occupancy_mean", "decode_dispatch_ms_mean",
            "tpot_engine_p50_ms", "admission_ms_mean"} <= got
    assert 0 < last["metrics"]["ssd_state_slots_stepped_mean"]["value"] <= 4
    assert 0 < last["metrics"]["attn_rows_read_per_step.parallel_gqa"][
        "value"] <= 4 * 90
    assert not {"ssd_state_step_roofline", "ssd_chunk_scan_roofline",
                "parallel_gqa_attn_roofline",
                "falcon_h1_step_mfu.serve"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"global": 64}
    # 2 layers x 4 slots x (4 x 16 x 8 state + 3 x 96 tail) float32
    assert built["state_bytes"] == 2 * 4 * (4 * 16 * 8 + 3 * 96) * 4
    warm = [n for n in notes if n.get("phase") == "warm"][0]
    # two buckets' executables, two layers each, on the CPU: blocked (the
    # counter is the process's: what ran before stays counted)
    calls = warm["ssd_scan_calls"]
    assert calls["kernel"] == 0 and calls["blocked"] >= 4


def test_the_needs_at_the_published_sizes_against_a_hand_count():
    model = manifest.Cell(CELL).config
    assert flops_ssm.state_values(model) == 32 * 256 * 128 == 1 << 20
    assert flops_ssm.ssd_step_bytes(model) == 2 * 4194304
    # 64 slots x 5 layers x 8 MiB: 2.68 GB a step
    assert flops_ssm.ssd_step_need_s(64, model, PEAKS) == pytest.approx(
        64 * 5 * 8388608 / 819e9)
    assert flops_ssm.kv_row_bytes(model) == 2048
    assert flops_ssm.gqa_decode_need_s(819e9 / (5 * 2048), model, PEAKS) \
        == pytest.approx(1.0)
    # ISSUE 51's table: in_proj 47,349,760 + out_proj 20,971,520 +
    # attention 31,457,280 + MLP 330,301,440
    assert flops_ssm.layer_matmul_params(model) == (
        47349760 + 4096 * 5120 + 31457280 + 330301440)
    assert flops_ssm.head_flops(model) == 2 * 5120 * 261120
    # a 4,096-row scan, a layer: x and y 2 x 16 KiB, B and C 4 KiB, a
    # decay 128 B a row, and the state once: the bytes bound it
    rows = 4096
    assert flops_ssm.ssd_scan_bytes(rows, 1, model) == rows * (
        32768 + 4096 + 128) + 4194304
    assert flops_ssm.ssd_scan_flops(rows, model) == rows * 5 * (1 << 20)
    assert flops_ssm.ssd_scan_need_s(rows, 1, model, PEAKS) \
        == pytest.approx(5 * flops_ssm.ssd_scan_bytes(rows, 1, model)
                         / 819e9)
    # a decode row: the layers and the head; a prefill: its rows, its
    # causal pairs and ONE head row
    per_row = flops_ssm.row_flops(model)
    assert per_row == 5 * (2 * 430080000 + 2 * 4 * 5120 + 5 * (1 << 20))
    assert flops_ssm.step_flops(64, 64 * 2000, [], model) == \
        64 * (per_row + 2 * 5120 * 261120) + 64 * 2000 * 5 * 4 * 20 * 128
    assert flops_ssm.step_flops(0, 0, [1024], model) == \
        1024 * per_row + 5 * 4 * 20 * 128 * 1024 * 1025 / 2 \
        + 2 * 5120 * 261120


def _op(module, name, opcode, text, start, end):
    return reduce.Op(name, module, start, end, opcode, "", text)


def test_the_readers_on_a_recorded_sample():
    """A hand-made trace of one decode step and one prefill: each reader
    finds its operation by the rule its docstring states, and a record
    without the samples or of another model reads nothing."""
    from grid.drivers.serve_ssm import Sample

    model = manifest.Cell(CELL).config
    pallas = 'custom_call_target="tpu_custom_call"'
    ops = [
        _op("jit_chunk", "ssd_state_step.1", "custom-call",
            "%ssd_state_step.1 = (f32[64,32,128], f32[5,64,32,256,128]) "
            "custom-call(...), " + pallas, 0.000, 0.004),
        _op("jit_chunk", "paged_attention.2", "custom-call",
            "%paged_attention.2 = bf16[64,8,512] custom-call(...), "
            + pallas, 0.004, 0.006),
        _op("jit_chunk", "fusion.7", "fusion", "%fusion.7 = ...", 0.006,
            0.010),
        _op("jit_chunk", "fusion.9", "fusion", "%fusion.9 = ...", 0.010,
            0.014),
        _op("jit_chunk", "fusion.11", "fusion", "%fusion.11 = ...", 0.014,
            0.016),
        _op("jit_prefill", "ssd_chunk_scan.3", "custom-call",
            "%ssd_chunk_scan.3 = (f32[1024,4096], f32[32,256,128]) "
            "custom-call(...), " + pallas, 0.016, 0.018),
        _op("jit_prefill", "while.4", "while",
            "%while.4 = (s32[], f32[32,256,128], f32[8,128,32,128]) "
            "while(...)", 0.018, 0.020),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    samples = [Sample(0.0, {"global": 10}, 0.0, 0.0, 0, 0.0),
               Sample(0.5, {"global": 12}, 0.0, 64.0, 1, 128000.0)]
    req = SimpleNamespace(prompt_len=700, admitted_t=0.25)
    record = {
        "samples": samples, "model": model, "peaks": PEAKS,
        "trace_window": (0.0, 0.020), "prompt_buckets": [1024, 2048, 4096],
        "marks": {"tail_open": 0.1, "tail_close": 1.0, "open": 0.1,
                  "close": 1.0},
        "tracked": [SimpleNamespace(req=req, refused=False)],
        "scoped_ops": {"jit_chunk": {"mixer/ssm_in": ["fusion.7"],
                                     "mlp": ["fusion.9"],
                                     "lm_head": ["fusion.11"]},
                       "jit_prefill": {}}}
    need = 64 * 5 * 8388608 / 819e9
    assert readers.ssd_state_step_roofline(record, trace) \
        == pytest.approx(100 * need / 0.004)
    assert readers.parallel_gqa_attn_roofline(record, trace) \
        == pytest.approx(100 * (128000 * 5 * 2048 / 819e9) / 0.002)
    # the kernel's call and the loop that carries the state both count
    assert readers.ssd_chunk_scan_roofline(record, trace) \
        == pytest.approx(100 * flops_ssm.ssd_scan_need_s(
            1024, 1, model, PEAKS) / 0.004)
    # state step 4 ms + ssm_in 4 ms + the scan's 4 ms of 20 busy
    assert readers.ssd_time_share(record, trace) == pytest.approx(60.0)
    assert readers.dense_mlp_time_share(record, trace) == pytest.approx(20.0)
    assert readers.head_time_share(record, trace) == pytest.approx(10.0)
    assert readers.falcon_h1_step_mfu(record, trace) == pytest.approx(
        100 * flops_ssm.step_flops(64, 128000, [1024], model)
        / (0.020 * 197e12))
    assert readers.ssd_state_slots_stepped_mean(record) == 64.0
    assert readers.parallel_gqa_rows_read_per_step(record) == 128000.0
    # another model's record, or none of the samples: nothing
    for other in (dict(record, model={"kda_lower_bound": -5}),
                  {k: v for k, v in record.items() if k != "samples"}):
        for read in (readers.ssd_state_step_roofline,
                     readers.ssd_chunk_scan_roofline,
                     readers.parallel_gqa_attn_roofline,
                     readers.ssd_time_share, readers.dense_mlp_time_share,
                     readers.head_time_share, readers.falcon_h1_step_mfu,
                     readers.ssd_state_slots_stepped_mean,
                     readers.parallel_gqa_rows_read_per_step):
            assert read(other, trace) is None
    assert readers.ssd_state_step_roofline(record, None) is None


def _control(monkeypatch, name):
    """``benchmarks/control_falcon_h1.py``'s control ``name`` applied;
    what it replaces is put back after the test."""
    from grid.drivers import serve_ssm
    from paddle_tpu.models import falcon_h1
    from paddle_tpu.ops.pallas_kernels import ssd
    from paddle_tpu.serving.kv_cache import PagedKVCache

    spec = importlib.util.spec_from_file_location(
        "control_falcon_h1", os.path.join(manifest.ROOT, "benchmarks",
                                          "control_falcon_h1.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    monkeypatch.setattr(serve_ssm, "build", serve_ssm.build)
    monkeypatch.setattr(ssd, "ssd_chunk_scan", ssd.ssd_chunk_scan)
    monkeypatch.setattr(PagedKVCache, "state_step", PagedKVCache.state_step)
    for fn in ("_attn_out", "_ssm_out", "_ssd_inputs"):
        monkeypatch.setattr(falcon_h1, fn, getattr(falcon_h1, fn))
    control.CONTROLS[name]()


@pytest.mark.parametrize("name", ["no_attn", "no_ssm", "key_one",
                                  "ssm_b_one", "wrong_group", "state_bf16",
                                  "ref_fp8"])
def test_a_control_fails_the_comparison(monkeypatch, capsys, ssm_root, name):
    """The controls through the harness's own comparison at toy widths,
    in float32: the run as stated reads a mean gap of 0 and a state gap
    under 1e-4 (the test above), so whatever a control reads is the
    control's. A branch left out, a wrong multiplier, the wrong group and
    a referee at float8 fail a rank limit outright. The state at
    bfloat16's precision is what ranks do NOT see, here (a context of 90
    tokens) as on the chip (PERF.md, PR 51): the VALUE the cache keeps
    sees it, a hundred times the float32 run's and more."""
    from grid.reference import falcon_h1 as reference

    _toy_limits(monkeypatch)
    monkeypatch.setattr(reference, "_f32", reference._f32)
    _control(monkeypatch, name)
    if name == "ref_fp8":      # the reference's layers are jitted: afresh
        reference._layer.clear_cache()
        reference._gap_parts.clear_cache()
    rc, last, notes = _run(monkeypatch, capsys, ssm_root, CELL, 0,
                           seconds="2.5")
    if name == "ref_fp8":
        reference._layer.clear_cache()
        reference._gap_parts.clear_cache()
    if name == "state_bf16":
        assert last["compared"]["state_gap"][0] > 1e-3
        return
    mean, limit = last["compared"]["mean_gap"]
    problems = [p for n in notes for p in n.get("problems", [])]
    assert not last["correct"] and problems
    assert mean > limit or last["compared"]["logit_margin"][0] \
        > last["compared"]["logit_margin"][1], last["compared"]


def test_the_benchmark_gained_entries_and_files_only():
    """Against the parent commit: no file under ``grid/`` that was there
    is edited, and ``BENCHMARK.json`` differs by one configuration, one
    cell, this cell's name at the END of ``workloads`` lists and new
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
    assert len(now["workloads"]) == 13
    for kind in ("end_to_end", "per_layer"):
        old = parent[kind]
        for was, is_ in zip(old, now[kind]):
            stripped = dict(is_)
            if is_.get("workloads", [None])[-1] == CELL:
                stripped["workloads"] = is_["workloads"][:-1]
            assert stripped == was, was["name"]
        for added in now[kind][len(old):]:
            assert added["workloads"] == [CELL] \
                and added["moves"] == "tpot_p50_ms"
    assert len(now["end_to_end"]) == len(parent["end_to_end"])

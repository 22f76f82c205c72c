"""The rehearsal of the looped decoder's cell: ``grid.run.main`` through
``drivers/serve_loop.py`` end to end on the CPU at toy widths (3 layers
run 4 times, 4 heads of 16; device check stubbed here, as in
``test_drivers.py``), traced and untraced; the arithmetic of
``flops_loop.py`` at the published sizes against a hand count; the readers
on a recorded sample; the controls through the harness's own comparison;
and that the benchmark gained entries and files only. A CPU run proves
control flow, counts and the last line's form only."""

import importlib.util
import json
import os
import subprocess
from types import SimpleNamespace

import pytest

from grid import flops_loop, manifest, reduce
from grid.readers import loop as readers
from grid.tests.conftest import ROOT, _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "ouro-math-sat"
CONFIG = "ouro-2.6b-serve"
TOY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
           head_dim=16, vocab_size=96, intermediate_size=96,
           num_hidden_layers=3)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def loop_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["model"].update(dtype="float32", max_seq=128)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=128,
                             max_queue=4096, group_pages={"global": 40})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[32, 64], preroll_s=0.3)
        # several times what the toy pool takes on a CPU: it stays full
        doc["arrivals"]["rate_per_s"] = 400.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "math-sat.json"), mix)
    return toy_root


def _toy_limits(monkeypatch):
    from grid.drivers import serve_loop

    monkeypatch.setattr(serve_loop, "LONG_CONTEXT", 40)
    monkeypatch.setattr(serve_loop, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_loop, "MIN_GATE_ROWS", 8)
    monkeypatch.setattr(serve_loop, "MIN_ROW_STEPS", 4)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, loop_root, trace):
    _toy_limits(monkeypatch)
    rc, last, notes = _run(monkeypatch, capsys, loop_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, loop_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap",
                                     "exit_p_gap", "row_gap"}
    # float32 on the CPU: the served tokens ARE the reference's, the decode
    # step's exit distribution the reference's, and so are the rows a
    # resident slot keeps, in the first cache layer and in the last
    assert last["compared"]["mean_gap"][0] < 1e-3
    assert 0 <= last["compared"]["exit_p_gap"][0] < 1e-4
    assert 0 <= last["compared"]["row_gap"][0] < 1e-4
    margins = [n for n in notes if "reference_margins" in n][0][
        "reference_margins"]
    assert len(margins) == 3 and margins[2]["resident"]
    assert set(margins[2]["row_gaps"]) == {"step 0 layer 0",
                                           "step 3 layer 2"}
    assert all(m["gate_rows"] >= 8 for m in margins[:2])
    # each of the four steps moves the state, attention and the MLP alike
    steps = margins[0]["step_rms"]
    assert len(steps) == 4
    for attn, mlp, moved in steps:
        assert attn == pytest.approx(0.1, rel=0.05)
        assert mlp == pytest.approx(0.1, rel=0.05)
        assert moved > 0.1
    assert 1.0 < margins[0]["expected_exit_step"] < 4.0
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"global": 40} and built["cache_steps"] == 4
    # 3 layers x 4 steps of 40 pages x 8 rows x 64 lanes, K and V, float32
    assert built["cache_bytes"] == 12 * 320 * 64 * 2 * 4
    # the loop's executables by their labels in the program's compile log
    warm = [n for n in notes if n.get("phase") == "warm"][0]
    assert {"chunk[fuse=1]", "prefill[32]", "prefill[64]"} <= {
        row[0] for row in warm["executables"]}
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"attn_rows_read_per_step.loop", "kv_pages_used_share.global",
            "admit_blocked_on_pages_share", "slot_occupancy_mean",
            "decode_dispatch_ms_mean", "tpot_engine_p50_ms",
            "admission_ms_mean"} <= got
    assert 0 < last["metrics"]["attn_rows_read_per_step.loop"]["value"] \
        <= 4 * 90
    assert not {"loop_weight_stream_roofline", "loop_paged_attn_roofline",
                "loop_attn_time_share.serve", "ouro_step_mfu.serve"} & got
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 1.0 < window["expected_exit_step_mean"] < 4.0


def test_the_needs_at_the_published_sizes_against_a_hand_count():
    model = manifest.Cell(CELL).config
    assert flops_loop.cache_layers(model) == 192
    assert flops_loop.layer_matmul_params(model) == \
        4 * 2048 ** 2 + 3 * 2048 * 5632
    assert flops_loop.layer_params(model) == 51388416
    assert flops_loop.head_params(model) == 100663296
    # ISSUE 56: 4 x 4.93 + 0.2 = 19.9 GB a step, 24.3 ms at 819 GB/s
    assert flops_loop.weight_bytes_per_step(model) == \
        2 * (192 * 51388416 + 100663296)
    assert flops_loop.weight_bytes_per_step(model) / 1e9 \
        == pytest.approx(19.93, abs=0.01)
    assert flops_loop.weight_need_s(1, model, PEAKS) == pytest.approx(
        0.02434, abs=1e-4)
    # 8 KiB a token a cache layer, 1.5 MiB a token
    assert flops_loop.kv_row_bytes(model) == 8192
    assert flops_loop.kv_token_bytes(model) == 3 * 2 ** 19
    assert flops_loop.kv_need_s(3000, model, PEAKS) == pytest.approx(
        3000 * 1572864 / 819e9)
    # a decode row: four times through the layers, once through the head;
    # a prefill: its rows, its causal pairs a cache layer and ONE head row
    per_row = flops_loop.row_flops(model)
    assert per_row == 192 * 2 * 51380224
    assert flops_loop.step_flops(8, 8 * 400, [], model) == \
        8 * (per_row + 2 * 2048 * 49152) + 8 * 400 * 192 * 4 * 16 * 128
    assert flops_loop.step_flops(0, 0, [512], model) == \
        512 * per_row + 192 * 4 * 16 * 128 * 512 * 513 / 2 \
        + 2 * 2048 * 49152


def _op(module, name, opcode, text, start, end):
    return reduce.Op(name, module, start, end, opcode, "", text)


def test_the_readers_on_a_recorded_sample():
    """A hand-made trace of one decode step: each reader finds its
    operation by the rule its docstring states (the weights' stream is held
    against the WHOLE loop, a custom call outside ``attn/loop`` is no
    attention), and a
    record without the samples or of another model reads nothing."""
    from grid.drivers.serve import Cycle
    from grid.drivers.serve_loop import Sample

    model = manifest.Cell(CELL).config
    pallas = 'custom_call_target="tpu_custom_call"'
    ops = [
        _op("jit_chunk", "while.1", "while", "%while.1 = (...) while(...)",
            0.000, 0.030),
        _op("jit_chunk", "fusion.7", "fusion", "%fusion.7 = ...", 0.000,
            0.012),
        _op("jit_chunk", "paged_attention.2", "custom-call",
            "%paged_attention.2 = bf16[12,16,128] custom-call(...), "
            + pallas, 0.012, 0.016),
        _op("jit_chunk", "fusion.9", "fusion", "%fusion.9 = ...", 0.016,
            0.028),
        _op("jit_chunk", "other.3", "custom-call",
            "%other.3 = bf16[12,16,128] custom-call(...), " + pallas,
            0.028, 0.030),
        _op("jit_chunk", "fusion.11", "fusion", "%fusion.11 = ...", 0.030,
            0.032),
        _op("jit_prefill", "fusion.20", "fusion", "%fusion.20 = ...", 0.032,
            0.040),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    samples = [Sample(0.0, {"global": 10}, 0.0, 0, 0.0, 0.0),
               Sample(0.5, {"global": 12}, 0.0, 1, 3000.0, 250.0)]
    req = SimpleNamespace(prompt_len=300, admitted_t=0.25)
    record = {
        "samples": samples, "model": model, "peaks": PEAKS,
        "trace_window": (0.0, 0.040), "prompt_buckets": [128, 256, 512],
        "marks": {"tail_open": 0.1, "tail_close": 1.0, "open": 0.1,
                  "close": 1.0},
        "tracked": [SimpleNamespace(req=req, refused=False)],
        "cycles": [Cycle(0.2, 0.5, 8, 0, 3008, 9)],
        "scoped_ops": {"jit_chunk": {
            "loop/step": ["fusion.7", "paged_attention.2", "fusion.9"],
            "attn/loop": ["fusion.7", "paged_attention.2"],
            "mlp/loop": ["fusion.9"], "loop/gate": [],
            "head": ["fusion.11"]}, "jit_prefill": {}}}
    # the loop's 30 ms, its kernel's 4 among them, and the head's 2
    assert readers.loop_weight_stream_roofline(record, trace) \
        == pytest.approx(100 * flops_loop.weight_need_s(1, model, PEAKS)
                         / 0.032)
    assert readers.loop_paged_attn_roofline(record, trace) \
        == pytest.approx(100 * (3000 * 1572864 / 819e9) / 0.004)
    # 4 ms of the decode executable's 32
    assert readers.loop_attn_time_share(record, trace) \
        == pytest.approx(100 * 0.004 / 0.032)
    # 9 tokens of which one the prefill's: 8 decoded rows
    assert readers.ouro_step_mfu(record, trace) == pytest.approx(
        100 * flops_loop.step_flops(8, 3000, [512], model)
        / (0.040 * 197e12))
    assert readers.loop_rows_read_per_step(record) == 3000.0
    every = (readers.loop_weight_stream_roofline,
             readers.loop_paged_attn_roofline, readers.loop_attn_time_share,
             readers.ouro_step_mfu, readers.loop_rows_read_per_step)
    for other in (dict(record, model={"mamba_d_state": 256}),
                  {k: v for k, v in record.items() if k != "samples"}):
        for read in every:
            assert read(other, trace) is None
    for read in every[:4]:
        assert read(record, None) is None


def _control(monkeypatch, name):
    """``benchmarks/control_ouro.py``'s control ``name`` applied; what it
    replaces is put back after the test."""
    from grid.drivers import serve_loop
    from paddle_tpu.serving.kv_cache import PagedKVCache

    spec = importlib.util.spec_from_file_location(
        "control_ouro", os.path.join(manifest.ROOT, "benchmarks",
                                     "control_ouro.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    monkeypatch.setattr(serve_loop, "build", serve_loop.build)
    for name_ in ("_write_rows", "_pool_layer"):
        monkeypatch.setattr(PagedKVCache, name_,
                            getattr(PagedKVCache, name_))
    control.CONTROLS[name]()


@pytest.mark.parametrize("name", ["shared_cache", "three_steps", "pool_fp8",
                                  "ref_fp8"])
def test_a_control_fails_the_comparison(monkeypatch, capsys, loop_root,
                                        name):
    """The controls through the harness's own comparison at toy widths, in
    float32: the run as stated reads a mean gap of 0 and a gate gap and a
    row gap under 1e-4 (the test above), so whatever a control reads is
    the control's. A cache layer shared by the steps and a step left out
    fail the rank and gate limits outright. A precision below the stated
    one moves three toy layers' ranks far less than 48's (those limits lie
    between the CHIP's readings at the published depth: PERF.md, PR 56):
    what it cannot pass is the VALUE the cache keeps, the rows of a
    resident slot against the reference's."""
    from grid.reference import ouro as reference

    _toy_limits(monkeypatch)
    monkeypatch.setattr(reference, "_f32", reference._f32)
    _control(monkeypatch, name)
    if name == "ref_fp8":      # the reference's layers are jitted: afresh
        reference._layer.clear_cache()
        reference._logits.clear_cache()
    rc, last, notes = _run(monkeypatch, capsys, loop_root, CELL, 0,
                           seconds="2.5")
    if name == "ref_fp8":
        reference._layer.clear_cache()
        reference._logits.clear_cache()
    problems = [p for n in notes for p in n.get("problems", [])]
    assert not last["correct"] and problems, last["compared"]
    over = {k for k in ("logit_margin", "mean_gap", "exit_p_gap", "row_gap")
            if last["compared"][k][0] > last["compared"][k][1]}
    assert over, last["compared"]
    if name.endswith("fp8"):
        assert "row_gap" in over, last["compared"]


def test_the_reference_runs_at_toy_size_and_is_the_programs_prefill():
    """``test_reference.py``'s case for this model: the plain float32
    reference (a Python loop over 4 x 3 layer applications) against the
    program's own prefill (one ``lax.scan``) at toy widths on the CPU,
    float32 against float32 in another order of operations; its four
    ``p_t`` a row sum to 1, and the rows it says a cache keeps are the
    rows the program's prefill hands the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from grid.reference import ouro as reference
    from paddle_tpu.models import ouro

    model = dict(TOY, total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e6)
    cfg = ouro.OuroConfig(96, 3, 64, 4, 4, 16, 96, max_seq=64)
    lm = ouro.OuroLM(cfg, seed=3)
    toks = np.random.RandomState(0).randint(0, 96, 40)
    with jax.default_matmul_precision("highest"):
        want, kept = lm.prefill(lm.params, jnp.asarray(toks)[None],
                                jnp.asarray([40]))
    rows = dict.fromkeys(reference.probes(model))
    assert list(rows) == [(0, 0), (3, 2)]
    x, p = reference.hidden(lm.params, model, jnp.asarray(toks), rows=rows)
    got, p_rows = reference.forward(lm.params, model, toks, rows=[3, 39])
    np.testing.assert_allclose(got, want[0][jnp.asarray([3, 39])], atol=2e-5)
    np.testing.assert_allclose(np.asarray(p).sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_allclose(p_rows, np.asarray(p)[[3, 39]])
    for (t, layer), k in rows.items():
        np.testing.assert_allclose(
            k, np.asarray(kept[layer][0][t, 0]).reshape(40, -1), atol=2e-5)
    gaps, p_out = reference.row_gaps(lm.params, model, toks[:10].tolist(),
                                     toks[10:18].tolist(), pad_to=8)
    assert gaps.shape == (8,) and p_out.shape == (8, 4)
    assert (gaps >= 0).all() and gaps.max() > reference.LOGIT_MARGIN


def test_the_parent_cannot_build_the_cell():
    """What the driver tries on the parent first: this PR's benchmark files
    over a program without ``paddle_tpu.models.ouro`` must fail at once,
    in ``build``'s import, before anything is placed on the device."""
    import inspect

    from grid.drivers import serve_loop

    source = inspect.getsource(serve_loop.build)
    assert source.index("from paddle_tpu.models.ouro import") \
        < source.index("ServingEngine(")


def test_the_benchmark_gained_entries_and_files_only():
    """Against the parent commit: no file under ``grid/`` that was there
    is edited, and ``BENCHMARK.json`` differs by one configuration, one
    cell, this cell's name at the END of ``workloads`` lists and five new
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
    assert now["configs"][-1]["reduced"] == []
    assert now["workloads"][:-1] == parent["workloads"]
    assert now["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in now["workloads"]) == 1
    assert len(now["workloads"]) == 15
    for kind in ("end_to_end", "per_layer"):
        old = parent[kind]
        for was, is_ in zip(old, now[kind]):
            stripped = dict(is_)
            if is_.get("workloads", [None])[-1] == CELL:
                stripped["workloads"] = is_["workloads"][:-1]
            assert stripped == was, was["name"]
        added = now[kind][len(old):]
        assert all(m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
                   for m in added)
    assert len(now["end_to_end"]) == len(parent["end_to_end"])
    assert [m["name"] for m in now["per_layer"][len(parent["per_layer"]):]
            ] == ["ouro_step_mfu.serve", "loop_weight_stream_roofline",
                  "loop_paged_attn_roofline", "loop_attn_time_share.serve",
                  "attn_rows_read_per_step.loop"]


def test_the_configuration_is_the_catalogs_row_uncut():
    """Every number of the published config under its own key, nothing
    reduced, every assumed item stated."""
    doc = manifest.Cell(CELL).config
    assert doc["reduced"] == [] and doc["kind"] == "serve_loop"
    published = dict(head_dim=128, hidden_size=2048, intermediate_size=5632,
                     max_position_embeddings=65536, max_window_layers=48,
                     num_attention_heads=16, num_hidden_layers=48,
                     num_key_value_heads=16, rms_norm_eps=1e-06,
                     rope_theta=1000000, total_ut_steps=4,
                     early_exit_threshold=1, vocab_size=49152)
    assert {k: doc[k] for k in published} == published
    assert doc["layer_types"] == ["full_attention"] * 48
    assert set(doc["assumed"]) >= {"bias", "rope_pairing", "sandwich_norms",
                                   "final_norm", "exit_gate", "cache",
                                   "seeded_scales", "weights", "serving"}
    assert doc["engine"]["group_pages"] == {"global": 288}
    assert doc["engine"]["slots"] == 12

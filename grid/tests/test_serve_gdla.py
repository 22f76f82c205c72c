"""The rehearsal of the grouped-differential latent decoder's cell:
``grid.run.main`` through ``drivers/serve_gdla.py`` end to end on the CPU
at toy widths (device check stubbed here, as in ``test_drivers.py``),
traced and untraced, and the arithmetic of ``flops_gdla.py`` and
``readers/gdla.py`` at the published sizes and on hand-made records. A CPU
run proves control flow, counts and the last line's form only."""

import os

import pytest

from grid import flops_gdla, manifest, reduce
from grid.readers import gdla
from grid.tests.conftest import _rewrite
from grid.tests.test_drivers import _run, _well_formed

CELL = "motif3-docreason-sat"
CONFIG = "motif-3-beta-ep16-serve"
TOY = dict(hidden_size=32, num_attention_heads=10, num_key_value_heads=2,
           num_noise_heads=2, head_dim=16, q_lora_rank=16, kv_lora_rank=16,
           qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
           moe_intermediate_size=16, sliding_window=8, num_hidden_layers=4,
           vocab_size=97, num_experts=4, experts_top_k=4,
           experts_held=[0, 1, 2, 3],
           layer_types=["window", "window", "full", "window"],
           published_layer_indices=[1, 4, 7, 8], dense_layers_held=[0])
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture
def gdla_root(toy_root):
    def config(doc):
        doc.update(TOY)
        doc["published"]["num_experts"] = 16
        doc["model"] = dict(dtype="float32", max_seq=64)
        doc["engine"] = dict(slots=4, page_size=8, max_seq=64, max_queue=64,
                             group_pages={"latent_full": 32})

    def mix(doc):
        doc.update(prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
                   output_len={"dist": "uniform", "lo": 17, "hi": 30},
                   prompt_buckets=[8, 16, 24], preroll_s=0.3)
        doc["arrivals"]["rate_per_s"] = 25.0

    _rewrite(os.path.join(toy_root, "grid", "configs", CONFIG + ".json"),
             config)
    _rewrite(os.path.join(toy_root, "grid", "traffic", "docreason-sat.json"),
             mix)
    return toy_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(monkeypatch, capsys, gdla_root, trace):
    from grid.drivers import serve_gdla

    # the toy's longest context is 54; a sixteenth of it served is enough
    monkeypatch.setattr(serve_gdla, "LONG_CONTEXT", 30)
    monkeypatch.setattr(serve_gdla, "MIN_TOKENS", 34)
    rc, last, notes = _run(monkeypatch, capsys, gdla_root, CELL, trace,
                           seconds="2.5")
    cell = manifest.Cell(CELL, gdla_root)
    assert rc == 0
    _well_formed(last, cell, bool(trace))
    problems = [n["problems"] for n in notes if "problems" in n]
    assert last["correct"], problems
    assert set(last["compared"]) >= {"logit_margin", "mean_gap",
                                     "stream_norm_gap"}
    # float32 on the CPU: the program's sums are the reference's
    gap, limit = last["compared"]["stream_norm_gap"]
    assert 0 <= gap < 1e-5 and limit == serve_gdla.reference.STREAM_NORM_LIMIT
    got = set(last["metrics"])
    if not trace:
        assert got == {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}
        return
    # the counters' readers answer; the device's find no TPU plane to read
    assert {"sixteenth_share_experts_touched_per_layer_mean",
            "attn_rows_read_per_step.latent_full",
            "attn_rows_read_per_step.latent_ring",
            "gdla_latent_pages_used_share", "slot_occupancy_mean",
            "decode_dispatch_ms_mean", "tpot_engine_p50_ms",
            "prefill_stall_ms_per_token_p50",
            "prefill_stall_ms_per_token_p95", "longest_handover_gap_ms_p50",
            "admission_ms_mean"} <= got
    full, ring = (last["metrics"]["attn_rows_read_per_step." + g]["value"]
                  for g in ("latent_full", "latent_ring"))
    assert 0 < ring <= 4 * 8 and ring < full <= 4 * 64
    assert 0 < last["metrics"][
        "sixteenth_share_experts_touched_per_layer_mean"]["value"] <= 4
    assert not {"gdla_full_attn_roofline", "gdla_ring_attn_roofline",
                "gdla_attn_time_share.serve", "mhc_time_share.serve",
                "sixteenth_share_expert_stream_roofline"} & got
    built = [n for n in notes if n.get("phase") == "built"][0]
    assert built["pools"] == {"latent_full": 32, "latent_ring": 4}
    # 3 window layers x 4 slots x 8 rows x 128 lanes, float32
    assert built["ring_bytes"] == 3 * 4 * 8 * 128 * 4
    window = [n for n in notes if n.get("phase") == "window"][0]
    assert 0 < window["rows_read_ring_mean"] < window["rows_read_full_mean"]


def test_the_maps_control_fails_the_norm_limit_and_no_other(
        monkeypatch, capsys, gdla_root):
    """``benchmarks/control_motif3.py maps_bf16`` through the harness's own
    comparison: the residual maps and the heads' lambda at bfloat16's
    precision leave every served token where the reference ranks it (the
    two rank limits pass) and fail the norm of the streams' sum."""
    import importlib.util

    from grid.drivers import serve_gdla

    spec = importlib.util.spec_from_file_location(
        "control_motif3", os.path.join(manifest.ROOT, "benchmarks",
                                       "control_motif3.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    monkeypatch.setattr(serve_gdla, "LONG_CONTEXT", 30)
    monkeypatch.setattr(serve_gdla, "MIN_TOKENS", 34)
    monkeypatch.setattr(serve_gdla, "model_config", serve_gdla.model_config)
    control.maps_bf16()
    rc, last, notes = _run(monkeypatch, capsys, gdla_root, CELL, 0,
                           seconds="2.5")
    problems = [p for n in notes for p in n.get("problems", [])]
    assert not last["correct"] and problems
    assert all("norm of the residual streams' sum" in p for p in problems)
    gap, limit = last["compared"]["stream_norm_gap"]
    assert gap > limit
    assert last["compared"]["mean_gap"][0] <= last["compared"]["mean_gap"][1]


HLO = """HloModule jit_chunk

%fused_computation.1 (p0: f32[64,4,4]) -> f32[64,4,4] {
  %p0 = f32[64,4,4]{2,1,0} parameter(0)
  %r = f32[64,4]{1,0} reduce(%p0, %c), dimensions={2}, to_apply=%region_0.1, metadata={op_name="jit(chunk)/residual/mhc/reduce_sum"}
  ROOT %d = f32[64,4,4]{2,1,0} divide(%p0, %b), metadata={op_name="jit(chunk)/residual/mhc/div"}
}

%fused_computation.2 (p0: bf16[64,4096], p1: bf16[4096,576]) -> bf16[64,576] {
  %p0 = bf16[64,4096]{1,0} parameter(0)
  %m = bf16[64,4096]{1,0} multiply(%p0, %p0), metadata={op_name="jit(chunk)/residual/mhc/mul"}
  %n = bf16[64,4096]{1,0} multiply(%m, %m), metadata={op_name="jit(chunk)/mul"}
  ROOT %dot = bf16[64,576]{1,0} dot(%n, %p1), metadata={op_name="jit(chunk)/dot_general"}
}

%fused_computation.3 (p0: bf16[64,8192], p1: bf16[8192,4096]) -> bf16[64,4096] {
  %p0 = bf16[64,8192]{1,0} parameter(0)
  %o = bf16[64,4096]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(chunk)/dot_general"}
  %m1 = bf16[64,4096]{1,0} multiply(%o, %o), metadata={op_name="jit(chunk)/residual/mhc/mul"}
  %m2 = bf16[64,4096]{1,0} multiply(%m1, %o), metadata={op_name="jit(chunk)/residual/mhc/mul"}
  ROOT %s = bf16[64,4096]{1,0} add(%m1, %m2), metadata={op_name="jit(chunk)/residual/mhc/add"}
}

%fused_computation.5 (p0: f32[64,4096], p1: f32[4096,24]) -> f32[64,24] {
  %p0 = f32[64,4096]{1,0} parameter(0)
  ROOT %zphi = f32[64,24]{1,0} dot(%p0, %p1), metadata={op_name="jit(chunk)/residual/mhc/dot_general"}
}

%fused_computation.4 (p0: f32[64,4096], p1: f32[4096,24]) -> f32[64,24] {
  %p0 = f32[64,4096]{1,0} parameter(0)
  %c1 = f32[64,4096]{1,0} convert(%p0), metadata={op_name="jit(chunk)/convert_element_type"}
  %c2 = f32[64,4096]{1,0} convert(%c1), metadata={op_name="jit(chunk)/convert_element_type"}
  ROOT %fusion.9 = f32[64,24]{1,0} fusion(%c2, %p1), kind=kOutput, calls=%fused_computation.5, metadata={op_name="jit(chunk)/residual/mhc/dot_general"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(chunk)/residual/mhc/reduce_sum"}
}

ENTRY %main.1 (x: bf16[64,4096]) -> bf16[64,576] {
  %x = bf16[64,4096]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[64,4,4]{2,1,0} fusion(%h), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/residual/mhc/div"}
  %gte.1 = f32[64,4]{1,0} get-tuple-element(%t), index=0, metadata={op_name="jit(chunk)/residual/mhc/div"}
  %convert.5 = f32[4096]{0} convert(%g), metadata={op_name="jit(chunk)/residual/mhc/convert_element_type"}
  %copy.2 = bf16[64,4096]{1,0} copy(%x), metadata={op_name="jit(chunk)/attn/gdla_ring/copy"}
  %fusion.3 = bf16[64,4096]{1,0} fusion(%a, %wo), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(chunk)/residual/mhc/add"}
  %fusion.4 = f32[64,24]{1,0} fusion(%z, %phi), kind=kOutput, calls=%fused_computation.4, metadata={op_name="jit(chunk)/residual/mhc/dot_general"}
  ROOT %fusion.2 = bf16[64,576]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(chunk)/residual/mhc/mul"}
}
"""


def test_the_executables_own_text_says_what_runs_under_a_scope():
    """A fusion is the scope's where most of what it fused is, and where
    it fused a matrix product, where the product is (the output projection
    with the mixing as its epilogue is not the residual path's, though
    three of its four instructions are; ``z Phi`` inside a fusion inside
    a fusion is); an instruction of its own where its ``op_name`` holds
    the scope; what is no event (inside a fusion or a reduction's region,
    an element of a tuple, a parameter) is left out."""
    assert gdla.scoped_instructions(HLO, "residual/mhc") == [
        "convert.5", "fusion.1", "fusion.4"]
    assert gdla.scoped_instructions(HLO, "attn/") == ["copy.2"]
    assert gdla.scoped_instructions("", "residual/mhc") == []


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
        hidden_size=4096, intermediate_size=12288, kv_lora_rank=512,
        q_lora_rank=1024, qk_rope_head_dim=64, v_head_dim=128, head_dim=192,
        moe_intermediate_size=1280, num_attention_heads=80,
        num_key_value_heads=16, num_noise_heads=16, experts_top_k=8,
        n_dense_first_layers=2, route_scale=2, rope_theta=10000,
        swa_rope_theta=10000, max_position_embeddings=262144,
        sliding_window=128, sliding_window_period=4, mhc_expansion_rate=4,
        mhc_sinkhorn_iters=20, hidden_clamp=1000000,
        polynorm_output_scale=0.5, polynorm_bias_clamp=0.5,
        num_nextn_predict_layers=1, max_window_layers=9, rope_factor=64,
        original_seq_len=4096, rms_norm_eps=1e-05, score_func="sigmoid",
        attention_cls="gdla", hidden_act="poly_norm")
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "original_max_position_embeddings": 4096, "factor": 64, "mscale": 1,
        "rope_type": "yarn", "rope_theta": 10000, "beta_fast": 32,
        "beta_slow": 1, "apply_yarn_scaling": False}
    assert cfg["published"] == {"num_hidden_layers": 53, "num_experts": 384,
                                "vocab_size": 220160}
    # one leading dense layer, then two whole periods (window x 3, full) of
    # the layers that follow, as the published pattern places them
    assert cfg["layer_types"] == ["window"] * 4 + ["full"] \
        + ["window"] * 3 + ["full"]
    assert cfg["published_layer_indices"] == [1, 4, 5, 6, 7, 8, 9, 10, 11]
    assert [(i + 1) % cfg["sliding_window_period"] == 0
            for i in cfg["published_layer_indices"]] == [
        t == "full" for t in cfg["layer_types"]]
    assert cfg["dense_layers_held"] == [0] and \
        cfg["published_layer_indices"][0] < cfg["n_dense_first_layers"]
    assert cfg["num_experts"] == len(cfg["experts_held"]) == 24
    assert cfg["experts_held"] == list(range(24))      # rank 0 of 16
    assert cfg["num_experts"] * 16 == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "16 chips share each layer" in cfg["deployment"]
    for key in ("layers_held", "layer_kinds", "heads", "kv_b", "lambda",
                "output_gate", "residual_path", "residual_precision",
                "residual_seeds", "poly_norm", "poly_norm_seeds", "rotary",
                "router", "hidden_clamp", "weights",
                "next_token_prediction", "max_seq", "engine"):
        assert cfg["assumed"][key]
    # the full group's pool is 9,216 rows a slot; the longest request fits
    # a slot and the largest bucket covers the longest prompt
    e, t = cfg["engine"], manifest.Cell(CELL).traffic
    assert e["group_pages"] == {"latent_full": 64 * 9216 // 16}
    worst = t["prompt_len"]["hi"] + t["output_len"]["hi"]
    assert worst <= e["max_seq"] and max(t["prompt_buckets"]) \
        >= t["prompt_len"]["hi"]
    assert t["arrivals"]["order_seed"] == t["arrivals"]["schedule_seed"] \
        == 43043


def test_the_driver_builds_the_share_the_file_states():
    from grid.drivers import serve_gdla

    config = manifest.Cell(CELL).config
    cfg = serve_gdla.model_config(config)
    assert (cfg.n_expert, len(cfg.experts_held), cfg.top_k) == (384, 24, 8)
    assert (cfg.n_head, cfg.n_kv_head, cfg.n_signal) == (80, 16, 64)
    assert cfg.latent_row == (512, 64) and cfg.vocab_size == 27520
    assert (cfg.d_nope, cfg.d_rope, cfg.d_v, cfg.q_rank) == (128, 64, 128,
                                                            1024)
    assert [(g[0], len(g[1]), g[2]) for g in cfg.cache_groups] == [
        ("latent_full", 2, None), ("latent_ring", 7, 128)]
    assert abs(cfg.sm_scale - 192 ** -0.5) < 1e-12
    # the window layers rotate plainly, the full layers at YaRN's
    # frequencies: the slowest pair 64 times slower
    ring, full = (cfg.latent_of[k].inv_freq for k in ("window", "full"))
    assert ring[0] == full[0] == 1.0
    assert full[-1] == pytest.approx(ring[-1] / 64)
    with pytest.raises(ValueError, match="experts_held names 2"):
        serve_gdla.model_config(dict(config, experts_held=[0, 1]))
    with pytest.raises(ValueError, match="written for"):
        serve_gdla.model_config(dict(config, diff_v2=False))


def test_the_operations_and_bytes_the_rooflines_divide():
    """The numbers of ISSUE 43 at the published sizes."""
    m = manifest.Cell(CELL).config
    assert flops_gdla.layers_of(m, "full") == 2
    assert flops_gdla.layers_of(m, "window") == 7
    # the two full layers' 350k rows x 1,152 B x 2 = 0.81 GB: 1.0 ms
    need = flops_gdla.attn_decode_need_s(350000, "full", m, PEAKS)
    assert need == pytest.approx(2 * 350000 * 1152 / 819e9)
    assert 0.95e-3 < need < 1.0e-3
    # 151 operations a byte against a ridge of 240: the bytes bound it,
    # and the operations where the peak is low
    assert 80 * (576 + 512) * 2 / 1152 == pytest.approx(151.1, abs=0.1)
    assert flops_gdla.attn_decode_need_s(
        1000, "full", m, dict(PEAKS, bf16_flops_per_s=1e12)) == \
        pytest.approx(2 * 1000 * 80 * 1088 * 2 / 1e12)
    # seven ring calls of 64 slots x 128 rows: 9.4 MB each
    assert flops_gdla.attn_decode_need_s(64 * 128, "window", m, PEAKS) == \
        pytest.approx(7 * 8192 * 1152 / 819e9)
    # an expert: 3 x 4096 x 1280 x 2 = 31.5 MB; 17.7 touched x 8 layers
    assert flops_gdla.expert_stream_bytes(1, m) == 31457280
    assert flops_gdla.expert_stream_bytes(17.7 * 8, m) == pytest.approx(
        4.45e9, rel=0.01)


def _op(text, start, end, module="jit_chunk"):
    name, opcode, shape = reduce.parse_hlo(text)
    return reduce.Op(name, module, start, end, opcode, shape, text)


def test_the_trace_readers_on_a_hand_made_trace():
    """One decode step: 1.5 ms of the full layers' kernel, 0.7 ms of the
    ring calls, 0.5 ms of the 8,192-wide gate (told by its columns), 0.4 ms
    and 0.2 ms of two residual halves (told by their instructions' names,
    which the driver took from the executable's own text), 2 ms of the
    share's loop, 1 ms of something else; and 10 ms of a prefill."""
    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    ops = [
        _op("%mla_latent_decode.3 = bf16[64,80,512]{2,1,0} " + call,
            0.000, 0.0015),
        _op("%mla_latent_decode_ring.5 = bf16[64,80,512]{2,1,0} " + call,
            0.0015, 0.0022),
        _op("%fusion.6 = f32[64,8192]{1,0} fusion(bf16[64,4096]{1,0} %x, "
            "bf16[4096,8192]{1,0} %w)", 0.0022, 0.0027),
        _op("%fusion.7 = bf16[4,64,4096]{2,1,0} fusion(f32[64,4,4]{2,1,0} "
            "%h, bf16[4,64,4096]{2,1,0} %x)", 0.0027, 0.0031),
        _op("%while.37 = (s32[], f32[64,4096]{1,0}, "
            "bf16[24,4096,1280]{2,1,0}) while(%t), condition=%c, body=%b",
            0.0031, 0.0051),
        _op("%fusion.11 = bf16[64,27520]{1,0} fusion(bf16[64,4096]{1,0} %x, "
            "f32[64]{0} %r)", 0.0051, 0.0061),
        _op("%fusion.12 = (f32[64]{0}, bf16[64,4096]{1,0}, bf16[64,4096]{1,0}) "
            "fusion(f32[64]{0} %a, bf16[64,4096]{1,0} %x0, bf16[64,4096]{1,0} "
            "%x1, f32[64]{0} %b)", 0.0061, 0.0063),
        _op("%fusion.40 = bf16[4,8192,4096]{2,1,0} fusion(%x)", 0.010, 0.020,
            module="jit_prefill"),
    ]
    trace = reduce.Trace({0: ops}, {0: []}, [])
    from grid.drivers.serve_gdla import Sample

    samples = [Sample(-1.0, 100, 0.0, 0, 0.0, 0.0, 0.0, 0),
               Sample(0.5, 300, 140.0, 8, 256.0, 350000.0, 8192.0, 1)]
    record = {"trace_window": (0.0, 0.020),
              "model": manifest.Cell(CELL).config, "slots": 64,
              "peaks": PEAKS, "samples": samples,
              "pools": {"latent_full": 36864, "latent_ring": 512},
              "residual_ops": ["fusion.7", "fusion.12"],
              "marks": {"tail_open": 0.0, "tail_close": 1.0, "open": 0.0,
                        "close": 1.0}}
    decode = 0.0063
    assert gdla.gdla_full_attn_roofline(record, trace) == pytest.approx(
        100 * (2 * 350000 * 1152 / 819e9) / 0.0015)
    assert gdla.gdla_ring_attn_roofline(record, trace) == pytest.approx(
        100 * (7 * 8192 * 1152 / 819e9) / 0.0007)
    assert gdla.gdla_attn_time_share(record, trace) == pytest.approx(
        100 * 0.0027 / decode)
    assert gdla.mhc_time_share(record, trace) == pytest.approx(
        100 * 0.0006 / decode)
    assert gdla.sixteenth_share_expert_stream_roofline(record, trace) == \
        pytest.approx(100 * (140 * 31457280 / 819e9) / 0.002)
    # the names decide, not the shapes: one that is the attention half's
    # by its shapes does not count, nor one of the prefill's; and without
    # the executable's account there is nothing to read
    named = dict(record, residual_ops=["fusion.12", "fusion.6", "fusion.40"])
    assert gdla.mhc_time_share(named, trace) == pytest.approx(
        100 * 0.0002 / decode)
    assert gdla.mhc_time_share(dict(record, residual_ops=[]), trace) is None
    assert gdla.attn_rows_read_per_step_latent_full(record) == 350000.0
    assert gdla.attn_rows_read_per_step_latent_ring(record) == 8192.0
    assert gdla.gdla_latent_pages_used_share(record) == pytest.approx(
        100 * 300 / 36864)
    # nothing to read: nothing returned, never 0 (the parent of this PR has
    # neither the kernels nor the counters; another model's record neither)
    empty = reduce.Trace({0: [ops[5]]}, {0: []}, [])
    for reader in (gdla.gdla_full_attn_roofline,
                   gdla.gdla_ring_attn_roofline, gdla.gdla_attn_time_share,
                   gdla.mhc_time_share,
                   gdla.sixteenth_share_expert_stream_roofline):
        assert reader(record, empty) is None
        assert reader(record, None) is None
        assert reader({"trace_window": (0, 1), "model": {"n_layer": 12},
                       "marks": {}}, trace) is None
    for reader in (gdla.attn_rows_read_per_step_latent_full,
                   gdla.attn_rows_read_per_step_latent_ring,
                   gdla.gdla_latent_pages_used_share):
        assert reader({"marks": record["marks"]}) is None


def test_the_benchmark_gained_entries_and_files_only():
    """The cell's name is appended to the ``workloads`` of the metrics it
    shares, and its own nine name it first. Written so that a later PR's
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
        "gdla_full_attn_roofline", "gdla_ring_attn_roofline",
        "gdla_attn_time_share.serve", "mhc_time_share.serve",
        "sixteenth_share_expert_stream_roofline",
        "sixteenth_share_experts_touched_per_layer_mean",
        "attn_rows_read_per_step.latent_full",
        "attn_rows_read_per_step.latent_ring",
        "gdla_latent_pages_used_share"])
    assert all(by_name[n]["moves"] == "tpot_p50_ms" for n in own)
    assert [w["chips"] for w in bench["workloads"] if w["name"] == CELL] \
        == [1]
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert "mla_paged_attn_roofline" not in cell.cell["reports"]
    with open(os.path.join(manifest.GRID_DIR, "reference", "motif3.py")) as f:
        copy = f.read()
    with open(os.path.join(manifest.ROOT, "paddle_tpu", "models",
                           "motif3_reference.py")) as f:
        assert f.read() == copy

"""CPU rehearsal of ``chip_smoke.py``: its control flow, its per-phase lines
and its failure exit, guarded without a chip.

The script has no option for either liberty taken here: the test swaps its
``SIZES`` for toy widths and stubs ``device_doc`` (the one place the script
asks JAX what it runs on). Everything else is the script as the chip runs
it — same entry points, same checks — on the CPU backend with the Pallas
kernels interpreted. It proves nothing about the chip.
"""

import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY = {
    "train": dict(n_layer=1, d_model=32, d_inner=64, n_head=2, vocab=128,
                  batch=8, seq=16, steps=2),
    "ctr": dict(vocab=1000, fields=4, width=10, batch=32, steps=2),
    "kernels": dict(flash=(1, 1, 128, 64), xent=(64, 256), sparse_vocab=512,
                    sparse_ids=64, sparse_widths=(10, 128),
                    expert_stream=dict(smallthinker=(24, 4, 32, 16, 3, 24),
                                       kimi_k2=(40, 6, 64, 48, 2, 5))),
    "serve": dict(vocab=64, n_layer=1, d_model=32, n_head=2, max_seq=64,
                  page_size=8, slots=4, requests=3, prompt_min=4,
                  prompt_max=12, new_tokens=3, buckets=(16,),
                  reference_requests=1),
    "serve_moe": dict(vocab=64, n_layer=4, d_model=32, n_head=4, n_kv_head=2,
                      d_head=8, n_expert=4, top_k=2, d_expert=16, window=8,
                      max_seq=32, page_size=4, slots=2, prompts=(3, 6),
                      new_tokens=6, buckets=(8,)),
    "serve_mla": dict(vocab=64, n_layer=2, d_model=32, n_head=4, q_rank=16,
                      kv_rank=16, d_nope=8, d_rope=8, d_v=8, d_dense=64,
                      n_expert=8, top_k=2, d_expert=16, held=4,
                      yarn_positions=16, dtype="float32", max_seq=64,
                      page_size=8, slots=2, prompt=11, new_tokens=17,
                      buckets=(16,)),
    "dp": dict(steps=2),
}


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "SIZES", TOY)
    return mod


def as_tpu(mod, monkeypatch, count):
    monkeypatch.setattr(mod, "device_doc", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": count})


def run(mod, capsys, argv):
    rc = mod.main(argv)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, lines


def test_one_chip_runs_six_phases_and_ends_with_the_contract_line(
        smoke, monkeypatch, capsys):
    as_tpu(smoke, monkeypatch, 1)
    rc, lines = run(smoke, capsys, [])
    assert rc == 0, lines
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "start", "train", "ctr", "kernels", "serve", "serve_moe",
        "serve_mla"]
    for ln in lines[1:-1]:
        assert ln["ok"] is True
        for key in ("seconds", "compile_seconds", "compiles",
                    "persistent_cache", "checked", "kernel_path", "tune"):
            assert key in ln, (ln["phase"], key)
        assert "tuned" not in ln["tune"].values()
    # exactly the contract's last line, nothing else in it
    assert lines[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    # on the CPU `auto` keeps every XLA path, and the lines say so
    by_phase = {ln["phase"]: ln for ln in lines[1:-1]}
    assert by_phase["serve"]["decode_kernel_info"] == ["gather", "n/a"]
    assert by_phase["serve_mla"]["decode_kernel_info"] == ["gather", "n/a"]
    assert by_phase["serve_mla"]["reference_margin"] < 1e-3
    assert "xla scatter" in by_phase["ctr"]["kernel_path"]["sparse_emb"]
    assert by_phase["kernels"]["kernel_path"] == "interpreted"
    stream = by_phase["kernels"]["kernels"]["ragged_dot_stream"]
    assert sorted(stream) == ["kimi_k2", "smallthinker"]
    for doc in stream.values():
        assert doc["mean_abs_err"] <= 1.02 * doc["mean_abs_err_ragged_dot"]


def test_four_chips_runs_only_the_data_parallel_phase(
        smoke, monkeypatch, capsys):
    """On conftest's eight virtual devices: the mesh, the sharding reads and
    the all-reduce count are real, the chip count is the stub's."""
    as_tpu(smoke, monkeypatch, 4)
    rc, lines = run(smoke, capsys, ["--chips", "4"])
    assert rc == 0, lines
    assert [ln.get("phase") for ln in lines[:-1]] == ["start",
                                                      "data_parallel"]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


@pytest.mark.parametrize("case", ["cpu", "wrong_count", "unknown_chip",
                                  "phase_raises", "check_fails",
                                  "tuned_table"])
def test_failure_exits_nonzero_with_ok_false(smoke, monkeypatch, capsys,
                                             case):
    if case == "cpu":
        pass  # the real device_doc: this process runs on the CPU
    elif case == "wrong_count":
        as_tpu(smoke, monkeypatch, 4)
    elif case == "unknown_chip":
        monkeypatch.setattr(smoke, "device_doc", lambda: {
            "platform": "tpu", "kind": "TPU v9 imaginary", "count": 1})
    else:
        as_tpu(smoke, monkeypatch, 1)
        ok_doc = {"checked": "nothing", "kernel_path": "none", "tune": {}}

        def passing(seed, meter):
            return dict(ok_doc)

        def failing(seed, meter):
            if case == "phase_raises":
                raise RuntimeError("the compiler refused the kernel")
            if case == "check_fails":
                smoke.check(False, "loss did not fall")
            return dict(ok_doc, tune={"paged_attention": "tuned"})

        monkeypatch.setattr(smoke, "PHASES", {
            1: (("first", failing), ("second", passing))})
    rc, lines = run(smoke, capsys, [])
    assert rc == 1
    assert lines[-1]["ok"] is False
    if case in ("cpu", "wrong_count", "unknown_chip"):
        # refused before any phase ran
        assert all("phase" not in ln for ln in lines)
        assert "error" in lines[-1]
    else:
        phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
        assert phases["first"]["ok"] is False and phases["first"]["error"]
        # a failed phase does not stop the next one from reporting...
        assert phases["second"]["ok"] is True
        # ...and nothing lets the run exit 0
        assert set(lines[-1]) == {"ok", "device"}


def test_tpu_place_raises_without_a_tpu_unless_the_cpu_was_asked_for():
    import jax

    import paddle_tpu as fluid

    assert fluid.TPUPlace(0).jax_device().platform == "cpu"  # conftest asked
    prev = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(RuntimeError, match="no accelerator"):
            fluid.TPUPlace(0)
        with pytest.raises(RuntimeError, match="no accelerator"):
            fluid.CUDAPlace(0)
    finally:
        jax.config.update("jax_platforms", prev)

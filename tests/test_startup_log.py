"""The compile log (``paddle_tpu/compile_cache.py``): one entry an
executable the process traced, lowered, compiled or loaded, by the seam's
label or JAX's own name; the three start-up phases; and the rule the log
turns on: no path that runs a cached executable calls a listener."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache as cc
from paddle_tpu import monitor, serving
from paddle_tpu.models import decoder_lm
from paddle_tpu.monitor import metrics as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DURATIONS = ("trace_s", "lower_s", "backend_s", "retrieval_s", "saved_s")


def _engine(buckets=(16,), vocab=64):
    model = decoder_lm.DecoderLM(
        decoder_lm.DecoderConfig(vocab_size=vocab, n_layer=2, d_model=32,
                                 n_head=2, max_seq=64), seed=0)
    return serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=buckets))


def _since(t0, labelled=None):
    return [e for e in cc.log() if e["t"] >= t0
            and (labelled is None or e["labelled"] == labelled)]


def _toy_program(seed=0):
    main, start = fluid.Program(), fluid.Program()
    start.random_seed = seed
    with fluid.program_guard(main, start):
        x = fluid.layers.data("x", shape=[8])
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, start, loss


def test_warmup_leaves_one_labelled_entry_an_executable_and_a_second_none():
    eng = _engine(buckets=(16, 32))
    t0 = time.perf_counter()
    eng.warmup()
    made = _since(t0, labelled=True)
    assert [e["name"] for e in made] == [
        "prefill[16]", "prefill[32]", "chunk[fuse=%d]" % eng.cfg.decode_fuse]
    for e in made:
        assert e["count"] == 1 and all(e[k] >= 0 for k in DURATIONS)
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
        # the tests keep the executable cache off (conftest.py)
        assert e["cache"] == "none" and e["retrieval_s"] == 0
        # the three stages lie inside the seam, whose length the entry has
        assert e["trace_s"] + e["lower_s"] + e["backend_s"] <= e["wall_s"]
        assert e["t"] <= e["t_last"] <= e["t"] + e["wall_s"]
    assert [e["t"] for e in made] == sorted(e["t"] for e in made)
    n = len(cc.log())
    eng.warmup()
    assert len(cc.log()) == n
    eng.close()


def test_a_bucket_nobody_warmed_is_a_later_entry_with_its_own_instant():
    eng = _engine(buckets=(16, 32))
    eng.warmup(buckets=(16,))
    eng.submit([1, 2, 3], 4)
    eng.run()
    served = time.perf_counter()
    eng.submit(list(range(20)), 2)      # the bucket of 32: compiled now
    eng.run()
    late = [e for e in _since(served) if e["labelled"]]
    assert [e["name"] for e in late] == ["prefill[32]"]
    first = next(e for e in cc.log() if e["name"] == "prefill[16]"
                 and e["t"] < served)
    # the recompile an operator looks for: the name, after the start
    assert late[0]["t"] > served > first["t_last"]
    assert "prefill[32]" in cc.report(since=served)
    assert "prefill[16]" not in cc.report(since=served)
    eng.close()


def test_a_program_files_one_step_entry_and_nothing_on_its_second_run():
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 8), np.float32)}
    main, start, loss = _toy_program()
    # a startup program runs op by op, and JAX keeps each such operation
    # for the process: where an earlier test of this worker ran a twin
    # (the same shapes), nothing would be built here and no entry filed
    jax.clear_caches()
    t0 = time.perf_counter()
    exe.run(start)
    exe.run(main, feed=feed, fetch_list=[loss])
    steps = _since(t0, labelled=True)
    fp = monitor.device.program_fingerprint
    assert [e["name"] for e in steps] == [
        "step[%s]" % fp(start)[:8], "step[%s]" % fp(main)[:8]]
    # the startup program made the weights: its first call was the phase
    assert steps[0]["phase"] == "startup/weights" and steps[1]["phase"] is None
    weights = [p for p in cc.phases() if p["t0"] >= t0]
    assert [p["name"] for p in weights] == ["startup/weights"]
    n, calls = len(cc.log()), cc.cost()["calls"]
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(start)
    assert len(cc.log()) == n and cc.cost()["calls"] == calls
    # a new feed shape is the SAME program again: a second entry of its name
    exe.run(main, feed={"x": np.ones((3, 8), np.float32)}, fetch_list=[loss])
    assert [e["name"] for e in _since(t0, labelled=True)][2:] == [
        "step[%s]" % fp(main)[:8]]


def test_prepare_observes_the_histogram_once_from_the_seams_own_length():
    exe = fluid.Executor(fluid.CPUPlace())
    main, start, loss = _toy_program()
    exe.run(start)
    hist = mx.histogram("executor/compile_time_ms")
    count, total = hist.count, hist.sum
    t0 = time.perf_counter()
    exe.prepare(main, feed={"x": ((2, 8), "float32")}, fetch_list=[loss])
    (entry,) = _since(t0, labelled=True)
    assert entry["name"].startswith("step[")
    assert hist.count == count + 1
    assert hist.sum - total == pytest.approx(entry["wall_s"] * 1e3)


def test_aot_compile_counts_one_a_compile_and_names_it():
    from paddle_tpu.executor import aot_compile

    def double(x):
        return x * 2

    hist = mx.histogram("executor/compile_time_ms")
    count = hist.count
    t0 = time.perf_counter()
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    aot_compile(double, args)
    aot_compile(double, args, label="resume[8]")
    assert [e["name"] for e in _since(t0)] == ["double", "resume[8]"]
    assert hist.count == count + 2


def test_eager_operations_merge_by_name_with_a_count():
    t0 = time.perf_counter()
    for n in (3, 5, 7, 9):      # a compile a shape, ONE name
        jnp.cumsum(jnp.arange(n, dtype=jnp.float32))
    merged = [e for e in _since(t0, labelled=False) if e["name"] == "cumsum"]
    assert len(merged) == 1 and merged[0]["count"] == 4
    assert merged[0]["t_last"] > merged[0]["t"]


def test_a_burst_long_after_the_last_is_an_entry_of_its_own(monkeypatch):
    monkeypatch.setattr(cc, "MERGE_WITHIN_S", 0.0)
    t0 = time.perf_counter()
    for n in (11, 13):
        jnp.cumprod(jnp.arange(n, dtype=jnp.float32))
    made = [e for e in _since(t0) if e["name"] == "cumprod"]
    assert [e["count"] for e in made] == [1, 1]


def test_a_function_traced_inside_another_is_the_outer_ones_time():
    @jax.jit
    def inner_part(x):
        return jnp.sin(x) * 2

    def outer_whole(x):
        return inner_part(x) + jnp.where(x > 0, x, 0)

    x = jnp.ones((4,))
    t0 = time.perf_counter()
    jax.jit(outer_whole)(x).block_until_ready()
    wall = time.perf_counter() - t0
    names = [e["name"] for e in _since(t0)]
    assert names == ["outer_whole"]     # no inner_part, sin, _where
    (e,) = _since(t0)
    assert 0 < e["trace_s"] + e["lower_s"] + e["backend_s"] <= wall


_CHILD = """
import json
import jax, jax.numpy as jnp
import paddle_tpu
from paddle_tpu import compile_cache as cc, monitor
from paddle_tpu.executor import aot_compile

def chunk(x):
    return jnp.tanh(x @ x.T).sum()

aot_compile(chunk, (jax.ShapeDtypeStruct((8, 8), jnp.float32),),
            label="chunk[fuse=1]")
jnp.ones((3,)) + 1
snap = monitor.snapshot()
print(json.dumps({"log": cc.log(), "phases": cc.phases(),
                  "hit": snap["compile_cache/hit"]["value"],
                  "miss": snap["compile_cache/miss"]["value"]}))
"""


def test_two_starts_over_one_cache_read_all_miss_then_all_hit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_ENABLE_COMPILATION_CACHE="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=ROOT)
    docs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        docs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = docs
    assert cold["log"] and {e["cache"] for e in cold["log"]} == {"miss"}
    assert {e["cache"] for e in warm["log"]} == {"hit"}
    assert [e["name"] for e in cold["log"]] == [e["name"]
                                                for e in warm["log"]]
    assert "chunk[fuse=1]" in [e["name"] for e in warm["log"]]
    for e in cold["log"]:
        assert e["retrieval_s"] == 0 and e["backend_s"] > 0
    for e in warm["log"]:
        assert e["retrieval_s"] > 0 and e["backend_s"] >= 0
    # the counters are fed from the same place: they agree with the log
    for doc in docs:
        assert doc["hit"] == sum(e["count"] for e in doc["log"]
                                 if e["cache"] == "hit")
        assert doc["miss"] == sum(e["count"] for e in doc["log"]
                                  if e["cache"] == "miss")
        # the package's own import is the process's first phase
        assert [p["name"] for p in doc["phases"]] == ["startup/import"]
        assert doc["phases"][0]["t1"] > doc["phases"][0]["t0"]


def test_the_three_phases_lie_inside_the_process_and_never_overlap():
    born = min(p["t0"] for p in cc.phases())
    t0 = time.perf_counter()
    eng = _engine(vocab=72)     # shapes nobody compiled for yet
    eng.close()
    now = time.perf_counter()
    spans = cc.phases()
    assert spans[0]["name"] == "startup/import" and spans[0]["t0"] == born
    mine = [p for p in spans if p["t0"] >= t0]
    assert [p["name"] for p in mine] == ["startup/weights", "startup/pools"]
    for p in spans:
        assert born <= p["t0"] < p["t1"] <= now
    ordered = sorted(spans, key=lambda p: p["t0"])
    for a, b in zip(ordered, ordered[1:]):
        assert a["t1"] <= b["t0"]
    # what the weights' eager programs cost is their entries', by phase
    inside = [e for e in _since(t0) if e["phase"] == "startup/weights"]
    assert inside and all(
        mine[0]["t0"] <= e["t"] <= mine[0]["t1"] for e in inside)


def test_init_params_under_a_jit_is_that_traces_time_and_no_phase():
    cfg = decoder_lm.DecoderConfig(vocab_size=32, n_layer=1, d_model=16,
                                   n_head=2, max_seq=16)
    n = len(cc.phases())
    jax.jit(lambda s: decoder_lm.init_params(cfg, s))(jnp.int32(3))
    assert len(cc.phases()) == n
    decoder_lm.init_params(cfg, 3)
    assert [p["name"] for p in cc.phases()[n:]] == ["startup/weights"]


def test_the_report_renders_every_entry_and_the_totals():
    eng = _engine()
    eng.warmup()
    eng.close()
    table = cc.report().splitlines()
    assert table[0].split() == ["t_s", "name", "n", "trace_s", "lower_s",
                                "backend_s", "load_s", "cache"]
    assert len(table) == 2 + len(cc.log()) + len(cc.phases())
    assert any(" prefill[16] " in row for row in table)
    assert any(" startup/pools " in row for row in table)
    assert table[-1].startswith("total: %d entries" % len(cc.log()))
    assert "listeners: %d calls" % cc.cost()["calls"] in table[-1]
    # copies: a caller cannot edit the log
    cc.log()[0]["name"] = "mine"
    assert cc.log()[0]["name"] != "mine"


def test_fifty_decode_cycles_call_no_listener():
    """The rule: serving from cached executables does no work here."""
    eng = _engine()
    eng.warmup()
    eng.submit([1, 2, 3], 4)    # the engine's one-operation programs
    eng.run()                   # compile on first use: served to its end
    eng.submit([4, 5, 6, 7], 60)
    eng.step()                  # the admission
    calls, entries = cc.cost()["calls"], len(cc.log())
    for _ in range(50):
        eng.step()
    assert eng.scheduler.occupancy == 1     # still decoding
    assert cc.cost()["calls"] == calls and len(cc.log()) == entries
    eng.close()

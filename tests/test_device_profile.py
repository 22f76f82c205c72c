"""Device-side observability (monitor/device.py): per-op named-scope
attribution in lowered HLO, cost/memory gauges from the AOT path, the
PADDLE_TPU_CHECK_NUMERICS=2 in-graph watchdog (run + run_steps, OPT_LEVEL
0 and 1), collective byte accounting on the 8-device CPU mesh, and the
flight-recorder crash-dump round-trip."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.monitor import device as dev
from paddle_tpu.monitor import metrics as mx


def _mlp_train(dim=8, hidden=16, classes=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[dim])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        logits = fluid.layers.fc(h, size=classes)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _prepare_mlp(batch=4):
    main, startup, loss = _mlp_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    compiled = exe.prepare(
        main, feed={"x": ((batch, 8), "float32"),
                    "y": ((batch, 1), "int64")},
        fetch_list=[loss])
    return exe, main, loss, compiled


# -- 1. per-op attribution ----------------------------------------------------

def test_named_scopes_in_lowered_hlo():
    """Every Program op's <slot>:<type> scope survives into the lowered
    module's debug locations (fwd ops additionally under jvp(...))."""
    _, main, _, compiled = _prepare_mlp()
    txt = dev.lowered_scope_text(compiled._lowered)
    cov = dev.op_scope_coverage(txt)
    assert cov, "no named scopes in lowered HLO"
    types = {k.split(":", 1)[1] for k in cov}
    assert "mul" in types, cov          # fwd matmul (under jvp scope)
    assert "sgd" in types, cov          # optimizer op (plain scope)
    # labels are <source-op-index>:<type> — slot must be a valid op index
    n_ops = len(main.global_block.ops)
    assert all(0 <= int(k.split(":")[0]) < n_ops for k in cov), cov


def test_scopes_disabled_by_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OP_SCOPES", "0")
    _, _, _, compiled = _prepare_mlp()
    cov = dev.op_scope_coverage(dev.lowered_scope_text(compiled._lowered))
    assert not cov, "PADDLE_TPU_OP_SCOPES=0 left scopes in HLO: %s" % cov


def test_cost_memory_gauges_populated_on_cpu():
    mx.enable()
    mx.reset()
    exe, main, loss, compiled = _prepare_mlp()
    snap = mx.snapshot()
    assert snap["device_profile/flops"]["value"] > 0
    assert snap["device_profile/bytes_accessed"]["value"] > 0
    assert snap["device_profile/peak_hbm_bytes"]["value"] > 0
    assert snap["device_profile/analyses"]["value"] >= 1
    # the full report: measured totals + analytic rows with stable slots
    rep = dev.step_report(compiled.program, compiled._aot, batch_size=4)
    assert rep["cost"]["flops"] > 0
    assert rep["memory"]["peak_hbm_bytes"] > 0
    rows = rep["op_costs"]
    assert rows and rows[0]["flops"] >= rows[-1]["flops"]  # sorted desc
    assert any(r["type"] == "mul" and r["intensity"] > 0 for r in rows)


def test_memory_report_pre_run():
    """Executor.memory_report: the authoritative pre-run figure
    (contrib.utils.memory_usage's docstring defers to it)."""
    main, startup, loss = _mlp_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rep = exe.memory_report(
        main, feed={"x": ((4, 8), "float32"), "y": ((4, 1), "int64")},
        fetch_list=[loss])
    assert rep["peak_hbm_bytes"] > 0
    assert rep["argument_bytes"] > 0
    for k in ("output_bytes", "temp_bytes"):
        assert k in rep


# -- 2. numerics watchdog -----------------------------------------------------

def _nan_prog():
    """log(x) at a known op position; feeding zeros makes THAT op the
    first non-finite producer (mean propagates downstream)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        # baggage BEFORE the faulting op, removed by OPT_LEVEL=1 DCE:
        # positional renumbering would shift the log op's index
        dead = fluid.layers.fc(x, size=8)
        bad = fluid.layers.log(x)
        out = fluid.layers.mean(bad)
    log_idx = [i for i, op in enumerate(main.global_block.ops)
               if op.type == "log"]
    assert len(log_idx) == 1
    return main, startup, out, log_idx[0]


@pytest.mark.parametrize("opt_level", ["0", "1"])
def test_watchdog_names_originating_op_run(monkeypatch, opt_level):
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    monkeypatch.setenv("PADDLE_TPU_OPT_LEVEL", opt_level)
    main, startup, out, log_idx = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(EnforceNotMet) as ei:
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])
    msg = str(ei.value)
    # attributed to the SOURCE program's op index even after DCE deleted
    # the dead fc ops ahead of it (slot stamping, passes/analysis.py)
    assert "%d:log" % log_idx in msg, msg
    assert "CHECK_NUMERICS" in msg


@pytest.mark.parametrize("opt_level", ["0", "1"])
def test_watchdog_under_run_steps_fused_chunk(monkeypatch, opt_level):
    """The packed mask rides the fused chunk per step: a NaN planted in
    step 1 of a 4-step chunk is attributed to op AND step (the legacy
    post-step scan only ever saw the last fetch)."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    monkeypatch.setenv("PADDLE_TPU_OPT_LEVEL", opt_level)
    main, startup, out, log_idx = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    ones = np.ones((2, 4), "float32")
    feeds = iter([{"x": ones}, {"x": np.zeros((2, 4), "float32")},
                  {"x": ones}, {"x": ones}])
    with pytest.raises(EnforceNotMet) as ei:
        exe.run_steps(main, feeds, steps=4, fetch_list=[out], fetch_every=4)
    msg = str(ei.value)
    assert "%d:log" % log_idx in msg, msg
    assert "step 1 of the fused chunk" in msg, msg
    assert "run_steps" in msg


def test_watchdog_and_stats_exclude_sub_blocks(monkeypatch):
    """Regression: CHECK_NUMERICS=2 (and armed streaming stats) over a
    While sub-block must compile and run — a watchdog bit or stat row
    born inside a lax.while body cannot be stacked outside it, so the
    interpreter gates both collectors on the sub-block offset. Top-level
    ops keep full attribution; sub-block ops contribute nothing."""
    from paddle_tpu.monitor import numerics as num

    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    monkeypatch.setenv("PADDLE_TPU_NUMERICS", "1")
    monkeypatch.setenv("PADDLE_TPU_NUMERICS_EVERY", "1")
    num.reset()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            i = fluid.layers.fill_constant([1], "int32", 0)
            n = fluid.layers.fill_constant([1], "int32", 4)
            s = fluid.layers.fill_constant([1], "float32", 0.0)
            cond = fluid.layers.less_than(i, n)
            w = fluid.layers.While(cond)
            with w.block():
                fluid.layers.assign(fluid.layers.cast(i, "float32") + s, s)
                fluid.layers.increment(i, value=1, in_place=True)
                fluid.layers.less_than(i, n, cond=cond)
            bad = fluid.layers.log(x)
            out = fluid.layers.mean(bad)
        log_idx = [k for k, op in enumerate(main.global_block.ops)
                   if op.type == "log"][0]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ones = np.ones((2, 4), "float32")
        sv, ov = exe.run(main, feed={"x": ones}, fetch_list=[s, out])
        assert float(np.asarray(sv).item()) == sum(range(4))
        assert np.isfinite(np.asarray(ov)).all()
        # streaming stats saw only top-level ops: every recorded label's
        # slot sits below the 10_000 sub-block offset, and none of the
        # loop body's op types appear
        snap = num.snapshot()
        assert snap, "armed run folded no stats"
        for label in snap:
            slot, _, typ = label.partition(":")
            assert int(slot) < 10_000, label
            assert typ not in ("increment", "assign"), label
        # the watchdog still attributes a top-level NaN by source slot
        with pytest.raises(EnforceNotMet) as ei:
            exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                    fetch_list=[s, out])
        assert "%d:log" % log_idx in str(ei.value)
    finally:
        num.reset()


def test_watchdog_silent_on_finite_and_cache_keyed(monkeypatch):
    """Level 2 on finite data: no raise; flipping the env var re-plans
    (guarded/unguarded variants must not share a cache entry)."""
    main, startup, out, _ = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    ones = np.ones((2, 4), "float32")
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "0")
    r0, = exe.run(main, feed={"x": ones}, fetch_list=[out])
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    r2, = exe.run(main, feed={"x": ones}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r2), rtol=1e-6)
    # and the guarded variant still catches after the unguarded ran
    with pytest.raises(EnforceNotMet):
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])


def test_level1_fused_reduction_backstop(monkeypatch):
    """Level 1 (and legacy FLAGS_check_nan_inf): ONE fused device-side
    isfinite reduction, legacy error message naming the offending fetch."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "1")
    main, startup, out, _ = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(RuntimeError) as ei:
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])
    assert "FLAGS_check_nan_inf" in str(ei.value)


def test_check_numerics_mask_helper():
    layout = [("0:mul", ("a",)), ("1:log", ("b",)), ("2:mean", ("c",))]
    dev.check_numerics_mask(np.ones(3, bool), layout)  # all finite: no-op
    with pytest.raises(EnforceNotMet) as ei:
        dev.check_numerics_mask(np.array([True, False, False]), layout)
    msg = str(ei.value)
    assert "1:log" in msg and "2:mean" in msg  # first + propagation
    # stacked [steps, K] chunk: step index reported
    m = np.ones((3, 3), bool)
    m[2, 1] = False
    with pytest.raises(EnforceNotMet) as ei:
        dev.check_numerics_mask(m, layout, driver="run_steps")
    assert "step 2 of the fused chunk" in str(ei.value)


def test_watchdog_attributes_early_microbatch_under_accumulation(monkeypatch):
    """Gradient accumulation scans microbatches; the watchdog bits must be
    ANDed across the chain — a NaN born in microbatch 0 of 4 is attributed
    to the originating forward op, not to the optimizer update it poisons."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        bad = fluid.layers.log(x)
        loss = fluid.layers.mean(bad)
        fluid.optimizer.SGD(0.1).minimize(loss)
    log_idx = [i for i, op in enumerate(main.global_block.ops)
               if op.type == "log"][0]
    bs = fluid.BuildStrategy()
    bs.gradient_accumulation_steps = 4
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, build_strategy=bs)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = np.ones((32, 4), "float32")
    feed[:8] = 0.0  # only microbatch 0 of 4 hits log(0)
    with pytest.raises(EnforceNotMet) as ei:
        exe.run(compiled, feed={"x": feed}, fetch_list=[loss])
    msg = str(ei.value)
    assert "%d:log" % log_idx in msg, msg


# -- 3. collective traffic accounting -----------------------------------------

def test_ring_attention_ppermute_bytes_counted():
    import jax.numpy as jnp

    from paddle_tpu.parallel.mesh import create_mesh
    from paddle_tpu.parallel.ring_attention import ring_attention

    mx.enable()
    mx.reset()
    sp = 4
    mesh = create_mesh({"sp": sp})
    b, h, s, d = 2, 2, 8 * sp, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d).astype("float32"))
    with mesh:
        out = ring_attention(q, q + 0.1, q + 0.2, mesh, axis_name="sp")
    assert np.isfinite(np.asarray(out)).all()
    snap = dev.collectives_snapshot()
    # fwd records K and V rotations: 2 buffers x sp hops of the local
    # [b, h, s/sp, d] f32 block, per device
    blk = b * h * (s // sp) * d * 4
    assert snap.get("collectives/ppermute/bytes") == 2 * sp * blk, snap
    assert snap.get("collectives/ppermute/sp/bytes") == 2 * sp * blk
    assert snap.get("collectives/ppermute/calls") == 2 * sp


def test_all_to_all_bytes_counted_in_row_routing():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.sparse import route_rows_to_shards
    from paddle_tpu.parallel.mesh import create_mesh
    from jax.sharding import PartitionSpec as P

    mx.enable()
    mx.reset()
    n, d, nsh = 16, 4, 8
    mesh = create_mesh({"model": nsh})
    ids = np.arange(n * nsh, dtype=np.int64) % (nsh * 10)
    rows = np.ones((n * nsh, d), np.float32)

    def body(i, r):
        return route_rows_to_shards(i, r, nsh, 10, "model",
                                    invalid_index=nsh * 10)

    with mesh:
        rid, rrow = jax.shard_map(
            body, mesh=mesh, in_specs=(P("model"), P("model", None)),
            out_specs=(P("model"), P("model", None)),
            check_vma=False)(ids, rows)
    snap = dev.collectives_snapshot()
    assert snap.get("collectives/all_to_all/bytes", 0) > 0, snap
    assert snap.get("collectives/all_to_all/model/bytes", 0) > 0


def test_record_collective_shapes_and_gating(monkeypatch):
    mx.enable()
    mx.reset()
    arr = np.zeros((4, 8), np.float32)
    dev.record_collective("psum", "data", arr, per_step_calls=3)
    snap = dev.collectives_snapshot()
    assert snap["collectives/psum/bytes"] == 4 * 8 * 4 * 3
    assert snap["collectives/psum/calls"] == 3
    assert snap["collectives/psum/data/bytes"] == 4 * 8 * 4 * 3
    # disabled registry: inert
    mx.reset()
    mx.disable()
    try:
        dev.record_collective("psum", "data", arr)
        assert not dev.collectives_snapshot()
    finally:
        mx.enable()


# -- 4. flight recorder -------------------------------------------------------

def test_flight_recorder_dump_roundtrip(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    main, startup, out, log_idx = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    ones = np.ones((2, 4), "float32")
    exe.run(main, feed={"x": ones}, fetch_list=[out])  # a good step first
    with pytest.raises(EnforceNotMet):
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])
    dumps = sorted(tmp_path.glob("flight_*.json"))
    assert dumps, "no flight-recorder dump on crash"
    with open(dumps[-1]) as f:
        doc = json.load(f)
    assert doc["reason"] == "executor.run"
    assert "%d:log" % log_idx in doc["exception"]
    steps = [e for e in doc["entries"] if e.get("driver") == "run"]
    assert len(steps) >= 2  # the good step AND the crashing step
    last = steps[-1]
    assert last["feed"] == [["x", "float32", [2, 4]]]
    assert last["fetch"] == [out.name]
    assert last["program"] == dev.program_fingerprint(main)
    assert "opt_level" in last and "metrics" in last
    assert doc["env"].get("PADDLE_TPU_CHECK_NUMERICS") == "2"


def test_flight_recorder_ring_capacity(tmp_path):
    fr = dev.FlightRecorder(str(tmp_path), capacity=3)
    main, startup, out, _ = _nan_prog()
    for i in range(7):
        fr.record_step("run", main, [("x", "float32", (2, 4))], ("out",),
                       extra={"i": i})
    path = fr.dump("test")
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["entries"]) == 3
    assert [e["i"] for e in doc["entries"]] == [4, 5, 6]  # last N kept


def test_flight_recorder_unwritable_dir_preserves_original_error(
        monkeypatch, tmp_path):
    """A failing crash-dump (unwritable PADDLE_TPU_FLIGHT_DIR) must never
    replace the step error it was meant to explain."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("")
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(blocker / "sub"))
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    main, startup, out, log_idx = _nan_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(EnforceNotMet) as ei:  # NOT the dump's OSError
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[out])
    assert "%d:log" % log_idx in str(ei.value)


def test_flight_recorder_off_by_default(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FLIGHT_DIR", raising=False)
    assert dev.flight_recorder() is None


def test_program_fingerprint_tracks_structure():
    main, startup, out, _ = _nan_prog()
    fp1 = dev.program_fingerprint(main)
    assert fp1 == dev.program_fingerprint(main)  # memoized, stable
    with fluid.program_guard(main, startup):
        fluid.layers.mean(main.global_block.var(out.name))
    assert dev.program_fingerprint(main) != fp1  # structure changed


# -- run_steps + device profile compose ---------------------------------------

def test_run_steps_finite_with_watchdog(monkeypatch):
    """Guarded run_steps on finite data matches the unguarded driver.
    Fresh programs per mode (param init and the per-step RNG ride the
    program's step counter, so re-running startup on ONE program would
    draw different weights, not expose a watchdog difference)."""
    rng = np.random.RandomState(0)
    batches = [{"x": rng.randn(4, 8).astype("float32"),
                "y": rng.randint(0, 4, (4, 1)).astype("int64")}
               for _ in range(4)]

    def losses():
        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                main, startup, loss = _mlp_train()
                main.random_seed = startup.random_seed = 11
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                return [r[0] for r in exe.run_steps(
                    main, iter(batches), steps=4, fetch_list=[loss],
                    fetch_every=2)]

    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "0")
    plain = losses()
    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    guarded = losses()
    np.testing.assert_allclose(plain, guarded, rtol=1e-6)

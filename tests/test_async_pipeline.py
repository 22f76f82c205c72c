"""The async step pipeline: FetchHandle, run_steps, dispatch-plan cache,
prefetcher lifecycle, AOT prepare.

The load-bearing guarantee is numeric: the fused ``run_steps(fetch_every=k)``
driver and the non-blocking ``FetchHandle`` path must be BIT-IDENTICAL to
the plain per-step ``run()`` loop — same RNG stream (the step counter
carried through the scan), same optimizer state trajectory, same losses.
"""

import threading
import time
import traceback

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.executor import FetchHandle
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.reader import DevicePrefetcher


def _mlp_program(with_dropout=True):
    """Tiny trainable MLP; dropout makes the per-step RNG stream observable
    so any counter drift between drivers breaks bit-for-bit parity."""
    x = fluid.layers.data("x", shape=[8])
    y = fluid.layers.data("y", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=8, act="relu")
    if with_dropout:
        h = fluid.layers.dropout(h, dropout_prob=0.3)
    logits = fluid.layers.fc(h, size=3)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.Adam(1e-2).minimize(loss)
    return loss


def _feeds(rng, n, batch=4):
    return [{"x": rng.randn(batch, 8).astype("float32"),
             "y": rng.randint(0, 3, (batch, 1)).astype("int64")}
            for _ in range(n)]


def _fresh(build=_mlp_program):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return exe, main, loss


# -- FetchHandle --------------------------------------------------------------

def test_fetch_handle_matches_sync_run(rng):
    exe, main, loss = _fresh()
    feeds = _feeds(rng, 4)
    sync = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds[:2]]

    handle = exe.run(main, feed=feeds[2], fetch_list=[loss],
                     return_numpy=False)
    assert isinstance(handle, FetchHandle)
    assert len(handle) == 1 and handle.names == (loss.name,)
    # sequence protocol: raw device arrays, unpacking keeps working
    lv, = handle
    resolved, = handle.numpy()
    assert np.array_equal(resolved, np.asarray(lv))
    # numpy() is cached and stable
    again, = handle.numpy()
    assert np.array_equal(resolved, again)
    handle.block()
    assert handle.done()

    # the async path sits on the same trajectory as the sync one
    sync.append(resolved)
    exe2, main2, loss2 = _fresh()
    ref = [exe2.run(main2, feed=f, fetch_list=[loss2])[0] for f in feeds[:3]]
    for a, b in zip(ref, sync):
        assert np.array_equal(a, b)


def test_fetch_bytes_accounting_is_deferred_to_resolve(rng):
    exe, main, loss = _fresh()
    feed = _feeds(rng, 1)[0]
    exe.run(main, feed=feed, fetch_list=[loss])  # compile outside the probe
    mx.reset()
    h = exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    assert mx.snapshot()["executor/fetch_bytes"]["value"] == 0
    out, = h.numpy()
    assert mx.snapshot()["executor/fetch_bytes"]["value"] == out.nbytes


# -- run_steps ----------------------------------------------------------------

def test_run_steps_bitwise_matches_per_step_run(rng):
    feeds = _feeds(rng, 10)

    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    mx.reset()
    rows = exe2.run_steps(main2, iter(feeds), steps=10, fetch_list=[loss2],
                          fetch_every=4)  # chunks of 4, 4, 2
    assert len(rows) == 10
    for a, row in zip(ref, rows):
        assert np.array_equal(a, row[0])

    snap = mx.snapshot()
    assert snap["executor/run_steps_steps"]["value"] == 10
    # 10 steps in 3 fused dispatches (4+4+2)
    assert snap["executor/run_steps_dispatches"]["value"] == 3


def test_run_steps_dispatch_reduction_8x(rng):
    """The acceptance-criteria shape: fetch_every=8 → dispatches/step ÷ 8,
    losses bit-identical to the per-step loop."""
    feeds = _feeds(rng, 16)

    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    mx.reset()
    rows = exe2.run_steps(main2, iter(feeds), steps=16, fetch_list=[loss2],
                          fetch_every=8)
    snap = mx.snapshot()
    assert snap["executor/run_steps_dispatches"]["value"] == 2  # 16 steps / 8
    for a, row in zip(ref, rows):
        assert np.array_equal(a, row[0])


def test_run_steps_interleaves_with_run(rng):
    """run() → run_steps() → run() shares one step-counter stream and one
    scope state; the combined trajectory equals a pure run() loop."""
    feeds = _feeds(rng, 8)

    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    got = [exe2.run(main2, feed=feeds[0], fetch_list=[loss2])[0]]
    rows = exe2.run_steps(main2, iter(feeds[1:7]), steps=6,
                          fetch_list=[loss2], fetch_every=3)
    got += [r[0] for r in rows]
    got.append(exe2.run(main2, feed=feeds[7], fetch_list=[loss2])[0])
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)


def test_run_steps_return_handles(rng):
    feeds = _feeds(rng, 6)
    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    handles = exe2.run_steps(main2, iter(feeds), steps=6, fetch_list=[loss2],
                             fetch_every=3, return_numpy=False)
    assert len(handles) == 2 and all(isinstance(h, FetchHandle)
                                     for h in handles)
    stacked = [h.numpy()[0] for h in handles]
    assert stacked[0].shape[0] == 3  # leading axis = chunk length
    flat = [row for s in stacked for row in s]
    for a, b in zip(ref, flat):
        assert np.array_equal(a, b)


def test_run_steps_drains_device_prefetcher(rng):
    feeds = _feeds(rng, 6)
    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    with DevicePrefetcher(iter(feeds), capacity=2) as pf:
        rows = exe2.run_steps(main2, pf, steps=6, fetch_list=[loss2],
                              fetch_every=2)
    for a, row in zip(ref, rows):
        assert np.array_equal(a, row[0])


def test_run_steps_stops_at_feed_exhaustion(rng):
    feeds = _feeds(rng, 5)
    exe, main, loss = _fresh()
    rows = exe.run_steps(main, iter(feeds), steps=None, fetch_list=[loss],
                         fetch_every=4)  # 4 + 1, steps unbounded
    assert len(rows) == 5


def test_run_steps_partial_final_batch_re_resolves(rng):
    """The last batch of a real epoch is smaller — run_steps must re-plan
    for the new shape mid-stream (like run()'s per-shape plans), matching
    the run()-per-step trajectory bit-for-bit."""
    feeds = _feeds(rng, 5, batch=4) + _feeds(rng, 1, batch=2)

    exe, main, loss = _fresh()
    ref = [exe.run(main, feed=f, fetch_list=[loss])[0] for f in feeds]

    exe2, main2, loss2 = _fresh()
    mx.reset()
    rows = exe2.run_steps(main2, iter(feeds), steps=6, fetch_list=[loss2],
                          fetch_every=4)
    # the collector cuts chunks at shape boundaries: 4@b4 | 1@b4 | 1@b2
    assert mx.snapshot()["executor/run_steps_dispatches"]["value"] == 3
    assert len(rows) == 6
    for a, row in zip(ref, rows):
        assert np.array_equal(a, row[0])


def test_run_steps_stops_owned_prefetcher_on_early_exit(rng):
    def endless():
        r = np.random.RandomState(0)
        while True:
            yield {"x": r.randn(4, 8).astype("float32"),
                   "y": r.randint(0, 3, (4, 1)).astype("int64")}

    # run_steps starts it -> run_steps stops it at steps
    exe, main, loss = _fresh()
    pf = DevicePrefetcher(endless(), capacity=2)
    rows = exe.run_steps(main, pf, steps=4, fetch_list=[loss], fetch_every=2)
    assert len(rows) == 4
    deadline = time.time() + 2.0
    while pf._thread.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not pf._thread.is_alive(), "run_steps abandoned its prefetcher"

    # caller-started prefetcher stays the caller's to stop
    exe2, main2, loss2 = _fresh()
    pf2 = DevicePrefetcher(endless(), capacity=2).start()
    exe2.run_steps(main2, pf2, steps=4, fetch_list=[loss2], fetch_every=2)
    assert pf2._thread.is_alive()
    pf2.stop()


def test_run_steps_grad_norm_gauge(rng, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_GRAD_NORM", "1")
    exe, main, loss = _fresh()
    assert monitor.GRAD_NORM_VAR in main.global_block.vars
    feeds = _feeds(rng, 4, batch=8)
    mx.reset()
    rows = exe.run_steps(main, iter(feeds), steps=4, fetch_list=[loss],
                         fetch_every=4)
    assert len(rows) == 4 and rows[0][0].size == 1  # hidden fetch stripped
    assert mx.snapshot()["optimizer/grad_global_norm"]["value"] > 0


def test_grad_norm_gauge_defers_to_handle_resolve(rng, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_GRAD_NORM", "1")
    exe, main, loss = _fresh()
    feed = _feeds(rng, 1, batch=8)[0]
    exe.run(main, feed=feed, fetch_list=[loss])
    mx.reset()
    h = exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    assert mx.snapshot()["optimizer/grad_global_norm"]["value"] == 0
    h.numpy()
    assert mx.snapshot()["optimizer/grad_global_norm"]["value"] > 0


# -- dispatch-plan cache ------------------------------------------------------

def test_dispatch_plan_cache_hits_and_invalidates_on_version_bump(rng):
    exe, main, loss = _fresh()
    feed = _feeds(rng, 1)[0]
    exe.run(main, feed=feed, fetch_list=[loss])
    mx.reset()
    exe.run(main, feed=feed, fetch_list=[loss])
    snap = mx.snapshot()
    assert snap["executor/plan_hit"]["value"] == 1
    assert snap["executor/plan_miss"]["value"] == 0
    assert snap["executor/cache_hit"]["value"] == 1

    # a program mutation bumps _version -> every cached plan is dropped
    v0 = main._version
    main.random_seed = 1234  # bumps version (seed is baked into the step)
    assert main._version > v0
    mx.reset()
    out, = exe.run(main, feed=feed, fetch_list=[loss])
    snap = mx.snapshot()
    assert snap["executor/plan_miss"]["value"] == 1
    assert snap["executor/cache_miss"]["value"] == 1  # new specialization too
    assert np.isfinite(out).all()


def test_dispatch_plan_misses_on_shape_change(rng):
    exe, main, loss = _fresh()
    exe.run(main, feed=_feeds(rng, 1, batch=4)[0], fetch_list=[loss])
    mx.reset()
    exe.run(main, feed=_feeds(rng, 1, batch=6)[0], fetch_list=[loss])
    snap = mx.snapshot()
    assert snap["executor/plan_hit"]["value"] == 0
    assert snap["executor/plan_miss"]["value"] == 1
    # and back: the original plan still hits
    mx.reset()
    exe.run(main, feed=_feeds(rng, 1, batch=4)[0], fetch_list=[loss])
    assert mx.snapshot()["executor/plan_hit"]["value"] == 1


def test_close_clears_caches_and_counter_dies_with_program(rng):
    exe, main, loss = _fresh()
    feed = _feeds(rng, 1)[0]
    exe.run(main, feed=feed, fetch_list=[loss])
    assert exe._cache
    assert getattr(main, "_tpu_step_counter", 0) > 0
    exe.close()
    assert not exe._cache
    # no executor-held per-program dict left to leak (the old bug)
    assert not hasattr(exe, "_step_counters")
    # plans + counters live on Program objects -> freed with them. Since the
    # default trace-time optimizer (PADDLE_TPU_OPT_LEVEL>=1), plans attach
    # to the optimized clone, which the SOURCE program owns via _opt_cache —
    # the chain still dies with `main`.
    optimized = exe._maybe_optimize(main, (loss.name,), fluid.global_scope())
    assert hasattr(optimized, "_dispatch_plans")
    if optimized is not main:
        assert any(optimized is p for _, p in main._opt_cache[1].values())


# -- prefetcher lifecycle -----------------------------------------------------

def test_prefetcher_propagates_worker_traceback(rng):
    def bad_source():
        yield {"x": np.ones((2, 2), "float32")}
        raise ValueError("exploding reader")

    pf = DevicePrefetcher(bad_source(), capacity=2)
    with pytest.raises(ValueError, match="exploding reader") as ei:
        for _ in pf:
            pass
    tb = "".join(traceback.format_tb(ei.value.__traceback__))
    assert "bad_source" in tb  # the worker's original frame survived


def test_prefetcher_stop_unblocks_worker(rng):
    def endless():
        i = 0
        while True:
            yield {"x": np.full((4,), i, "float32")}
            i += 1

    pf = DevicePrefetcher(endless(), capacity=2)
    it = iter(pf)
    next(it), next(it)
    assert pf._thread.is_alive()
    pf.stop()
    deadline = time.time() + 2.0
    while pf._thread.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not pf._thread.is_alive(), "stop() left the worker blocked"
    with pytest.raises(RuntimeError):
        pf.start()  # one-shot: no silent restart on a drained source


def test_prefetcher_reiterate_after_exhaustion_terminates(rng):
    """A second epoch loop over a drained prefetcher must terminate
    immediately (one worker per prefetcher now), not block in q.get()."""
    pf = DevicePrefetcher(iter([{"x": np.ones((2,), "float32")}]), capacity=2)
    assert len(list(pf)) == 1
    assert list(pf) == []  # immediate, no hang


def test_prefetcher_context_manager(rng):
    def endless():
        while True:
            yield {"x": np.zeros((4,), "float32")}

    with DevicePrefetcher(endless(), capacity=2) as pf:
        for i, feed in enumerate(pf):
            assert feed["x"].shape == (4,)
            if i >= 2:
                break
    t = pf._thread
    deadline = time.time() + 2.0
    while t.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not t.is_alive()


# -- AOT prepare --------------------------------------------------------------

def test_prepare_shares_cache_entry_with_run(rng):
    import jax

    exe, main, loss = _fresh()
    exe.prepare(main, feed={"x": jax.ShapeDtypeStruct((4, 8), np.float32),
                            "y": ((4, 1), "int64")}, fetch_list=[loss])
    mx.reset()
    out, = exe.run(main, feed=_feeds(rng, 1)[0], fetch_list=[loss])
    snap = mx.snapshot()
    assert snap["executor/cache_miss"]["value"] == 0  # prepare pre-built it
    assert np.isfinite(out).all()


def test_compile_cache_counters_registered():
    snap = mx.snapshot()
    assert "compile_cache/hit" in snap
    assert "compile_cache/miss" in snap


@pytest.mark.parametrize("placed", ["from_outside", "unset"])
def test_compile_cache_location(placed, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and the package sets no
    directory in code. Unset: the one fixed path beside the package, which
    .gitignore lists."""
    import os

    import jax
    from jax import monitoring

    from paddle_tpu import compile_cache as cc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(cc.__file__)))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(monitoring, "register_event_listener",
                        lambda fn: None)  # the real one is already hooked
    monkeypatch.setattr(cc, "_configured", False)
    if placed == "from_outside":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        cc.setup_compile_cache()
        assert cc.compile_cache_dir() == str(tmp_path)
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cc.setup_compile_cache()
        fixed = os.path.join(repo, ".jax_cache")
        assert cc.compile_cache_dir() == fixed
        assert updates == [("jax_compilation_cache_dir", fixed)]
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def test_second_step_does_not_compile_again(rng):
    """The startup program's outputs are uncommitted and the step's own
    outputs committed; jit specializes on that, so without _place
    committing the state the SECOND run compiled the whole step again."""
    from jax import monitoring

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _mlp_program()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    feed = _feeds(rng, 1)[0]
    exe.run(main, feed=feed, fetch_list=[loss])
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    assert compiles == []

"""Diagnose the framework-vs-raw-JAX gap at the XLA level.

Lowers both the paddle_tpu transformer train step and bench.py's raw-JAX
twin at identical shapes, compiles, and prints XLA cost analysis (flops,
bytes accessed) plus a measured per-step time for each. The delta in flops
or bytes names the part of the traced program that raw JAX doesn't have.

Usage: python benchmarks/diag_overhead.py          (on the TPU)
       python benchmarks/diag_overhead.py --host   (any backend, incl. CPU)
       python benchmarks/diag_overhead.py --opt    (any backend, incl. CPU)

``--host`` measures pure HOST dispatch overhead on a tiny MLP where device
compute is negligible: per-step wall time of the cache-hit ``run()`` path
(the dispatch-plan cache's hot path) and of the fused
``run_steps(fetch_every=8)`` driver, plus dispatches-per-step from the
monitor counters — the number the async-pipeline work optimizes.

``--opt`` is the CPU MLP probe for the default trace-time optimizer
(paddle_tpu.passes): builds the same MLP with a metrics side branch and a
constant chain, runs it at ``PADDLE_TPU_OPT_LEVEL=0`` and ``=1``, and
reports traced-op count, trace+compile wall time of the first step, and a
bit-identity check on the losses (dropout RNG included). Exits non-zero if
level 1 fails to shrink the program or perturbs a loss bit.

``--numerics`` is the CPU MLP probe for the streaming tensor-statistics
layer (paddle_tpu.monitor.numerics): cache-hit steady-state ms/step with
``PADDLE_TPU_NUMERICS`` off vs armed (level 1), the measured overhead
ratio, and a bit-identity check of the off-mode losses against a build
that never armed stats. Exits non-zero if the armed overhead exceeds the
documented <=15% contract or level 0 perturbs a loss bit.
"""

from __future__ import annotations

import sys
import time

import numpy as np


def fmt(ca):
    return {k: ca.get(k) for k in ("flops", "bytes accessed", "transcendentals")
            if k in ca}


def host_mode(steps=300, fetch_every=8):
    """CPU-friendly per-step host dispatch cost: cache-hit run() vs the
    fused run_steps driver. Prints one machine-greppable line per driver."""
    sys.path.insert(0, ".")
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import monitor

    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup):
                x = fluid.layers.data("x", shape=[64])
                y = fluid.layers.data("y", shape=[1], dtype="int64")
                h = fluid.layers.fc(x, size=64, act="relu")
                logits = fluid.layers.fc(h, size=10)
                loss = fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(logits, y))
                fluid.optimizer.Adam(1e-3).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(0)
            feed = {"x": jax.device_put(rng.randn(32, 64).astype("float32")),
                    "y": jax.device_put(
                        rng.randint(0, 10, (32, 1)).astype("int64"))}

            # steps divisible by fetch_every: no partial-chunk compile
            # inside a timed region
            steps = (steps // fetch_every) * fetch_every

            for _ in range(10):  # compile + warm the dispatch plan
                exe.run(main_prog, feed=feed, fetch_list=[loss],
                        return_numpy=False)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            run_ms = (time.perf_counter() - t0) / steps * 1e3
            np.asarray(out[0])
            print("host_dispatch_ms run()      : %.4f  (cache-hit, "
                  "return_numpy=False)" % run_ms)

            def rep(n):
                return (feed for _ in range(n))

            exe.run_steps(main_prog, rep(2 * fetch_every),
                          steps=2 * fetch_every, fetch_list=[loss],
                          fetch_every=fetch_every, return_numpy=False)
            monitor.metrics.reset()
            t0 = time.perf_counter()
            hs = exe.run_steps(main_prog, rep(steps), steps=steps,
                               fetch_list=[loss], fetch_every=fetch_every,
                               return_numpy=False)
            rs_ms = (time.perf_counter() - t0) / steps * 1e3
            hs[-1].block()
            snap = monitor.snapshot()
            n_disp = snap["executor/run_steps_dispatches"]["value"]
            n_steps = snap["executor/run_steps_steps"]["value"]
            print("host_dispatch_ms run_steps(): %.4f  (fetch_every=%d, "
                  "dispatches/step=%.3f)"
                  % (rs_ms, fetch_every, n_disp / max(n_steps, 1)))
            print("dispatch_reduction          : %.1fx fewer dispatched "
                  "calls" % (n_steps / max(n_disp, 1)))


def opt_mode(steps=6):
    """CPU probe for PADDLE_TPU_OPT_LEVEL: op count + trace/compile time +
    loss bit-identity, level 1 vs level 0 (ISSUE 3 acceptance gate)."""
    import os

    sys.path.insert(0, ".")

    def run_level(level):
        os.environ["PADDLE_TPU_OPT_LEVEL"] = str(level)
        import paddle_tpu as fluid

        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                main_prog, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main_prog, startup):
                    x = fluid.layers.data("x", shape=[64])
                    y = fluid.layers.data("y", shape=[1], dtype="int64")
                    h = fluid.layers.fc(x, size=64, act="relu")
                    h = fluid.layers.dropout(
                        h, 0.2, dropout_implementation="upscale_in_train")
                    logits = fluid.layers.fc(h, size=10)
                    loss = fluid.layers.mean(
                        fluid.layers.softmax_with_cross_entropy(logits, y))
                    # train-loop baggage the optimizer should shed when only
                    # the loss is fetched: a metrics branch and a dead
                    # constant chain (lr-schedule-style host arithmetic)
                    fluid.layers.accuracy(fluid.layers.softmax(logits), y)
                    c = fluid.layers.fill_constant([1], "float32", 2.0)
                    fluid.layers.scale(c, scale=0.5)
                    fluid.optimizer.Adam(1e-3).minimize(loss)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                rng = np.random.RandomState(0)
                feed = {"x": rng.randn(32, 64).astype("float32"),
                        "y": rng.randint(0, 10, (32, 1)).astype("int64")}
                t0 = time.perf_counter()
                first, = exe.run(main_prog, feed=feed, fetch_list=[loss])
                compile_ms = (time.perf_counter() - t0) * 1e3
                losses = [first.copy()]
                for _ in range(steps - 1):
                    lv, = exe.run(main_prog, feed=feed, fetch_list=[loss])
                    losses.append(lv.copy())
                traced = exe._maybe_optimize(
                    main_prog, (loss.name,), fluid.global_scope())
                return (len(main_prog.global_block.ops),
                        len(traced.global_block.ops), compile_ms, losses)

    src0, traced0, ms0, losses0 = run_level(0)
    src1, traced1, ms1, losses1 = run_level(1)
    identical = all(np.array_equal(a, b)
                    for a, b in zip(losses0, losses1))
    print("opt_probe op_count    : src=%d  traced@0=%d  traced@1=%d"
          % (src0, traced0, traced1))
    print("opt_probe compile_ms  : level0=%.1f  level1=%.1f  (first step, "
          "trace+XLA)" % (ms0, ms1))
    print("opt_probe loss_parity : bit_identical=%s  (%d steps, dropout on)"
          % (identical, len(losses0)))
    ok = traced1 < traced0 and identical and ms1 <= ms0 * 1.05
    print("opt_probe verdict     : %s" % ("OK" if ok else "FAIL"))
    return 0 if ok else 1


def numerics_mode(steps=40, reps=3):
    """CPU MLP probe for PADDLE_TPU_NUMERICS: steady-state cache-hit
    ms/step off vs armed (level 1) and the overhead ratio vs the <=15%
    contract, plus loss bit-identity for the off path (ISSUE 14
    acceptance gate). Two things make the armed path cheap enough: the
    per-op stat reductions are single fused kernels, and armed runs only
    fold stats every PADDLE_TPU_NUMERICS_EVERY-th chunk (default 4) —
    the probe measures the honest steady-state mean over both kinds of
    step. The MLP uses a 1024-wide hidden layer at batch 512 so the
    matmuls carry realistic arithmetic intensity (a toy 512-wide net at
    batch 256 makes ANY per-op observation look like ~50% because its
    matmuls are nearly as memory-bound as the stats themselves)."""
    import os

    sys.path.insert(0, ".")

    def run_mode(level):
        if level is None:
            os.environ.pop("PADDLE_TPU_NUMERICS", None)
        else:
            os.environ["PADDLE_TPU_NUMERICS"] = str(level)
        import paddle_tpu as fluid

        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                main_prog, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main_prog, startup):
                    x = fluid.layers.data("x", shape=[1024])
                    y = fluid.layers.data("y", shape=[1], dtype="int64")
                    h = fluid.layers.fc(x, size=1024, act="relu")
                    logits = fluid.layers.fc(h, size=10)
                    loss = fluid.layers.mean(
                        fluid.layers.softmax_with_cross_entropy(logits, y))
                    fluid.optimizer.Adam(1e-3).minimize(loss)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                rng = np.random.RandomState(0)
                feed = {"x": rng.randn(512, 1024).astype("float32"),
                        "y": rng.randint(0, 10, (512, 1)).astype("int64")}
                losses = []
                for _ in range(3):  # compile + settle the caches
                    lv, = exe.run(main_prog, feed=feed, fetch_list=[loss])
                    losses.append(lv.copy())
                best = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        exe.run(main_prog, feed=feed, fetch_list=[loss])
                    best.append((time.perf_counter() - t0) / steps * 1e3)
                best.sort()
                # mean of the fastest half: cross-process stable where a
                # bare median wobbles
                half = best[:max(1, len(best) // 2)]
                return sum(half) / len(half), losses

    base_ms, base_losses = run_mode(None)
    off_ms, off_losses = run_mode(0)
    armed_ms, armed_losses = run_mode(1)
    os.environ.pop("PADDLE_TPU_NUMERICS", None)
    off_identical = all(np.array_equal(a, b)
                        for a, b in zip(base_losses, off_losses))
    armed_close = all(np.allclose(a, b, rtol=1e-6)
                      for a, b in zip(base_losses, armed_losses))
    overhead = armed_ms / off_ms - 1.0
    print("numerics_probe ms/step   : unset=%.3f  level0=%.3f  armed=%.3f"
          % (base_ms, off_ms, armed_ms))
    from paddle_tpu.monitor import numerics as _num

    print("numerics_probe overhead  : %+.1f%%  (armed level 1 vs off, "
          "stats every %d chunks; contract <=15%%)"
          % (100.0 * overhead, _num.stats_every()))
    print("numerics_probe loss_parity: level0_bit_identical=%s  "
          "armed_allclose=%s" % (off_identical, armed_close))
    ok = overhead <= 0.15 and off_identical and armed_close
    print("numerics_probe verdict   : %s" % ("OK" if ok else "FAIL"))
    return 0 if ok else 1


def main():
    sys.path.insert(0, ".")
    import jax

    import bench

    batch, seq, vocab = 64, 256, 30000

    # -- framework step ------------------------------------------------------
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup):
                src = fluid.layers.data("src", shape=[seq], dtype="int64")
                trg = fluid.layers.data("trg", shape=[seq], dtype="int64")
                lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
                smask = fluid.layers.data("smask", shape=[seq], dtype="float32")
                tmask = fluid.layers.data("tmask", shape=[seq], dtype="float32")
                logits, loss = tfm.transformer_base(
                    src, trg, lbl, smask, tmask, src_vocab_size=vocab,
                    trg_vocab_size=vocab, max_length=seq, dropout_rate=0.1)
                opt = fluid.optimizer.Adam(learning_rate=1e-4)
                opt = fluid.amp.decorate(opt)
                opt.minimize(loss)

            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(startup)
            rng = np.random.RandomState(0)
            feed = bench._device_feed({
                "src": rng.randint(2, vocab, (batch, seq)).astype("int64"),
                "trg": rng.randint(2, vocab, (batch, seq)).astype("int64"),
                "lbl": rng.randint(2, vocab, (batch, seq, 1)).astype("int64"),
                "smask": np.ones((batch, seq), "float32"),
                "tmask": np.ones((batch, seq), "float32"),
            })
            # trigger compile + grab the cached step
            exe.run(main_prog, feed=feed, fetch_list=[loss], return_numpy=False)
            compiled = next(c for c in exe._cache.values() if c.fetch_names)
            scope = fluid.global_scope()
            state = {n: scope.vars[n] for n in compiled.state_names
                     if n in scope.vars}
            comp = compiled.fn.lower(state, feed, np.uint32(0)).compile()
            ca = comp.cost_analysis()
            print("paddle_tpu :", fmt(ca))
            print("paddle_tpu mem:", comp.memory_analysis())
            with open("/tmp/hlo_paddle.txt", "w") as f:
                f.write(comp.as_text())

            def fw_step():
                lv, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                return lv

            eps, sps = bench._timeit(fw_step, batch)
            print("paddle_tpu : %.1f ex/s  %.2f ms/step" % (eps, 1e3 / sps))

    # -- raw JAX twin --------------------------------------------------------
    # rebuild raw bench pieces with lowering access
    import functools

    import jax.numpy as jnp  # noqa

    diag = {}
    eps_raw, sps_raw = bench.bench_raw_jax_transformer(batch, seq, vocab,
                                                       _diag=diag)
    if "lowered" in diag:
        rcomp = diag["lowered"].compile()
        print("raw jax    :", fmt(rcomp.cost_analysis()))
        print("raw jax mem:", rcomp.memory_analysis())
        with open("/tmp/hlo_raw.txt", "w") as f:
            f.write(rcomp.as_text())
    print("raw jax    : %.1f ex/s  %.2f ms/step" % (eps_raw, 1e3 / sps_raw))
    print("overhead   : %.4f" % (eps_raw / eps))


if __name__ == "__main__":
    if "--host" in sys.argv:
        host_mode()
    elif "--opt" in sys.argv:
        sys.exit(opt_mode())
    elif "--numerics" in sys.argv:
        sys.exit(numerics_mode())
    else:
        main()

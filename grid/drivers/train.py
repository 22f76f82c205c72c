"""Trains a Fluid program through ``fluid.Executor``: on one chip the
program as built, on several ``CompiledProgram.with_data_parallel``.

Every step feeds the next host batch of a seeded ring through the
executor's normal feed path and fetches the loss without waiting for it;
the loop stays one step ahead of the device and no further, so the
window's end (``block_until_ready`` on the last fetch) is the end of the
work it counts.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from .. import generate, runtime


STEP_SEED = 24


def build(job):
    """(executor, program to run, main program, loss variable)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    m, t = job.config["model"], job.config["trainer"]
    seq = int(t["seq"])
    main, startup = fluid.Program(), fluid.Program()
    # --seed makes the weights (the startup program) and the data. The
    # step program's seed is a constant of the compiled step: were it
    # --seed, every new seed would compile the step anew (232 s on the
    # chip), so the dropout stream is the same in every run.
    startup.random_seed = generate.np_seed(job.seed)
    main.random_seed = STEP_SEED
    with fluid.program_guard(main, startup):
        src = fluid.layers.data("src", shape=[seq], dtype="int64")
        trg = fluid.layers.data("trg", shape=[seq], dtype="int64")
        lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
        smask = fluid.layers.data("smask", shape=[seq], dtype="float32")
        tmask = fluid.layers.data("tmask", shape=[seq], dtype="float32")
        _, loss = tfm.transformer(
            src, trg, lbl, smask, tmask, m["vocab_size"], m["vocab_size"],
            max_length=seq, n_layer=m["n_layer"], n_head=m["n_head"],
            d_model=m["d_model"], d_inner=m["d_inner"],
            dropout_rate=m["dropout"], label_smooth_eps=m["label_smoothing"])
        opt = fluid.optimizer.Adam(learning_rate=t["learning_rate"])
        if t["amp"]:
            opt = fluid.amp.decorate(opt)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    prog = main
    if job.chips > 1:
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
    return exe, prog, main, loss


def _specializations() -> int:
    """Step programs the executor has specialized so far."""
    from paddle_tpu import monitor

    return int(monitor.snapshot()["executor/cache_miss"]["value"])


def _layout_problems(step, main, feed, chips: int) -> List[str]:
    """On several chips: feeds split over all of them and parameters
    replicated, read from the shardings as ``chip_smoke.py`` does."""
    import paddle_tpu as fluid

    (_, feed_sh, _), _ = step._aot.input_shardings
    rows = {n: sh.shard_shape(feed[n].shape)[0] for n, sh in feed_sh.items()}
    devs = {n: len(sh.device_set) for n, sh in feed_sh.items()}
    scope = fluid.global_scope()
    param_devs = sorted({len(scope.find_var(p.name).sharding.device_set)
                         for p in main.all_parameters()})
    out = []
    per_chip = len(feed["src"]) // chips
    if any(r != per_chip for r in rows.values()) \
            or any(d != chips for d in devs.values()):
        out.append("feeds are not split over %d chips: rows %s, devices %s"
                   % (chips, rows, devs))
    if param_devs != [chips]:
        out.append("parameters are not laid out over all %d chips: %s"
                   % (chips, param_devs))
    return out


def run(job) -> Dict[str, Any]:
    import jax
    import paddle_tpu as fluid

    m, t = job.config["model"], job.config["trainer"]
    rows, seq = int(t["rows_per_chip"]) * job.chips, int(t["seq"])
    tokens_per_step = rows * seq
    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        t0 = time.perf_counter()
        exe, prog, main, loss = build(job)
        t_built = time.perf_counter()
        ring = generate.train_ring(job.traffic, m["vocab_size"], rows, seq,
                                   job.seed)
        probe = ring[0]

        def step(feed):
            with runtime.span("grid/exe.run"):
                return exe.run(prog, feed=feed, fetch_list=[loss],
                               return_numpy=False)[0]

        def value(fetch) -> float:
            with runtime.span("grid/loss_fetch"):
                return float(np.asarray(fetch).ravel()[0])

        # warm-up: the one step program compiles (or loads) on the probe
        # batch, whose loss is also the seeded-init loss the check reads
        probe_before = value(step(probe))
        job.log({"phase": "warm", "build_and_startup_s": t_built - t0,
                 "first_step_s": time.perf_counter() - t_built})
        for i in range(int(job.traffic["warm_steps"])):
            last = step(ring[i % len(ring)])
        jax.block_until_ready(last)

        specializations = _specializations()
        compiles = job.meter.compiles
        tail_s = float(job.trace_seconds) if job.profiler.wanted else 0.0
        losses, exe_s, feed_s = [], [], []
        marks: Dict[str, float] = {}
        prev = last
        i = 0
        with runtime.stopping(job.profiler):
            marks["open"] = t_open = time.perf_counter()
            deadline = t_open + job.seconds
            phase = "window"
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    jax.block_until_ready(prev)
                    now = time.perf_counter()
                    if phase == "window":
                        marks["close"] = now
                        marks["steps"] = i
                        if not tail_s:
                            break
                        phase = "tail"
                        job.profiler.start()
                        marks["tail_open"] = time.perf_counter()
                        marks["tail_first_step"] = i
                        deadline = marks["tail_open"] + tail_s
                        continue
                    marks["tail_close"] = now
                    break
                with runtime.span("grid/feed"):
                    # a reader hands over a fresh buffer each step
                    feed = {k: v.copy()
                            for k, v in ring[i % len(ring)].items()}
                t1 = time.perf_counter()
                fetch = step(feed)
                t2 = time.perf_counter()
                # one step ahead of the device and no further
                with runtime.span("grid/wait_previous"):
                    jax.block_until_ready(prev)
                prev = fetch
                losses.append(fetch)
                feed_s.append(t1 - now)
                exe_s.append(t2 - t1)
                i += 1
        record = {
            "kind": "train", "marks": marks, "chips": job.chips,
            "tokens_per_step": tokens_per_step,
            "feed_s": feed_s, "exe_s": exe_s,
            "compiles": job.meter.compiles - compiles,
            "specializations": _specializations() - specializations,
            "model": m, "seq": seq,
        }
        # the step as the window ran it, compiled ahead of time at the
        # same shapes and layout: a load from the compile cache
        prepared = exe.prepare(prog, feed=probe, fetch_list=[loss])
        record["memory"] = runtime.memory([prepared._aot])
        loss_values = [value(x) for x in losses]
        probe_after = value(step(probe))
        problems = []
        if not all(math.isfinite(x) for x in loss_values + [probe_after]):
            problems.append("a loss is not finite")
        init = math.log(m["vocab_size"])
        if abs(probe_before - init) > 0.01 * init:
            problems.append("the first loss %.4f is not within 1%% of "
                            "ln V = %.4f" % (probe_before, init))
        if not probe_after < probe_before:
            problems.append("the probe batch's loss did not fall: %.4f "
                            "before the window, %.4f after"
                            % (probe_before, probe_after))
        if record["compiles"] or record["specializations"]:
            problems.append("%d compilations and %d step specializations "
                            "inside the window" % (record["compiles"],
                                                   record["specializations"]))
        if job.chips > 1:
            problems += _layout_problems(prepared, main, probe, job.chips)
        record.update(correct=not problems, problems=problems,
                      attempted=marks["steps"], failed=0,
                      compared={
                          "first_loss_off_ln_v": [abs(probe_before - init),
                                                  0.01 * init],
                          "probe_loss_after": [probe_after, probe_before],
                          "compiles": [record["compiles"]
                                       + record["specializations"], 0]},
                      loss={"probe_before": probe_before,
                            "probe_after": probe_after,
                            "first": loss_values[0],
                            "last": loss_values[marks["steps"] - 1]})
        exe.close()
    return record

"""Per-op TPU busy-time diff of the paddle vs raw BERT-base train steps.

Same method as profile_xplane.py (which profiles the Transformer config):
trace 3 steps of each, bucket device-lane events by fusion name, diff.

Usage: python benchmarks/profile_bert.py  (on the TPU)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))); sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

from profile_xplane import parse_xplane, profile_step  # noqa: E402


def main():
    import bench
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    batch, seq, n_mask = 32, 128, 20
    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            ids = fluid.layers.data("ids", shape=[seq], dtype="int64")
            pos = fluid.layers.data("pos", shape=[seq], dtype="int64")
            sent = fluid.layers.data("sent", shape=[seq], dtype="int64")
            mask = fluid.layers.data("mask", shape=[seq], dtype="float32")
            mpos = fluid.layers.data("mpos", shape=[n_mask], dtype="int64")
            mlbl = fluid.layers.data("mlbl", shape=[1], dtype="int64")
            nsp = fluid.layers.data("nsp", shape=[1], dtype="int64")
            loss, _, _ = bert.bert_pretrain(ids, pos, sent, mask, mpos, mlbl,
                                            nsp, **bert.BERT_BASE_CONFIG)
            opt = fluid.amp.decorate(fluid.optimizer.Adam(learning_rate=1e-4))
            opt.minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        rng = np.random.RandomState(0)
        mpos_np = (np.arange(batch)[:, None] * seq
                   + rng.randint(0, seq, (batch, n_mask))).astype("int64")
        feed = bench._device_feed({
            "ids": rng.randint(0, 30522, (batch, seq)).astype("int64"),
            "pos": np.tile(np.arange(seq), (batch, 1)).astype("int64"),
            "sent": np.zeros((batch, seq), "int64"),
            "mask": np.ones((batch, seq), "float32"),
            "mpos": mpos_np,
            "mlbl": rng.randint(0, 30522, (batch * n_mask, 1)).astype("int64"),
            "nsp": rng.randint(0, 2, (batch, 1)).astype("int64"),
        })

        def pstep():
            lv, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            return lv

        profile_step(pstep, "/tmp/prof_bert_paddle")
    t_p = parse_xplane("/tmp/prof_bert_paddle")

    # raw twin: rebuild the pieces of bench_raw_jax_bert with a profile loop
    import jax

    diag = {}
    # reuse the bench function but only to build; easiest is to re-run its
    # step under the profiler via a tiny monkeypatch of _timeit
    orig_timeit = bench._timeit
    captured = {}

    def grab(step, batch_, skip=3, iters=12):
        captured["step"] = step
        return orig_timeit(step, batch_, skip=2, iters=4)

    bench._timeit = grab
    try:
        bench.bench_raw_jax_bert(batch, seq, n_mask)
    finally:
        bench._timeit = orig_timeit
    profile_step(captured["step"], "/tmp/prof_bert_raw")
    t_r = parse_xplane("/tmp/prof_bert_raw")

    sp, sr = sum(t_p.values()), sum(t_r.values())
    print("device busy: paddle %.2f ms  raw %.2f ms (3 profiled steps)"
          % (sp, sr))
    keys = sorted(set(t_p) | set(t_r),
                  key=lambda k: -abs(t_p.get(k, 0) - t_r.get(k, 0)))
    print("%-40s %9s %9s %9s" % ("op bucket", "paddle ms", "raw ms", "delta"))
    for k in keys[:30]:
        d = t_p.get(k, 0) - t_r.get(k, 0)
        if abs(d) < 0.05:
            continue
        print("%-40s %9.2f %9.2f %+9.2f" % (k[:40], t_p.get(k, 0),
                                            t_r.get(k, 0), d))


if __name__ == "__main__":
    main()

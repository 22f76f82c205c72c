"""Per-op TPU busy-time profile of the paddle vs raw Transformer steps.

jax.profiler trace -> parse <run>/plugins/profile/*/​*.xplane.pb with
tensorflow's xplane proto (PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python),
aggregate device-lane event durations by fusion-name bucket, and diff.

Usage: PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python python
       benchmarks/profile_xplane.py  (on the TPU)
"""
import collections
import glob
import os
import re
import sys

import numpy as np

sys.path.insert(0, ".")
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")


def profile_step(run_step, outdir, steps=3):
    import jax

    np.asarray(run_step())  # ensure compiled
    with jax.profiler.trace(outdir):
        for _ in range(steps):
            out = run_step()
        np.asarray(out)


def parse_xplane(outdir):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    per_op = collections.Counter()
    for p in paths:
        xs = xplane_pb2.XSpace()
        with open(p, "rb") as f:
            xs.ParseFromString(f.read())
        for plane in xs.planes:
            if "TPU" not in plane.name or "XLA" in plane.name:
                continue
            ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
            # exact match: "Async XLA Ops" (overlapped DMA spans) also
            # contains the substring "XLA Ops" and must NOT be summed as
            # busy time — that double-count inflated the r4 bucket numbers
            op_lines = [l for l in plane.lines if l.name == "XLA Ops"]
            if not op_lines:
                # fallback for other profiler line layouts: never re-admit
                # the async spans the exact-match filter exists to exclude
                import warnings

                warnings.warn("no 'XLA Ops' line in %s; summing non-async "
                              "lines" % plane.name)
                op_lines = [l for l in plane.lines if "Async" not in l.name]
            for line in op_lines:
                for ev in line.events:
                    nm = ev_meta.get(ev.metadata_id, "?")
                    per_op[_bucket(nm)] += ev.duration_ps / 1e9  # ms
    return per_op


def _bucket(name):
    """'%divide_subtract_fusion.2 = (f32[...' -> 'divide_subtract_fusion'.
    Async copy-start/done spans overlap compute — bucket them apart."""
    tok = name.split(" = ")[0].split("/")[-1].lstrip("%")
    tok = re.sub(r"[.\d]+$", "", tok)
    if tok.startswith(("copy-start", "copy-done")):
        return "(async copies)"
    return tok


def main():
    import jax

    import bench
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    batch, seq, vocab = 64, 256, 30000
    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
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
            opt = fluid.amp.decorate(fluid.optimizer.Adam(learning_rate=1e-4))
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

        def pstep():
            lv, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            return lv

        profile_step(pstep, "/tmp/prof_paddle")
    t_p = parse_xplane("/tmp/prof_paddle")

    diag = {}
    bench.bench_raw_jax_transformer(batch, seq, vocab, _diag=diag,
                                    _profile_dir="/tmp/prof_raw")
    t_r = parse_xplane("/tmp/prof_raw")

    sp, sr = sum(t_p.values()), sum(t_r.values())
    print("device busy: paddle %.2f ms  raw %.2f ms (over profiled steps)"
          % (sp, sr))
    keys = sorted(set(t_p) | set(t_r),
                  key=lambda k: -abs(t_p.get(k, 0) - t_r.get(k, 0)))
    print("%-40s %9s %9s %9s" % ("op bucket", "paddle ms", "raw ms", "delta"))
    for k in keys[:25]:
        d = t_p.get(k, 0) - t_r.get(k, 0)
        if abs(d) < 0.05:
            continue
        print("%-40s %9.2f %9.2f %+9.2f" % (k[:40], t_p.get(k, 0),
                                            t_r.get(k, 0), d))


if __name__ == "__main__":
    main()

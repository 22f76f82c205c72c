"""AOT compile-cache warmup (the TVM "compile once, deploy many" leg).

Builds a named model's train program and ahead-of-time compiles its step
via ``Executor.prepare`` — ``jax.jit(...).lower().compile()`` — WITHOUT
running a single step. The XLA executable lands in the persistent on-disk
cache (``paddle_tpu/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), so the real training/bench job that follows
(same program, same shapes, same jaxlib, same cache directory) starts with
a cache hit instead of a multi-minute compile.

    python -m tools.warmup --model transformer --batch 64 --seq 256

    python -m tools.warmup --model mlp          # CPU smoke (<5s)

Exits 0 on success and prints the process's compile log
(``compile_cache.report()``: every executable it traced, lowered, compiled
or loaded, by name, the step as ``step[<fingerprint>]``) — run it twice to
see the second invocation's ``step[..]`` flip from ``miss`` to ``hit``.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _specs(**shapes):
    """name -> (shape, dtype) feed spec dict for Executor.prepare."""
    return {n: (tuple(shape), dtype) for n, (shape, dtype) in shapes.items()}


def build_mlp(args):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[64])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=64, act="relu")
        logits = fluid.layers.fc(h, size=10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    b = args.batch
    return main, startup, loss, _specs(
        x=((b, 64), "float32"), y=((b, 1), "int64"))


def build_transformer(args):
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    b, s, v = args.batch, args.seq, args.vocab
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        src = fluid.layers.data("src", shape=[s], dtype="int64")
        trg = fluid.layers.data("trg", shape=[s], dtype="int64")
        lbl = fluid.layers.data("lbl", shape=[s, 1], dtype="int64")
        smask = fluid.layers.data("smask", shape=[s], dtype="float32")
        tmask = fluid.layers.data("tmask", shape=[s], dtype="float32")
        _, loss = tfm.transformer_base(
            src, trg, lbl, smask, tmask, src_vocab_size=v, trg_vocab_size=v,
            max_length=s, dropout_rate=0.1)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if args.amp:
            opt = fluid.amp.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss, _specs(
        src=((b, s), "int64"), trg=((b, s), "int64"), lbl=((b, s, 1), "int64"),
        smask=((b, s), "float32"), tmask=((b, s), "float32"))


def build_resnet50(args):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet as rn

    b, im = args.batch, args.image
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, im, im])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        _, loss, _ = rn.resnet50(img, label, class_num=1000)
        opt = fluid.optimizer.Momentum(0.1, 0.9)
        if args.amp:
            opt = fluid.amp.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss, _specs(
        img=((b, 3, im, im), "float32"), label=((b, 1), "int64"))


def build_bert(args):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    b, s, m = args.batch, args.seq, args.n_mask
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[s], dtype="int64")
        pos = fluid.layers.data("pos", shape=[s], dtype="int64")
        sent = fluid.layers.data("sent", shape=[s], dtype="int64")
        mask = fluid.layers.data("mask", shape=[s], dtype="float32")
        mpos = fluid.layers.data("mpos", shape=[m], dtype="int64")
        mlbl = fluid.layers.data("mlbl", shape=[1], dtype="int64")
        nsp = fluid.layers.data("nsp", shape=[1], dtype="int64")
        loss, _, _ = bert.bert_pretrain(ids, pos, sent, mask, mpos, mlbl, nsp,
                                        **bert.BERT_BASE_CONFIG)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if args.amp:
            opt = fluid.amp.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss, _specs(
        ids=((b, s), "int64"), pos=((b, s), "int64"), sent=((b, s), "int64"),
        mask=((b, s), "float32"), mpos=((b, m), "int64"),
        mlbl=((b * m, 1), "int64"), nsp=((b, 1), "int64"))


BUILDERS = {
    "mlp": build_mlp,
    "transformer": build_transformer,
    "resnet50": build_resnet50,
    "bert": build_bert,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tools.warmup",
        description="AOT-compile a model's train step into the persistent "
                    "XLA compile cache (paddle_tpu/compile_cache.py).")
    p.add_argument("--model", choices=sorted(BUILDERS), default="mlp")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--vocab", type=int, default=30000)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--n-mask", type=int, default=20)
    p.add_argument("--no-amp", dest="amp", action="store_false",
                   help="skip bf16 AMP decoration (default: on, matching "
                        "bench.py shapes so the bench gets the cache hit)")
    args = p.parse_args(argv)

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache

    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main_prog, startup, loss, feed_specs = BUILDERS[args.model](args)
            exe = fluid.Executor(fluid.TPUPlace(0)
                                 if fluid.is_compiled_with_tpu()
                                 else fluid.CPUPlace())
            exe.run(startup)
            exe.prepare(main_prog, feed=feed_specs, fetch_list=[loss])

    print("warmup[%s]: the compile log (%s)"
          % (args.model, compile_cache.compile_cache_dir()))
    print(compile_cache.report())
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""ResNet-50 perf diagnosis: framework step vs a raw-JAX twin (NCHW + NHWC).

Prints XLA cost analysis (flops / bytes accessed) and measured step time for
(a) the paddle_tpu ResNet-50 bench step, (b) a hand-written JAX ResNet-50
train step in NCHW, and (c) the same in NHWC — separating framework tax from
layout effects.

Usage: python benchmarks/diag_resnet.py  (on the TPU)
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np


def fmt(ca):
    return {k: ca.get(k) for k in ("flops", "bytes accessed", "transcendentals")
            if k in ca}


def _timeit(step, batch, skip=3, iters=10):
    for _ in range(skip):
        np.asarray(step())
    t0 = time.time()
    for _ in range(iters):
        out = step()
    assert np.isfinite(np.asarray(out)).all()
    dt = time.time() - t0
    return batch * iters / dt, iters / dt


def framework(batch=64, image=224, classes=1000):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet as rn

    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup):
                img = fluid.layers.data("img", shape=[3, image, image])
                label = fluid.layers.data("label", shape=[1], dtype="int64")
                logits, loss, acc = rn.resnet50(img, label, class_num=classes)
                opt = fluid.optimizer.Momentum(0.1, 0.9)
                opt = fluid.amp.decorate(opt)
                opt.minimize(loss)
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(startup)
            rng = np.random.RandomState(0)
            feed = {k: jax.device_put(v) for k, v in {
                "img": rng.randn(batch, 3, image, image).astype("float32"),
                "label": rng.randint(0, classes, (batch, 1)).astype("int64"),
            }.items()}
            exe.run(main_prog, feed=feed, fetch_list=[loss], return_numpy=False)
            compiled = next(c for c in exe._cache.values() if c.fetch_names)
            scope = fluid.global_scope()
            state = {n: scope.vars[n] for n in compiled.state_names
                     if n in scope.vars}
            comp = compiled.fn.lower(state, feed, np.uint32(0)).compile()
            print("paddle_tpu :", fmt(comp.cost_analysis()))
            with open("/tmp/hlo_resnet_paddle.txt", "w") as f:
                f.write(comp.as_text())

            def step():
                lv, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                return lv

            eps, sps = _timeit(step, batch)
            print("paddle_tpu : %.1f ex/s  %.2f ms/step" % (eps, 1e3 / sps))


def raw(layout="NCHW", batch=64, image=224, classes=1000):
    import jax
    import jax.numpy as jnp

    nhwc = layout == "NHWC"
    dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")

    cfg = [(64, 256, 3, 1), (128, 512, 4, 2), (256, 1024, 6, 2), (512, 2048, 3, 2)]
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))

    def conv_p(cin, cout, k):
        shape = (k, k, cin, cout) if nhwc else (cout, cin, k, k)
        fan = cin * k * k
        return jax.random.normal(next(keys), shape, jnp.float32) * (2.0 / fan) ** 0.5

    def bn_p(c):
        return {"g": jnp.ones((c,)), "b": jnp.zeros((c,)),
                "m": jnp.zeros((c,)), "v": jnp.ones((c,))}

    params = {"stem": conv_p(3, 64, 7), "stem_bn": bn_p(64)}
    cin = 64
    for si, (mid, cout, n, stride) in enumerate(cfg):
        for bi in range(n):
            p = {}
            p["c1"], p["bn1"] = conv_p(cin, mid, 1), bn_p(mid)
            p["c2"], p["bn2"] = conv_p(mid, mid, 3), bn_p(mid)
            p["c3"], p["bn3"] = conv_p(mid, cout, 1), bn_p(cout)
            if bi == 0:
                p["sc"], p["sbn"] = conv_p(cin, cout, 1), bn_p(cout)
            params["s%d_%d" % (si, bi)] = p
            cin = cout
    params["fc_w"] = jax.random.normal(next(keys), (2048, classes)) * 0.01
    params["fc_b"] = jnp.zeros((classes,))

    def conv(x, w, stride):
        k = w.shape[0] if nhwc else w.shape[2]
        pad = (k - 1) // 2
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=dn)

    def bn(x, p):
        ax = (0, 1, 2) if nhwc else (0, 2, 3)
        sh = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
        xf = x.astype(jnp.float32)
        m = xf.mean(ax)
        v = (xf ** 2).mean(ax) - m ** 2
        inv = jax.lax.rsqrt(v + 1e-5).astype(x.dtype)
        return ((x - m.astype(x.dtype).reshape(sh)) * inv.reshape(sh)
                * p["g"].astype(x.dtype).reshape(sh)
                + p["b"].astype(x.dtype).reshape(sh))

    def block(x, p, stride):
        h = jax.nn.relu(bn(conv(x, p["c1"], 1), p["bn1"]))
        h = jax.nn.relu(bn(conv(h, p["c2"], stride), p["bn2"]))
        h = bn(conv(h, p["c3"], 1), p["bn3"])
        if "sc" in p:
            x = bn(conv(x, p["sc"], stride), p["sbn"])
        return jax.nn.relu(x + h)

    def loss_fn(params32, img, lbl):
        p = jax.tree_util.tree_map(
            lambda t: t.astype(jnp.bfloat16) if t.dtype == jnp.float32 else t,
            params32)
        x = img.astype(jnp.bfloat16)
        x = jax.nn.relu(bn(conv(x, p["stem"], 2), p["stem_bn"]))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1, 1, 3, 3) if not nhwc else (1, 3, 3, 1),
            (1, 1, 2, 2) if not nhwc else (1, 2, 2, 1),
            [(0, 0), (0, 0), (1, 1), (1, 1)] if not nhwc
            else [(0, 0), (1, 1), (1, 1), (0, 0)])
        for si, (mid, cout, n, stride) in enumerate(cfg):
            for bi in range(n):
                x = block(x, p["s%d_%d" % (si, bi)], stride if bi == 0 else 1)
        ax = (1, 2) if nhwc else (2, 3)
        x = x.mean(ax)
        logits = (x @ p["fc_w"] + p["fc_b"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, lbl, axis=-1).mean()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, mom, img, lbl):
        loss, g = jax.value_and_grad(loss_fn)(params, img, lbl)
        mom = jax.tree_util.tree_map(lambda m, gg: 0.9 * m + gg, mom, g)
        params = jax.tree_util.tree_map(lambda p_, m: p_ - 0.1 * m, params, mom)
        return params, mom, loss

    rng = np.random.RandomState(0)
    img = rng.randn(batch, 3, image, image).astype("float32")
    if nhwc:
        img = img.transpose(0, 2, 3, 1)
    img = jax.device_put(jnp.asarray(img))
    lbl = jax.device_put(jnp.asarray(rng.randint(0, classes, (batch, 1))))
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    comp = train_step.lower(params, mom, img, lbl).compile()
    print("raw %s  :" % layout, fmt(comp.cost_analysis()))
    with open("/tmp/hlo_resnet_raw_%s.txt" % layout, "w") as f:
        f.write(comp.as_text())

    state = {"p": params, "m": mom}

    def step():
        state["p"], state["m"], loss = train_step(state["p"], state["m"], img, lbl)
        return loss

    eps, sps = _timeit(step, batch)
    print("raw %s  : %.1f ex/s  %.2f ms/step" % (layout, eps, 1e3 / sps))


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "fw"):
        framework()
    if which in ("all", "nchw"):
        raw("NCHW")
    if which in ("all", "nhwc"):
        raw("NHWC")

"""The standing proof that the trainer and the server start on the TPU.

    python chip_smoke.py            one chip: train, ctr, kernels, serve,
                                    serve_moe, serve_mla
    python chip_smoke.py --chips 4  four chips: the data-parallel phase only

One process, no children, no JAX_PLATFORMS set here: a chip belongs to the
process that touched JAX first. Every phase drives the system through the
entry points a user calls, at the published widths of a model the repo
supports, with weights and data made from ``--seed``, and checks what comes
out by the repo's own references. Each phase prints one JSON line; the last
line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run fails (exit 1, last line ``"ok": false``) where JAX finds no TPU,
where the device count is not the one asked for, where a phase raises or a
check does not hold, and where a kernel took its configuration from a
``tuned`` table — the chip run must not depend on a stray
``autotune_table.json`` in somebody's checkout. tests/test_chip_smoke.py
rehearses the control flow on the CPU at toy widths (it swaps ``SIZES`` and
stubs ``device_doc``; the script has no option for either).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback

# Published widths. train: Transformer-base (Vaswani et al. 2017, table 3
# "base": 6+6 layers, d=512, d_inner=2048, 8 heads) at the batch BASELINE.json
# benchmarks. ctr: DeepFM at bench.py's Criteo-shaped size. serve: GPT-2
# small (Radford et al. 2019: V=50257, 12 layers, d=768, 12 heads of 64,
# 1024 positions, tied embeddings), which models/decoder_lm.py matches
# layer for layer.
SIZES = {
    "train": dict(n_layer=6, d_model=512, d_inner=2048, n_head=8,
                  vocab=30000, batch=64, seq=256, steps=8),
    "ctr": dict(vocab=1_000_000, fields=26, width=10, batch=1024, steps=4),
    "kernels": dict(flash=(1, 8, 2048, 64), xent=(16384, 30000),
                    sparse_vocab=1_000_000, sparse_ids=26624,
                    sparse_widths=(1, 10, 128),
                    # the fused expert kernel at two served decode passes'
                    # published widths: (rows a pass, experts held, d, f,
                    # experts touched, live rows): SmallThinker's 16 slots
                    # x top-6 over all 64 experts (whole matrices, one
                    # grid step an expert), Kimi-K2's 12-of-384 share
                    # (29 MB matrices in row blocks, 248 of 256 rows dead)
                    expert_stream=dict(
                        smallthinker=(96, 64, 2560, 768, 45, 96),
                        kimi_k2=(256, 12, 7168, 2048, 6, 8))),
    "serve": dict(vocab=50257, n_layer=12, d_model=768, n_head=12,
                  max_seq=1024, page_size=16, slots=8, requests=8,
                  prompt_min=16, prompt_max=512, new_tokens=32,
                  buckets=(128, 512), reference_requests=2),
    # SmallThinker's block (models/smallthinker.py) small, but at the
    # geometry the chip's compiler judges: heads of 128, 7 query heads a KV
    # head, a window that the longer request's context passes
    "serve_moe": dict(vocab=1024, n_layer=4, d_model=256, n_head=14,
                      n_kv_head=2, d_head=128, n_expert=8, top_k=3,
                      d_expert=128, window=256, max_seq=1024, page_size=16,
                      slots=4, prompts=(40, 200), new_tokens=120,
                      buckets=(64, 256)),
    # Kimi-K2's block (models/kimi_k2.py) at its published widths, two
    # layers (the dense one and one expert layer), the share of one chip of
    # 32: 12 of 384 experts, 20,480 rows of the vocabulary
    "serve_mla": dict(vocab=20480, n_layer=2, d_model=7168, n_head=64,
                      q_rank=1536, kv_rank=512, d_nope=128, d_rope=64,
                      d_v=128, d_dense=18432, n_expert=384, top_k=8,
                      d_expert=2048, held=12, yarn_positions=4096,
                      dtype="bfloat16", max_seq=1024, page_size=16, slots=4,
                      prompt=200, new_tokens=17, buckets=(256,)),
    "dp": dict(steps=4),
}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CheckFailed(AssertionError):
    """A phase ran to its end and what came out is wrong."""


def check(ok, what):
    if not ok:
        raise CheckFailed(what)


def device_doc():
    """The device as JAX reports it — the ``device`` object of the last
    line, and what decides whether the run may start at all."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Meter:
    """Compiles and persistent-cache traffic, read per phase as deltas."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def read(self):
        from paddle_tpu import monitor

        snap = monitor.snapshot()
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hit": int(snap["compile_cache/hit"]["value"]),
                "cache_miss": int(snap["compile_cache/miss"]["value"]),
                "step_specializations":
                    int(snap["executor/cache_miss"]["value"]),
                "aot_compiles":
                    int(snap["executor/compile_time_ms"]["count"])}

    @staticmethod
    def delta(after, before):
        out = {k: after[k] - before[k] for k in after}
        out["compile_s"] = round(out["compile_s"], 3)
        return out


def tune_layers():
    """{kernel: tune-table layer} of every lookup since the last reset."""
    from paddle_tpu import tune

    return {k: v["source"] for k, v in tune.provenance_snapshot().items()}


def on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def on_device(arr, dev):
    return set(arr.devices()) == {dev}


# -- train ---------------------------------------------------------------------


def _build_transformer(fluid, cfg, seed):
    from paddle_tpu.models import transformer as tfm

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    seq, vocab = cfg["seq"], cfg["vocab"]
    with fluid.program_guard(main, startup):
        src = fluid.layers.data("src", shape=[seq], dtype="int64")
        trg = fluid.layers.data("trg", shape=[seq], dtype="int64")
        lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
        smask = fluid.layers.data("smask", shape=[seq], dtype="float32")
        tmask = fluid.layers.data("tmask", shape=[seq], dtype="float32")
        _, loss = tfm.transformer(
            src, trg, lbl, smask, tmask, vocab, vocab, max_length=seq,
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_model=cfg["d_model"], d_inner=cfg["d_inner"], dropout_rate=0.1)
        fluid.amp.decorate(fluid.optimizer.Adam(learning_rate=1e-3)) \
            .minimize(loss)
    return main, startup, loss


def _transformer_feed(cfg, seed):
    import numpy as np

    rng = np.random.RandomState(seed)
    b, s, v = cfg["batch"], cfg["seq"], cfg["vocab"]
    return {"src": rng.randint(2, v, (b, s)).astype("int64"),
            "trg": rng.randint(2, v, (b, s)).astype("int64"),
            "lbl": rng.randint(2, v, (b, s, 1)).astype("int64"),
            "smask": np.ones((b, s), "float32"),
            "tmask": np.ones((b, s), "float32")}


def _train_steps(exe, prog, feed, loss, steps):
    """``steps`` runs on the fixed batch; (losses, last fetch handle)."""
    import numpy as np

    losses, fetched = [], None
    for _ in range(steps):
        fetched = exe.run(prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
        losses.append(float(np.asarray(fetched[0]).ravel()[0]))
    return losses, fetched


def phase_train(seed, meter):
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.ops.nn_ops import fused_xent_gate

    cfg = SIZES["train"]
    dev = jax.devices()[0]
    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, startup, loss = _build_transformer(fluid, cfg, seed)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        feed = _transformer_feed(cfg, seed)
        step = exe.prepare(main, feed=feed, fetch_list=[loss])
        hlo = step._aot.as_text()
        losses, fetched = _train_steps(exe, main, feed, loss, 1)
        after_first = meter.read()
        more, fetched = _train_steps(exe, main, feed, loss,
                                     cfg["steps"] - 1)
        losses += more
        steady = Meter.delta(meter.read(), after_first)
        scope = fluid.global_scope()
        params = [scope.find_var(p.name) for p in main.all_parameters()]
        n_rows = cfg["batch"] * cfg["seq"]
        # the model's own loss: label smoothing 0.1 (models/transformer.py)
        xent_why_not = fused_xent_gate((n_rows, cfg["vocab"]), "bfloat16",
                                       smooth=0.1)
        # by the kernels' own names: the attention's single-tile kernels are
        # tpu_custom_calls of this step too
        fused_xent = "softmax_xent_fwd" in hlo
        single_tile = "single_tile_attention_fwd" in hlo
        check(all(np.isfinite(losses)), "a loss is not finite: %s" % losses)
        check(losses[-1] < losses[0],
              "loss did not fall on a fixed batch: %s" % losses)
        check(all(on_device(p, dev) for p in params)
              and on_device(fetched[0], dev),
              "parameters or fetches are not on %s" % dev)
        check(steady["compiles"] == 0
              and steady["step_specializations"] == 0,
              "compiled again after the first step: %s" % steady)
        check(fused_xent == (xent_why_not is None),
              "the compiled step %s the softmax_xent kernel, but the "
              "cross-entropy gate says: %s"
              % ("holds" if fused_xent else "lacks",
                 xent_why_not or "fused kernel"))
        exe.close()
    return {"checked": "losses finite and falling over %d steps; %d "
                       "parameters and the fetch on %s; no compile after "
                       "step 1" % (cfg["steps"], len(params), dev),
            "loss_first": losses[0], "loss_last": losses[-1],
            "kernel_path": {
                "cross_entropy": ("pallas fused_softmax_xent [%d x %d]"
                                  % (n_rows, cfg["vocab"]) if fused_xent
                                  else "xla, no softmax_xent kernel (gate: %s)"
                                  % xent_why_not),
                "attention": "single_tile_attention_fwd|bwd" if single_tile
                             else "composed (seq %d < flash_attention_min_seq "
                                  "%d)" % (cfg["seq"], fluid.get_flag(
                                      "flash_attention_min_seq"))},
            "tune": tune_layers()}


# -- ctr -----------------------------------------------------------------------


def _run_deepfm(fluid, cfg, seed):
    """Fresh scope + program, ``steps`` Adam steps on a fixed batch;
    (losses, {table name: numpy array})."""
    import numpy as np

    from paddle_tpu.models import deepfm as dfm

    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data("ids", shape=[cfg["fields"]],
                                    dtype="int64")
            dense = fluid.layers.data("dense", shape=[13])
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            _, loss, _ = dfm.deepfm(
                ids, dense, label, sparse_feature_dim=cfg["vocab"],
                embedding_size=cfg["width"], num_fields=cfg["fields"],
                is_sparse=True)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        rng = np.random.RandomState(seed)
        b = cfg["batch"]
        feed = {"ids": rng.randint(0, cfg["vocab"],
                                   (b, cfg["fields"])).astype("int64"),
                "dense": rng.rand(b, 13).astype("float32"),
                "label": rng.randint(0, 2, (b, 1)).astype("int64")}
        losses = [float(np.asarray(exe.run(main, feed=feed,
                                           fetch_list=[loss])[0]).ravel()[0])
                  for _ in range(cfg["steps"])]
        scope = fluid.global_scope()
        tables = {n: np.asarray(scope.find_var(n))
                  for n in ("sparse_emb", "sparse_w1")}
        exe.close()
    return losses, tables


def phase_ctr(seed, meter):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.ops.optimizer_ops import sparse_update_path

    cfg = SIZES["ctr"]
    f32 = np.dtype("float32")
    paths = {}
    for name, width in (("sparse_emb", cfg["width"]), ("sparse_w1", 1)):
        kmode, _, why = sparse_update_path((cfg["vocab"], width), f32,
                                           f32, f32)
        paths[name] = ("pallas sparse_adam_rows (%s)" % kmode if kmode
                       else "xla scatter (%s)" % why)
    losses, tables = _run_deepfm(fluid, cfg, seed)
    default_flag = fluid.get_flag("sparse_update_kernel")
    fluid.set_flag("sparse_update_kernel", "off")
    try:
        ref_losses, ref_tables = _run_deepfm(fluid, cfg, seed)
    finally:
        fluid.set_flag("sparse_update_kernel", default_flag)
    check(all(np.isfinite(losses)), "a loss is not finite: %s" % losses)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, err_msg=(
        "losses under default flags vs FLAGS_sparse_update_kernel=off"))
    for n in tables:
        # Adam at lr 1e-3 on two summation orders: see the tolerance note
        # in tests/test_sparse_kernel.py (lr * 1e-4 per step)
        np.testing.assert_allclose(tables[n], ref_tables[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)
        check(np.any(tables[n][:64]), "table %s is all zero" % n)
    return {"checked": "%d steps under default flags == the same steps with "
                       "FLAGS_sparse_update_kernel=off: losses rtol 1e-5, "
                       "tables [%d, %d] and [%d, 1] rtol 1e-4"
                       % (cfg["steps"], cfg["vocab"], cfg["width"],
                          cfg["vocab"]),
            "loss_first": losses[0], "loss_last": losses[-1],
            "kernel_path": paths, "tune": tune_layers()}


# -- kernels -------------------------------------------------------------------


def _max_err(got, want):
    import numpy as np

    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(g - w))), float(np.max(np.abs(w)))


def _kernel_flash(seed, interpret):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    b, h, s, d = SIZES["kernels"]["flash"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
               .astype(jnp.bfloat16) for kk in ks)
    sm = 1.0 / float(d) ** 0.5
    bs = attention_ops._tuned_block_sizes(s, s)

    def flash_loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, sm_scale=sm,
                               block_sizes=bs)
        return o.astype(jnp.float32).sum(), o

    def composed_loss(q, k, v):
        # sdpa below its flash crossover IS the composed path
        o = attention_ops.sdpa(q, k, v, causal=True, sm_scale=sm)
        return o.astype(jnp.float32).sum(), o

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                                has_aux=True))
    prev_interp, fa.INTERPRET = fa.INTERPRET, interpret
    prev_min = fluid.get_flag("flash_attention_min_seq")
    fluid.set_flag("flash_attention_min_seq", s + 1)
    try:
        (_, o_f), g_f = grad(flash_loss)(q, k, v)
        (_, o_c), g_c = grad(composed_loss)(q, k, v)
    finally:
        fa.INTERPRET = prev_interp
        fluid.set_flag("flash_attention_min_seq", prev_min)
    errs = {}
    for name, got, want in (("out", o_f, o_c), ("dq", g_f[0], g_c[0]),
                            ("dk", g_f[1], g_c[1]), ("dv", g_f[2], g_c[2])):
        err, scale = _max_err(got, want)
        errs[name] = err
        # two bf16 computations of an O(1)-per-term sum: a few bf16 ulps
        # of the largest element
        check(err <= 0.05 * max(scale, 1.0),
              "flash %s differs from composed by %g (scale %g)"
              % (name, err, scale))
    return {"shape": [b, h, s, d], "dtype": "bfloat16", "causal": True,
            "blocks": [bs.block_q, bs.block_k], "max_abs_err": errs}


def _kernel_xent(seed, interpret):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import fused_softmax_xent

    n, v = SIZES["kernels"]["xent"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    logits = (2.0 * jax.random.normal(k1, (n, v), jnp.float32)) \
        .astype(jnp.bfloat16)
    labels = jax.random.randint(k2, (n, 1), 0, v, jnp.int32)

    def fused(x):
        return fused_softmax_xent(x, labels, interpret).sum()

    def composed(x):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels, axis=-1).sum()

    l_f, g_f = jax.jit(jax.value_and_grad(fused))(logits)
    l_c, g_c = jax.jit(jax.value_and_grad(composed))(logits)
    rel = abs(float(l_f) - float(l_c)) / abs(float(l_c))
    gerr, _ = _max_err(g_f, g_c)
    check(rel <= 1e-4, "fused xent loss off by %g relative" % rel)
    # the gradient is softmax - onehot in [-1, 1], stored in bf16
    check(gerr <= 2 ** -7, "fused xent gradient off by %g" % gerr)
    return {"shape": [n, v], "dtype": "bfloat16", "loss_rel_err": rel,
            "grad_max_abs_err": gerr}


def _kernel_sparse(seed, interpret):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.sparse import merge_rows
    from paddle_tpu.ops.pallas_kernels import sparse_adam as sa

    cfg = SIZES["kernels"]
    vocab, n_ids = cfg["sparse_vocab"], cfg["sparse_ids"]
    rng = np.random.RandomState(seed)
    out = {}
    for width in cfg["sparse_widths"]:
        why = sa.sparse_rows_gate(vocab, width, jnp.float32, interpret)
        if why is not None:
            out["width_%d" % width] = "gate: " + why
            continue
        ids = jnp.asarray(rng.randint(0, vocab, (n_ids,)).astype(np.int32))
        rows = jnp.asarray(rng.randn(n_ids, width).astype(np.float32))
        uniq, merged = merge_rows(ids, rows, vocab)
        p = jnp.asarray(rng.randn(vocab, width).astype(np.float32))
        m = jnp.asarray(0.1 * rng.randn(vocab, width).astype(np.float32))
        v = jnp.asarray(0.1 * np.abs(rng.randn(vocab, width))
                        .astype(np.float32))
        b1, b2, eps, lr = 0.9, 0.999, 1e-8, 0.01
        # the XLA scatter path of ops/optimizer_ops.adam_op, verbatim
        m_rows = b1 * m[uniq] + (1 - b1) * merged
        v_rows = b2 * v[uniq] + (1 - b2) * jnp.square(merged)
        want = (p.at[uniq].add(-(lr * m_rows / (jnp.sqrt(v_rows) + eps))),
                m.at[uniq].add(m_rows - m[uniq]),
                v.at[uniq].add(v_rows - v[uniq]),
                p.at[uniq].add(-0.5 * merged))
        want = [np.asarray(w) for w in want]
        sgd = np.asarray(sa.sparse_sgd_rows(p, uniq, merged, 0.5,
                                            interpret=interpret))
        got = [np.asarray(g) for g in sa.sparse_adam_rows(
            p, m, v, uniq, merged, lr, b1, b2, eps, interpret=interpret)]
        for name, g, w in zip(("param", "m", "v", "sgd"), got + [sgd], want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6, err_msg=(
                "sparse rows kernel vs scatter: %s at width %d"
                % (name, width)))
        out["width_%d" % width] = ("adam+sgd kernel == scatter on [%d, %d], "
                                   "%d ids" % (vocab, width, n_ids))
    return out


def _kernel_paged(seed, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    cfg = SIZES["serve"]
    slots, h = cfg["slots"], cfg["n_head"]
    d, ps = cfg["d_model"] // h, cfg["page_size"]
    pps = cfg["max_seq"] // ps
    why = pa.paged_attention_gate(jnp.float32, h, d, ps, interpret)
    if why is not None:
        return {"gate": why}
    num_pages = slots * pps
    rng = np.random.RandomState(seed)
    pt = rng.permutation(num_pages).reshape(slots, pps).astype(np.int32)
    # ragged: one token, a page boundary on both sides, the maximum
    ctx = np.resize(np.array([1, ps, ps + 1, cfg["max_seq"] // 2 + 3,
                              cfg["max_seq"]], np.int32), slots)
    ctx[-1] = cfg["max_seq"]
    k_pool = jnp.asarray(rng.randn(num_pages * ps, h * d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(num_pages * ps, h * d), jnp.float32)
    q = jnp.asarray(rng.randn(slots, h, d), jnp.float32)
    sm = 1.0 / float(d) ** 0.5
    got = jax.jit(lambda *a: pa.paged_decode_attention(
        *a, page_size=ps, sm_scale=sm, interpret=interpret))(
            q, k_pool, v_pool, jnp.asarray(pt), jnp.asarray(ctx))
    # the TPU's default f32 matmul rounds its inputs to bf16; the kernel
    # does not, so its reference must not either
    with jax.default_matmul_precision("highest"):
        want = pa.gather_reference(q, k_pool, v_pool, jnp.asarray(pt),
                                   jnp.asarray(ctx), ps, sm_scale=sm)
    err, _ = _max_err(got, want)
    check(err <= 1e-4, "paged kernel differs from gather by %g" % err)
    return {"shape": {"slots": slots, "n_head": h, "d_head": d,
                      "page_size": ps, "pages_per_slot": pps},
            "dtype": "float32", "ctx_len": [int(c) for c in ctx],
            "max_abs_err": err}


def _kernel_mla(seed, interpret):
    """The latent decode kernel at the served row (64 heads over 640 lanes,
    512 of them the latent), four double-buffered waves a full slot: in
    float32 against the gather path at full precision, and in bfloat16
    (the MXU's own type, as served) against the same rows in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas_kernels import mla_attention as mla

    cfg = SIZES["serve_mla"]
    h, rank = cfg["n_head"], cfg["kv_rank"]
    values = rank + cfg["d_rope"]
    width = -(-values // 128) * 128
    ps, slots, pps = cfg["page_size"], 8, 128
    why = mla.mla_decode_gate(jnp.bfloat16, width, rank, ps, interpret)
    if why is not None:
        return {"gate": why}
    rng = np.random.RandomState(seed)
    pt = rng.permutation(slots * pps).reshape(slots, pps).astype(np.int32)
    ctx = np.resize(np.array([1, ps, ps + 1, 515, 0, pps * ps // 2 + 3],
                             np.int32), slots)
    ctx[-1] = pps * ps
    pool = np.zeros((slots * pps * ps, width), np.float32)
    pool[:, :values] = rng.randn(pool.shape[0], values)
    q = np.zeros((slots, h, width), np.float32)
    q[..., :values] = rng.randn(slots, h, values)
    sm = 1.0 / float(values) ** 0.5
    errs = {}
    for dtype, bound in (("float32", 1e-4), ("bfloat16", 3e-2)):
        qd, pd = jnp.asarray(q, dtype), jnp.asarray(pool, dtype)
        got = jax.jit(lambda *a: mla.mla_paged_decode(
            *a, page_size=ps, rank=rank, sm_scale=sm, interpret=interpret))(
                qd, pd, jnp.asarray(pt), jnp.asarray(ctx))
        with jax.default_matmul_precision("highest"):
            want = mla.mla_gather_reference(
                qd.astype(jnp.float32), pd.astype(jnp.float32),
                jnp.asarray(pt), jnp.asarray(ctx), ps, rank, sm_scale=sm)
        live = ctx > 0
        err, _ = _max_err(np.asarray(got, np.float32)[live],
                          np.asarray(want)[live])
        check(err <= bound, "latent kernel (%s) differs from gather by %g"
              % (dtype, err))
        check(not np.asarray(got, np.float32)[~live].any(),
              "a slot of length 0 did not come back exactly 0.0")
        errs[dtype] = err
    return {"shape": {"slots": slots, "n_head": h, "row": width,
                      "rank": rank, "page_size": ps, "pages_per_slot": pps},
            "ctx_len": [int(c) for c in ctx], "max_abs_err": errs}


def _kernel_expert_stream(seed, interpret):
    """The routed experts' fused feed-forward (gate, up and down in one
    kernel over the touched experts' weights) in bfloat16, as served,
    against the plain float32 statement of the same rows; and beside what
    it replaces, ``ragged_dot`` x 3, which rounds gate and up before the
    activation: the kernel may not lie farther from the statement."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas_kernels import expert_stream as es

    out = {}
    for name, (m, e, d, f, touched, live) in sorted(
            SIZES["kernels"]["expert_stream"].items()):
        why = es.expert_stream_gate(m, e, d, f, jnp.bfloat16,
                                    interpret=interpret)
        if why is not None:
            out[name] = {"gate": why}
            continue
        rng = np.random.RandomState(seed)
        sizes = np.zeros((e,), np.int32)
        who = rng.choice(e, touched, replace=False)
        sizes[who] = 1 + rng.multinomial(live - touched,
                                         np.ones(touched) / touched)
        kx, kg, ku, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
        xs = jax.random.normal(kx, (m, d), jnp.float32).astype(jnp.bfloat16)
        wg, wu = ((jax.random.normal(k, (e, d, f), jnp.float32)
                   / np.sqrt(d)).astype(jnp.bfloat16) for k in (kg, ku))
        wd = (jax.random.normal(kd, (e, f, d), jnp.float32)
              / np.sqrt(f)).astype(jnp.bfloat16)
        sizes = jnp.asarray(sizes)
        got = np.asarray(jax.jit(lambda *a: es.expert_stream_ffn(
            *a, jax.nn.silu, interpret=interpret))(xs, wg, wu, wd, sizes),
            np.float32)
        want = np.asarray(jax.jit(lambda *a: es.expert_ffn_reference(
            *a, jax.nn.silu))(xs, wg, wu, wd, sizes))
        before = np.asarray(jax.jit(lambda *a: moe_ops._ragged_ffn(
            *a, jax.nn.silu))(xs, wg, wu, wd, sizes), np.float32)[:live]
        err, _ = _max_err(got[:live], want[:live])
        scale = float(np.abs(want).max())
        mean = float(np.abs(got[:live] - want[:live]).mean())
        mean_before = float(np.abs(before - want[:live]).mean())
        check(err <= 2e-2 * scale,
              "expert stream kernel (%s) differs from the float32 "
              "statement by %g of %g" % (name, err, scale))
        check(mean <= 1.02 * mean_before,
              "expert stream kernel (%s) lies farther from the float32 "
              "statement (%g a value) than ragged_dot x 3 (%g)"
              % (name, mean, mean_before))
        check(not got[live:].any(),
              "rows past the last group did not come back 0 (%s)" % name)
        plan = es.expert_stream_plan(m, e, d, f, jnp.bfloat16)
        out[name] = {"shape": {"rows": m, "experts": e, "d": d, "f": f,
                               "touched": touched, "live": live},
                     "blocks": [plan["nkd"], plan["nkf"]],
                     "max_abs_err": err, "max_abs": scale,
                     "mean_abs_err": mean,
                     "mean_abs_err_ragged_dot": mean_before}
    return out


def phase_kernels(seed, meter):
    interpret = not on_tpu()
    out = {"flash_attention": _kernel_flash(seed, interpret),
           "softmax_xent": _kernel_xent(seed, interpret),
           "sparse_rows": _kernel_sparse(seed, interpret),
           "paged_attention": _kernel_paged(seed, interpret),
           "mla_latent_decode": _kernel_mla(seed, interpret),
           "ragged_dot_stream": _kernel_expert_stream(seed, interpret)}
    return {"checked": "each Pallas kernel against its plain reference",
            "kernel_path": "interpreted" if interpret else "compiled",
            "kernels": out, "tune": tune_layers()}


# -- serve ---------------------------------------------------------------------


def phase_serve(seed, meter):
    import jax
    import numpy as np

    from paddle_tpu.models.decoder_lm import (
        DecoderConfig, DecoderLM, reference_tokens)
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = SIZES["serve"]
    mcfg = DecoderConfig(vocab_size=cfg["vocab"], n_layer=cfg["n_layer"],
                         d_model=cfg["d_model"], n_head=cfg["n_head"],
                         max_seq=cfg["max_seq"], dtype="float32")
    rng = np.random.RandomState(seed)
    lengths = np.linspace(cfg["prompt_min"], cfg["prompt_max"],
                          cfg["requests"]).round().astype(int)
    prompts = [rng.randint(0, cfg["vocab"], (n,)).tolist() for n in lengths]
    # Greedy tokens of random weights tie within the error of the TPU's
    # default f32 matmul (inputs rounded to bf16), and the decode kernel
    # does not round where XLA's einsum does: engine and reference are
    # comparable token for token only at one stated precision.
    with jax.default_matmul_precision("highest"):
        model = DecoderLM(mcfg, seed=seed)
        engine = ServingEngine(model, ServingConfig(
            slots=cfg["slots"], page_size=cfg["page_size"],
            max_seq=cfg["max_seq"], prompt_buckets=cfg["buckets"]))
        with engine:
            kernel_info = engine.decode_kernel_info()
            engine.warmup()
            after_warmup = meter.read()
            reqs = [engine.submit(p, cfg["new_tokens"]) for p in prompts]
            engine.run()
            serving = Meter.delta(meter.read(), after_warmup)
            accounting = engine.page_accounting_ok()
        states = [r.state for r in reqs]
        check(all(s == "finished" for s in states),
              "not every request finished: %s" % states)
        check(all(len(r.tokens_out) == cfg["new_tokens"] for r in reqs),
              "token counts: %s" % [len(r.tokens_out) for r in reqs])
        check(accounting, "page accounting does not balance after the drain")
        # (the host-side slot bookkeeping still jits a few one-op programs
        # on first use; the engine's own executables all come from warmup)
        check(serving["aot_compiles"] == 0,
              "the engine built an executable after warmup(): %s" % serving)
        for i in range(cfg["reference_requests"]):
            got = list(reqs[i].tokens_out)
            want = reference_tokens(model.params, mcfg, prompts[i], got)
            check(got == want,
                  "request %d (prompt %d): engine %s != reference %s"
                  % (i, lengths[i], got, want))
    if on_tpu():
        check(kernel_info[0] == "paged",
              "default flags did not arm the paged kernel: %s"
              % (kernel_info,))
    return {"checked": "%d requests (prompts %s) finished with %d tokens "
                       "each; greedy tokens of the first %d == the "
                       "float32 full-recompute reference "
                       "(decoder_lm.reference_tokens: reference_decode's "
                       "verdict in one pass); page accounting "
                       "balances; no executable built after warmup()"
                       % (len(reqs), [int(n) for n in lengths],
                          cfg["new_tokens"], cfg["reference_requests"]),
            "matmul_precision": "highest",
            "kernel_path": {"decode_attention": kernel_info[0],
                            "prefill_attention": "composed (segment ids)"},
            "decode_kernel_info": list(kernel_info),
            "tune": tune_layers()}


def phase_serve_moe(seed, meter):
    """The sparse decoder through the same engine: two cache groups, the
    grouped-query kernel, the dropless expert layer; greedy tokens against
    its own float32 reference, one context past the window."""
    import jax
    import numpy as np

    from grid.reference import smallthinker as reference
    from paddle_tpu.models.smallthinker import (
        SmallThinkerConfig, SmallThinkerLM)
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = SIZES["serve_moe"]
    pattern = ([0, 1, 1, 1] * cfg["n_layer"])[:cfg["n_layer"]]
    mcfg = SmallThinkerConfig(
        vocab_size=cfg["vocab"], n_layer=cfg["n_layer"],
        d_model=cfg["d_model"], n_head=cfg["n_head"],
        n_kv_head=cfg["n_kv_head"], d_head=cfg["d_head"],
        n_expert=cfg["n_expert"], top_k=cfg["top_k"],
        d_expert=cfg["d_expert"], window=cfg["window"], rope_layout=pattern,
        window_layout=pattern, max_seq=cfg["max_seq"], dtype="float32")
    published = {
        "num_attention_heads": cfg["n_head"],
        "num_key_value_heads": cfg["n_kv_head"],
        "moe_num_active_primary_experts": cfg["top_k"],
        "rope_layout": pattern, "sliding_window_layout": pattern,
        "sliding_window_size": cfg["window"], "rope_theta": mcfg.rope_theta,
        "rms_norm_eps": mcfg.rms_eps}
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg["vocab"], (n,)).tolist()
               for n in cfg["prompts"]]
    # one stated precision on both sides, as in phase_serve
    with jax.default_matmul_precision("highest"):
        model = SmallThinkerLM(mcfg, seed=seed)
        engine = ServingEngine(model, ServingConfig(
            slots=cfg["slots"], page_size=cfg["page_size"],
            max_seq=cfg["max_seq"], prompt_buckets=cfg["buckets"]))
        with engine:
            kernel_info = engine.decode_kernel_info()
            engine.warmup()
            reqs = [engine.submit(p, cfg["new_tokens"]) for p in prompts]
            peak = 0
            while not engine.scheduler.idle():
                engine.step()
                peak = max(peak, engine.pools[1].num_used)
            accounting = engine.page_accounting_ok()
        check(all(r.state == "finished" for r in reqs),
              "not every request finished: %s" % [r.state for r in reqs])
        check(accounting, "page accounting does not balance in every group")
        ring = cfg["window"] // cfg["page_size"]
        check(peak <= ring * len(reqs),
              "the window group held %d pages, over %d a slot" % (peak, ring))
        longest = max(cfg["prompts"]) + cfg["new_tokens"]
        check(longest > cfg["window"], "no context passed the window")
        for prompt, req in zip(prompts, reqs):
            got = list(req.tokens_out)
            seq = prompt + got[:-1]
            first = len(prompt) - 1
            rows = reference.forward(
                model.params, published, np.asarray(seq, np.int32),
                rows=np.arange(first, first + len(got)))
            want = [int(t) for t in np.asarray(rows).argmax(-1)]
            check(got == want,
                  "prompt %d: engine %s != reference %s"
                  % (len(prompt), got, want))
    if on_tpu():
        check(kernel_info[0] == "paged",
              "default flags did not arm the paged kernel at 7 query heads "
              "a KV head: %s" % (kernel_info,))
    return {"checked": "%d requests (prompts %s, %d tokens each, the longest "
                       "context %d past the window of %d) through two cache "
                       "groups: greedy tokens == the float32 reference's "
                       "(grid/reference/smallthinker.py); the window group "
                       "never over %d pages a slot; both pools balance"
                       % (len(reqs), list(cfg["prompts"]), cfg["new_tokens"],
                          longest, cfg["window"], ring),
            "matmul_precision": "highest",
            "kernel_path": {"decode_attention": kernel_info[0],
                            "prefill_attention": "composed, banded past "
                                                 "the window",
                            "experts": "ragged_dot"},
            "decode_kernel_info": list(kernel_info),
            "tune": tune_layers()}


def phase_serve_mla(seed, meter):
    """The latent-attention decoder through the same engine at its
    published widths: one expanded prefill, sixteen absorbed decode steps
    through the latent cache, the served tokens within the configuration's
    margin of its float32 reference given the same share."""
    import numpy as np

    from grid.reference import kimi_k2 as reference
    from paddle_tpu.models.kimi_k2 import KimiK2Config, KimiK2LM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = SIZES["serve_mla"]
    yarn = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
            "mscale_all_dim": 1, "type": "yarn",
            "original_max_position_embeddings": cfg["yarn_positions"]}
    held = list(range(cfg["held"]))
    mcfg = KimiK2Config(
        vocab_size=cfg["vocab"], n_layer=cfg["n_layer"],
        d_model=cfg["d_model"], n_head=cfg["n_head"], q_rank=cfg["q_rank"],
        kv_rank=cfg["kv_rank"], d_nope=cfg["d_nope"], d_rope=cfg["d_rope"],
        d_v=cfg["d_v"], d_dense=cfg["d_dense"], n_dense=1,
        n_expert=cfg["n_expert"], top_k=cfg["top_k"],
        d_expert=cfg["d_expert"], routed_scale=2.827, rope_scaling=yarn,
        max_seq=cfg["max_seq"], dtype=cfg["dtype"], experts_held=held)
    published = {
        "num_attention_heads": cfg["n_head"],
        "qk_nope_head_dim": cfg["d_nope"], "qk_rope_head_dim": cfg["d_rope"],
        "num_experts_per_tok": cfg["top_k"], "routed_scaling_factor": 2.827,
        "rope_theta": mcfg.rope_theta, "rope_scaling": yarn,
        "rms_norm_eps": mcfg.rms_eps, "experts_held": held}
    prompt = np.random.RandomState(seed).randint(
        0, cfg["vocab"], (cfg["prompt"],)).tolist()
    model = KimiK2LM(mcfg, seed=seed)
    engine = ServingEngine(model, ServingConfig(
        slots=cfg["slots"], page_size=cfg["page_size"],
        max_seq=cfg["max_seq"], prompt_buckets=cfg["buckets"],
        collect_logits=True))
    with engine:
        kernel_info = engine.decode_kernel_info()
        engine.warmup()
        req = engine.submit(prompt, cfg["new_tokens"])
        engine.run()
        logits = np.stack(engine.captured_logits(req)).astype(np.float32)
        accounting = engine.page_accounting_ok()
        layout = sorted(engine._cache)
    check(req.state == "finished" and len(req.tokens_out)
          == cfg["new_tokens"], "the request ended %s with %d tokens"
          % (req.state, len(req.tokens_out)))
    check(accounting, "page accounting does not balance after the drain")
    check(layout == ["c", "pt"], "the cache holds %s" % layout)
    check(np.isfinite(logits).all(), "a served logit is not finite")
    worst = reference.worst_margin(model.params, published, prompt,
                                   list(req.tokens_out))
    check(worst <= reference.LOGIT_MARGIN,
          "a served token ranks %.4f below the float32 reference's argmax "
          "(margin %.4f)" % (worst, reference.LOGIT_MARGIN))
    if on_tpu():
        check(kernel_info[0] == "mla_paged",
              "default flags did not arm the latent kernel: %s"
              % (kernel_info,))
    return {"checked": "a prompt of %d: one expanded prefill and %d absorbed "
                       "decode steps through a latent cache of one [c | kr] "
                       "row a token; logits finite; the served tokens rank "
                       "%.4f below the float32 reference's best at worst "
                       "(grid/reference/kimi_k2.py given experts 0-%d, "
                       "margin %.2f); the pool balances"
                       % (cfg["prompt"], cfg["new_tokens"] - 1, worst,
                          cfg["held"] - 1, reference.LOGIT_MARGIN),
            "matmul_precision": "default (%s weights)" % cfg["dtype"],
            "kernel_path": {"decode_attention": kernel_info[0],
                            "prefill_attention": "composed, expanded",
                            "experts": "ragged_dot over the share"},
            "decode_kernel_info": list(kernel_info),
            "reference_margin": worst,
            "tune": tune_layers()}


# -- data parallel (--chips 4) ------------------------------------------------


def phase_data_parallel(seed, meter):
    import jax
    import numpy as np

    import paddle_tpu as fluid

    cfg = dict(SIZES["train"], steps=SIZES["dp"]["steps"])
    n_dev = len(jax.devices())
    feed = _transformer_feed(cfg, seed)
    results = {}
    for mode in ("one_device", "data_parallel"):
        with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, startup, loss = _build_transformer(fluid, cfg, seed)
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(startup)
            prog = main
            if mode == "data_parallel":
                prog = fluid.CompiledProgram(main).with_data_parallel(
                    loss_name=loss.name)
            step = exe.prepare(prog, feed=feed, fetch_list=[loss])
            losses, _ = _train_steps(exe, prog, feed, loss, cfg["steps"])
            if mode == "data_parallel":
                hlo = step._aot.as_text()
                (_, feed_sh, _), _ = step._aot.input_shardings
                scope = fluid.global_scope()
                params = [scope.find_var(p.name)
                          for p in main.all_parameters()]
            exe.close()
        results[mode] = losses
    one, dp = results["one_device"], results["data_parallel"]
    check(all(np.isfinite(one + dp)), "a loss is not finite: %s" % results)
    # bf16 activations, and the batch reduced in another order
    np.testing.assert_allclose(dp, one, rtol=2e-2, err_msg=(
        "data-parallel losses vs one device"))
    shard_rows = {n: sh.shard_shape(feed[n].shape)[0]
                  for n, sh in feed_sh.items()}
    feed_devs = {n: len(sh.device_set) for n, sh in feed_sh.items()}
    param_devs = sorted({len(p.sharding.device_set) for p in params})
    check(all(r == cfg["batch"] // n_dev for r in shard_rows.values())
          and all(c == n_dev for c in feed_devs.values()),
          "feeds are not split over %d devices: rows %s, devices %s"
          % (n_dev, shard_rows, feed_devs))
    check(param_devs == [n_dev],
          "parameters are not laid out over all %d devices: %s"
          % (n_dev, param_devs))
    n_allreduce = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    check(n_allreduce > 0, "no all-reduce in the compiled step")
    return {"checked": "%d steps over %d devices == one device within bf16 "
                       "tolerance; each feed split %d rows a device over %d "
                       "distinct devices (so each device's gradient comes "
                       "from its own rows); parameters replicated over %d "
                       "devices; %d all-reduce ops in the compiled step"
                       % (cfg["steps"], n_dev, cfg["batch"] // n_dev, n_dev,
                          n_dev, n_allreduce),
            "losses_one_device": one, "losses_data_parallel": dp,
            "kernel_path": "CompiledProgram.with_data_parallel (GSPMD)",
            "tune": tune_layers()}


# -- driver --------------------------------------------------------------------

PHASES = {1: (("train", phase_train), ("ctr", phase_ctr),
              ("kernels", phase_kernels), ("serve", phase_serve),
              ("serve_moe", phase_serve_moe),
              ("serve_mla", phase_serve_mla)),
          4: (("data_parallel", phase_data_parallel),)}


def _tuned(doc):
    """Kernels of a phase line whose configuration came from a ``tuned``
    table."""
    return sorted(k for k, v in (doc.get("tune") or {}).items()
                  if v == "tuned")


def run_phase(name, fn, seed, meter):
    """Run one phase and print its line; True iff it passed."""
    from paddle_tpu import tune

    tune.reset_provenance()
    before = meter.read()
    t0 = time.perf_counter()
    line = {"phase": name}
    try:
        doc = fn(seed, meter)
        stray = _tuned(doc)
        check(not stray, "kernels %s were configured by a tuned table at %s "
                         "— the chip run must not depend on one"
                         % (stray, tune.table_path()))
        line.update(ok=True, **doc)
    except Exception as e:
        traceback.print_exc()
        line.update(ok=False, error="%s: %s" % (type(e).__name__, e))
    used = Meter.delta(meter.read(), before)
    line.update(seconds=round(time.perf_counter() - t0, 3),
                compile_seconds=used["compile_s"], compiles=used["compiles"],
                persistent_cache={"hits": used["cache_hit"],
                                  "misses": used["cache_miss"]})
    print(json.dumps(line), flush=True)
    gc.collect()
    return line["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: train, ctr, kernels, serve, serve_moe, "
                         "serve_mla. 4: the data-parallel phase and what it "
                         "is compared with, and no other")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def last_line(ok, dev, **extra):
        print(json.dumps(dict({"ok": ok, "device": dev}, **extra)),
              flush=True)
        return 0 if ok else 1

    dev = None
    try:
        dev = device_doc()
        if dev["platform"] != "tpu":
            return last_line(False, dev, error="JAX found no TPU")
        if dev["count"] != args.chips:
            return last_line(False, dev, error=(
                "--chips %d asked, JAX has %d device(s)"
                % (args.chips, dev["count"])))
        import paddle_tpu  # noqa: F401  (places the compile cache)
        from paddle_tpu.compile_cache import compile_cache_dir
        from paddle_tpu.monitor.stepstats import device_peaks

        # THE peak table; a chip it does not know raises, before any work
        peaks = device_peaks(dev["kind"])
    except Exception as e:
        traceback.print_exc()
        return last_line(False, dev, error="%s: %s" % (type(e).__name__, e))
    print(json.dumps({"phase": "start", "device": dev, "peaks": peaks,
                      "compile_cache_dir": compile_cache_dir(),
                      "seed": args.seed}), flush=True)
    meter = Meter()
    results = [run_phase(name, fn, args.seed, meter)
               for name, fn in PHASES[args.chips]]
    return last_line(all(results), dev)


if __name__ == "__main__":
    sys.exit(main())

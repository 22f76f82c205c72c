"""Times the parts of GLM-5.3-Flash's prefill alone on the chip, at the
served widths and a bucket of ``--rows``: the DSA layer's masked attention
in both its forms (the blocked XLA at several ``block_q`` and the
``dsa_prefill_attention`` kernel: their times, the largest gap between
their results, the seconds one copy of the kernel takes to lower and to
compile, the kernel alone under a mask made before, and with ``--sweep``
the kernel at other tiles), index scores and
selection each alone (``--length``: the prompt in the bucket, which the
kernel's path is told: its query blocks past it are neither chosen nor
attended, and the gap is read over the rows under it), and one KDA layer's
chunk scan at 64
and at 32 heads in both its forms (the XLA loop and the ``kda_chunk_scan``
kernel: their times, the largest gap between their results, the seconds
one copy of the kernel takes to lower and to compile, and a KDA layer's
whole half, ``blocks.kda_prefill`` at Ling's hidden size, around each).
Milliseconds a call, the best of ``--reps`` after a warm-up.

    python benchmarks/diag_dsa_prefill.py --rows 8192
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def best_ms(fn, *args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


def dsa_part(out, s, reps, trace_dir, sweep=(), length=None):
    """The DSA layer's masked attention at GLM's widths, ``s`` rows of
    which ``length`` are the prompt's (None: all)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.monitor import metrics
    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.ops.pallas_kernels import dsa_prefill as dp

    h, d, hi, li, kpool, top = 64, 256, 32, 128, 4, 512
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (s, h, d), bf)
    k = jax.random.normal(ks[1], (s, h, d), bf)
    v = jax.random.normal(ks[2], (s, h, d), bf)
    qi = jax.random.normal(ks[3], (s, hi, li), bf)
    wi = jax.random.normal(ks[4], (s, hi), jnp.float32)
    kp = jax.random.normal(ks[5], (s // kpool, li), bf)
    live = s if length is None else int(length)
    n = jnp.asarray(live, jnp.int32)    # traced: one executable a bucket
    if trace_dir:
        from grid import reduce

        fn = jax.jit(lambda n, *a: ao.dsa_causal_attention(
            *a, kpool, top, 0.0625, length=n))
        jax.block_until_ready(fn(n, q, k, v, qi, wi, kp))
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(fn(n, q, k, v, qi, wi, kp))
        trace = reduce.load(reduce.find_xplane(trace_dir))
        out["device_ops_ms"] = [
            [name, round(t * 1e3, 2)] for name, t in
            reduce.breakdown(trace, None, top=14)["device_ops"]]
    # the blocked form: what dsa_causal_attention takes where no chip is
    on_tpu = ao._on_tpu
    ao._on_tpu = lambda: False
    try:
        for bq in (128, 256, 512):
            fn = jax.jit(lambda *a, bq=bq: ao.dsa_causal_attention(
                *a, kpool, top, 0.0625, block_q=bq))
            out["dsa_causal_attention_ms.blocked.block_q_%d" % bq] = best_ms(
                fn, q, k, v, qi, wi, kp, reps=reps)
            if bq == 256:
                blocked = fn(q, k, v, qi, wi, kp)
    finally:
        ao._on_tpu = on_tpu
    out["dsa_prefill_gate"] = dp.dsa_prefill_gate(h, d, d, s, kpool)
    fn = jax.jit(lambda n, *a: ao.dsa_causal_attention(
        *a, kpool, top, 0.0625, length=n))
    out["dsa_causal_attention_ms.kernel"] = best_ms(
        fn, n, q, k, v, qi, wi, kp, reps=reps)
    gap = jnp.abs(fn(n, q, k, v, qi, wi, kp).astype(jnp.float32)
                  - blocked.astype(jnp.float32))[:live]
    out["dsa_causal_attention_gap"] = float(gap.max())
    out["dsa_causal_attention_gap_mean"] = float(gap.mean())
    out["dsa_prefill_calls"] = {
        form: int(metrics.counter("dsa/prefill_calls." + form).value)
        for form in ("kernel", "blocked")}

    # the kernel alone, under the mask of rows that choose nothing (the
    # causal triangle: the work is the mask's shape's, not its content's)
    mask = jnp.tril(jnp.ones((s, s), jnp.int8))
    t0 = time.perf_counter()
    lowered = jax.jit(lambda *a: dp.dsa_prefill_attention(
        *a, sm_scale=0.0625)).lower(q, k, v, mask, n)
    t1 = time.perf_counter()
    alone = lowered.compile()
    out["dsa_prefill_attention_lower_s"] = t1 - t0
    out["dsa_prefill_attention_compile_s"] = time.perf_counter() - t1
    out["dsa_prefill_attention_ms"] = best_ms(alone, q, k, v, mask, n,
                                              reps=reps)
    for tiles in sweep:
        bq, bk, g = (int(x) for x in tiles.split("x"))
        fn = jax.jit(lambda *a, bq=bq, bk=bk, g=g: dp.dsa_prefill_attention(
            *a, sm_scale=0.0625, block_q=bq, block_k=bk, heads=g))
        try:
            out["dsa_prefill_attention_ms.%s" % tiles] = best_ms(
                fn, q, k, v, mask, n, reps=reps)
        except Exception as e:      # a tile the chip's compiler refuses
            out["dsa_prefill_attention_ms.%s" % tiles] = repr(e)[:200]

    def index_only(qi, wi, kp):
        own = jnp.arange(s) // kpool
        return ao.dsa_index_scores(qi, wi, kp, own)

    scores = jax.jit(index_only)(qi, wi, kp)
    out["index_scores_all_rows_ms"] = best_ms(jax.jit(index_only), qi, wi,
                                              kp, reps=reps)
    own = jnp.arange(s) // kpool
    out["select_all_rows_ms"] = best_ms(
        jax.jit(lambda sc, own: ao.dsa_select(sc, own, top)[0]), scores, own,
        reps=reps)
    out["top_k_all_rows_ms"] = best_ms(
        jax.jit(lambda sc: jax.lax.top_k(sc, top - 1)[0]), scores,
        reps=reps)


def kda_part(out, s, reps, heads=(64, 32), dk=128, d=2560):
    """One KDA layer's scan over ``s`` rows at 64 and at 32 heads of 128,
    by the XLA loop and by the kernel (whose one copy's lowering and
    compilation are timed first, cold), and the layer's whole half around
    each form (what either leaves the compiler to do before and after the
    loop shows there and not in the scan alone)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import blocks
    from paddle_tpu.models import ling3_flash as lf
    from paddle_tpu.ops.pallas_kernels import kda

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    h = max(heads)
    qk = jax.random.normal(ks[0], (s, h, dk), jnp.float32)
    unit = (qk / jnp.linalg.norm(qk, axis=-1, keepdims=True)).astype(bf)
    a = -jnp.abs(jax.random.normal(ks[1], (s, h, dk), jnp.float32)) * 0.1
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (s, h), jnp.float32))
    hidden = jax.random.normal(ks[3], (s, d), bf)
    for n in heads:
        x = (unit[:, :n], unit[:, :n], qk[:, :n].astype(bf), a[:, :n],
             beta[:, :n])
        t0 = time.perf_counter()
        lowered = jax.jit(kda.kda_chunk_scan_kernel).lower(*x)
        t1 = time.perf_counter()
        forms = {"kernel": lowered.compile(),
                 "xla": jax.jit(kda.kda_chunk_scan_xla)}
        out["kda_chunk_scan_kernel_lower_s.%d_heads" % n] = t1 - t0
        out["kda_chunk_scan_kernel_compile_s.%d_heads" % n] = (
            time.perf_counter() - t1)
        for form, fn in forms.items():
            out["kda_chunk_scan_ms.%d_heads.%s" % (n, form)] = best_ms(
                fn, *x, reps=reps)
        out["kda_chunk_scan_gap.%d_heads" % n] = max(
            float(jnp.abs(p - r).max())
            for p, r in zip(forms["kernel"](*x), forms["xla"](*x)))
        cfg = lf.Ling3FlashConfig(
            vocab_size=128, n_layer=1, d_model=d, n_head=n, d_state=dk,
            layer_types=["kda"], kv_rank=512, d_nope=128, d_rope=64,
            d_v=128, d_dense=128, dense_layers=(0,), n_expert=8, top_k=2,
            d_expert=128, n_group=1, topk_group=1, max_seq=s,
            dtype="bfloat16", experts_held=tuple(range(8)))
        lp = lf.init_params(cfg, 0)["layers"][0]
        scan = kda.kda_chunk_scan
        try:
            for form, fn in (("xla", kda.kda_chunk_scan_xla),
                             ("kernel", kda.kda_chunk_scan_kernel)):
                kda.kda_chunk_scan = fn
                out["kda_prefill_ms.%d_heads.%s" % (n, form)] = best_ms(
                    jax.jit(lambda lp, x: blocks.kda_prefill(cfg, lp, x, s)),
                    lp, hidden, reps=reps)
        finally:
            kda.kda_chunk_scan = scan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--length", type=int, default=None,
                    help="the prompt's rows in the bucket of --rows (all)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--parts", default="dsa,kda",
                    help="which layers' parts to time")
    ap.add_argument("--sweep", default="",
                    help="the kernel alone at other tiles too: a list of "
                         "BLOCK_QxBLOCK_KxHEADS, such as 1024x512x4,256x256x8")
    ap.add_argument("--trace", default="",
                    help="a directory: profile one call of the DSA layer's "
                         "attention there and print its operations by time")
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        print("diag_dsa_prefill: no TPU", file=sys.stderr)
        return 3
    out = {"rows": args.rows, "length": args.length or args.rows,
           "device": jax.devices()[0].device_kind}
    parts = args.parts.split(",")
    if "dsa" in parts:
        dsa_part(out, args.rows, args.reps, args.trace,
                 [t for t in args.sweep.split(",") if t], args.length)
    if "kda" in parts:
        kda_part(out, args.rows, args.reps)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

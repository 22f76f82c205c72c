"""Times the parts of GLM-5.3-Flash's prefill alone on the chip, at the
served widths and a bucket of ``--rows``: the DSA layer's masked attention
by query blocks (index scores, selection, attention: each alone and
together, at several ``block_q``), and one KDA layer's chunk scan at 64
heads. Milliseconds a call, the best of ``--reps`` after a warm-up.

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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--trace", default="",
                    help="a directory: profile one call of the DSA layer's "
                         "attention there and print its operations by time")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.ops.pallas_kernels import kda

    if jax.default_backend() != "tpu":
        print("diag_dsa_prefill: no TPU", file=sys.stderr)
        return 3
    s, h, d, hi, li, kpool, top = args.rows, 64, 256, 32, 128, 4, 512
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (s, h, d), bf)
    k = jax.random.normal(ks[1], (s, h, d), bf)
    v = jax.random.normal(ks[2], (s, h, d), bf)
    qi = jax.random.normal(ks[3], (s, hi, li), bf)
    wi = jax.random.normal(ks[4], (s, hi), jnp.float32)
    kp = jax.random.normal(ks[5], (s // kpool, li), bf)
    out = {"rows": s, "device": jax.devices()[0].device_kind}
    if args.trace:
        from grid import reduce

        fn = jax.jit(lambda *a: ao.dsa_causal_attention(
            *a, kpool, top, 0.0625))
        jax.block_until_ready(fn(q, k, v, qi, wi, kp))
        with jax.profiler.trace(args.trace):
            jax.block_until_ready(fn(q, k, v, qi, wi, kp))
        trace = reduce.load(reduce.find_xplane(args.trace))
        out["device_ops_ms"] = [
            [name, round(t * 1e3, 2)] for name, t in
            reduce.breakdown(trace, None, top=14)["device_ops"]]
    for bq in (128, 256, 512):
        fn = jax.jit(lambda *a, bq=bq: ao.dsa_causal_attention(
            *a, kpool, top, 0.0625, block_q=bq))
        out["dsa_causal_attention_ms.block_q_%d" % bq] = best_ms(
            fn, q, k, v, qi, wi, kp, reps=args.reps)

    def index_only(qi, wi, kp):
        own = jnp.arange(s) // kpool
        return ao.dsa_index_scores(qi, wi, kp, own)

    scores = jax.jit(index_only)(qi, wi, kp)
    out["index_scores_all_rows_ms"] = best_ms(jax.jit(index_only), qi, wi,
                                              kp, reps=args.reps)
    own = jnp.arange(s) // kpool
    out["select_all_rows_ms"] = best_ms(
        jax.jit(lambda sc, own: ao.dsa_select(sc, own, top)[0]), scores, own,
        reps=args.reps)
    out["top_k_all_rows_ms"] = best_ms(
        jax.jit(lambda sc: jax.lax.top_k(sc, top - 1)[0]), scores,
        reps=args.reps)
    # one KDA layer's scan at 64 heads of 128
    dk = 128
    qk = jax.random.normal(ks[6], (s, h, dk), bf)
    a = -jnp.abs(jax.random.normal(ks[7], (s, h, dk), jnp.float32)) * 0.1
    beta = jax.nn.sigmoid(jax.random.normal(ks[0], (s, h), jnp.float32))
    out["kda_chunk_scan_ms.64_heads"] = best_ms(
        jax.jit(kda.kda_chunk_scan), qk, qk, qk, a, beta, reps=args.reps)
    out["kda_chunk_scan_ms.32_heads"] = best_ms(
        jax.jit(kda.kda_chunk_scan), qk[:, :32], qk[:, :32], qk[:, :32],
        a[:, :32], beta[:, :32], reps=args.reps)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

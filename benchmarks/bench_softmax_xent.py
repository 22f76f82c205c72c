"""Microbenchmark: Pallas fused softmax-xent vs the XLA-composed lowering.

Run on a real TPU: ``python benchmarks/bench_softmax_xent.py``. Prints one
JSON line per config with the fwd+bwd wall time of both paths and the
speedup.

Claimed on a TPU v5 lite, July 2026, before this round (8192×32000 fp32
fwd+bwd, min of 20 per-call timings): pallas 107.1 ms vs XLA 131.7 ms →
1.23×. Both numbers carried a fixed per-call cost of tens of ms, so the
ratio is a lower bound; not re-measured since.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas_kernels import fused_softmax_xent


def composed(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels.astype(jnp.int32), axis=-1)


def timeit(fn, *args, iters=20):
    """Min-of-N per-call latency; the scalar fetch is the sync."""
    warm = fn(*args)
    float((warm[0] if isinstance(warm, tuple) else warm).sum())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        s = out[0] if isinstance(out, tuple) else out
        float(s.sum())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main():
    for n, v, dtype in [(8192, 32000, "float32"), (8192, 32000, "bfloat16")]:
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        logits = jax.random.normal(k1, (n, v), jnp.float32).astype(dtype)
        labels = jax.random.randint(k2, (n, 1), 0, v, jnp.int32)

        def step_fused(lg, lb):
            def f(x):
                return fused_softmax_xent(x, lb).sum()
            l, g = jax.value_and_grad(f)(lg)
            return l, g

        def step_composed(lg, lb):
            def f(x):
                return composed(x, lb).sum()
            l, g = jax.value_and_grad(f)(lg)
            return l, g

        jf = jax.jit(step_fused)
        jc = jax.jit(step_composed)
        # numerics parity on-device
        lf, gf = jf(logits, labels)
        lc, gc = jc(logits, labels)
        np.testing.assert_allclose(float(lf), float(lc), rtol=2e-3)
        np.testing.assert_allclose(np.asarray(gf, dtype="float32"),
                                   np.asarray(gc, dtype="float32"),
                                   rtol=5e-2, atol=5e-3)
        tf = timeit(jf, logits, labels)
        tc = timeit(jc, logits, labels)
        print(json.dumps({
            "bench": "softmax_xent_fwd_bwd", "n": n, "v": v, "dtype": dtype,
            "pallas_ms": round(tf * 1e3, 3), "xla_ms": round(tc * 1e3, 3),
            "speedup": round(tc / tf, 3),
        }))


if __name__ == "__main__":
    main()

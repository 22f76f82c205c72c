"""Attention microbenchmark on real TPU: the Pallas flash kernel vs the
XLA-composed O(S²) path, fwd+bwd, bf16 causal. Chained-loop difference
timing (k-vs-1 iterations inside one jit) cancels the per-call dispatch
cost.

Measured 2026-07-30 on v5e (loop-difference timing, causal fwd+bwd):
  r2 (f32 softmax): S=2048 flash 5.22 vs composed 3.32 ms; S=8192 13.41 vs 16.39
  r3 (bf16 softmax): S=8192 flash 11.53 vs composed 4.03 ms;
                     S=16384 flash 96.64 vs composed 59.45 ms
  r4 (v5e-tuned BlockSizes 512x512): S=2048 flash 1.24 vs composed 2.00 ms
     (1.61x); S=4096 1.85 vs 6.40 (3.46x); S=8192 3.12 vs 12.93 (4.15x);
     S=16384 12.07 vs 39.20 (3.25x). Sweeps: sweep_flash_blocks.py,
     sweep_flash_crossover.py.
The stock all-128 BlockSizes were the r3 loss; with 512x512 tiles flash wins
everywhere above S~2048, so FLAGS_flash_attention_min_seq (default 2048) is
a PERF crossover, and flash's O(S) memory additionally rescues shapes where
composed OOMs (~24k single-chip).
"""

import json
import time

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention_ops import sdpa


def composed(q, k, v, causal):
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        m = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
        scores = jnp.where(m, scores, jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _per_iter_ms(fn, q, k, v, lo=1, hi=5, reps=4):
    def make(iters):
        def body(i, carry):
            qq, acc = carry

            def loss(t):
                return jnp.sum(fn(t, k, v).astype(jnp.float32) ** 2)

            l, g = jax.value_and_grad(loss)(qq)
            return qq + 1e-6 * g.astype(qq.dtype), acc + l

        return jax.jit(lambda: jax.lax.fori_loop(0, iters, body, (q, 0.0))[1])

    def tmin(f):
        float(f())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f())
            ts.append(time.perf_counter() - t0)
        return min(ts)

    return (tmin(make(hi)) - tmin(make(lo))) / (hi - lo) * 1e3


def main():
    from paddle_tpu.flags import get_flag, set_flag

    old_gate = get_flag("flash_attention_min_seq")
    for b, h, s, d in [(4, 8, 2048, 64), (1, 8, 8192, 64)]:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(k2, (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(k3, (b, h, s, d), jnp.bfloat16)
        set_flag("flash_attention_min_seq", 128)  # force flash for the A side
        tf = _per_iter_ms(lambda t, kk, vv: sdpa(t, kk, vv, causal=True,
                                                 sm_scale=d ** -0.5), q, k, v)
        set_flag("flash_attention_min_seq", old_gate)  # restore the default
        # B side calls the local composed() directly — no gate involved
        tc = _per_iter_ms(lambda t, kk, vv: composed(t, kk, vv, True), q, k, v)
        print(json.dumps({"bench": "attention_fwd_bwd_bf16_causal",
                          "b": b, "h": h, "s": s, "d": d,
                          "flash_ms": round(tf, 2), "composed_ms": round(tc, 2),
                          "flash_speedup": round(tc / tf, 3)}))


if __name__ == "__main__":
    main()

"""Sweep Pallas flash-attention BlockSizes on the real TPU.

Round-3 verdict: stock defaults (all-128 blocks) lose 0.627x to XLA-composed
attention at S=8192 (b1 h8 d64 causal bf16, fwd+bwd). This sweep finds the
v5e-optimal tiling. Timing is loop-difference (lo vs hi chained iterations)
per the established methodology in benchmarks/RESNET50_PROFILE.md.
"""
import functools
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as fa

B, H, S, D = 1, 8, 8192, 64
CAUSAL = True
DTYPE = jnp.bfloat16

key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, H, S, D), DTYPE)
k = jax.random.normal(kk, (B, H, S, D), DTYPE)
v = jax.random.normal(kv, (B, H, S, D), DTYPE)


def timeit(fn, *args, lo=2, hi=12):
    """Loop-difference timing of fn chained n times; returns ms/call."""
    def chain(n):
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                qq, kk2, vv = c
                o, g = fn(qq, kk2, vv)
                # real data dependence so XLA cannot hoist the body out of
                # the loop (a *0 perturbation gets constant-folded)
                return (qq + 1e-6 * g[0].astype(qq.dtype), kk2, vv), o[0][0, 0, 0, 0]
            (c, outs) = jax.lax.scan(body, (q, k, v), None, length=n)
            return outs
        return run
    import numpy as np
    r_lo, r_hi = chain(lo), chain(hi)
    # np.asarray (fetching the bytes) is the sync
    np.asarray(r_lo(q, k, v)); np.asarray(r_hi(q, k, v))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter(); np.asarray(r_lo(q, k, v)); t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); np.asarray(r_hi(q, k, v)); t_hi = time.perf_counter() - t0
        best = min(best, (t_hi - t_lo) / (hi - lo))
    return best * 1e3


def fwd_bwd(attn):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))
    def run(q, k, v):
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (g[0],), g
    return run


def composed(q, k, v):
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / D ** 0.5)
    cm = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(cm, scores, jnp.full_like(scores, -1e9))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def flash_with(bs):
    def attn(q, k, v):
        return fa.flash_attention(q, k, v, causal=CAUSAL, sm_scale=1.0 / D ** 0.5,
                                  block_sizes=bs)
    return attn


results = {}
t = timeit(fwd_bwd(composed))
results["composed"] = t
print(f"composed: {t:.2f} ms", flush=True)

configs = []
# (block_q, block_k_major=block_k, block_q_dkv=block_k_dkv, block_q_dq=block_k_dq)
for bq in (128, 256, 512, 1024):
    for bk in (128, 256, 512, 1024, 2048):
        configs.append((bq, bk))

for bq, bk in configs:
    name = f"q{bq}_k{bk}"
    try:
        bs = fa.BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq,
            block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
        )
        t = timeit(fwd_bwd(flash_with(bs)))
        results[name] = t
        print(f"{name}: {t:.2f} ms", flush=True)
    except Exception as e:
        print(f"{name}: FAIL {type(e).__name__}: {str(e)[:120]}", flush=True)

if len(results) > 1:
    best = min((v, k) for k, v in results.items() if k != "composed")
    print(json.dumps({"composed_ms": results["composed"], "best": best[1],
                      "best_ms": best[0],
                      "speedup": results["composed"] / best[0]}))
else:
    print(json.dumps({"composed_ms": results.get("composed"),
                      "best": None, "note": "every block config failed"}))

"""What a decode pass of the routed experts costs on the chip, apart from
everything around it: the three grouped matmuls (gate, up, down) of
``ops/moe_ops.py`` at the served decode geometries, with ``sizes`` drawn as
the cells draw them (so many experts touched, so many live rows), varying
one quantity at a time: rows a pass, experts touched, experts held (the
empty groups), live rows. Both forms are timed where both exist: the
compiler's ``ragged_dot`` x 3 and the fused stream kernel
(``ops/pallas_kernels/expert_stream.py``).

    python benchmarks/diag_expert_stream.py [--only ling] [--impl ragged]

One JSON line a point: the geometry, microseconds a call (the median of
``--reps`` timings of ``--chain`` chained calls in one executable), the
bytes of the touched experts' weights and the share of the HBM rate that
is. Run on the chip; it refuses another backend.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

HBM_BYTES_PER_S = 819e9     # TPU v5e (grid/peaks.json)

# name: (rows a pass, experts held, d, f, experts touched, live rows)
GEOMETRIES = {
    "smallthinker": (96, 64, 2560, 768, 45, 96),
    "ling": (256, 128, 2560, 768, 47, 128),
    "kimi": (256, 12, 7168, 2048, 6, 8),
    "laguna": (160, 128, 3072, 1024, 59, 80),
}


def draw_sizes(rng, e, touched, live):
    """``touched`` of ``e`` groups share ``live`` rows, each at least one."""
    touched = max(1, min(touched, e, live))
    sizes = np.zeros((e,), np.int32)
    who = rng.choice(e, touched, replace=False)
    sizes[who] = 1
    extra = rng.multinomial(live - touched, np.ones(touched) / touched)
    sizes[who] += extra.astype(np.int32)
    return sizes


def _impl(name):
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas_kernels import expert_stream

    return {"ragged": moe_ops._ragged_ffn,
            "stream": expert_stream.expert_stream_ffn}[name]


def chained(impl, chain):
    """``chain`` calls in one executable, each fed the one before (so the
    compiler can neither drop nor overlap them)."""
    def run(xs, wg, wu, wd, sizes):
        def body(_, x):
            y = impl(x, wg, wu, wd, sizes, jax.nn.relu)
            return (x + y * jnp.asarray(1e-3, x.dtype)).astype(x.dtype)
        return jax.lax.fori_loop(0, chain, body, xs)
    return jax.jit(run)


def time_point(fn, args, reps, chain):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) / chain)
    return statistics.median(out) * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--impl", default="ragged,stream")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--chain", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(json.dumps({"ok": False, "why": "no TPU: %s" % dev.platform}))
        return 1
    impls = [(name, _impl(name)) for name in a.impl.split(",")]
    rng = np.random.default_rng(a.seed)
    lines = []
    for geo, (m0, e0, d, f, t0_, l0) in GEOMETRIES.items():
        if a.only and geo not in a.only.split(","):
            continue
        key = jax.random.PRNGKey(a.seed)
        kg, ku, kd, kx = jax.random.split(key, 4)
        full = {}

        def weights(e):
            if e not in full:
                s = 1.0 / np.sqrt(d)
                full[e] = (
                    (jax.random.normal(kg, (e, d, f), jnp.float32) * s
                     ).astype(jnp.bfloat16),
                    (jax.random.normal(ku, (e, d, f), jnp.float32) * s
                     ).astype(jnp.bfloat16),
                    (jax.random.normal(kd, (e, f, d), jnp.float32)
                     / np.sqrt(f)).astype(jnp.bfloat16))
            return full[e]

        points = [("base", m0, e0, t0_, l0)]
        for m in (32, 64, 128, 256, 512):
            if m != m0 and m >= l0:
                points.append(("rows", m, e0, t0_, l0))
        for t in sorted({1, max(1, t0_ // 4), max(1, t0_ // 2),
                         min(e0, l0, 2 * t0_), min(e0, l0)}):
            if t != t0_:
                points.append(("touched", m0, e0, t, max(l0, t)))
        for e in (e0 // 2, ):
            if e >= t0_:
                points.append(("held", m0, e, t0_, l0))
        for l in (max(t0_, l0 // 4), min(m0, l0 * 2)):
            if l != l0:
                points.append(("live", m0, e0, t0_, l))
        for why, m, e, t, l in points:
            wg, wu, wd = weights(e)
            sizes = jnp.asarray(draw_sizes(rng, e, t, l))
            xs = jax.random.normal(kx, (m, d), jnp.float32
                                   ).astype(jnp.bfloat16)
            touched = int(np.sum(np.asarray(sizes) > 0))
            need = touched * 3 * d * f * 2
            line = {"geometry": geo, "vary": why, "rows": m, "held": e,
                    "touched": touched, "live": int(np.sum(sizes)),
                    "d": d, "f": f, "need_us": need / HBM_BYTES_PER_S * 1e6}
            for name, impl in impls:
                try:
                    us = time_point(chained(impl, a.chain),
                                    (xs, wg, wu, wd, sizes), a.reps, a.chain)
                    line[name + "_us"] = us
                    line[name + "_roofline"] = 100.0 * line["need_us"] / us
                except Exception as exc:   # a geometry the kernel refuses
                    line[name + "_error"] = repr(exc)[:300]
            if len(impls) == 2 and "stream_error" not in line:
                # the two forms on the live rows (the others are unspecified)
                live_rows = (np.arange(m) < line["live"])[:, None]
                a_, b_ = (np.where(live_rows, np.asarray(jax.jit(
                    lambda *xs, f_=impl: f_(*xs, jax.nn.relu))(
                        xs, wg, wu, wd, sizes), np.float32), 0)
                    for _, impl in impls)
                line["max_abs_diff"] = float(np.max(np.abs(a_ - b_)))
                line["max_abs"] = float(np.max(np.abs(a_)))
            lines.append(line)
            print(json.dumps(line), flush=True)
        full.clear()
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

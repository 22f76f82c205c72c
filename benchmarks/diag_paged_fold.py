"""What ONE call of the paged decode kernel costs on the chip at a served
geometry, apart from the model around it, by the kernel file of one or more
trees: ``paged_decode_attention`` over a WHOLE pool with the layer a traced
scalar (the step of a device loop, as the looped model calls it), every
cache layer once a program.

    python benchmarks/diag_paged_fold.py [--kernel NAME=FILE ...]
        [--geometry ouro] [--block-pages 4,8] [--reps 7]

``--kernel`` names a ``paged_attention.py`` to time beside this tree's
(``parent=.scratch/parent/paddle_tpu/ops/pallas_kernels/paged_attention.py``):
each file is loaded as a module of THIS tree's package, so two folds meet
the same pool on the same chip, interleaved. One JSON line a point: the
geometry, microseconds a call (the median of ``--reps`` timings of one
program of ``layers`` calls), the live rows' bytes over the call's time as
a share of the HBM's rate, and the largest difference from the gather path
in float32 over the same bf16 values at layer 1. Run on the chip; it
refuses another backend.
"""

from __future__ import annotations

import argparse
import importlib.util
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

# name: slots, query heads, KV heads, head width, cache layers, pages,
# pages a slot, page size, live lengths of a decode step
GEOMETRIES = {
    # ouro-math-sat: 7.3 of 12 slots live, 2,987 rows a cache layer
    "ouro": (12, 16, 16, 128, 192, 288, 64, 16,
             (190, 269, 348, 427, 506, 585, 662)),
    # laguna-s-code-sat's full layers (two of them: the pool is 2 deep)
    "laguna_global": (16, 48, 8, 128, 2, 9216, 1024, 16,
                      tuple(range(2400, 7201, 320))),
    # gpt2-small-serve as a loaded server would run it
    "gpt2": (32, 12, 12, 64, 12, 2048, 64, 16,
             tuple(range(100, 901, 35))),
}


def load_kernel(name, path):
    """``path`` as a module of this tree's kernel package (its relative
    imports resolve here) under a name of its own."""
    modname = "paddle_tpu.ops.pallas_kernels._diag_" + name
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def program(mod, ps, n_layer, sm_scale, block_pages):
    """Every cache layer's call once, the layer the loop's own counter and
    the lengths moving round the slots (or the compiler finds the calls the
    same and lifts them out)."""
    @jax.jit
    def run(q, k, v, pt, ctx):
        def body(i, acc):
            out = mod.paged_decode_attention(
                q, k, v, pt, jnp.roll(ctx, i), page_size=ps, layer=i,
                sm_scale=sm_scale, block_pages=block_pages)
            return acc + out.astype(jnp.float32)

        return jax.lax.fori_loop(0, n_layer, body,
                                 jnp.zeros(q.shape, jnp.float32))

    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", action="append", default=[])
    ap.add_argument("--geometry", default="ouro")
    ap.add_argument("--block-pages", default="")
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("diag_paged_fold times the chip; this is %s"
              % jax.default_backend())
        return 2
    from paddle_tpu.ops.pallas_kernels import paged_attention as here

    kernels = [("change", here)] + [
        (n, load_kernel(n, p)) for n, p in
        (spec.split("=", 1) for spec in args.kernel)]
    blocks = [int(x) for x in args.block_pages.split(",") if x] or [None]
    for geometry in args.geometry.split(","):
        slots, hq, h, d, n_layer, pages, pps, ps, live = GEOMETRIES[geometry]
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        pool = (n_layer, pages * ps, h * d)
        k = jax.random.normal(keys[0], pool, jnp.bfloat16)
        v = jax.random.normal(keys[1], pool, jnp.bfloat16)
        q = jax.random.normal(keys[2], (slots, hq, d), jnp.bfloat16)
        rng = np.random.RandomState(0)
        pt = jnp.asarray(np.stack([rng.permutation(pages)[:pps]
                                   for _ in range(slots)]).astype(np.int32))
        ctx = np.zeros(slots, np.int32)
        ctx[:len(live)] = live
        ctx = jnp.asarray(ctx)
        sm_scale = d ** -0.5
        want = np.asarray(here.gather_reference(
            q.astype(jnp.float32), k[1].astype(jnp.float32),
            v[1].astype(jnp.float32), pt, ctx, ps, sm_scale=sm_scale))
        need_s = float(sum(live)) * 2 * h * d * 2 / HBM_BYTES_PER_S
        timed = []
        for name, mod in kernels:
            for bp in blocks:
                run = program(mod, ps, n_layer, sm_scale, bp)
                jax.block_until_ready(run(q, k, v, pt, ctx))
                got = np.asarray(mod.paged_decode_attention(
                    q.astype(jnp.float32), k, v, pt, ctx, page_size=ps,
                    layer=1, sm_scale=sm_scale, block_pages=bp))
                timed.append((name, bp, run, float(np.abs(
                    got - want)[:len(live)].max())))
        # the kernels' timings interleaved, a rep a round
        seconds = {(name, bp): [] for name, bp, _, _ in timed}
        for _ in range(args.reps):
            for name, bp, run, _ in timed:
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, k, v, pt, ctx))
                seconds[name, bp].append(
                    (time.perf_counter() - t0) / n_layer)
        for name, bp, _, gap in timed:
            call_s = statistics.median(seconds[name, bp])
            print(json.dumps({
                "geometry": geometry, "kernel": name, "block_pages": bp,
                "slots": slots, "q_heads": hq, "kv_heads": h, "d_head": d,
                "cache_layers": n_layer, "live_rows": int(sum(live)),
                "us_a_call": call_s * 1e6,
                "us_a_call_min_max": [min(seconds[name, bp]) * 1e6,
                                      max(seconds[name, bp]) * 1e6],
                "stream_share": need_s / call_s,
                "max_gap_from_float32_gather": gap,
                "max_abs_v": float(jnp.max(jnp.abs(v[1]))),
                "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

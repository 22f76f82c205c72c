"""Attention at Transformer-base's training shape, alone on the chip: the
composed path of ``ops/attention_ops.sdpa`` (what ``jit_step`` ran before
PR 45) against the single-tile kernels of
``ops/pallas_kernels/short_attention.py``, forward and backward, with
dropout 0.1 and segment ids that mask a padded tail, in the three mask
cases the model has (encoder: not causal; decoder self: causal; cross: not
causal, the keys' ids another tensor), and the long path's kernels
(``flash_attention``'s single-step forward at ``block_b`` rows a step and
its two backward kernels) for what they cost at this length.

    python benchmarks/diag_short_attention.py [--shape 96,8,256,64]
        [--blocks 1x2,1x4,1x8,2x2,4x2,8x2,2x8] [--reps 9]
        [--chain 4] [--also gpt2]

The operands are made as the model makes them: ``[B, S, H * D]`` rows (a
projection's output), split and transposed to ``[B, H, S, D]`` for every
form alike, and the result merged back, so each form pays for the layout
it needs and no other. One JSON line a point: the case, the form (a
single-tile form is named by the ROWS x HEADS of a grid step),
milliseconds a call (forward
and backward of one attention: the median of ``--reps`` timings of
``--chain`` chained calls in one executable, less the same chain's empty
loop), and the form's speed over composed. ``--also gpt2`` adds GPT-2
small's prefill (``[1, 12, S, 64]``, causal, segment ids, no dropout,
forward only) at S = 128, 256, 512; ``--also rows`` the encoder case at 1
to 16 rows (how few pairs are worth a kernel). Run on the chip; it refuses another
backend.
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

CASES = {"encoder": (False, False), "decoder_self": (True, False),
         "cross": (False, True)}


def inputs(shape, seed=0):
    """q, k, v as ``[B, S, H * D]`` rows, and two sets of segment ids."""
    b, h, s, d = shape
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(b, s, h * d), jnp.bfloat16)
               for _ in range(3))
    lens_q = rng.randint(s // 2, s + 1, size=b)
    lens_k = rng.randint(s // 2, s + 1, size=b)
    seg_q = jnp.asarray(np.arange(s)[None] < lens_q[:, None], jnp.int32)
    seg_k = jnp.asarray(np.arange(s)[None] < lens_k[:, None], jnp.int32)
    return q, k, v, seg_q, seg_k


def composed(rate):
    """``sdpa`` with every kernel gate shut: the composed lines."""
    from paddle_tpu.ops import attention_ops as ao

    def fn(q, k, v, sq, sk, key, causal):
        return ao._composed(q, k, v, None, sq, sk, causal,
                            q.shape[-1] ** -0.5, rate, key)
    return fn


def single_tile(rate, blocks):
    from paddle_tpu.ops.pallas_kernels import short_attention as sa

    def fn(q, k, v, sq, sk, key, causal):
        seed = None
        if rate > 0.0:
            seed = jax.lax.bitcast_convert_type(
                jax.random.bits(key, (1,), jnp.uint32), jnp.int32)
        return sa.single_tile_attention(q, k, v, sq, sk, seed, causal,
                                        q.shape[-1] ** -0.5, rate, blocks)
    return fn


def long_path(block_b):
    """The vendored kernel as the long path would run it here: the
    single-step forward body at ``block_b`` rows a grid step, the two
    backward kernels; segment ids, NO dropout (its dropout takes none)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    def fn(q, k, v, sq, sk, key, causal):
        s = q.shape[2]
        bs = fa.BlockSizes(
            block_q=s, block_k_major=s, block_k=s, block_b=block_b,
            block_q_major_dkv=s, block_k_major_dkv=s, block_k_dkv=s,
            block_q_dkv=s, block_k_major_dq=s, block_k_dq=s, block_q_dq=s)
        return fa.flash_attention(
            q, k, v, segment_ids=fa.SegmentIds(q=sq, kv=sk), causal=causal,
            sm_scale=q.shape[-1] ** -0.5, block_sizes=bs)
    return fn


def chain_ms(fn, args, causal, chain, reps, backward=True, heads=8):
    """Milliseconds a call of ``fn`` (and of its gradients), chained
    ``chain`` times inside one executable so that no dispatch is timed."""
    q, k, v, sq, sk = args

    def attend(q, k, v, key):
        """Rows in, rows out, through ``fn``'s ``[B, H, S, D]``: the
        model's split and merge of the heads."""
        b, s, w = q.shape

        def split(x):
            return jnp.swapaxes(
                x.reshape(b, x.shape[1], heads, w // heads), 1, 2)

        o = fn(split(q), split(k), split(v), sq, sk, key, causal)
        return jnp.swapaxes(o, 1, 2).reshape(b, s, w)

    def one(q, k, v, key):
        if not backward:
            return attend(q, k, v, key), k, v

        def loss(q, k, v):
            o = attend(q, k, v, key)
            return jnp.sum(o.astype(jnp.float32) * o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def run(n):
        @jax.jit
        def f(q, k, v, key):
            def body(i, c):
                q, k, v = c
                dq, dk, dv = one(q, k, v, jax.random.fold_in(key, i))
                # the next call reads this one's results: nothing is hoisted
                return (q + dq * 1e-6).astype(q.dtype), \
                    (k + dk * 1e-6).astype(k.dtype), \
                    (v + dv * 1e-6).astype(v.dtype)
            return jax.lax.fori_loop(0, n, body, (q, k, v))
        key = jax.random.PRNGKey(0)
        jax.block_until_ready(f(q, k, v, key))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v, key))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    return (run(1 + chain) - run(1)) / chain * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="96,8,256,64")
    ap.add_argument("--blocks",
                    default="1x2,1x4,1x8,2x2,4x2,8x2,2x8")
    ap.add_argument("--cases", default="encoder,decoder_self,cross")
    ap.add_argument("--rate", type=float, default=0.1)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--also", default="")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("diag_short_attention: needs the chip, found %r"
              % jax.default_backend(), file=sys.stderr)
        return 3
    shape = tuple(int(x) for x in args.shape.split(","))
    blocks = [tuple(int(x) for x in b.split("x"))
              for b in args.blocks.split(",") if b]
    data = inputs(shape)

    def point(case, form, fn, a, causal, base=None, chain=args.chain,
              backward=True, heads=shape[1]):
        """Time one form and print its line; a form the chip's compiler
        refuses prints why and costs the table nothing else."""
        try:
            ms = chain_ms(fn, a, causal, chain, args.reps, backward,
                          heads)
        except Exception as e:
            print(json.dumps({"case": case, "form": form,
                              "refused": str(e)[:300]}), flush=True)
            return None
        print(json.dumps({"shape": list(a[0].shape), "case": case,
                          "form": form, "ms": round(ms, 4),
                          "over_composed": round((base or ms) / ms, 3)}),
              flush=True)
        return ms

    for case in args.cases.split(","):
        causal, cross = CASES[case]
        q, k, v, sq, sk = data
        a = (q, k, v, sq, sk if cross else sq)
        base = point(case, "composed", composed(args.rate), a, causal)
        for bb, bh in blocks:
            point(case, "single_tile %dx%d" % (bb, bh),
                  single_tile(args.rate, (bb, bh)), a, causal, base)
        if case == "encoder":
            point(case, "composed, no dropout", composed(0.0), a, causal,
                  base)
            point(case, "single_tile 1x8, no dropout",
                  single_tile(0.0, (1, 8)), a, causal, base)
            point(case, "single_tile 1x8, forward alone",
                  single_tile(args.rate, (1, 8)), a, causal, base,
                  backward=False)
            for bb in (1, 8):
                point(case, "long path block_b=%d, no dropout" % bb,
                      long_path(bb), a, causal, base)
    if "rows" in args.also:
        # how few (row, head) pairs are worth a kernel: the cell's encoder
        # case at fewer rows (attention_ops.SINGLE_TILE_MIN_PAIRS)
        for rows in (1, 2, 4, 8, 16):
            q, k, v, sq, _ = inputs((rows,) + shape[1:])
            a = (q, k, v, sq, sq)
            base = point("rows=%d" % rows, "composed", composed(args.rate),
                         a, False, chain=32)
            point("rows=%d" % rows, "single_tile 1x8",
                  single_tile(args.rate, (1, 8)), a, False, base, chain=32)
    if "gpt2" in args.also:
        for s in (128, 256, 512):
            q, k, v, sq, _ = inputs((1, 12, s, 64))
            a = (q, k, v, sq, sq)
            base = point("gpt2_prefill", "composed", composed(0.0), a, True,
                         chain=64, backward=False, heads=12)
            for bb, bh in ((1, 2), (1, 4), (1, 6), (1, 12)):
                point("gpt2_prefill", "single_tile %dx%d" % (bb, bh),
                      single_tile(0.0, (bb, bh)), a, True, base, chain=64,
                      backward=False, heads=12)
    return 0


if __name__ == "__main__":
    sys.exit(main())

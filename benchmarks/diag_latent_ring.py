"""What the window layers' decode attention costs on the chip, apart from
everything around it: absorbed latent attention over a RING of ``window``
rows a slot (``serving.kv_cache.LatentPagedCache``'s window group at the
served geometry: 64 slots, 80 heads, 640-lane rows, page 16, seven layers)
in both forms the cache has for it, the ``mla_latent_decode`` kernel over
the ring's eight pages and the XLA gather of 128 rows a slot followed by
``ops.attention_ops.mla_decode_attention``; and, beside them, the same
kernel over a full layer's pages at a few thousand rows a slot.

    python benchmarks/diag_latent_ring.py [--slots 64] [--context 5500]

One JSON line a point: microseconds a layer (the median of ``--reps``
timings of the layers chained in one executable, each layer's query made
from the one before's output so that none is elided), the rows' bytes and
the share of the HBM rate that is. Run on the chip; it refuses another
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

HBM_BYTES_PER_S = 819e9     # TPU v5e (grid/peaks.json)
HEADS, RANK, ROPE, WIDTH, PAGE = 80, 512, 64, 640, 16


def _chained(attend, layers):
    """``layers`` calls in one executable, layer i's query nudged by layer
    i - 1's output (a sum over the latent lanes, added to the query's)."""
    def run(q, pool, pt, lens):
        for li in range(layers):
            o = attend(q, pool, pt, lens, li)
            q = q.at[..., :RANK].add((o * 1e-3).astype(q.dtype))
        return q

    return jax.jit(run)


def _kernel(q, pool, pt, lens, li):
    from paddle_tpu.ops.pallas_kernels import mla_attention as mla

    return mla.mla_paged_decode(q, pool, pt, lens, page_size=PAGE, rank=RANK,
                                layer=li, sm_scale=192 ** -0.5)


def _gather(q, pool, pt, lens, li):
    from paddle_tpu.ops import attention_ops

    rows = (pt * PAGE)[:, :, None] + jnp.arange(PAGE)[None, None, :]
    ctx = pool[li, rows.reshape(q.shape[0], -1)]
    return attention_ops.mla_decode_attention(q, ctx, lens, RANK,
                                              sm_scale=192 ** -0.5)


def time_us(fn, args, reps, layers):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return statistics.median(took) * 1e6 / layers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--context", type=int, default=5500)
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--reps", type=int, default=9)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("diag_latent_ring: needs the chip, found %r"
              % jax.default_backend(), file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    b = args.slots
    q = jnp.asarray(rng.standard_normal((b, HEADS, WIDTH)) * 0.1,
                    jnp.bfloat16).at[..., RANK + ROPE:].set(0)

    def case(name, attend, rows_a_slot, pages_a_slot, layers):
        pool = jnp.asarray(rng.standard_normal(
            (layers, b * pages_a_slot * PAGE, WIDTH)) * 0.3, jnp.bfloat16)
        pt = jnp.asarray(rng.permutation(b * pages_a_slot).reshape(
            b, pages_a_slot), jnp.int32)
        lens = jnp.full((b,), rows_a_slot, jnp.int32)
        us = time_us(_chained(attend, layers), (q, pool, pt, lens),
                     args.reps, layers)
        need = b * rows_a_slot * (RANK + ROPE) * 2
        print(json.dumps({
            "form": name, "slots": b, "rows_a_slot": rows_a_slot,
            "layers": layers, "us_a_layer": us, "bytes_a_layer": need,
            "hbm_share": need / HBM_BYTES_PER_S / (us * 1e-6)}), flush=True)

    ring_pages = args.window // PAGE
    case("ring_kernel", _kernel, args.window, ring_pages, args.layers)
    case("ring_gather", _gather, args.window, ring_pages, args.layers)
    full_pages = -(-args.context // PAGE)
    case("full_kernel", _kernel, args.context, full_pages, 2)
    return 0


if __name__ == "__main__":
    sys.exit(main())

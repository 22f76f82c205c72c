"""What the latent decode kernel costs on the chip, apart from everything
around it: ``mla_latent_decode`` at the three served geometries (Ling's 32
heads over 64 slots, Kimi's 64 over 32, Motif's 80 over 64; 640-lane bf16
rows, page 16) over a sweep of context lengths, and the window layers'
call beside it: a RING of ``window`` rows a slot (seven layers) by the
kernel and by the XLA gather of 128 rows a slot followed by
``ops.attention_ops.mla_decode_attention``.

    python benchmarks/diag_latent_ring.py [--heads 32,64,80,128]
        [--context 512,2688,4000,4800,5500] [--wave-rows 512] [--ragged]
        [--copy-pages 1,4,8]

One JSON line a point: microseconds a layer (the slope between the
medians of ``--reps`` timings of N and of 2N calls chained in one
executable, each call's query made from the one before's output so that
none is elided; a single chain's time over its calls reads a ring call
at twice what a traced cell shows, the launch and the host's wait being
in it), microseconds a WAVE
(a layer's time over the waves its slots hold at ``--wave-rows`` rows a
wave), the rows' bytes and the share of the HBM rate that is. Run on the
chip; it refuses another backend. ``--wave-rows`` hands the kernel its
``block_pages`` (a diagnosis; the program ships one constant).
``--copy-pages`` (a list; PR 64) hands it the RUN it may copy with one
descriptor, each point once a run: a slot's table here is made of aligned
runs of 8 pages in a scrambled order (what ``serving/page_pool.py`` gives
a latent group), so 1, 4 and 8 all read it rightly and ``copy_pages=1`` is
the kernel as it was. This is where ``mla_attention.RUN_PAGES`` was chosen
(PERF.md section 6, PR 64, has the table).

What a wave's parts cost, read with this file's ``measure`` over scratch
copies of the kernel with one part taken out (PR 44, TPU v5 lite, 80
heads over 64 slots of 4,800 rows, 512 rows a wave, us a wave; a wave's
655 KB stream in 0.80 us):

    reading                            parent    change
    the kernel whole                    2.31      1.51
    both masks dropped                  2.25      1.51
    interior copies, no predicate       1.70     (shipped)
    the copies alone (no fold)          1.48      0.85
    the fold alone (no copies)          0.84      0.84
    the next slot's wave not started     -        1.53

The parent's copies and fold did not overlap at all (1.48 + 0.84); the
change's overlap in part (0.85 + 0.84 for 1.51): what is left above the
fold is the issue of 32 descriptors a wave, serial with it. With every
copy of a wave unrolled and its destination a constant the same slots
read 1.21 us a wave, and the kernel compiled in 1.0 s where the parent's
takes 0.2 and the shipped one 0.3: a decode executable holds a copy a
layer and a serve cell compiles them at every start (Kimi's set-up 44 ->
64 s), so the shipped kernel starts a full wave's copies in a loop of
unrolled runs of four. A ring call (64 slots x 128 rows) reads 83.8 us on
the parent and 54.3 on the change, 84.1 with the next slot's wave not
started.
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
RANK, ROPE, WIDTH, PAGE = 512, 64, 640, 16
SERVED_SLOTS = {32: 64, 64: 32, 80: 64, 128: 32}    # heads: the cells' slots
RUN = 8     # pages of a run of the tables made here: every --copy-pages' own


def _chained(attend, layers):
    """``n`` calls in one executable (``n`` an argument: one compilation
    for every chain length) over a pool of ``layers`` layers taken in
    turn, call i's query nudged by call i - 1's output (a sum over the
    latent lanes, added to the query's) so that none is elided."""
    def run(q, pool, pt, lens, n):
        def call(i, q):
            o = attend(q, pool, pt, lens, i % layers)
            return q.at[..., :RANK].add((o * 1e-3).astype(q.dtype))

        return jax.lax.fori_loop(0, n, call, q)

    return jax.jit(run)


def kernel_form(decode=None, **kw):
    """The kernel as a form ``measure`` takes; ``decode`` is
    ``mla_paged_decode`` or a scratch copy of it, ``kw`` what it is handed
    beside the served geometry."""
    def attend(q, pool, pt, lens, li):
        from paddle_tpu.ops.pallas_kernels import mla_attention as mla

        return (decode or mla.mla_paged_decode)(
            q, pool, pt, lens, page_size=PAGE, rank=RANK, layer=li,
            sm_scale=192 ** -0.5, **kw)

    return attend


def _gather(q, pool, pt, lens, li):
    from paddle_tpu.ops import attention_ops

    rows = (pt * PAGE)[:, :, None] + jnp.arange(PAGE)[None, None, :]
    ctx = pool[li, rows.reshape(q.shape[0], -1)]
    return attention_ops.mla_decode_attention(q, ctx, lens, RANK,
                                              sm_scale=192 ** -0.5)


def time_us(fn, args, reps):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return statistics.median(took) * 1e6


def measure(name, attend, heads, slots, rows_a_slot, layers, reps=9,
            wave_rows=512, calls=8, ragged=False, **said):
    """One point: ``slots`` slots of ``rows_a_slot`` rows each (``ragged``:
    of half to one and a half times as many, drawn evenly, as a loaded
    cell's slots lie) over a scrambled page table. A layer's time is the
    SLOPE between a chain of ``calls`` calls and one of twice as many, so
    that what an executable costs whatever it holds (its launch, the
    host's wait) is left out and reported beside it."""
    rng = np.random.default_rng(0)
    rows = np.full(slots, rows_a_slot)
    if ragged:
        rows = rng.integers(rows_a_slot // 2, rows_a_slot * 3 // 2 + 1, slots)
    pages_a_slot = -(-int(rows.max()) // (PAGE * RUN)) * RUN
    q = jnp.asarray(rng.standard_normal((slots, heads, WIDTH)) * 0.1,
                    jnp.bfloat16).at[..., RANK + ROPE:].set(0)
    pool = jnp.asarray(rng.standard_normal(
        (layers, slots * pages_a_slot * PAGE, WIDTH)) * 0.3, jnp.bfloat16)
    first = rng.permutation(slots * pages_a_slot // RUN) * RUN
    pt = jnp.asarray((first[:, None] + np.arange(RUN)).reshape(
        slots, pages_a_slot), jnp.int32)
    lens = jnp.asarray(rows, jnp.int32)
    chain = _chained(attend, layers)
    once, twice = (time_us(chain, (q, pool, pt, lens, jnp.int32(n)), reps)
                   for n in (calls, 2 * calls))
    us = (twice - once) / calls
    need = int(rows.sum()) * (RANK + ROPE) * 2
    waves = int((-(-rows // min(wave_rows, pages_a_slot * PAGE))).sum())
    point = {"form": name, "heads": heads, "slots": slots,
             "rows_a_slot": rows_a_slot, "ragged": bool(ragged),
             "wave_rows": wave_rows,
             "layers": layers, "us_a_layer": us, "us_a_wave": us / waves,
             "us_an_executable": once - us * calls, "bytes_a_layer": need,
             "hbm_share": need / HBM_BYTES_PER_S / (us * 1e-6), **said}
    print(json.dumps(point), flush=True)
    return point


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=_ints, default=[32, 64, 80])
    ap.add_argument("--slots", type=int, default=0,
                    help="0: what the cell of each --heads runs")
    ap.add_argument("--context", type=_ints,
                    default=[512, 2688, 4000, 4800, 5500])
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--wave-rows", type=int, default=512)
    ap.add_argument("--ragged", action="store_true",
                    help="slots of half to one and a half times --context")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--copy-pages", type=_ints, default=[1],
                    help="pages a copy moves (the pool's run), each in turn")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("diag_latent_ring: needs the chip, found %r"
              % jax.default_backend(), file=sys.stderr)
        return 3
    slots_80 = args.slots or SERVED_SLOTS[80]
    for cp in args.copy_pages:
        kernel = kernel_form(block_pages=max(1, args.wave_rows // PAGE),
                             copy_pages=cp)
        for heads in args.heads:
            slots = args.slots or SERVED_SLOTS.get(heads, 64)
            for ctx in args.context:
                measure("full_kernel", kernel, heads, slots, ctx, 2,
                        args.reps, args.wave_rows, ragged=args.ragged,
                        copy_pages=cp)
        measure("ring_kernel", kernel, 80, slots_80, args.window,
                args.layers, args.reps, args.wave_rows, calls=64,
                copy_pages=cp)
    measure("ring_gather", _gather, 80, slots_80, args.window, args.layers,
            args.reps, args.wave_rows, calls=64)
    return 0


if __name__ == "__main__":
    sys.exit(main())

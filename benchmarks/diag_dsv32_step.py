"""What the parts of a ``dsv32-sparsedoc-sat`` decode step cost ALONE, at
the served geometry (32 slots, contexts drawn evenly over 4,608-10,240 rows
of a 16,384-row table, 128 heads over rows of 576 values in 640 lanes, 64
index heads over a key of 128 lanes a row), and where a traced run's device
time goes by scope. One JSON line a point to standard output and all of
them to ``chiprun_out/diag_dsv32_step.json``. A part's time is the SLOPE
between a chain of ``calls`` calls in one executable and one of twice as
many (``benchmarks/diag_latent_ring.py``'s rule), so what an executable
costs whatever it holds is left out.

``--parts select``: the choice of 2,048 of a slot's scores, by
``lax.top_k`` (a sort on this chip; its indices are a table of rows) and by
``attention_ops.dsa_select_rows`` (the 2,048th score by bisection: what
ships) with and without ``dsa_chosen_rows`` (the table of the chosen rows by
counts and two small products: what the off-chip gather and the probe
take).

``--parts index``: ``LatentPagedCache.index_scores`` as the decode step
calls it (the ``dsa_index_scores`` kernel over a slot's live pages) and the
XLA form it replaced (the gather of a slot's whole table of key pages and
the 64 heads' weighted ReLUs) against ``flops_rowdsa.index_score_need_s``.

``--parts read``: the sparse read in its two exact forms, the table of
rows or the mask given. (b), what ships: the latent kernel's wave over the
WHOLE context with the choice as a row mask (and the same wave with no
mask: the dense read). (a): a read of the chosen ROWS alone. As a Pallas
kernel it does not exist: the chip's compiler takes no copy of fewer than 8
rows out of HBM whatever the word size (a ``[327680, 384]`` int32 pool, two
bfloat16 lanes a word, one row a copy: "Slice shape along dimension 0 must
be aligned to tiling (8), but is 1", compiled for the described chip, PR
62), and 8-row tiles hold a chosen row in 94% of a context of which 30% is
kept; so it is measured as the XLA gather of the 2,048 chosen rows a slot
through a table and XLA's attention over them (the off-chip path of
``LatentPagedCache.rows_decode_attention``). ``rows_chosen`` is the same
2,048 a slot for both.

``--parts prefill``: the prefill's two kernels against their XLA forms at
4,096 rows and the served widths: the index scores' values and time, and a
layer's masked attention by the kernels' path against the blocked one;
then the prefill's three sparse-attention passes ALONE in the served
bucket of 8,192 rows at prompts of 4,608, 6,144 and 8,192 rows (the index
scores' kernel, the selection's loop over query blocks, the masked
attention's kernel; every call's operand the chain's carry, so that nothing
is hoisted out of the chain), each beside the share of its time at 8,192
rows that the rows a token can read predict: ``L^2 - 2,048^2``, ``L -
2,048`` and ``L^2``.

``--copy-pages 1,4,8`` (after ``--parts``; PR 64): ``index`` and ``read``
time their kernels once a RUN (the pages one copy moves: the tables here
are made of aligned runs of 8 pages in a scrambled order, as
``serving/page_pool.py`` hands a latent group its pages, so every run in
the list reads them rightly and 1 is the kernel as it was), with the us a
512-row WAVE beside the us a layer. Without it the kernels take the run
the cache gives them (``mla_attention.RUN_PAGES``). ``--index-wave-rows
512,1024`` times the index kernel once a WAVE length too (a diagnosis: the
script sets ``dsa_index._WAVE_ROWS``; the program ships one constant).

``--cell <grid.run's arguments>``: the cell's own traced run, and after its
last line the decode and prefill executables' device seconds of the traced
stretch under each of ``drivers/serve_rowdsa.SCOPES`` and under none, every
Pallas call and ``while`` by name, and the twenty instructions that took
most. About four minutes of chip for the parts, the cell's own for
``--cell``.

    python benchmarks/diag_dsv32_step.py --parts select,index,read
    python benchmarks/diag_dsv32_step.py --parts index,read --copy-pages 1,4,8
    python benchmarks/diag_dsv32_step.py --cell --workload \
        dsv32-sparsedoc-sat --seed 7 --seconds 40 --trace 1
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
SLOTS, HEADS, RANK, ROPE, WIDTH, PAGE = 32, 128, 512, 64, 640, 16
TABLE_ROWS, TOPK, LO, HI = 16384, 2048, 4608, 10240
INDEX_HEADS, INDEX_LANES = 64, 128
SCALE = 0.1352
RUN = 8     # pages of a run of the tables made here: every --copy-pages' own
COPY_PAGES = [None]     # --copy-pages; None: the cache's own run
INDEX_WAVE_ROWS = [None]    # --index-wave-rows; None: the kernel's own
POINTS = []


def emit(point):
    POINTS.append(point)
    print(json.dumps(point), flush=True)


def time_us(fn, args, reps=7):
    import jax

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return statistics.median(took) * 1e6


def slope_us(step, carry, consts, calls=8):
    """Microseconds one ``step(carry, i, *consts) -> carry`` takes inside
    a chain."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(carry, n, *consts):
        return jax.lax.fori_loop(
            0, n, lambda i, c: step(c, i, *consts), carry)

    once, twice = (time_us(chain, (carry, jnp.int32(n)) + tuple(consts))
                   for n in (calls, 2 * calls))
    return (twice - once) / calls


def _lengths(rng):
    import numpy as np

    return np.sort(rng.integers(LO, HI + 1, SLOTS))[::-1].copy()


def _table(rng, pages):
    """A page table a slot of ``pages`` pages made of aligned runs of
    ``RUN`` in a scrambled order, in the first ``HI // PAGE`` entries of a
    table of ``TABLE_ROWS`` rows."""
    import numpy as np

    table = np.zeros((SLOTS, TABLE_ROWS // PAGE), np.int32)
    first = rng.permutation(pages // RUN) * RUN
    table[:, :HI // PAGE] = (first[:, None] + np.arange(RUN)).reshape(
        SLOTS, -1)
    return table


def _waves(lens, rows=512):
    """Waves of ``rows`` rows that slots of ``lens`` rows hold."""
    return int((-(-lens // rows)).sum())


def part_select(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import attention_ops

    lens = _lengths(rng)
    low = float(attention_ops.neg_inf(jnp.float32))
    scores = rng.standard_normal((SLOTS, TABLE_ROWS)).astype("float32")
    scores[np.arange(TABLE_ROWS)[None, :] >= lens[:, None]] = low
    scores = jnp.asarray(scores)

    def nudged(s, x):       # the next call's scores depend on this one's
        return s + (x.astype(jnp.float32) * 1e-9)

    def by_top_k(s, i):
        vals, idx = jax.lax.top_k(s, TOPK)
        return nudged(s, jnp.sum(idx, axis=-1, keepdims=True) % 2)

    def by_top_k_mask(s, i):
        vals, _ = jax.lax.top_k(s, TOPK)
        return nudged(s, jnp.sum(s >= vals[:, -1:], axis=-1, keepdims=True))

    def by_bisection(s, i):
        chosen = attention_ops.dsa_select_rows(s, TOPK)
        return nudged(s, jnp.sum(chosen, axis=-1, keepdims=True))

    want = jax.lax.top_k(scores, TOPK)[1]
    got = attention_ops.dsa_select_rows(scores, TOPK)
    same = bool((jnp.sum(got, -1) == TOPK).all()) and bool(
        jnp.take_along_axis(got, want, axis=1).all())
    def by_bisection_and_table(s, i):
        rows, held = attention_ops.dsa_chosen_rows(
            attention_ops.dsa_select_rows(s, TOPK), TOPK)
        return nudged(s, jnp.sum(jnp.where(held, rows, 0), axis=-1,
                                 keepdims=True) % 2)

    rows, held = attention_ops.dsa_chosen_rows(got, TOPK)
    same = same and bool(held.all()) and bool(
        (jnp.sort(want, axis=-1) == rows).all())
    for name, step in (("top_k_indices", by_top_k),
                       ("top_k_threshold_mask", by_top_k_mask),
                       ("bisection_mask", by_bisection),
                       ("bisection_mask_and_table", by_bisection_and_table)):
        emit({"part": "select", "form": name, "slots": SLOTS,
              "table_rows": TABLE_ROWS, "topk": TOPK,
              "same_set_as_top_k": same,
              "us_a_layer": slope_us(step, scores, ())})


def part_index(rng):
    import jax.numpy as jnp
    import numpy as np

    from grid import flops_rowdsa
    from paddle_tpu.serving.kv_cache import (LATENT, CacheGroup,
                                             LatentPagedCache)

    pages = SLOTS * HI // PAGE
    ops = LatentPagedCache(
        1, RANK, ROPE, SLOTS, TABLE_ROWS, PAGE, pages, dtype="bfloat16",
        groups=[CacheGroup("latent_sparse", (0,), None, pages, LATENT)],
        index=(1, INDEX_LANES, TOPK))
    lens = _lengths(rng)
    table = _table(rng, pages)
    state = {"pt": jnp.asarray(table),
             "ik": jnp.asarray(rng.standard_normal(
                 (1, pages, PAGE, INDEX_LANES)) * 0.5, jnp.bfloat16)}
    q = jnp.asarray(rng.standard_normal(
        (SLOTS, INDEX_HEADS, INDEX_LANES)) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((SLOTS, INDEX_HEADS)) * 0.1,
                    jnp.float32)
    ctx = jnp.asarray(lens, jnp.int32)
    active = jnp.ones((SLOTS,), bool)

    from paddle_tpu.ops import attention_ops

    def by_the_cache(q, pt, ik, w):     # the kernel, where the flag arms it
        return ops.index_scores({"pt": pt, "ik": ik}, 0, q, w, ctx, active)[0]

    def by_the_kernel(cp, wave):
        from paddle_tpu.ops.pallas_kernels import dsa_index

        def scores(q, pt, ik, w):
            kept = dsa_index._WAVE_ROWS
            dsa_index._WAVE_ROWS = wave or kept     # read at trace time
            try:
                return dsa_index.dsa_index_scores_paged(
                    q, w, ik, pt, ctx, layer=0, copy_pages=cp)
            finally:
                dsa_index._WAVE_ROWS = kept

        return scores

    def by_xla(q, pt, ik, w):
        # the table made to depend on the query, so that the gather of
        # the keys is not hoisted out of the chain as loop-invariant
        keys = ik[0, pt + (jnp.max(q).astype(jnp.int32) >> 30)]
        return attention_ops.dsa_index_scores(
            q, w, keys.reshape(SLOTS, -1, INDEX_LANES), ctx)

    model = {"index_head_dim": INDEX_LANES, "index_n_heads": INDEX_HEADS,
             "num_hidden_layers": 1}
    need = flops_rowdsa.index_score_need_s(float(lens.sum()), model, PEAKS)
    args = (state["pt"], state["ik"], w)
    forms = [("cache_%s" % (ops.index_kernel_mode()[0] or "xla"),
              by_the_cache, ops.group_run_pages(0), None)]
    if COPY_PAGES != [None] or INDEX_WAVE_ROWS != [None]:
        forms = [("kernel", by_the_kernel(cp or ops.group_run_pages(0), wave),
                  cp or ops.group_run_pages(0), wave)
                 for cp in COPY_PAGES for wave in INDEX_WAVE_ROWS]
    for name, form, cp, wave in forms + [("xla_gather_and_scores", by_xla,
                                          None, None)]:
        def step(q, i, pt, ik, w, form=form):
            bump = jnp.max(form(q, pt, ik, w), axis=-1)[:, None, None] * 1e-9
            return q + bump.astype(q.dtype)

        us = slope_us(step, q, args)
        emit({"part": "index", "form": name, "copy_pages": cp,
              "slots": SLOTS,
              "rows_scored": int(lens.sum()), "table_rows": TABLE_ROWS,
              "wave_rows": wave or 512,
              "us_a_layer": us, "us_a_wave": us / _waves(lens, wave or 512),
              "need_us": need * 1e6, "roofline_share": need * 1e6 / us,
              "max_gap_to_xla": float(jnp.max(jnp.abs(
                  form(q, *args) - by_xla(q, *args))))})


def part_read(rng):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from grid import flops_rowdsa
    from paddle_tpu.ops.pallas_kernels import mla_attention as mla

    lens = _lengths(rng)
    pages = SLOTS * HI // PAGE
    table = _table(rng, pages)
    pool = jnp.asarray(rng.standard_normal((1, pages * PAGE, WIDTH)) * 0.3,
                       jnp.bfloat16).at[..., RANK + ROPE:].set(0)
    q = jnp.asarray(rng.standard_normal((SLOTS, HEADS, WIDTH)) * 0.1,
                    jnp.bfloat16).at[..., RANK + ROPE:].set(0)
    chosen = np.zeros((SLOTS, TABLE_ROWS), bool)
    rows = np.zeros((SLOTS, TOPK), np.int32)
    for b, n in enumerate(lens):
        picked = np.sort(rng.choice(n, TOPK, replace=False))
        chosen[b, picked] = True
        rows[b] = table[b, picked // PAGE] * PAGE + picked % PAGE
    pt, ctx = jnp.asarray(table), jnp.asarray(lens, jnp.int32)
    mask = jnp.asarray(chosen)
    model = {"kv_lora_rank": RANK, "qk_rope_head_dim": ROPE,
             "num_attention_heads": HEADS, "num_hidden_layers": 1}
    need = flops_rowdsa.sparse_read_need_s(SLOTS * TOPK, model, PEAKS) * 1e6

    def wave_form(masked, cp):
        def step(q, i, pool, pt, mask):
            o = mla.mla_paged_decode(
                q, pool, pt, ctx, page_size=PAGE, rank=RANK, layer=0,
                sm_scale=SCALE, row_valid=mask if masked else None,
                name=mla.SPARSE_KERNEL_NAME if masked else mla.KERNEL_NAME,
                copy_pages=cp)
            return q.at[..., :RANK].add((o * 1e-3).astype(q.dtype))

        return step

    for cp in COPY_PAGES:
        cp = cp or mla.run_pages(PAGE, TABLE_ROWS // PAGE)
        for name, masked in (("b_whole_context_row_mask", True),
                             ("dense_whole_context", False)):
            us = slope_us(wave_form(masked, cp), q, (pool, pt, mask))
            emit({"part": "read", "form": name, "copy_pages": cp,
                  "slots": SLOTS, "heads": HEADS,
                  "rows_chosen": SLOTS * TOPK,
                  "rows_copied": int(lens.sum()), "us_a_layer": us,
                  "us_a_wave": us / _waves(lens), "need_us": need,
                  "roofline_share": need / us})
    # form (a): the chosen rows alone, gathered by XLA and attended in
    # XLA, the table of rows given
    from paddle_tpu.ops import attention_ops

    tab = jnp.asarray(rows)

    held = jnp.ones((SLOTS, TOPK), bool)

    def gathered(q, i, pool, tab):
        # the table made to depend on the query: a gather of invariant
        # operands is hoisted out of the chain and not timed (this PR's
        # first reading, 176 us, was the attention alone)
        tab = tab + (jnp.max(q).astype(jnp.int32) >> 30)
        o = attention_ops.mla_rows_attention(q, pool[0, tab], held, RANK,
                                             sm_scale=SCALE)
        return q.at[..., :RANK].add((o * 1e-3).astype(q.dtype))

    us = slope_us(gathered, q, (pool, tab))
    emit({"part": "read", "form": "a_xla_gather_of_the_chosen_rows",
          "slots": SLOTS, "heads": HEADS, "rows_chosen": SLOTS * TOPK,
          "rows_copied": SLOTS * TOPK, "us_a_layer": us, "need_us": need,
          "roofline_share": need / us})


def part_prefill(rng):
    """The prefill's two kernels against their XLA forms at the served
    widths, 4,096 rows: the index scores (values over the causal triangle,
    and time), and a layer's masked attention (the kernels' path against
    the blocked one: values)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import dsa_index

    s = 4096
    bf = jnp.bfloat16
    q_idx = jnp.asarray(rng.standard_normal((s, INDEX_HEADS, INDEX_LANES)),
                        bf)
    w_idx = jnp.asarray(rng.standard_normal((s, INDEX_HEADS)) * 0.1,
                        jnp.float32)
    k_idx = jnp.asarray(rng.standard_normal((s, INDEX_LANES)), bf)
    tril = jnp.tril(jnp.ones((s, s), bool))

    @jax.jit
    def by_xla(q, w, k):
        f32 = jnp.float32
        return jax.lax.map(lambda a: jnp.einsum(
            "qh,qhn->qn", a[1], jax.nn.relu(jnp.einsum(
                "qhl,nl->qhn", a[0], k, preferred_element_type=f32))),
            (q.reshape(s // 256, 256, INDEX_HEADS, INDEX_LANES),
             w.reshape(s // 256, 256, INDEX_HEADS))).reshape(s, s)

    got = dsa_index.dsa_index_scores_prefill(q_idx, w_idx, k_idx)
    want = by_xla(q_idx, w_idx, k_idx)
    gap = jnp.where(tril, jnp.abs(got - want), 0.0)
    emit({"part": "prefill", "what": "index_scores_kernel_vs_xla", "rows": s,
          "max_gap": float(gap.max()), "mean_gap": float(gap.sum()
                                                         / tril.sum()),
          "scores_std": float(jnp.std(jnp.where(tril, want, 0.0))),
          "kernel_us": time_us(dsa_index.dsa_index_scores_prefill,
                               (q_idx, w_idx, k_idx)),
          "xla_us": time_us(by_xla, (q_idx, w_idx, k_idx))})
    q = jnp.asarray(rng.standard_normal((s, HEADS, 192)) * 1.5, bf)
    k = jnp.asarray(rng.standard_normal((s, HEADS, 192)), bf)
    v = jnp.asarray(rng.standard_normal((s, HEADS, 128)), bf)
    run = jax.jit(lambda *a: attention_ops.dsa_rows_causal_attention(
        *a, TOPK, SCALE))
    kernels = run(q, k, v, q_idx, w_idx, k_idx)
    armed = attention_ops._on_tpu
    attention_ops._on_tpu = lambda: False
    try:
        blocked = jax.jit(lambda *a: attention_ops.dsa_rows_causal_attention(
            *a, TOPK, SCALE))(q, k, v, q_idx, w_idx, k_idx)
    finally:
        attention_ops._on_tpu = armed
    diff = jnp.abs(kernels.astype(jnp.float32) - blocked.astype(jnp.float32))
    rows_off = jnp.max(diff, axis=(1, 2)) > 0.05
    emit({"part": "prefill", "what": "layer_attention_kernels_vs_blocked",
          "rows": s, "max_gap": float(diff.max()),
          "mean_gap": float(diff.mean()),
          "out_std": float(jnp.std(blocked.astype(jnp.float32))),
          "rows_off_by_0.05": int(rows_off.sum()),
          "first_rows_off": np.nonzero(np.asarray(rows_off))[0][:8].tolist()})
    del q, k, v, kernels, blocked, diff
    part_prefill_lengths(rng)


def part_prefill_lengths(rng, s=8192, lengths=(4608, 6144, 8192)):
    """The three sparse-attention passes of one layer's prefill ALONE in a
    bucket of ``s`` rows told each of ``lengths``: microseconds a call by
    the chain's slope, and its share of the same pass's time at ``s`` rows
    beside what the rows a token can read predict."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import dsa_index, dsa_prefill

    bf, f32 = jnp.bfloat16, jnp.float32
    q_idx = jnp.asarray(rng.standard_normal((s, INDEX_HEADS, INDEX_LANES)),
                        bf)
    w_idx = jnp.asarray(rng.standard_normal((s, INDEX_HEADS)) * 0.1, f32)
    k_idx = jnp.asarray(rng.standard_normal((s, INDEX_LANES)), bf)
    # the attention kernel's own operands: a head's 192 lanes padded to 256
    q = jnp.asarray(rng.standard_normal((s, HEADS, 256)) * 1.5, bf)
    k = jnp.asarray(rng.standard_normal((s, HEADS, 256)), bf)
    v = jnp.asarray(rng.standard_normal((s, HEADS, 128)), bf)
    tril = jnp.tril(jnp.ones((s, s), jnp.int8))
    scores = dsa_index.dsa_index_scores_prefill(q_idx, w_idx, k_idx)
    scores = jnp.where(tril != 0, scores, 0.0)      # no tile of garbage
    bq = 256                    # dsa_rows_causal_attention's query block

    def score(qi, i, w, ki, n):
        sc = dsa_index.dsa_index_scores_prefill(
            qi, w, ki, -(-n // bq) * bq, first=TOPK // bq * bq)
        # rows every length reads, columns under their causal edge
        return qi.at[:8, 0, :].add(
            (sc[TOPK:TOPK + 8, :INDEX_LANES] * 1e-6).astype(bf))

    def choose(sc, i, qq, kk, vv, qi, ki, n):
        # the selection's loop alone: the two kernels stand aside, the
        # scores' handing back the carry (passed as the weights, which
        # nothing else reads) and the attention's the mask it was handed
        real = (dsa_index.dsa_index_scores_prefill,
                dsa_prefill.dsa_prefill_attention)
        dsa_index.dsa_index_scores_prefill = \
            lambda q_idx, w_idx, k_idx, end=None, **kw: w_idx
        dsa_prefill.dsa_prefill_attention = \
            lambda q, k, v, mask, length=None, **kw: mask
        try:
            mask = attention_ops.dsa_rows_causal_attention(
                qq[..., :192], kk[..., :192], vv, qi, sc, ki, TOPK, SCALE,
                length=n)
        finally:
            (dsa_index.dsa_index_scores_prefill,
             dsa_prefill.dsa_prefill_attention) = real
        return sc.at[0, :1].add(jnp.sum(mask[::bq].astype(f32)) * 1e-9)

    def attend(qq, i, kk, vv, mask, n):
        o = dsa_prefill.dsa_prefill_attention(qq, kk, vv, mask, n,
                                              sm_scale=SCALE)
        return qq.at[:8, :, :128].add(o[:8] * 1e-3)

    predicted = {
        "index_scores": lambda n: (n * n - TOPK * TOPK)
        / (s * s - TOPK * TOPK),
        "selection": lambda n: (n - TOPK) / (s - TOPK),
        "masked_attention": lambda n: n * n / (s * s)}
    passes = (("index_scores", score, q_idx, (w_idx, k_idx)),
              ("selection", choose, scores, (q, k, v, q_idx, k_idx)),
              ("masked_attention", attend, q, (k, v, tril)))
    for name, step, carry, consts in passes:
        took = {n: slope_us(step, carry, consts + (jnp.int32(n),))
                for n in lengths}
        for n in lengths:
            emit({"part": "prefill", "what": "pass_alone_at_a_length",
                  "pass": name, "bucket": s, "length": n,
                  "us_a_layer": took[n],
                  "share_of_the_bucket's": took[n] / took[s],
                  "share_predicted": predicted[name](n)})


def parts(names) -> int:
    import jax
    import numpy as np

    if jax.default_backend() != "tpu":
        print("diag_dsv32_step: the parts need the chip, found %r"
              % jax.default_backend(), file=sys.stderr)
        return 3
    for name in names:
        {"select": part_select, "index": part_index, "read": part_read,
         "prefill": part_prefill}[name](np.random.default_rng(62))
    return 0


def cell(argv) -> int:
    """The cell's traced run, then its executables' device time by scope."""
    from grid import manifest, reduce, run
    from grid.drivers import serve_rowdsa
    from grid.readers.gdla import scoped_instructions

    seen = {}
    scoped_ops = serve_rowdsa.scoped_ops

    def every_module(engine):
        seen["by_module"] = {}
        for module, exes in (("jit_chunk", engine._decode_exe),
                             ("jit_prefill", engine._prefill_exe)):
            names = {s: set() for s in serve_rowdsa.SCOPES + ("moe/",)}
            for exe in exes.values():
                text = exe.as_text()
                for s in names:
                    names[s].update(scoped_instructions(text, s))
            seen["by_module"][module] = {s: sorted(n)
                                         for s, n in names.items()}
        return scoped_ops(engine)

    serve_rowdsa.scoped_ops = every_module
    reader = manifest.reader

    def capturing(spec):
        fn = reader(spec)

        def read(record, trace):
            seen.update(record=record, trace=trace)
            return fn(record, trace)

        return read

    manifest.reader = capturing
    rc = run.main(argv)
    record, trace = seen.get("record"), seen.get("trace")
    if rc or trace is None:
        return rc
    win = tuple(record["trace_window"])
    out = {"part": "cell", "busy_s": reduce.busy_seconds(trace, win)}
    for module, ops in seen["by_module"].items():
        claimed = {}
        for scope, names in ops.items():
            for name in names:
                claimed.setdefault(name, []).append(scope)
        by_name = {}
        for chip_ops in trace.ops.values():
            for o in chip_ops:
                if o.module == module and win[0] <= o.start <= win[1]:
                    t = by_name.setdefault(o.name, [0.0, o.opcode, o.shape])
                    t[0] += o.end - o.start
        by_scope = {scope: sum(by_name.get(n, [0.0])[0] for n in names)
                    for scope, names in ops.items()}
        by_scope["(none)"] = sum(t for n, (t, _, _) in by_name.items()
                                 if n not in claimed)
        calls = {}
        for name, (t, op, shape) in by_name.items():
            if op in ("custom-call", "while"):
                label = "%s_%s" % (re.sub(r"\.\d+$", "", name), shape)
                calls[label] = calls.get(label, 0.0) + t
        top = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:20]
        out[module] = {
            "whole_s": sum(t for t, _, _ in by_name.values()),
            "by_scope_s": by_scope, "kernels_and_loops_s": calls,
            "top": [[n, round(t, 5), op, shape, claimed.get(n, [])]
                    for n, (t, op, shape) in top]}
    emit(out)
    return 0


def main(argv) -> int:
    if argv and argv[0] == "--cell":
        rc = cell(argv[1:])
    else:
        names = ["select", "index", "read"]
        if argv[:1] == ["--parts"]:
            names = [n for n in argv[1].split(",") if n]
        for flag, into in (("--copy-pages", COPY_PAGES),
                           ("--index-wave-rows", INDEX_WAVE_ROWS)):
            if flag in argv:
                into[:] = [int(n) for n in
                           argv[argv.index(flag) + 1].split(",") if n]
        rc = parts(names)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "diag_dsv32_step.json"),
              "a") as f:
        for point in POINTS:
            f.write(json.dumps(point) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

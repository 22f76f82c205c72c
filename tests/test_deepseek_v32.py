"""DeepSeek-V3.2's row-choosing latent decoder against its plain float32
reference (``grid/reference/deepseek_v32.py``), at a toy of four layers
(one dense), 32 experts in 4 groups of which 2 are kept, ``index_topk`` 16
and contexts of 5-128, so that the selection bites at every row that
matters:

(a) the prefill, under the bucket's padding, equals the reference's full
    forward at five lengths on both sides of ``index_topk``, by the
    blocked form and by the prefill kernel's interpreter;
(b) prefill, then decoding through the latent pages and the index keys
    beside them, equals it too, logits and chosen rows, from three starts,
    by the XLA forms and by the two kernels interpreted, two slots at
    different lengths beside an idle one;
(c) the choice: exactly ``lax.top_k``'s set without a sort, ties to the
    lower row, every row where there are fewer; the index of a key a row
    beside the pages (rows, pages and ``pages_needed`` by hand);
(d) what a decode step moves: no pool-sized copy;
(e) the share: sixteen shares and one shared expert add up to the uncut
    layer, and the group-limited router against a loop by hand;
(f) a lower precision where the configuration states one fails.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import deepseek_v32 as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import deepseek_v32 as ds
from paddle_tpu.models.blocks import routed_feed_forward
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops, moe_ops
from paddle_tpu.ops.pallas_kernels import dsa_index, dsa_prefill
from paddle_tpu.serving.kv_cache import LATENT, CacheGroup, LatentPagedCache

TOL = 5e-5
SCALING = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
           "mscale_all_dim": 1, "original_max_position_embeddings": 64,
           "type": "yarn"}
PUBLISHED = {  # the toy under the published config's own keys
    "num_hidden_layers": 4, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 16, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "n_group": 4, "topk_group": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": SCALING}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=4, d_model=64, n_head=4, q_rank=24,
              kv_rank=16, d_nope=16, d_rope=8, d_v=16, index_heads=4,
              index_dim=16, index_topk=16, d_dense=128, n_dense=1,
              n_expert=32, top_k=4, d_expert=32, n_group=4, topk_group=2,
              routed_scale=2.5, rope_theta=1e4, rope_scaling=SCALING,
              max_seq=256, dtype="float32", score_std=0.02)
    kw.update(over)
    return ds.DeepSeekV32Config(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention, the index and routing are decisive at d = 64."""
    return jax.tree_util.tree_map(lambda a: a * 6.0 if a.ndim > 1 else a,
                                  params)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return ds.DeepSeekV32LM(cfg, params=_scaled(ds.init_params(cfg, 3)))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def _padded(seq, to=128):
    toks = np.zeros((-(-len(seq) // to) * to,), np.int32)
    toks[:len(seq)] = seq
    return toks


def reference_rows(model, seq, rows, **over):
    return np.asarray(ref.forward(model.params, dict(PUBLISHED, **over),
                                  _padded(seq), rows=rows))


def _prefill(model, seq, bucket=128, run=None):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return (run or model.prefill)(model.params, jnp.asarray(toks),
                                  jnp.asarray([len(seq)], jnp.int32))


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=16, max_seq=256, prompt_buckets=(128,),
               num_pages=40, collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


def _served_against_reference(eng, req, **over):
    full = list(req.prompt) + list(req.tokens_out)
    rows = np.arange(len(req.prompt) - 1, len(full) - 1)
    want = reference_rows(eng.model, full, rows, **over)
    return np.abs(np.stack(eng.captured_logits(req)) - want).max()


# -- (a) prefill ---------------------------------------------------------------


def _arm_the_prefill_kernel(monkeypatch, **tiles):
    """``dsa_rows_causal_attention`` as on a chip whose gate takes the
    shapes, the kernel's interpreter standing in at ``tiles``."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(dsa_prefill, "dsa_prefill_gate", functools.partial(
        dsa_prefill.dsa_prefill_gate, interpret=True))
    monkeypatch.setattr(
        dsa_prefill, "dsa_prefill_attention", functools.partial(
            dsa_prefill.dsa_prefill_attention, interpret=True, **tiles))
    # ... and the index scores' kernel, at its own tiles
    monkeypatch.setattr(dsa_index, "dsa_index_prefill_gate", functools.partial(
        dsa_index.dsa_index_prefill_gate, interpret=True))
    monkeypatch.setattr(
        dsa_index, "dsa_index_scores_prefill", functools.partial(
            dsa_index.dsa_index_scores_prefill, interpret=True, block_q=32,
            block_k=64))


@pytest.mark.parametrize("form", ["blocked", "kernel"])
def test_prefill_equals_the_reference_on_both_sides_of_index_topk(
        toy, rng, form, monkeypatch):
    """Five prompts of 5, 16, 17, 60 and 100 rows in a bucket of 128 (the
    first two read their whole prefix: the mask is the causal triangle;
    row 99 reads 16 of 100): every valid row's logits are the reference's,
    by the blocked form and by the ``dsa_prefill_attention`` kernel over
    masks chosen from the ``dsa_index_scores_prefill`` kernel's scores
    (both interpreted; the heads' 24 lanes padded to whole lane tiles)."""
    if form == "kernel":
        _arm_the_prefill_kernel(monkeypatch, block_q=64, block_k=32, heads=2)
    counter = mx.counter("dsa/prefill_calls." + form)
    scored = mx.counter("dsa/prefill_index_calls." + form)
    before = counter.value, scored.value
    run = jax.jit(toy.prefill)      # one trace for the five
    for n in (5, 16, 17, 60, 100):
        seq = rng.randint(0, 96, n)
        logits, kept = _prefill(toy, seq, run=run)
        np.testing.assert_allclose(
            np.asarray(logits[0, :n]),
            reference_rows(toy, seq, np.arange(n)), atol=TOL, rtol=0)
    # a call a layer a trace, of the attention and of the index scores
    assert counter.value >= before[0] + 4 and scored.value >= before[1] + 4
    # what the cache is handed, a layer: the rows and a key a row
    assert [tuple(t.shape[1:] for t in k) for k in kept] \
        == [((128, 24), (128, 16))] * 4


def _rows_case(rng, s, h=4, d=24, dv=16, hi=4, li=16):
    def arr(*shape):
        return jnp.asarray(rng.randn(*shape).astype("float32"))

    return (arr(s, h, d), arr(s, h, d), arr(s, h, dv), arr(s, hi, li),
            arr(s, hi), arr(s, li))


def _rows_read_by_hand(case, topk):
    """[S, S] bool: row t reads the ``topk`` rows s <= t of highest index
    score, a tie to the lower row, by a sort a row."""
    q_idx, w_idx, k_idx = (np.asarray(x, np.float64) for x in case[3:])
    s = q_idx.shape[0]
    index = np.einsum("th,ths->ts", w_idx, np.maximum(
        np.einsum("thl,sl->ths", q_idx, k_idx), 0.0)).astype(np.float32)
    mask = np.zeros((s, s), bool)
    for t in range(s):
        order = np.argsort(-index[t, :t + 1], kind="stable")
        mask[t, order[:topk]] = True
    return mask


@pytest.mark.parametrize("length,why", [
    (1, "one row"), (159, "index_topk - 1"), (161, "index_topk + 1"),
    (300, "inside the third query block"), (511, "S - 1"), (512, "S")])
def test_a_prefill_stops_at_its_prompts_end_and_chooses_nothing_under_topk(
        rng, monkeypatch, prefill_told_its_length, length, why):
    """``dsa_rows_causal_attention`` told the prompt's length, at ``topk``
    160 in a bucket of 512 and mask blocks of 128 (the first reads its
    whole prefix, the second not), the scores' kernel interpreted at query
    blocks of 64: ``conftest.prefill_told_its_length`` has what holds."""
    case = _rows_case(rng, 512)

    def arm():
        _arm_the_prefill_kernel(monkeypatch)
        monkeypatch.setattr(
            dsa_index, "dsa_index_scores_prefill", functools.partial(
                dsa_index.dsa_index_scores_prefill, interpret=True,
                block_q=64, block_k=128))

    prefill_told_its_length(
        functools.partial(attention_ops.dsa_rows_causal_attention, *case,
                          160, 0.25, block_q=128),
        arm, case, _rows_read_by_hand(case, 160), length, 128)


@pytest.mark.parametrize("first,end", [(0, 512), (128, 300), (256, 256),
                                       (130, 1), (512, 512)])
def test_the_scores_kernel_computes_the_query_blocks_that_are_read(
        rng, first, end):
    """``dsa_index_scores_prefill`` told the rows whose scores are read:
    every query block of 64 that holds one equals the kernel told nothing,
    up to its causal edge (what the others hold is nobody's to read)."""
    q_idx, w_idx, k_idx = _rows_case(rng, 512)[3:]
    run = functools.partial(dsa_index.dsa_index_scores_prefill, q_idx, w_idx,
                            k_idx, block_q=64, block_k=128, interpret=True)
    whole = np.tril(np.asarray(run()))
    got = np.tril(np.asarray(run(jnp.asarray(end, jnp.int32), first=first)))
    lo, hi = first // 64 * 64, -(-end // 64) * 64
    np.testing.assert_array_equal(got[lo:hi], whole[lo:hi])
    want = np.tril(np.maximum(np.einsum(
        "thl,sl->ths", q_idx, k_idx), 0.0).transpose(1, 0, 2)
        * np.asarray(w_idx).T[:, :, None]).sum(0)
    np.testing.assert_allclose(whole, np.tril(want), atol=2e-5, rtol=0)


# -- (b) decode through the cache ------------------------------------------------


def _reference_choice(model, full, positions):
    """The rows the reference's FIRST layer chooses at ``positions``,
    ascending, -1 where fewer than ``index_topk``."""
    _, (_, chosen) = ref.hidden(model.params, PUBLISHED, _padded(full),
                                probe_rows=np.asarray(positions))
    out = np.full((len(positions), 16), -1, np.int32)
    for i, row in enumerate(np.asarray(chosen)):
        picked = np.nonzero(row)[0]
        out[i, :len(picked)] = picked
    return out


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_pages_and_index_keys_equals_the_reference(
        toy, kernel, rng):
    """Three requests of 100, 7 and 40 rows (one starts under
    ``index_topk`` and passes it) decode 24 tokens each through the latent
    pages and the index keys, in three slots of which one goes idle first:
    every served row's logits are the reference's full forward's, and the
    rows slot 0's first layer chose at every step are the reference's
    own, by the XLA forms (a gather of the chosen rows) and by the index
    scores' and the sparse read's kernels interpreted."""
    set_flag("paged_attention_kernel", kernel)
    try:
        eng = _engine(toy)
        mode = "interpret" if kernel == "interpret" else None
        assert eng.cache_ops.index_kernel_mode()[0] == mode
        reqs = [eng.submit(list(rng.randint(0, 96, m)), n)
                for m, n in ((100, 24), (7, 12), (40, 24))]
        probes = {}
        while not eng.scheduler.idle():
            eng.step()
            last = eng.last_decode_stats
            if last is not None and last[0][0] is reqs[0]:
                for row in np.asarray(last[1]["dsa_probe"]).reshape(-1, 17):
                    if row[0] >= 0:
                        probes[int(row[0])] = row[1:]
        eng.drain()
        for r in reqs:
            assert r.state == "finished"
            assert _served_against_reference(eng, r) < TOL
        full = list(reqs[0].prompt) + list(reqs[0].tokens_out)
        positions = sorted(probes)
        assert len(positions) >= 20 and positions[0] == 100
        np.testing.assert_array_equal(
            np.stack([probes[p] for p in positions]),
            _reference_choice(toy, full, positions))
        _, stats = eng.last_decode_stats
        assert set(stats) >= {"dsa_probe", "index_rows_scored",
                              "moe_groups_kept_with_held",
                              "attn_rows_read.latent_sparse",
                              "attn_rows_context.latent_sparse"}
    finally:
        set_flag("paged_attention_kernel", "auto")


# -- (c) the choice, and the index beside the rows --------------------------------


def test_the_choice_is_top_ks_set_without_a_sort_and_ties_go_low(rng):
    """``dsa_select_rows`` against ``lax.top_k`` (through the reference's
    ``choose``) over random scores with repeated values, negative scores
    and both zeros' neighbours, rows that may not be chosen, fewer
    candidates than ``topk``, and none: the same set, and no ``sort`` or
    ``top_k`` in its jaxpr."""
    neg = float(attention_ops.neg_inf(jnp.float32))
    scores = np.round(rng.randn(9, 40), 1).astype("float32")   # many ties
    scores[0, :] = 1.5                    # all equal: the 16 lowest rows
    scores[1, 20:] = neg                  # 20 candidates
    scores[2, 5:] = neg                   # 5 candidates: all of them
    scores[3, :] = neg                    # none
    scores[4, :] = -np.abs(scores[4])     # negative scores order too
    scores[5, ::2] = 1e-30
    scores[5, 1::2] = -1e-30
    chosen = np.asarray(attention_ops.dsa_select_rows(jnp.asarray(scores),
                                                      16))
    may = scores > neg / 2
    want, _ = ref.choose(jnp.where(may, scores, -jnp.inf),
                         jnp.full((9,), 39), 16)
    np.testing.assert_array_equal(chosen, np.asarray(want) & may)
    np.testing.assert_array_equal(np.nonzero(chosen[0])[0], np.arange(16))
    assert chosen[2].sum() == 5 and chosen[3].sum() == 0
    names = {e.primitive.name for e in _eqns(jax.make_jaxpr(
        lambda s: attention_ops.dsa_select_rows(s, 16))(scores).jaxpr)}
    assert not names & {"sort", "top_k", "cumsum", "argsort"}


def _index_cache(slots=2, max_ctx=64, pages=8):
    groups = [CacheGroup("latent_sparse", (0, 1), None, pages, LATENT)]
    return LatentPagedCache(2, 16, 8, slots, max_ctx, 16, pages,
                            groups=groups, index=(1, 8, 4))


def test_a_key_a_row_lies_beside_its_row_through_the_same_page_table(rng):
    """The pools by hand: 8 pages of 16 rows, two layers; a latent row of
    16 + 8 values in 128 lanes, an index key of 8 lanes a row, a page's 16
    keys a tile of their own in the index pool; no open block and no
    slot entry after the page table; a request of 33 positions needs 3
    pages of BOTH. A prompt of 20 rows and two decoded rows land in pages
    5 and 2 at their places; an inactive slot writes nothing; a step
    scores every row of the context, its own among them."""
    ops = _index_cache()
    state = ops.init_state()
    assert {k: v.shape for k, v in state.items()} == {
        "c": (2, 128, 128), "pt": (2, 4), "ik": (2, 8, 16, 8)}
    assert ops.page_table_len == 4 and ops.pages_needed(0, 33) == 3
    assert ops.index_bytes(state) == 2 * 8 * 16 * 8 * 4
    assert ops.cache_bytes(state) == 2 * 128 * 128 * 4 + ops.index_bytes(
        state)
    dest = jnp.asarray(ops.prompt_dest_groups([[5, 2]], slot=0))
    assert dest.shape == (4,)
    state = ops.set_page_table(state, 0, dest)
    rows = rng.randn(32, 24).astype("float32")
    keys = rng.randn(32, 8).astype("float32")
    state = ops.write_prompt(state, 1, jnp.asarray(rows), jnp.asarray(keys),
                             dest, jnp.asarray(20))
    active = jnp.asarray([True, False])
    more_rows = rng.randn(2, 2, 24).astype("float32")
    more_keys = rng.randn(2, 2, 8).astype("float32")
    for j, t in enumerate((20, 21)):
        pos = jnp.asarray([t, t])
        state = ops.write_token(state, 1, jnp.asarray(more_rows[j]), pos,
                                active)
        state = ops.write_index(state, 1, jnp.asarray(more_keys[j]), pos,
                                active)
    want_rows = np.concatenate([rows[:20], more_rows[:, 0]])
    want_keys = np.concatenate([keys[:20], more_keys[:, 0]])
    pool = np.asarray(state["c"][1])
    index = np.asarray(state["ik"][1]).reshape(8 * 16, 8)
    where = np.concatenate([5 * 16 + np.arange(16), 2 * 16 + np.arange(6)])
    np.testing.assert_array_equal(pool[where, :24], want_rows)
    np.testing.assert_array_equal(index[where], want_keys)
    assert not pool[:, 24:].any() and not np.asarray(state["c"][0]).any()
    assert not np.asarray(state["ik"][0]).any()
    # the keys of a page's rows past the prompt are the bucket's padding:
    # never scored before the row is written
    others = np.setdiff1d(np.arange(128), np.concatenate(
        [where, 2 * 16 + np.arange(6, 16)]))
    assert not index[others].any() and not pool[others].any()
    q = jnp.asarray(rng.randn(2, 2, 8).astype("float32"))
    w = jnp.asarray(np.abs(rng.randn(2, 2)).astype("float32"))
    scores, scored = ops.index_scores(state, 1, q, w, jnp.asarray([22, 22]),
                                      active)
    np.testing.assert_array_equal(np.asarray(scored), [22, 0])
    neg = float(attention_ops.neg_inf(jnp.float32))
    assert (np.asarray(scores[0, 22:]) == neg).all() \
        and (np.asarray(scores[1]) == neg).all()
    np.testing.assert_allclose(
        np.asarray(scores[0, :22]),
        (np.maximum(np.einsum("hl,nl->hn", np.asarray(q[0]), want_keys), 0)
         * np.asarray(w[0])[:, None]).sum(0), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_index_kernel_equals_the_scores_of_the_gathered_table(rng, dtype):
    """Contexts of 700, 9 and 0 rows in a table of 1,024, pages out of
    order and shared between slots: the ``dsa_index_scores`` kernel's
    interpreter (two waves of 512 rows, the second partial) equals
    ``dsa_index_scores`` over the gathered table, the masking constant at
    and past a slot's length, and the cache takes it by the flag."""
    pool = jnp.asarray(rng.randn(2, 80, 16, 16), dtype)
    pt = jnp.asarray(rng.randint(0, 80, (3, 64)), jnp.int32)
    q = jnp.asarray(rng.randn(3, 4, 16), dtype)
    w = jnp.asarray(rng.randn(3, 4), jnp.float32)
    ctx = jnp.asarray([700, 9, 0], jnp.int32)
    got = dsa_index.dsa_index_scores_paged(q, w, pool, pt, ctx, layer=1,
                                           interpret=True)
    want = attention_ops.dsa_index_scores(
        q, w, pool[1][pt].reshape(3, 1024, 16), ctx)
    live = np.arange(1024)[None, :] < np.asarray(ctx)[:, None]
    np.testing.assert_array_equal(np.asarray(got)[~live],
                                  np.asarray(want)[~live])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5 if dtype == "float32" else 2e-2,
                               atol=1e-5)
    assert "lanes" in dsa_index.dsa_index_gate(jnp.bfloat16, 64, 16, 16384)
    assert dsa_index.dsa_index_gate(jnp.bfloat16, 128, 16, 16384) is None
    ops = _index_cache()
    for flag, mode in (("off", None), ("interpret", "interpret")):
        set_flag("paged_attention_kernel", flag)
        try:
            assert ops.index_kernel_mode()[0] == mode
        finally:
            set_flag("paged_attention_kernel", "auto")


def test_the_table_of_a_choice_is_its_rows_ascending_without_a_sort(rng):
    """``dsa_chosen_rows`` against ``numpy.nonzero`` over masks of 0 to 24
    chosen rows of 300 (chunks of 128 lanes: the last one partial), runs
    of neighbours, a chunk with none and a chunk with all of them; no
    ``sort``, ``top_k``, ``scatter`` or ``gather`` in its jaxpr."""
    chosen = np.zeros((6, 300), bool)
    chosen[0, rng.choice(300, 24, replace=False)] = True
    chosen[1, [0, 127, 128, 299]] = True
    chosen[2, 128:152] = True                   # one chunk holds them all
    chosen[3, 290:300] = True
    chosen[5, rng.choice(300, 7, replace=False)] = True
    rows, held = attention_ops.dsa_chosen_rows(jnp.asarray(chosen), 24)
    for b in range(6):
        want = np.nonzero(chosen[b])[0]
        np.testing.assert_array_equal(np.asarray(held[b]),
                                      np.arange(24) < len(want))
        np.testing.assert_array_equal(np.asarray(rows[b])[:len(want)], want)
        assert not np.asarray(rows[b])[len(want):].any()
    names = {e.primitive.name for e in _eqns(jax.make_jaxpr(
        lambda c: attention_ops.dsa_chosen_rows(c, 24))(chosen).jaxpr)}
    assert not names & {"sort", "top_k", "scatter", "gather", "argsort"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sparse_reads_kernel_equals_the_gather_of_the_chosen_rows(
        rng, dtype):
    """Contexts of 150, 9 and 0 rows, 24 rows read at the most, a page
    table of runs out of order: the latent kernel's wave over the whole context
    under the choice as a row mask (interpreted, under the name
    ``dsa_sparse_decode``) equals the XLA gather of the chosen rows alone;
    both count the rows CHOSEN; a slot that holds nothing reads nothing,
    and a chosen row past the position is not read."""
    ops = LatentPagedCache(
        1, 128, 0, 3, 256, 16, 48, dtype=dtype,
        groups=[CacheGroup("latent_sparse", (0,), None, 48, LATENT)],
        index=(1, 8, 24))
    state = ops.init_state()
    # a latent group's pages come in aligned runs (the pool's: the kernel
    # copies a run from its first entry), the runs out of order
    r = ops.group_run_pages(0)
    order = (rng.permutation(48 // r)[:, None] * r + np.arange(r)).reshape(-1)
    for slot in range(3):
        state = ops.set_page_table(state, slot, jnp.asarray(
            ops.prompt_dest_groups([order[16 * slot:16 * slot + 16]],
                                   slot=slot)))
    state["c"] = jnp.asarray(rng.randn(1, 48 * 16, 128), dtype)
    q = jnp.asarray(rng.randn(3, 4, 128), dtype)
    ctx = jnp.asarray([150, 9, 0])
    active = jnp.asarray([True, True, False])
    chosen = np.zeros((3, 256), bool)
    chosen[0, rng.choice(150, 24, replace=False)] = True
    chosen[0, 200] = True                       # past the position
    chosen[1, [0, 3, 8]] = True
    chosen[2, [5]] = True                       # not active: reads nothing
    outs = {}
    for mode in ("off", "interpret"):
        set_flag("paged_attention_kernel", mode)
        try:
            outs[mode] = ops.rows_decode_attention(
                state, 0, q, jnp.asarray(chosen), ctx, active, sm_scale=0.1)
        finally:
            set_flag("paged_attention_kernel", "auto")
    for mode in outs:
        np.testing.assert_array_equal(np.asarray(outs[mode][1]), [24, 3, 0])
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(
        np.asarray(outs["interpret"][0][:2], np.float32),
        np.asarray(outs["off"][0][:2], np.float32), atol=tol, rtol=0)
    assert not np.asarray(outs["interpret"][0][2], np.float32).any()


def test_an_index_of_rows_is_refused_beside_a_ring():
    """One latent group of pages: a ring beside it is refused with the
    rule's own words, which say what holds for an index of rows."""
    ring = [CacheGroup("latent_sparse", (0,), None, 8, LATENT),
            CacheGroup("ring", (1,), 32, 8, LATENT)]
    with pytest.raises(ValueError, match="a key a ROW"):
        LatentPagedCache(2, 16, 8, 2, 64, 16, 8, groups=ring,
                         index=(1, 8, 4))


# -- (d) what the decode step moves ------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_decode_step_holds_no_pool_sized_copy():
    """The jaxpr of a decode step, the kernel armed: whatever yields an
    array with either pool's rows is a row scatter into it (a latent row
    a layer, a page's row of keys a layer), never a copy, a select or a
    slice of it; keys and rows come out through the two kernels alone."""
    set_flag("paged_attention_kernel", "interpret")
    try:
        cfg = toy_cfg()
        model = ds.DeepSeekV32LM(cfg, params={})
        params = jax.eval_shape(lambda: ds.init_params(cfg, 0))
        groups = [CacheGroup(n, l, w, 300, k)
                  for n, l, w, k in cfg.cache_groups]
        ops = LatentPagedCache(4, 16, 8, 2, 256, 16, 300, groups=groups,
                               index=cfg.index_row)
        cache = jax.eval_shape(ops.init_state)
        ints = jax.ShapeDtypeStruct((2,), jnp.int32)
        flags = jax.ShapeDtypeStruct((2,), jnp.bool_)
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, a: model.decode(p, c, ops, t, pos, a))(
                params, cache, ints, ints, flags).jaxpr
        for shape in ((300 * 16, 128), (300, 16, 16)):    # latent, index
            wrote = [e.primitive.name for e in _eqns(jaxpr)
                     if any(getattr(v.aval, "shape", ())[1:] == shape
                            for v in e.outvars)]
            assert sorted(set(wrote) - {"pjit"}) == ["scatter"], wrote
            assert wrote.count("scatter") == cfg.n_layer
        # the index scores and the sparse read, a kernel each a layer
        assert len([e for e in _eqns(jaxpr)
                    if e.primitive.name == "pallas_call"]) == 2 * cfg.n_layer
        read = [e for e in _eqns(jaxpr) if e.primitive.name == "gather"
                and e.invars[0].aval.shape[-2:] in ((300 * 16, 128),
                                                    (300, 16, 16))]
        assert read == []
    finally:
        set_flag("paged_attention_kernel", "auto")


# -- (e) the share, and the router -----------------------------------------------


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        toy, rng):
    """The deployment's arithmetic at toy size: sixteen chips hold two of
    32 experts each (a chip half a group of 8), every chip has attention,
    the router and the shared expert. The routed parts of the sixteen
    shares, with the residual and the shared expert counted ONCE, add up
    to the reference's uncut layer; a share's count of the rows that kept
    its group is the by-hand one."""
    lp = toy.params["layers"][1]
    x = jnp.asarray(rng.randn(9, 64).astype("float32"))
    st = (4, 2.5, 4, 2)
    whole = np.asarray(ref._sparse(lp, x, 1e-6, st + (tuple(range(32)),)))
    once = whole - np.asarray(ref.routed(lp, x, 1e-6,
                                         st + (tuple(range(32)),)))
    with jax.default_matmul_precision("highest"):
        u = ref._rms(x, lp["g2"], 1e-6)
        w = np.asarray(ref.route(u, lp["wr"], lp["br"], 4, 2.5, 4, 2))
    total = once.copy()
    for c in range(16):
        held = (2 * c, 2 * c + 1)
        part = {**lp, **{k: lp[k][2 * c:2 * c + 2]
                         for k in ("wg", "wu", "wd")}}
        out, stats = routed_feed_forward(toy_cfg(experts_held=held), part, x,
                                         None, count_groups=True)
        assert int(stats["experts_touched"]) <= 2
        if c in (0, 9):     # a share alone is the reference given the share
            np.testing.assert_allclose(
                np.asarray(out),
                np.asarray(ref._sparse(part, x, 1e-6, st + (held,))),
                atol=TOL, rtol=0)
        # the rows of which a CHOSEN expert lies in the share's group are
        # among those that kept it
        group = w[:, 8 * (c // 4):8 * (c // 4) + 8]
        assert int(stats["groups_kept_with_held"]) >= int(
            (group > 0).any(axis=1).sum())
        total += np.asarray(out) - once
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_the_group_limited_router_against_a_loop_by_hand(rng):
    """8 experts a group, 4 groups, 2 kept, 4 chosen: a group's score is
    the sum of its two largest ``s + b``, the best groups stay (a tie to
    the lower group), the 4 largest ``s + b`` among their experts are
    chosen (a tie to the lower expert) and weighed by ``s`` alone; the
    groups kept come back for the share's counter."""
    h = jnp.asarray(rng.randn(12, 16).astype("float32"))
    wr = jnp.asarray(rng.randn(16, 32).astype("float32"))
    # a coarse grid of scores, so that ties occur
    wr = jnp.round(wr * 2) / 2
    h = jnp.round(h * 2) / 2
    bias = jnp.asarray(np.round(rng.randn(32) * 0.1, 1).astype("float32"))
    idx, w, kept = moe_ops.route_sigmoid_topk(
        h, wr, bias, 4, 2.5, n_group=4, topk_group=2, with_groups=True)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(h) @ np.asarray(wr))))
    s = np.asarray(jax.nn.sigmoid(h @ wr))
    biased = s + np.asarray(bias)
    for n in range(12):
        groups = biased[n].reshape(4, 8)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        keep = sorted(range(4), key=lambda g: (-score[g], g))[:2]
        np.testing.assert_array_equal(np.nonzero(np.asarray(kept[n]))[0],
                                      sorted(keep))
        allowed = [e for e in range(32) if e // 8 in keep]
        want = sorted(allowed, key=lambda e: (-biased[n, e], e))[:4]
        np.testing.assert_array_equal(np.asarray(idx[n]), want)
        np.testing.assert_allclose(np.asarray(w[n]),
                                   2.5 * s[n, want] / s[n, want].sum(),
                                   rtol=1e-5)
    # the plain top-k chooses outside the kept groups somewhere
    plain, _ = moe_ops.route_sigmoid_topk(h, wr, bias, 4, 2.5)
    assert (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(idx), 1)
            ).any()


# -- (f) a lower precision, or another choice, fails ---------------------------------


def test_what_a_control_lowers_is_seen(rng):
    """``TOL`` is tight enough to tell: the index scores rounded to
    bfloat16 put the prefill's logits outside it (a row near the 16th
    score changes places), and latent rows kept at float8's precision
    differ from the rows as made by more than a hundredth of their length
    (the plain top-k in the group-limited router's place is held by the
    router's own test)."""
    model = toy_model(score_dtype="bfloat16")
    seq = rng.randint(0, 96, 120)
    logits, _ = _prefill(model, seq, run=jax.jit(model.prefill))
    err = np.abs(np.asarray(logits[0, :120])
                 - reference_rows(model, seq, np.arange(120))).max()
    assert err > 10 * TOL, err
    h = jnp.asarray(rng.randn(7, 64).astype("float32"))
    pos = jnp.arange(7)
    lp = model.params["layers"][0]
    stated = ds._inputs(toy_cfg(), lp, h, pos)[2]
    lowered = ds._inputs(toy_cfg(row_dtype="float8_e4m3fn"), lp, h, pos)[2]
    assert ref.relative_gap(lowered, stated) > 0.01

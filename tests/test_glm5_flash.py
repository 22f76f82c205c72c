"""GLM-5.3-Flash's sparse latent hybrid against its plain float32 reference
(``grid/reference/glm5_flash.py``), at a toy of two KDA layers and two DSA
layers with ``index_topk`` 32 (8 blocks of 4 rows) and contexts of 100-200,
so that the selection bites at every row that matters:

(a) the prefill, under the bucket's padding, equals the reference's full
    forward;
(b) prefill, then decoding through the pages, the index pool and the open
    block's keys, equals it too, by the gather and by the kernel's
    interpreter;
(c) the cache's index: a block closes exactly when its fourth row is
    written, the own block is always read, ties go to the lower block, a
    context under ``index_topk`` is dense attention;
(d) the sparse read's kernel against the gather, and the prefill's
    attention kernel against the blocked form;
(e) the share: eight shares and one shared expert add up to the whole
    layer, and a share through the engine equals the reference given it;
(f) a lower precision anywhere the configuration states one fails;
(g) what this cache cannot do is refused at construction.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import glm5_flash as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import glm5_flash as gf
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import dsa_prefill
from paddle_tpu.ops.pallas_kernels import mla_attention as mla
from paddle_tpu.serving.kv_cache import (LATENT, STATE, CacheGroup,
                                         LatentPagedCache)

TOL = 5e-5
TYPES = [gf.KDA, gf.DSA, gf.KDA, gf.DSA]
PUBLISHED = {  # the toy under the published config's own keys
    "num_hidden_layers": 4, "layer_types": TYPES, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 32, "index_kpool": 4,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-5, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "swiglu_limit": 10,
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "gate_lower_bound": -5,
                           "short_conv_kernel_size": 4},
    "model": {"index_rope_dim": 8, "index_rope_theta": 8e6}}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=4, d_model=64, n_head=4, d_state=16,
              layer_types=TYPES, q_rank=24, kv_rank=16, d_nope=16, d_v=16,
              index_heads=4, index_dim=16, index_topk=32, index_kpool=4,
              d_dense=128, dense_layers=(0,), n_expert=16, top_k=4,
              d_expert=32, routed_scale=2.5, index_rope=8, decay_rank=8,
              max_seq=256, dtype="float32", half_life=(2.0, 64.0),
              score_std=0.02)
    kw.update(over)
    return gf.Glm5FlashConfig(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention, the index, the gates and routing are decisive at d = 64."""
    def scale(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return a * 6.0 if a.ndim > 1 and name != "cw" else a

    return jax.tree_util.tree_map_with_path(scale, params)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return gf.Glm5FlashLM(cfg, params=_scaled(gf.init_params(cfg, 3)))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_rows(model, seq, rows, **over):
    size = -(-len(seq) // 64) * 64
    toks = np.zeros((size,), np.int32)
    toks[:len(seq)] = seq
    return np.asarray(ref.forward(model.params, dict(PUBLISHED, **over), toks,
                                  rows=rows))


def _prefill(model, seq, bucket=256):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return model.prefill(model.params, jnp.asarray(toks),
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
    """``dsa_causal_attention`` as on a chip whose gate takes the shapes,
    the kernel's interpreter standing in at ``tiles``."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(dsa_prefill, "dsa_prefill_gate", functools.partial(
        dsa_prefill.dsa_prefill_gate, interpret=True))
    monkeypatch.setattr(
        dsa_prefill, "dsa_prefill_attention", functools.partial(
            dsa_prefill.dsa_prefill_attention, interpret=True, **tiles))


@pytest.mark.parametrize("form", ["blocked", "kernel"])
def test_prefill_equals_the_reference(toy, rng, form, monkeypatch):
    """Both kinds of layer under four streams: the chunk scan against the
    recurrence, and attention under each row's own mask against the
    reference's selection row by row (150 rows: 37 blocks, 8 read), by the
    blocked form and by the ``dsa_prefill_attention`` kernel (interpreted:
    four query blocks of eight key tiles, two heads a step)."""
    if form == "kernel":
        _arm_the_prefill_kernel(monkeypatch, block_q=64, block_k=32, heads=2)
    counter = mx.counter("dsa/prefill_calls." + form)
    before = counter.value
    n = 150
    seq = rng.randint(0, 96, n)
    logits, kept = _prefill(toy, seq)
    assert counter.value == before + 2          # the toy's two DSA layers
    want = reference_rows(toy, seq, np.arange(n))
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    # what the cache is handed: a state and a tail of a KDA layer; rows,
    # pooled keys and the open block's keys of a DSA layer
    assert [tuple(t.shape[1:] for t in k) for k in kept] == [
        ((4, 16, 16), (3, 192)), ((256, 16), (64, 16), (3, 16))] * 2


# -- (b) decode through the cache ------------------------------------------------


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_pages_index_and_tail_equals_the_reference(
        toy, kernel, rng):
    """Three requests of different lengths (100, 37, 120: one starts under
    ``index_topk`` and passes it) decode 40 tokens each through the latent
    pages, the pooled keys and the open blocks' keys; every served row's
    logits are the reference's full forward's, by the XLA gather and by
    the kernels' interpreter."""
    set_flag("paged_attention_kernel", kernel)
    try:
        eng = _engine(toy)
        mode = "interpret" if kernel == "interpret" else None
        assert eng.cache_ops.sparse_kernel_mode()[0] == mode
        reqs = [eng.submit(list(rng.randint(0, 96, m)), 40)
                for m in (100, 37, 120)]
        eng.run()
        for r in reqs:
            assert _served_against_reference(eng, r) < TOL
        tenants, stats = eng.last_decode_stats
        assert set(stats) >= {"dsa_probe", "index_blocks_scored",
                              "attn_rows_read.latent_sparse",
                              "attn_rows_context.latent_sparse"}
        assert np.asarray(stats["dsa_probe"]).shape[-1] == 1 + 7
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_a_reused_slot_gives_a_fresh_engines_logits(toy, rng):
    """One slot, two requests one after the other: the second's index (the
    pooled keys in pages the first used, the open block's keys of the
    slot) is its own."""
    eng = _engine(toy, slots=1)
    first = eng.submit(list(rng.randint(0, 96, 90)), 30)
    eng.run()
    second = eng.submit(list(rng.randint(0, 96, 101)), 30)
    eng.run()
    assert first.state == second.state == "finished"
    assert _served_against_reference(eng, second) < TOL


# -- (c) the index beside the rows -----------------------------------------------


def _index_cache(slots=2, max_ctx=64, pages=8):
    groups = [CacheGroup("latent_sparse", (0,), None, pages, LATENT)]
    return LatentPagedCache(1, 16, 0, slots, max_ctx, 16, pages,
                            groups=groups, index=(4, 8, 4))


def test_a_block_closes_exactly_when_its_fourth_row_is_written(rng):
    """Rows 0..6 of slot 0 written one a step: block 0's pooled key
    appears with row 3 and is the mean of rows 0-3; block 1 is still open
    after row 6 and its three keys are the slot's. The slot reused: the
    new request's prompt overwrites the open block's keys, and the pooled
    key of its first block is its own."""
    ops = _index_cache()
    state = ops.init_state()
    dest = jnp.asarray(ops.prompt_dest_groups([[5, 2]], slot=0))
    state = ops.set_page_table(state, 0, dest)
    keys = rng.randn(7, 8).astype("float32")
    active = jnp.asarray([True, False])
    for t in range(7):
        before = np.asarray(state["ik"])
        state = ops.write_index(
            state, 0, jnp.asarray(np.stack([keys[t], keys[t] + 9.0])),
            jnp.asarray([t, t]), active)
        if t != 3:      # only the fourth row writes a pooled key
            np.testing.assert_array_equal(np.asarray(state["ik"]), before)
    pool = np.asarray(state["ik"][0])                # [pages, 4 x 8 lanes]
    np.testing.assert_allclose(pool[5, :8], keys[:4].mean(0), atol=1e-6)
    assert not pool[5, 8:].any() and not pool[np.arange(8) != 5].any()
    np.testing.assert_array_equal(np.asarray(state["it"][0, 0]), keys[4:7])
    assert not np.asarray(state["it"][0, 1]).any()   # the inactive slot
    # what a step scores: block 0 alone is closed at 7 rows
    q = jnp.asarray(rng.randn(2, 2, 8).astype("float32"))
    w = jnp.ones((2, 2), jnp.float32)
    scores, closed = ops.index_scores(state, 0, q, w, jnp.asarray([7, 7]),
                                      active)
    np.testing.assert_array_equal(np.asarray(closed), [1, 0])
    neg = float(attention_ops.neg_inf(jnp.float32))
    assert float(scores[0, 0]) > neg / 2 and (np.asarray(scores[0, 1:])
                                              == neg).all()
    np.testing.assert_allclose(
        float(scores[0, 0]),
        np.maximum(np.asarray(q[0]) @ keys[:4].mean(0), 0).sum(), rtol=1e-5)
    # a prompt of 6 rows into the same slot, other pages: block 0 closed
    dest = jnp.asarray(ops.prompt_dest_groups([[7, 1]], slot=0))
    state = ops.set_page_table(state, 0, dest)
    pooled = rng.randn(4, 8).astype("float32")
    tail = rng.randn(3, 8).astype("float32")
    state = ops.write_prompt(
        state, 0, jnp.zeros((16, 16)), jnp.asarray(pooled), jnp.asarray(tail),
        dest, jnp.asarray(6))
    pool = np.asarray(state["ik"][0])
    np.testing.assert_allclose(pool[7, :8], pooled[0], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state["it"][0, 0]), tail)
    # rows 6 and 7 decoded: block 1 closes over the open block's keys
    more = rng.randn(2, 8).astype("float32")
    for t, key in zip((6, 7), more):
        state = ops.write_index(state, 0, jnp.asarray(np.stack([key, key])),
                                jnp.asarray([t, t]), active)
    pool = np.asarray(state["ik"][0])
    np.testing.assert_allclose(
        pool[7, 8:16], (tail[0] + tail[1] + more[0] + more[1]) / 4,
        atol=1e-6)
    np.testing.assert_allclose(pool[7, :8], pooled[0], atol=1e-6)


def test_the_own_block_is_always_read_and_ties_go_to_the_lower_block():
    """Six closed blocks scored, four read a query (one its own): of equal
    scores the LOWER blocks are chosen; the block the position lies in is
    read whatever it scores; a block that is not closed is never chosen;
    program and reference agree."""
    neg = float(attention_ops.neg_inf(jnp.float32))
    scores = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 2.0, 0.5, neg, neg],
                          [3.0, neg, neg, neg, neg, neg, neg, neg]])
    chosen, picked = attention_ops.dsa_select(scores, jnp.asarray([6, 1]), 4)
    np.testing.assert_array_equal(
        np.asarray(chosen),
        [[0, 1, 1, 1, 0, 0, 1, 0], [1, 1, 0, 0, 0, 0, 0, 0]])
    np.testing.assert_array_equal(np.asarray(picked),
                                  [[1, 2, 3], [0, -1, -1]])
    want, _ = ref.choose(jnp.where(scores > neg / 2, scores, 0.0),
                         jnp.asarray([6 * 4 + 1, 1 * 4]), 4, 4)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))


def test_a_context_under_index_topk_is_dense_attention(toy, rng):
    """20 rows a slot (5 blocks of 8 read): the sparse read over every
    closed block and the own block is the dense latent read of the same
    rows, by the gather to float round-off."""
    ops = LatentPagedCache(
        1, 16, 0, 2, 64, 16, 8,
        groups=[CacheGroup("latent_sparse", (0,), None, 8, LATENT)],
        index=(4, 8, 8))
    state = ops.init_state()
    for slot, pages in enumerate(([3, 6], [1, 4])):
        state = ops.set_page_table(state, slot, jnp.asarray(
            ops.prompt_dest_groups([pages], slot=slot)))
    rows = jnp.asarray(rng.randn(20, 2, 16).astype("float32"))
    both = jnp.asarray([True, True])
    for t in range(20):
        state = ops.write_token(state, 0, rows[t], jnp.full((2,), t), both)
    q = jnp.asarray(rng.randn(2, 4, 16).astype("float32"))
    ctx = jnp.asarray([20, 18])
    chosen = jnp.arange(16)[None, :] <= ((ctx - 1) // 4)[:, None]
    sparse, read = ops.sparse_decode_attention(state, 0, q, chosen, ctx, both,
                                               sm_scale=0.25)
    dense = ops.decode_attention(state, 0, q, ctx, both, sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=1e-6, rtol=0)
    np.testing.assert_array_equal(np.asarray(read), [20, 18])


# -- (d) the sparse read's kernel -------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sparse_kernel_equals_the_gather(rng, dtype):
    """Contexts of 150, 9 and 0 rows, 6 blocks read at the most, a page
    table out of order: the kernel's interpreter over the table of 8-row
    tiles and the row mask equals the XLA gather of the same tiles, and a
    slot that holds nothing reads nothing."""
    ops = LatentPagedCache(
        1, 128, 0, 3, 256, 16, 48, dtype=dtype,
        groups=[CacheGroup("latent_sparse", (0,), None, 48, LATENT)],
        index=(4, 8, 6))
    state = ops.init_state()
    order = rng.permutation(48)
    for slot in range(3):
        state = ops.set_page_table(state, slot, jnp.asarray(
            ops.prompt_dest_groups([order[16 * slot:16 * slot + 16]],
                                   slot=slot)))
    state["c"] = jnp.asarray(rng.randn(1, 48 * 16, 128), dtype)
    q = jnp.asarray(rng.randn(3, 4, 128), dtype)
    ctx = jnp.asarray([150, 9, 0])
    active = jnp.asarray([True, True, False])
    chosen = np.zeros((3, 64), bool)
    chosen[0, [3, 4, 17, 30, 31, 37]] = True     # 37: position 149's block
    chosen[1, [0, 1, 2]] = True
    chosen[2, [5]] = True                        # not active: reads nothing
    outs = {}
    for mode in ("off", "interpret"):
        set_flag("paged_attention_kernel", mode)
        try:
            outs[mode] = ops.sparse_decode_attention(
                state, 0, q, jnp.asarray(chosen), ctx, active, sm_scale=0.1)
        finally:
            set_flag("paged_attention_kernel", "auto")
    np.testing.assert_array_equal(np.asarray(outs["off"][1]), [22, 9, 0])
    np.testing.assert_array_equal(np.asarray(outs["interpret"][1]),
                                  [22, 9, 0])
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(
        np.asarray(outs["interpret"][0][:2], np.float32),
        np.asarray(outs["off"][0][:2], np.float32), atol=tol, rtol=0)
    assert not np.asarray(outs["interpret"][0][2], np.float32).any()


def test_the_sparse_call_has_a_name_of_its_own_and_a_gate():
    assert mla.SPARSE_KERNEL_NAME == "dsa_sparse_decode"
    assert mla.mla_decode_gate(jnp.bfloat16, 512, 512, mla.SPARSE_TILE,
                               sparse=True) is None
    assert "multiple" in mla.mla_decode_gate(jnp.bfloat16, 512, 512, 4,
                                             sparse=True)
    # the dense call's page is still held to the pool type's sublanes
    assert "multiple" in mla.mla_decode_gate(jnp.bfloat16, 512, 512, 8)


def _dsa_case(rng, s, h=4, d=16, hi=4, li=16, kpool=4):
    def arr(*shape):
        return jnp.asarray(rng.randn(*shape).astype("float32"))

    return (arr(s, h, d), arr(s, h, d), arr(s, h, d), arr(s, hi, li),
            arr(s, hi), arr(s // kpool, li))


@pytest.mark.parametrize("rows,why", [
    (24, "under index_topk: dense causal"),
    (100, "past it: 17 of 25 blocks dropped at the last row"),
    (88, "not whole query blocks of 16: tiles of 11 rows")])
def test_the_prefill_kernel_equals_the_blocked_form(rng, monkeypatch, rows,
                                                    why):
    """``dsa_causal_attention`` at ``kpool`` 4 and 8 blocks read, by the
    blocked form and by the kernel's interpreter at query blocks of 16
    (where they divide the rows) and key tiles of 8: the same rows chosen,
    the same float32 softmax, a row's sum gathered tile by tile."""
    case = _dsa_case(rng, rows)
    want = attention_ops.dsa_causal_attention(*case, 4, 8, 0.25, block_q=16)
    _arm_the_prefill_kernel(monkeypatch, block_q=16, block_k=8, heads=2)
    got = attention_ops.dsa_causal_attention(*case, 4, 8, 0.25, block_q=16)
    assert got.shape == want.shape == (rows, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6,
                               rtol=0)


def _rows_read_by_hand(case, kpool, top_blocks):
    """[S, S] bool: row t reads its own block up to itself and the
    ``top_blocks - 1`` closed blocks of highest index score, a tie to the
    lower block, by a sort a row."""
    q_idx, w_idx, k_pool = (np.asarray(x, np.float64) for x in case[3:])
    s = q_idx.shape[0]
    index = np.einsum("th,thb->tb", w_idx, np.maximum(
        np.einsum("thl,bl->thb", q_idx, k_pool), 0.0)).astype(np.float32)
    mask = np.zeros((s, s), bool)
    for t in range(s):
        own = t // kpool
        order = np.argsort(-index[t, :own], kind="stable")
        for b in list(order[:top_blocks - 1]) + [own]:
            mask[t, b * kpool:(b + 1) * kpool] = True
    return mask & np.tril(np.ones((s, s), bool))


@pytest.mark.parametrize("length,why", [
    (1, "one row"), (159, "index_topk - 1"), (161, "index_topk + 1"),
    (300, "inside the third query block"), (511, "S - 1"), (512, "S")])
def test_a_prefill_stops_at_its_prompts_end_and_chooses_nothing_under_topk(
        rng, monkeypatch, prefill_told_its_length, length, why):
    """``dsa_causal_attention`` told the prompt's length, at ``kpool`` 4
    and 40 blocks read (160 rows) in a bucket of 512 and mask blocks of 128
    (the first keeps every closed block, the second not):
    ``conftest.prefill_told_its_length`` has what holds."""
    case = _dsa_case(rng, 512)
    prefill_told_its_length(
        functools.partial(attention_ops.dsa_causal_attention, *case, 4, 40,
                          0.25, block_q=128),
        lambda: _arm_the_prefill_kernel(monkeypatch), case,
        _rows_read_by_hand(case, 4, 40), length, 128)


def test_the_prefill_kernel_reads_no_key_tile_past_a_query_block(rng):
    """The mask is the caller's, the causal edge the grid's: keys past a
    query block's last row are not read even where the mask names them,
    and the key tiles of a block that ends inside one are."""
    q, k, v = _dsa_case(rng, 48)[:3]
    tril = jnp.tril(jnp.ones((48, 48), jnp.int8))
    run = functools.partial(dsa_prefill.dsa_prefill_attention, q, k, v,
                            sm_scale=0.25, block_q=16, block_k=12, heads=4,
                            interpret=True)
    loose = tril.at[:16, 16:].set(1)      # the first block's rows name more
    np.testing.assert_array_equal(np.asarray(run(loose)[16:]),
                                  np.asarray(run(tril)[16:]))
    np.testing.assert_array_equal(np.asarray(run(tril.at[:16, 24:].set(1))),
                                  np.asarray(run(tril)))
    sc = jnp.einsum("qhd,khd->hqk", q, k) * 0.25
    sc = jnp.where(tril[None] != 0, sc, attention_ops.neg_inf(jnp.float32))
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
    np.testing.assert_allclose(np.asarray(run(tril)), np.asarray(want),
                               atol=2e-6, rtol=0)


def test_the_prefill_gate_names_what_it_refuses_and_chooses_the_form(
        rng, monkeypatch):
    """The kernel takes whole 128-lane heads and whole 128-row tiles of
    the mask within its VMEM, and ``dsa_causal_attention`` asks it only on
    a TPU: here, and where the gate refuses, the blocked form runs."""
    gate = dsa_prefill.dsa_prefill_gate
    for rows in (4096, 8192, 4096 + 128):         # the cell's buckets, and
        assert gate(64, 256, 256, rows, 4) is None    # tiles of 128 rows
    assert "128-lane" in gate(64, 192, 256, 8192, 4)
    assert "128-lane" in gate(64, 256, 64, 8192, 4)
    assert "128-row tiles" in gate(64, 256, 256, 8192 + 64, 4)
    assert "blocks of 4" in gate(64, 256, 256, 8190, 4)
    assert "KiB of VMEM" in gate(64, 1024, 1024, 8192, 4)
    assert "KiB of VMEM" in gate(64, 512, 512, 8192, 4, itemsize=4)
    assert gate(4, 16, 16, 88, 4, interpret=True) is None
    assert "blocks of 4" in gate(4, 16, 16, 90, 4, interpret=True)
    q, k, v = _dsa_case(rng, 128)[:3]
    with pytest.raises(ValueError, match="128-lane"):
        dsa_prefill.dsa_prefill_attention(q, k, v,
                                          jnp.ones((128, 128), jnp.int8))
    took = []
    monkeypatch.setattr(
        dsa_prefill, "dsa_prefill_attention",
        lambda q, *a, **kw: took.append("kernel") or q)
    monkeypatch.setattr(
        jax.lax, "map", lambda f, xs: took.append("map") or jnp.zeros(
            (xs[0].shape[0], xs[1].shape[1], 128), jnp.int8))
    small = _dsa_case(rng, 128)
    served = tuple(jnp.zeros(x, jnp.bfloat16) for x in (
        (128, 2, 128), (128, 2, 128), (128, 2, 128), (128, 2, 16), (128, 2),
        (32, 16)))
    attention_ops.dsa_causal_attention(*served, 4, 8)
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    attention_ops.dsa_causal_attention(*small, 4, 8)
    attention_ops.dsa_causal_attention(*served, 4, 8)
    # blocked: ONE map (selection and attention together); the kernel's
    # form: the map that makes the mask, then the call
    assert took == ["map", "map", "map", "kernel"]


@pytest.mark.parametrize("form", ["blocked", "kernel"])
def test_the_prefill_attention_counts_the_form_it_chose_once_a_trace(
        form, rng, monkeypatch):
    """``dsa/prefill_calls.<form>`` rises by one when a program with one
    layer's attention is traced, and not again when it runs."""
    if form == "kernel":
        _arm_the_prefill_kernel(monkeypatch, block_q=32, block_k=32)
    counts = {f: mx.counter("dsa/prefill_calls." + f)
              for f in ("blocked", "kernel")}
    before = {f: c.value for f, c in counts.items()}
    case = _dsa_case(rng, 64)
    program = jax.jit(lambda *a: attention_ops.dsa_causal_attention(
        *a, 4, 8, 0.25))
    text = str(program.trace(*case).jaxpr)
    program(*case), program(*case)
    assert {f: c.value - before[f] for f, c in counts.items()} == {
        f: 1.0 * (f == form) for f in counts}
    assert ("pallas_call" in text) is (form == "kernel")


# -- (e) the share -----------------------------------------------------------------


def test_eight_shares_and_one_shared_expert_add_up_to_the_whole_layer(
        toy, rng):
    """The deployment's arithmetic at toy size: eight chips hold two of
    sixteen experts each, every chip has the router and the shared expert.
    The routed parts of the eight shares, with the shared expert counted
    ONCE, add up to the uncut reference's whole block."""
    lp = toy.params["layers"][1]
    u = jnp.asarray(rng.randn(9, 64).astype("float32"))

    def block(lp, held):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref._sparse(lp, u, (4, 2.5, 10.0, held)))

    whole = block(lp, tuple(range(16)))
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._mlp(u, lp["sg"], lp["su"], lp["sd"], 10.0))
    total = shared.copy()
    for c in range(8):
        held = (2 * c, 2 * c + 1)
        part = {**lp, **{k: lp[k][2 * c:2 * c + 2]
                         for k in ("wg", "wu", "wd")}}
        out, stats = gf._feed_forward(toy_cfg(experts_held=held), part, u,
                                      None)
        assert int(stats["experts_touched"]) <= 2
        np.testing.assert_allclose(np.asarray(out), block(part, held),
                                   atol=TOL, rtol=0)
        total += np.asarray(out) - shared
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_a_share_through_the_engine_equals_the_reference_given_the_share(rng):
    """Four of sixteen experts held: prefill and decode through the cache
    equal the reference given the same share."""
    held = (1, 6, 7, 12)
    whole = toy_model()
    cfg = toy_cfg(experts_held=held)
    params = dict(whole.params, layers=[
        {k: (v[jnp.asarray(held)] if k in ("wg", "wu", "wd") and v.ndim == 3
             else v) for k, v in lp.items()} for lp in whole.params["layers"]])
    eng = _engine(gf.Glm5FlashLM(cfg, params=params), slots=2)
    req = eng.submit(list(rng.randint(0, 96, 70)), 20)
    eng.run()
    assert _served_against_reference(eng, req, experts_held=held) < TOL
    assert _served_against_reference(eng, req) > 100 * TOL   # not the whole


# -- (f) a lower precision fails ----------------------------------------------------


@pytest.mark.parametrize("what", ["rows", "index", "maps", "state"])
def test_a_lower_precision_fails(what, rng, monkeypatch):
    """``TOL`` is tight enough to tell: the latent rows, the index keys or
    the residual maps kept at bfloat16's precision, or the recurrent state
    rounded to bfloat16 after every decode step, put the served logits
    outside it."""
    over = {"rows": dict(row_dtype="bfloat16"),
            "index": dict(index_dtype="bfloat16"),
            "maps": dict(maps_dtype="bfloat16"), "state": {}}[what]
    model = toy_model(**over)
    if what != "state":     # the prefill keeps rows, keys and maps too
        seq = rng.randint(0, 96, 150)
        logits, _ = _prefill(model, seq)
        err = np.abs(np.asarray(logits[0, :150])
                     - reference_rows(model, seq, np.arange(150))).max()
        assert err > 10 * TOL, err
        return
    from paddle_tpu.ops.pallas_kernels import kda

    step = kda.kda_state_step_xla

    def rounded(*a, **kw):
        o, s = step(*a, **kw)
        return o, s.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(kda, "kda_state_step_xla", rounded)
    eng = _engine(model, slots=1)
    req = eng.submit(list(rng.randint(0, 96, 120)), 24)
    eng.run()
    assert _served_against_reference(eng, req) > 10 * TOL


# -- (g) what the cache refuses -------------------------------------------------------


def test_the_cache_groups_and_what_the_index_holds(toy):
    eng = _engine(toy)
    ops = eng.cache_ops
    assert [(g.name, g.kind, g.layers) for g in ops.groups] == [
        ("latent_sparse", LATENT, (1, 3)), ("state", STATE, (0, 2))]
    assert ops.row_values == ops.rank == 16 and ops.rope == 0
    state = eng._cache
    assert state["ik"].shape == (2, 40, 4 * 16)      # 4 blocks a page
    assert state["it"].shape == (2, 3, 3, 16)
    assert ops.index_bytes(state) == (2 * 160 * 16 + 2 * 3 * 3 * 16) * 4
    assert ops.page_table_len == 16 + 1 + 1          # pages, state, slot
    assert [p.name for p in eng.pools] == ["latent_sparse"]


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache_pages=8), "the prefix cache"),
    (dict(kv_dtype="int8"), "the int8 KV pool"),
    (dict(paged=False), "the contiguous layout")])
def test_what_this_cache_cannot_do_is_refused_at_construction(toy, kw, what):
    with pytest.raises(ValueError, match=what):
        _engine(toy, **kw)


def test_an_index_needs_one_group_of_pages_and_whole_blocks_a_tile():
    full = CacheGroup("a", (0,), None, 8, LATENT)
    ring = CacheGroup("b", (1,), 16, 8, LATENT)
    with pytest.raises(ValueError, match="ONE latent group of pages"):
        LatentPagedCache(2, 16, 0, 2, 64, 16, 8, groups=[full, ring],
                         index=(4, 8, 4))
    with pytest.raises(ValueError, match="whole blocks"):
        LatentPagedCache(1, 16, 0, 2, 64, 16, 8, groups=[full],
                         index=(3, 8, 4))
    with pytest.raises(ValueError):
        _index_cache().export_pages(_index_cache().init_state(), [0])

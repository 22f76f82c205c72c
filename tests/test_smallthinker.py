"""SmallThinker's block through the serving stack, against its plain
float32 reference (``grid/reference/smallthinker.py``), at a toy size on
the CPU: 4 layers in the published (global, window, window, window)
pattern, d 64, 4 query / 2 KV heads of 16, 8 experts top-3 of width 32,
window 8, page 4. LOGITS are compared, never sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (grouped matmul over sorted rows
against a dense loop over experts, online softmax against a plain one, a
ring against a full context): the worst logit difference read was 2.5e-6 on
logits of standard deviation 0.93. ``TOL`` = 5e-5 is twenty times that and
far under what a lower precision gives: in bfloat16 the combine weights
move a logit by 4.4e-3, the attention softmax by 9.0e-3 and the router's
logits (which flip a chosen expert) by 0.39
(``test_a_lower_precision_fails`` asks for ten times ``TOL`` of each), so
none of them can hide inside it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import smallthinker as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import smallthinker as st
from paddle_tpu.ops import attention_ops, moe_ops
from paddle_tpu.ops.pallas_kernels import paged_attention as pa

TOL = 5e-5
PUBLISHED = {  # the toy under the published config's own keys
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_num_active_primary_experts": 3, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "rope_theta": 1.5e6, "rms_norm_eps": 1e-6}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=4, d_model=64, n_head=4, n_kv_head=2,
              d_head=16, n_expert=8, top_k=3, d_expert=32, window=8,
              rope_layout=[0, 1, 1, 1], window_layout=[0, 1, 1, 1],
              max_seq=64, dtype="float32")
    kw.update(over)
    return st.SmallThinkerConfig(**kw)


def toy_model():
    """Seeded weights, scaled up from the 0.02 a real width wants so that
    attention and routing are decisive at d = 64."""
    cfg = toy_cfg()
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim > 1 else a, st.init_params(cfg, 3))
    return st.SmallThinkerLM(cfg, params=params)


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_rows(model, seq, rows):
    return np.asarray(ref.forward(model.params, PUBLISHED,
                                  np.asarray(seq, np.int32), rows=rows))


# -- (a) prefill against the reference's full forward -------------------------


@pytest.mark.parametrize("n", [5, 8, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Below, at and well beyond the window (23 = nearly three windows),
    padded to a bucket of 32."""
    seq = rng.randint(0, 96, n)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq
    logits, kvs = toy.prefill(toy.params, jnp.asarray(toks),
                              jnp.asarray([n], jnp.int32))
    want = reference_rows(toy, seq, np.arange(n))
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    last, _ = toy.prefill_last(toy.params, jnp.asarray(toks),
                               jnp.asarray([n], jnp.int32))
    np.testing.assert_allclose(np.asarray(last[0]), want[-1], atol=TOL,
                               rtol=0)
    assert len(kvs) == 4 and kvs[0][0].shape == (1, 32, 2, 16)


@pytest.mark.parametrize("what", ["router", "combine", "softmax"])
def test_a_lower_precision_fails(toy, what, rng, monkeypatch):
    """``TOL`` is tight enough to tell: each of the three computed in
    bfloat16 on the served path puts the prefill outside it."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if what == "router":
        real = moe_ops.route_topk

        def low(h, wr, k):
            idx, w = real(bf16(h), bf16(wr), k)
            return idx, w
        monkeypatch.setattr(moe_ops, "route_topk", low)
    elif what == "combine":
        real = moe_ops.expert_layer

        def low(u, idx, w, *a, **kw):
            return real(u, idx, bf16(w), *a, **kw)
        monkeypatch.setattr(moe_ops, "expert_layer", low)
    else:
        real = jax.nn.softmax
        monkeypatch.setattr(
            attention_ops.jax.nn, "softmax",
            lambda x, axis=-1: bf16(real(bf16(x), axis=axis)))
    n = 23
    seq = rng.randint(0, 96, n)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq
    logits, _ = toy.prefill(toy.params, jnp.asarray(toks),
                            jnp.asarray([n], jnp.int32))
    monkeypatch.undo()
    err = np.abs(np.asarray(logits[0, :n])
                 - reference_rows(toy, seq, np.arange(n))).max()
    assert err > 10 * TOL, err


def test_windowed_prefill_attention_in_blocks(rng):
    """Beyond the window the queries go in blocks against their band; the
    result is the masked S x S softmax, which the blocks never build."""
    s, hq, hkv, d, w = 64, 4, 2, 16, 8
    q = jnp.asarray(rng.randn(s, hq, d).astype("float32"))
    k = jnp.asarray(rng.randn(s, hkv, d).astype("float32"))
    v = jnp.asarray(rng.randn(s, hkv, d).astype("float32"))
    got = attention_ops.windowed_causal_attention(q, k, v, w, 0.25,
                                                  block_q=16)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    ok = (j <= i) & (i - j < w)
    kr, vr = np.repeat(k, 2, axis=1), np.repeat(v, 2, axis=1)
    sc = np.einsum("qhd,khd->hqk", q, kr) * 0.25
    sc = np.where(ok[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), vr)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=0)
    # at S <= window it is the causal attention
    short = attention_ops.windowed_causal_attention(q[:8], k[:8], v[:8], w,
                                                    0.25)
    np.testing.assert_allclose(np.asarray(short), want[:8], atol=2e-6,
                               rtol=0)


# -- (b) prefill, then decoding through the grouped paged cache ---------------


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=4, max_seq=64, prompt_buckets=(8, 16, 32),
               collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_groups_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: one stays inside the window, one crosses it in the prompt,
    one decodes 44 positions past a prompt of 5, which wraps the 8-row ring
    five times. Every emitted token's logits row equals the reference's
    full forward over the same tokens; by the gather path and by the
    grouped-query kernel (interpreted)."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "paged")
            plan = [(rng.randint(0, 96, 3), 4), (rng.randint(0, 96, 19), 12),
                    (rng.randint(0, 96, 5), 44)]
            reqs = [eng.submit(list(p), m) for p, m in plan]
            peak = {p.name: 0 for p in eng.pools}
            while not eng.scheduler.idle():
                eng.step()
                for p in eng.pools:
                    peak[p.name] = max(peak[p.name], p.num_used)
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want = reference_rows(toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
            # (e) a ring never holds more than window / page_size pages a
            # slot, whatever the context; both pools balance and end empty
            assert peak["window"] <= 3 * (8 // 4)
            assert peak["global"] == 2 + 8 + 13
            assert eng.page_accounting_ok()
            assert [p.num_used for p in eng.pools] == [0, 0]
    finally:
        set_flag("paged_attention_kernel", "auto")


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_a_retired_slot_reads_no_row_of_either_group(toy, kernel, rng,
                                                      attention_spy):
    """A request whose context (19 + 3) is past the 8-row window retires
    from slot 0 while a short one decodes on in slot 1: from then on slot
    0's length is 0 in the global layer AND in the window layers (the clamp
    to the ring comes after, so 0 stays 0), and slot 1's logits are those
    of a run in which slot 0 was never used."""
    leaver = (list(rng.randint(0, 96, 19)), 3)
    stayer = (list(rng.randint(0, 96, 4)), 14)

    def drive(stream):
        with _engine(toy) as eng:
            reqs = [eng.submit(p, m) for p, m in stream]
            eng.run()
            assert eng.page_accounting_ok()
            return [np.stack(eng.captured_logits(r)) for r in reqs]

    set_flag("paged_attention_kernel", kernel)
    try:
        mixed = drive([leaver, stayer])
        calls = attention_spy()
        alone = drive([stayer])
    finally:
        set_flag("paged_attention_kernel", "auto")
    assert all((rows <= kept[:, None]).all() for _, kept, rows in calls)
    retired = [rows[1, 0] for active, kept, rows in calls
               if active[1] and not active[0] and kept[0] > 8]
    # slot 1 alive beside the retired slot 0, in every one of the 4 layers;
    # its own rows: the whole context (global) or the ring's 8 (window)
    assert len(retired) >= 4 * 8 and max(retired) > 8 and 8 in retired
    np.testing.assert_allclose(mixed[1], alone[0], atol=TOL, rtol=0)


def test_decode_counts_the_experts_it_touched(toy):
    from paddle_tpu.serving import metrics as sm

    n0, s0 = sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_EXPERTS_TOUCHED.sum
    with _engine(toy, collect_logits=False) as eng:
        eng.submit([1, 2, 3], 5)
        eng.run()
    steps = 4                      # the first token comes from the prefill
    assert sm.MOE_EXPERTS_TOUCHED.count - n0 == steps * 4
    # one live slot: its 3 experts a layer, never the idle slots' rows
    assert sm.MOE_EXPERTS_TOUCHED.sum - s0 == steps * 4 * 3
    assert sm.MOE_MAX_EXPERT_ROWS.count >= steps * 4


# -- (c) the kernel against the gather path, grouped queries -------------------


@pytest.mark.parametrize("g,d", [(2, 16), (7, 128)])
def test_grouped_query_kernel_equals_the_gather(g, d, rng):
    slots, h, ps, pps, npg = 3, 2, 4, 6, 20
    pt = rng.permutation(npg)[:slots * pps].reshape(slots, pps).astype("int32")
    ctx = np.array([1, 9, 24], np.int32)
    kp = rng.randn(2, npg * ps, h * d).astype("float32")
    vp = rng.randn(2, npg * ps, h * d).astype("float32")
    q = rng.randn(slots, h * g, d).astype("float32")
    sm_scale = 1.0 / np.sqrt(d)
    for block in (1, 4, None):
        got = pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(ctx), page_size=ps, layer=1, sm_scale=sm_scale,
            block_pages=block, interpret=True)
        want = pa.gather_reference(
            jnp.asarray(q), jnp.asarray(kp[1]), jnp.asarray(vp[1]),
            jnp.asarray(pt), jnp.asarray(ctx), ps, sm_scale=sm_scale)
        assert got.shape == (slots, h * g, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)


def test_the_gate_knows_grouped_queries():
    bf16 = jnp.bfloat16
    assert pa.paged_attention_gate(bf16, 4, 128, 16, q_per_kv=7) is None
    why = pa.paged_attention_gate(bf16, 8, 64, 16, q_per_kv=2)
    assert why is not None and "d_head=64" in why
    assert pa.paged_attention_gate(bf16, 8, 64, 16, q_per_kv=1) is None
    assert pa.paged_attention_gate(jnp.float32, 2, 16, 4, interpret=True,
                                   q_per_kv=2) is None


# -- (d) the expert layer against the dense loop -------------------------------


def _dense_experts(u, idx, w, wg, wu, wd):
    n, e = u.shape[0], wg.shape[0]
    full = np.zeros((n, e), np.float64)
    np.put_along_axis(full, np.asarray(idx), np.asarray(w, np.float64), 1)
    y = np.zeros((n, wd.shape[2]), np.float64)
    for j in range(e):
        a = np.maximum(u @ wg[j], 0) * (u @ wu[j])
        y += full[:, j:j + 1] * (a @ wd[j])
    return y


def test_expert_layer_equals_the_dense_loop(rng):
    """Routing as the router gives it, then two chosen by hand: expert 2
    receives EVERY row and expert 5 none."""
    n, d, f, e, k = 11, 16, 8, 6, 3
    u = rng.randn(n, d).astype("float32")
    wg, wu = (rng.randn(e, d, f).astype("float32") for _ in range(2))
    wd = rng.randn(e, f, d).astype("float32")
    wr = rng.randn(d, e).astype("float32")
    idx, w = moe_ops.route_topk(jnp.asarray(u), jnp.asarray(wr), k)
    logits = u @ wr
    want_idx = np.argsort(-logits, axis=1)[:, :k]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(want_idx, 1))
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-6)
    full = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    kept = np.take_along_axis(full, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w),
                               kept / kept.sum(1, keepdims=True), atol=1e-6)
    forced = np.stack([np.full(n, 2), rng.choice([0, 1], n),
                       rng.choice([3, 4], n)], 1).astype("int32")
    for ids in (np.asarray(idx), forced):
        y, stats = moe_ops.expert_layer(
            jnp.asarray(u), jnp.asarray(ids), w, jnp.asarray(wg),
            jnp.asarray(wu), jnp.asarray(wd))
        np.testing.assert_allclose(
            np.asarray(y), _dense_experts(u, ids, w, wg, wu, wd),
            atol=2e-4, rtol=1e-5)
        sizes = np.bincount(ids.reshape(-1), minlength=e)
        assert int(stats["experts_touched"]) == (sizes > 0).sum()
        assert int(stats["max_expert_rows"]) == sizes.max()
    assert int(stats["max_expert_rows"]) == n   # expert 2 took every row


def test_expert_shares_add_up_and_unused_rows_cost_nothing(rng):
    """A chip's share: the experts ``held`` here, routed over all of them.
    The shares of two halves add up to the whole layer; rows marked unused
    come back zero and are counted by no expert."""
    n, d, f, e, k = 9, 16, 8, 8, 3
    u = jnp.asarray(rng.randn(n, d).astype("float32"))
    wg, wu = (jnp.asarray(rng.randn(e, d, f).astype("float32"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d).astype("float32"))
    idx, w = moe_ops.route_topk(u, jnp.asarray(
        rng.randn(d, e).astype("float32")), k)
    whole, _ = moe_ops.expert_layer(u, idx, w, wg, wu, wd)
    parts = []
    for held in ([0, 2, 4, 6], [1, 3, 5, 7]):
        h = np.asarray(held)
        part, stats = moe_ops.expert_layer(u, idx, w, wg[h], wu[h], wd[h],
                                           n_expert=e, held=held)
        assert int(stats["experts_touched"]) <= 4
        parts.append(np.asarray(part))
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(whole),
                               atol=2e-4, rtol=1e-5)
    live = np.arange(n) % 2 == 0
    y, stats = moe_ops.expert_layer(u, idx, w, wg, wu, wd,
                                    row_valid=jnp.asarray(live))
    assert np.all(np.asarray(y)[~live] == 0)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(whole)[live],
                               atol=2e-4, rtol=1e-5)
    assert int(stats["max_expert_rows"]) <= live.sum()
    with pytest.raises(ValueError, match="say which"):
        moe_ops.expert_layer(u, idx, w, wg[:4], wu[:4], wd[:4], n_expert=e)


# -- (e) admission over two groups ---------------------------------------------


def test_admission_waits_for_the_global_group_with_a_slot_free(toy):
    """The global pool holds 12 pages: a request of 40 positions takes 10
    of them and its whole ring (2 pages); the next, of 20, needs 5 and
    waits though two slots are free, takes nothing from the window pool
    meanwhile, and is admitted when the first retires."""
    from paddle_tpu.serving import metrics as sm

    with _engine(toy, collect_logits=False, num_pages=12) as eng:
        assert [p.num_pages for p in eng.pools] == [12, 3 * 2]
        first = eng.submit(list(range(1, 9)), 32)
        second = eng.submit(list(range(1, 9)), 12)
        blocked0 = sm.ADMISSION_BLOCKED.value
        eng.step()
        assert first.state == "running" and second.state == "queued"
        assert eng.scheduler.occupancy == 1
        assert [p.num_used for p in eng.pools] == [10, 2]
        assert first.group_pages[1] and len(first.group_pages[1]) == 2
        assert sm.ADMISSION_BLOCKED.value == blocked0 + 1
        assert eng.page_accounting_ok()
        eng.run()
        assert first.state == second.state == "finished"
        assert len(second.tokens_out) == 12
        assert [p.num_used for p in eng.pools] == [0, 0]
        assert eng.stats()["pages_by_group"] == {"global": [0, 12],
                                                 "window": [0, 6]}
    with pytest.raises(ValueError, match="global pool only has 12"):
        with _engine(toy, num_pages=12) as eng:
            eng.submit(list(range(1, 30)), 30)


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
    (dict(group_pages={"ring": 4}), "group_pages names"),
])
def test_what_two_groups_cannot_do_is_refused_at_construction(toy, kw, what):
    with pytest.raises(ValueError, match=what):
        _engine(toy, **kw)


def test_page_export_is_refused_over_two_groups(toy):
    with _engine(toy) as eng:
        with pytest.raises(ValueError, match="page export"):
            eng.cache_ops.export_pages(eng._cache, [0])


def test_one_group_is_the_same_cache(rng):
    """GPT-2's case: no groups named, the state keys and geometry a
    one-group cache always had."""
    from paddle_tpu.serving.kv_cache import PagedKVCache

    c = PagedKVCache(3, 2, 8, slots=2, max_ctx=32, page_size=4, num_pages=9)
    state = c.init_state()
    assert sorted(state) == ["k", "pt", "v"]
    assert state["k"].shape == (3, 36, 16) and state["pt"].shape == (2, 8)
    assert [g.name for g in c.groups] == ["global"]
    assert c.pages_needed(0, 13) == 4
    assert np.array_equal(c.prompt_dest([5, 6]), c.prompt_dest_groups([[5, 6]]))
    from paddle_tpu.serving.kv_cache import CacheGroup
    # a layer no group names keeps nothing (PR 65: a feed-forward that is a
    # layer of its own); asking the cache for it is an error, and so is a
    # group that names a layer the model has not
    gap = PagedKVCache(3, 2, 8, 2, 32, 4, 9,
                       groups=[CacheGroup("global", (0, 2), None, 9)])
    assert gap.init_state()["k"].shape[0] == 2
    with pytest.raises(KeyError):
        gap.context(gap.init_state(), 1)
    with pytest.raises(ValueError, match="name layers of 0..2"):
        PagedKVCache(3, 2, 8, 2, 32, 4, 9,
                     groups=[CacheGroup("global", (0, 3), None, 9)])

"""Laguna-S-2.1's block (query heads by layer type over the same KV heads,
a head-wise gate, a YaRN table with a partial rotation beside a plain one,
a dense first layer, a scaled softmax router with a shared expert) through
the serving stack, against its plain float32 reference
(``grid/reference/laguna.py``), at a toy size on the CPU: layers full
(dense), sliding, sliding, sliding, full; d 64, two KV heads of 16 lanes
under 4 query heads on full layers and 6 on sliding ones (two query groups:
2 and 3 a KV head), window 8, 8 experts top-3 of width 32 and one shared,
YaRN factor 128 over 64 positions, page 4. LOGITS are compared, never
sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (grouped matmul over sorted rows
against a dense loop over experts, online softmax over ring rows against a
plain one over positions): the worst logit difference read was 5.4e-6 in
prefill and 7.5e-6 through the cache (the kernel, interpreted) on logits
of standard deviation 0.96. ``TOL`` = 5e-5 is over six times that and far
under what a lower precision gives: in bfloat16 the router's inputs move a
logit by 8.4e-3, the combine weights by 6.0e-3 and the attention softmax by
3.2e-2 (``test_a_lower_precision_fails`` asks for ten times ``TOL`` of
each), so none of them can hide inside it.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import laguna as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import laguna as lg
from paddle_tpu.ops import attention_ops, moe_ops
from paddle_tpu.ops.pallas_kernels import paged_attention as pa

TOL = 5e-5
FULL, SLIDING = lg.FULL, lg.SLIDING
ROPE = {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 64, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}
TYPES = [FULL, SLIDING, SLIDING, SLIDING, FULL]
HEADS = [4, 6, 6, 6, 4]
PUBLISHED = {  # the toy under the published config's own keys
    "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads_per_layer": HEADS, "layer_types": TYPES,
    "sliding_window": 8, "rope_parameters": ROPE, "num_experts_per_tok": 3,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=5, d_model=64, n_head=HEADS,
              n_kv_head=2, d_head=16, layer_types=TYPES, window=8, rope=ROPE,
              d_dense=128, dense_layers=[0], n_expert=8, top_k=3,
              d_expert=32, d_shared=32, routed_scale=2.5, max_seq=64,
              dtype="float32")
    kw.update(over)
    return lg.LagunaConfig(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention, the gate and routing are decisive at d = 64."""
    return jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim > 1 else a, params)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return lg.LagunaLM(cfg, params=_scaled(lg.init_params(cfg, 3)))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_rows(model, seq, rows, **over):
    return np.asarray(ref.forward(model.params, dict(PUBLISHED, **over),
                                  np.asarray(seq, np.int32), rows=rows))


def _prefill(model, seq, bucket=32):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return model.prefill(model.params, jnp.asarray(toks),
                         jnp.asarray([len(seq)], jnp.int32))


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=4, max_seq=64, prompt_buckets=(8, 16, 32),
               num_pages=40, collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


# -- (a) prefill against the reference's full forward ---------------------------


@pytest.mark.parametrize("n", [5, 8, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Inside the window (5), at it (8) and past it (23: the banded
    prefill of the sliding layers, three query groups of 8)."""
    seq = rng.randint(0, 96, n)
    logits, kvs = _prefill(toy, seq)
    want = reference_rows(toy, seq, np.arange(n))
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq
    last, _ = toy.prefill_last(toy.params, jnp.asarray(toks),
                               jnp.asarray([n], jnp.int32))
    np.testing.assert_allclose(np.asarray(last[0]), want[-1], atol=TOL,
                               rtol=0)
    # what the cache keeps: K and V of the TWO KV heads in every layer,
    # whatever the layer's query heads
    assert len(kvs) == 5
    assert all(k.shape == v.shape == (1, 32, 2, 16) for k, v in kvs)


@pytest.mark.parametrize("what", ["router", "combine", "softmax"])
def test_a_lower_precision_fails(toy, what, rng, monkeypatch):
    """``TOL`` is tight enough to tell: each of the three computed in
    bfloat16 on the served path puts the prefill outside it."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if what == "router":
        real = moe_ops.route_topk
        monkeypatch.setattr(moe_ops, "route_topk",
                            lambda h, wr, k: real(bf16(h), bf16(wr), k))
    elif what == "combine":
        real = moe_ops.expert_layer
        monkeypatch.setattr(
            moe_ops, "expert_layer",
            lambda u, idx, w, *a, **kw: real(u, idx, bf16(w), *a, **kw))
    else:
        real = jax.nn.softmax
        monkeypatch.setattr(
            attention_ops.jax.nn, "softmax",
            lambda x, axis=-1: bf16(real(bf16(x), axis=axis)))
    seq = rng.randint(0, 96, 23)
    logits, _ = _prefill(toy, seq)
    monkeypatch.undo()
    err = np.abs(np.asarray(logits[0, :23])
                 - reference_rows(toy, seq, np.arange(23))).max()
    assert err > 10 * TOL, err


# -- (b) prefill, then decoding through the two cache groups --------------------


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_groups_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: a prompt inside the window that decodes past it and round
    the ring more than twice (3 + 24 positions over a ring of 8), one past
    the window from the start (19), one that stays inside it. Every
    emitted token's logits row equals the reference's full forward over
    the same tokens, by the gather path and by the paged kernel
    (interpreted), which folds 2 query heads a KV head in the ``global``
    group and 3 in the ``window`` group."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "paged")
            assert eng.cache_ops.q_per_kv == {"global": 2, "window": 3}
            assert [(p.name, p.num_pages) for p in eng.pools] == [
                ("global", 40), ("window", 6)]
            plan = [(rng.randint(0, 96, 3), 24), (rng.randint(0, 96, 19), 12),
                    (rng.randint(0, 96, 2), 4)]
            reqs = [eng.submit(list(p), m) for p, m in plan]
            most = 0
            while not eng.scheduler.idle():
                eng.step()
                most = max(most, eng.pools[1].num_used)
                assert eng.page_accounting_ok()
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want = reference_rows(toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
            assert most <= 3 * 2            # a ring is two pages a slot
            assert all(p.num_used == 0 for p in eng.pools)
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_decode_counts_rows_read_by_group_and_the_held_load(rng):
    """One live slot: each step's ``attn_rows_read.global`` is its context
    and ``.window`` that under the window, never the idle slots' rows; the
    expert counters see the four expert layers."""
    from paddle_tpu.serving import metrics as sm

    model = toy_model(experts_held=(0, 1, 2, 3))
    glob, win = sm.attn_rows_read("global"), sm.attn_rows_read("window")
    g0, w0 = (glob.count, glob.sum), (win.count, win.sum)
    t0, p0 = sm.MOE_EXPERTS_TOUCHED.count, (sm.MOE_HELD_PAIRS.count,
                                            sm.MOE_HELD_PAIRS.sum)
    with _engine(model, collect_logits=False) as eng:
        eng.submit(list(rng.randint(0, 96, 5)), 7)
        eng.run()
    steps = 6                      # the first token comes from the prefill
    ctx = [5 + j + 1 for j in range(steps)]    # rows read: the new one too
    assert (glob.count - g0[0], win.count - w0[0]) == (steps, steps)
    assert glob.sum - g0[1] == sum(ctx)
    assert win.sum - w0[1] == sum(min(c, 8) for c in ctx)
    assert sm.MOE_EXPERTS_TOUCHED.count - t0 == steps * 4
    assert sm.MOE_HELD_PAIRS.count - p0[0] == steps * 4
    assert 0 <= sm.MOE_HELD_PAIRS.sum - p0[1] <= steps * 4 * 3


# -- (c) the kernel against the gather path at both query-group sizes -----------


@pytest.mark.parametrize("g,d", [(2, 16), (3, 16), (6, 128), (9, 128)])
def test_grouped_query_kernel_equals_the_gather(g, d, rng):
    """The toy's two groups, and the published ones: 6 query heads a KV
    head leave a quarter of the 8-row tile of states empty, 9 take two."""
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


def test_the_gate_is_asked_for_each_groups_query_heads():
    """The cache takes the query heads a KV head by group; one value is
    every group's. A group the kernel's gate refuses keeps the whole cache
    on the gather path, and the engine says which rule."""
    from paddle_tpu.serving.kv_cache import CacheGroup, PagedKVCache

    groups = [CacheGroup("global", (0, 4), None, 64),
              CacheGroup("window", (1, 2, 3), 512, 32)]
    cache = PagedKVCache(5, 8, 128, 2, 1024, 16, 64, dtype=jnp.bfloat16,
                         groups=groups, q_per_kv={"global": 6, "window": 9})
    assert cache.q_per_kv == {"global": 6, "window": 9}
    assert cache._kernel_gate(interpret=False) is None
    one = PagedKVCache(5, 8, 128, 2, 1024, 16, 64, dtype=jnp.bfloat16,
                       groups=groups, q_per_kv=7)
    assert one.q_per_kv == {"global": 7, "window": 7}
    # heads of 64 lanes: one query head a KV head passes, a grouped one
    # is refused, in whichever group it sits
    narrow = PagedKVCache(5, 8, 64, 2, 1024, 16, 64, dtype=jnp.bfloat16,
                          groups=groups, q_per_kv={"global": 1, "window": 2})
    assert "d_head=64" in narrow._kernel_gate(interpret=False)
    with pytest.raises(ValueError, match="q_per_kv names"):
        PagedKVCache(5, 8, 128, 2, 1024, 16, 64, groups=groups,
                     q_per_kv={"global": 6})


def test_a_group_has_one_number_of_query_heads(toy):
    """The engine derives a group's query heads a KV head from its layers'
    ``n_head``: layers of one group that disagree are refused."""
    bad = toy_cfg(n_head=[4, 6, 6, 4, 4])
    with pytest.raises(ValueError, match="cache group 'window'"):
        _engine(lg.LagunaLM(bad, params=toy.params))


# -- (d) the shares add up ------------------------------------------------------


def test_two_shares_and_one_shared_expert_add_up_to_the_whole_layer(toy, rng):
    """The deployment's arithmetic at toy size: two chips hold four routed
    experts each (0-3 and 4-7), both have the router and the shared
    expert. The routed parts of the two shares, with the shared expert
    counted ONCE, add up to the uncut reference's whole layer; and the
    reference given a share agrees with the program given it."""
    cfg, lp = toy.cfg, toy.params["layers"][2]
    x = jnp.asarray(rng.randn(9, cfg.d_model).astype("float32"))
    whole = np.asarray(ref._sparse(lp, x, 3, 2.5, 1e-6, tuple(range(8))))
    shared = np.asarray(blocks.swiglu(blocks.rms_norm(x, lp["g2"], 1e-6),
                                      lp["sg"], lp["su"], lp["sd"]))
    total = np.asarray(x) + shared
    pairs = 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        part = {**lp, **{k: lp[k][np.asarray(held)] for k in ("wg", "wu",
                                                              "wd")}}
        out, stats = lg._feed_forward(toy_cfg(experts_held=held), part, x,
                                      None)
        pairs += int(stats["held_pairs"])
        total += np.asarray(out) - np.asarray(x) - shared
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ref._sparse(part, x, 3, 2.5, 1e-6, held)),
            atol=TOL, rtol=0)
    assert pairs == 9 * 3              # every pair lands on one share
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_a_share_through_the_engine_equals_the_reference_given_the_share(rng):
    """Four of eight experts held: prefill past the window and decode
    through both groups equal the reference given the same share."""
    held = (0, 1, 2, 3)
    model = toy_model(experts_held=held)
    assert model.params["layers"][1]["wg"].shape[0] == 4
    assert model.params["layers"][1]["wr"].shape[1] == 8
    with _engine(model) as eng:
        prompt = rng.randint(0, 96, 11)
        req = eng.submit(list(prompt), 10)
        eng.run()
        seq = list(prompt) + req.tokens_out[:-1]
        want = reference_rows(model, seq, np.arange(10, 20),
                              experts_held=list(held))
        np.testing.assert_allclose(np.stack(eng.captured_logits(req)), want,
                                   atol=TOL, rtol=0)


def test_the_router_is_the_scaled_renormalised_softmax(rng):
    """``w_e = 2.5 s_e / sum of the chosen s`` with ``s`` the softmax over
    ALL experts, written out by hand."""
    u = rng.randn(7, 16).astype("float32")
    wr = rng.randn(16, 8).astype("float32")
    idx, w = moe_ops.route_topk(jnp.asarray(u), jnp.asarray(wr), 3)
    logits = (u @ wr).astype(np.float64)
    s = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    top = np.argsort(-s, axis=1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(top, 1))
    kept = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(2.5 * np.asarray(w),
                               2.5 * kept / kept.sum(1, keepdims=True),
                               atol=1e-6)


# -- (e) positions and the gate, by hand ----------------------------------------


def test_yarn_table_at_the_published_values():
    """Over the 64 rotary lanes of a full layer (32 pairs) at theta
    500,000, factor 128 over 8,192 positions, ``beta_fast`` 32 and
    ``beta_slow`` 1: the correction range is floor/ceil of 9.04 and 17.49,
    so pairs 0-9 keep their frequency, pairs 18-31 run at 1/128 of it and
    the pairs between ramp linearly; the attention factor is 0.1 ln 128 +
    1. A sliding layer's table is the plain one over all 128 lanes."""
    full = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5}
    f, factor = ref.rope_table(128, full)
    assert f.shape == (32,)
    base = 500000.0 ** (-np.arange(32) / 32.0)
    lo = 64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    hi = 64 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(lo), math.ceil(hi)) == (9, 18)
    np.testing.assert_allclose(f[:10], base[:10], rtol=1e-12)
    np.testing.assert_allclose(f[18:], base[18:] / 128, rtol=1e-12)
    ramp = (np.arange(10, 18) - 9) / 9.0
    np.testing.assert_allclose(
        f[10:18], base[10:18] * (1 - ramp) + base[10:18] / 128 * ramp,
        rtol=1e-12)
    assert factor == 1.4852030263919618
    assert abs(factor - (0.1 * math.log(128) + 1)) < 1e-12
    del full["attention_factor"]
    assert abs(ref.rope_table(128, full)[1] - factor) < 1e-12
    f, factor = ref.rope_table(128, ROPE[SLIDING])
    np.testing.assert_allclose(f, 10000.0 ** (-np.arange(64) / 64.0))
    assert factor == 1.0


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_rotation_by_hand(toy, kind, rng):
    """A full layer's head: lanes 0-7 rotate (lane i with lane i + 4) with
    the attention factor in cos and sin, lanes 8-15 are untouched. A
    sliding layer's: all 16 lanes rotate, factor 1. Program and reference
    alike."""
    table = toy.cfg.rope[kind]
    inv_freq, factor = table
    half = len(inv_freq)
    assert (half, factor) == ((4, 1.4852030263919618) if kind == FULL
                              else (8, 1.0))
    x = rng.randn(3, 2, 16).astype("float32")
    pos = np.array([0, 5, 40])
    want = x.copy()
    for s, p in enumerate(pos):
        for i in range(half):
            c = math.cos(p * inv_freq[i]) * factor
            sn = math.sin(p * inv_freq[i]) * factor
            want[s, :, i] = x[s, :, i] * c - x[s, :, i + half] * sn
            want[s, :, i + half] = x[s, :, i + half] * c + x[s, :, i] * sn
    got = np.asarray(blocks.rope_lanes(jnp.asarray(x), jnp.asarray(pos),
                                       table))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        np.asarray(ref._rope(jnp.asarray(x), jnp.asarray(pos),
                             tuple(inv_freq), factor)), want, atol=1e-5,
        rtol=0)
    if kind == FULL:
        assert np.array_equal(got[..., 8:], x[..., 8:])
        # position 0 is no rotation, and the factor still scales the lanes
        np.testing.assert_allclose(got[0, :, :8], x[0, :, :8] * factor,
                                   atol=1e-6)


def test_a_closed_gate_halves_attention(toy, rng):
    """``Wgamma = 0`` makes every gamma 0.5: attention's contribution to
    the residual stream is half of what the ungated heads give."""
    lp = toy.params["layers"][1]
    h = jnp.asarray(rng.randn(5, 64).astype("float32"))
    o = jnp.asarray(rng.randn(5, 6, 16).astype("float32"))
    half = blocks.gated({**lp, "wgam": jnp.zeros_like(lp["wgam"])}, h, o)
    np.testing.assert_allclose(np.asarray(half),
                               0.5 * np.asarray(o).reshape(5, -1), atol=1e-7)
    gamma = 1 / (1 + np.exp(-(np.asarray(h) @ np.asarray(lp["wgam"]))))
    np.testing.assert_allclose(
        np.asarray(blocks.gated(lp, h, o)),
        (np.asarray(o) * gamma[:, :, None]).reshape(5, -1), atol=1e-6)
    # through the whole model: the reference, given the same zero gate,
    # still agrees, and differs from the gated one
    seq = rng.randint(0, 96, 12)
    shut = jax.tree_util.tree_map(lambda a: a, toy.params)
    shut["layers"] = [{**p, "wgam": jnp.zeros_like(p["wgam"])}
                      for p in toy.params["layers"]]
    model = lg.LagunaLM(toy.cfg, params=shut)
    got = np.asarray(_prefill(model, seq)[0][0, :12])
    np.testing.assert_allclose(got, reference_rows(model, seq, np.arange(12)),
                               atol=TOL, rtol=0)
    assert np.abs(got - reference_rows(toy, seq, np.arange(12))).max() > 0.01


# -- (f) what the groups cannot do ----------------------------------------------


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
    (dict(group_pages={"ring": 4}), "group_pages names"),
])
def test_what_two_groups_cannot_do_is_refused_at_construction(toy, kw, what):
    with pytest.raises(ValueError, match=what):
        _engine(toy, **kw)

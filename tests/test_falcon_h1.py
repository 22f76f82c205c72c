"""Falcon-H1's decoder (a Mamba-2 mixer AND grouped-query attention in
every block, muP multipliers, a dense SwiGLU) through the serving stack,
against its plain float32 reference (``grid/reference/falcon_h1.py``), at a
toy size on the CPU that keeps every ratio: d 64, 10 query heads over 2 KV
heads of 16 (5 a KV head), an SSM of 4 heads of 8 in 2 groups of state 16
(``N = 2 P``), a convolution of 4 taps over 96 channels, ff 128, three
layers, page 8, the published multipliers. LOGITS are compared, never
sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (the chunk-wise scan against the
recurrence token by token, the paged kernel's online softmax against a
whole one): the worst logit difference read was 4e-6 on logits of standard
deviation 1. ``TOL`` = 5e-5 is ten times that and far under what a part
left out or a multiplier changed gives (0.05 and more).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import falcon_h1 as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import falcon_h1 as fh
from paddle_tpu.serving.kv_cache import KV, STATE, CacheGroup, PagedKVCache

TOL = 5e-5
MUP = {  # Falcon-H1-34B-Instruct's, as published
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845}
PUBLISHED = dict(  # the toy under the published config's own keys
    MUP, hidden_size=64, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, vocab_size=96, intermediate_size=128, mamba_n_heads=4,
    mamba_d_head=8, mamba_n_groups=2, mamba_d_state=16, mamba_d_ssm=32,
    mamba_d_conv=4, mamba_chunk_size=128, num_hidden_layers=3,
    rms_norm_eps=1e-5, rope_theta=1e11)


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=3, d_model=64, n_head=10, n_kv_head=2,
              d_head=16, d_ff=128, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
              ssm_state=16, mup=MUP, max_seq=256, dtype="float32")
    kw.update(over)
    return fh.FalconH1Config(**kw)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return fh.FalconH1LM(cfg, params=fh.init_params(cfg, 3))


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
    cfg = dict(slots=3, page_size=8, max_seq=256,
               prompt_buckets=(8, 32, 192), num_pages=80,
               collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


# -- (a) prefill against the reference's full forward --------------------------

@pytest.mark.parametrize("n", [5, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Both mixers of every layer: the chunk scan under the bucket's
    padding against the recurrence token by token, causal GQA at 5 query
    heads a KV head."""
    seq = rng.randint(0, 96, n)
    logits, kept = _prefill(toy, seq)
    want = reference_rows(toy, seq, np.arange(n))
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    # what the cache is handed, in the contract's order: the layer's rows,
    # then what the prompt leaves in its slot
    for (k, v), (state, tail) in kept:
        assert k.shape == v.shape == (1, 32, 2, 16)
        assert state.shape == (1, 4, 16, 8) and state.dtype == jnp.float32
        assert tail.shape == (1, 3, 96)
    # the tail is the last three inputs of the convolution BELOW the length
    lp = toy.params["layers"][0]
    u = blocks.rms_norm(toy.params["tok_emb"][jnp.asarray(seq)]
                        * MUP["embedding_multiplier"], lp["g1"], 1e-5)
    _, xbc, _ = fh._ssm_in(toy.cfg, lp, u)
    np.testing.assert_allclose(np.asarray(kept[0][1][1][0]),
                               np.asarray(xbc[n - 3:n]), atol=1e-6)


def test_each_branch_adds_a_share_the_comparison_can_see(toy, rng):
    """At the PUBLISHED multipliers the seeded scales leave no branch
    invisible: each of the SSM branch, the attention branch and the MLP is
    between a tenth and the whole of the residual it is added to, and the
    reference with a branch left out moves the logits far beyond ``TOL``."""
    seq = rng.randint(0, 96, 40)
    shares = []
    whole = ref.forward(toy.params, PUBLISHED, np.asarray(seq), shares=shares)
    for ssm, attn, mlp, resid in np.asarray(shares):
        assert 0.1 * resid < min(ssm, attn, mlp) \
            and max(ssm, attn, mlp) < resid
    for branch in ("ssm", "attn"):
        without = ref.forward(toy.params, PUBLISHED, np.asarray(seq),
                              leave_out=branch)
        assert float(jnp.abs(whole - without).max()) > 0.05


MULTIPLIERS = [("embedding_multiplier", None), ("lm_head_multiplier", None),
               ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
               ("attention_in_multiplier", None),
               ("attention_out_multiplier", None), ("key_multiplier", None),
               ("ssm_multipliers", 0), ("ssm_multipliers", 1),
               ("ssm_multipliers", 2), ("ssm_multipliers", 3),
               ("ssm_multipliers", 4), ("mlp_multipliers", 0),
               ("mlp_multipliers", 1)]


@pytest.mark.parametrize("key,index", MULTIPLIERS, ids=[
    k if i is None else "%s[%d]" % (k, i) for k, i in MULTIPLIERS])
def test_each_multiplier_sits_where_the_reference_applies_it(toy, key, index,
                                                             rng):
    """One case a multiplier, fourteen in all: doubled (the SAME weights),
    the served logits move, and they move to where the reference's move
    with the same multiplier doubled."""
    value = MUP[key]
    if index is None:
        changed = 2.0 * value
    else:
        changed = list(value)
        changed[index] = 2.0 * value[index]
    seq = rng.randint(0, 96, 21)
    other = fh.FalconH1LM(toy_cfg(mup=dict(MUP, **{key: changed})),
                          params=toy.params)
    got, _ = _prefill(other, seq)
    base, _ = _prefill(toy, seq)
    assert float(jnp.abs(got - base)[0, :21].max()) > 20 * TOL
    want = reference_rows(toy, seq, np.arange(21), **{key: changed})
    np.testing.assert_allclose(np.asarray(got[0, :21]), want, atol=TOL,
                               rtol=0)


# -- (b) prefill, then decode through the pools and the states -----------------

@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_cache_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: one a prompt past a chunk that is no multiple of 128. Every
    layer's state and tail written by the prefill's scan and advanced a
    token at a time, AND its K and V rows across page boundaries. Every
    emitted token's logits row equals the reference's full forward over
    the same tokens; in plain XLA and by both kernels (interpreted)."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "paged")
            assert eng.cache_ops.state_kernel_mode()[0] == (
                None if kernel == "off" else "interpret")
            assert eng.cache_ops.q_per_kv["global"] == 5
            plan = [(rng.randint(0, 96, 3), 4), (rng.randint(0, 96, 150), 12),
                    (rng.randint(0, 96, 5), 40)]
            reqs = [eng.submit(list(p), m) for p, m in plan]
            peak = 0
            while not eng.scheduler.idle():
                eng.step()
                peak = max(peak, eng.pool.num_used)
                assert eng.page_accounting_ok()
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want = reference_rows(toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
            # the paged group alone holds pages: ceil(7/8), ceil(162/8),
            # ceil(45/8); the state group has none
            assert peak == 1 + 21 + 6
            assert eng.pool.num_used == 0 and len(eng.pools) == 1
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_a_reused_slot_starts_from_nothing_and_an_idle_one_is_untouched(
        toy, rng):
    """ONE slot serves two requests in turn: the second's logits are a
    fresh engine's, bit for bit (the prefill executable zeroes the slot's
    state and tail as it arms it, then writes the prompt's over them: a
    zero state and an empty tail). Meanwhile a slot that holds no request
    keeps what it held, poison included: its state is neither read nor
    written (the kernel, interpreted)."""
    set_flag("paged_attention_kernel", "interpret")
    try:
        a, b = rng.randint(0, 96, 11), rng.randint(0, 96, 2)
        with _engine(toy, slots=2) as eng:
            eng._cache = {**eng._cache,
                          "s.ssm": eng._cache["s.ssm"].at[:, 1].set(1e4),
                          "tail.ssm": eng._cache["tail.ssm"].at[:, 1].set(
                              -7.0)}
            first = eng.submit(list(a), 9)
            eng.run()
            assert first.state == "finished"
            assert np.all(np.asarray(eng._cache["s.ssm"][:, 1]) == 1e4)
            assert np.all(np.asarray(eng._cache["tail.ssm"][:, 1]) == -7.0)
            assert np.abs(np.asarray(eng._cache["s.ssm"][:, 0])).max() > 0
            # a prompt of TWO tokens: its tail's first row is the empty
            # start's zeros, not what the first request left there
            second = eng.submit(list(b), 7)
            eng.run()
            got = np.stack(eng.captured_logits(second))
        with _engine(toy, slots=2) as fresh:
            again = fresh.submit(list(b), 7)
            fresh.run()
            np.testing.assert_array_equal(
                got, np.stack(fresh.captured_logits(again)))
        assert second.tokens_out == again.tokens_out
        want = reference_rows(toy, list(b) + second.tokens_out[:-1],
                              np.arange(1, 8))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_the_decode_stats_count_both_kinds(toy, rng):
    """``state_slots_stepped`` (the live slots) and the cache's
    ``attn_rows_read.global`` ride a decode step's stats to their
    histograms; ``serving/state_pool_bytes`` is set at construction."""
    from paddle_tpu.serving import metrics as sm

    with _engine(toy) as eng:
        # 3 layers x 3 slots x (4 x 16 x 8 state + 3 x 96 tail) float32
        assert sm.STATE_POOL_BYTES.value == 3 * 3 * (512 + 288) * 4
        stepped, rows = sm.STATE_SLOTS_STEPPED, sm.attn_rows_read("global")
        n0, s0, r0 = stepped.count, stepped.sum, rows.sum
        eng.submit(list(rng.randint(0, 96, 10)), 6)
        eng.submit(list(rng.randint(0, 96, 20)), 6)
        eng.run()
        steps = stepped.count - n0
        assert steps >= 5 and stepped.sum - s0 == 2 * 5
        # step j reads 10 + j and 20 + j rows (the prompt and what came)
        assert rows.sum - r0 == sum(11 + j + 21 + j for j in range(5))


# -- (c) one layer in two cache groups -------------------------------------------

def _two_kinds(**kw):
    groups = [CacheGroup("global", (0, 1), None, 8, KV),
              CacheGroup("ssm", (0, 1), None, 0, STATE)]
    args = dict(groups=groups, slot_state=(4, 16, 8, 3, 96),
                recurrence="ssd", q_per_kv=5)
    args.update(kw)
    return PagedKVCache(2, 2, 16, 3, 64, 8, 8, dtype=jnp.float32, **args)


def test_a_layer_in_a_paged_and_a_state_group_arms_writes_and_steps_both(
        rng):
    """``PagedKVCache`` over layers that stand in BOTH a K-and-V group and
    a state group: ``set_page_table`` points the slot at its pages and
    zeroes its state, ``write_prompt`` lands the rows, ``write_slot_state``
    what the prompt leaves, ``write_token``/``decode_attention`` read the
    pages and ``tail_step``/``state_step`` the state, each of the SAME
    layer; the page table has the paged group's entries and one for the
    slot."""
    ops = _two_kinds()
    assert ops.page_table_len == 8 + 1
    assert ops.pages_needed(0, 20) == 3 and len(
        [g for g in ops.groups if g.kind != STATE]) == 1
    cache = ops.init_state()
    assert sorted(cache) == ["k", "pt", "s.ssm", "tail.ssm", "v"]
    cache = {**cache, "s.ssm": cache["s.ssm"] + 5.0,
             "tail.ssm": cache["tail.ssm"] + 5.0}
    dest = jnp.asarray(ops.prompt_dest_groups([[3, 1]], slot=2))
    assert list(np.asarray(dest)) == [3, 1, 0, 0, 0, 0, 0, 0, 2]
    cache = ops.set_page_table(cache, 2, dest)
    assert not np.asarray(cache["s.ssm"][:, 2]).any()        # armed: zero
    assert not np.asarray(cache["tail.ssm"][:, 2]).any()
    assert np.all(np.asarray(cache["s.ssm"][:, 0]) == 5.0)   # others stay
    k = jnp.asarray(rng.randn(16, 2, 16).astype("float32"))
    state = jnp.asarray(rng.randn(4, 16, 8).astype("float32"))
    tail = jnp.asarray(rng.randn(3, 96).astype("float32"))
    cache = ops.write_prompt(cache, 1, k, k + 1, dest, 11)
    cache = ops.write_slot_state(cache, 1, state, tail, dest)
    np.testing.assert_array_equal(np.asarray(cache["s.ssm"][1, 2]), state)
    np.testing.assert_array_equal(np.asarray(cache["tail.ssm"][1, 2]), tail)
    assert not np.asarray(cache["s.ssm"][0, 2]).any()        # layer 0: not
    rows = np.asarray(cache["k"][1]).reshape(-1, 2, 16)
    np.testing.assert_array_equal(rows[3 * 8:3 * 8 + 8], k[:8])   # page 3
    np.testing.assert_array_equal(rows[8:8 + 3], k[8:11])         # page 1
    assert not rows[8 + 3:16].any()                # past the length: none
    # one decode step of layer 1, both kinds
    active = jnp.asarray([False, False, True])
    pos = jnp.asarray([0, 0, 11])
    new = jnp.asarray(rng.randn(3, 2, 16).astype("float32"))
    cache = ops.write_token(cache, 1, new, new, pos, active)
    q = jnp.asarray(rng.randn(3, 10, 16).astype("float32"))
    o = ops.decode_attention(cache, 1, q, pos + 1, active, sm_scale=0.25)
    assert o.shape == (3, 10, 16) and np.isfinite(np.asarray(o)).all()
    u = jnp.asarray(rng.randn(3, 96).astype("float32"))
    window, cache = ops.tail_step(cache, 1, u, active)
    np.testing.assert_array_equal(np.asarray(window[2, :3]), tail)
    np.testing.assert_array_equal(np.asarray(cache["tail.ssm"][1, 2, :2]),
                                  tail[1:])
    x = jnp.asarray(rng.randn(3, 4, 8).astype("float32"))
    b, c = (jnp.asarray(rng.randn(3, 2, 16).astype("float32"))
            for _ in range(2))
    a = -jnp.ones((3, 4))
    y, cache = ops.state_step(cache, 1, x, b, c, a, active)
    want = state * np.exp(-1.0) + np.repeat(np.asarray(b[2]), 2, axis=0)[
        :, :, None] * np.asarray(x[2])[:, None, :]
    np.testing.assert_allclose(np.asarray(cache["s.ssm"][1, 2]), want,
                               atol=1e-6)
    assert np.all(np.asarray(cache["s.ssm"][1, 0]) == 5.0)
    assert ops.rows_read(pos + 1, active) == {
        "attn_rows_read.global": 12}


def test_a_layer_stands_once_in_a_kind_of_group():
    """Two PAGED groups naming one layer still raise, and so do two state
    groups; a state group needs its geometry, comes last, and names a
    recurrence that exists."""
    kv = CacheGroup("global", (0, 1), None, 8, KV)
    st = CacheGroup("ssm", (0, 1), None, 0, STATE)
    with pytest.raises(ValueError, match="layer 1 is in two paged"):
        _two_kinds(groups=[kv, CacheGroup("window", (1,), 16, 8, KV), st],
                   q_per_kv={"global": 5, "window": 5, "ssm": 5})
    with pytest.raises(ValueError, match="layer 0 is in two state"):
        _two_kinds(groups=[kv, st, CacheGroup("more", (0,), None, 0, STATE)],
                   q_per_kv={"global": 5, "ssm": 5, "more": 5})
    with pytest.raises(ValueError, match="after every paged group"):
        _two_kinds(groups=[st, kv])
    with pytest.raises(ValueError, match="slot_state"):
        _two_kinds(slot_state=None)
    with pytest.raises(ValueError, match="recurrence='s4'"):
        _two_kinds(recurrence="s4")
    with pytest.raises(ValueError, match="name layers of 0..1"):
        _two_kinds(groups=[CacheGroup("global", (0,), None, 8, KV),
                           CacheGroup("ssm", (2,), None, 0, STATE)])
    # a layer may stand in NO group (it keeps nothing: PR 65)
    _two_kinds(groups=[CacheGroup("global", (0,), None, 8, KV),
                       CacheGroup("ssm", (0,), None, 0, STATE)])
    # the state group alone may cover a layer the paged one does not
    ops = _two_kinds(groups=[CacheGroup("global", (0,), None, 8, KV), st])
    assert ops.state_kernel_mode()[0] in (None, "interpret", "compiled")


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
])
def test_what_this_cache_cannot_do_is_refused_at_construction(toy, kw, what):
    """What the engine refuses over groups it refuses here too."""
    with pytest.raises(ValueError, match=what + ".*cache with 2 groups"):
        _engine(toy, **kw)


def test_page_export_is_refused_over_this_cache(toy):
    with _engine(toy) as eng:
        for call, what in (
                (lambda: eng.cache_ops.export_pages(eng._cache, [0]),
                 "page export"),
                (lambda: eng.cache_ops.import_pages(eng._cache, [0], {}, []),
                 "page import"),
                (lambda: eng.cache_ops.copy_pages(eng._cache, None, None),
                 "page copy")):
            with pytest.raises(ValueError, match=what + ".*ssm: state"):
                call()


# -- (d) the blocks and the seeds -------------------------------------------------

def test_the_gated_group_norm_gates_first_and_norms_a_group(rng):
    y, z = (jnp.asarray(rng.randn(5, 32).astype("float32"))
            for _ in range(2))
    g = jnp.asarray(rng.rand(32).astype("float32") + 0.5)
    got = blocks.gated_group_norm(y, z, g, 2, 1e-5)
    v = np.asarray(y) * np.asarray(jax.nn.silu(z))
    want = np.concatenate([
        part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5)
        for part in (v[:, :16], v[:, 16:])], axis=-1) * np.asarray(g)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    # the norm BEFORE the gate is another function
    other = blocks.rms_norm(y, g, 1e-5) * jax.nn.silu(z)
    assert float(jnp.abs(got - other).max()) > 0.1


def test_the_convolution_pair_agrees_and_leaves_the_tail(rng):
    """``causal_conv_prefill`` over a sequence is ``causal_conv_step`` over
    each position's window, and its tail is what the next step reads."""
    u = jnp.asarray(rng.randn(12, 6).astype("float32"))
    cw = jnp.asarray(rng.randn(4, 6).astype("float32"))
    cb = jnp.asarray(rng.randn(6).astype("float32"))
    out, tail = blocks.causal_conv_prefill(u, cw, cb, 9)
    padded = np.concatenate([np.zeros((3, 6), "float32"), np.asarray(u)])
    for t in (0, 2, 8):
        want = blocks.causal_conv_step(jnp.asarray(padded[None, t:t + 4]),
                                       cw, cb)
        np.testing.assert_allclose(np.asarray(out[t]), np.asarray(want[0]),
                                   atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(u[6:9]))
    _, early = blocks.causal_conv_prefill(u, cw, cb, 2)
    np.testing.assert_array_equal(np.asarray(early),
                                  padded[2:5])     # a zero, then two rows


def test_the_seeds_are_mamba2s_and_the_scales_follow_the_multipliers(toy):
    cfg, lp = toy.cfg, toy.params["layers"][0]
    step = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 0.001 <= step.min() and step.max() <= 0.1
    decay = np.exp(np.asarray(lp["a_log"]))
    assert 1.0 <= decay.min() and decay.max() <= 16.0
    assert np.all(np.asarray(lp["dskip"]) == 1) and np.all(
        np.asarray(lp["gn"]) == 1)
    # a projection's deviation: its target over sqrt(fan_in) and over its
    # multipliers (the keys': 1.5 / (8 x 1 x 0.011))
    assert np.asarray(lp["wk"]).std() == pytest.approx(
        1.5 / (8 * MUP["key_multiplier"]), rel=0.1)
    assert np.asarray(toy.params["tok_emb"]).std() == pytest.approx(
        1 / MUP["embedding_multiplier"], rel=0.05)
    assert cfg.slot_state == (4, 16, 8, 3, 96)
    assert cfg.state_recurrence == "ssd"
    assert cfg.cache_groups == [("global", (0, 1, 2), None, KV),
                                ("ssm", (0, 1, 2), None, STATE)]
    with pytest.raises(ValueError, match="mup needs"):
        toy_cfg(mup={k: v for k, v in MUP.items()
                     if k != "key_multiplier"})

"""Ouro's looped decoder (the same layers run FOUR times over a token, a
cache layer a step a layer, sandwich norms, an exit gate) through the
serving stack, against its plain float32 reference
(``grid/reference/ouro.py``), at a toy size on the CPU: 3 layers x 4 steps,
d 64, 4 heads of 16 (as many KV heads), ff 96, vocabulary 64, page 8.
LOGITS and the four exit probabilities are compared, never sampled tokens.

Tolerance. In float32 the served path and the reference differ in the
ORDER of their sums only (the paged kernel's online softmax against a
whole one, a device loop against a Python one): the worst logit difference
read was 2e-7 on logits of standard deviation 0.15, and 2e-7 on a
probability. ``TOL`` = ``TOL_P`` = 5e-6 is twenty-five times that and far
under what a step left out or a cache layer shared gives (0.05 and more,
in float32, where nothing else moves). In bfloat16 the twelve layer
applications round activations and rows to 8 bits each: the worst
difference read was 0.035 of the logits' deviation and 0.0034 on a
probability; ``TOL_BF16`` and ``TOL_P_BF16`` are three times those.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import ouro as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import ouro
from paddle_tpu.serving.kv_cache import PagedKVCache

TOL, TOL_P = 5e-6, 5e-6
TOL_BF16, TOL_P_BF16 = 0.1, 0.01
PUBLISHED = dict(  # the toy under the published config's own keys
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, vocab_size=64, intermediate_size=96, num_hidden_layers=3,
    total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e6)


def toy_cfg(**over):
    kw = dict(vocab_size=64, n_layer=3, d_model=64, n_head=4, n_kv_head=4,
              d_head=16, d_ff=96, ut_steps=4, max_seq=128, dtype="float32")
    kw.update(over)
    return ouro.OuroConfig(**kw)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return ouro.OuroLM(cfg, params=ouro.init_params(cfg, 3))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_rows(model, seq, rows):
    """``(logits [R, V], p [R, 4])`` of the reference over ``seq``."""
    logits, p = ref.forward(model.params, PUBLISHED,
                            np.asarray(seq, np.int32), rows=rows)
    return np.asarray(logits), np.asarray(p)


def _prefill(model, seq, bucket=32):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return model.prefill(model.params, jnp.asarray(toks),
                         jnp.asarray([len(seq)], jnp.int32))


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=8, max_seq=128,
               prompt_buckets=(8, 32, 96), num_pages=40, collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


def _serve(eng, plan):
    """The plan's requests through ``submit``/``step``; each request's
    served exit distributions a decoded row, in order, beside it."""
    reqs = [eng.submit(list(p), m) for p, m in plan]
    seen, exit_p = eng.last_decode_stats, {r.id: [] for r in reqs}
    while not eng.scheduler.idle():
        eng.step()
        assert eng.page_accounting_ok()
        read = eng.last_decode_stats
        if read is not None and read is not seen:
            seen = read
            tenants, stats = read
            for p in np.asarray(stats["ut_exit_p"]):
                for slot, req in enumerate(tenants):
                    if req is not None and p[slot].sum() > 0.5:
                        exit_p[req.id].append(p[slot])
    return reqs, exit_p


# -- (a) prefill against the reference's full forward --------------------------

@pytest.mark.parametrize("n", [5, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Four passes over three layers under the bucket's padding; what the
    cache is handed has a leading STEP axis."""
    seq = rng.randint(0, 64, n)
    logits, kept = _prefill(toy, seq)
    want, _ = reference_rows(toy, seq, np.arange(n))
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    assert len(kept) == 3
    for k, v in kept:
        assert k.shape == v.shape == (4, 1, 32, 4, 16)
    # the steps made different rows from the same weights
    k0 = np.asarray(kept[0][0])
    assert np.abs(k0[0, 0, :n] - k0[1, 0, :n]).max() > 0.1


def test_each_step_and_each_half_moves_the_state(toy, rng):
    """The seeded scales leave nothing invisible: attention and the MLP
    add a tenth of the state's length a layer each, every one of the four
    steps moves the state by a fifth of it and more, and the gate spreads
    the exits over the steps."""
    seq = rng.randint(0, 64, 40)
    shares = []
    _, p = ref.forward(toy.params, PUBLISHED, np.asarray(seq), shares=shares)
    assert len(shares) == 4
    for attn, mlp, moved in np.asarray(shares):
        assert attn == pytest.approx(0.1, rel=0.02)
        assert mlp == pytest.approx(0.1, rel=0.02)
        assert 0.15 < moved < 0.5
    p = np.asarray(p)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-6)
    assert (p.mean(axis=0) > 0.02).all()
    np.testing.assert_allclose(
        np.asarray(ouro.exit_distribution(jnp.asarray(
            [[0.5, 0.5, 0.5, 0.9]]))), [[0.5, 0.25, 0.125, 0.125]])


# -- (b) prefill, then decode through the 12-layer pool ------------------------

@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_cache_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: every (step, layer) writes its cache layer across page
    boundaries and attends over it under a TRACED step. Every emitted
    token's logits row and every decoded row's four ``p_t`` equal the
    reference's full forward over the same tokens; in plain XLA and by the
    paged kernel (interpreted)."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "paged")
            ops = eng.cache_ops
            assert ops.cache_steps == 4 and ops.n_layer == 3
            assert eng._cache["k"].shape == (12, 40 * 8, 64)
            plan = [(rng.randint(0, 64, 3), 4), (rng.randint(0, 64, 70), 12),
                    (rng.randint(0, 64, 5), 40)]
            reqs, exit_p = _serve(eng, plan)
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want, want_p = reference_rows(
                    toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
                # decode step k chose token k + 1: the prefill chose token 0
                got_p = np.stack(exit_p[req.id])
                assert got_p.shape == (m - 1, 4)
                np.testing.assert_allclose(got_p, want_p[1:], atol=TOL_P,
                                           rtol=0)
            stats = eng.last_decode_stats[1]
            assert set(stats) == {"ut_expected_exit_step", "ut_exit_p",
                                  "attn_rows_read.global",
                                  "attn_rows_context.global"}
            assert 100 <= int(stats["ut_expected_exit_step"][-1]) <= 400
        from paddle_tpu.serving import metrics as sm

        assert sm.UT_EXPECTED_EXIT_STEP.count > 0
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_bfloat16_stays_inside_its_margin(rng):
    """The served type: weights, activations and the pool's rows in
    bfloat16 against the float32 reference over the SAME (bfloat16)
    weights."""
    model = toy_model(dtype="bfloat16")
    with _engine(model) as eng:
        plan = [(rng.randint(0, 64, 20), 24), (rng.randint(0, 64, 6), 30)]
        reqs, exit_p = _serve(eng, plan)
        for (prompt, m), req in zip(plan, reqs):
            seq = list(prompt) + req.tokens_out[:-1]
            first = len(prompt) - 1
            want, want_p = reference_rows(model, seq,
                                          np.arange(first, first + m))
            got = np.stack(eng.captured_logits(req)).astype(np.float32)
            assert np.abs(got - want).max() < TOL_BF16 * want.std()
            assert np.abs(np.stack(exit_p[req.id]) - want_p[1:]).max() \
                < TOL_P_BF16


@pytest.mark.parametrize("variant", ["three_steps", "shared_cache"])
def test_a_variant_fails_the_same_comparison(toy, variant, rng,
                                             monkeypatch):
    """The SAME weights served with three steps, or with ONE cache layer
    for the four steps (the family's last-step reuse: every step attends
    over what the last one wrote at the earlier positions), are a
    different result: the comparison of test (b) fails by orders of
    magnitude, on the logits and on the gate."""
    if variant == "shared_cache":   # every step in the layer's first layer
        monkeypatch.setattr(PagedKVCache, "_pool_layer",
                            lambda self, li, step: li * self.cache_steps)
    model = ouro.OuroLM(toy_cfg(ut_steps=3 if variant == "three_steps"
                                else 4), params=toy.params)
    with _engine(model) as eng:
        prompt, m = rng.randint(0, 64, 21), 16
        (req,), exit_p = _serve(eng, [(prompt, m)])
        seq = list(prompt) + req.tokens_out[:-1]
        want, want_p = reference_rows(toy, seq, np.arange(20, 20 + m))
        got = np.stack(eng.captured_logits(req))
        assert np.abs(got - want).max() > 0.05
        got_p = np.stack(exit_p[req.id])
        got_p = np.pad(got_p, ((0, 0), (0, 4 - got_p.shape[1])))
        assert np.abs(got_p - want_p[1:]).max() > 0.01


def test_a_step_reads_only_its_own_rows(toy, rng):
    """Step t of layer l attends over what step t of layer l wrote: with
    every OTHER cache layer of the pool overwritten by NaN, that call's
    result does not change; with its own, it does."""
    ops = PagedKVCache(3, 4, 16, 2, 32, 8, 8, dtype="float32", cache_steps=4)
    state = ops.init_state()
    state = ops.set_page_table(state, 0, ops.prompt_dest([1, 2, 3, 4]))
    state = ops.set_page_table(state, 1, ops.prompt_dest([5, 6, 7, 0]))
    dest = jnp.asarray(ops.prompt_dest([1, 2, 3, 4]))
    for layer in range(3):
        for t in range(4):
            k, v = (jnp.asarray(rng.randn(20, 4, 16), jnp.float32)
                    for _ in range(2))
            state = ops.write_prompt(state, layer, k, v, dest, 20, step=t)
    q = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
    ctx, active = jnp.asarray([20, 0]), jnp.asarray([True, False])
    for layer, t in ((0, 0), (1, 2), (2, 3)):
        mine = layer * 4 + t
        want = ops.decode_attention(state, layer, q, ctx, active, step=t)
        others = jnp.arange(12) != mine
        poisoned = {**state, **{
            key: jnp.where(others[:, None, None], jnp.nan, state[key])
            for key in ("k", "v")}}
        got = ops.decode_attention(poisoned, layer, q, ctx, active, step=t)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        own = {**state, "k": state["k"].at[mine].set(jnp.nan)}
        assert np.isnan(np.asarray(ops.decode_attention(
            own, layer, q, ctx, active, step=t)[0])).any()


def _eqns(jaxpr, name, inside=()):
    """``(equation, the loop primitives it sits in)`` of every ``name``
    equation of ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            nest = inside + ((eqn.primitive.name,)
                             if eqn.primitive.name in ("while", "scan")
                             else ())
            yield from _eqns(sub, name, nest)


def test_the_steps_are_one_device_loop_over_the_layers_body():
    """The decode program holds ONE attention call a layer of weights (3
    here, 48 at the published depth), not one a step a layer, all inside
    one loop of four trips; the prefill's scan likewise holds the layers'
    products once."""
    set_flag("paged_attention_kernel", "interpret")
    try:
        cfg = toy_cfg()
        model = ouro.OuroLM(cfg, params={})
        params = jax.eval_shape(lambda: ouro.init_params(cfg, 0))
        ops = PagedKVCache(3, 4, 16, 2, 32, 8, 8, dtype="float32",
                           cache_steps=4)
        cache = jax.eval_shape(ops.init_state)
        ints = jax.ShapeDtypeStruct((2,), jnp.int32)
        flags = jax.ShapeDtypeStruct((2,), jnp.bool_)
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, a: model.decode(p, c, ops, t, pos, a))(
                params, cache, ints, ints, flags).jaxpr
        calls = list(_eqns(jaxpr, "pallas_call"))
        assert len(calls) == 3
        assert all(len(nest) == 1 for _, nest in calls)
        loop, = [e for e in jaxpr.eqns
                 if e.primitive.name in ("while", "scan")]
        trips = loop.params.get("length")
        assert trips is None or trips == 4
        toks = jax.ShapeDtypeStruct((1, 32), jnp.int32)
        lens = jax.ShapeDtypeStruct((1,), jnp.int32)
        pre = jax.make_jaxpr(model.prefill_last)(params, toks, lens).jaxpr
        scan, = [e for e in pre.eqns if e.primitive.name == "scan"]
        assert scan.params["length"] == 4
        # seven products a layer in the body: q, k, v, o, gate, up, down
        # (attention's two are einsums of their own, counted apart)
        body = scan.params["jaxpr"].jaxpr
        weights = [e for e, _ in _eqns(body, "dot_general")
                   if len(e.invars[1].aval.shape) == 2
                   and e.invars[1].aval.shape[0] in (64, 96)]
        assert len(weights) == 3 * 7
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_cache_steps_absent_means_one(rng):
    """The engine reads ``cache_steps`` by ``getattr``: a model without it
    keeps one cache layer a layer (GPT-2 here), one with it that many, and
    the pool, the page accounting and the fingerprint follow."""
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.DecoderConfig(vocab_size=50, n_layer=2, d_model=32,
                                   n_head=2, max_seq=64)
    assert not hasattr(cfg, "cache_steps")
    with serving.ServingEngine(
            decoder_lm.DecoderLM(cfg, seed=0), serving.ServingConfig(
                slots=2, page_size=8, max_seq=64, num_pages=16)) as eng:
        assert eng.cache_ops.cache_steps == 1
        assert eng._cache["k"].shape[0] == 2
    looped = toy_model()
    with _engine(looped) as eng:
        assert eng.cache_ops.cache_steps == 4
        assert eng._cache["k"].shape[0] == 12
        # a page is four times the bytes, the same page: the table is one
        assert eng._cache["pt"].shape == (3, 128 // 8)
        assert eng.cache_ops.cache_bytes(eng._cache) == 2 * 12 * 320 * 64 * 4
    with pytest.raises(ValueError, match="int8 KV pool is not supported"):
        _engine(looped, kv_dtype="int8")
    with pytest.raises(ValueError, match="exit_threshold"):
        toy_cfg(exit_threshold=0.5)


def test_the_contiguous_layout_and_the_prefix_cache_serve_it_too(toy, rng):
    """The dense reference cache takes ``step=`` as the paged one does (the
    same logits), and a prompt resumed from donated pages, whose copy moves
    every cache layer's rows, decodes what a cold one decodes."""
    prompt = list(rng.randint(0, 64, 19))
    with _engine(toy) as eng:
        (cold,), _ = _serve(eng, [(prompt, 10)])
        want = np.stack(eng.captured_logits(cold))
    with _engine(toy, paged=False) as eng:
        assert eng._cache["k"].shape[0] == 12
        (req,), _ = _serve(eng, [(prompt, 10)])
        np.testing.assert_allclose(np.stack(eng.captured_logits(req)), want,
                                   atol=TOL, rtol=0)
    with _engine(toy, prefix_cache_pages=8) as eng:
        (first,), _ = _serve(eng, [(prompt, 10)])
        (again,), _ = _serve(eng, [(prompt, 10)])
        assert eng._resumes == 1
        assert again.tokens_out == first.tokens_out == cold.tokens_out
        np.testing.assert_allclose(np.stack(eng.captured_logits(again)),
                                   want, atol=TOL, rtol=0)

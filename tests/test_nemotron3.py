"""Nemotron-3-Nano's decoder (layers of ONE part each: a Mamba-2 mixer of
64-lane heads, an ungated relu^2 expert layer, grouped-query attention
without positions) through the serving stack, against its plain float32
reference (``grid/reference/nemotron3.py``), at a toy size on the CPU that
keeps what the published geometry forces: the first nine letters of the
published pattern (``MEMEM*EME``), SSM heads of 64 channels over 128 state
lanes (so the cache PACKS two heads a lane tile, as at the published
size), 2 groups, a convolution of 4 taps, 32 query heads over 2 KV heads,
16 experts of width 24 (no whole lane tiles) of which a half share holds
8, top-3, a shared expert of 48, scaling 2.5. LOGITS are compared, never
sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (the chunk-wise scan against the
recurrence token by token, the paged kernel's online softmax against a
whole one, the experts' sorted passes against a dense loop): the worst
logit difference read was 3e-5 on logits of standard deviation 1.
``TOL`` = 2e-4 is some six times that and far under what a part left out
gives (0.05 and more).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import nemotron3 as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import nemotron3 as nm
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas_kernels import expert_stream as es
from paddle_tpu.ops.pallas_kernels import ssd
from paddle_tpu.serving.kv_cache import KV, STATE, CacheGroup, PagedKVCache

TOL = 2e-4
PATTERN = "MEMEM*EME"
HELD = list(range(8))
PUBLISHED = dict(  # the toy under the published config's own keys
    model_type="nemotron_h", hidden_size=64, num_attention_heads=32,
    num_key_value_heads=2, head_dim=16, vocab_size=96, mamba_num_heads=4,
    mamba_head_dim=64, n_groups=2, ssm_state_size=128, conv_kernel=4,
    chunk_size=128, hybrid_override_pattern=PATTERN + "MEM*",
    num_hidden_layers=9, layer_norm_epsilon=1e-5, n_routed_experts=8,
    num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    experts_held=HELD, published={"n_routed_experts": 16})


def toy_cfg(**over):
    kw = dict(vocab_size=96, pattern=PATTERN, d_model=64, n_head=32,
              n_kv_head=2, d_head=16, ssm_heads=4, ssm_head_dim=64,
              ssm_groups=2, ssm_state=128, n_expert=16, top_k=3,
              d_expert=24, d_shared=48, routed_scale=2.5, experts_held=HELD,
              max_seq=256, dtype="float32")
    kw.update(over)
    return nm.Nemotron3Config(**kw)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return nm.Nemotron3LM(cfg, params=nm.init_params(cfg, 3))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def published(model, **over):
    """``PUBLISHED`` for ``model``'s pattern and share."""
    cfg = model.cfg
    return dict(PUBLISHED, hybrid_override_pattern="".join(cfg.layer_kinds),
                num_hidden_layers=cfg.n_layer,
                experts_held=list(cfg.experts_held),
                n_routed_experts=len(cfg.experts_held), **over)


def reference_rows(model, seq, rows, **kw):
    return np.asarray(ref.forward(model.params, published(model),
                                  np.asarray(seq, np.int32), rows=rows, **kw))


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


@pytest.fixture
def stream_here(monkeypatch):
    """The experts' decode pass takes the stream kernel here, interpreted
    (``tests/test_expert_stream.py``'s fixture)."""
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    for name in ("expert_stream_ffn", "expert_stream_gate"):
        monkeypatch.setattr(es, name, functools.partial(
            getattr(es, name), interpret=True))


# -- (a) prefill against the reference's full forward --------------------------

@pytest.mark.parametrize("n", [5, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Nine layers of one part each under the bucket's padding: the chunk
    scan against the recurrence token by token, the half share's sorted
    ungated experts against the dense loop, causal GQA at 16 query heads a
    KV head with no position embedding."""
    seq = rng.randint(0, 96, n)
    logits, kept = _prefill(toy, seq)
    want = reference_rows(toy, seq, np.arange(n))
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    # what the cache is handed, a layer, by the layer's ONE group (or none)
    for kind, got in zip(PATTERN, kept):
        if kind == "E":
            assert got is None
        elif kind == "M":
            state, tail = got
            assert state.shape == (1, 4, 128, 64)
            assert state.dtype == jnp.float32 and tail.shape == (1, 3, 768)
        else:
            assert got[0].shape == got[1].shape == (1, 32, 2, 16)


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_a_layer_of_each_kind_equals_the_reference(kind, rng):
    """ONE layer of each kind alone (after an ``M`` layer, so that its
    input is no bare embedding) against the reference, and the reference
    with that kind left out is far from it."""
    model = toy_model(pattern="M" + kind)
    seq = rng.randint(0, 96, 29)
    logits, _ = _prefill(model, seq)
    want = reference_rows(model, seq, np.arange(29))
    np.testing.assert_allclose(np.asarray(logits[0, :29]), want, atol=TOL,
                               rtol=0)
    without = reference_rows(model, seq, np.arange(29), leave_out=kind)
    assert np.abs(want - without).max() > 0.05


def test_each_kind_of_part_adds_a_share_the_comparison_can_see(toy, rng):
    """At the PUBLISHED scaling factor and relu^2 the seeded scales leave
    no kind of part invisible: every layer's part is between a tenth and
    the whole of the residual it is added to."""
    seq = rng.randint(0, 96, 40)
    shares = []
    ref.forward(toy.params, published(toy), np.asarray(seq), shares=shares)
    assert len(shares) == 9
    for part, resid in np.asarray(shares):
        assert 0.1 * resid < part < resid


# -- (b) prefill, then decode through the pool and the states ------------------

@pytest.mark.parametrize("kernel", ["off", "interpret", "interpret+stream"])
def test_decode_through_the_cache_equals_the_reference(
        toy, kernel, rng, request):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: one a prompt past a chunk that is no multiple of 128. The
    ``M`` layers' packed states and tails written by the prefill's scan and
    advanced a token at a time, the ``*`` layer's K and V rows across page
    boundaries, the ``E`` layers touching no cache. Every emitted token's
    logits row equals the reference's full forward over the same tokens;
    in plain XLA, by the paged and the state kernel (interpreted), and
    with the experts' decode pass by the stream kernel too."""
    if kernel.endswith("stream"):
        request.getfixturevalue("stream_here")
    set_flag("paged_attention_kernel", kernel.split("+")[0])
    try:
        with _engine(toy) as eng:
            ops = eng.cache_ops
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "paged")
            assert ops.state_kernel_mode()[0] == (
                None if kernel == "off" else "interpret")
            assert ops.q_per_kv["global"] == 16
            # the ONE attention layer's pool, the four M layers' states
            # with two heads side by side in a lane tile; nothing for an E
            assert sorted(eng._cache) == ["k", "pt", "s.ssm", "tail.ssm", "v"]
            assert eng._cache["k"].shape[0] == 1
            assert eng._cache["s.ssm"].shape == (4, 3, 2, 128, 128)
            plan = [(rng.randint(0, 96, 3), 4), (rng.randint(0, 96, 150), 12),
                    (rng.randint(0, 96, 5), 40)]
            reqs = [eng.submit(list(p), m) for p, m in plan]
            while not eng.scheduler.idle():
                eng.step()
                assert eng.page_accounting_ok()
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want = reference_rows(toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
            assert eng.pool.num_used == 0 and len(eng.pools) == 1
    finally:
        set_flag("paged_attention_kernel", "auto")


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_the_states_a_slot_keeps_equal_the_recurrence(toy, kernel, rng):
    """After a prompt alone (one emitted token) and after 21 decode steps
    more, what the cache KEEPS for the slot, unpacked into the model's
    order, is the reference's token-by-token recurrence over the tokens
    consumed, every ``M`` layer's."""
    set_flag("paged_attention_kernel", kernel)
    try:
        prompt = list(rng.randint(0, 96, 140))
        with _engine(toy, slots=1) as eng:
            ops = eng.cache_ops
            gi = [g.name for g in ops.groups].index("ssm")
            for budget in (1, 22):      # the ONE slot, reused
                req = eng.submit(prompt, budget)
                eng.run()
                consumed = (prompt + req.tokens_out)[:-1]
                got = np.asarray(ops.slot_states(eng._cache, gi, 0))
                want = ref.final_states(toy.params, published(toy), consumed)
                assert got.shape == want.shape == (4, 4, 128, 64)
                assert ref.state_gaps(got, want)[-1] < 1e-5
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_the_decode_stats_count_states_experts_and_rows(toy, rng):
    """``state_slots_stepped``, the four ``E`` layers' ``moe_*`` counts and
    the cache's ``attn_rows_read.global`` ride a decode step's stats to
    their histograms."""
    from paddle_tpu.serving import metrics as sm

    with _engine(toy) as eng:
        stepped, rows = sm.STATE_SLOTS_STEPPED, sm.attn_rows_read("global")
        touched, pairs = sm.MOE_EXPERTS_TOUCHED, sm.MOE_HELD_PAIRS
        s0, r0, t0, p0 = stepped.sum, rows.sum, touched.count, pairs.sum
        eng.submit(list(rng.randint(0, 96, 10)), 6)
        eng.submit(list(rng.randint(0, 96, 20)), 6)
        eng.run()
        assert stepped.sum - s0 == 2 * 5
        assert rows.sum - r0 == sum(11 + j + 21 + j for j in range(5))
        # an observation an E layer a step; at most 2 slots x 3 pairs held
        steps = (touched.count - t0) // 4
        assert steps >= 5 and (touched.count - t0) % 4 == 0
        assert 0 < pairs.sum - p0 <= steps * 4 * 6


# -- (c) the 64-lane state: layout and both kernels -----------------------------

def _ssd_case(rng, t, h=8, p=64, g=2, n=128):
    return (jnp.asarray(rng.randn(t, h, p).astype("float32")),
            jnp.asarray(rng.randn(t, g, n).astype("float32") * 0.3),
            jnp.asarray(rng.randn(t, g, n).astype("float32") * 0.3),
            jnp.asarray(-np.abs(rng.randn(t, h)).astype("float32") * 0.2))


def test_a_slots_state_is_two_mib_at_the_published_geometry():
    """64 heads of [128, 64] are kept as 32 of [128, 128]: 2 MiB a slot a
    layer with no padding lanes (a [.., 128, 64] float32 array is padded
    to 128 lanes in HBM: 4 MiB); Falcon-H1's [32, 256, 128] stays as it
    is; heads that do not pair inside a group stay unpacked."""
    assert ssd.state_shape(64, 128, 64, 8) == (32, 128, 128)
    assert int(np.prod(ssd.state_shape(64, 128, 64, 8))) * 4 == 2 << 20
    assert ssd.state_shape(32, 256, 128, 2) == (32, 256, 128)
    assert ssd.state_shape(6, 128, 64, 2) == (6, 128, 64)
    ops = PagedKVCache(
        2, 2, 128, 4, 64, 16, 8, dtype=jnp.bfloat16, q_per_kv=16,
        groups=[CacheGroup("global", (1,), None, 8, KV),
                CacheGroup("ssm", (0,), None, 0, STATE)],
        slot_state=(64, 128, 64, 3, 6144), recurrence="ssd")
    assert ops.state_shape() == (32, 128, 128)
    assert ops.init_state()["s.ssm"].shape == (1, 4, 32, 128, 128)
    s = jnp.arange(2 * 8 * 4 * 6, dtype=jnp.float32).reshape(2, 8, 4, 6)
    packed = ssd.pack_state(s, 2)
    assert packed.shape == (2, 4, 4, 12)
    np.testing.assert_array_equal(packed[1, 2, :, :6], s[1, 4])
    np.testing.assert_array_equal(packed[1, 2, :, 6:], s[1, 5])
    np.testing.assert_array_equal(ssd.unpack_state(packed, 2), s)


@pytest.mark.parametrize("t", [128, 200])
def test_the_chunk_scan_kernel_takes_64_lane_heads(t, rng):
    """The scan kernel (interpreted) with two heads a lane tile, and the
    blocked form, against the recurrence token by token: outputs and the
    state a prompt leaves, in the model's order."""
    x, b, c, a = _ssd_case(rng, t)
    want_y, want_s = ssd.ssd_recurrence(x, b, c, a)
    for form in (functools.partial(ssd.ssd_chunk_scan_kernel, interpret=True),
                 ssd.ssd_chunk_scan_xla):
        y, s = form(x, b, c, a)
        assert s.shape == (8, 128, 64)
        np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=0)
        np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)


@pytest.mark.parametrize("live", [(True, False, True, True, False),
                                  (False,) * 5])
def test_the_state_step_kernel_takes_64_lane_heads(live, rng):
    """One decode step over the PACKED pool (a whole slot a grid step, the
    pair's decays a row) against the recurrence: live slots advanced,
    idle slots and the other layers untouched, by the kernel and in plain
    XLA."""
    full = jnp.asarray(rng.randn(3, 5, 8, 128, 64).astype("float32"))
    states = ssd.pack_state(full, 2)
    assert states.shape == (3, 5, 4, 128, 128)
    assert ssd._step_blocks(8, 2, 128, 64) == (2, 2, 2)
    x, b, c, a = _ssd_case(rng, 5)
    active = jnp.asarray(live)
    for form in (ssd.ssd_state_step_xla,
                 functools.partial(ssd.ssd_state_step, interpret=True)):
        y, out = form(states, 1, x, b, c, a, active)
        assert y.shape == (5, 8, 64) and out.shape == states.shape
        for slot in range(5):
            if not live[slot]:
                np.testing.assert_array_equal(out[1, slot], states[1, slot])
                assert not np.any(np.asarray(y[slot]))
                continue
            want_y, want_s = ssd.ssd_recurrence(
                x[slot:slot + 1], b[slot:slot + 1], c[slot:slot + 1],
                a[slot:slot + 1], full[1, slot])
            np.testing.assert_allclose(y[slot], want_y[0], atol=2e-5, rtol=0)
            np.testing.assert_allclose(
                ssd.unpack_state(out[1, slot], 2), want_s, atol=1e-6, rtol=0)
        np.testing.assert_array_equal(out[0], states[0])
        np.testing.assert_array_equal(out[2], states[2])


def test_the_state_step_counts_the_form_it_was_traced_in(toy, rng):
    """``ssd/step_calls.kernel`` where the cache arms the kernel,
    ``.xla`` where it does not: once a traced call."""
    counts = {}
    for kernel, name in (("off", "xla"), ("interpret", "kernel")):
        set_flag("paged_attention_kernel", kernel)
        try:
            before = mx.counter("ssd/step_calls." + name).value
            with _engine(toy, slots=2) as eng:
                eng.submit(list(rng.randint(0, 96, 6)), 3)
                eng.run()
            counts[name] = mx.counter("ssd/step_calls." + name).value - before
        finally:
            set_flag("paged_attention_kernel", "auto")
    assert counts["xla"] >= 4 and counts["kernel"] >= 4   # four M layers


# -- (d) ungated experts of a width that is no whole lane tiles -----------------

def _ungated(rng, m, e, d, f, dtype=jnp.float32):
    """``(xs, W_up TRANSPOSED [e, f, d], W_down [e, f, d])``."""
    def arr(*shape, scale=1.0):
        return jnp.asarray((rng.randn(*shape) * scale).astype("float32")
                           ).astype(dtype)
    return arr(m, d), arr(e, f, d, scale=d ** -0.5), arr(e, f, d,
                                                        scale=f ** -0.5)


@pytest.mark.parametrize("blocks_of", ["whole", "split"])
def test_ungated_experts_through_both_products(rng, monkeypatch, blocks_of):
    """``relu(x W_up)^2 W_down`` over sorted rows, width 72 (no whole lane
    tiles, so ``W_down`` is read by COLUMN blocks): two ``ragged_dot``s,
    and the stream kernel (interpreted) reading two matrices an expert,
    whole and in two row blocks of ``W_up`` and two column blocks of
    ``W_down``, against ``expert_ffn_reference``."""
    if blocks_of == "split":
        monkeypatch.setattr(es, "_BLOCK_BYTES", 40_000)
    m, e, d, f = 40, 5, 256, 72
    xs, wu, wd = _ungated(rng, m, e, d, f)
    plan = es.expert_stream_plan(m, e, d, f, jnp.float32, gated=False)
    assert plan["down_cols"] and plan["fits"]
    assert (plan["nkd"], plan["nkf"]) == ((2, 2) if blocks_of == "split"
                                          else (1, 1))
    sizes = jnp.asarray([7, 0, 12, 3, 9], jnp.int32)
    want = es.expert_ffn_reference(xs, None, wu, wd, sizes, moe_ops.relu2,
                                   transposed_up=True)
    assert not np.any(np.asarray(want[31:])) and np.any(np.asarray(want[:31]))
    got = es.expert_stream_ffn(xs, None, wu, wd, sizes, moe_ops.relu2,
                               transposed_up=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # ... and with W_up as it is written, [e, d, f]
    plain = es.expert_stream_ffn(xs, None, jnp.swapaxes(wu, 1, 2), wd, sizes,
                                 moe_ops.relu2, interpret=True)
    np.testing.assert_allclose(plain, want, atol=2e-5, rtol=0)
    calls = []
    dot = jax.lax.ragged_dot
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda *a, **kw: (calls.append(1), dot(*a, **kw))[1])
    rag = moe_ops._ragged_ffn(xs, None, wu, wd, sizes, moe_ops.relu2,
                              transposed_up=True)
    assert len(calls) == 2      # two matrices an expert, not three
    np.testing.assert_allclose(rag[:31], want[:31], atol=2e-5, rtol=0)


def test_the_gate_takes_the_published_width_and_names_what_it_refuses():
    """768 rows over 64 experts of [2688, 1856] ungated in bfloat16: taken
    (``W_up`` in three row blocks, ``W_down`` in three column blocks); a
    hidden size of no whole lane tiles and an expert width of no whole
    sublane tiles are refused by name; the seven gated geometries keep
    the plan they had (one of them held here)."""
    bf16 = jnp.bfloat16
    assert es.expert_stream_gate(768, 64, 2688, 1856, bf16,
                                 gated=False) is None
    plan = es.expert_stream_plan(768, 64, 2688, 1856, bf16, gated=False)
    assert (plan["tile"], plan["rows"], plan["nkd"], plan["nkf"],
            plan["down_cols"]) == (32, 768, 3, 3, True)
    assert "hidden size 2700" in es.expert_stream_gate(768, 64, 2700, 1856,
                                                      bf16)
    assert "expert width 1850" in es.expert_stream_gate(768, 64, 2688, 1850,
                                                       bf16, gated=False)
    laguna = es.expert_stream_plan(160, 128, 3072, 1024, bf16)
    assert (laguna["nkd"], laguna["nkf"], laguna["down_cols"]) == (1, 1,
                                                                   False)
    with pytest.raises(ValueError, match="no numbers of its own"):
        es.expert_stream_ffn(
            jnp.zeros((8, 128)), None, jnp.zeros((2, 128, 16)),
            jnp.zeros((2, 16, 128)), jnp.asarray([4, 4], jnp.int32),
            moe_ops.relu2, act_params=jnp.ones((2, 1)), interpret=True)


@pytest.mark.parametrize("form", ["stream", "grouped"])
def test_the_expert_layer_counts_the_form_each_pass_took(
        form, rng, request):
    """``moe/pass_form.stream`` where the rule and the kernel's gate take
    the pass, ``.grouped`` else: once a traced pass."""
    if form == "stream":
        request.getfixturevalue("stream_here")
    n, e, k, d, f = 11, 6, 3, 128, 24
    u, wu, wd = _ungated(rng, n, e, d, f)
    idx = jnp.asarray(np.argsort(rng.rand(n, e), axis=1)[:, :k], jnp.int32)
    w = jnp.asarray(rng.rand(n, k).astype("float32"))
    before = mx.counter("moe/pass_form." + form).value
    y, _ = moe_ops.expert_layer(u, idx, w, None, wu, wd,
                                activation=moe_ops.relu2, transposed_up=True)
    assert mx.counter("moe/pass_form." + form).value == before + 1
    want = sum(np.asarray(w)[:, j, None] * np.stack([
        np.asarray(moe_ops.relu2(u[i] @ wu[idx[i, j]].T) @ wd[idx[i, j]])
        for i in range(n)]) for j in range(k))
    np.testing.assert_allclose(y, want, atol=5e-5, rtol=1e-5)


# -- (e) a layer in no cache group ----------------------------------------------

def test_a_layer_may_stand_in_no_cache_group():
    """Layers 1 and 3 of four keep nothing: the cache builds, holds a pool
    layer and a state layer only, maps the others nowhere, and a group
    that names a layer past the last is refused."""
    groups = [CacheGroup("global", (2,), None, 8, KV),
              CacheGroup("ssm", (0,), None, 0, STATE)]
    kw = dict(dtype=jnp.float32, q_per_kv=16, recurrence="ssd",
              slot_state=(4, 128, 64, 3, 768))
    ops = PagedKVCache(4, 2, 16, 3, 64, 8, 8, groups=groups, **kw)
    state = ops.init_state()
    assert state["k"].shape[0] == 1 and state["s.ssm"].shape[0] == 1
    assert sorted(ops._where) == [2] and sorted(ops._where_state) == [0]
    with pytest.raises(ValueError, match="name layers of 0..3"):
        PagedKVCache(4, 2, 16, 3, 64, 8, 8, groups=[
            groups[0], CacheGroup("ssm", (4,), None, 0, STATE)], **kw)


# -- (f) the share ---------------------------------------------------------------

def test_two_shares_and_the_shared_expert_once_make_the_whole_layer(rng):
    """EP2: rank 0 holds experts 0-7, rank 1 experts 8-15, each its own
    rows' router and shared expert. The two shares' ROUTED parts plus the
    shared expert counted once are the uncut reference's whole ``E``
    layer, by the program's ``_moe`` and by the reference's own share."""
    whole = toy_model(pattern="E", experts_held=range(16))
    lp = whole.params["layers"][0]
    u = jnp.asarray(rng.randn(37, 64).astype("float32"))
    model = published(whole)
    want = np.asarray(ref.expert_part(lp, u, model))
    none = dict(lp, wu=lp["wu"][:0], wd=lp["wd"][:0])
    shared = np.asarray(ref.expert_part(none, u, model, held_ids=(),
                                        shared=True))
    routed_ref, routed_prog = [], []
    for held in (range(8), range(8, 16)):
        at = jnp.asarray(list(held))
        share = dict(lp, wu=lp["wu"][at], wd=lp["wd"][at])
        routed_ref.append(np.asarray(ref.expert_part(
            share, u, model, held_ids=list(held), shared=False)))
        y, stats = nm._moe(toy_cfg(pattern="E", experts_held=held), share, u,
                           None)
        routed_prog.append(np.asarray(y) - shared)
        assert int(stats["held_pairs"]) == int(np.isin(np.asarray(
            ref.route(lp, u, 3, 2.5)[0]), list(held)).sum())
    for parts in (routed_ref, routed_prog):
        assert all(np.abs(p).max() > 0.01 for p in parts)
        np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                                   atol=5e-5, rtol=0)


# -- (g) the configuration file ---------------------------------------------------

def test_the_configuration_file_states_the_published_widths():
    """``grid/configs/nemotron-3-nano-ep2-serve.json`` against the
    catalog's row where the catalog is installed: every number of the
    published config under its own key, but the three cut ones; the floors
    of a cut (a whole nine-layer period, 64 >= 8 experts, half >= an
    eighth of the vocabulary)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "grid", "configs",
                           "nemotron-3-nano-ep2-serve.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 64, 65536)
    assert config["experts_held"] == list(range(64))
    assert ref.pattern(config) == PATTERN
    assert "no_position_embedding" in config["assumed"]
    assert config["deployment"].startswith("2 chips share each layer")
    for key, value in (("hidden_size", 2688), ("mamba_num_heads", 64),
                       ("mamba_head_dim", 64), ("n_groups", 8),
                       ("ssm_state_size", 128), ("conv_kernel", 4),
                       ("chunk_size", 128), ("moe_intermediate_size", 1856),
                       ("moe_shared_expert_intermediate_size", 3712),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 2), ("head_dim", 128),
                       ("num_experts_per_tok", 6),
                       ("routed_scaling_factor", 2.5)):
        assert config[key] == value
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"])


def test_the_seeds_are_mamba2s_and_the_router_is_float32(toy):
    lp = toy.params["layers"][0]
    step = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 0.001 <= step.min() and step.max() <= 0.1
    assert 1.0 <= np.exp(np.asarray(lp["a_log"])).min()
    assert np.exp(np.asarray(lp["a_log"])).max() <= 16.0
    assert np.all(np.asarray(lp["dskip"]) == 1)
    moe = toy.params["layers"][1]
    assert moe["wr"].dtype == moe["br"].dtype == jnp.float32
    assert moe["wr"].shape == (64, 16)
    assert moe["wu"].shape == moe["wd"].shape == (8, 24, 64)
    assert "wg" not in moe and "sg" not in moe
    attn = toy.params["layers"][5]
    assert sorted(attn) == ["g", "wk", "wo", "wq", "wv"]
    assert blocks.held_experts(toy.cfg) == tuple(HELD)

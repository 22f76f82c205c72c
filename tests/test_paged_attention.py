"""Ragged paged-attention Pallas decode kernel + device-side sampled
decoding (ISSUE 13 tentpole coverage).

Kernel parity runs in Pallas interpret mode on CPU against the XLA
page-gather + ``decode_attention`` reference — same tolerance discipline
as the sparse_adam kernel tests (rtol/atol 1e-6 on live rows, BIT-exact
indifference to garbage beyond ``ctx_len``). A bf16 pool's grouped fold
(several query heads a KV head, or heads of whole lane tiles: ``d_head %
128 == 0``) hands the MXU its probabilities as bf16 high and low halves:
against a float32 reference over the SAME bf16 values it is held to that
rounding's bound (``P_ROUNDOFF``).
Engine-level tests arm
``FLAGS_paged_attention_kernel=interpret`` and assert the full serving
stack emits the same token streams either way, that ``temperature=0`` is
bit-identical to greedy, that seeded sampling is invariant to
``decode_fuse`` width, and that top-k can never select outside the top-k
set.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import decoder_lm
from paddle_tpu.ops.pallas_kernels import paged_attention as pa

_MODEL = None


def get_model():
    global _MODEL
    if _MODEL is None:
        cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                       n_head=2, max_seq=64)
        _MODEL = decoder_lm.DecoderLM(cfg, seed=0)
    return _MODEL


@pytest.fixture(autouse=True)
def _restore_flag():
    yield
    set_flag("paged_attention_kernel", "auto")


def make_pool(rng, slots, pages_per_slot, num_pages, page_size, h, d):
    """Synthetic one-layer paged KV pool ``[rows, H*D]`` + a permuted page
    table: one layer of the layout PagedKVCache hands the kernel."""
    k = rng.randn(num_pages * page_size, h * d).astype(np.float32)
    v = rng.randn(num_pages * page_size, h * d).astype(np.float32)
    pt = np.stack([rng.permutation(num_pages)[:pages_per_slot]
                   for _ in range(slots)]).astype(np.int32)
    q = rng.randn(slots, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pt)


def as_pool(x, dtype):
    """``x`` rounded to ``dtype`` as the pool stores it, and the SAME
    values in float32 for the reference."""
    stored = jnp.asarray(x).astype(dtype)
    return stored, stored.astype(jnp.float32)


# A bf16 pool's grouped fold hands the second product each probability as
# its bf16 high and low halves: 16 bits, so ``p`` is off by at most 2**-17
# of itself and the output, a weighted mean of V rows under weights that sum
# to l within the same bound, by at most 2**-16 * max|v| (float32 pools and
# the fold over the head-membership matrices, which round nothing, keep
# 1e-6).
P_ROUNDOFF = 2.0 ** -16


def tolerance(dtype, g, v, d=16):
    """By the geometry, as the kernel chooses its fold: one query head a KV
    head of ``d`` lanes that are NOT whole lane tiles goes through the
    head-membership matrices in float32 whatever the pool's type."""
    per_lane = g == 1 and d % 128 != 0
    if jnp.dtype(dtype) == jnp.float32 or per_lane:
        return dict(rtol=1e-6, atol=1e-6)
    return dict(rtol=1e-6, atol=P_ROUNDOFF * float(jnp.max(jnp.abs(v))))


# -- kernel parity (interpret mode) ------------------------------------------

@pytest.mark.parametrize("d", [16, 128], ids=["d16", "lane_tile_heads"])
def test_kernel_matches_gather_at_ragged_lengths(rng, d):
    slots, h, ps, pps = 5, 2, 8, 8
    q, k, v, pt = make_pool(rng, slots, pps, 24, ps, h, d)
    ctx = jnp.asarray([1, 7, 8, 33, 64], jnp.int32)  # ragged, page-straddling
    want = pa.gather_reference(q, k, v, pt, ctx, ps, sm_scale=d ** -0.5)
    for bp in (1, 3, 4, None):  # incl. non-divisor + tuned-table default
        got = pa.paged_decode_attention(q, k, v, pt, ctx, page_size=ps,
                                        sm_scale=d ** -0.5, block_pages=bp,
                                        interpret=True)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg="block_pages=%r" % (bp,))


@pytest.mark.parametrize("g,d", [(1, 8), (6, 8), (1, 128)],
                         ids=["g1", "g6", "g1_lane_tile_heads"])
@pytest.mark.parametrize("dtype,junk", [("float32", (1e4, -1e4)),
                                        ("bfloat16", (np.inf, np.nan))],
                         ids=["float32_huge", "bfloat16_inf_nan"])
def test_garbage_pages_move_no_output_bit(rng, dtype, junk, g, d):
    """Pages beyond ctx_len belong to OTHER requests (or are stale) — the
    kernel must ignore them EXACTLY, not approximately: trashing every
    invalid row (large finite values; Inf and NaN in a bf16 pool, for both
    folds, the grouped one at one query head of a whole lane tile too)
    moves no output bit."""
    slots, h, ps, pps = 4, 2, 8, 4
    _, k, v, pt = make_pool(rng, slots, pps, 12, ps, h, d)
    q = jnp.asarray(rng.randn(slots, g * h, d).astype(np.float32))
    ctx = jnp.asarray([3, 8, 17, 29], jnp.int32)

    def run(kp, vp):
        return np.asarray(pa.paged_decode_attention(
            q, jnp.asarray(kp).astype(dtype), jnp.asarray(vp).astype(dtype),
            pt, ctx, page_size=ps, block_pages=2, interpret=True))

    clean = run(k, v)
    kp, vp = np.asarray(k).copy(), np.asarray(v).copy()
    used = np.zeros(kp.shape[0], bool)
    for s in range(slots):
        n = int(ctx[s])
        for j in range(pps):
            row0 = int(pt[s, j]) * ps
            live = max(0, min(ps, n - j * ps))
            used[row0:row0 + live] = True
    stale = np.flatnonzero(~used)
    kp[stale], vp[stale] = junk
    vp[stale[::2]] = junk[0]  # both kinds of junk in V as well
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(run(kp, vp), clean)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_layer_of_a_pool_is_bit_equal_to_the_layer_alone(rng, layer):
    """The engine's entry: the whole ``[n_layer, rows, H*D]`` pool and a
    layer, indexed inside the kernel's page DMA. Neighbouring layers are
    poisoned; the output must be the single-layer call's, bit for bit —
    for a Python int and for a traced scalar alike."""
    import jax

    slots, h, d, ps, pps = 4, 2, 16, 8, 4
    q, k, v, pt = make_pool(rng, slots, pps, 16, ps, h, d)
    ctx = jnp.asarray([1, 8, 19, 32], jnp.int32)
    alone = pa.paged_decode_attention(q, k, v, pt, ctx, page_size=ps,
                                      block_pages=2, interpret=True)

    def pooled(x, fill):
        layers = [jnp.full_like(x, fill * (i + 1)) for i in range(3)]
        layers[layer] = x
        return jnp.stack(layers)

    kp, vp = pooled(k, 1e4), pooled(v, -1e4)
    got = pa.paged_decode_attention(q, kp, vp, pt, ctx, page_size=ps,
                                    layer=layer, block_pages=2,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    traced = jax.jit(lambda l: pa.paged_decode_attention(
        q, kp, vp, pt, ctx, page_size=ps, layer=l, block_pages=2,
        interpret=True))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(alone))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("g,d", [(1, 16), (7, 16), (1, 128)],
                         ids=["one_query_head_a_kv_head",
                              "seven_padded_to_eight",
                              "one_of_a_lane_tile_padded_to_eight"])
def test_rowless_slots_cost_no_read_and_return_zero(rng, g, d, dtype):
    """``ctx_len`` 0 = the slot holds nothing (the cache hands the kernel
    LIVE lengths). Such slots sit among live ones with their page-table
    rows on pages of Inf and NaN: they come back exactly 0.0, and the live
    slots are bit-equal to the same call without them."""
    slots, h, ps, pps = 6, 2, 8, 4
    live_pages = 16
    _, k, v, pt = make_pool(rng, slots, pps, live_pages, ps, h, d)
    (k, k32), (v, v32) = as_pool(k, dtype), as_pool(v, dtype)
    q = jnp.asarray(rng.randn(slots, g * h, d).astype(np.float32))
    q = as_pool(q, dtype)[1]
    ctx = np.asarray([5, 0, 32, 0, 17, 0], np.int32)
    dead = ctx == 0
    # four more pages, poisoned, and only the rowless slots point at them
    poison = np.full((4 * ps, h * d), np.nan, np.float32)
    poison[::2] = np.inf
    k = jnp.concatenate([k, jnp.asarray(poison).astype(dtype)])
    v = jnp.concatenate([v, jnp.asarray(-poison).astype(dtype)])
    pt = np.asarray(pt).copy()
    pt[dead] = live_pages + np.arange(4)
    kw = dict(page_size=ps, sm_scale=d ** -0.5, block_pages=2, interpret=True)
    got = np.asarray(pa.paged_decode_attention(
        q, k, v, jnp.asarray(pt), jnp.asarray(ctx), **kw))
    np.testing.assert_array_equal(got[dead], np.zeros_like(got[dead]))
    alone = np.asarray(pa.paged_decode_attention(
        q[~dead], k, v, jnp.asarray(pt[~dead]), jnp.asarray(ctx[~dead]),
        **kw))
    np.testing.assert_array_equal(got[~dead], alone)
    want = pa.gather_reference(q[~dead], k32, v32, jnp.asarray(pt[~dead]),
                               jnp.asarray(ctx[~dead]), ps, sm_scale=d ** -0.5)
    np.testing.assert_allclose(alone, want, **tolerance(dtype, g, v32, d))


@pytest.mark.parametrize("g,d", [(1, 16), (6, 16), (7, 16), (9, 16),
                                 (1, 128)],
                         ids=["1", "6", "7", "9", "1_lane_tile_heads"])
def test_bf16_pool_against_float32_reference_over_the_same_values(rng, g, d):
    """A bf16 pool under 1, 6, 7 and 9 query heads a KV head (GPT-2's,
    Laguna's full layers', SmallThinker's, Laguna's rings') and under one
    query head of a whole lane tile (Ouro's): the grouped fold multiplies
    in bf16 with a float32 accumulator and keeps its softmax state in
    float32, so against a float32 reference over the same bf16 values it is
    off by the rounding of ``p`` to 16 bits alone; G = 1 over heads that
    are not whole lane tiles widens the pool and keeps 1e-6."""
    slots, h, ps, pps = 5, 2, 8, 8
    q, k, v, pt = make_pool(rng, slots, pps, 40, ps, h, d)
    (k, k32), (v, v32) = as_pool(k, jnp.bfloat16), as_pool(v, jnp.bfloat16)
    q = as_pool(rng.randn(slots, g * h, d).astype(np.float32),
                jnp.bfloat16)[1]  # float32-typed: the result is not rounded
    ctx = jnp.asarray([1, 7, 16, 33, 64], jnp.int32)
    want = pa.gather_reference(q, k32, v32, pt, ctx, ps, sm_scale=d ** -0.5)
    for bp in (1, 3, None):
        got = pa.paged_decode_attention(q, k, v, pt, ctx, page_size=ps,
                                        sm_scale=d ** -0.5, block_pages=bp,
                                        interpret=True)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, err_msg="block_pages=%r" % bp,
                                   **tolerance(jnp.bfloat16, g, v32, d))


# ps = 8 and two pages a wave: a wave is 16 rows, a slot has 8 pages
WAVE_EDGES = {
    "one_row": ([1], 2),
    "exactly_one_wave": ([16], 2),
    "one_row_past_a_wave": ([17], 2),
    "exactly_three_waves_odd": ([48], 2),
    "exactly_four_waves_even": ([64], 2),
    "block_pages_not_dividing_the_slots_pages": ([64, 41], 3),
    "a_short_slot_after_a_long_one": ([64, 3, 50, 1], 2),
    "a_rowless_slot_between_two_live_ones": ([40, 0, 23], 2),
    "one_wave_holds_the_whole_slot": ([64, 9], 8),
}


@pytest.mark.parametrize("dtype,g,d", [
    ("float32", 1, 16), ("float32", 7, 16), ("bfloat16", 1, 16),
    ("bfloat16", 6, 16), ("float32", 1, 128), ("bfloat16", 1, 128)],
    ids=["float32-1", "float32-7", "bfloat16-1", "bfloat16-6",
         "float32-1-lane_tile_heads", "bfloat16-1-lane_tile_heads"])
@pytest.mark.parametrize("edge", sorted(WAVE_EDGES))
def test_double_buffered_waves_at_their_edges(rng, edge, dtype, g, d):
    """Wave ``w + 1`` is copied into the other buffer while wave ``w`` is
    folded, and both buffers keep what the slot before left in them: the
    lengths at which a start, a wait or a mask could slip by one."""
    ctx, bp = WAVE_EDGES[edge]
    slots, h, ps, pps = len(ctx), 2, 8, 8
    _, k, v, pt = make_pool(rng, slots, pps, slots * pps, ps, h, d)
    (k, k32), (v, v32) = as_pool(k, dtype), as_pool(v, dtype)
    q = as_pool(rng.randn(slots, g * h, d).astype(np.float32), dtype)[1]
    ctx = jnp.asarray(ctx, jnp.int32)
    got = np.asarray(pa.paged_decode_attention(
        q, k, v, pt, ctx, page_size=ps, sm_scale=d ** -0.5, block_pages=bp,
        interpret=True))
    live = np.asarray(ctx) > 0
    want = pa.gather_reference(q[live], k32, v32, pt[live], ctx[live], ps,
                               sm_scale=d ** -0.5)
    np.testing.assert_allclose(got[live], want,
                               **tolerance(dtype, g, v32, d))
    np.testing.assert_array_equal(got[~live], 0.0)


def test_wave_buffers_fit_their_budget_at_every_served_row_width():
    """``_block_pages`` clamps whatever the table says to two K and two V
    buffers of a wave in the pool's type."""
    for hd, itemsize in ((512, 2), (768, 2), (1024, 2), (768, 4), (4096, 4)):
        for want in (1, 8, 16, 32, 1 << 20):
            bp = pa._block_pages(want, 16, 1024, 16384, hd, itemsize)
            assert 1 <= bp <= want
            assert 4 * bp * 16 * hd * itemsize <= pa._VMEM_WAVE_BUDGET \
                or bp == 1
    assert pa._block_pages(64, 16, 4, 64, 128, 2) == 4  # the slot's pages


@pytest.mark.parametrize("d", [64, 128], ids=["d64", "lane_tile_heads"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_and_gather_agree_under_a_traced_cache_step(rng, dtype, d):
    """A looped model's cache: three cache layers a layer, the step a
    TRACED scalar inside a device loop whose carry is the pool. Every
    (layer, step) call of ``PagedKVCache.decode_attention`` by the kernel
    (interpreted, in the fold the head's width chooses) equals the gather
    path's, each over its own pool layer: the others hold other rows."""
    import jax

    from paddle_tpu.serving.kv_cache import PagedKVCache

    ops = PagedKVCache(2, 2, d, 3, 32, 8, 12, dtype=dtype, cache_steps=3)
    state = ops.init_state()
    for key in ("k", "v"):
        state[key] = jnp.asarray(rng.randn(*state[key].shape)).astype(dtype)
    state["pt"] = jnp.asarray(rng.permutation(12).reshape(3, 4), jnp.int32)
    q = jnp.asarray(rng.randn(3, 2, d)).astype(dtype)
    ctx = jnp.asarray([5, 32, 17], jnp.int32)
    active = jnp.asarray([True, True, False])

    def looped(state):
        def body(t, out):
            return out.at[t].set(jnp.stack([
                ops.decode_attention(state, layer, q, ctx, active,
                                     sm_scale=d ** -0.5, step=t)
                for layer in range(2)]))

        return jax.lax.fori_loop(0, 3, body,
                                 jnp.zeros((3, 2, 3, 2, d), dtype))

    got = {}
    for mode in ("off", "interpret"):
        set_flag("paged_attention_kernel", mode)
        assert (ops.kernel_mode()[0] is None) == (mode == "off")
        assert ops.kernel_folds() == ({} if mode == "off" else {
            "global": "grouped" if d == 128 else "per_lane"})
        got[mode] = np.asarray(jax.jit(looped)(state), np.float32)
    live = got["off"][:, :, :2]
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" \
        else dict(rtol=0, atol=2.0 ** -6 * np.abs(live).max())   # two ulps
    np.testing.assert_allclose(got["interpret"][:, :, :2], live, **tol)
    # each (layer, step) read a pool layer of its own
    flat = live.reshape(6, -1)
    assert np.abs(flat[:, None] - flat[None]).max(axis=-1)[
        ~np.eye(6, dtype=bool)].min() > 0.01
    # and it is the one the int names: layer-major, ``layer * 3 + step``
    want = pa.gather_reference(q, state["k"][1 * 3 + 2], state["v"][1 * 3 + 2],
                               state["pt"], jnp.where(active, ctx, 0), 8,
                               sm_scale=d ** -0.5)
    np.testing.assert_allclose(live[2, 1], np.asarray(want, np.float32)[:2],
                               **tol)


@pytest.mark.parametrize("h,g,d,fold,rows", [
    (4, 1, 64, "per_lane", 1),     # GPT-2's: half a lane tile a head
    (2, 1, 128, "grouped", 8),     # Ouro's: one query head, a whole tile
    (1, 1, 256, "grouped", 8),
    (2, 6, 128, "grouped", 8),     # Laguna's full layers
    (2, 9, 128, "grouped", 16),
    (2, 1, 192, "per_lane", 1),    # a tile and a half cannot be sliced out
], ids=["1x64", "1x128", "1x256", "6x128", "9x128", "1x192"])
def test_the_fold_is_chosen_by_the_heads_width_not_by_g_alone(rng, h, g, d,
                                                              fold, rows):
    """One query head a KV head takes the grouped fold exactly where a head
    is whole lane tiles: the call's ``q`` and result carry the query heads
    of a KV head padded to whole sublanes there (the trace readers' label)
    and one row for every other width, and what the cache reports is what
    the call was traced with."""
    import jax

    from paddle_tpu.serving.kv_cache import PagedKVCache

    assert pa.paged_attention_fold(g, d) == fold
    _, k, v, pt = make_pool(rng, 2, 2, 4, 8, h, d)
    q = jnp.asarray(rng.randn(2, g * h, d).astype(np.float32))
    ctx = jnp.asarray([3, 9], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: pa.paged_decode_attention(
        *a, page_size=8, interpret=True))(q, k, v, pt, ctx)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.outvars[0].aval.shape == (2, rows, h * d)
    set_flag("paged_attention_kernel", "interpret")
    ops = PagedKVCache(1, h, d, 2, 16, 8, 4, q_per_kv=g)
    assert ops.kernel_folds() == {"global": fold}


def test_pool_shape_errors_are_typed(rng):
    """A pool without a layer, a layer outside the pool, and a ``[rows, H,
    D]`` layer (the layout this kernel no longer takes) all raise before
    anything is traced."""
    q, k, v, pt = make_pool(rng, 2, 2, 4, 8, 2, 8)
    ctx = jnp.asarray([3, 9], jnp.int32)
    kw = dict(page_size=8, interpret=True)
    with pytest.raises(ValueError, match="with a layer"):
        pa.paged_decode_attention(q, k[None], v[None], pt, ctx, **kw)
    with pytest.raises(ValueError, match="outside a pool of 1"):
        pa.paged_decode_attention(q, k[None], v[None], pt, ctx, layer=1,
                                  **kw)
    with pytest.raises(ValueError, match="with a layer"):
        pa.paged_decode_attention(q, k.reshape(-1, 2, 8),
                                  v.reshape(-1, 2, 8), pt, ctx, **kw)


# -- engine-level parity + sampling ------------------------------------------

def _serve(stream, flag_mode, decode_fuse=1, **submit_kw):
    """Drive one engine over ``stream`` with the kernel flag pinned to
    ``flag_mode``; returns ([tokens_out per request], engine stats)."""
    set_flag("paged_attention_kernel", flag_mode)
    try:
        eng = serving.ServingEngine(get_model(), serving.ServingConfig(
            slots=2, page_size=8, max_seq=64, prompt_buckets=(16,),
            decode_fuse=decode_fuse))
        reqs = [eng.submit(p, m, **submit_kw) for p, m in stream]
        eng.run()
        stats = eng.stats()
        eng.close()
        return [list(r.tokens_out) for r in reqs], stats
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_engine_kernel_vs_gather_token_parity(rng):
    stream = [(list(rng.randint(0, 64, int(n))), 6) for n in (3, 9, 14)]
    got, stats = _serve(stream, "interpret")
    want, base_stats = _serve(stream, "off")
    assert got == want, "kernel decode diverged from the gather path"
    assert stats["decode_kernel"] == "paged"
    # which table answered the wave's width, and the fold the geometry
    # takes: two heads of 16 lanes are no lane tile
    source, fold = stats["decode_kernel_source"].split("; ")
    assert source in ("tuned", "shipped", "default")
    assert fold == "fold: per_lane"
    assert base_stats["decode_kernel"] == "gather"
    assert base_stats["decode_kernel_source"] == "n/a"


def test_temperature_zero_bit_identical_to_greedy(rng):
    stream = [(list(rng.randint(0, 64, int(n))), 8) for n in (4, 11)]
    greedy, _ = _serve(stream, "off")
    # explicit temperature=0 (with sampling params set) must stay greedy
    t0, _ = _serve(stream, "off", temperature=0.0, top_k=5, seed=123)
    assert t0 == greedy, "temperature=0 is not bit-identical to greedy"


def test_seeded_sampling_invariant_to_decode_fuse(rng):
    """The RNG is keyed per (seed, absolute position), not per dispatch —
    a request's stream must not depend on how many decode steps the
    engine fuses into one lax.scan chunk."""
    stream = [(list(rng.randint(0, 64, int(n))), 8) for n in (5, 12, 7)]
    kw = dict(temperature=0.8, top_k=5, seed=4242)
    f1, _ = _serve(stream, "off", decode_fuse=1, **kw)
    f4, _ = _serve(stream, "off", decode_fuse=4, **kw)
    assert f1 == f4, "sampled stream depends on decode_fuse width"
    greedy, _ = _serve(stream, "off")
    assert f1 != greedy, "temperature=0.8 never diverged from greedy"


def test_top_k_never_selects_outside_top_k(rng):
    k = 3
    set_flag("paged_attention_kernel", "off")
    eng = serving.ServingEngine(get_model(), serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(16,),
        collect_logits=True))
    reqs = [eng.submit(list(rng.randint(0, 64, n)), 8,
                       temperature=1.5, top_k=k, seed=7 + n)
            for n in (4, 10)]
    eng.run()
    checked = 0
    for r in reqs:
        rows = eng.captured_logits(r)
        assert len(rows) == len(r.tokens_out), (len(rows), len(r.tokens_out))
        for tok, row in zip(r.tokens_out, rows):
            top = np.argsort(np.asarray(row, np.float32))[-k:]
            assert tok in top, "token %d outside top-%d set %s" % (
                tok, k, top)
            checked += 1
    eng.close()
    assert checked >= 16


def test_sampled_requests_mix_with_greedy_in_one_batch(rng):
    """Per-request sampling params ride slot state — one continuous batch
    serves greedy and sampled requests side by side, and the greedy ones
    match a pure-greedy run exactly."""
    prompts = [list(rng.randint(0, 64, n)) for n in (6, 6, 9)]
    set_flag("paged_attention_kernel", "off")
    eng = serving.ServingEngine(get_model(), serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(16,)))
    r_greedy = eng.submit(prompts[0], 8)
    r_sampled = eng.submit(prompts[1], 8, temperature=0.9, seed=99)
    r_greedy2 = eng.submit(prompts[2], 8)
    eng.run()
    eng.close()
    pure, _ = _serve([(prompts[0], 8), (prompts[2], 8)], "off")
    assert list(r_greedy.tokens_out) == pure[0]
    assert list(r_greedy2.tokens_out) == pure[1]
    assert len(r_sampled.tokens_out) == 8

"""Motif-3-Beta (grouped differential attention over latent rows, a ring of
the last W rows on three layers in four beside one page pool, four
residual streams mixed by Sinkhorn matrices, PolyNorm experts) through the
serving stack, against its plain float32 reference
(``grid/reference/motif3.py``), at a toy size on the CPU: layers window
(dense), window, full, window; d 64 x 4 streams, 10 query heads over 2 KV
heads (8 signal, 2 noise), latent 16 + 8 rotary, nope 16, v 16, window 16,
16 experts top-4 of width 32 and one shared, page 8. LOGITS are compared,
never sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (absorbed products against expanded
heads, the subtraction on latent outputs against the one after the value
up-projection, a ring's rows against a masked softmax, grouped matmul over
sorted rows against a dense loop over experts): the worst logit difference
read was 4e-6 on logits of standard deviation 0.9. ``TOL`` = 5e-5 is ten
times that and far under what a lower precision gives
(``test_a_lower_precision_fails`` asks for ten times ``TOL``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import motif3 as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import motif3 as mf
from paddle_tpu.ops import attention_ops, moe_ops
from paddle_tpu.ops.pallas_kernels import expert_stream as es
from paddle_tpu.ops.pallas_kernels import mla_attention as mla
from paddle_tpu.serving.kv_cache import LATENT, CacheGroup, LatentPagedCache

TOL = 5e-5
W = 16
TYPES = ["window", "window", "full", "window"]
SCALING = {"original_max_position_embeddings": 32, "factor": 4,
           "beta_fast": 32, "beta_slow": 1}
PUBLISHED = {  # the toy under the published config's own keys
    "num_hidden_layers": 4, "layer_types": TYPES, "hidden_size": 64,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "sliding_window": W,
    "rms_norm_eps": 1e-5, "mhc_expansion_rate": 4, "mhc_sinkhorn_iters": 20,
    "hidden_clamp": 1e6, "polynorm_output_scale": 0.5,
    "polynorm_bias_clamp": 0.5, "swa_rope_theta": 1e4, "rope_theta": 1e4,
    "rope_scaling": SCALING, "experts_top_k": 4, "route_scale": 2.0}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=4, d_model=64, n_head=10, n_kv_head=2,
              q_rank=32, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
              layer_types=TYPES, window=W, d_dense=128, dense_layers=(0,),
              n_expert=16, top_k=4, d_expert=32, routed_scale=2.0,
              rope_scaling=SCALING, max_seq=128, dtype="float32")
    kw.update(over)
    return mf.Motif3Config(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention, the gates and routing are decisive at d = 64; the residual
    maps' and PolyNorm's numbers keep their own."""
    def scale(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return a * 6.0 if a.ndim > 1 and name not in ("pn", "pa", "pm") else a

    return jax.tree_util.tree_map_with_path(scale, params)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return mf.Motif3LM(cfg, params=_scaled(mf.init_params(cfg, 3)))


@pytest.fixture(scope="module")
def toy():
    return toy_model()


def reference_rows(model, seq, rows, **over):
    return np.asarray(ref.forward(model.params, dict(PUBLISHED, **over),
                                  np.asarray(seq, np.int32), rows=rows))


def _prefill(model, seq, bucket=64):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return model.prefill(model.params, jnp.asarray(toks),
                         jnp.asarray([len(seq)], jnp.int32))


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=8, max_seq=128,
               prompt_buckets=(8, 16, 32, 64), num_pages=40,
               collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


# -- (a) prefill against the reference's full forward --------------------------


@pytest.mark.parametrize("types,n", [
    (TYPES, 5), (TYPES, 57), (["window"], 41), (["full"], 41)])
def test_prefill_equals_the_reference(types, n, rng):
    """The whole toy short of and past the window, then ONE window layer
    and ONE full layer alone past it (banded against causal; the rows a
    prefill hands the cache are the reference's latent rows)."""
    model = toy_model(n_layer=len(types), layer_types=types)
    seq = rng.randint(0, 96, n)
    logits, rows = _prefill(model, seq)
    want = reference_rows(model, seq, np.arange(n),
                          num_hidden_layers=len(types), layer_types=types)
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    assert len(rows) == len(types) and rows[0][0].shape == (1, 64, 24)


@pytest.mark.parametrize("what", ["maps", "sinkhorn_2"])
def test_a_lower_precision_fails(toy, what, rng):
    """The residual maps (and with them the heads' lambda) computed in
    bfloat16, or a Sinkhorn of 2 iterations for the published 20: each
    moves a logit by far more than ``TOL``."""
    over = (dict(maps_dtype="bfloat16") if what == "maps"
            else dict(sinkhorn_iters=2))
    model = mf.Motif3LM(toy_cfg(**over), params=toy.params)
    seq = rng.randint(0, 96, 41)
    logits, _ = _prefill(model, seq)
    err = np.abs(np.asarray(logits[0, :41])
                 - reference_rows(toy, seq, np.arange(41))).max()
    assert err > 10 * TOL, err


@pytest.mark.parametrize("maps", ["float32", "bfloat16"])
def test_the_norm_of_the_streams_sum_sees_the_maps_precision(toy, maps, rng):
    """What the chip's comparison holds beside its two rank limits
    (``ref.STREAM_NORM_LIMIT``): the norm of the streams' sum before the
    final norm, the program's prefill forward against the reference's, at
    the median over the rows. As stated it reads rounding; with the maps
    and the heads' lambda at bfloat16's precision it is past the limit,
    while every token still ranks where the reference ranks it."""
    model = mf.Motif3LM(toy_cfg(maps_dtype=maps), params=toy.params)
    seq = [int(t) for t in rng.randint(0, 96, 57)]
    toks = np.zeros((1, 64), np.int32)
    toks[0, :57] = seq
    served, _ = mf.prefill_forward(model.params, model.cfg, jnp.asarray(toks),
                                   jnp.asarray([57], jnp.int32))
    # the reference's one teacher-forced pass gives the sums beside the
    # gaps: here over a prompt of 41 of the tokens and the 16 that follow
    gaps, plain = ref.teacher_forced(toy.params, PUBLISHED, seq[:41],
                                     seq[41:], pad_to=64, sum_rows=41)
    assert gaps.shape == (16,) and plain.shape == (41, 64)
    want = np.asarray(jnp.sum(ref.hidden(
        toy.params, PUBLISHED, jnp.asarray(toks[0])), axis=1))[:57]
    np.testing.assert_allclose(plain, want[:41], atol=1e-5, rtol=0)
    gap = ref.stream_norm_gap(np.asarray(served[0, :57]), want)
    if maps == "float32":
        assert gap < 1e-5
    else:
        assert gap > ref.STREAM_NORM_LIMIT
    assert ref.stream_norm_gap(want * 1.001, want) == pytest.approx(1e-3,
                                                                    rel=1e-3)


# -- (b) the residual path and the heads' subtraction --------------------------


def test_h_res_is_doubly_stochastic_and_two_iterations_are_not(toy, rng):
    lp = toy.params["layers"][1]
    x = jnp.asarray(rng.randn(7, 4, 64).astype("float32"))
    major = jnp.moveaxis(x, -2, 0)          # the served streams: [n, B, d]
    _, _, h_res = blocks.mix_in(toy.cfg, lp, "a", major, lp["g1"])
    for axis in (-1, -2):
        np.testing.assert_allclose(np.asarray(h_res.sum(axis)), 1.0,
                                   atol=1e-5)
    _, _, want = ref.mhc_maps(lp["pa"], lp["aa"], lp["ba"], x, 4, 20, 1e-5)
    np.testing.assert_allclose(np.asarray(h_res), np.asarray(want),
                               atol=1e-6)
    _, _, two = blocks.mix_in(toy_cfg(sinkhorn_iters=2), lp, "a", major,
                           lp["g1"])
    assert np.abs(np.asarray(two.sum(-1)) - 1.0).max() > 1e-3


def test_the_streams_are_mixed_as_the_equations_say(toy, rng):
    """``X' = H_res X + H_post^T y`` with ``u = H_pre X`` against the
    plain statement, a token at a time."""
    lp = toy.params["layers"][0]
    x = rng.randn(5, 4, 64).astype("float32")
    y = rng.randn(5, 64).astype("float32")
    major = jnp.moveaxis(jnp.asarray(x), -2, 0)     # [n, B, d] as served
    u, h_post, h_res = blocks.mix_in(toy.cfg, lp, "m", major, lp["g2"])
    out = np.moveaxis(np.asarray(blocks.mix_out(
        toy.cfg, major, jnp.asarray(y), h_post, h_res)), 0, -2)
    h_pre, hp, hr = (np.asarray(t) for t in ref.mhc_maps(
        lp["pm"], lp["am"], lp["bm"], jnp.asarray(x), 4, 20, 1e-5))
    for t in range(5):
        np.testing.assert_allclose(
            out[t], hr[t] @ x[t] + np.outer(hp[t], y[t]), atol=1e-5)
        mixed = h_pre[t] @ x[t]
        np.testing.assert_allclose(
            np.asarray(u[t]),
            mixed / np.sqrt(np.mean(mixed ** 2) + 1e-5), atol=1e-5)


def test_the_subtraction_on_latent_outputs_equals_the_one_on_values(toy, rng):
    """A group's five heads share ONE value up-projection, which is linear:
    combining the latent outputs and projecting 8 heads equals projecting
    10 and combining the values."""
    cfg = toy.cfg
    wkvb = toy.params["layers"][2]["wkvb"]
    o_lat = jnp.asarray(rng.randn(3, 10, 16).astype("float32"))
    lam = jnp.asarray(rng.rand(3, 8).astype("float32"))
    early = mf.absorbed_output(
        cfg, wkvb, attention_ops.differential_combine(o_lat, lam, 2))
    w_uv = np.asarray(wkvb).reshape(16, 2, 32)[..., 16:]
    values = np.einsum("bgjc,cgv->bgjv",
                       np.asarray(o_lat).reshape(3, 2, 5, 16), w_uv)
    late = np.asarray(attention_ops.differential_combine(
        jnp.asarray(values.reshape(3, 10, 16)), lam, 2))
    np.testing.assert_allclose(np.asarray(early), late.reshape(3, -1),
                               atol=1e-5)
    # and by hand: signal head s of group g loses lam_s x the group's fifth
    np.testing.assert_allclose(
        late[1, 5], values[1, 1, 1] - float(lam[1, 5]) * values[1, 1, 4],
        atol=1e-6)


# -- (c) prefill, then decoding through the ring and the pages ----------------


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_ring_and_pages_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch: a prompt past the
    window whose ring the prefill fills with its LAST 16 rows, a short one
    that decodes past 3 x W (its ring wraps three times, its pages cross
    seven boundaries), one that ends early. Every emitted token's logits
    row equals the reference's full forward over the same tokens; in plain
    XLA and by the kernel (interpreted), H = 10."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy, prompt_buckets=(64,)) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "mla_paged")
            plan = [(rng.randint(0, 96, 37), 9), (rng.randint(0, 96, 5), 52),
                    (rng.randint(0, 96, 3), 4)]
            reqs = [eng.submit(list(p), m) for p, m in plan]
            peak = [0, 0]
            while not eng.scheduler.idle():
                eng.step()
                peak = [max(p, pool.num_used)
                        for p, pool in zip(peak, eng.pools)]
                assert eng.page_accounting_ok()
            for (prompt, m), req in zip(plan, reqs):
                assert len(req.tokens_out) == m
                seq = list(prompt) + req.tokens_out[:-1]
                first = len(prompt) - 1
                want = reference_rows(toy, seq, np.arange(first, first + m))
                got = np.stack(eng.captured_logits(req))
                np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
            # pages: ceil(46/8), ceil(57/8), ceil(7/8) in whole runs of 4;
            # rings: 2, 2, 1 in whole runs of 2 (a ring is two pages)
            assert peak == [8 + 8 + 4, 2 + 2 + 2]
            assert [p.num_used for p in eng.pools] == [0, 0]
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_a_window_layer_forgets_token_0_and_a_full_layer_does_not(rng):
    """A context of W + 1 tokens: changing token 0 moves the next token's
    logits through a full layer and not at all through a window layer
    (decoded through the cache: token 0's row has been overwritten in the
    ring)."""
    moved = {}
    for kind in ("window", "full"):
        model = toy_model(n_layer=1, layer_types=[kind], dense_layers=())
        a = rng.randint(1, 96, W + 1)
        b = a.copy()
        b[0] = (a[0] + 1) % 96
        rows = []
        with _engine(model, slots=1, prompt_buckets=(16,)) as eng:
            for seq in (a, b):
                req = eng.submit(list(seq[:W]), 3)
                eng.run()
                rows.append(np.stack(eng.captured_logits(req)))
        # row 0 is chosen from position W - 1 (sees token 0 either way);
        # rows 1, 2 from positions W, W + 1, whose window starts at 1, 2
        moved[kind] = np.abs(rows[0][1:] - rows[1][1:]).max()
    # one layer: nothing carries token 0 past its window
    assert moved["full"] > 1e-3 and moved["window"] == 0.0, moved


def test_a_reused_slot_gives_a_fresh_engines_logits(toy, rng):
    """ONE slot serves two requests in turn, the first long enough to fill
    its ring: the second's logits are a fresh engine's, bit for bit, and
    the ring rows the second wrote are the only ones it reads (its
    context's rows: the rest of the ring still holds the first's, which
    the live length masks)."""
    a, b = rng.randint(0, 96, 29), rng.randint(0, 96, 6)
    with _engine(toy, slots=1, prompt_buckets=(32,)) as eng:
        first = eng.submit(list(a), 9)
        eng.run()
        assert first.state == "finished"
        assert [p.num_used for p in eng.pools] == [0, 0]
        second = eng.submit(list(b), 5)
        eng.run()
        got = np.stack(eng.captured_logits(second))
    with _engine(toy, slots=1, prompt_buckets=(32,)) as fresh:
        again = fresh.submit(list(b), 5)
        fresh.run()
        np.testing.assert_array_equal(
            got, np.stack(fresh.captured_logits(again)))
    assert second.tokens_out == again.tokens_out


# -- (d) PolyNorm with an expert's own numbers ---------------------------------


def _poly_case(rng, m=40, d=128, f=128, e=6):
    xs = rng.randn(m, d).astype("float32")
    wg, wu = (0.2 * rng.randn(e, d, f).astype("float32") for _ in range(2))
    wd = 0.2 * rng.randn(e, f, d).astype("float32")
    pn = np.concatenate([1 / 3 + 0.3 * rng.rand(e, 3),
                         rng.rand(e, 1) - 0.5], 1).astype("float32")
    sizes = np.asarray([7, 0, 11, 1, 0, 9], np.int32)
    return [jnp.asarray(t) for t in (xs, wg, wu, wd, pn, sizes)]


def _poly_loop(xs, wg, wu, wd, pn, sizes):
    """Each group's rows through ITS expert's three matrices and ITS four
    numbers, in numpy."""
    xs, wg, wu, wd, pn = (np.asarray(t, np.float64)
                          for t in (xs, wg, wu, wd, pn))
    out, lo = np.zeros_like(xs), 0

    def n(t):
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True) + 1e-6)

    for e, rows in enumerate(np.asarray(sizes)):
        v = xs[lo:lo + rows] @ wg[e]
        act = 0.5 * (pn[e, 0] * n(v) + pn[e, 1] * n(v ** 2)
                     + pn[e, 2] * n(v ** 3) + np.clip(pn[e, 3], -0.5, 0.5))
        out[lo:lo + rows] = (act * (xs[lo:lo + rows] @ wu[e])) @ wd[e]
        lo += rows
    return out, lo


@pytest.mark.parametrize("path", ["ragged", "kernel"])
def test_polynorm_with_per_expert_numbers_against_a_loop(rng, path):
    xs, wg, wu, wd, pn, sizes = _poly_case(rng)
    act = mf._activation(0.5, 0.5)
    want, live = _poly_loop(xs, wg, wu, wd, pn, sizes)
    with jax.default_matmul_precision("highest"):
        if path == "ragged":
            got = moe_ops._ragged_ffn(xs, wg, wu, wd, sizes, act, pn)
        else:
            got = es.expert_stream_ffn(xs, wg, wu, wd, sizes, act,
                                       act_params=pn, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:live], want[:live],
                               atol=2e-4, rtol=0)
    # the wrong expert's numbers do not pass
    with jax.default_matmul_precision("highest"):
        wrong = moe_ops._ragged_ffn(xs, wg, wu, wd, sizes, act, pn[::-1])
    assert np.abs(np.asarray(wrong)[:live] - want[:live]).max() > 1e-2


@pytest.mark.parametrize("departure", [None, "order", "axis", "clamp",
                                       "scale"])
def test_the_programs_polynorm_is_the_references(rng, departure):
    """``models/motif3.py`` and the reference each state PolyNorm in their
    own words (the program's is what the expert paths and the fused kernel
    run): both equal the formula by hand, in float64, and neither equals
    it with the weights in another order, the mean square over the other
    axis, the bias unclamped or the scale left out."""
    v = np.asarray(rng.randn(6, 48) * 1.5, np.float32)
    p = np.array([0.31, 0.42, 0.23, 0.7])    # a bias past the clamp

    def by_hand(v, w, axis=-1, clamp=0.5, scale=0.5):
        v = np.asarray(v, np.float64)

        def n(t):
            return t / np.sqrt(np.mean(t * t, axis=axis, keepdims=True)
                               + 1e-6)

        return scale * (w[0] * n(v) + w[1] * n(v ** 2) + w[2] * n(v ** 3)
                        + np.clip(w[3], -clamp, clamp))

    want = by_hand(v, p, **{
        None: {}, "order": {}, "axis": dict(axis=0),
        "clamp": dict(clamp=1.0), "scale": dict(scale=1.0)}[departure]) \
        if departure != "order" else by_hand(v, p[[2, 1, 0, 3]])
    for fn in (mf.poly_norm, ref.poly_norm):
        got = np.asarray(fn(jnp.asarray(v), list(jnp.asarray(p, jnp.float32))))
        if departure is None:
            np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
        else:
            assert np.abs(got - want).max() > 1e-2
    assert mf.poly_norm.__code__ is not ref.poly_norm.__code__


@pytest.mark.parametrize("scaling", [SCALING, {
    "original_max_position_embeddings": 4096, "factor": 64, "mscale": 1,
    "beta_fast": 32, "beta_slow": 1, "apply_yarn_scaling": False}])
def test_the_programs_rotary_tables_are_the_references(scaling):
    """The program makes its frequencies with ``blocks.yarn_inv_freq``
    and this model's reference has its own statement: a full layer YaRN's
    over ``rope_theta`` (its ramp inside the table at the published
    numbers), a window layer plain at ``swa_rope_theta``."""
    rope = 8 if scaling is SCALING else 64
    cfg = toy_cfg(d_rope=rope, rope_scaling=scaling, rope_theta=1e4,
                  window_rope_theta=5e3)
    want = ref.rotary(dict(PUBLISHED, qk_rope_head_dim=rope,
                           rope_scaling=scaling, swa_rope_theta=5e3))
    for kind in (mf.FULL, mf.RING):
        np.testing.assert_allclose(cfg.latent_of[kind].inv_freq, want[kind],
                                   rtol=1e-12)
    plain = 5e3 ** (-np.arange(rope // 2) * 2.0 / rope)
    np.testing.assert_allclose(want[mf.RING], plain, rtol=1e-12)
    full = np.asarray(want[mf.FULL]) * 1e4 ** (np.arange(rope // 2) * 2.0
                                               / rope)
    assert full[0] == 1.0 and abs(full[-1] - 1 / scaling["factor"]) < 1e-12
    assert (mf.FULL, mf.RING) == (ref.FULL, ref.RING)


@pytest.mark.parametrize("form", ["grouped", "stream"])
def test_without_a_parameter_array_every_path_is_as_it_was(rng, form,
                                                            monkeypatch):
    """``act_params=None``: the ``ragged_dot`` form and the kernel give,
    bit for bit, what the three products written out (the form before this
    argument existed) and the kernel called as before give."""
    xs, wg, wu, wd, _, sizes = _poly_case(rng)
    if form == "grouped":
        got = moe_ops._grouped_ffn(xs, wg, wu, wd, sizes, jax.nn.silu)
        gate = jax.lax.ragged_dot(xs, wg, sizes)
        up = jax.lax.ragged_dot(xs, wu, sizes)
        want = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wd, sizes)
    else:
        monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
        for name in ("expert_stream_ffn", "expert_stream_gate"):
            monkeypatch.setattr(es, name, functools.partial(
                getattr(es, name), interpret=True))
        got = moe_ops._grouped_ffn(xs, wg, wu, wd, sizes, jax.nn.silu)
        want = es.expert_stream_ffn(xs, wg, wu, wd, sizes, jax.nn.silu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("path", ["grouped", "stream"])
def test_sixteen_shares_and_one_shared_expert_add_up_to_the_whole_layer(
        toy, rng, path, monkeypatch):
    """The deployment's arithmetic at toy size: sixteen chips hold one
    expert each with its own four numbers, every chip has the router and
    the shared expert. The routed parts of the sixteen shares (each
    through the share's passes; by ``ragged_dot`` and by the kernel's
    interpreter), with the shared expert counted ONCE, add up to the uncut
    reference's whole block."""
    if path == "stream":
        monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
        for name in ("expert_stream_ffn", "expert_stream_gate"):
            monkeypatch.setattr(es, name, functools.partial(
                getattr(es, name), interpret=True))
    lp = toy.params["layers"][1]
    u = jnp.asarray(rng.randn(9, 64).astype("float32"))
    act = toy.cfg.activation

    def block(lp, held):
        """The reference's sparse block WITHOUT the residual path: what F
        returns for normed rows ``u``."""
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(u @ lp["wr"])
            top, idx = jax.lax.top_k(s, 4)
            w = jnp.zeros_like(s).at[jnp.arange(9)[:, None], idx].set(
                2.0 * top / top.sum(-1, keepdims=True))
            y = ref._mlp(u, lp["sg"], lp["su"], lp["sd"], lp["spn"], act)
            for j, e in enumerate(held):
                y = y + w[:, e][:, None] * ref._mlp(
                    u, lp["wg"][j], lp["wu"][j], lp["wd"][j], lp["pn"][j],
                    act)
            return np.asarray(y)

    whole = block(lp, tuple(range(16)))
    shared = np.asarray(ref._mlp(u, lp["sg"], lp["su"], lp["sd"], lp["spn"],
                                 act))
    total = shared.copy()
    for c in range(16):
        part = {**lp, **{k: lp[k][c:c + 1] for k in ("wg", "wu", "wd",
                                                     "pn")}}
        out, stats = mf._feed_forward(toy_cfg(experts_held=(c,)), part, u,
                                      None)
        assert int(stats["experts_touched"]) <= 1
        np.testing.assert_allclose(np.asarray(out), block(part, (c,)),
                                   atol=TOL, rtol=0)
        total += np.asarray(out) - shared
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_a_share_through_the_engine_equals_the_reference_given_the_share(rng):
    """Four of sixteen experts held: prefill and decode through the cache
    equal the reference given the same share; the counters see the share's
    load and each group's rows."""
    from paddle_tpu.serving import metrics as sm

    held = (0, 1, 2, 3)
    model = toy_model(experts_held=held)
    assert model.params["layers"][1]["wg"].shape[0] == 4
    assert model.params["layers"][1]["pn"].shape == (4, 4)
    t0 = sm.MOE_EXPERTS_TOUCHED.count
    full0 = sm.attn_rows_read("latent_full").sum
    ring0 = sm.attn_rows_read("latent_ring").sum
    with _engine(model, prompt_buckets=(16,)) as eng:
        # three window layers x (3 slots x 16 rows) x 128 lanes, float32
        assert sm.LATENT_RING_BYTES.value == eng.cache_ops.ring_bytes(
            eng._cache) == 3 * 48 * 128 * 4
        prompt = rng.randint(0, 96, 9)
        req = eng.submit(list(prompt), 14)
        eng.run()
        seq = list(prompt) + req.tokens_out[:-1]
        want = reference_rows(model, seq, np.arange(8, 22),
                              experts_held=list(held))
        np.testing.assert_allclose(np.stack(eng.captured_logits(req)), want,
                                   atol=TOL, rtol=0)
    steps = 13                     # the first token comes from the prefill
    assert sm.MOE_EXPERTS_TOUCHED.count - t0 == steps * 3   # expert layers
    # a full layer read contexts of 10 .. 22 rows, a ring min(that, 16)
    assert sm.attn_rows_read("latent_full").sum - full0 == sum(range(10, 23))
    assert sm.attn_rows_read("latent_ring").sum - ring0 == sum(
        min(c, W) for c in range(10, 23))


# -- (e) the cache --------------------------------------------------------------


def test_two_latent_groups_one_of_pages_and_one_of_rings(toy):
    """Nine layers as the cell cuts them (window x 4, full, window x 3,
    full): two layers' rows live in pages, seven layers' in rings of W
    rows a slot; each group has its pool, page table and free list, and
    admission reserves a request's worst case in the first and its ring
    in the second."""
    types = ["window"] * 4 + ["full"] + ["window"] * 3 + ["full"]
    cfg = toy_cfg(n_layer=9, layer_types=types)
    with _engine(mf.Motif3LM(cfg, params={})) as eng:
        ops = eng.cache_ops
        assert isinstance(ops, LatentPagedCache)
        assert [(g.name, g.kind, g.layers, g.window, g.num_pages)
                for g in ops.groups] == [
            ("latent_full", LATENT, (4, 8), None, 40),
            ("latent_ring", LATENT, (0, 1, 2, 3, 5, 6, 7), W, 3 * 2)]
        assert sorted(eng._cache) == ["c", "c.latent_ring", "pt",
                                      "pt.latent_ring"]
        assert eng._cache["c"].shape == (2, 320, 128)
        assert eng._cache["c.latent_ring"].shape == (7, 48, 128)
        assert eng._cache["pt.latent_ring"].shape == (3, 2)
        assert [p.name for p in eng.pools] == ["latent_full", "latent_ring"]
        assert ops.page_table_len == 16 + 2
        assert ops.cache_bytes(eng._cache) == (2 * 320 + 7 * 48) * 128 * 4
        assert ops.ring_bytes(eng._cache) == 7 * 48 * 128 * 4
        assert ops.pages_needed(0, 100) == 13 and ops.pages_needed(1, 100) == 2
        rows = ops.rows_read(jnp.asarray([3, 0, 40]),
                             jnp.asarray([True, False, True]))
        assert {k: int(v) for k, v in rows.items()} == {
            "attn_rows_read.latent_full": 43,
            "attn_rows_read.latent_ring": 3 + W}
    with _engine(toy, collect_logits=False, prompt_buckets=(16,)) as eng:
        req = eng.submit(list(range(1, 12)), 30)
        eng.step()
        # 41 positions: 6 pages in two runs of 4; the ring is one run of 2
        assert [p.num_used for p in eng.pools] == [8, 2]
        assert eng.page_accounting_ok()
        assert eng.stats()["pages_by_group"] == {"latent_full": [8, 40],
                                                 "latent_ring": [2, 6]}
        assert eng.stats()["page_run_pages"] == {"latent_full": 4,
                                                 "latent_ring": 2}
        assert eng.stats()["pages_padding"] == {"latent_full": 2,
                                                "latent_ring": 0}
        eng.run()
        assert req.state == "finished"
        assert [p.num_used for p in eng.pools] == [0, 0]
        assert eng.page_accounting_ok()
        assert eng.stats()["layout"] == "paged-latent"


def test_a_latent_cache_has_one_group_a_window():
    with pytest.raises(ValueError, match="ONE latent group a window"):
        LatentPagedCache(2, 16, 8, 2, 64, 8, 8, groups=[
            CacheGroup("a", (0,), 16, 4, LATENT),
            CacheGroup("b", (1,), 16, 4, LATENT)])
    with pytest.raises(ValueError, match="multiple of page_size"):
        LatentPagedCache(2, 16, 8, 2, 64, 8, 8, groups=[
            CacheGroup("a", (0,), None, 8, LATENT),
            CacheGroup("b", (1,), 12, 4, LATENT)])


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
])
def test_what_this_cache_cannot_do_is_refused_at_construction(toy, kw, what):
    with pytest.raises(ValueError, match=what + ".*latent cache.*2 latent "
                       "groups.*latent_ring.*rings"):
        _engine(toy, **kw)


def test_page_export_is_refused_over_this_cache(toy):
    with _engine(toy) as eng:
        for call, what in (
                (lambda: eng.cache_ops.export_pages(eng._cache, [0]),
                 "page export"),
                (lambda: eng.cache_ops.copy_pages(eng._cache, None, None),
                 "page copy")):
            with pytest.raises(ValueError,
                               match=what + ".*latent_ring: latent"):
                call()


def test_the_ring_call_has_a_name_of_its_own_and_the_gate_says_what_it_refuses():
    assert mla.RING_KERNEL_NAME == "mla_latent_decode_ring" != mla.KERNEL_NAME
    # 80 heads of a 640-lane row with a 512-lane latent: taken
    assert mla.mla_decode_gate(jnp.bfloat16, 640, 512, 16) is None
    assert "multiples of 128" in mla.mla_decode_gate(jnp.bfloat16, 576, 512,
                                                     16)
    assert "16 rows" in mla.mla_decode_gate(jnp.bfloat16, 640, 512, 8)
    assert "float8" in mla.mla_decode_gate(jnp.float8_e4m3fn, 640, 512, 32)
    q = jnp.asarray(np.random.RandomState(0).randn(2, 80, 128), jnp.float32)
    pool = jnp.asarray(np.random.RandomState(1).randn(1, 64, 128),
                       jnp.float32)
    pt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([16, 5], jnp.int32)
    got = mla.mla_paged_decode(q, pool, pt, lens, page_size=16, rank=64,
                               layer=0, sm_scale=0.1, interpret=True,
                               name=mla.RING_KERNEL_NAME)
    want = mla.mla_gather_reference(q, pool[0], pt, lens, 16, 64, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


RING_LENGTHS = {
    "one_row_a_row_short_and_the_whole_ring": [1, 127, 128],
    "whole_rings_in_a_row": [128, 128, 128, 128],
    "a_rowless_slot_between_two_whole_rings": [128, 0, 128, 5],
    "the_last_slot_rowless": [127, 128, 0],
    "only_the_last_slot_live": [0, 0, 128],
}


@pytest.mark.parametrize("case", sorted(RING_LENGTHS))
def test_a_ring_call_of_eight_pages_equals_the_gather(case,
                                                      poisoned_latent_pool):
    """The window layers' call: ONE wave of eight pages a slot, so what
    overlaps a slot's fold is the NEXT live slot's copy. 80 heads, every
    row past a length Inf or NaN: finite and the gather's."""
    lens = np.asarray(RING_LENGTHS[case], np.int32)
    ps, rank = 16, 32
    q, poisoned, clean, pt = poisoned_latent_pool(
        np.random.RandomState(7), lens, 80, rank, 16, ps, 8)
    got = np.asarray(mla.mla_paged_decode(
        q, poisoned, pt, jnp.asarray(lens), page_size=ps, rank=rank, layer=1,
        sm_scale=0.1, interpret=True, name=mla.RING_KERNEL_NAME))
    want = np.asarray(mla.mla_gather_reference(
        q, clean, pt, jnp.asarray(lens), ps, rank, 0.1))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert np.all(got[lens == 0] == 0)

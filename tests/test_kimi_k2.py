"""Kimi-K2's block (latent attention, a sigmoid-routed expert layer with a
shared expert) through the serving stack, against its plain float32
reference (``grid/reference/kimi_k2.py``), at a toy size on the CPU: one
dense and two expert layers, d 64, 4 heads, ``q_lora_rank`` 32, latent 16
+ 8 rotary, nope 16, v 16, 16 experts top-4 of width 32 and one shared,
YaRN factor 32 over 64 positions, page 8. LOGITS are compared, never
sampled tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (absorbed products against expanded
heads, grouped matmul over sorted rows against a dense loop over experts,
online softmax against a plain one): the worst logit difference read was
3.0e-6 on logits of standard deviation 0.97. ``TOL`` = 5e-5 is over ten
times that and far under what a lower precision gives: in bfloat16 the
router's inputs move a logit by 2.5e-3, the combine weights by 3.9e-3 and
the attention softmax by 2.1e-2 (``test_a_lower_precision_fails`` asks for
ten times ``TOL`` of each), so none of them can hide inside it.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import kimi_k2 as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import kimi_k2 as kk
from paddle_tpu.ops import attention_ops, moe_ops
from paddle_tpu.ops.pallas_kernels import mla_attention as mla
from paddle_tpu.serving.kv_cache import LatentPagedCache

TOL = 5e-5
YARN = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
PUBLISHED = {  # the toy under the published config's own keys
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.827,
    "rope_theta": 5e4, "rope_scaling": YARN, "rms_norm_eps": 1e-6}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=3, d_model=64, n_head=4, q_rank=32,
              kv_rank=16, d_nope=16, d_rope=8, d_v=16, d_dense=128,
              n_dense=1, n_expert=16, top_k=4, d_expert=32,
              routed_scale=2.827, rope_theta=5e4, rope_scaling=YARN,
              max_seq=64, dtype="float32")
    kw.update(over)
    return kk.KimiK2Config(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention and routing are decisive at d = 64; the bias keeps its own
    deviation (it is compared with sigmoids, not with products)."""
    return jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim > 1 else a, params)


def toy_model():
    cfg = toy_cfg()
    return kk.KimiK2LM(cfg, params=_scaled(kk.init_params(cfg, 3)))


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


# -- (a) prefill (expanded) against the reference's full forward --------------


@pytest.mark.parametrize("n", [5, 16, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    seq = rng.randint(0, 96, n)
    logits, rows = _prefill(toy, seq)
    want = reference_rows(toy, seq, np.arange(n))
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq
    last, _ = toy.prefill_last(toy.params, jnp.asarray(toks),
                               jnp.asarray([n], jnp.int32))
    np.testing.assert_allclose(np.asarray(last[0]), want[-1], atol=TOL,
                               rtol=0)
    # what the cache keeps: ONE row of rank + rope values a token a layer
    assert len(rows) == 3 and len(rows[0]) == 1
    assert rows[0][0].shape == (1, 32, 16 + 8)


@pytest.mark.parametrize("what", ["router", "combine", "softmax"])
def test_a_lower_precision_fails(toy, what, rng, monkeypatch):
    """``TOL`` is tight enough to tell: each of the three computed in
    bfloat16 on the served path puts the prefill outside it."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if what == "router":
        real = moe_ops.route_sigmoid_topk
        monkeypatch.setattr(
            moe_ops, "route_sigmoid_topk",
            lambda h, wr, b, k, scale: real(bf16(h), bf16(wr), b, k, scale))
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


# -- (b) prefill, then decoding through the latent paged cache ----------------


def _engine(model, **kw):
    cfg = dict(slots=3, page_size=8, max_seq=64, prompt_buckets=(8, 16, 32),
               num_pages=20, collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_latent_cache_equals_the_reference(toy, kernel,
                                                              rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: the absorbed decode over ``[c | kr]`` rows written by the
    expanded prefill and by earlier decode steps, across page boundaries
    (a prompt of 5 decodes 40 positions: six pages of 8). Every emitted
    token's logits row equals the reference's full forward over the same
    tokens; by the gather path and by the latent kernel (interpreted)."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "mla_paged")
            plan = [(rng.randint(0, 96, 3), 4), (rng.randint(0, 96, 19), 12),
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
            # ceil(7/8), ceil(31/8), ceil(45/8) pages, in whole runs of 4
            assert peak == 4 + 4 + 8
            assert eng.pool.num_used == 0
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_absorbed_decode_equals_expanded_attention(toy, rng):
    """One layer, one new position over a context of 11: the absorbed
    products over the ``[c | kr]`` rows give what attention over K and V
    expanded from the same rows gives."""
    cfg, lp = toy.cfg, toy.params["layers"][1]
    n = 12
    h = jnp.asarray(rng.randn(n, cfg.d_model).astype("float32"))
    q_n, q_r, row = blocks.latent(cfg, lp, h, jnp.arange(n))
    kv = (row[:, :cfg.kv_rank] @ lp["wkvb"]).reshape(n, cfg.n_head, -1)
    want = attention_ops.mla_causal_attention(
        jnp.concatenate([q_n, q_r], -1), kv[..., :cfg.d_nope],
        row[:, cfg.kv_rank:], kv[..., cfg.d_nope:], cfg.sm_scale)[-1]
    q = kk.absorbed_query(cfg, lp["wkvb"], q_n[-1:], q_r[-1:])
    o_lat = attention_ops.mla_decode_attention(
        q, row[None], jnp.asarray([n]), cfg.kv_rank, cfg.sm_scale)
    got = kk.absorbed_output(cfg, lp["wkvb"], o_lat)[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(-1), atol=2e-6,
                               rtol=0)


# -- (c) the latent kernel against the gather path ----------------------------


@pytest.mark.parametrize("block", [1, 3, None])
def test_latent_kernel_equals_the_gather(block, rng):
    """Ragged lengths (one token, mid-page, page-exact, several waves, a
    slot that holds nothing) over a scrambled page table, every row the
    lengths do not cover poisoned: the kernel equals the gather path, the
    rowless slot is exactly 0.0, garbage contributes nothing."""
    slots, h, rank, rope, ps, pps, pages = 5, 4, 16, 8, 8, 8, 44
    width = 128
    pool = np.zeros((2, pages * ps, width), np.float32)
    pool[..., :rank + rope] = rng.randn(2, pages * ps, rank + rope)
    perm = rng.permutation(pages)
    pt = np.stack([np.resize(perm[s::slots], pps) for s in range(slots)])
    lens = np.array([1, 7, 8, 61, 0], np.int32)
    q = np.zeros((slots, h, width), np.float32)
    q[..., :rank + rope] = rng.randn(slots, h, rank + rope)

    def run(p):
        got = mla.mla_paged_decode(
            jnp.asarray(q), jnp.asarray(p), jnp.asarray(pt.astype(np.int32)),
            jnp.asarray(lens), page_size=ps, rank=rank, layer=1,
            sm_scale=0.3, block_pages=block, interpret=True)
        want = mla.mla_gather_reference(
            jnp.asarray(q), jnp.asarray(p[1]),
            jnp.asarray(pt.astype(np.int32)), jnp.asarray(lens), ps, rank,
            sm_scale=0.3)
        return np.asarray(got), np.asarray(want)

    got, want = run(pool)
    assert got.shape == (slots, h, rank)
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-6, rtol=0)
    assert np.all(got[4] == 0)
    live = np.zeros(pages * ps, bool)
    for s in range(slots):
        flat = (pt[s].repeat(ps) * ps + np.tile(np.arange(ps), pps))
        live[flat[:lens[s]]] = True
    poisoned = pool.copy()
    poisoned[1, ~live] = 1e4
    poisoned[0] = -1e4                      # the neighbouring layer
    np.testing.assert_array_equal(run(poisoned)[0], got)


# a wave is 2 pages of 8 rows here; a slot's table holds 6 pages (3 waves)
WAVE_LENGTHS = {
    "nothing_one_row_and_a_row_short_of_a_wave": [0, 1, 15],
    "one_wave_a_row_past_it_and_two_waves": [16, 17, 32],
    "a_rowless_slot_between_two_live_ones": [5, 0, 40],
    "two_rowless_slots_between_two_full_waves": [16, 0, 0, 32],
    "the_last_slot_rowless": [33, 16, 0],
    "only_the_last_slot_live": [0, 0, 7],
    "every_slot_rowless": [0, 0, 0],
    "the_whole_table": [48, 48],
    "odd_and_even_waves_in_turn": [16, 32, 16, 48, 9, 31],
    "a_length_past_the_table": [16, 60, 3],
}


@pytest.mark.parametrize("block", [2, 4], ids=["2_pages_a_wave",
                                                "4_pages_a_wave"])
@pytest.mark.parametrize("case", sorted(WAVE_LENGTHS))
def test_every_branch_of_the_wave_loop_equals_the_gather(
        case, block, rng, poisoned_latent_pool):
    """Lengths that land on each branch of the kernel's wave loop (a full
    wave folded as it lies, the boundary wave zeroed and masked, the next
    live slot's first wave started behind this slot's last, over rowless
    slots; with 4 pages a wave the table's 6 pages end in a wave that is
    never full): every row past a length is Inf or NaN and the outputs
    are finite and the gather's."""
    ps, pps, rank = 8, 6, 16
    lens = np.minimum(WAVE_LENGTHS[case], ps * pps)
    q, poisoned, clean, pt = poisoned_latent_pool(rng, lens, 4, rank, 8, ps,
                                                  pps)
    # the kernel is handed the lengths as they come; the table bounds them
    got = np.asarray(mla.mla_paged_decode(
        q, poisoned, pt, jnp.asarray(WAVE_LENGTHS[case], jnp.int32),
        page_size=ps, rank=rank, layer=1, sm_scale=0.3, block_pages=block,
        interpret=True))
    want = np.asarray(mla.mla_gather_reference(
        q, clean, pt, jnp.asarray(lens), ps, rank, sm_scale=0.3))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert np.all(got[lens == 0] == 0)


def test_the_gate_knows_the_latent_row():
    assert mla.mla_decode_gate(jnp.bfloat16, 640, 512, 16) is None
    assert "multiples of 128" in mla.mla_decode_gate(jnp.bfloat16, 576, 512,
                                                     16)
    assert "page_size" in mla.mla_decode_gate(jnp.bfloat16, 640, 512, 8)
    assert mla.mla_decode_gate(jnp.float32, 24, 16, 4, interpret=True) is None
    assert "int8" in mla.mla_decode_gate(jnp.int8, 640, 512, 32)
    # 576 values a row are stored as 640 lanes: the padding is stated
    c = LatentPagedCache(7, 512, 64, 32, 16384, 16, 64, dtype="bfloat16")
    assert (c.row_values, c.row_width) == (576, 640)
    assert c.kernel_mode()[0] is None      # auto, off the chip: the gather


# -- (d) the router and the expert layer --------------------------------------


def test_sigmoid_router_against_a_loop(rng):
    """Chosen by ``s + b``, weighed by ``s``: with a bias that changes the
    selection in most rows, the weights are the unbiased sigmoids of the
    chosen experts, normalised and scaled."""
    n, d, e, k, scale = 11, 16, 12, 3, 2.827
    h = rng.randn(n, d).astype("float32")
    wr = rng.randn(d, e).astype("float32")
    b = rng.randn(e).astype("float32")
    idx, w = moe_ops.route_sigmoid_topk(jnp.asarray(h), jnp.asarray(wr),
                                        jnp.asarray(b), k, scale)
    s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64) @ wr)))
    moved = 0
    for i in range(n):
        want = np.argsort(-(s[i] + b))[:k]
        assert sorted(np.asarray(idx[i]).tolist()) == sorted(want.tolist())
        moved += sorted(want.tolist()) != sorted(
            np.argsort(-s[i])[:k].tolist())
        chosen = s[i][np.asarray(idx[i])]
        np.testing.assert_allclose(np.asarray(w[i]),
                                   scale * chosen / chosen.sum(), rtol=1e-5)
    assert moved >= n // 2


def test_a_share_computes_every_pair_however_many(rng):
    """A share's grouped matmul takes its pairs in passes of a bound (twice
    an even router's load): a router that sends 2 held experts of 16 ALL 512
    pairs makes it run four passes, and nothing is dropped; SwiGLU by
    argument."""
    n, d, f, e, k = 512, 16, 8, 16, 1
    assert moe_ops._share_rows(n * k, 2, e) == 256
    u = jnp.asarray(rng.randn(n, d).astype("float32"))
    wg, wu = (jnp.asarray(rng.randn(2, d, f).astype("float32"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(2, f, d).astype("float32"))
    idx = jnp.asarray(rng.randint(0, 2, (n, k)) * 5 + 3, jnp.int32)  # 3 or 8
    w = jnp.asarray(rng.rand(n, k).astype("float32"))
    y, stats = moe_ops.expert_layer(u, idx, w, wg, wu, wd, n_expert=e,
                                    held=[3, 8], activation=jax.nn.silu)
    assert int(stats["max_expert_rows"]) > 128      # over half of one pass
    assert int(stats["experts_touched"]) == 2
    j = (np.asarray(idx)[:, 0] == 8).astype(int)
    un = np.asarray(u, np.float64)
    gate = np.einsum("nd,ndf->nf", un, np.asarray(wg, np.float64)[j])
    up = np.einsum("nd,ndf->nf", un, np.asarray(wu, np.float64)[j])
    want = np.einsum("nf,nfd->nd", gate / (1 + np.exp(-gate)) * up,
                     np.asarray(wd, np.float64)[j]) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-3, rtol=1e-4)


def test_four_shares_and_one_shared_expert_add_up_to_the_whole_layer(toy,
                                                                      rng):
    """The deployment's arithmetic at toy size: four chips hold four
    routed experts each, every chip has the router and the shared expert.
    The routed parts of the four shares, with the shared expert counted
    ONCE, add up to the uncut reference's whole layer."""
    cfg, lp = toy.cfg, toy.params["layers"][2]
    x = jnp.asarray(rng.randn(9, cfg.d_model).astype("float32"))
    whole = np.asarray(ref._sparse(lp, x, 4, 2.827, 1e-6, tuple(range(16))))
    shared = np.asarray(blocks.swiglu(blocks.rms_norm(x, lp["g2"], 1e-6),
                                      lp["sg"], lp["su"], lp["sd"]))
    total = np.asarray(x) + shared
    for c in range(4):
        held = tuple(range(4 * c, 4 * c + 4))
        part = {**lp, **{k: lp[k][np.asarray(held)] for k in ("wg", "wu",
                                                              "wd")}}
        out, stats = blocks.routed_feed_forward(
            toy_cfg(experts_held=held), part, x, None)
        assert int(stats["experts_touched"]) <= 4
        total += np.asarray(out) - np.asarray(x) - shared
        # the reference, given the same share, agrees with the program
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ref._sparse(part, x, 4, 2.827, 1e-6, held)),
            atol=TOL, rtol=0)
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_a_share_through_the_engine_equals_the_reference_given_the_share(rng):
    """Four of sixteen experts held, the vocabulary a slice: prefill and
    decode through the cache equal the reference given the same share."""
    held = (0, 1, 2, 3)
    cfg = toy_cfg(experts_held=held)
    model = kk.KimiK2LM(cfg, params=_scaled(kk.init_params(cfg, 5)))
    assert model.params["layers"][1]["wg"].shape[0] == 4
    with _engine(model) as eng:
        prompt = rng.randint(0, 96, 9)
        req = eng.submit(list(prompt), 10)
        eng.run()
        seq = list(prompt) + req.tokens_out[:-1]
        want = reference_rows(model, seq, np.arange(8, 18),
                              experts_held=list(held))
        np.testing.assert_allclose(np.stack(eng.captured_logits(req)), want,
                                   atol=TOL, rtol=0)


def test_decode_counts_the_held_experts_load(rng):
    from paddle_tpu.monitor import metrics as mx
    from paddle_tpu.serving import metrics as sm

    cfg = toy_cfg(experts_held=(0, 1, 2, 3))
    model = kk.KimiK2LM(cfg, params=_scaled(kk.init_params(cfg, 5)))
    t0 = (sm.MOE_EXPERTS_TOUCHED.count, sm.MOE_EXPERTS_TOUCHED.sum)
    p0 = (sm.MOE_HELD_PAIRS.count, sm.MOE_HELD_PAIRS.sum)
    with _engine(model, collect_logits=False) as eng:
        eng.submit([1, 2, 3], 5)
        eng.step()
        assert sm.pages_used("latent").value == 4     # one page: a run
        assert sm.pages_padding("latent").value == 3
        eng.run()
    steps, expert_layers = 4, 2    # the first token comes from the prefill
    assert sm.MOE_EXPERTS_TOUCHED.count - t0[0] == steps * expert_layers
    assert sm.MOE_HELD_PAIRS.count - p0[0] == steps * expert_layers
    # one live slot routes 4 pairs a layer; at most those land on the share
    pairs = sm.MOE_HELD_PAIRS.sum - p0[1]
    assert 0 <= pairs <= steps * expert_layers * 4
    assert sm.MOE_EXPERTS_TOUCHED.sum - t0[1] == pairs   # top-k are distinct


# -- (e) YaRN and the scale at the published values ---------------------------


def test_yarn_frequencies_and_scale_at_the_published_values():
    yarn = dict(YARN, original_max_position_embeddings=4096)
    f = ref.yarn_inv_freq(64, 50000.0, yarn)
    base = 50000.0 ** (-np.arange(32) * 2.0 / 64)
    # the correction range is floor/ceil of 19.16: pairs <= 19 keep their
    # frequency, pairs >= 20 run at 1/32 of it, nothing lies between
    np.testing.assert_allclose(f[:20], base[:20], rtol=1e-12)
    np.testing.assert_allclose(f[20:], base[20:] / 32, rtol=1e-12)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 50000.0, None), base)
    scale = ref.softmax_scale({"qk_nope_head_dim": 128,
                               "qk_rope_head_dim": 64, "rope_scaling": yarn})
    assert abs(scale - 0.1309) < 5e-5
    assert abs(scale - 192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2) < 1e-12
    cfg = toy_cfg(d_nope=128, d_rope=64, rope_scaling=yarn)
    assert abs(cfg.sm_scale - scale) < 1e-12
    np.testing.assert_allclose(cfg.inv_freq, f)


# -- (f) the cache -------------------------------------------------------------


def test_the_cache_holds_one_latent_row_a_token_and_no_v_pool(toy):
    with _engine(toy, collect_logits=False) as eng:
        ops = eng.cache_ops
        assert isinstance(ops, LatentPagedCache)
        assert sorted(eng._cache) == ["c", "pt"]
        # 3 layers x 20 pages x 8 rows x (16 + 8 values, padded to a tile)
        assert eng._cache["c"].shape == (3, 160, 128)
        assert ops.cache_bytes(eng._cache) == 3 * 160 * 128 * 4
        assert [p.name for p in eng.pools] == ["latent"]
        req = eng.submit(list(range(1, 12)), 6)
        eng.step()
        # 17 positions: 3 pages, handed out as one run of 4
        assert eng.pool.num_used == 4 and eng.page_accounting_ok()
        assert eng.stats()["page_run_pages"] == {"latent": 4}
        assert eng.stats()["pages_padding"] == {"latent": 1}
        rows = np.asarray(eng._cache["c"][1])
        written = np.flatnonzero(np.abs(rows).sum(-1))
        assert len(written) == 11 + 1     # the prompt and one decode step
        assert np.all(rows[:, 24:] == 0)  # the padding lanes stay zero
        eng.run()
        assert req.state == "finished" and eng.pool.num_used == 0
        assert eng.stats()["pages_by_group"] == {"latent": [0, 20]}
        assert eng.stats()["layout"] == "paged-latent"


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
])
def test_what_a_latent_cache_cannot_do_is_refused_at_construction(toy, kw,
                                                                  what):
    with pytest.raises(ValueError, match=what + ".*latent cache"):
        _engine(toy, **kw)


def test_page_export_is_refused_over_a_latent_cache(toy):
    with _engine(toy) as eng:
        for call, what in (
                (lambda: eng.cache_ops.export_pages(eng._cache, [0]),
                 "page export"),
                (lambda: eng.cache_ops.import_pages(eng._cache, [0], {}, []),
                 "page import")):
            with pytest.raises(ValueError, match=what):
                call()

"""Ling-3.0-flash-VL's language model (five KDA layers to each MLA layer,
a group-limited sigmoid router with a shared expert) through the serving
stack, against its plain float32 reference
(``grid/reference/ling3_flash.py``), at a toy size on the CPU: layers
KDA (dense), KDA, MLA, KDA; d 64, 4 heads, a 16 x 16 state a head, latent
16 + 8 rotary, nope 16, v 16, 16 experts in 4 groups (2 stay) top-4 of
width 32 and one shared, page 8. LOGITS are compared, never sampled
tokens.

Tolerance. Served path and reference both compute in float32 here and
differ in the ORDER of their sums only (the chunk-wise scan against the
recurrence token by token, absorbed products against expanded heads,
grouped matmul over sorted rows against a dense loop over experts): the
worst logit difference read was 8.6e-6 on logits of standard deviation
0.95. ``TOL`` = 5e-5 is five times that and far under what a lower
precision gives: the state kept in bfloat16 moves a logit by 2e-3 and the
decay computed in bfloat16 by 1e-3 (``test_a_lower_precision_fails`` asks
for ten times ``TOL`` of each), so neither can hide inside it.
"""


import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import ling3_flash as ref
from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import blocks
from paddle_tpu.models import ling3_flash as lf
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas_kernels import kda
from paddle_tpu.serving.kv_cache import (LATENT, STATE, CacheGroup,
                                         LatentPagedCache)

TOL = 5e-5
TYPES = ["kda", "kda", "mla", "kda"]
PUBLISHED = {  # the toy under the published config's own keys
    "num_hidden_layers": 4, "layer_types": TYPES, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "rope_theta": 6e6, "rms_norm_eps": 1e-6, "kda_lower_bound": -5}


def toy_cfg(**over):
    kw = dict(vocab_size=96, n_layer=4, d_model=64, n_head=4, d_state=16,
              layer_types=TYPES, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
              d_dense=128, dense_layers=(0,), n_expert=16, top_k=4,
              d_expert=32, n_group=4, topk_group=2, routed_scale=2.5,
              max_seq=64, dtype="float32", half_life=(2.0, 64.0))
    kw.update(over)
    return lf.Ling3FlashConfig(**kw)


def _scaled(params):
    """Seeded weights scaled up from the 0.02 a real width wants, so that
    attention, the gates and routing are decisive at d = 64; the
    convolution's taps, the bias and the decay's keep their own."""
    def scale(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return a * 6.0 if a.ndim > 1 and name != "cw" else a

    return jax.tree_util.tree_map_with_path(scale, params)


def toy_model(**over):
    cfg = toy_cfg(**over)
    return lf.Ling3FlashLM(cfg, params=_scaled(lf.init_params(cfg, 3)))


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
    cfg = dict(slots=3, page_size=8, max_seq=64, prompt_buckets=(8, 16, 32),
               num_pages=20, collect_logits=True)
    cfg.update(kw)
    return serving.ServingEngine(model, serving.ServingConfig(**cfg))


# -- (a) prefill against the reference's full forward --------------------------


@pytest.mark.parametrize("n", [5, 23])
def test_prefill_equals_the_reference(toy, n, rng):
    """Both kinds of layer: the chunk scan under the bucket's padding
    against the recurrence token by token, expanded latent attention."""
    seq = rng.randint(0, 96, n)
    logits, kept = _prefill(toy, seq)
    want = reference_rows(toy, seq, np.arange(n))
    np.testing.assert_allclose(np.asarray(logits[0, :n]), want, atol=TOL,
                               rtol=0)
    # what the cache is handed: a state and a tail of a KDA layer, ONE row
    # a token of the MLA layer
    assert [len(k) for k in kept] == [2, 2, 1, 2]
    assert kept[0][0].shape == (1, 4, 16, 16)
    assert kept[0][1].shape == (1, 3, 3 * 64)
    assert kept[2][0].shape == (1, 32, 16 + 8)
    # the tail is the last three inputs of the convolution BELOW the length
    u = blocks.rms_norm(toy.params["tok_emb"][jnp.asarray(seq)],
                toy.params["layers"][0]["g1"], 1e-6) \
        @ toy.params["layers"][0]["wqkv"]
    np.testing.assert_allclose(np.asarray(kept[0][1][0]),
                               np.asarray(u[n - 3:n]), atol=1e-6)


def test_each_layer_kind_alone_equals_the_reference(toy, rng):
    """One layer's attention half of each kind, on random inputs."""
    cfg = toy.cfg
    x = jnp.asarray(rng.randn(23, 64).astype("float32"))
    for i, kind in ((1, "kda"), (2, "mla")):
        lp = toy.params["layers"][i]
        h = blocks.rms_norm(x, lp["g1"], cfg.rms_eps)
        if kind == "kda":
            y, _, _ = blocks.kda_prefill(cfg, lp, h, 23)
            want = ref._kda(lp, x, 4, -5.0, 1e-6)
        else:
            y, _ = lf._mla_prefill(cfg, lp, h, jnp.arange(23))
            want = ref._mla(lp, x, jnp.arange(23), 4, 16, 8, 1e-6,
                            tuple(float(f) for f in cfg.inv_freq))
        np.testing.assert_allclose(np.asarray(x + y), np.asarray(want),
                                   atol=TOL, rtol=0)


@pytest.mark.parametrize("what", ["state", "decay"])
def test_a_lower_precision_fails(toy, what, rng, monkeypatch):
    """``TOL`` is tight enough to tell: the state kept in bfloat16 from
    chunk to chunk, or the decay computed in bfloat16, on the served path
    puts the prefill outside it."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if what == "state":
        # chunks of 8, so that a prompt of 23 hands a state on twice
        chunk, scan = kda._chunk, kda.kda_chunk_scan

        def rounded(s0, x, sub):
            s, o = chunk(bf16(s0), x, sub)
            return bf16(s), o

        monkeypatch.setattr(kda, "_chunk", rounded)
        monkeypatch.setattr(kda, "kda_chunk_scan",
                            lambda *a, **kw: scan(*a, chunk=8, **kw))
    else:
        real = blocks.log_decay
        monkeypatch.setattr(
            blocks, "log_decay",
            lambda z, a_log, lb: bf16(real(bf16(z), a_log, lb)))
    seq = rng.randint(0, 96, 23)
    logits, _ = _prefill(toy, seq)
    monkeypatch.undo()
    err = np.abs(np.asarray(logits[0, :23])
                 - reference_rows(toy, seq, np.arange(23))).max()
    assert err > 10 * TOL, err


# -- (b) prefill, then decoding through the state and the latent pool ---------


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_decode_through_the_cache_equals_the_reference(toy, kernel, rng):
    """Three requests of mixed lengths in one batch, through ``submit`` /
    ``step``: every KDA layer's state and tail written by the prefill's
    scan and advanced a token at a time, the MLA layer's rows across page
    boundaries. Every emitted token's logits row equals the reference's
    full forward over the same tokens; in plain XLA and by both kernels
    (interpreted)."""
    set_flag("paged_attention_kernel", kernel)
    try:
        with _engine(toy) as eng:
            assert eng.decode_kernel_info()[0] == (
                "gather" if kernel == "off" else "mla_paged")
            assert eng.cache_ops.state_kernel_mode()[0] == (
                None if kernel == "off" else "interpret")
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


def test_a_reused_slot_starts_from_nothing_and_an_idle_one_is_untouched(
        toy, rng):
    """ONE slot serves two requests in turn: the second's logits are a
    fresh engine's, bit for bit (the prefill executable zeroes the slot's
    state and tail as it arms it, then writes the prompt's over them).
    Meanwhile a slot that holds no request keeps what it held, poison
    included: its state is neither read nor written (the kernel,
    interpreted)."""
    set_flag("paged_attention_kernel", "interpret")
    try:
        a, b = rng.randint(0, 96, 11), rng.randint(0, 96, 6)
        with _engine(toy, slots=2) as eng:
            poison = eng._cache["s.state"].at[:, 1].set(1e4)
            eng._cache = {**eng._cache, "s.state": poison,
                          "tail.state": eng._cache["tail.state"].at[
                              :, 1].set(-7.0)}
            first = eng.submit(list(a), 9)
            eng.run()
            assert first.state == "finished"
            assert np.all(np.asarray(eng._cache["s.state"][:, 1]) == 1e4)
            assert np.all(np.asarray(eng._cache["tail.state"][:, 1]) == -7.0)
            assert np.abs(np.asarray(eng._cache["s.state"][:, 0])).max() > 0
            second = eng.submit(list(b), 7)
            eng.run()
            got = np.stack(eng.captured_logits(second))
        with _engine(toy, slots=2) as fresh:
            again = fresh.submit(list(b), 7)
            fresh.run()
            np.testing.assert_array_equal(
                got, np.stack(fresh.captured_logits(again)))
        assert second.tokens_out == again.tokens_out
    finally:
        set_flag("paged_attention_kernel", "auto")


# -- (c) the recurrence: chunk-wise against token by token --------------------


def _kda_case(rng, t, h=3, dk=16, dv=8):
    q, k = (rng.randn(t, h, dk).astype("float32") for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(t, h, dv).astype("float32")
    a = (-5.0 / (1 + np.exp(-(rng.randn(t, h, dk) * 2 - 2)))).astype(
        "float32")
    beta = (1 / (1 + np.exp(-rng.randn(t, h)))).astype("float32")
    return [jnp.asarray(x) for x in (q, k, v, a, beta)]


SCAN_FORMS = {
    "xla": kda.kda_chunk_scan_xla,
    "kernel": functools.partial(kda.kda_chunk_scan_kernel, interpret=True)}


@pytest.fixture(params=sorted(SCAN_FORMS))
def scan(request):
    """The chunk scan in each of its forms: the blocked ``jax.numpy`` loop,
    and the ``kda_chunk_scan`` kernel in the interpreter."""
    return SCAN_FORMS[request.param]


@pytest.mark.parametrize("t", [1, 50, 64, 130, 256])
def test_the_chunk_scan_equals_the_recurrence(t, scan, rng):
    """Lengths that are and are not multiples of 64, decays anywhere in
    (-5, 0): within 2e-5 of the token-by-token recurrence on outputs of
    order 1, and the final state within the same."""
    x = _kda_case(rng, t)
    o1, s1 = kda.kda_recurrence(*x)
    o2, s2 = scan(*x)
    assert o2.shape == o1.shape and s2.shape == s1.shape
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)


@pytest.mark.parametrize("heads", [32, 64])
def test_the_chunk_scan_at_the_served_head_counts_from_a_given_state(
        heads, scan, rng):
    """32 and 64 heads (four and eight blocks of the kernel's grid), 100
    tokens (a chunk and a padded tail) and a state to start from: outputs
    and final state within 2e-5 of the recurrence's, in the bfloat16 the
    served model hands the scan too."""
    x = _kda_case(rng, 100, heads)
    s0 = jnp.asarray(rng.randn(heads, 16, 8).astype("float32"))
    o1, s1 = kda.kda_recurrence(*x, s0=s0)
    o2, s2 = scan(*x, s0=s0)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)
    q, k, v = (t.astype(jnp.bfloat16) for t in x[:3])
    o1, s1 = kda.kda_recurrence(q, k, v, *x[3:], s0=s0)
    o2, s2 = scan(q, k, v, *x[3:], s0=s0)
    assert o2.dtype == s2.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)


def test_the_chunk_scan_is_exact_where_a_chunks_keys_resemble_each_other(
        scan, rng):
    """Keys that are nearly ONE direction with little decay and strong
    writes (what a served model's hidden states give: the chip's first run
    read 5 row deviations from the series form of ``(I + A)^-1``, whose
    terms reach 1e10 there and cancel): the blocked forward substitution
    is as exact as anywhere."""
    t, h, dk, dv = 200, 2, 16, 8
    base = rng.randn(1, h, dk)
    k = base + 0.05 * rng.randn(t, h, dk)
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype("float32")
    q, _, v, _, _ = _kda_case(rng, t, h, dk, dv)
    a = jnp.full((t, h, dk), -1e-3, jnp.float32)
    beta = jnp.full((t, h), 0.95, jnp.float32)
    o1, s1 = kda.kda_recurrence(q, jnp.asarray(k), v, a, beta)
    o2, s2 = scan(q, jnp.asarray(k), v, a, beta)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)


def test_the_scan_is_safe_at_the_decay_bound_and_carries_a_state(scan, rng):
    """Every step at the lower bound (e^-320 over a chunk) neither
    overflows nor loses the near pairs; and a scan continued from a state
    is the scan of the whole."""
    q, k, v, a, beta = _kda_case(rng, 128)
    hard = jnp.full_like(a, -4.999)
    o1, s1 = kda.kda_recurrence(q, k, v, hard, beta)
    o2, s2 = scan(q, k, v, hard, beta)
    assert np.isfinite(np.asarray(o2)).all()
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    whole, s_whole = scan(q, k, v, a, beta)
    head, s_head = scan(q[:70], k[:70], v[:70], a[:70], beta[:70])
    tail, s_tail = scan(q[70:], k[70:], v[70:], a[70:], beta[70:],
                        s0=s_head)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(whole[70:]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_tail), np.asarray(s_whole),
                               atol=2e-5)


def test_the_scan_gate_names_what_it_refuses_and_chooses_the_form(
        monkeypatch):
    """The kernel takes chunks of 64 of heads in blocks of 8 whose q, k and
    v are whole lane tiles, and ``kda_chunk_scan`` asks it only on a TPU:
    here, and where the gate refuses, the blocked ``jax.numpy`` runs."""
    assert kda.kda_chunk_scan_gate(32, 128, 128) is None
    assert kda.kda_chunk_scan_gate(64, 128, 128) is None
    assert "128-lane" in kda.kda_chunk_scan_gate(32, 128, 64)
    assert "128-lane" in kda.kda_chunk_scan_gate(32, 64, 128)
    assert "blocks of 8" in kda.kda_chunk_scan_gate(12, 128, 128)
    assert "KiB of VMEM" in kda.kda_chunk_scan_gate(32, 256, 256)
    assert "chunks of 64" in kda.kda_chunk_scan_gate(32, 128, 128, chunk=32)
    assert "chunks of 64" in kda.kda_chunk_scan_gate(4, 16, 8, chunk=8,
                                                     interpret=True)
    assert kda.kda_chunk_scan_gate(3, 16, 8, interpret=True) is None
    with pytest.raises(ValueError, match="128-lane"):
        kda.kda_chunk_scan_kernel(*_kda_case(np.random.RandomState(0), 8))
    took = []
    monkeypatch.setattr(kda, "kda_chunk_scan_kernel",
                        lambda *a, **kw: took.append("kernel"))
    monkeypatch.setattr(kda, "kda_chunk_scan_xla",
                        lambda *a, **kw: took.append("xla"))
    small = _kda_case(np.random.RandomState(0), 8)
    served = [jnp.zeros((8, 8, 128))] * 4 + [jnp.zeros((8, 8))]
    kda.kda_chunk_scan(*served)
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    kda.kda_chunk_scan(*small)
    kda.kda_chunk_scan(*served)
    kda.kda_chunk_scan(*served, chunk=32)
    assert took == ["xla", "xla", "kernel", "xla"]


def _scan_calls():
    snap = mx.snapshot()
    return {f: snap.get("kda/scan_calls." + f, {"value": 0})["value"]
            for f in ("kernel", "blocked")}


@pytest.mark.parametrize("form", ["blocked", "kernel"])
def test_the_scan_counts_the_form_it_chose_once_a_traced_call(
        form, monkeypatch):
    """Two layers' scans in one program, at a geometry the gate takes:
    ``kda/scan_calls.<form>`` rises by two when the program is traced
    (here the blocked form, on a chip that is pretended the kernel) and
    not again when the traced program runs or is asked for again."""
    x = [jnp.ones((64, 8, 128))] * 4 + [jnp.ones((64, 8))]
    if form == "kernel":
        monkeypatch.setattr(kda, "_on_tpu", lambda: True)

    def two_layers(*x):
        o, s = kda.kda_chunk_scan(*x)
        return kda.kda_chunk_scan(*x, s0=s)[0] + o

    program = jax.jit(two_layers)
    before = _scan_calls()
    text = str(program.trace(*x).jaxpr)
    after = _scan_calls()
    assert {f: after[f] - before[f] for f in after} == {
        f: 2.0 * (f == form) for f in after}
    assert ("pallas_call" in text) is (form == "kernel")
    if form == "blocked":
        program(*x), program(*x)
    else:
        program.trace(*x)
    assert _scan_calls() == after


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0] * 5, [1] * 5,
                                  [0, 0, 0, 0, 1]])
def test_the_step_kernel_equals_plain_xla_and_skips_idle_slots(live, rng):
    """One decode step of layer 1 of two, 5 slots, by the kernel
    (interpreted) and in plain XLA: the live slots' outputs and states
    agree, an idle slot's output is 0 and its state and the other layer's
    bit-equal what they were."""
    b, h, dk, dv = 5, 4, 16, 128
    states = jnp.asarray(rng.randn(2, b, h, dk, dv).astype("float32"))
    q, k, v, a, beta = _kda_case(rng, b, h, dk, dv)
    active = jnp.asarray(live, bool)
    o1, s1 = kda.kda_state_step_xla(states, 1, q, k, v, a, beta, active)
    o2, s2 = kda.kda_state_step(states, 1, q, k, v, a, beta, active,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-6)
    idle = ~np.asarray(active)
    assert np.all(np.asarray(o2)[idle] == 0)
    np.testing.assert_array_equal(np.asarray(s2[1])[idle],
                                  np.asarray(states[1])[idle])
    np.testing.assert_array_equal(np.asarray(s2[0]), np.asarray(states[0]))
    # and the step IS the recurrence's
    want_o, want_s = kda.kda_recurrence(q[:1], k[:1], v[:1], a[:1], beta[:1],
                                        s0=states[1, 0])
    if live[0]:
        np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(want_o[0]),
                                   atol=2e-6)
        np.testing.assert_allclose(np.asarray(s1[1, 0]),
                                   np.asarray(want_s), atol=2e-6)


def test_the_gate_names_what_it_refuses():
    assert kda.kda_state_step_gate(32, 128, 128) is None
    assert "float32 tiles" in kda.kda_state_step_gate(32, 128, 64)
    assert "sublanes" in kda.kda_state_step_gate(6, 128, 128)
    assert kda.kda_state_step_gate(4, 16, 16, interpret=True) is None
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunk_scan(*_kda_case(np.random.RandomState(0), 8),
                           chunk=48)
    with pytest.raises(ValueError, match="lower_bound"):
        toy_cfg(lower_bound=-9.0)


def test_the_seeded_gates_keep_a_long_context_in_the_state(rng):
    """What the decay's seeding is for: under ``init_params``'s gates (at a
    zero pre-activation, half-lives spread over 4 to 4,096 tokens) the
    state after 2,048 tokens still depends on token 1: a write of 0.05 an
    entry is still 1.6e-5 there (the slowest channels keep 0.7 of it, and
    2,047 delta-rule writes of strength one half over 128-lane keys erase
    all but e^-8 of any one direction), fifty times float32's step at the
    state's size; with ``dt_bias`` 0 every channel has forgotten it
    exactly."""
    cfg = toy_cfg(n_head=1, d_state=128, half_life=(4.0, 4096.0))
    lp = lf._init_layer(cfg, jax.random.PRNGKey(0), "kda", True)
    t = 2048
    q, k, v, _, beta = _kda_case(rng, t, 1, 128, 16)
    finals = {}
    for name, bias in (("seeded", lp["dt_bias"]),
                       ("zero", jnp.zeros_like(lp["dt_bias"]))):
        a = jnp.broadcast_to(
            ref.log_decay(bias.reshape(1, 128), lp["a_log"], -5.0),
            (t, 1, 128))
        life = -np.log(2) / np.asarray(a[0, 0])
        _, s = kda.kda_chunk_scan(q, k, v, a, beta)
        _, s_moved = kda.kda_chunk_scan(q, k, v.at[1].add(1.0), a, beta)
        finals[name] = (life, float(jnp.abs(s_moved - s).max()),
                        float(jnp.abs(s).max()))
    life, moved, size = finals["seeded"]
    assert 4.0 <= life.min() < 8.0 and 2048.0 < life.max() <= 4096.0
    assert moved > 50 * np.finfo(np.float32).eps * size > 0, finals
    assert finals["zero"][0].max() < 0.3 and finals["zero"][1] == 0.0


# -- (d) the router and the expert layer --------------------------------------


def test_group_limited_router_against_a_loop(rng):
    """8 groups of 4, 3 stay, top-5: a group scores the sum of its two
    largest ``s + b``; the chosen lie in the kept groups and are the
    largest ``s + b`` there; weighed by ``s`` alone. And ``n_group`` 1 is
    the function as it was, bit for bit."""
    n, d, e, k, scale, groups, keep = 13, 16, 32, 5, 2.5, 8, 3
    h = jnp.asarray(rng.randn(n, d).astype("float32"))
    wr = jnp.asarray(rng.randn(d, e).astype("float32"))
    b = jnp.asarray((0.3 * rng.randn(e)).astype("float32"))
    idx, w = moe_ops.route_sigmoid_topk(h, wr, b, k, scale, n_group=groups,
                                        topk_group=keep)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(wr))))
    limited = 0
    for i in range(n):
        biased = s[i] + np.asarray(b)
        score = [np.sort(biased[g * 4:g * 4 + 4])[-2:].sum()
                 for g in range(groups)]
        kept = np.argsort(score)[-keep:]
        allowed = [j for j in range(e) if j // 4 in kept]
        want = sorted(allowed, key=lambda j: -biased[j])[:k]
        assert sorted(np.asarray(idx[i]).tolist()) == sorted(want)
        limited += sorted(want) != sorted(np.argsort(-biased)[:k].tolist())
        chosen = s[i][np.asarray(idx[i])]
        np.testing.assert_allclose(np.asarray(w[i]),
                                   scale * chosen / chosen.sum(), rtol=1e-5)
    assert limited >= n // 3      # the limit changes the choice
    plain = moe_ops.route_sigmoid_topk(h, wr, b, k, scale)
    one = moe_ops.route_sigmoid_topk(h, wr, b, k, scale, n_group=1,
                                     topk_group=1)
    s32 = jax.nn.sigmoid(jnp.dot(h, wr, preferred_element_type=jnp.float32))
    _, old = jax.lax.top_k(s32 + b, k)
    for got in (plain, one):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(old))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(plain[1]))
    # the reference's own statement of the rule agrees
    ridx, rw = ref.route(s32, b, k, groups, keep, scale)
    np.testing.assert_array_equal(np.sort(np.asarray(ridx)),
                                  np.sort(np.asarray(idx)))


def test_four_shares_and_one_shared_expert_add_up_to_the_whole_layer(toy,
                                                                      rng):
    """The deployment's arithmetic at toy size: four chips hold one router
    group of four experts each, every chip has the router and the shared
    expert. The routed parts of the four shares, with the shared expert
    counted ONCE, add up to the uncut reference's whole layer."""
    lp = toy.params["layers"][1]
    x = jnp.asarray(rng.randn(9, 64).astype("float32"))
    whole = np.asarray(ref._sparse(lp, x, 4, 4, 2, 2.5, 1e-6,
                                   tuple(range(16))))
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
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ref._sparse(part, x, 4, 4, 2, 2.5, 1e-6, held)),
            atol=TOL, rtol=0)
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)


def test_a_share_through_the_engine_equals_the_reference_given_the_share(rng):
    """Two of four router groups held (8 of 16 experts): prefill and decode
    through the cache equal the reference given the same share; the
    counters see the share's load and the states stepped."""
    from paddle_tpu.serving import metrics as sm

    held = tuple(range(8))
    model = toy_model(experts_held=held)
    assert model.params["layers"][1]["wg"].shape[0] == 8
    s0 = (sm.STATE_SLOTS_STEPPED.count, sm.STATE_SLOTS_STEPPED.sum)
    t0 = sm.MOE_EXPERTS_TOUCHED.count
    r0 = sm.attn_rows_read("latent").sum
    with _engine(model) as eng:
        assert sm.STATE_POOL_BYTES.value == eng.cache_ops.state_bytes(
            eng._cache) == 3 * 3 * (4 * 16 * 16 + 3 * 192) * 4
        prompt = rng.randint(0, 96, 9)
        req = eng.submit(list(prompt), 10)
        eng.run()
        seq = list(prompt) + req.tokens_out[:-1]
        want = reference_rows(model, seq, np.arange(8, 18),
                              experts_held=list(held))
        np.testing.assert_allclose(np.stack(eng.captured_logits(req)), want,
                                   atol=TOL, rtol=0)
    steps = 9                      # the first token comes from the prefill
    assert sm.STATE_SLOTS_STEPPED.count - s0[0] == steps
    assert sm.STATE_SLOTS_STEPPED.sum - s0[1] == steps      # one live slot
    assert sm.MOE_EXPERTS_TOUCHED.count - t0 == steps * 3   # expert layers
    # the latent layer read contexts of 10 .. 18 rows
    assert sm.attn_rows_read("latent").sum - r0 == sum(range(10, 19))


# -- (e) the cache --------------------------------------------------------------


def test_the_cache_holds_pages_for_the_latent_layers_alone(toy):
    """Seven layers as the cell cuts them (six KDA, one MLA): ONE layer's
    rows live in pages, six layers' states belong to the slots; only the
    latent group has a pool, and admission reserves there alone."""
    cfg = toy_cfg(n_layer=7, layer_types=["kda"] * 6 + ["mla"])
    with _engine(lf.Ling3FlashLM(cfg, params={})) as eng:
        ops = eng.cache_ops
        assert isinstance(ops, LatentPagedCache)
        assert [(g.name, g.kind, g.layers, g.num_pages)
                for g in ops.groups] == [
            ("latent", LATENT, (6,), 20), ("state", STATE, tuple(range(6)), 0)]
        assert sorted(eng._cache) == ["c", "pt", "s.state", "tail.state"]
        assert eng._cache["c"].shape == (1, 160, 128)
        assert eng._cache["s.state"].shape == (6, 3, 4, 16, 16)
        assert eng._cache["s.state"].dtype == jnp.float32
        assert eng._cache["tail.state"].shape == (6, 3, 3, 192)
        assert [p.name for p in eng.pools] == ["latent"]
        assert ops.page_table_len == 8 + 1     # the slot rides last
        dest = ops.prompt_dest_groups([[5, 2]], slot=2)
        assert dest.tolist() == [5, 2, 0, 0, 0, 0, 0, 0, 2]
        assert ops.cache_bytes(eng._cache) == 160 * 128 * 4 \
            + ops.state_bytes(eng._cache)
        assert set(ops.rows_read(jnp.asarray([3, 0, 9]),
                                 jnp.asarray([True, False, True]))) == {
            "attn_rows_read.latent"}
    with _engine(toy, collect_logits=False) as eng:
        req = eng.submit(list(range(1, 12)), 6)
        eng.step()
        # 17 positions: 3 pages, handed out as one run of 4
        assert eng.pool.num_used == 4 and eng.page_accounting_ok()
        assert eng.stats()["pages_by_group"] == {"latent": [4, 20]}
        eng.run()
        assert req.state == "finished" and eng.pool.num_used == 0
        assert eng.page_accounting_ok()
        assert eng.stats()["layout"] == "paged-latent"


def test_a_state_group_comes_last_and_needs_its_geometry():
    groups = [CacheGroup("state", (0,), None, 0, STATE),
              CacheGroup("latent", (1,), None, 8, LATENT)]
    with pytest.raises(ValueError, match="after every paged group"):
        LatentPagedCache(2, 16, 8, 2, 64, 8, 8, groups=groups,
                         slot_state=(4, 16, 16, 3, 192))
    with pytest.raises(ValueError, match="slot_state"):
        LatentPagedCache(2, 16, 8, 2, 64, 8, 8, groups=groups[::-1])
    with pytest.raises(ValueError, match="ONE latent group"):
        LatentPagedCache(2, 16, 8, 2, 64, 8, 8, groups=[
            CacheGroup("a", (0,), None, 8, LATENT),
            CacheGroup("b", (1,), None, 8, LATENT)])


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(prefix_cache_pages=4), "prefix cache"),
    (dict(paged=False), "contiguous layout"),
])
def test_what_this_cache_cannot_do_is_refused_at_construction(toy, kw, what):
    with pytest.raises(ValueError,
                       match=what + ".*latent cache.*state a slot"):
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
            with pytest.raises(ValueError, match=what + ".*state: state"):
                call()

"""The single-tile attention kernels (ops/pallas_kernels/short_attention.py)
against the composed reference AT THE SAME KEEP MASK, and the rule by
which ``sdpa`` takes them.

The kernels' dropout is a hash of absolute coordinates
(``flash_attention._dropout_keep_tile``), so the reference regenerates the
mask outside the kernel and output and all three gradients must agree
elementwise. The real kernel bodies run here in Pallas's interpreter on
the CPU; ``tests/test_chip_compile.py`` compiles them for the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.ops.pallas_kernels import flash_attention as fa
from paddle_tpu.ops.pallas_kernels import short_attention as sa

B, H, D = 3, 4, 64           # three rows: a block of two does not divide them
SM_SCALE = 0.125
SEED = 1234
# (causal, S_q, S_k): the encoder's, the decoder's own, the decoder's cross
MASK_CASES = {"not_causal": (False, 128, 128), "causal": (True, 128, 128),
              "cross": (False, 128, 256)}
CASES = [(m, seg, rate) for m in MASK_CASES for seg in (False, True)
         for rate in (0.0, 0.1)]


@pytest.fixture(autouse=True)
def _interpret_kernels():
    sa.INTERPRET = fa.INTERPRET = True
    yield
    sa.INTERPRET = fa.INTERPRET = False


def _keep_mask(rate, seed, b, h, sq, sk, first_row=0):
    """The mask the kernels generate, outside them."""
    return jnp.stack([jnp.stack([
        fa._dropout_keep_tile(rate, seed, first_row + bi, hi, 0, 0, (sq, sk))
        for hi in range(h)]) for bi in range(b)])


def _segments(rng, b, s):
    """Ids that mask a padded tail: 1 on a row's tokens, 0 on its pads."""
    lens = rng.randint(s // 2, s + 1, size=b)
    return jnp.asarray(np.arange(s)[None] < lens[:, None], jnp.int32)


def _reference(q, k, v, seg_q, seg_kv, keep, causal, rate):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * SM_SCALE
    if seg_q is not None:
        s = jnp.where(seg_q[:, None, :, None] == seg_kv[:, None, None, :], s,
                      fa.DEFAULT_MASK_VALUE)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s,
                      fa.DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _case(rng, mask, seg, rate, blocks=(2, 2)):
    causal, sq, sk = MASK_CASES[mask]
    q = jnp.asarray(rng.randn(B, H, sq, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, sk, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, sk, D).astype("float32"))
    seg_q = _segments(rng, B, sq) if seg else None
    seg_kv = None if not seg else seg_q if sq == sk else _segments(rng, B, sk)
    seed = jnp.asarray([SEED], jnp.int32) if rate else None
    keep = _keep_mask(rate, SEED, B, H, sq, sk)
    args = (q, k, v, seg_q, seg_kv, seed, causal, SM_SCALE, rate, blocks)

    def ref(q, k, v):
        return _reference(q, k, v, seg_q, seg_kv, keep, causal, rate)

    return args, ref


@pytest.mark.parametrize("mask,seg,rate", CASES)
def test_output_matches_composed_at_the_same_keep_mask(rng, mask, seg, rate):
    args, ref = _case(rng, mask, seg, rate)
    out = sa.single_tile_attention(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(*args[:3])),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mask,seg,rate", CASES)
def test_gradients_match_composed_at_the_same_keep_mask(rng, mask, seg, rate):
    """dq, dk and dv of the ONE backward kernel against ``jax.vjp`` of the
    reference, for a cotangent that is no constant."""
    args, ref = _case(rng, mask, seg, rate)
    do = jnp.asarray(rng.randn(*args[0].shape).astype("float32"))
    got = jax.vjp(lambda q, k, v: sa.single_tile_attention(
        q, k, v, *args[3:]), *args[:3])[1](do)
    want = jax.vjp(ref, *args[:3])[1](do)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


@pytest.mark.parametrize("blocks", [(1, 2), (1, 4), (2, 2), (3, 4), (4, 2)])
def test_blocks_do_not_change_the_result(rng, blocks):
    """Rows and heads a grid step are the schedule's, not the result's: a
    block larger than the batch and one that does not divide it too."""
    args, _ = _case(rng, "causal", True, 0.1, blocks=(1, 2))
    want = sa.single_tile_attention(*args)
    got = sa.single_tile_attention(*args[:-1], blocks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bfloat16_runs_the_tile_in_float32(rng):
    """bfloat16 operands: the products take them as they are, the softmax
    is float32; within bfloat16's rounding of the float32 reference."""
    args, _ = _case(rng, "causal", True, 0.1)
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    out = sa.single_tile_attention(q, k, v, *args[3:])
    assert out.dtype == jnp.bfloat16
    keep = _keep_mask(0.1, SEED, B, H, 128, 128)
    want = _reference(*(x.astype(jnp.float32) for x in (q, k, v)),
                      args[3], args[4], keep, True, 0.1)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_a_shards_seed_moves_the_batch_coordinate(rng):
    """``shard_seed(seed, r)`` at row b hashes as ``seed`` at row r + b."""
    moved = sa.shard_seed(jnp.asarray([SEED], jnp.int32), 96)[0]
    for b in (0, 5):
        np.testing.assert_array_equal(
            np.asarray(fa._dropout_keep_tile(0.1, moved, b, 3, 0, 0,
                                             (128, 128))),
            np.asarray(fa._dropout_keep_tile(0.1, SEED, 96 + b, 3, 0, 0,
                                             (128, 128))))


# -- the rule of shapes -----------------------------------------------------


def _shape(*dims):
    return jax.ShapeDtypeStruct(dims, jnp.bfloat16)


@pytest.mark.parametrize("q,k,causal,bias,takes", [
    ((96, 8, 256, 64), (96, 8, 256, 64), False, False, True),   # the cell's
    ((96, 8, 256, 64), (96, 8, 256, 64), True, False, True),
    ((96, 8, 128, 64), (96, 8, 512, 64), False, False, True),   # cross
    ((96, 8, 128, 64), (96, 8, 512, 64), True, False, False),   # causal: square
    ((96, 8, 256, 64), (96, 8, 256, 64), False, True, False),   # a bias
    ((4, 8, 1024, 64), (4, 8, 1024, 64), True, False, False),   # past one tile
    ((4, 8, 2048, 64), (4, 8, 2048, 64), True, False, False),   # the long path's
    ((96, 8, 200, 64), (96, 8, 200, 64), False, False, False),  # no lane tiles
    ((1, 12, 256, 64), (1, 12, 256, 64), True, False, False),   # GPT-2's prefill
    ((96, 8, 256, 192), (96, 8, 256, 192), True, False, False),  # a wide head
])
def test_the_rule_of_shapes(monkeypatch, q, k, causal, bias, takes):
    monkeypatch.setattr(ao, "_on_tpu", lambda: True)
    got = ao._single_tile_ok(_shape(*q), _shape(*k), causal,
                             object() if bias else None)
    assert got is takes
    # the long path's gate reads as it did: from 2048 on, whatever the rows
    assert ao._flash_ok(_shape(*q), _shape(*k), causal) is (
        max(q[2], k[2]) >= 2048)


def test_off_the_chip_nothing_takes_the_kernels():
    assert not ao._single_tile_ok(_shape(96, 8, 256, 64),
                                  _shape(96, 8, 256, 64), False)


@pytest.mark.parametrize("helper", ["gqa", "mla"])
@pytest.mark.parametrize("s,asks", [(256, False), (2048, True)])
def test_grouped_and_latent_prefills_stay_where_they_were(
        monkeypatch, rng, helper, s, asks):
    """``gqa_causal_attention`` and ``mla_causal_attention`` ask
    ``_flash_ok`` themselves: a short sequence composes its scores in the
    helper and never reaches ``sdpa``; a long one reaches it with ONE row,
    which the single-tile rule leaves to the long path."""
    monkeypatch.setattr(ao, "_on_tpu", lambda: True)
    seen = []

    def spy(q, k, v, **kw):
        seen.append((q.shape, ao._single_tile_ok(q, k, kw.get("causal"))))
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    monkeypatch.setattr(ao, "sdpa", spy)
    if helper == "gqa":
        q = jnp.zeros((s, 8, 64), jnp.bfloat16)
        kv = jnp.zeros((s, 2, 64), jnp.bfloat16)
        out = ao.gqa_causal_attention(q, kv, kv, 0.125)
        assert out.shape == (s, 8, 64)
    else:
        q = jnp.zeros((s, 4, 96), jnp.bfloat16)
        out = ao.mla_causal_attention(
            q, jnp.zeros((s, 4, 64), jnp.bfloat16),
            jnp.zeros((s, 32), jnp.bfloat16),
            jnp.zeros((s, 4, 64), jnp.bfloat16), 0.125)
        assert out.shape == (s, 4, 64)
    assert bool(seen) is asks
    assert all(not single_tile for _, single_tile in seen)


def _calls():
    snap = mx.snapshot()
    return {p: snap.get("attention/sdpa_calls." + p, {"value": 0})["value"]
            for p in ("single_tile", "composed", "flash")}


@pytest.mark.parametrize("s,path", [(128, "single_tile"), (1024, "composed")])
def test_sdpa_counts_the_path_it_chose_and_agrees_with_composed(
        monkeypatch, rng, s, path):
    """Through ``sdpa`` itself, on a chip that is pretended: 64 pairs of
    one tile take the kernels, S = 1024 the composed lines; dropout off,
    so both are held to the composed result."""
    monkeypatch.setattr(ao, "_on_tpu", lambda: True)
    b, h = (8, 8) if s == 128 else (1, 2)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, 64).astype("float32"))
               for _ in range(3))
    seg = _segments(rng, b, s)
    before = _calls()
    out = ao.sdpa(q, k, v, None, seg, seg, True, 0.125, 0.1, None)
    after = _calls()
    assert {p: after[p] - before[p] for p in after} == {
        p: float(p == path) for p in after}
    want = ao._composed(q, k, v, None, seg, seg, True, 0.125, 0.0, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# -- four chips: the kernel runs a shard --------------------------------------


@pytest.fixture
def mesh4():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]), ("data",))


@pytest.mark.parametrize("rate", [0.0, 0.1])
def test_the_sharded_call_equals_the_unsharded_one(rng, mesh4, rate):
    """Over a mesh of 4 the call is mapped over the rows' shards. With
    dropout off it is the unsharded call; with it on too, because a shard
    hashes its rows at their place in the whole batch."""
    b, h, s = 8, 2, 128
    q, k, v = (jnp.asarray(rng.randn(b, h, s, D).astype("float32"))
               for _ in range(3))
    seg = _segments(rng, b, s)
    key = jax.random.PRNGKey(3) if rate else None
    whole = ao._single_tile(q, k, v, seg, seg, True, SM_SCALE, rate, key, None)
    sharded = jax.jit(lambda q, k, v, seg: ao._single_tile(
        q, k, v, seg, seg, True, SM_SCALE, rate, key, mesh4))(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


def test_two_shards_drop_different_elements(rng, mesh4):
    """Every shard is handed the SAME two rows; were the shard's index not
    folded into the hash, every shard would return the same numbers."""
    h, s = 2, 128
    one = [rng.randn(2, h, s, D).astype("float32") for _ in range(3)]
    q, k, v = (jnp.asarray(np.tile(x, (4, 1, 1, 1))) for x in one)
    out = np.asarray(jax.jit(lambda q, k, v: ao._single_tile(
        q, k, v, None, None, False, SM_SCALE, 0.5, jax.random.PRNGKey(0),
        mesh4))(q, k, v)).reshape(4, 2, h, s, D)
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.abs(out[a] - out[b]).max() > 1e-3, (a, b)
    # and with dropout off they do return the same numbers
    same = np.asarray(ao._single_tile(q, k, v, None, None, False, SM_SCALE,
                                      0.0, None, mesh4)).reshape(4, -1)
    np.testing.assert_allclose(same[1:], same[:1].repeat(3, 0), rtol=1e-6,
                               atol=1e-6)


# -- through the trainer ------------------------------------------------------


@pytest.mark.parametrize("chips", [1, 4])
def test_transformer_trains_through_the_kernels(monkeypatch, rng, chips):
    """One block of the translation Transformer through ``fluid.Executor``
    (and ``with_data_parallel`` over four virtual chips) on a chip that is
    pretended: its three attentions (encoder, decoder, cross; dropout 0.1,
    segment ids from the masks) trace into the kernels, forward and
    backward, and the loss of a repeated batch falls."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    monkeypatch.setattr(ao, "_on_tpu", lambda: True)
    rows, seq, vocab = 8, 128, 50
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = 7
    with fluid.program_guard(main, startup):
        src = fluid.layers.data("src", shape=[seq], dtype="int64")
        trg = fluid.layers.data("trg", shape=[seq], dtype="int64")
        lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
        smask = fluid.layers.data("smask", shape=[seq], dtype="float32")
        tmask = fluid.layers.data("tmask", shape=[seq], dtype="float32")
        _, loss = tfm.transformer(
            src, trg, lbl, smask, tmask, vocab, vocab, max_length=seq,
            n_layer=1, n_head=8, d_model=512, d_inner=64, dropout_rate=0.1)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    prog = main if chips == 1 else fluid.CompiledProgram(
        main).with_data_parallel(loss_name=loss.name)
    lens = rng.randint(seq // 2, seq + 1, size=rows)
    mask = (np.arange(seq)[None] < lens[:, None]).astype("float32")
    feed = {"src": rng.randint(1, vocab, (rows, seq)).astype("int64"),
            "trg": rng.randint(1, vocab, (rows, seq)).astype("int64"),
            "lbl": rng.randint(1, vocab, (rows, seq, 1)).astype("int64"),
            "smask": mask, "tmask": mask}
    before = _calls()
    losses = [float(np.asarray(exe.run(prog, feed=feed,
                                       fetch_list=[loss])[0]).ravel()[0])
              for _ in range(4)]
    after = _calls()
    assert after["single_tile"] - before["single_tile"] >= 3
    assert after["composed"] == before["composed"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses

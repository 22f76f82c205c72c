"""The ``dropout`` op's mask: the attention kernels' coordinate hash
(``ops/keep_hash.py``) over an element's position in the whole array and
the op's two key words. Held here: the rate, independence along every
axis and between sites, steps and seeds (each within 4 sigma of what
independent Bernoulli draws give), determinism in (seed, site, step), ONE
mask forward and backward, the same global mask on one device and on four,
shape inference, and the trace-time counter ``dropout/draws.hash``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import keep_hash
from paddle_tpu.testing.op_test import run_op

SHAPE = (96, 256, 512)          # a residual dropout of Transformer-base
N = int(np.prod(SHAPE))


def _sigmas(share, want, n):
    return abs(share - want) / np.sqrt(want * (1.0 - want) / n)


def _agree(p):
    """How often two independent keep masks of rate ``p`` say the same."""
    return (1.0 - p) ** 2 + p ** 2


# -- the rate and the axes ----------------------------------------------------


@pytest.mark.parametrize("p", [0.1, 0.3, 0.5])
def test_kept_share(p):
    out = np.asarray(run_op("dropout", {"X": np.ones(SHAPE, "float32")},
                            ["Out"], attrs={"dropout_prob": p})["Out"])
    assert _sigmas((out != 0).mean(), 1.0 - p, N) < 4.0


@pytest.fixture(scope="module")
def one_mask():
    return np.asarray(keep_hash.keep_mask(
        jax.random.PRNGKey(11), SHAPE, 0.3))


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_neighbours_along_an_axis_are_independent(one_mask, axis):
    a = np.take(one_mask, np.arange(SHAPE[axis] - 1), axis)
    b = np.take(one_mask, np.arange(1, SHAPE[axis]), axis)
    assert _sigmas((a == b).mean(), _agree(0.3), a.size) < 4.0


def test_a_typed_key_is_its_two_words(one_mask):
    typed = jax.random.wrap_key_data(jax.random.PRNGKey(11))
    assert (np.asarray(keep_hash.keep_mask(typed, SHAPE, 0.3))
            == one_mask).all()


@pytest.mark.parametrize("word", [0, 1])
def test_a_near_key_is_no_translate_of_the_mask(one_mask, word):
    """Two keys one bit apart in either word: the masks agree as
    independent ones do, at the same positions and at the positions the
    bit moves (``i ^ 1``: with ONE round over ``i ^ key`` the second would
    be the first mask again, its neighbours swapped; at Transformer-base's
    sizes some ten pairs of the 44 sites a step would share a mask so)."""
    key = np.asarray(jax.random.PRNGKey(11)).copy()
    key[word] ^= 1
    near = np.asarray(keep_hash.keep_mask(jnp.asarray(key), SHAPE, 0.3))
    swapped = near.reshape(-1, 2)[:, ::-1].reshape(SHAPE)
    for other in (near, swapped):
        assert _sigmas((other == one_mask).mean(), _agree(0.3), N) < 4.0


def test_positions_past_one_word_stay_unique():
    """An array of more than 2**32 elements cannot be made here: the same
    split at a limit of 40 elements."""
    low, high = keep_hash.position_words((3, 4, 5, 6), limit=40)
    pairs = set(zip(np.asarray(low).ravel().tolist(),
                    np.asarray(high).ravel().tolist()))
    assert len(pairs) == 360 and int(np.asarray(low).max()) == 29
    low, high = keep_hash.position_words((3, 4, 5, 6))
    assert high is None
    assert (np.asarray(low).ravel() == np.arange(360)).all()


# -- sites, steps, seeds: through the Executor --------------------------------

P = 0.3


def _two_sites(seed):
    """``x`` through two dropout ops (two RNG slots): the masks of both."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=list(SHAPE[1:]), dtype="float32")
        a = fluid.layers.dropout(x, P)
        b = fluid.layers.dropout(x, P)
    exe = fluid.Executor(fluid.TPUPlace(0))

    def step():
        return [np.asarray(v) != 0 for v in exe.run(
            main, feed={"x": np.ones(SHAPE, "float32")}, fetch_list=[a, b])]

    return step


@pytest.fixture(scope="module")
def masks():
    """(seed, step) -> the two sites' masks. Seed 0 is the unseeded
    program, whose keys follow the step; a program WITH a seed draws the
    same mask every step (Fluid's ``fix_seed``: ``TraceContext.op_rng``)."""
    out = {}
    for seed in (0, 5, 6):
        step = _two_sites(seed)
        for i in range(2):
            out[seed, i] = step()
    return out


@pytest.mark.parametrize("which,one,other", [
    ("two sites", (0, 0, 0), (0, 0, 1)),
    ("two sites of a seeded program", (5, 0, 0), (5, 0, 1)),
    ("two steps", (0, 0, 0), (0, 1, 0)),
    ("two steps, the second site", (0, 0, 1), (0, 1, 1)),
    ("two seeds", (5, 0, 0), (6, 0, 0)),
    ("a seed and none", (0, 0, 0), (5, 0, 0)),
])
def test_masks_are_independent_between(masks, which, one, other):
    a, b = (masks[s, i][site] for s, i, site in (one, other))
    assert _sigmas((a == b).mean(), _agree(P), N) < 4.0, which


def test_the_same_seed_site_and_step_give_the_same_mask(masks):
    for seed in (0, 5):
        again = _two_sites(seed)
        for i in range(2):
            for site, mask in enumerate(again()):
                assert (mask == masks[seed, i][site]).all(), (seed, i, site)


# -- one mask, forward and backward -------------------------------------------


@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
def test_out_is_zero_exactly_where_mask_is(rng, impl):
    x = (rng.rand(64, 384) + 0.5).astype("float32")
    got = run_op("dropout", {"X": x}, ["Out", "Mask"],
                 attrs={"dropout_prob": 0.4, "dropout_implementation": impl})
    out, mask = np.asarray(got["Out"]), np.asarray(got["Mask"])
    assert set(np.unique(mask)) == {0.0, 1.0}
    assert ((out == 0) == (mask == 0)).all()
    scale = 1.0 / 0.6 if impl == "upscale_in_train" else 1.0
    np.testing.assert_allclose(out, x * mask * scale, rtol=1e-6)


def test_the_gradient_is_zero_exactly_where_the_output_is(rng):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[384], dtype="float32")
        x.stop_gradient = False
        w = fluid.layers.data("w", shape=[384], dtype="float32")
        out = fluid.layers.dropout(
            x, 0.4, dropout_implementation="upscale_in_train")
        loss = fluid.layers.reduce_sum(out * w)
        grad, = fluid.backward.gradients([loss], [x])
    feed = {"x": (rng.rand(64, 384) + 0.5).astype("float32"),
            "w": (rng.rand(64, 384) + 0.5).astype("float32")}
    o, g = (np.asarray(v) for v in fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=feed, fetch_list=[out, grad]))
    assert 0.3 < (o == 0).mean() < 0.5
    assert ((g == 0) == (o == 0)).all()
    np.testing.assert_allclose(g[o != 0], feed["w"][o != 0] / 0.6, rtol=1e-6)


# -- four devices -------------------------------------------------------------


def test_four_devices_drop_what_one_device_drops():
    """The data-parallel program over four virtual chips hashes GLOBAL
    positions: the same elements of the same 384 rows as one device, so no
    two shards repeat a pattern either."""
    rows, inner = 384, (16, 64)

    def run(chips):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=list(inner), dtype="float32")
            out = fluid.layers.dropout(x, P)
        prog = main if chips == 1 else fluid.CompiledProgram(
            main).with_data_parallel(
                places=[fluid.TPUPlace(i) for i in range(chips)])
        got, = fluid.Executor(fluid.TPUPlace(0)).run(
            prog, feed={"x": np.ones((rows,) + inner, "float32")},
            fetch_list=[out])
        return np.asarray(got) != 0

    one, four = run(1), run(4)
    assert (one == four).all()
    shards = four.reshape(4, -1)
    for a in range(4):
        for b in range(a + 1, 4):
            assert _sigmas((shards[a] == shards[b]).mean(), _agree(P),
                           shards[a].size) < 4.0, (a, b)


# -- shapes and the counter ---------------------------------------------------


def test_shape_inference_runs_the_op_abstractly():
    """``core/shape_inference.py`` runs the op under ``jax.eval_shape``
    with an abstract ``uint32[2]`` for the key."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[7, 12], dtype="float32")
        out = fluid.layers.dropout(x, 0.2)
    assert tuple(out.shape) == (-1, 7, 12) and out.dtype == x.dtype
    shapes = jax.eval_shape(
        lambda key: keep_hash.keep_mask(key, (3, 7, 12), 0.2),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert shapes.shape == (3, 7, 12) and shapes.dtype == jnp.bool_


def _draws():
    return mx.snapshot().get("dropout/draws.hash", {"value": 0})["value"]


def test_transformer_base_traces_44_dropouts(rng):
    """Six blocks a side, as Transformer-base: 30 residual sites, 2 at the
    embeddings, 12 inside the FFNs (attention's own dropout is the
    attention call's). Building the program infers each op's shapes once;
    the step's trace counts each once more."""
    from paddle_tpu.models import transformer as tfm

    rows, seq, vocab = 2, 8, 30
    main, startup = fluid.Program(), fluid.Program()
    before = _draws()
    with fluid.program_guard(main, startup):
        names = ["src", "trg", "lbl", "smask", "tmask"]
        src, trg = (fluid.layers.data(n, shape=[seq], dtype="int64")
                    for n in names[:2])
        lbl = fluid.layers.data("lbl", shape=[seq, 1], dtype="int64")
        smask, tmask = (fluid.layers.data(n, shape=[seq], dtype="float32")
                        for n in names[3:])
        _, loss = tfm.transformer(
            src, trg, lbl, smask, tmask, vocab, vocab, max_length=seq,
            n_layer=6, n_head=2, d_model=8, d_inner=16, dropout_rate=0.1)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    built = _draws()
    assert built - before == 44
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    ids = lambda *shape: rng.randint(1, vocab, shape).astype("int64")  # noqa: E731
    feed = {"src": ids(rows, seq), "trg": ids(rows, seq),
            "lbl": ids(rows, seq, 1),
            "smask": np.ones((rows, seq), "float32"),
            "tmask": np.ones((rows, seq), "float32")}
    for _ in range(2):          # the second run is the same executable
        exe.run(main, feed=feed, fetch_list=[loss])
    assert _draws() - built == 44


# -- the diag that tells a draw in an executable's text -----------------------

_HLO = """HloModule jit_step

FileNames
1 "/x/paddle_tpu/executor.py"
2 "/x/paddle_tpu/ops/optimizer_ops.py"

FileLocations
1 {file_name_id=1 function_name_id=1 line=9 end_line=9 column=1 end_column=2}
2 {file_name_id=2 function_name_id=2 line=70 end_line=70 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=0}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=1 parent_frame_id=2}

%fused_computation.1 (p: u32[8]) -> pred[8] {
  %a = u32[] constant(MUL_A)
  %b = u32[] constant(MUL_B)
  ROOT %k = pred[8] compare(%p, %p), direction=GE
}

%fused_computation.2 (p: f32[8,8]) -> f32[8,8] {
  %m = pred[8] fusion(%p), kind=kLoop, calls=%fused_computation.1
  %c = f32[8,8] convolution(%p, %p), dim_labels=bf_io->bf
  ROOT %d = f32[8,8] divide(%c, %c), metadata={op_name="adam/div" stack_frame_id=3}
}

%fused_computation.3 (p: f32[8,8]) -> f32[8,8] {
  ROOT %d = f32[8,8] divide(%p, %p), metadata={op_name="softmax/div" stack_frame_id=1}
}

ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %divide_subtract_fusion.7 = f32[8,8] fusion(%p), kind=kOutput, calls=%fused_computation.2
  ROOT %fusion.9 = f32[8,8] fusion(%p), kind=kLoop, calls=%fused_computation.3
}
""".replace("MUL_A", str(keep_hash.MUL_A)).replace(
    "MUL_B", str(keep_hash.MUL_B))


def test_the_diag_tells_what_a_fusion_holds_nested_fusions_included():
    """``benchmarks/diag_train_split.py``: the mixer's multipliers in a
    NESTED fusion count for the fusion the trace shows; a ``divide`` is
    Adam's by its stack frame's file, not by its name."""
    from benchmarks import diag_train_split as dts

    assert dts.frames_of(_HLO, "optimizer_ops.py") == {2, 3}
    holds = dts.fusion_contents(_HLO)
    assert holds["divide_subtract_fusion.7"] == {"hash", "product", "adam"}
    assert holds["fusion.9"] == set() and holds["m"] == {"hash"}
    assert not dts.drawing_fusions(_HLO)

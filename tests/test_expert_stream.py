"""The fused expert-stream kernel (``ops/pallas_kernels/expert_stream.py``)
through the Pallas interpreter at toy widths on the CPU: against
``jax.lax.ragged_dot`` x 3 (what it replaces), against the plain float32
statement, and inside ``moe_ops.expert_layer`` against the dense loop over
experts of ``tests/test_smallthinker.py``; then the rule that chooses
between the two forms (``moe_ops.matmul_form``) and the counter that says
which a served dispatch took (``serving/expert_matmul_dispatches.*``).

The interpreter has no tiling: it proves the lists, the index maps, the
masks and the accumulation over blocks, not what the chip's compiler takes
(``tests/test_chip_compile.py`` compiles the served geometries) nor a time.
In float32 the kernel and ``ragged_dot`` differ in the order of their sums
only.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas_kernels import expert_stream as es

from test_smallthinker import _dense_experts, toy_model

D, F = 64, 96


def _weights(rng, e, d=D, f=F, dtype="float32"):
    wg, wu = (jnp.asarray((rng.randn(e, d, f) / np.sqrt(d)).astype(dtype))
              for _ in range(2))
    return wg, wu, jnp.asarray((rng.randn(e, f, d) / np.sqrt(f)).astype(dtype))


_ragged3 = moe_ops._ragged_ffn   # what the kernel replaces


# rows a pass, the groups' sizes; the tile is 32 rows at these sizes
GROUPS = {
    "every expert touched": (40, [3, 9, 20, 8]),
    "one expert touched": (40, [0, 0, 17, 0]),
    "an empty group first": (40, [0, 5, 30, 5]),
    "an empty group last": (40, [12, 20, 8, 0]),
    "an empty group between": (40, [10, 0, 0, 30]),
    "a group straddles a row tile": (70, [30, 5, 33, 2]),
    "rows past the last group": (40, [3, 0, 9, 2]),
    "no expert touched": (40, [0, 0, 0, 0]),
    "rows not a whole tile": (37, [30, 0, 4, 3]),
}


@pytest.mark.parametrize("activation", [jax.nn.relu, jax.nn.silu],
                         ids=["relu", "silu"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_kernel_equals_ragged_dot_and_the_plain_statement(rng, case,
                                                          activation):
    m, sizes = GROUPS[case]
    xs = jnp.asarray(rng.randn(m, D).astype("float32"))
    wg, wu, wd = _weights(rng, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(es.expert_stream_ffn(xs, wg, wu, wd, sizes, activation,
                                          interpret=True))
    live = (np.arange(m) < int(sizes.sum()))[:, None]
    want = np.asarray(_ragged3(xs, wg, wu, wd, sizes, activation))
    np.testing.assert_allclose(np.where(live, got, 0),
                               np.where(live, want, 0), atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        got, np.asarray(es.expert_ffn_reference(xs, wg, wu, wd, sizes,
                                                activation)),
        atol=2e-5, rtol=0)
    # what ragged_dot leaves unspecified this kernel leaves 0
    assert not np.any(np.where(live, 0, got))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matrices_read_in_row_blocks(rng, monkeypatch, dtype):
    """A matrix over ``_BLOCK_BYTES`` is read in row blocks (Kimi-K2's on
    the chip): two blocks of ``Wg``/``Wu`` accumulate gate and up, two of
    ``Wd`` the result, a group over three row tiles among them. In bfloat16
    the one rounding between the products is the kernel's own: it lies
    closer to the plain float32 statement than ``ragged_dot`` x 3 does."""
    d, f, m, sizes = 256, 512, 192, [3, 0, 150, 2, 0, 0, 9, 0]
    monkeypatch.setattr(es, "_BLOCK_BYTES",
                        128 * 512 * jnp.dtype(dtype).itemsize)
    plan = es.expert_stream_plan(m, 8, d, f, dtype)
    assert (plan["nkd"], plan["nkf"], plan["tile"]) == (2, 2, 64)
    xs = jnp.asarray(rng.randn(m, d)).astype(dtype)
    wg, wu, wd = (w.astype(dtype) for w in _weights(rng, 8, d, f))
    sizes = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(es.expert_stream_ffn(
        xs, wg, wu, wd, sizes, jax.nn.silu, interpret=True), np.float32)
    want = np.asarray(es.expert_ffn_reference(xs, wg, wu, wd, sizes,
                                              jax.nn.silu))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
        return
    live = (np.arange(m) < 164)[:, None]
    thrice = np.where(live, np.asarray(
        _ragged3(xs, wg, wu, wd, sizes, jax.nn.silu), np.float32), 0)
    assert np.abs(got - want).max() < 0.02 * np.abs(want).max()
    assert np.abs(got - want).mean() <= np.abs(thrice - want).mean()


@pytest.fixture
def stream_here(monkeypatch):
    """The rule takes the stream kernel here, and the kernel runs in the
    interpreter: the two choices ``moe_ops`` makes by asking the backend,
    made in the test."""
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    for name in ("expert_stream_ffn", "expert_stream_gate"):
        monkeypatch.setattr(es, name, functools.partial(
            getattr(es, name), interpret=True))


def _layer_case(rng):
    n, d, f, e, k = 11, 16, 8, 6, 3
    u = rng.randn(n, d).astype("float32")
    wg, wu = (rng.randn(e, d, f).astype("float32") for _ in range(2))
    wd = rng.randn(e, f, d).astype("float32")
    idx, w = moe_ops.route_topk(jnp.asarray(u), jnp.asarray(
        rng.randn(d, e).astype("float32")), k)
    forced = np.stack([np.full(n, 2), rng.choice([0, 1], n),
                       rng.choice([3, 4], n)], 1).astype("int32")
    return u, wg, wu, wd, np.asarray(idx), forced, w


@pytest.mark.parametrize("routing", ["routed", "forced"])
def test_expert_layer_over_the_kernel_equals_the_dense_loop(
        rng, stream_here, routing):
    """The all-held branch: routing as the router gives it, and expert 2
    given EVERY row and expert 5 none."""
    u, wg, wu, wd, idx, forced, w = _layer_case(rng)
    ids = idx if routing == "routed" else forced
    y, stats = moe_ops.expert_layer(
        jnp.asarray(u), jnp.asarray(ids), w, jnp.asarray(wg),
        jnp.asarray(wu), jnp.asarray(wd))
    np.testing.assert_allclose(
        np.asarray(y), _dense_experts(u, ids, w, wg, wu, wd),
        atol=2e-4, rtol=1e-5)
    sizes = np.bincount(ids.reshape(-1), minlength=6)
    assert int(stats["experts_touched"]) == (sizes > 0).sum()


@pytest.mark.parametrize("activation", [jax.nn.relu, jax.nn.silu],
                         ids=["relu", "silu"])
def test_a_share_over_the_kernel_runs_two_passes(rng, stream_here,
                                                 monkeypatch, activation):
    """``_share`` with a pass of 4 rows and 7 held pairs: two passes, each
    its own slice of every group; rows marked unused cost nothing. Equal to
    the same share over ``ragged_dot``."""
    n, d, f, e, k = 9, 16, 8, 8, 3
    u = jnp.asarray(rng.randn(n, d).astype("float32"))
    wg, wu = (jnp.asarray(rng.randn(e, d, f).astype("float32"))
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d).astype("float32"))
    ids = np.stack([rng.choice([1, 3, 5, 7], n), np.full(n, 2),
                    rng.choice([0, 4, 6], n)], 1).astype("int32")
    valid = jnp.asarray(np.arange(n) < 7)
    w = jnp.asarray(rng.rand(n, k).astype("float32"))
    held = [2, 5]
    monkeypatch.setattr(moe_ops, "_share_rows", lambda *a: 4)
    passes = []
    real = es.expert_stream_ffn
    monkeypatch.setattr(es, "expert_stream_ffn", lambda xs, *a, **kw: (
        passes.append(xs.shape[0]), real(xs, *a, **kw))[1])
    args = (u, jnp.asarray(ids), w, wg[jnp.asarray(held)],
            wu[jnp.asarray(held)], wd[jnp.asarray(held)])
    kw = dict(n_expert=e, held=held, row_valid=valid, activation=activation)
    got, stats = moe_ops.expert_layer(*args, **kw)
    assert passes == [4] and int(stats["max_expert_rows"]) == 7
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: False)
    want, _ = moe_ops.expert_layer(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-5)
    assert not np.any(np.asarray(got)[7:])
    assert np.any(np.asarray(got)[:7])


def test_the_rule_sends_512_rows_to_the_kernel_and_513_to_ragged_dot(
        rng, stream_here, monkeypatch):
    """The choice is a function of the pass's static rows an expert and the
    backend: 512 rows over 16 experts on a TPU (32 an expert) take the
    kernel, 513 the compiler's grouped matmul, and no row count takes the
    kernel elsewhere."""
    taken = []
    real = es.expert_stream_ffn
    monkeypatch.setattr(es, "expert_stream_ffn", lambda xs, *a, **kw: (
        taken.append(xs.shape[0]), real(xs, *a, **kw))[1])
    wg, wu, wd = _weights(rng, 16, 16, 8)
    for m in (512, 513):
        xs = jnp.asarray(rng.randn(m, 16).astype("float32"))
        sizes = jnp.asarray([m - 9, 0, 4, 3] + [0] * 12, jnp.int32)
        got = moe_ops._grouped_ffn(xs, wg, wu, wd, sizes, jax.nn.relu)
        live = (np.arange(m) < m - 2)[:, None]
        np.testing.assert_allclose(
            np.where(live, np.asarray(got), 0), np.where(live, np.asarray(
                _ragged3(xs, wg, wu, wd, sizes, jax.nn.relu)), 0),
            atol=2e-5, rtol=0)
    assert taken == [512]
    assert [moe_ops.matmul_form(m, 16) for m in (1, 512, 513, 81920)] == [
        "stream", "stream", "grouped", "grouped"]
    # the served cells' passes: every decode pass under the bound, every
    # prefill pass over it (pairs, experts held, experts)
    assert [moe_ops.pass_rows(*g) for g in (
        (16 * 6, 64, 64), (64 * 8, 128, 512), (32 * 8, 12, 384),
        (16 * 10, 128, 256))] == [96, 256, 256, 160]
    assert not any(moe_ops._stream_bound(moe_ops.pass_rows(*g), g[1])
                   for g in ((1024 * 6, 64, 64), (2048 * 8, 128, 512),
                             (2048 * 8, 12, 384), (4096 * 10, 128, 256)))
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: False)
    assert moe_ops.matmul_form(96, 64) == "grouped"


def _forms():
    snap = mx.snapshot()
    return np.asarray([
        snap["serving/expert_matmul_dispatches." + f]["value"]
        for f in ("stream", "grouped")])


def test_dispatch_counters_say_which_form_each_executable_took(
        rng, stream_here, monkeypatch):
    """Over a short served run of the toy sparse decoder (3 slots x top-3:
    9 rows a decode pass; prompts of 24 to 96 rows a prefill pass, the
    bound put between them): every decode dispatch counts into ``.stream``,
    every prefill into ``.grouped``, and a model with no expert layer into
    neither."""
    monkeypatch.setattr(moe_ops, "STREAM_ROWS_AN_EXPERT", 2)   # x 8 experts
    snap0 = mx.snapshot()
    base = {k: snap0[k]["value"] for k in (
        "serving/decode_dispatches", "serving/prefills")}
    f0 = _forms()
    cfg = serving.ServingConfig(slots=3, page_size=4, max_seq=64,
                                prompt_buckets=(8, 16, 32))
    with serving.ServingEngine(toy_model(), cfg) as eng:
        reqs = [eng.submit(list(rng.randint(0, 96, n)), m)
                for n, m in ((5, 6), (19, 3), (9, 4))]
        eng.run()
    assert [len(r.tokens_out) for r in reqs] == [6, 3, 4]
    snap = mx.snapshot()
    decodes = snap["serving/decode_dispatches"]["value"] \
        - base["serving/decode_dispatches"]
    prefills = snap["serving/prefills"]["value"] - base["serving/prefills"]
    assert prefills == 3 and decodes >= 5
    np.testing.assert_array_equal(_forms() - f0, [decodes, prefills])
    assert serving.engine._expert_matmul_form(
        type("Dense", (), {})(), 3) is None

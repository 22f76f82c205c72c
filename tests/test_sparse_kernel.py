"""Pallas row-wise sparse-update kernel parity (interpret mode on CPU).

The kernel (ops/pallas_kernels/sparse_adam.py) replaces the three XLA
scatter fusions of the SelectedRows Adam path (benchmarks/SPARSE_PROFILE.md
§1) with one batched row-DMA pass. Contract: bit-for-bit the same update
semantics as the scatter formulation — duplicate ids merged by
``core/sparse.merge_rows`` upstream, merge-padding ids (== V) dropped like
an OOB scatter, ``padding_idx`` rows carried through the normal lazy-Adam
moment decay.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.sparse import merge_rows
from paddle_tpu.flags import set_flag
from paddle_tpu.ops.pallas_kernels import sparse_adam_rows, sparse_sgd_rows


@pytest.fixture(autouse=True)
def _restore_flag():
    yield
    set_flag("sparse_update_kernel", "auto")


def _merged(rng, vocab, dim, n):
    ids = rng.randint(0, vocab, (n,)).astype(np.int32)
    ids[: n // 4] = ids[n // 4 : n // 2]  # duplicates exercise merge_rows
    rows = rng.randn(n, dim).astype(np.float32)
    return merge_rows(jnp.asarray(ids), jnp.asarray(rows), vocab)


def test_kernel_adam_matches_scatter(rng):
    vocab, dim = 500, 10
    uniq, merged = _merged(rng, vocab, dim, 64)
    p = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
    m = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.1)
    v = jnp.asarray(np.abs(rng.randn(vocab, dim)).astype(np.float32) * 0.1)
    b1, b2, eps, lr_t = 0.9, 0.999, 1e-8, 0.01

    m_rows = b1 * m[uniq] + (1 - b1) * merged
    v_rows = b2 * v[uniq] + (1 - b2) * jnp.square(merged)
    ref_p = p.at[uniq].add(-(lr_t * m_rows / (jnp.sqrt(v_rows) + eps)))
    ref_m = m.at[uniq].add(m_rows - m[uniq])
    ref_v = v.at[uniq].add(v_rows - v[uniq])

    k_p, k_m, k_v = sparse_adam_rows(p, m, v, uniq, merged, lr_t,
                                     b1, b2, eps, interpret=True)
    np.testing.assert_allclose(ref_p, k_p, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ref_m, k_m, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ref_v, k_v, rtol=1e-6, atol=1e-6)
    # untouched rows must be bit-identical (aliased, never copied)
    touched = np.zeros(vocab, bool)
    touched[np.asarray(uniq)[np.asarray(uniq) < vocab]] = True
    np.testing.assert_array_equal(np.asarray(p)[~touched],
                                  np.asarray(k_p)[~touched])


def test_kernel_sgd_matches_scatter(rng):
    vocab, dim = 300, 7  # dim deliberately not lane-aligned
    uniq, merged = _merged(rng, vocab, dim, 40)
    p = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
    ref = p.at[uniq].add(-0.3 * merged)
    out = sparse_sgd_rows(p, uniq, merged, 0.3, interpret=True)
    np.testing.assert_allclose(ref, out, rtol=1e-6, atol=1e-6)


def test_kernel_drops_merge_padding(rng):
    """All-padding tail (few distinct ids in a big batch): rows past the
    distinct count carry id == V and must leave the table untouched."""
    vocab, dim = 100, 10
    ids = np.full((32,), 7, np.int32)  # ONE distinct id, 31 pad slots
    rows = rng.randn(32, dim).astype(np.float32)
    uniq, merged = merge_rows(jnp.asarray(ids), jnp.asarray(rows), vocab)
    p = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
    out = sparse_sgd_rows(p, uniq, merged, 1.0, interpret=True)
    expect = np.asarray(p).copy()
    expect[7] -= rows.sum(0)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def _build(vocab, dim, optimizer, padding_idx=None):
    from paddle_tpu.core import unique_name

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[4], dtype="int64")
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[vocab, dim], is_sparse=True,
                                     padding_idx=padding_idx)
        flat = fluid.layers.reshape(emb, [-1, 4 * dim])
        logits = fluid.layers.fc(flat, size=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        optimizer().minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_end_to_end_kernel_vs_scatter(rng, opt):
    """FLAGS_sparse_update_kernel=interpret drives the whole training step
    through the kernel; losses and every persistable (params + moments)
    must track the scatter path. Includes a padding_idx row in the batch
    (zero grad rows still get lazy moment decay — both paths agree)."""
    vocab, dim = 200, 10
    make = {
        "adam": lambda: fluid.optimizer.Adam(learning_rate=0.05),
        "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.5),
    }[opt]
    ids_np = rng.randint(0, vocab, (24, 4)).astype("int64")
    ids_np[:6] = ids_np[6:12]   # duplicates
    ids_np[0, 0] = 3            # the padding_idx row
    feed = {"ids": ids_np, "label": (ids_np[:, :1] % 2).astype("int64")}
    results = {}
    for mode in ("off", "interpret"):
        set_flag("sparse_update_kernel", mode)
        main, startup, loss = _build(vocab, dim, make, padding_idx=3)
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                      for _ in range(4)]
            params = {
                n: np.asarray(scope.find_var(n))
                for n in sorted(s.name for s in main.list_vars()
                                if s.persistable)
                if scope.find_var(n) is not None
                and "learning_rate" not in n
            }
        results[mode] = (losses, params)
    l_ref, p_ref = results["off"]
    l_k, p_k = results["interpret"]
    np.testing.assert_allclose(l_ref, l_k, rtol=1e-4)
    assert set(p_ref) == set(p_k)
    # Adam's step is lr * m / (sqrt(v) + eps): where a merged gradient row
    # nearly cancels, the two paths' summation orders move m / sqrt(v) in
    # its fourth digit, i.e. a parameter by lr * 1e-4 = 5e-6 at Adam's
    # lr = 0.05 (seen: one element in 2000 off by 1.04e-6). SGD is linear
    # in the gradient and sits far inside the same bound.
    for n in p_ref:
        np.testing.assert_allclose(p_ref[n], p_k[n], rtol=1e-4, atol=5e-6,
                                   err_msg=n)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_masked_negative_ids_never_touch_row0(rng, mode):
    """ids < 0 are the masked-feature convention (lookup output zeroed);
    the grad path maps them to the merge invalid index (== V) so the
    row-wise update DROPS them — row 0 must stay bit-identical, not decay
    its Adam moments every step."""
    set_flag("sparse_update_kernel", mode)
    vocab, dim = 50, 10
    main, startup, loss = _build(
        vocab, dim, lambda: fluid.optimizer.Adam(learning_rate=0.1))
    ids_np = rng.randint(1, vocab, (16, 4)).astype("int64")
    ids_np[:, 0] = -1  # a masked column every step
    feed = {"ids": ids_np, "label": (ids_np[:, 1:2] % 2).astype("int64")}
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        table0 = {n: np.asarray(scope.find_var(n))[0].copy()
                  for n in scope.vars
                  if getattr(scope.find_var(n), "shape", None) == (vocab, dim)}
        assert table0
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        for n, before in table0.items():
            np.testing.assert_array_equal(
                before, np.asarray(scope.find_var(n))[0], err_msg=n)


def test_selftest_entry():
    """The CI smoke (`python -m paddle_tpu.ops.pallas_kernels.sparse_adam
    --selftest`, ROADMAP fast smokes) must stay green."""
    from paddle_tpu.ops.pallas_kernels import sparse_adam

    assert sparse_adam._selftest() == 0

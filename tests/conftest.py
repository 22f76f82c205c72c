"""Test config: force an 8-device virtual CPU mesh (SURVEY.md §4 implication c).

Tests never require real TPU hardware; sharding/collective tests use the
virtual devices, numeric tests run on CPU. The environment is set here,
before anything imports jax, and children inherit it.
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The run gets a cache directory of its own, placed the way any caller
# places it, so that the tuned/calibration tables that live there
# (tune.table_path, numerics.table_path) do not leak between the checkout's
# cache and the tests, in either direction. The executable cache itself is
# off: thousands of sub-second CPU compiles would each pay for a cache key
# and never be written (measured: a few percent of a run that has a time
# limit).
_cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_test_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'`; slow marks the opt-out extras
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + name generator."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.framework import switch_main_program, switch_startup_program
    from paddle_tpu.core.scope import Scope, scope_guard

    prev_main = switch_main_program(fluid.Program())
    prev_startup = switch_startup_program(fluid.Program())
    with unique_name.guard():
        with scope_guard(Scope()):
            yield
    switch_main_program(prev_main)
    switch_startup_program(prev_startup)


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def prefill_told_its_length(monkeypatch):
    """What a sparse-attention prefill holds when it is told its prompt's
    length (``attention_ops.dsa_rows_causal_attention``'s test and
    ``dsa_causal_attention``'s): ``check(run, arm, case, want_mask, length,
    bq)`` with ``run`` the call over ``case`` (q, k, v first) at scale 0.25
    and mask blocks of ``bq`` rows, taking ``length=``; ``arm()`` what
    stands the kernels' interpreters in, the attention's through
    ``dsa_prefill.dsa_prefill_attention``; ``want_mask`` [S, S] bool the
    rows each row reads, by hand. The blocked form, the kernel interpreted
    at query blocks of 256 and the softmax by hand under ``want_mask`` agree
    on every row under the length; the rows from the first query block past
    it on are exactly zero and the rest finite; the ``int8`` mask the
    kernel is handed is, on the live rows, the one made with no length (by
    hand's), the causal triangle in the first mask block and zeros in the
    mask's blocks past the length."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import dsa_prefill

    def check(run, arm, case, want_mask, length, bq):
        q, k, v = (np.asarray(x, np.float64) for x in case[:3])
        s = q.shape[0]
        sc = np.where(want_mask[None],
                      np.einsum("qhd,khd->hqk", q, k) * 0.25, -np.inf)
        p = np.exp(sc - sc.max(axis=-1, keepdims=True))
        want = np.einsum("hqk,khd->qhd", p / p.sum(axis=-1, keepdims=True),
                         v)
        n = jnp.asarray(length, jnp.int32)

        def held(got, block):
            got = np.asarray(got)
            np.testing.assert_allclose(got[:length], want[:length],
                                       atol=2e-6, rtol=0)
            assert np.isfinite(got).all()
            assert not got[-(-length // block) * block:].any()

        held(run(length=n), bq)
        arm()
        masks, real = [], dsa_prefill.dsa_prefill_attention

        def spy(q, k, v, mask, *a, **kw):
            masks.append(np.asarray(mask))
            return real(q, k, v, mask, *a, block_q=256, block_k=128,
                        heads=2, **kw)

        monkeypatch.setattr(dsa_prefill, "dsa_prefill_attention", spy)
        held(run(length=n), 256)
        held(run(), s)
        told, untold = masks
        np.testing.assert_array_equal(untold != 0, want_mask)
        np.testing.assert_array_equal(told[:length], untold[:length])
        np.testing.assert_array_equal(told[:bq] != 0,
                                      np.tril(np.ones((bq, s), bool)))
        assert not told[-(-length // bq) * bq:].any()

    return check


@pytest.fixture
def poisoned_latent_pool():
    """Builds a latent pool for the kernel's tests: ``(q, poisoned, clean,
    page_table)`` for slots of ``lens`` rows. ``poisoned`` [2, rows, width]
    holds random rows below each slot's length in layer 1 and Inf or NaN
    in every other row of both layers; ``clean`` [rows, width] is layer 1
    with zeros where the poison is (what a reference may read); the page
    table is scrambled."""
    import jax.numpy as jnp

    def build(rng, lens, h, rank, rope, ps, pps, width=128):
        lens = np.asarray(lens, np.int32)
        slots = len(lens)
        pages = slots * pps + 3
        clean = np.zeros((pages * ps, width), np.float32)
        pt = rng.permutation(pages)[:slots * pps].reshape(slots, pps)
        live = np.zeros(pages * ps, bool)
        for s in range(slots):
            flat = pt[s].repeat(ps) * ps + np.tile(np.arange(ps), pps)
            live[flat[:lens[s]]] = True
        clean[live, :rank + rope] = rng.randn(int(live.sum()), rank + rope)
        poisoned = np.stack([np.full_like(clean, np.nan), clean])
        poisoned[1, ~live] = np.where(np.arange((~live).sum()) % 2,
                                      np.inf, np.nan)[:, None]
        q = np.zeros((slots, h, width), np.float32)
        q[..., :rank + rope] = rng.randn(slots, h, rank + rope)
        return (jnp.asarray(q), jnp.asarray(poisoned), jnp.asarray(clean),
                jnp.asarray(pt.astype(np.int32)))

    return build


@pytest.fixture
def attention_spy(monkeypatch):
    """What the attention of every decode step is given, for engines built
    inside the test: wraps the caches' ``decode_attention`` (which sees
    each slot's length as the engine keeps it and the ``active`` mask) and
    the two functions that consume a length (the gather path's
    ``decode_attention``, the kernel's entry). Call the fixture's value
    after driving an engine: a list of ``(active [B], kept [B], rows [B, 1])``
    a call, ``kept`` the engine's lengths and ``rows`` the rows each slot
    attends over, CHECKED: 0 rows for every slot that is not active, at
    least its own token for every one that is, and some inactive slot did
    keep the length of a request that left (or the case shows nothing)."""
    import jax

    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving import kv_cache

    seen = []

    def note(tag, *arrays):
        jax.debug.callback(
            lambda *a: seen.append((tag,) + tuple(np.asarray(x) for x in a)),
            *arrays, ordered=True)

    def spy_method(cls, name):
        real = getattr(cls, name)

        def method(self, state, layer, q, ctx_len, active, **kw):
            note("given", active, ctx_len)
            return real(self, state, layer, q, ctx_len, active, **kw)

        monkeypatch.setattr(cls, name, method)

    def spy_consumer(mod, name, rows_of):
        real = getattr(mod, name)

        def fn(*args, **kw):
            note("rows", rows_of(*args))
            return real(*args, **kw)

        monkeypatch.setattr(mod, name, fn)

    for cls in (kv_cache.PagedKVCache, kv_cache.ContiguousKVCache):
        spy_method(cls, "decode_attention")
    spy_consumer(attention_ops, "decode_attention",
                 lambda q, k, v, n: n)
    spy_consumer(pa, "paged_decode_attention",
                 lambda q, k, v, pt, n: n)

    def calls():
        jax.effects_barrier()
        assert [s[0] for s in seen] == ["given", "rows"] * (len(seen) // 2)
        out = [(a, kept, rows.reshape(a.shape[0], -1))
               for (_, a, kept), (_, rows) in zip(seen[::2], seen[1::2])]
        for active, kept, rows in out:
            assert (rows[~active] == 0).all(), (active, kept, rows)
            assert (rows[active] >= 1).all(), (active, kept, rows)
        assert any((kept[~active] > 0).any() for active, kept, _ in out)
        return out

    return calls

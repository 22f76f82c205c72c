"""Ring attention vs full attention parity on the 8-device CPU mesh
(forward + gradients, causal + non-causal, with dp×sp mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops.attention_ops import sdpa
from paddle_tpu.parallel.mesh import create_mesh
from paddle_tpu.parallel.ring_attention import ring_attention


@pytest.fixture
def qkv():
    r = np.random.RandomState(0)
    shape = (2, 2, 32, 8)  # [B, H, S, D], S divisible by sp=4
    return tuple(jnp.asarray(r.randn(*shape).astype("float32")) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(qkv, causal):
    q, k, v = qkv
    mesh = create_mesh({"sp": 4})
    scale = q.shape[-1] ** -0.5

    want = sdpa(q, k, v, causal=causal, sm_scale=scale)

    @jax.jit
    def run(q, k, v):
        return ring_attention(q, k, v, mesh, causal=causal, sm_scale=scale)

    got = run(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_ring_attention_grads_match(qkv):
    q, k, v = qkv
    mesh = create_mesh({"sp": 4})
    scale = q.shape[-1] ** -0.5

    def loss_full(q, k, v):
        return jnp.sum(sdpa(q, k, v, causal=True, sm_scale=scale) ** 2)

    @jax.jit
    def loss_ring_grads(q, k, v):
        def f(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                          sm_scale=scale) ** 2)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = loss_ring_grads(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   atol=5e-4, rtol=1e-3)


def test_ring_attention_dp_sp_mesh(qkv):
    """Combined data×sequence parallel mesh."""
    q, k, v = qkv
    mesh = create_mesh({"data": 2, "sp": 4})
    scale = q.shape[-1] ** -0.5
    want = sdpa(q, k, v, causal=False, sm_scale=scale)

    sh = NamedSharding(mesh, P("data", None, "sp", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    @jax.jit
    def run(q, k, v):
        return ring_attention(q, k, v, mesh, causal=False, sm_scale=scale)

    got = run(qs, ks, vs)
    assert len(got.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_ring_attention_op_fallback_without_sp(qkv):
    """The graph op degrades to fused attention when no sp axis exists."""
    import paddle_tpu as fluid
    from paddle_tpu.testing import run_op

    q, k, v = (np.asarray(x) for x in qkv)
    scale = q.shape[-1] ** -0.5
    got = run_op("ring_attention", {"Q": q, "K": k, "V": v}, ["Out"],
                 attrs={"causal": True, "sm_scale": scale})["Out"]
    want = sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
                sm_scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_ring_flash_block_gate(monkeypatch):
    """Flash blocks only on TPU, 128-aligned shards, above the crossover."""
    import importlib

    ra = importlib.import_module("paddle_tpu.parallel.ring_attention")

    q32 = jnp.zeros((1, 2, 4096, 64), jnp.float32)
    # off-TPU: never
    assert not ra._use_flash_blocks(q32, 4096)
    monkeypatch.setattr("paddle_tpu.ops.attention_ops._on_tpu", lambda: True)
    if ra._block_sizes_for(4096):
        from paddle_tpu.ops.attention_ops import _flash_fn

        if _flash_fn()[0] is not None:
            assert ra._use_flash_blocks(q32, 4096)
            assert not ra._use_flash_blocks(q32, 1024)   # below crossover
            assert not ra._use_flash_blocks(q32, 2100)   # not 128-aligned
            qi = jnp.zeros((1, 2, 4096, 64), jnp.int32)
            assert not ra._use_flash_blocks(qi, 4096)    # wrong dtype


def test_ring_blockwise_residuals_are_linear_in_s():
    """The custom VJP must not save per-step score blocks: residuals are
    (q, k, v, out, lse) only — O(S_local), not O(S_local^2)."""
    from paddle_tpu.parallel.ring_attention import _ring_blockwise_fwd

    b, h, s, d = 1, 2, 64, 16
    q = jnp.ones((b, h, s, d), jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("sp",))

    def local(q, k, v):
        return _ring_blockwise_fwd("sp", True, 0.25, False, q, k, v)

    out, res = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=(P(None, None, "sp", None),
                   (P(None, None, "sp", None),) * 4 + (P(None, None, "sp"),)))(q, q, q)
    assert out.shape == q.shape
    q_r, k_r, v_r, out_r, lse_r = res
    assert lse_r.shape == (b, h, s)          # O(S) softmax stats
    for r in (q_r, k_r, v_r, out_r):
        assert r.shape == q.shape            # no [*, S, S] buffer saved

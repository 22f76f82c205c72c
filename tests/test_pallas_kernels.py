"""Builder-written Pallas kernel tests (interpret mode on CPU) + fused-path
gating and the no-silent-fallback contract for flash attention."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import fused_softmax_xent


def _ref_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels.astype(jnp.int32), axis=-1)


@pytest.mark.parametrize("n,v", [(32, 1000), (64, 4096), (17, 300), (8, 128)])
def test_fused_softmax_xent_forward_parity(rng, n, v):
    logits = jnp.asarray(rng.randn(n, v).astype("float32") * 3)
    labels = jnp.asarray(rng.randint(0, v, (n, 1)).astype("int32"))
    loss = fused_softmax_xent(logits, labels, True)
    np.testing.assert_allclose(loss, _ref_loss(logits, labels), rtol=2e-5, atol=2e-5)


def test_fused_softmax_xent_grad_parity(rng):
    n, v = 24, 1536
    logits = jnp.asarray(rng.randn(n, v).astype("float32"))
    labels = jnp.asarray(rng.randint(0, v, (n, 1)).astype("int32"))
    w = jnp.asarray(rng.randn(n, 1).astype("float32"))  # non-uniform cotangent
    g1 = jax.grad(lambda x: (fused_softmax_xent(x, labels, True) * w).sum())(logits)
    g2 = jax.grad(lambda x: (_ref_loss(x, labels) * w).sum())(logits)
    np.testing.assert_allclose(g1, g2, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("n,v", [(24, 1536), (17, 300)])
def test_fused_softmax_xent_label_smoothing(rng, n, v):
    """Smoothed loss/grad must match the composed formula (incl. v-padding)."""
    eps = 0.1
    logits = jnp.asarray(rng.randn(n, v).astype("float32") * 2)
    labels = jnp.asarray(rng.randint(0, v, (n, 1)).astype("int32"))

    def ref(x):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels.astype(jnp.int32), axis=-1)
        return (1 - eps) * nll + (eps / v) * (-logp.sum(-1, keepdims=True))

    loss = fused_softmax_xent(logits, labels, True, eps)
    np.testing.assert_allclose(loss, ref(logits), rtol=2e-5, atol=2e-5)
    w = jnp.asarray(rng.randn(n, 1).astype("float32"))
    g1 = jax.grad(lambda x: (fused_softmax_xent(x, labels, True, eps) * w).sum())(logits)
    g2 = jax.grad(lambda x: (ref(x) * w).sum())(logits)
    np.testing.assert_allclose(g1, g2, rtol=2e-4, atol=1e-5)


def test_fused_softmax_xent_bf16(rng):
    n, v = 16, 512
    logits = jnp.asarray(rng.randn(n, v).astype("float32")).astype(jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, v, (n, 1)).astype("int32"))
    loss = fused_softmax_xent(logits, labels, True)
    ref = _ref_loss(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), rtol=2e-2, atol=2e-2)
    g = jax.grad(lambda x: fused_softmax_xent(x, labels, True).sum())(logits)
    assert g.dtype == jnp.bfloat16


def test_fused_gate_is_tpu_only():
    """On CPU the op must keep the composed XLA path (interpret-mode pallas
    would crawl); the gate also rejects tiny vocabs."""
    from paddle_tpu.ops.nn_ops import fused_xent_gate

    assert jax.default_backend() == "cpu"
    assert "cpu" in fused_xent_gate((32, 32768), jnp.float32)


# -- flash-attention fallback contract ---------------------------------------


def _mk_qkv(rng, s=256, d=64):
    q = jnp.asarray(rng.randn(2, 4, s, d).astype("float32"))
    return q, q + 0.1, q + 0.2


@pytest.fixture
def _flash_any_seq():
    """Lower the profitability threshold so small test shapes take flash."""
    from paddle_tpu.flags import get_flag, set_flag

    old = get_flag("flash_attention_min_seq")
    set_flag("flash_attention_min_seq", 128)
    yield
    set_flag("flash_attention_min_seq", old)


def test_flash_failure_warns_not_silent(rng, monkeypatch, _flash_any_seq):
    """A failing Pallas flash call must emit a RuntimeWarning, not vanish."""
    q, k, v = _mk_qkv(rng)

    def boom(*a, **kw):
        raise ValueError("synthetic pallas failure")

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_flash_fn", lambda: (boom, None))
    with pytest.warns(RuntimeWarning, match="falling back"):
        out = attention_ops.sdpa(q, k, v)
    assert out.shape == q.shape


def test_flash_failure_strict_mode_raises(rng, monkeypatch, _flash_any_seq):
    from paddle_tpu.flags import set_flag

    q, k, v = _mk_qkv(rng)

    def boom(*a, **kw):
        raise ValueError("synthetic pallas failure")

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_flash_fn", lambda: (boom, None))
    set_flag("strict_fused_attention", True)
    try:
        with pytest.raises(RuntimeError, match="flash-attention failed"):
            attention_ops.sdpa(q, k, v)
    finally:
        set_flag("strict_fused_attention", False)


def test_flash_path_taken_when_gates_pass(rng, monkeypatch, _flash_any_seq):
    """When on 'TPU' with clean shapes, sdpa must call the flash kernel."""
    q, k, v = _mk_qkv(rng)
    called = {}

    def fake_flash(q, k, v, ab=None, segment_ids=None, causal=False,
                   sm_scale=1.0, block_sizes=None):
        called["yes"] = True
        called["block_sizes"] = block_sizes
        return q

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_flash_fn", lambda: (fake_flash, None))
    attention_ops.sdpa(q, k, v, causal=True)
    assert called.get("yes"), "flash path not taken despite passing gates"


def test_flash_gate_rejects_causal_rectangular(rng, monkeypatch, _flash_any_seq):
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    q = jnp.zeros((2, 4, 128, 64))
    k = jnp.zeros((2, 4, 256, 64))
    assert not attention_ops._flash_ok(q, k, causal=True)
    assert attention_ops._flash_ok(q, k, causal=False) or attention_ops._flash_fn()[0] is None


def test_flash_gate_profitability_threshold(rng, monkeypatch):
    """Below the measured crossover (S=2048 with v5e-tuned BlockSizes, r4
    sweep) the composed path must win the gate; at/above it flash must."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_flash_fn", lambda: (lambda *a, **k: None, None))
    q = jnp.zeros((2, 4, 1024, 64))
    assert not attention_ops._flash_ok(q, q, causal=False)
    q2 = jnp.zeros((2, 4, 2048, 64))
    assert attention_ops._flash_ok(q2, q2, causal=False)
    q8 = jnp.zeros((1, 4, 8192, 64))
    assert attention_ops._flash_ok(q8, q8, causal=False)


def test_tuned_block_sizes():
    """v5e tuning: 512x512 tiles when the sequence allows, largest divisor
    otherwise (blocks must divide the sequence lengths)."""
    bs = attention_ops._tuned_block_sizes(8192, 8192)
    assert bs.block_q == 512 and bs.block_k == 512
    assert bs.block_q_dkv == 512 and bs.block_k_major_dq == 512
    bs = attention_ops._tuned_block_sizes(2048, 2048)
    assert bs.block_q == 512
    bs = attention_ops._tuned_block_sizes(384, 2048)
    assert bs.block_q == 128 and bs.block_k == 512  # 384 = 3*128

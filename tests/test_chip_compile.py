"""The chip-compiler rehearsal kept as tests (on-chip-measurement guide §2).

The TPU's compiler is installed here and compiles for a chip that is
described, not attached. Every Pallas kernel of the two main paths is
compiled with ``interpret=False`` at the shapes ``chip_smoke.py`` runs on
the chip, and must either come back holding a ``tpu_custom_call`` or be
excluded by its static gate — the interpreter the other kernel tests use
has no tiling and passes shapes the chip refuses (a ``[1, 10]`` row DMA, a
``[R, 12, 64]`` page DMA). A compile that passes is not a chip run: nothing
here says a kernel computes the right thing or how fast.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import flash_attention as fa
from paddle_tpu.ops.pallas_kernels import paged_attention as pa
from paddle_tpu.ops.pallas_kernels import sparse_adam as sa
from paddle_tpu.ops.pallas_kernels import fused_softmax_xent


@pytest.fixture(scope="module")
def chip():
    """A described v5e chip's sharding. The persistent compile cache is off
    around the module: an executable compiled for a described chip is
    written to it but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compiled_text(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_attention_fwd_bwd(chip, seq):
    """bf16 causal at the tiles the tune table hands out for this length."""
    bs = attention_ops._tuned_block_sizes(seq, seq)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, sm_scale=0.125,
                               block_sizes=bs)
        return o.astype(jnp.float32).sum()

    shape = ((1, 8, seq, 64), jnp.bfloat16)
    text = compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)),
                         shape, shape, shape)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


def test_softmax_xent_fwd_bwd(chip):
    def loss(logits, labels):
        return fused_softmax_xent(logits, labels).sum()

    text = compiled_text(chip, jax.grad(loss),
                         ((16384, 30000), jnp.bfloat16),
                         ((16384, 1), jnp.int32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("width", [1, 10, 128])
@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_sparse_rows(chip, opt, width):
    """DeepFM's tables (width 10 and 1) and a lane-wide one, V=1e6, one
    batch of 1024 x 26 ids."""
    vocab, n_ids = 1_000_000, 26624
    why = sa.sparse_rows_gate(vocab, width, jnp.float32)
    if width % 128:
        assert why is not None and "128 lanes" in why
        assert not sa.sparse_rows_supported(vocab, width, jnp.float32)
        # ...while the interpreter, which has no tiling, still takes it
        assert sa.sparse_rows_supported(vocab, width, jnp.float32,
                                        interpret=True)
        return
    assert why is None
    table = ((vocab, width), jnp.float32)
    ids, rows = ((n_ids,), jnp.int32), ((n_ids, width), jnp.float32)
    if opt == "adam":
        text = compiled_text(
            chip, lambda p, m, v, i, r: sa.sparse_adam_rows(p, m, v, i, r,
                                                            0.01),
            table, table, table, ids, rows)
    else:
        text = compiled_text(
            chip, lambda p, i, r: sa.sparse_sgd_rows(p, i, r, 0.5),
            table, ids, rows)
    assert "tpu_custom_call" in text


PAGED_SHAPES = {
    # slots, heads, d_head, page_size, pages/slot, dtype
    "gpt2_small_f32": (8, 12, 64, 16, 64, jnp.float32),
    "gpt2_small_bf16": (8, 12, 64, 16, 64, jnp.bfloat16),
    "serve_bench": (8, 4, 32, 16, 16, jnp.float32),
    "speculative_window": (8 * 5, 12, 64, 16, 64, jnp.float32),
    "tiny_test_model": (4, 2, 16, 8, 8, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_decode_attention(chip, name):
    """``block_pages`` comes from the tune table, as in the engine."""
    b, h, d, ps, pps, dtype = PAGED_SHAPES[name]
    why = pa.paged_attention_gate(dtype, h, d, ps)
    if name == "tiny_test_model":
        # H*D = 32 does not fill a 128-lane row: `auto` keeps the gather path
        assert why is not None and "multiple of 128" in why
        assert pa.paged_attention_gate(dtype, h, d, ps, interpret=True) is None
        return
    assert why is None
    rows = b * pps * ps
    text = compiled_text(
        chip,
        functools.partial(pa.paged_decode_attention, page_size=ps,
                          sm_scale=0.125),
        ((b, h, d), dtype), ((rows, h, d), dtype), ((rows, h, d), dtype),
        ((b, pps), jnp.int32), ((b,), jnp.int32))
    # the kernel runs under its own name: what a profile's XLA Ops line and
    # the grid's device_ops show in place of closed_call
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%paged_attention")

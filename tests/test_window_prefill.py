"""The window layers' prefill attention as one kernel
(``ops/pallas_kernels/window_prefill.py``) against the blocked XLA form of
``attention_ops.windowed_causal_attention``, which stays the CPU's and the
reference here. The kernel runs in the interpreter, armed as a chip would
arm it (``attention_ops._on_tpu``, the gate and the kernel's ``interpret``
patched, as ``tests/test_glm5_flash.py`` arms the DSA layer's), at the three
served head geometries cut in rows and window:

(a) kernel and blocked form agree, in float32 and in bfloat16;
(b) a row reads the keys of its band and no others;
(c) rows below the window are plain causal attention;
(d) the gate's refusals, each by its rule, and the served shapes it takes;
(e) ``attn/window_prefill_calls.kernel|blocked`` count the choice.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import window_prefill as wp

# name: (query heads, KV heads, D, Dv, window, two buckets past the window,
# the interpreter's tiles where the kernel's own would be one a bucket)
GEOMETRIES = {
    # SmallThinker: 7 query heads a KV head, a band of several key tiles
    "smallthinker": (28, 4, 128, 128, 64, (128, 256), dict(block_q=32,
                                                           block_k=32)),
    # Laguna's window layers: 9 a KV head, the served window and its tiles
    "laguna": (18, 2, 128, 128, 512, (1024, 2048), {}),
    # Motif: 5 a KV head, keys wider than values, a window of ONE tile
    "motif3": (10, 2, 192, 128, 128, (256, 512), {}),
    # a window NARROWER than a key tile, and no whole number of them
    "narrow": (4, 2, 64, 64, 48, (128, 192), dict(block_q=32, block_k=64)),
}
CASES = [(g, s) for g, geo in GEOMETRIES.items() for s in geo[5]]
TOL = {"float32": 5e-6, "bfloat16": 2e-2}


def _arm_the_kernel(monkeypatch, **tiles):
    """``windowed_causal_attention`` as on a chip whose gate takes the
    shapes, the kernel's interpreter standing in at ``tiles``."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(wp, "window_prefill_gate", functools.partial(
        wp.window_prefill_gate, interpret=True))
    monkeypatch.setattr(wp, "window_prefill_attention", functools.partial(
        wp.window_prefill_attention, interpret=True, **tiles))


def _qkv(rng, geometry, s, dtype="float32"):
    hq, hkv, d, d_v = GEOMETRIES[geometry][:4]
    return tuple(jnp.asarray(rng.randn(s, h, w), dtype)
                 for h, w in ((hq, d), (hkv, d), (hkv, d_v)))


def _both_forms(monkeypatch, geometry, q, k, v):
    """(kernel, blocked) of ``windowed_causal_attention``, each counted."""
    window, tiles = GEOMETRIES[geometry][4], GEOMETRIES[geometry][6]
    scale = q.shape[-1] ** -0.5
    counts = [mx.counter("attn/window_prefill_calls." + f)
              for f in ("kernel", "blocked")]
    before = [c.value for c in counts]
    # the CPU has no bfloat16 x bfloat16 = float32 batched product: the
    # blocked form reads the same (rounded) numbers as float32
    blocked = attention_ops.windowed_causal_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), window, scale)
    _arm_the_kernel(monkeypatch, **tiles)
    kernel = attention_ops.windowed_causal_attention(q, k, v, window, scale)
    monkeypatch.undo()
    assert [c.value - b for c, b in zip(counts, before)] == [1, 1]
    return np.asarray(kernel, np.float32), np.asarray(blocked, np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry,s", CASES)
def test_kernel_equals_the_blocked_form(rng, monkeypatch, geometry, s, dtype):
    """Every row of every query head, by the online softmax over the band's
    tiles and by the blocked form's softmax over ``window + 512`` keys."""
    q, k, v = _qkv(rng, geometry, s, dtype)
    kernel, blocked = _both_forms(monkeypatch, geometry, q, k, v)
    assert kernel.shape == (s,) + q.shape[1:2] + v.shape[2:]
    np.testing.assert_allclose(kernel, blocked, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_row_reads_its_band_alone(rng, monkeypatch, geometry):
    """Keys and values outside a row's band replaced by noise a thousand
    times their size: the row's result does not move by a bit (a masked
    score's weight is exactly 0, and a tile that holds none of the row's
    keys leaves nothing behind)."""
    window, buckets, tiles = GEOMETRIES[geometry][4:]
    s = buckets[1]
    q, k, v = _qkv(rng, geometry, s)
    _arm_the_kernel(monkeypatch, **tiles)
    scale = q.shape[-1] ** -0.5
    want = np.asarray(
        attention_ops.windowed_causal_attention(q, k, v, window, scale))
    for row in (window - 1, window, s // 2 + 3, s - 1):
        band = (np.arange(s) <= row) & (row - np.arange(s) < window)
        noise_k, noise_v = (1e3 * rng.randn(*x.shape).astype("float32")
                            for x in (k, v))
        k2 = jnp.where(band[:, None, None], k, noise_k)
        v2 = jnp.where(band[:, None, None], v, noise_v)
        got = attention_ops.windowed_causal_attention(q, k2, v2, window,
                                                      scale)
        np.testing.assert_array_equal(np.asarray(got[row]), want[row])


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_rows_below_the_window_are_causal_attention(rng, monkeypatch,
                                                    geometry):
    """The first ``window`` rows see every key before them: the kernel's
    rows there are ``gqa_causal_attention``'s of the sequence cut there."""
    window, buckets, tiles = GEOMETRIES[geometry][4:]
    q, k, v = _qkv(rng, geometry, buckets[0])
    scale = q.shape[-1] ** -0.5
    _arm_the_kernel(monkeypatch, **tiles)
    got = attention_ops.windowed_causal_attention(q, k, v, window, scale)
    want = attention_ops.gqa_causal_attention(q[:window], k[:window],
                                              v[:window], scale)
    np.testing.assert_allclose(np.asarray(got[:window]), np.asarray(want),
                               atol=TOL["float32"], rtol=0)


@pytest.mark.parametrize("shape,rule", [
    # (query heads, KV heads, D, Dv, S, window)
    ((28, 4, 128, 128, 8192, 4096), None),        # SmallThinker's bucket
    ((72, 8, 128, 128, 4096, 512), None),         # Laguna's two
    ((72, 8, 128, 128, 8192, 512), None),
    ((80, 16, 192, 128, 2048, 128), None),        # Motif's three: 192 is
    ((80, 16, 192, 128, 4096, 128), None),        # ... whole sublane tiles
    ((80, 16, 192, 128, 8192, 128), None),
    ((28, 4, 128, 72, 8192, 4096), "sublane"),    # no whole 16-row tiles
    ((28, 4, 128, 128, 8192 + 64, 4096), "whole tiles"),
    ((28, 4, 128, 128, 8192, 64), None),          # a window under a tile
    ((30, 4, 128, 128, 8192, 4096), "whole groups"),
    ((128, 4, 256, 256, 8192, 4096), "VMEM"),     # 32 query heads a step
])
def test_the_gate_answers_by_rule(shape, rule):
    why_not = wp.window_prefill_gate(*shape)
    if rule is None:
        assert why_not is None
    else:
        assert rule in why_not
        s, hq, hkv = shape[4], shape[0], shape[1]
        sds = jax.ShapeDtypeStruct
        with pytest.raises(ValueError, match=rule):
            jax.eval_shape(
                functools.partial(wp.window_prefill_attention,
                                  window=shape[5]),
                sds((s, hq, shape[2]), jnp.bfloat16),
                sds((s, hkv, shape[2]), jnp.bfloat16),
                sds((s, hkv, shape[3]), jnp.bfloat16))


@pytest.mark.parametrize("where,form", [
    ("chip", "kernel"), ("chip_gate_refuses", "blocked"), ("cpu", "blocked")])
def test_the_choice_is_counted_once_a_traced_call(rng, monkeypatch, where,
                                                  form):
    """``attn/window_prefill_calls.<form>`` rises by one a call of a traced
    program, not once a run; at ``S <= window`` the call is the causal
    attention's and neither moves."""
    hq, hkv, d, d_v, window = 4, 2, 16, 16, 8
    if where != "cpu":
        _arm_the_kernel(monkeypatch)
    if where == "chip_gate_refuses":
        # the chip's own rules: 32 rows under a window of 8 are no tile
        monkeypatch.setattr(wp, "window_prefill_gate", functools.partial(
            wp.window_prefill_gate.func, interpret=False))
    counts = {f: mx.counter("attn/window_prefill_calls." + f)
              for f in ("kernel", "blocked")}
    before = {f: c.value for f, c in counts.items()}
    q, k, v = (jnp.asarray(rng.randn(32, h, w), jnp.float32)
               for h, w in ((hq, d), (hkv, d), (hkv, d_v)))

    @jax.jit
    def two_layers(q, k, v):
        o = attention_ops.windowed_causal_attention(q, k, v, window, 0.25)
        short = attention_ops.windowed_causal_attention(
            q[:window], k[:window], v[:window], window, 0.25)
        return o + attention_ops.windowed_causal_attention(
            o, k, v, window, 0.25), short

    for _ in range(3):
        two_layers(q, k, v)
    other = "blocked" if form == "kernel" else "kernel"
    assert counts[form].value == before[form] + 2
    assert counts[other].value == before[other]

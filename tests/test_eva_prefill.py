"""EvaByte's prefill attention as one kernel
(``ops/pallas_kernels/eva_prefill.py``) against the blocked XLA form of
``models/evabyte._prefill_attention``, which stays the CPU's, and against
the float32 statement (``grid/reference/evabyte._attention``). The kernel
runs in the interpreter, armed as a chip would arm it
(``attention_ops._on_tpu``, the gate and the kernel's ``interpret``
patched, as ``tests/test_window_prefill.py`` arms the window layers'), at
toy widths (4 heads of 16, windows of 32 in chunks of 4, so 8 summaries a
closed window) and tiles that cut a window into several:

(a) kernel, blocked form and reference agree at one, two and eight
    windows, in float32 and in bfloat16;
(b) a row reads its own window's earlier rows and the CLOSED windows'
    summaries alone;
(c) zero summaries and ``S <= window``;
(d) padding rows do not reach real rows;
(e) the gate's refusals, each by its rule, and the served shapes it takes;
(f) ``attn/eva_prefill_calls.kernel|blocked`` count the choice.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import evabyte as ref
from paddle_tpu.models import evabyte
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import eva_prefill as ep

H, D, W, C = 4, 16, 32, 4
KEPT = W // C
# the interpreter's tiles: the kernel's own would be one a window
TILES = {"8x8": dict(block_q=8, block_k=8), "16x8": dict(block_q=16,
                                                         block_k=8),
         "32x32x1": dict(heads=1), "16x16x3": dict(block_q=16, block_k=16,
                                                   heads=3)}
TOL = {"float32": 5e-6, "bfloat16": 2e-2}


def _cfg(dtype="float32"):
    return evabyte.EvaByteConfig(40, 1, H * D, H, H, 48, window=W, chunk=C,
                                 max_seq=8 * W, dtype=dtype)


def _arm_the_kernel(monkeypatch, **tiles):
    """``_prefill_attention`` as on a chip whose gate takes the shapes,
    the kernel's interpreter standing in at ``tiles``."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ep, "eva_prefill_gate", functools.partial(
        ep.eva_prefill_gate, interpret=True))
    monkeypatch.setattr(ep, "eva_prefill_attention", functools.partial(
        ep.eva_prefill_attention, interpret=True, **tiles))


def _operands(rng, s, dtype="float32", n_sum=None):
    """``(q, k, v, ks, vs)``: the summaries are NOISE, not pooled rows, so
    that a summary read in a row's place, or a row in a summary's, shows."""
    n_sum = s // C if n_sum is None else n_sum
    return tuple(jnp.asarray(rng.randn(n, H, D), dtype)
                 for n in (s, s, s, n_sum, n_sum))


def _counts():
    return [mx.counter("attn/eva_prefill_calls." + f).value
            for f in ("kernel", "blocked")]


def _both_forms(monkeypatch, cfg, args, tiles):
    """(kernel, blocked) of ``_prefill_attention``, each counted."""
    before = _counts()
    # the CPU has no bfloat16 x bfloat16 = float32 batched product: the
    # blocked form reads the same (rounded) numbers as float32
    blocked = evabyte._prefill_attention(
        cfg, *(x.astype(jnp.float32) for x in args))
    _arm_the_kernel(monkeypatch, **tiles)
    kernel = evabyte._prefill_attention(cfg, *args)
    monkeypatch.undo()
    assert [c - b for c, b in zip(_counts(), before)] == [1, 1]
    return np.asarray(kernel, np.float32), np.asarray(blocked, np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("windows", [1, 2, 8])
def test_kernel_equals_the_blocked_form_and_the_reference(
        rng, monkeypatch, windows, tiles, dtype):
    """Every row of every head, by the online softmax over the summary
    tiles and the own window's tiles, by the blocked form's softmax over
    two parts, and by the reference's mask over ``[chunks ++ positions]``
    (float32 at "highest" over the same rounded numbers)."""
    s = windows * W
    args = _operands(rng, s, dtype)
    kernel, blocked = _both_forms(monkeypatch, _cfg(dtype), args,
                                  TILES[tiles])
    assert kernel.shape == (s, H, D)
    np.testing.assert_allclose(kernel, blocked, atol=TOL[dtype], rtol=0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._attention(
            *(x.astype(jnp.float32) for x in args), W, C))
    np.testing.assert_allclose(kernel, want, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("tiles", sorted(TILES))
def test_a_row_reads_its_window_and_the_closed_windows_summaries_alone(
        rng, monkeypatch, tiles):
    """What a row may NOT read, poisoned: the exact rows of other windows
    and the summaries of its own window and of later ones by NaN (their
    tiles are never folded), its own window's later rows by noise a
    thousand times their size (they share the diagonal's tile, where a
    masked score's weight is exactly 0). The row's result does not move by
    a bit."""
    s = 4 * W
    q, k, v, ks, vs = _operands(rng, s)
    _arm_the_kernel(monkeypatch, **TILES[tiles])
    cfg = _cfg()
    want = np.asarray(evabyte._prefill_attention(cfg, q, k, v, ks, vs))
    at = np.arange(s)
    for row in (0, W - 1, W, W + 9, 2 * W + 17, s - 1):
        w = row // W
        own = (at // W == w)[:, None, None]
        seen = (own[:, 0, 0] & (at <= row))[:, None, None]
        sums = (np.arange(s // C) < KEPT * w)[:, None, None]
        k2, v2 = (jnp.where(seen, t, jnp.where(
            own, 1e3 * rng.randn(*t.shape).astype("float32"), jnp.nan))
            for t in (k, v))
        ks2, vs2 = (jnp.where(sums, t, jnp.nan) for t in (ks, vs))
        got = np.asarray(
            evabyte._prefill_attention(cfg, q, k2, v2, ks2, vs2)[row])
        np.testing.assert_array_equal(got, want[row])


@pytest.mark.parametrize("s,n_sum", [(W, 0), (W, W // C), (W // 2, 0),
                                     (W // 2, W // (2 * C))])
def test_one_window_reads_no_summary(rng, monkeypatch, s, n_sum):
    """``S <= window``, with the summaries a bucket brings or with ZERO
    rows of them (``benchmarks/control_evabyte.py`` ``no_summaries`` calls
    a window at a time that way): plain causal attention, in both forms."""
    args = _operands(rng, s, n_sum=n_sum)
    kernel, blocked = _both_forms(monkeypatch, _cfg(), args, TILES["8x8"])
    q, k, v = args[:3]
    want = attention_ops.gqa_causal_attention(q, k, v, D ** -0.5)
    np.testing.assert_allclose(kernel, np.asarray(want),
                               atol=TOL["float32"], rtol=0)
    np.testing.assert_allclose(blocked, np.asarray(want),
                               atol=TOL["float32"], rtol=0)


@pytest.mark.parametrize("length", [W + 1, 2 * W - 3, 2 * W, 3 * W + C + 1])
def test_padding_rows_do_not_reach_real_rows(rng, monkeypatch, length):
    """A bucket's rows past the prompt replaced by noise a thousand times
    a row's size, the windows past the prompt's by NaN, and by NaN the
    summaries of every chunk that holds a padding row: the prompt's rows
    are the clean bucket's to the bit (a summary of a chunk that holds
    padding belongs to a window that closes past the prompt)."""
    s = 4 * W
    q, k, v, ks, vs = _operands(rng, s)
    _arm_the_kernel(monkeypatch, **TILES["16x8"])
    cfg = _cfg()
    want = np.asarray(evabyte._prefill_attention(cfg, q, k, v, ks, vs))
    at = np.arange(s)
    real = (at < length)[:, None, None]
    last = (at // W <= (length - 1) // W)[:, None, None]
    whole = (np.arange(s // C) < length // C)[:, None, None]
    got = np.asarray(evabyte._prefill_attention(
        cfg, *(jnp.where(real, t, jnp.where(
            last, 1e3 * rng.randn(*t.shape).astype("float32"), jnp.nan))
            for t in (q, k, v)),
        *(jnp.where(whole, t, jnp.nan) for t in (ks, vs))))
    np.testing.assert_array_equal(got[:length], want[:length])


@pytest.mark.parametrize("shape,rule", [
    # (heads, D, S, window, chunk)
    ((32, 128, 4096, 2048, 16), None),          # the cell's three buckets
    ((32, 128, 8192, 2048, 16), None),
    ((32, 128, 16384, 2048, 16), None),
    ((32, 128, 2048, 2048, 16), None),          # one window
    ((32, 128, 4096 + 512, 2048, 16), "whole windows"),
    ((32, 128, 4096, 2048, 24), "whole windows"),
    ((32, 72, 4096, 2048, 16), "sublane"),
    ((32, 128, 4032, 2016, 16), "128-row tiles"),
    ((32, 128, 4096, 2048, 32), "lane tiles"),  # 64 summaries a window
    ((32, 2048, 4096, 2048, 16), "VMEM"),
])
def test_the_gate_answers_by_rule(shape, rule):
    why_not = ep.eva_prefill_gate(*shape)
    if rule is None:
        assert why_not is None
        return
    assert rule in why_not
    n_head, d, s, window, chunk = shape
    sds = jax.ShapeDtypeStruct
    with pytest.raises(ValueError, match=rule):
        jax.eval_shape(
            functools.partial(ep.eva_prefill_attention, window=window,
                              chunk=chunk),
            *[sds((s, n_head, d), jnp.bfloat16)] * 3,
            *[sds((s // chunk, n_head, d), jnp.bfloat16)] * 2)


@pytest.mark.parametrize("where,form", [
    ("chip", "kernel"), ("chip_gate_refuses", "blocked"), ("cpu", "blocked")])
def test_the_choice_is_counted_once_a_traced_call(rng, monkeypatch, where,
                                                  form):
    """``attn/eva_prefill_calls.<form>`` rises by one a call of a traced
    program (a layer), not once a run."""
    if where != "cpu":
        _arm_the_kernel(monkeypatch)
    if where == "chip_gate_refuses":
        # the chip's own rules: windows of 32 rows are no 128-row tile
        monkeypatch.setattr(ep, "eva_prefill_gate", functools.partial(
            ep.eva_prefill_gate.func, interpret=False))
    cfg = _cfg()
    before = dict(zip(("kernel", "blocked"), _counts()))
    args = _operands(rng, 2 * W)

    @jax.jit
    def two_layers(q, k, v, ks, vs):
        o = evabyte._prefill_attention(cfg, q, k, v, ks, vs)
        return evabyte._prefill_attention(cfg, o, k, v, ks, vs)

    for _ in range(3):
        two_layers(*args)
    after = dict(zip(("kernel", "blocked"), _counts()))
    other = "blocked" if form == "kernel" else "kernel"
    assert after[form] == before[form] + 2
    assert after[other] == before[other]


def test_the_served_prefill_takes_the_kernel_in_every_layer(monkeypatch):
    """``prefill_forward`` of two toy layers over a bucket of four windows,
    armed: two kernel calls under the scope the benchmark's readers tell
    the attention by, the logits the blocked form's."""
    cfg = evabyte.EvaByteConfig(40, 2, H * D, H, H, 48, window=W, chunk=C,
                                n_pred_heads=3, max_seq=4 * W,
                                dtype="float32")
    model = evabyte.EvaByteLM(cfg, params=evabyte.init_params(cfg, 3))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 40, (1, 4 * W)),
                       jnp.int32)
    lens = jnp.asarray([3 * W + 5], jnp.int32)
    want, _ = model.prefill_last(model.params, toks, lens)
    _arm_the_kernel(monkeypatch, **TILES["16x8"])
    before = _counts()
    fn = jax.jit(model.prefill_last)
    text = fn.lower(model.params, toks, lens).as_text(debug_info=True)
    got, _ = fn(model.params, toks, lens)
    assert [c - b for c, b in zip(_counts(), before)] == [2, 0]
    assert "attn/eva_prefill" in text
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)

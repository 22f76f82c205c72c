"""A share of the served expert layer (``ops/moe_ops._share``): the passes
of a prefill's size, and each way a pass gives its rows back to their
tokens, against the path that holds every expert (``expert_layer`` with
all the weights, the pairs of absent experts weighed zero). The decode
passes' sizes and forms are pinned as the five served shares make them.
``tests/test_moe.py`` is the training Switch layer's; the kernel a decode
pass takes is ``tests/test_expert_stream.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import moe_ops

N, K, D, F = 2304, 4, 16, 8       # 9,216 pairs: a 1/32 share's pass is a
E = 32                            # prefill's (768 rows), not a decode's

# (pairs a decode step, experts held, experts) of the five served shares:
# slots x top-k of grid/configs/*.json, and the rows a pass of theirs holds
DECODE_PASSES = {
    "laguna": ((16 * 10, 128, 256), 160),
    "ling": ((64 * 8, 128, 512), 256),
    "glm": ((64 * 8, 36, 288), 256),
    "motif": ((64 * 8, 24, 384), 256),
    "kimi": ((32 * 8, 12, 384), 256),
}

# (pairs of the largest prompt bucket, experts held, experts): the rows a
# pass holds and how it returns them
PREFILL_PASSES = {
    "laguna": ((8192 * 10, 128, 256), 51200, "gather"),
    "ling": ((8192 * 8, 128, 512), 20480, "gather"),
    "glm": ((8192 * 8, 36, 288), 10240, "scatter"),
    "motif": ((8192 * 8, 24, 384), 5120, "scatter"),
    "kimi": ((4096 * 8, 12, 384), 1280, "scatter"),
}


def _poly(gate, p):
    return jax.nn.silu(gate) * p[0] + p[1]


def _layer(rng, dtype, with_params):
    def arr(*shape, scale=1.0):
        return jnp.asarray((rng.randn(*shape) * scale).astype("float32")
                           ).astype(dtype)
    u = arr(N, D)
    wg, wu = arr(E, D, F, scale=D ** -0.5), arr(E, D, F, scale=D ** -0.5)
    wd = arr(E, F, D, scale=F ** -0.5)
    pn = jnp.asarray(rng.rand(E, 2).astype("float32") + 0.5) \
        if with_params else None
    w = jnp.asarray(rng.rand(N, K).astype("float32"))
    return u, w, wg, wu, wd, pn


def _share_against_all_held(u, idx, w, wg, wu, wd, pn, held, valid,
                            combine, monkeypatch):
    """``(the share's y, the all-held path's y with the absent experts'
    pairs weighed zero, the share's stats)``."""
    act = jax.nn.silu if pn is None else _poly
    at = jnp.asarray(held)
    here = np.zeros((E,), bool)
    here[held] = True
    want, _ = moe_ops.expert_layer(
        u, idx, jnp.where(jnp.asarray(here)[idx], w, 0), wg, wu, wd,
        row_valid=valid, activation=act, act_params=pn)
    monkeypatch.setattr(moe_ops, "combine_form", lambda *a: combine)
    got, stats = moe_ops.expert_layer(
        u, idx, w, wg[at], wu[at], wd[at], n_expert=E, held=held,
        row_valid=valid, activation=act,
        act_params=None if pn is None else pn[at])
    return np.asarray(got), np.asarray(want), stats


@pytest.mark.parametrize("combine", ["gather", "scatter"])
@pytest.mark.parametrize("held_of", [2, 4, 8, 16, 32])
@pytest.mark.parametrize("case", [
    "float32", "bfloat16", "prompt_tail", "act_params", "every_pair",
    "no_pair"])
def test_a_prefill_pass_returns_every_held_pair(rng, monkeypatch, case,
                                                held_of, combine):
    """Shares of a half to a thirty-second at a prefill's row count, each
    combine: equal to the all-held path over the same pairs (float32 to
    round-off, bfloat16 within that path's own rounding), with a prompt's
    tail cut by ``row_valid``, with an activation's own numbers, under a
    router that sends EVERY pair to the held experts (several passes,
    nothing dropped) and one that sends none (no pass, zeros)."""
    e_held = E // held_of
    held = sorted(rng.choice(E, e_held, replace=False).tolist())
    rows = moe_ops.pass_rows(N * K, e_held, E)
    assert rows > moe_ops.STREAM_ROWS
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    u, w, wg, wu, wd, pn = _layer(rng, dtype, case == "act_params")
    away = sorted(set(range(E)) - set(held))
    if case == "every_pair":
        idx = rng.choice(held, (N, K))
    elif case == "no_pair":
        idx = rng.choice(away, (N, K))
    else:
        idx = np.argsort(rng.rand(N, E), axis=1)[:, :K]
    idx = jnp.asarray(idx, jnp.int32)
    valid = jnp.asarray(np.arange(N) < 1500) if case == "prompt_tail" \
        else None
    before = mx.counter("moe/share_combine." + combine).value
    got, want, stats = _share_against_all_held(
        u, idx, w, wg, wu, wd, pn, held, valid, combine, monkeypatch)
    assert mx.counter("moe/share_combine." + combine).value == before + 1
    load = int(moe_ops.held_pairs(idx, held, E, valid))
    if case == "every_pair":
        assert load == N * K and -(-load // rows) >= 2
    if case == "no_pair":
        assert load == 0 and not np.any(got)
        assert int(stats["experts_touched"]) == 0
    if case == "prompt_tail":
        assert not np.any(got[1500:]) and np.any(got[:1500])
    if case == "bfloat16":
        f32 = [x.astype(jnp.float32) for x in (u, wg, wu, wd)]
        exact = _share_against_all_held(
            f32[0], idx, w, *f32[1:], pn, held, valid, combine,
            monkeypatch)[1]
        tol = float(np.max(np.abs(want - exact)))
        assert 0 < tol < 0.1
        assert float(np.max(np.abs(got - want))) <= tol
    else:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("share", sorted(DECODE_PASSES))
def test_a_decode_pass_is_what_it_was(share, monkeypatch):
    """Every decode pass the five served shares make holds the rows it
    held before a prefill's pass was cut to its load (twice an even
    router's, in whole tiles of 256), streams on a TPU and scatters."""
    geometry, rows = DECODE_PASSES[share]
    assert moe_ops.pass_rows(*geometry) == rows
    assert moe_ops.combine_form(rows, geometry[0]) == "scatter"
    assert moe_ops.matmul_form(rows) == "grouped"
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    assert moe_ops.matmul_form(rows) == "stream"


@pytest.mark.parametrize("share", sorted(PREFILL_PASSES))
def test_a_prefill_pass_holds_its_load_and_a_margin(share, monkeypatch):
    """The largest bucket's pass of each served share: the even load and
    ``PASS_MARGIN`` of it in whole tiles, a grouped product, and the
    combine its part of the pairs calls for."""
    geometry, rows, combine = PREFILL_PASSES[share]
    n_pairs, e_held, n_expert = geometry
    even = n_pairs * e_held // n_expert
    assert moe_ops.pass_rows(*geometry) == rows
    assert even < rows <= 2 * even and rows % 256 == 0
    assert moe_ops.combine_form(rows, n_pairs) == combine
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    assert moe_ops.matmul_form(rows) == "grouped"


def test_a_pass_is_never_cut_to_a_decode_pass():
    """A prefill's pass stays over ``STREAM_ROWS`` however small its even
    load (the form of its product does not change with the margin), and
    holds every pair where every expert is held."""
    assert moe_ops._share_rows(8 * 257, 1, 8) == moe_ops.STREAM_ROWS + 256
    assert moe_ops._share_rows(512, 2, 16) == 256
    assert moe_ops.pass_rows(8192 * 6, 64, 64) == 8192 * 6

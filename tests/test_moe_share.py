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
    assert not moe_ops._stream_bound(rows, e_held)
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
    e_held = geometry[1]
    assert moe_ops.pass_rows(*geometry) == rows
    assert moe_ops.combine_form(rows, geometry[0], e_held) == "scatter"
    assert moe_ops.matmul_form(rows, e_held) == "grouped"
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    assert moe_ops.matmul_form(rows, e_held) == "stream"


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
    assert moe_ops.combine_form(rows, n_pairs, e_held) == combine
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    assert moe_ops.matmul_form(rows, e_held) == "grouped"


def test_a_pass_is_never_cut_to_a_decode_pass(monkeypatch):
    """A prefill's pass stays over ``STREAM_ROWS_AN_EXPERT`` rows an expert
    however small its even load (the form of its product does not change
    with the margin), and holds every pair where every expert is held."""
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    bound = moe_ops.STREAM_ROWS_AN_EXPERT
    # twice the even load (2 x 2,064) is over the bound of 32 x 64 by a
    # little: the pass is a prefill's, the even load and a margin
    rows = moe_ops._share_rows(8 * 2064, 64, 512)
    assert rows == 2816 and rows > bound * 64
    assert moe_ops.matmul_form(rows, 64) == "grouped"
    # ... and a margin that would fall under the bound stays over it
    rows = moe_ops._share_rows(8 * 257, 1, 8)
    assert rows == 512 and moe_ops.matmul_form(rows, 1) == "grouped"
    assert moe_ops._share_rows(512, 16, 128) == 256
    assert moe_ops.matmul_form(256, 16) == "stream"
    assert moe_ops.pass_rows(8192 * 6, 64, 64) == 8192 * 6


# (configuration, pass, pairs, experts held, experts) -> (rows, product on
# a TPU, combine): every pass the served expert configurations make at
# their slots and prompt buckets (grid/configs, grid/traffic), as the rule
# of rows a pass (STREAM_ROWS = 512, the parent of PR 65) gave them. The
# rule of rows AN EXPERT gives each the same, and Nemotron-3's decode pass
# of 768 rows over 64 held experts (12 an expert) the stream.
SERVED_PASSES = [
    ("smallthinker", "decode", 96, 64, 64, 96, "stream", "scatter"),
    ("smallthinker", "1024", 6144, 64, 64, 6144, "grouped", "gather"),
    ("smallthinker", "2048", 12288, 64, 64, 12288, "grouped", "gather"),
    ("smallthinker", "4096", 24576, 64, 64, 24576, "grouped", "gather"),
    ("smallthinker", "8192", 49152, 64, 64, 49152, "grouped", "gather"),
    ("kimi", "decode", 256, 12, 384, 256, "stream", "scatter"),
    ("kimi", "2048", 16384, 12, 384, 768, "grouped", "scatter"),
    ("kimi", "4096", 32768, 12, 384, 1280, "grouped", "scatter"),
    ("laguna", "decode", 160, 128, 256, 160, "stream", "scatter"),
    ("laguna", "4096", 40960, 128, 256, 25600, "grouped", "gather"),
    ("laguna", "8192", 81920, 128, 256, 51200, "grouped", "gather"),
    ("ling", "decode", 512, 128, 512, 256, "stream", "scatter"),
    ("ling", "2048", 16384, 128, 512, 5120, "grouped", "gather"),
    ("ling", "4096", 32768, 128, 512, 10240, "grouped", "gather"),
    ("ling", "8192", 65536, 128, 512, 20480, "grouped", "gather"),
    ("motif", "decode", 512, 24, 384, 256, "stream", "scatter"),
    ("motif", "2048", 16384, 24, 384, 1280, "grouped", "scatter"),
    ("motif", "4096", 32768, 24, 384, 2560, "grouped", "scatter"),
    ("motif", "8192", 65536, 24, 384, 5120, "grouped", "scatter"),
    ("glm", "decode", 512, 36, 288, 256, "stream", "scatter"),
    ("glm", "4096", 32768, 36, 288, 5120, "grouped", "scatter"),
    ("glm", "8192", 65536, 36, 288, 10240, "grouped", "scatter"),
    ("dsv32", "decode", 256, 16, 256, 256, "stream", "scatter"),
    ("dsv32", "8192", 65536, 16, 256, 5120, "grouped", "scatter"),
    ("nemotron3", "decode", 768, 64, 128, 768, "stream", "scatter"),
    ("nemotron3", "2048", 12288, 64, 128, 7680, "grouped", "gather"),
    ("nemotron3", "4096", 24576, 64, 128, 15360, "grouped", "gather"),
    ("nemotron3", "8192", 49152, 64, 128, 30720, "grouped", "gather"),
]


@pytest.mark.parametrize(
    "pairs,e_held,n_expert,rows,matmul,combine",
    [c[2:] for c in SERVED_PASSES],
    ids=["%s-%s" % c[:2] for c in SERVED_PASSES])
def test_a_served_pass_keeps_its_rows_and_forms(
        monkeypatch, pairs, e_held, n_expert, rows, matmul, combine):
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    assert moe_ops.pass_rows(pairs, e_held, n_expert) == rows
    assert moe_ops.matmul_form(rows, e_held) == matmul
    assert moe_ops.combine_form(rows, pairs, e_held) == combine


def test_the_pinned_passes_are_the_configurations_own():
    """The table's pairs, experts held and experts are what
    ``grid/configs`` and ``grid/traffic`` say: slots (decode) or a prompt
    bucket's rows, times the experts a token."""
    from grid import manifest

    short = {"smallthinker-21b-a3b-serve": "smallthinker",
             "kimi-k2-ep32-serve": "kimi", "laguna-s-ep2-serve": "laguna",
             "ling-3-flash-ep4-serve": "ling",
             "motif-3-beta-ep16-serve": "motif",
             "glm-5.3-flash-ep8-serve": "glm",
             "deepseek-v32-ep16-serve": "dsv32",
             "nemotron-3-nano-ep2-serve": "nemotron3"}
    found = set()
    for w in manifest.benchmark()["workloads"]:
        if w["config"] not in short:
            continue
        cell = manifest.Cell(w["name"])
        mcfg = manifest.driver(cell.kind).model_config(cell.config)
        geometry = (len(mcfg.experts_held), mcfg.n_expert)
        found.add((short[w["config"]], "decode",
                   cell.config["engine"]["slots"] * mcfg.top_k) + geometry)
        for b in cell.traffic["prompt_buckets"]:
            found.add((short[w["config"]], str(b), b * mcfg.top_k)
                      + geometry)
    assert found == {c[:5] for c in SERVED_PASSES}

"""EvaByte's decoder (EVA attention: exact keys inside a tumbling window,
one pooled key and value a chunk of every closed window, one softmax; a
COMPACTING cache group; several prediction heads) through the serving
stack, against its plain float32 reference (``grid/reference/evabyte.py``),
at a toy size on the CPU: 2 layers, d 32, 4 heads of 8, ff 48, vocabulary
40, 3 prediction heads, windows of 32 in chunks of 4, pages of 4 rows.
LOGITS of every head are compared, never sampled tokens.

Tolerance. In float32 the served path and the reference differ in the
ORDER of their sums only (the paged kernel's online softmax, the prefill's
softmax over two parts, against a whole one over a mask): the worst logit
difference read was 1.2e-6 on logits of standard deviation 1. ``TOL`` =
1e-5 is eight times that and far under what a summary left out or pooled
wrongly gives (0.05 and more, in float32, where nothing else moves).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import evabyte as ref
from paddle_tpu.flags import set_flag
from paddle_tpu.models import evabyte
from paddle_tpu.serving.kv_cache import (KV, CacheGroup, PagedKVCache,
                                         open_window_start)

TOL = 1e-5
W, C, PS = 32, 4, 4
PUBLISHED = dict(  # the toy under the published config's own keys
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    vocab_size=40, intermediate_size=48, num_hidden_layers=2,
    window_size=W, chunk_size=C, num_pred_heads=3, rms_norm_eps=1e-5,
    rope_theta=1e5)


def toy_cfg(**over):
    kw = dict(vocab_size=40, n_layer=2, d_model=32, n_head=4, n_kv_head=4,
              d_ff=48, window=W, chunk=C, n_pred_heads=3, max_seq=160,
              dtype="float32")
    kw.update(over)
    return evabyte.EvaByteConfig(**kw)


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    return evabyte.EvaByteLM(cfg, params=evabyte.init_params(cfg, 3))


@pytest.fixture(scope="module")
def text():
    return np.random.RandomState(0).randint(0, 40, 128).astype(np.int32)


@pytest.fixture(scope="module")
def want(toy, text):
    """The reference's logits [128, 3, 40] over the whole text."""
    return np.asarray(ref.forward(toy.params, PUBLISHED, text))


def toy_cache(cfg, slots=3, num_pages=200):
    return PagedKVCache(
        cfg.n_layer, cfg.n_head, cfg.d_head, slots, cfg.max_seq, PS,
        num_pages, dtype=cfg.dtype,
        groups=[CacheGroup("eva", tuple(range(cfg.n_layer)), cfg.window,
                           num_pages, KV, cfg.chunk)])


def _prefill(model, seq, bucket=128):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(seq)] = seq
    return model.prefill_forward(model.params, model.cfg, jnp.asarray(toks),
                                 jnp.asarray([len(seq)], jnp.int32))


def _admit(model, ops, cache, slot, pages, seq, bucket=128):
    """What the engine's prefill executable does with ``kept``."""
    dest = jnp.asarray(ops.prompt_dest_groups([pages], slot))
    cache = ops.set_page_table(cache, slot, dest)
    _, kept = _prefill(model, seq, bucket)
    for i, kv in enumerate(kept):
        cache = ops.write_prompt(cache, i, *(t[0] for t in kv), dest,
                                 len(seq))
    return cache


# -- (a) the map, against a table written out by hand ------------------------

def test_the_row_map_and_the_pages_a_request_needs_by_hand():
    ops = toy_cache(toy_cfg())
    # position -> view row: 8 summaries a closed window, then p mod 32
    by_hand = {0: 0, 3: 3, 31: 31, 32: 8, 33: 9, 63: 39, 64: 16, 95: 47,
               96: 24, 127: 55}
    got = np.asarray(ops._view_row(0, jnp.asarray(list(by_hand))))
    assert got.tolist() == list(by_hand.values())
    # context length -> rows attention reads (a slot that holds nothing: 0)
    lengths = {0: 0, 1: 1, 32: 32, 33: 9, 64: 40, 65: 17, 100: 24 + 4}
    got = np.asarray(ops._group_len(0, jnp.asarray(list(lengths))))
    assert got.tolist() == list(lengths.values())
    # positions -> pages of 4 rows: the closed windows' summaries, a
    # window's rows (or the request's, if fewer), and 2 pages of waiting
    pages = {1: 1 + 2, 5: 2 + 2, 31: 8 + 2, 32: 10 + 2, 33: 10 + 2,
             64: 12 + 2, 100: 14 + 2, 160: 18 + 2}
    assert {n: ops.pages_needed(0, n) for n in pages} == pages
    assert ops.pages_per_slot == ops.page_table_len == 20
    assert ops.group_rows(0) == 80
    # at the published sizes: 136 + 8 (n // 2048) past a window
    real = PagedKVCache(1, 32, 128, 1, 18432, 16, 8, dtype="bfloat16",
                        groups=[CacheGroup("eva", (0,), 2048, 8, KV, 16)])
    assert {n: real.pages_needed(0, n) for n in (2047, 2048, 7840, 17408)} \
        == {2047: 128 + 8, 2048: 136 + 8, 7840: 152 + 8, 17408: 192 + 8}
    assert real.pages_per_slot == 208
    # a request shorter than a window: its last 2 pages are where the
    # first window's summaries wait, after a WHOLE window's entries
    dest = ops.prompt_dest_groups([[11, 12, 13, 14]], slot=2)
    assert dest[:2].tolist() == [11, 12] and dest[8:10].tolist() == [13, 14]
    assert dest[2:8].tolist() == [0] * 6 and dest[10:].tolist() == [0] * 10
    long = list(range(30, 46))
    assert ops.prompt_dest_groups([long])[:16].tolist() == long
    assert np.asarray(open_window_start(
        jnp.asarray([5, 32, 70, 128]), 128, W)).tolist() == [0, 32, 64, 96]
    assert int(open_window_start(jnp.asarray(20), 16, W)) == 0


def test_a_compacting_group_states_its_geometry():
    cfg = toy_cfg()
    for bad in (dict(window=None), dict(chunk=3), dict(window=40),
                dict(kind="latent")):
        g = dict(name="eva", layers=(0, 1), window=W, num_pages=8, kind=KV,
                 chunk=C)
        g.update(bad)
        with pytest.raises(ValueError, match="compacting group"):
            PagedKVCache(2, 4, 8, 2, 160, PS, 8, groups=[CacheGroup(**g)])
    with pytest.raises(ValueError, match="compacting group"):
        PagedKVCache(2, 4, 8, 2, 160, PS, 8, cache_steps=2,
                     groups=[CacheGroup("eva", (0, 1), W, 8, KV, C)])
    ops = toy_cache(cfg)
    state = ops.init_state()
    for call in (lambda: ops.export_pages(state, [1]),
                 lambda: ops.copy_pages(state, jnp.asarray([1]),
                                        jnp.asarray([2]))):
        with pytest.raises(ValueError, match="compacting group"):
            call()


# -- (b) prefill against the reference ---------------------------------------

@pytest.mark.parametrize("length", [
    pytest.param(6, id="mid-chunk"), pytest.param(8, id="chunk-edge"),
    pytest.param(32, id="window-edge"), pytest.param(63, id="window-last"),
    pytest.param(101, id="past-three-windows")])
def test_prefill_equals_the_reference(toy, text, want, length):
    """Every row of a bucket-padded prompt, every head; ``kept`` is the
    open window's rows and a summary a chunk of the bucket."""
    x, kept = _prefill(toy, text[:length])
    got = np.asarray(toy.head(toy.params, toy.cfg, x))[0, :length]
    np.testing.assert_allclose(got, want[:length], atol=TOL, rtol=0)
    assert got.shape == (length, 3, 40)
    last, _ = toy.prefill_last(toy.params, jnp.asarray(text[None]),
                               jnp.asarray([length]))
    np.testing.assert_allclose(np.asarray(last)[0], want[length - 1, 0],
                               atol=TOL, rtol=0)
    assert len(kept) == 2
    k_open, v_open, ks, vs = kept[0]
    assert k_open.shape == v_open.shape == (1, 32, 4, 8)
    assert ks.shape == vs.shape == (1, 32, 4, 8)


def test_a_bucket_is_whole_chunks_and_whole_windows(toy):
    for bucket in (30, 48):
        with pytest.raises(ValueError, match="whole chunks"):
            _prefill(toy, [1, 2, 3], bucket)


# -- (c) decode through the cache, across window closes ----------------------

@pytest.mark.parametrize("kernel", ["off", "interpret"])
@pytest.mark.parametrize("start", [
    pytest.param(5, id="from-mid-chunk"), pytest.param(32, id="from-a-close"),
    pytest.param(70, id="from-the-third-window")])
def test_decode_through_the_cache_equals_the_reference(toy, text, want,
                                                       start, kernel):
    """A prompt of ``start`` bytes, then position by position to 128
    through the pool: from 5 that is across THREE window closes (at 31, 63
    and 95) and thirty chunk ends; the logits of every head at every
    position equal the reference's full forward; in plain XLA and by the
    paged kernel (interpreted). The counters say what the lengths imply."""
    set_flag("paged_attention_kernel", kernel)
    try:
        cfg = toy.cfg
        ops = toy_cache(cfg)
        pages = list(range(7, 7 + ops.pages_needed(0, 128)))
        cache = _admit(toy, ops, ops.init_state(), 1, pages, text[:start])
        step = jax.jit(lambda c, t, p, a: toy.decode_forward(
            toy.params, cfg, c, ops, t, p, a))
        active = jnp.asarray([False, True, False])
        closed = {"eva_chunks_closed": 0, "eva_windows_closed": 0}
        for p in range(start, 128):
            logits, cache, stats = step(
                cache, jnp.asarray([0, text[p], 0]), jnp.asarray([0, p, 0]),
                active)
            np.testing.assert_allclose(np.asarray(logits)[1], want[p],
                                       atol=TOL, rtol=0, err_msg=str(p))
            assert int(stats["attn_rows_read.eva_summary"]) == 8 * (p // W)
            assert int(stats["attn_rows_read.eva_exact"]) == p % W + 1
            assert int(stats["attn_rows_context.eva"]) == p + 1
            for name in closed:
                closed[name] += int(stats[name])
        assert closed["eva_chunks_closed"] == 128 // C - start // C
        assert closed["eva_windows_closed"] == 128 // W - start // W
        # only the request's pages were written, and its table holds them
        table = np.asarray(cache["pt"][1])
        assert sorted(table[:len(pages)].tolist()) == pages
        rows = np.asarray(cache["k"][0]).reshape(200, PS, -1)
        assert not rows[[p for p in range(200) if p not in pages]].any()
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_two_slots_close_windows_at_different_steps(toy, text, want):
    """Slot 0 closes a window at step 11 (position 31), slot 2 at step 3
    (a text shifted by 8: position 31 of its own); slot 1 holds nothing,
    is never written and keeps its page table."""
    cfg = toy.cfg
    ops = toy_cache(cfg)
    other = np.roll(text, -8)
    want_other = np.asarray(ref.forward(toy.params, PUBLISHED, other))
    need = ops.pages_needed(0, 64)
    cache = _admit(toy, ops, ops.init_state(), 0,
                   list(range(1, 1 + need)), text[:20])
    cache = _admit(toy, ops, cache, 2, list(range(40, 40 + need)),
                   other[:28])
    idle = np.asarray(cache["pt"][1]).copy()
    step = jax.jit(lambda c, t, p, a: toy.decode_forward(
        toy.params, cfg, c, ops, t, p, a))
    active = jnp.asarray([True, False, True])
    closes = []
    for j in range(36):
        pos = jnp.asarray([20 + j, 9, 28 + j])
        logits, cache, stats = step(
            cache, jnp.asarray([text[20 + j], 7, other[28 + j]]), pos,
            active)
        got = np.asarray(logits)
        np.testing.assert_allclose(got[0], want[20 + j], atol=TOL, rtol=0)
        np.testing.assert_allclose(got[2], want_other[28 + j], atol=TOL,
                                   rtol=0)
        closes.append(int(stats["eva_windows_closed"]))
    assert [j for j, n in enumerate(closes) if n] == [3, 11, 35]
    assert (np.asarray(cache["pt"][1]) == idle).all()
    used = set(range(1, 1 + need)) | set(range(40, 40 + need))
    rows = np.asarray(cache["k"][1]).reshape(200, PS, -1)
    assert not rows[[p for p in range(200) if p not in used]].any()


@pytest.mark.parametrize("heads,d_model", [(4, 32), (2, 256)])
def test_the_kernel_and_the_gather_agree_on_a_compacted_view(heads, d_model):
    """The same compacted pool read by the paged kernel (interpreted) and
    by the XLA gather: heads of 8 lanes (the kernel's per-lane fold) and
    heads of a whole lane tile (its grouped fold, the published
    geometry's), three slots at contexts in the first, third and fourth
    window and one that holds nothing."""
    rng = np.random.RandomState(1)
    ops = PagedKVCache(1, heads, d_model // heads, 4, 160, PS, 100,
                       dtype="float32",
                       groups=[CacheGroup("eva", (0,), W, 100, KV, C)])
    state = ops.init_state()
    state["k"] = jnp.asarray(rng.randn(*state["k"].shape), jnp.float32)
    state["v"] = jnp.asarray(rng.randn(*state["v"].shape), jnp.float32)
    state["pt"] = jnp.asarray(rng.permutation(100)[:80].reshape(4, 20),
                              jnp.int32)
    q = jnp.asarray(rng.randn(4, heads, d_model // heads), jnp.float32)
    ctx = jnp.asarray([20, 70, 128, 55])
    active = jnp.asarray([True, True, True, False])
    out = {}
    for kernel in ("off", "interpret"):
        set_flag("paged_attention_kernel", kernel)
        try:
            out[kernel] = np.asarray(ops.decode_attention(
                state, 0, q, ctx, active, sm_scale=0.3))
        finally:
            set_flag("paged_attention_kernel", "auto")
    np.testing.assert_allclose(out["interpret"][:3], out["off"][:3],
                               atol=2e-6, rtol=0)
    # what the lengths mean: slot 1 reads 16 summaries and 6 exact rows
    k, v = ops.context(state, 0)
    sc = np.einsum("hd,rhd->hr", np.asarray(q[1]),
                   np.asarray(k[1, :22])) * 0.3
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        out["off"][1], np.einsum("hr,rhd->hd", pr, np.asarray(v[1, :22])),
        atol=2e-6, rtol=0)


# -- (d) what the decode step moves -------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_the_decode_step_holds_no_pool_sized_copy():
    """The jaxpr of a decode step, the kernel armed: whatever yields an
    array with the pool's rows is a row scatter into the pool (a row, a
    summary a layer), never a copy, a gather, a select or a slice of it;
    the compaction writes the page table alone; the open chunk comes out
    as ``chunk`` rows a slot."""
    set_flag("paged_attention_kernel", "interpret")
    try:
        cfg = toy_cfg()
        model = evabyte.EvaByteLM(cfg, params={})
        params = jax.eval_shape(lambda: evabyte.init_params(cfg, 0))
        ops = toy_cache(cfg, slots=2, num_pages=300)
        cache = jax.eval_shape(ops.init_state)
        ints = jax.ShapeDtypeStruct((2,), jnp.int32)
        flags = jax.ShapeDtypeStruct((2,), jnp.bool_)
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, a: model.decode(p, c, ops, t, pos, a))(
                params, cache, ints, ints, flags).jaxpr
        rows = 300 * PS
        wrote = [e.primitive.name for e in _eqns(jaxpr)
                 if any(rows in getattr(v.aval, "shape", ())
                        for v in e.outvars)]
        # K and V, a layer: the position's row and the chunk's summary
        assert sorted(set(wrote) - {"pjit"}) == ["scatter"]
        assert wrote.count("scatter") == 2 * 2 * cfg.n_layer
        assert len([e for e in _eqns(jaxpr)
                    if e.primitive.name == "pallas_call"]) == cfg.n_layer
        chunks = [e for e in _eqns(jaxpr) if e.primitive.name == "gather"
                  and rows in e.invars[0].aval.shape]
        assert len(chunks) == 2 * cfg.n_layer
        assert all(e.outvars[0].aval.shape == (2, 1, C, 32) for e in chunks)
    finally:
        set_flag("paged_attention_kernel", "auto")


def test_a_lower_precision_is_seen(monkeypatch, toy, text, want):
    """What ``benchmarks/control_evabyte.py bf16`` lowers from outside (the
    program has no option for it): the residual and the pooling in
    bfloat16 move the logits a thousand times the float32 path's distance
    from the reference."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "control_evabyte", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "control_evabyte.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    for name in ("summarize", "_attn_out", "_mlp"):     # put back after
        monkeypatch.setattr(evabyte, name, getattr(evabyte, name))
    control.bf16()
    x, _ = _prefill(toy, text)
    got = np.asarray(toy.head(toy.params, toy.cfg, x))[0]
    assert np.abs(got - want).max() > 1000 * TOL


def test_the_diagnostic_finds_the_copies_across_a_kernel_call():
    """``benchmarks/diag_eva_step.async_copies`` on a scheduled module's
    text in both forms a chip's compiler prints (``slice-start`` and
    ``async-start`` under a slice's name): bytes into fast memory by the
    operand fetched, and those started before a Pallas call and waited for
    after it. A copy that stays in HBM is none."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "diag_eva_step", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "diag_eva_step.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    fast, hbm = "{1,0:T(8,128)(2,1)S(1)}", "{1,0:T(8,128)(2,1)}"
    text = "\n".join([
        "ENTRY %main {",
        "  %slice-start.4 = ((bf16[64,32]" + hbm + "), bf16[16,32]" + fast
        + ", s32[]{:S(2)}) slice-start(%params__layers___1___wo__.1), "
        "slice={[0:16], [0:32]}",
        "  %slice-start.5 = ((bf16[64,32]" + hbm + "), bf16[16,32]" + fast
        + ", s32[]{:S(2)}) async-start(%params__layers___0___wg__), "
        "calls=%async.1",
        "  %slice-done.5 = bf16[16,32]" + fast
        + " async-done(%slice-start.5)",
        "  %copy-start.1 = (s32[12]{0}, s32[12]{0:S(1)}, u32[]{:S(2)}) "
        "copy-start(%fusion.3)",
        '  %paged_attention.2 = bf16[12,8,32]{2,1,0} custom-call(%q), '
        'custom_call_target="tpu_custom_call"',
        "  %slice-done.4 = bf16[16,32]" + fast
        + " slice-done(%slice-start.4)",
        "  %copy-done.1 = s32[12]{0} copy-done(%copy-start.1)",
        "}"])
    got = diag.async_copies(text)
    assert got["pallas_calls"] == 1
    assert got["bytes_by_operand"] == {
        "params__layers___N___wg__": 1024, "params__layers___N___wo__": 1024}
    assert got["bytes_across_a_pallas_call"] == {
        "params__layers___N___wo__": 1024}
    assert (got["total_bytes"], got["total_across"]) == (2048, 1024)

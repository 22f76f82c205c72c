"""A page RUN (PR 64): the pool of a latent cache group hands its pages out
in aligned runs (``serving/page_pool.py``), and the two kernels that walk
a latent page table copy a run at a time (``copy_pages``:
``mla_attention.mla_paged_decode``, ``dsa_index.dsa_index_scores_paged``).
All on the CPU, the kernels in the interpreter: the pool's guarantees, the
kernels against their gathers over tables MADE of runs, and an engine that
admits and retires out of order and keeps every latent slot's table whole
runs."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import dsa_index
from paddle_tpu.ops.pallas_kernels import mla_attention as mla
from paddle_tpu.serving import metrics as sm
from paddle_tpu.serving.page_pool import PagePool, PagePoolExhausted

# -- the pool -----------------------------------------------------------------


def _runs(pages, r):
    """The first pages of ``pages`` read as whole runs of ``r``; raises
    where a run is not aligned, ascending and whole."""
    p = np.asarray(pages).reshape(-1, r)
    assert (p[:, 0] % r == 0).all() and (p == p[:, :1] + np.arange(r)).all()
    return p[:, 0].tolist()


@pytest.mark.parametrize("r", [4, 8])
def test_a_pool_of_runs_hands_out_whole_aligned_ascending_runs(r, rng):
    """Two hundred allocations and out-of-order frees: every allocation
    is ``ceil(n / r)`` runs, no run is out twice, and the counts, the
    padding and the gauges follow."""
    pool = PagePool(24 * r, 16, name="runs_%d" % r, primary=False,
                    run_pages=r)
    assert sm.page_run_pages("runs_%d" % r).value == r
    held, asked = [], []
    for _ in range(200):
        if held and (rng.rand() < 0.45 or pool.num_free < 5 * r):
            i = rng.randint(len(held))
            pool.free(held.pop(i))
            asked.pop(i)
        else:
            n = int(rng.randint(1, 5 * r))
            pages = pool.alloc(n)
            assert len(pages) == -(-n // r) * r == pool.rounded(n)
            _runs(pages, r)
            held.append(pages)
            asked.append(n)
        out = [p for pages in held for p in pages]
        assert len(set(out)) == len(out) == pool.num_used
        assert pool.num_free == pool.capacity - len(out)
        assert pool.num_padding == len(out) - sum(asked) \
            == sm.pages_padding("runs_%d" % r).value
        assert sm.pages_used("runs_%d" % r).value == len(out)
    for pages in held:
        pool.free(pages)
    assert pool.num_used == pool.num_padding == 0


@pytest.mark.parametrize("r", [1, 4, 8])
def test_exhaustion_is_all_or_nothing_and_leaves_the_pool_untouched(r):
    pool = PagePool(8 * r, 16, name="t", primary=False, run_pages=r)
    a = pool.alloc(5 * r + 1)               # six runs
    free, padding = list(pool._free), pool.num_padding
    with pytest.raises(PagePoolExhausted):
        pool.alloc(2 * r + 1)               # three runs, two are free
    assert pool._free == free and pool.num_padding == padding
    assert pool.num_used == 6 * r
    assert len(pool.alloc(2 * r)) == 2 * r  # what IS free still comes
    pool.free(a)
    assert pool.num_free == 6 * r


@pytest.mark.parametrize("what", ["a_partial_run", "a_run_out_of_order",
                                  "a_run_that_starts_mid_run",
                                  "a_double_free", "a_page_past_the_pool"])
def test_free_refuses(what):
    pool = PagePool(32, 16, name="t", primary=False, run_pages=4)
    a = pool.alloc(8)
    bad = {"a_partial_run": a[:3], "a_run_out_of_order": a[:4][::-1],
           "a_run_that_starts_mid_run": a[1:5],
           "a_double_free": a[:4] + a[:4],
           "a_page_past_the_pool": [32, 33, 34, 35]}[what]
    with pytest.raises(ValueError):
        pool.free(bad)
    # what was refused took nothing with it beyond the runs before it
    assert pool.num_used == (4 if what == "a_double_free" else 8)


@pytest.mark.parametrize("r", [4, 8])
def test_a_pool_whose_pages_are_no_multiple_of_a_run(r):
    """The pages past the last whole run are never handed out, and the
    pool counts its whole runs as what it has."""
    pool = PagePool(3 * r + r - 1, 16, name="t", primary=False, run_pages=r)
    assert pool.num_pages == 4 * r - 1 and pool.capacity == 3 * r \
        == pool.num_free
    assert pool.pages_needed(16 * r + 1) == 2 * r
    with pytest.raises(PagePoolExhausted):
        pool.alloc(3 * r + 1)
    pages = pool.alloc(3 * r)
    assert sorted(pages) == list(range(3 * r)) and pool.num_free == 0
    with pytest.raises(ValueError):
        pool.free(list(range(3 * r, 4 * r)))
    with pytest.raises(ValueError):
        PagePool(4, 16, name="t", primary=False, run_pages=8)


class _SinglePages:
    """The pool as it was before runs, to the letter: a LIFO of pages."""

    def __init__(self, n):
        self.free_list = list(range(n - 1, -1, -1))

    def alloc(self, n):
        if n > len(self.free_list):
            raise PagePoolExhausted("x")
        return [self.free_list.pop() for _ in range(n)]

    def free(self, pages):
        for p in pages:
            if p in self.free_list:
                raise ValueError("double free")
            self.free_list.append(p)


def test_a_run_of_one_page_is_the_pool_of_single_pages_call_for_call(rng):
    pool, was = PagePool(40, 16, name="t", primary=False), _SinglePages(40)
    assert pool.run_pages == 1 and pool.capacity == 40
    assert [pool.pages_needed(n) for n in (1, 16, 17)] == [1, 1, 2]
    held = []
    for _ in range(300):
        if held and rng.rand() < 0.5:
            pages = held.pop(rng.randint(len(held)))
            # what a prefix donation leaves: any part of a reservation
            cut = rng.randint(len(pages) + 1)
            for part in (pages[:cut], pages[cut:]):
                pool.free(part)
                was.free(part)
        else:
            n = int(rng.randint(0, 12))
            try:
                want = was.alloc(n)
            except PagePoolExhausted:
                with pytest.raises(PagePoolExhausted):
                    pool.alloc(n)
                continue
            got = pool.alloc(n)
            assert got == want
            held.append(got)
        assert pool._free == was.free_list and pool.num_padding == 0
        assert pool.num_free == len(was.free_list)
    with pytest.raises(ValueError):
        pool.free([pool._free[0]])


# -- the latent kernel over tables made of runs -------------------------------

R_MAX = 8


def _latent_case(rng, lens, pps, ps=8, rank=16, rope=8, h=4, width=128):
    """``(q, poisoned, clean, pt)``: ``conftest.poisoned_latent_pool``'s
    pool over a table of whole aligned runs of ``R_MAX`` pages (which are
    runs of 4 and of 1 too) in a scrambled order; every row past a slot's
    length, its own pages' rows among them, is Inf or NaN."""
    lens = np.asarray(lens, np.int32)
    slots = len(lens)
    runs = slots * pps // R_MAX + 2
    first = rng.permutation(runs)[:slots * pps // R_MAX] * R_MAX
    pt = (first[:, None] + np.arange(R_MAX)).reshape(slots, pps)
    clean = np.zeros((runs * R_MAX * ps, width), np.float32)
    live = np.zeros(len(clean), bool)
    for s in range(slots):
        flat = pt[s].repeat(ps) * ps + np.tile(np.arange(ps), pps)
        live[flat[:lens[s]]] = True
    clean[live, :rank + rope] = rng.randn(int(live.sum()), rank + rope)
    poisoned = np.stack([np.full_like(clean, np.nan), clean])
    poisoned[1, ~live] = np.where(np.arange((~live).sum()) % 2, np.inf,
                                  np.nan)[:, None]
    q = np.zeros((slots, h, width), np.float32)
    q[..., :rank + rope] = rng.randn(slots, h, rank + rope)
    return (jnp.asarray(q), jnp.asarray(poisoned), jnp.asarray(clean),
            jnp.asarray(pt.astype(np.int32)))


# a page is 8 rows; a table of 24 pages is three waves of 8 pages or one
# of 16 and a last one the table cuts short; a ring is ONE wave of 8 pages
RUN_CASES = {
    "full_waves_and_an_empty_slot": (24, 8, [64, 0, 128, 192]),
    "partial_waves_a_run_that_straddles_the_length": (24, 8,
                                                      [1, 63, 65, 150, 97]),
    "a_wave_the_table_cuts_short": (24, 16, [128, 129, 192, 0, 250]),
    "a_ring_of_one_wave": (8, None, [5, 64, 0, 30, 64, 57]),
}


@pytest.mark.parametrize("copy_pages", [1, 4, 8])
@pytest.mark.parametrize("case,masked", [
    (case, masked) for case in sorted(RUN_CASES) for masked in (False, True)
    # a row mask comes in whole waves: no table cuts its last one short
    if not (masked and case == "a_wave_the_table_cuts_short")])
def test_the_latent_kernel_copies_a_run_at_a_time_and_equals_the_gather(
        case, masked, copy_pages, rng):
    """Full waves, partial last waves (runs wholly past the length not
    copied, the run that straddles it copied whole and its rows past the
    length dropped), empty slots, a wave the table cuts short and a ring,
    with and without the sparse read's row mask: whatever the run, the
    kernel equals the gather and reads nothing it should not (every such
    row is Inf or NaN)."""
    pps, block, lens = RUN_CASES[case]
    ps, rank = 8, 16
    q, poisoned, clean, pt = _latent_case(rng, np.minimum(lens, pps * ps),
                                          pps)
    lens = jnp.asarray(lens, jnp.int32)
    valid = None
    if masked:
        keep = rng.rand(len(lens), pps * ps) < 0.4
        keep[:, 0] = True       # a slot with a length has one row at least
        valid = jnp.asarray(keep)
    got = np.asarray(mla.mla_paged_decode(
        q, poisoned, pt, lens, page_size=ps, rank=rank, layer=1,
        sm_scale=0.3, block_pages=block, interpret=True, row_valid=valid,
        copy_pages=copy_pages,
        name=mla.RING_KERNEL_NAME if block is None else mla.KERNEL_NAME))
    bound = jnp.minimum(lens, pps * ps)
    rows = (pt * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
    want = np.asarray(attention_ops.mla_decode_attention(
        q, clean[rows.reshape(len(lens), -1)], bound, rank, sm_scale=0.3,
        row_valid=valid))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert np.all(got[np.asarray(bound) == 0] == 0)


def test_a_run_has_to_tile_the_wave_and_the_table(rng):
    q, poisoned, _, pt = _latent_case(rng, [8, 8], 24)
    for kw in (dict(block_pages=6, copy_pages=4),    # not the wave
               dict(block_pages=8, copy_pages=16),   # nor a wave of it
               dict(block_pages=8, copy_pages=0)):
        with pytest.raises(ValueError, match="runs of"):
            mla.mla_paged_decode(q, poisoned, pt, jnp.asarray([8, 8]),
                                 page_size=8, rank=16, layer=1,
                                 interpret=True, **kw)
    # the run a geometry takes: RUN_PAGES where the table and the wave
    # allow, else its largest half that both hold, else single pages
    assert mla.RUN_PAGES in (4, 8)
    assert mla.run_pages(16, 640) == mla.run_pages(16, 8) == mla.RUN_PAGES
    assert mla.run_pages(16, 12) == 4 and mla.run_pages(16, 6) == 2
    assert mla.run_pages(16, 7) == 1 and mla.run_pages(256, 64) == 2


# -- the index kernel over tables made of runs --------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("copy_pages", [1, 4, 8])
def test_the_index_kernel_copies_a_run_at_a_time_and_equals_the_scores(
        copy_pages, dtype, rng):
    """Contexts of 4,096 (two full waves of 2,048 rows), 2,700 (a partial
    second wave: a run that straddles the length), 9 and 0 rows in a table
    of 256 pages made of aligned runs of 8 out of order: the kernel equals
    ``dsa_index_scores`` over the gathered table whatever the run."""
    pool = jnp.asarray(rng.randn(2, 288, 16, 16), dtype)
    first = np.stack([rng.permutation(36)[:32] for _ in range(4)]) * 8
    pt = jnp.asarray((first[:, :, None] + np.arange(8)).reshape(4, 256),
                     jnp.int32)
    q = jnp.asarray(rng.randn(4, 4, 16), dtype)
    w = jnp.asarray(rng.randn(4, 4), jnp.float32)
    ctx = jnp.asarray([4096, 2700, 9, 0], jnp.int32)
    assert dsa_index._WAVE_ROWS == 2048
    got = dsa_index.dsa_index_scores_paged(
        q, w, pool, pt, ctx, layer=1, interpret=True, copy_pages=copy_pages)
    want = attention_ops.dsa_index_scores(
        q, w, pool[1][pt].reshape(4, 4096, 16), ctx)
    live = np.arange(4096)[None, :] < np.asarray(ctx)[:, None]
    np.testing.assert_array_equal(np.asarray(got)[~live],
                                  np.asarray(want)[~live])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5 if dtype == "float32" else 2e-2,
                               atol=1e-5)
    with pytest.raises(ValueError, match="runs of"):
        dsa_index.dsa_index_scores_paged(q, w, pool, pt, ctx, layer=1,
                                         interpret=True, copy_pages=5)


# -- the engine ---------------------------------------------------------------


def _drive(kernel, rng, check):
    """Forty requests of every length through a toy Kimi-K2 (a latent
    group of 16 pages a slot, runs of 8, room for four whole slots and a
    run), admitted and retired out of order; ``check(eng)`` after every
    cycle. Returns each request's logits rows."""
    from paddle_tpu.flags import set_flag
    from paddle_tpu.models import kimi_k2 as kk
    from test_kimi_k2 import _scaled, toy_cfg

    cfg = toy_cfg(max_seq=128)
    model = kk.KimiK2LM(cfg, params=_scaled(kk.init_params(cfg, 3)))
    stream = [(list(rng.randint(0, 96, rng.randint(1, 30))),
               int(rng.randint(2, 40))) for _ in range(40)]
    set_flag("paged_attention_kernel", kernel)
    try:
        with serving.ServingEngine(model, serving.ServingConfig(
                slots=4, max_seq=128, page_size=8, num_pages=44,
                prompt_buckets=(32,), max_queue=64,
                collect_logits=True)) as eng:
            reqs = [eng.submit(p, n) for p, n in stream]
            cycles = 0
            while not eng.scheduler.idle():
                eng.step()
                cycles += 1
                check(eng)
            assert cycles > 200
            assert eng.pool.num_used == eng.pool.num_padding == 0
            return [np.stack(eng.captured_logits(q)) for q in reqs]
    finally:
        set_flag("paged_attention_kernel", "auto")


def _tables_are_whole_runs(eng):
    """Every running request's pages in every latent group, as the
    engine's page table on the device holds them: whole aligned ascending
    runs of the group's own length, and the padding what the rounding of
    each reservation says."""
    ops = eng.cache_ops
    running = [(slot, eng.scheduler.slot_request(slot))
               for slot in range(eng.cfg.slots)]
    running = [(slot, req) for slot, req in running if req is not None]
    for gi, pool in enumerate(eng.pools):
        r = ops.group_run_pages(gi)
        assert pool.run_pages == r == mla.RUN_PAGES
        table = np.asarray(eng._cache[ops._key(gi, "pt")])
        padding = 0
        for slot, req in running:
            pages = req.group_pages[gi]
            asked = ops.pages_needed(gi, req.prompt_len + req.max_new_tokens)
            assert len(pages) == -(-asked // r) * r and _runs(pages, r)
            np.testing.assert_array_equal(table[slot, :len(pages)], pages)
            padding += len(pages) - asked
        stats = eng.stats()
        assert stats["page_run_pages"] == {pool.name: r}
        assert stats["pages_padding"] == {pool.name: padding}
        assert sm.pages_padding(pool.name).value == padding
        assert sm.page_run_pages(pool.name).value == r
    assert eng.page_accounting_ok()


def test_an_engine_keeps_every_latent_table_whole_runs(rng):
    """Requests admitted and retired out of order for a few hundred
    cycles: after every cycle every live slot's table is whole aligned
    runs, ``serving/pages_padding`` equals what the rounding says and the
    page accounting holds; and the kernel (interpreted), copying a run at
    a time through those tables, hands out the gather path's logits."""
    state = rng.get_state()
    got = _drive("interpret", rng, _tables_are_whole_runs)
    rng.set_state(state)
    want = _drive("off", rng, lambda eng: None)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=0)


def test_the_engine_refuses_a_table_that_is_not_whole_runs():
    """The host's check where a slot's table is set: a latent group
    handed pages out of order raises before anything reads them."""
    from paddle_tpu.models import kimi_k2 as kk
    from test_kimi_k2 import toy_cfg

    cfg = toy_cfg()
    model = kk.KimiK2LM(cfg, params=kk.init_params(cfg, 3))
    with serving.ServingEngine(model, serving.ServingConfig(
            slots=2, max_seq=64, page_size=8, num_pages=16,
            prompt_buckets=(8,))) as eng:
        eng._check_runs([list(range(8, 16))])
        for bad in ([1, 2, 3, 4, 5, 6, 7, 8], list(range(7)),
                    list(range(8))[::-1]):
            with pytest.raises(ValueError, match="whole aligned runs"):
                eng._check_runs([bad])

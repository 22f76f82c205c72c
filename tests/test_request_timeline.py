"""A request's own timeline (ISSUE 38): ``Request.timeline`` holds one entry
a HAND-OVER of tokens, ``(t, n, prefill_clock_s)``, appended where
``tokens_out`` grows (``_finish_prefill``, ``_hand_over``) from clock reads
the engine makes anyway; the engine's prefill clock counts the seconds inside
``serving/prefill`` spans, so the difference of two entries' clocks is what
the request lost behind admissions between them. A CPU run proves the
arithmetic and the control flow; no number here is a time of the system."""

import pytest

from paddle_tpu import serving
from paddle_tpu.models import decoder_lm
from paddle_tpu.monitor import tracer
from paddle_tpu.serving import metrics as sm
from paddle_tpu.serving import trace as strace

_MODEL = None


def _engine(**kw):
    global _MODEL
    if _MODEL is None:
        _MODEL = decoder_lm.DecoderLM(
            decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                     n_head=2, max_seq=64), seed=0)
    kw.setdefault("slots", 2)
    kw.setdefault("prompt_buckets", (16,))
    return serving.ServingEngine(_MODEL, serving.ServingConfig(
        page_size=8, max_seq=64, **kw))


def _stall_s(req):
    return req.timeline[-1][2] - req.timeline[0][2]


def _assert_sound(req):
    """What holds of every request that was given a token."""
    tl = req.timeline
    assert tl[0][:2] == (req.first_token_t, 1)
    assert tl[-1][1] == len(req.tokens_out)
    for (t0, n0, c0), (t1, n1, c1) in zip(tl, tl[1:]):
        assert t1 > t0 and n1 > n0
        # the clock runs only inside a span, so never faster than the wall
        assert 0.0 <= c1 - c0 <= (t1 - t0) + 1e-9
    assert req.prefill_s > 0.0


# fuse: tokens a dispatch may bring
CASES = {"plain": 1, "fused": 4}


@pytest.mark.parametrize("case", sorted(CASES))
def test_entries_are_monotone_and_end_at_the_tokens_handed_over(case, rng):
    """Five requests through two slots, so that some are admitted behind a
    dispatch in flight: the first entry is ``(first_token_t, 1, .)``, ``t``
    and ``n`` rise, the last ``n`` is ``len(tokens_out)``. A fused chunk
    gives ONE entry for the several tokens it brings."""
    fuse = CASES[case]
    motif = list(rng.randint(0, 64, 3))
    stream = [(motif * 3, 12)] + [
        (list(rng.randint(0, 64, int(n))), m)
        for n, m in ((9, 7), (5, 10), (14, 2), (7, 9))]
    eng = _engine(decode_fuse=fuse)
    reqs = [eng.submit(p, m) for p, m in stream]
    eng.run()
    eng.close()
    assert all(r.state == "finished" for r in reqs)
    for r in reqs:
        _assert_sound(r)
    widest = max(b[1] - a[1] for r in reqs
                 for a, b in zip(r.timeline, r.timeline[1:]))
    if case == "plain":
        assert widest == 1
        assert all(len(r.timeline) == len(r.tokens_out) for r in reqs)
    else:
        assert widest == 4


def test_a_request_that_ends_with_its_first_token_has_one_entry(rng):
    eng = _engine()
    req = eng.submit(list(rng.randint(0, 64, 6)), 1)
    eng.run()
    eng.close()
    assert req.state == "finished" and len(req.tokens_out) == 1
    assert req.timeline == [(req.first_token_t, 1, req.timeline[0][2])]
    assert 0.0 < req.timeline[0][2] < req.prefill_s


def test_a_request_that_decodes_alone_shows_only_the_rest_of_its_arming(rng):
    """No other admission falls in its life: its clock moves once, by what
    was left of its own ``serving/prefill`` span after the first token."""
    eng = _engine()
    req = eng.submit(list(rng.randint(0, 64, 9)), 8)
    eng.run()
    eng.close()
    clocks = [c for _, _, c in req.timeline]
    assert clocks[1] > clocks[0] and len(set(clocks[1:])) == 1
    # the first entry was stamped inside the span: part of it lay behind
    assert _stall_s(req) < req.prefill_s
    assert clocks[-1] == pytest.approx(req.prefill_s, abs=1e-12)
    assert eng.prefill_clock(0.0) == clocks[-1]


def test_a_request_decoding_while_another_is_admitted_loses_its_prefill(rng):
    """``first`` decodes alone for some cycles, then ``second`` is admitted
    beside it: between the two hand-overs around that admission ``first``'s
    clock advances by exactly ``second.prefill_s``, and ``first``'s whole
    stall is the rest of its own arming plus that."""
    eng = _engine()
    first = eng.submit(list(rng.randint(0, 64, 9)), 20)
    for _ in range(4):
        eng.step()
    own = _stall_s(first)
    assert 0.0 < own < first.prefill_s
    second = eng.submit(list(rng.randint(0, 64, 12)), 4)
    eng.run()
    eng.close()
    assert first.state == second.state == "finished"
    _assert_sound(first)
    _assert_sound(second)
    assert _stall_s(first) >= second.prefill_s
    assert _stall_s(first) == pytest.approx(own + second.prefill_s, abs=1e-9)
    steps = [b[2] - a[2] for a, b in zip(first.timeline, first.timeline[1:])]
    # the clock moved twice: after its own first token, then by the other
    assert [s for s in steps if s > 0.0] == pytest.approx(
        [own, second.prefill_s], abs=1e-9)
    # the longest interval between two of first's hand-overs holds it
    gaps = [b[0] - a[0] for a, b in zip(first.timeline, first.timeline[1:])]
    assert max(gaps) >= second.prefill_s
    # second saw no admission but its own
    assert _stall_s(second) < second.prefill_s


@pytest.mark.parametrize("how", ["deadline", "failed batch"])
def test_a_request_retired_since_the_launch_gets_no_entry(how, rng):
    """As it gets no tokens: the dispatch in flight was launched for it, and
    its timeline ends where its ``tokens_out`` does."""
    eng = _engine(slots=1)
    doomed = eng.submit(list(rng.randint(0, 64, 8)), 20, deadline_s=600.0)
    for _ in range(3):
        eng.step()
    assert eng._unread is not None and doomed.state == "running"
    had = list(doomed.timeline)
    assert len(had) == 3 == len(doomed.tokens_out)
    if how == "deadline":
        doomed.deadline_s = 0.0
        assert doomed in eng.step() and doomed.state == "timeout"
    else:
        def sync_fails(d):
            raise RuntimeError("UNAVAILABLE: injected at the sync")

        eng._sync = sync_fails
        assert eng.step() == [doomed] and doomed.state == "failed"
    assert doomed.timeline == had and len(doomed.tokens_out) == 3
    eng.close()


def test_the_resume_path_sets_prefill_s_and_moves_the_clock(rng):
    """A prefix-cache hit is an admission like any other: one
    ``serving/prefill`` span (cause ``resume``), its length the request's
    ``prefill_s`` and the clock's advance."""
    eng = _engine(num_pages=32, prefix_cache_pages=8,
                  prompt_buckets=(8, 16, 32))
    prompt = list(range(1, 18)) + [30]    # two whole pages are cacheable
    cold = eng.submit(prompt, 5)
    eng.run()
    resumes, before = eng.health()["resumes"], eng.prefill_clock(0.0)
    warm = eng.submit(prompt, 5)
    eng.run()
    assert eng.health()["resumes"] == resumes + 1
    assert warm.tokens_out == cold.tokens_out
    _assert_sound(warm)
    assert eng.prefill_clock(0.0) - before == pytest.approx(
        warm.prefill_s, abs=1e-12)
    eng.close()


def test_a_drained_run_adds_up_and_its_request_tracks_still_validate(rng):
    """After a drained run the sum of every request's ``prefill_s`` is the
    prefill clock; the two histograms observed once a request that finished
    with two tokens or more, with the values its timeline gives; and
    ``serving/trace.validate_request_spans`` passes on the same run (the
    per-request Perfetto tracks are not touched)."""
    tracer.clear_spans()
    tracer.start_tracing()
    eng = _engine(slots=3)
    n0, s0 = sm.TPOT_MS.count, sm.TPOT_MS.sum
    m0, r0 = (sm.PREFILL_STALL_MS_PER_TOKEN.count,
              sm.PREFILL_STALL_MS_PER_TOKEN.sum)
    reqs = []
    try:
        for i in range(8):
            p = list(rng.randint(0, 64, int(rng.randint(3, 16))))
            reqs.append(eng.submit(p, 1 if i == 3 else
                                   int(rng.randint(2, 8))))
        assert len(eng.run()) == 8
        clock = eng.prefill_clock(0.0)
    finally:
        eng.close()
        spans = tracer.stop_tracing()
    assert sum(r.prefill_s for r in reqs) == pytest.approx(clock, abs=1e-9)
    # ... which is the serving/prefill spans' own time, to the microsecond
    # the host tracer keeps
    recorded = sum(s["dur_us"] for s in spans
                   if s["name"] == "serving/prefill") / 1e6
    assert clock == pytest.approx(recorded, abs=2e-6 * len(reqs))
    with_gap = [r for r in reqs if len(r.tokens_out) > 1]
    assert len(with_gap) == 7
    assert sm.TPOT_MS.count - n0 == 7
    assert sm.PREFILL_STALL_MS_PER_TOKEN.count - m0 == 7
    tpot = [(r.timeline[-1][0] - r.timeline[0][0]) * 1e3
            / (len(r.tokens_out) - 1) for r in with_gap]
    stall = [_stall_s(r) * 1e3 / (len(r.tokens_out) - 1) for r in with_gap]
    assert sm.TPOT_MS.sum - s0 == pytest.approx(sum(tpot))
    assert sm.PREFILL_STALL_MS_PER_TOKEN.sum - r0 == pytest.approx(sum(stall))
    assert all(0.0 < s <= t for s, t in zip(stall, tpot))
    assert len(strace.validate_request_spans(spans, reqs)) == 8


def test_the_timeline_has_two_writers_and_no_switch():
    """It is appended in ``_finish_prefill`` and in ``_hand_over`` and
    nowhere else, always on, and the PR that brought it added no option."""
    import inspect

    from paddle_tpu.serving import engine as eng_mod

    src = inspect.getsource(eng_mod)
    assert src.count("timeline.append(") == 2
    for fn in (eng_mod.ServingEngine._finish_prefill,
               eng_mod.ServingEngine._hand_over):
        assert inspect.getsource(fn).count("timeline.append(") == 1
    params = inspect.signature(serving.ServingConfig.__init__).parameters
    assert not [p for p in params if "timeline" in p or "stall" in p]

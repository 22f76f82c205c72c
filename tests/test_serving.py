"""Serving subsystem tests: scheduler churn invariants, paged-vs-contiguous
KV-cache bit parity, ragged-vs-padded logit parity, page-pool backpressure
and flight-recorder capture (ISSUE 6 tentpole coverage)."""

import json
import os
import re

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import decoder_lm
from paddle_tpu.serving.page_pool import PagePoolExhausted
from paddle_tpu.serving.request import Request

_MODEL = None


def get_model():
    """One tiny decoder shared across tests (init cost, not compile cost —
    each engine still AOT-compiles its own step functions)."""
    global _MODEL
    if _MODEL is None:
        cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                       n_head=2, max_seq=64)
        _MODEL = decoder_lm.DecoderLM(cfg, seed=0)
    return _MODEL


def make_stream(n, rng, max_prompt=16, max_new=8, vocab=64):
    return [(list(rng.randint(0, vocab, int(rng.randint(3, max_prompt + 1)))),
             int(rng.randint(2, max_new + 1))) for _ in range(n)]


def small_config(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prompt_buckets", (16,))
    return serving.ServingConfig(**kw)


# -- scheduler ---------------------------------------------------------------

def test_scheduler_admit_retire_invariants_under_churn(rng):
    sched = serving.Scheduler(n_slots=3, max_queue=100)
    submitted, running, finished = [], {}, []
    for step in range(200):
        op = rng.randint(0, 3)
        if op == 0:
            r = sched.submit(Request([1, 2], max_new_tokens=2))
            submitted.append(r)
        elif op == 1 and sched.peek() is not None and sched.admissible_slots():
            slot = sched.admissible_slots()[rng.randint(
                0, len(sched.admissible_slots()))]
            r = sched.admit(slot)
            # FIFO: the admitted request is the oldest not-yet-started one
            expect = next(q for q in submitted
                          if q not in running.values() and q not in finished)
            assert r is expect, "admission broke FIFO order"
            assert r.slot == slot and r.state == "running"
            running[slot] = r
        elif op == 2 and running:
            slot = list(running)[rng.randint(0, len(running))]
            r = sched.retire(slot)
            assert r is running.pop(slot)
            assert r.state == "finished" and r.slot is None
            finished.append(r)
        # core invariants, every step
        assert sched.occupancy == len(running)
        assert sched.queue_depth == len(submitted) - len(running) - len(finished)
        assert {r.slot for r in sched.running()} == set(running)
    # every request is in exactly one place
    assert len(submitted) == sched.queue_depth + len(running) + len(finished)


def test_scheduler_bounded_queue_and_slot_errors():
    sched = serving.Scheduler(n_slots=1, max_queue=2)
    sched.submit(Request([1], 1))
    sched.submit(Request([1], 1))
    with pytest.raises(serving.BackpressureError):
        sched.submit(Request([1], 1))
    sched.admit(0)
    with pytest.raises(ValueError):
        sched.admit(0)  # double occupancy
    sched.retire(0)
    with pytest.raises(ValueError):
        sched.retire(0)  # empty slot


# -- page pool ---------------------------------------------------------------

def test_page_pool_accounting_and_atomic_exhaustion():
    pool = serving.PagePool(num_pages=8, page_size=16)
    assert pool.pages_needed(1) == 1 and pool.pages_needed(16) == 1
    assert pool.pages_needed(17) == 2
    a = pool.alloc(5)
    assert pool.num_used == 5 and abs(pool.utilization - 5 / 8) < 1e-9
    with pytest.raises(PagePoolExhausted):
        pool.alloc(4)  # atomic: nothing taken
    assert pool.num_free == 3
    assert isinstance(PagePoolExhausted("x"), serving.BackpressureError)
    pool.free(a)
    assert pool.num_used == 0
    with pytest.raises(ValueError):
        pool.free([a[0]])  # double free
    b = pool.alloc(8)
    assert sorted(b) == list(range(8))


# -- decode parity -----------------------------------------------------------

def drive_stream(stream, **cfg_kw):
    eng = serving.ServingEngine(get_model(), small_config(**cfg_kw))
    reqs = [eng.submit(p, m) for p, m in stream]
    done = eng.run()
    assert len(done) == len(reqs)
    return eng, reqs


def test_paged_vs_contiguous_bit_parity(rng):
    """The paged gather decode must be BIT-identical to the contiguous
    reference cache on the same request stream — tokens and logits."""
    stream = make_stream(8, rng)
    e1, r1 = drive_stream(stream, paged=True, collect_logits=True)
    e2, r2 = drive_stream(stream, paged=False, collect_logits=True)
    for a, b in zip(r1, r2):
        assert a.tokens_out == b.tokens_out
        la, lb = e1.captured_logits(a), e2.captured_logits(b)
        assert len(la) == len(lb) == len(a.tokens_out)
        for x, y in zip(la, lb):
            assert np.array_equal(x, y), "paged logits diverged bitwise"


@pytest.mark.parametrize("kernel", ["off", "interpret"])
def test_a_retired_slot_streams_nothing_and_moves_no_live_token(
        rng, attention_spy, kernel):
    """Requests with long contexts finish in slots 0 and 2 while two with
    short prompts decode on in slots 1 and 3. From then on the attention is
    given length 0 for the two retired slots (the engine's ``_len`` stays
    where their requests ended), by the gather path and by the interpreted
    kernel; and the survivors' tokens and logits are those of a run in which no other
    slot was ever used."""
    from paddle_tpu.flags import set_flag

    motif = list(rng.randint(0, 64, 3))
    leavers = [(list(rng.randint(0, 64, 16)), 2),
               (list(rng.randint(0, 64, 14)), 3)]
    stayers = [(motif * 2, 20), (motif * 3, 24)]

    def drive(stream):
        eng = serving.ServingEngine(get_model(),
                                    small_config(collect_logits=True))
        reqs = [eng.submit(p, m) for p, m in stream]
        eng.run()
        out = [(r.tokens_out, eng.captured_logits(r)) for r in reqs]
        assert eng.page_accounting_ok()
        eng.close()
        return out

    set_flag("paged_attention_kernel", kernel)
    try:
        mixed = drive([leavers[0], stayers[0], leavers[1], stayers[1]])
        attention_spy()
        alone = drive(stayers)
    finally:
        set_flag("paged_attention_kernel", "auto")
    for (toks, logits), (toks_alone, logits_alone) in zip(mixed[1::2], alone):
        assert toks == toks_alone
        for x, y in zip(logits, logits_alone):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def test_ragged_vs_padded_full_recompute_logit_parity(rng):
    """Bucket-padded prefill + incremental paged decode at mixed lengths
    must match the O(S^2) full-recompute reference on the unpadded
    prompt: same greedy tokens, logits to float tolerance."""
    model = get_model()
    stream = make_stream(4, rng)
    eng, reqs = drive_stream(stream, paged=True, collect_logits=True)
    for req in reqs:
        toks, logits = decoder_lm.reference_decode(
            model.params, model.cfg, req.prompt, req.max_new_tokens)
        assert req.tokens_out == toks
        for got, want in zip(eng.captured_logits(req), logits):
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_reference_tokens_is_reference_decode_in_one_pass(rng):
    """The teacher-forced one-pass check gives reference_decode's verdict:
    the same tokens for its own output, and a first difference at the same
    index for an output that strays."""
    model = get_model()
    for prompt, n_new in make_stream(1, rng):
        want, _ = decoder_lm.reference_decode(model.params, model.cfg,
                                              prompt, n_new)
        assert decoder_lm.reference_tokens(model.params, model.cfg, prompt,
                                           want) == want
        strayed = list(want)
        strayed[n_new // 2] = (strayed[n_new // 2] + 1) % model.cfg.vocab_size
        got = decoder_lm.reference_tokens(model.params, model.cfg, prompt,
                                          strayed)
        first = next(i for i, (a, b) in enumerate(zip(got, strayed)) if a != b)
        assert first == n_new // 2 and got[:first] == want[:first]


def test_decode_fuse_token_parity(rng):
    """Fusing k decode steps into one dispatched scan (the run_steps
    analog) must not change any emitted token."""
    stream = make_stream(6, rng)
    _, r1 = drive_stream(stream, decode_fuse=1)
    _, r4 = drive_stream(stream, decode_fuse=4)
    for a, b in zip(r1, r4):
        assert a.tokens_out == b.tokens_out


# -- backpressure + observability --------------------------------------------

def test_pool_exhaustion_queues_not_crashes(rng, monkeypatch, tmp_path):
    """An undersized page pool must degrade to queueing (admission
    backpressure) and still drain; the flight recorder captures the
    pressure event with the in-flight batch."""
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    from paddle_tpu.monitor import device as _dev, metrics as mx

    blocked0 = mx.snapshot()["serving/admission_blocked_on_pages"]["value"]
    # 4 slots but pages for only ~1.5 in-flight worst-case requests
    eng = serving.ServingEngine(get_model(), small_config(num_pages=3))
    reqs = [eng.submit(list(rng.randint(0, 64, 12)), 8) for _ in range(4)]
    saw_queued_while_running = False
    guard = 0
    while not eng.scheduler.idle():
        eng.step()
        if eng.scheduler.queue_depth and eng.scheduler.occupancy:
            saw_queued_while_running = True
        guard += 1
        assert guard < 200, "engine failed to drain under page pressure"
    assert all(r.state == "finished" for r in reqs)
    assert all(len(r.tokens_out) == r.max_new_tokens for r in reqs)
    assert saw_queued_while_running, "pool never actually backpressured"
    assert mx.snapshot()["serving/admission_blocked_on_pages"]["value"] \
        > blocked0
    assert eng.pool.num_used == 0
    fr = _dev.flight_recorder()
    events = [e for e in fr._entries
              if e.get("event") == "serving_admission_blocked"]
    assert events, "flight recorder missed the backpressure event"
    assert "batch" in events[-1] and events[-1]["need_pages"] > 0


def test_flight_recorder_captures_batch_on_decode_failure(
        rng, monkeypatch, tmp_path):
    """A decode failure is flight-dumped AND absorbed (ISSUE 7): the batch
    is FAILED with pages reclaimed, and the engine survives — fail_fast
    restores the old raise-through behavior for debugging."""
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    eng = serving.ServingEngine(get_model(), small_config())
    req = eng.submit(list(rng.randint(0, 64, 8)), 4)

    def boom(*a, **kw):
        raise RuntimeError("injected decode failure")

    eng._decode_exe[eng.cfg.decode_fuse] = boom
    done = eng.step()  # absorbed, not raised
    assert [r.id for r in done] == [req.id] and req.state == "failed"
    assert req.error and not req.pages and eng.pool.num_used == 0
    assert eng.health()["status"] == "degraded"
    dumps = [f for f in os.listdir(str(tmp_path)) if f.startswith("flight_")]
    assert dumps, "no flight dump written"
    with open(os.path.join(str(tmp_path), sorted(dumps)[-1])) as f:
        doc = json.load(f)
    assert doc["reason"] == "serving.decode"
    batches = [e for e in doc["entries"]
               if e.get("event") == "serving_inflight_batch"]
    assert batches, "dump missing the in-flight batch spec"
    spec = batches[-1]
    assert spec["slots"] and spec["slots"][0]["prompt_len"] == 8
    assert spec["layout"] == "paged"

    # fail_fast: the old contract, raise through after the dump
    eng2 = serving.ServingEngine(get_model(), small_config(fail_fast=True))
    eng2.submit(list(rng.randint(0, 64, 8)), 4)
    eng2._decode_exe[eng2.cfg.decode_fuse] = boom
    with pytest.raises(RuntimeError, match="injected decode failure"):
        eng2.step()


def test_submit_validation_and_immediate_finish(rng):
    eng = serving.ServingEngine(get_model(), small_config())
    with pytest.raises(ValueError):
        eng.submit(list(range(17)), 4)       # beyond largest bucket
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 62)            # prompt+max_new > max_seq
    # max_new_tokens=1 finishes at prefill without touching a decode slot
    req = eng.submit(list(rng.randint(0, 64, 8)), 1)
    done = eng.run()
    assert [r.id for r in done] == [req.id]
    assert len(req.tokens_out) == 1 and req.state == "finished"
    assert eng.scheduler.idle() and eng.pool.num_used == 0


def test_eos_stops_generation(rng):
    """With eos_id set to the model's (fixed-point) greedy token, requests
    stop at the first emission instead of running out max_new_tokens."""
    model = get_model()
    prompt = list(rng.randint(0, 64, 8))
    toks, _ = decoder_lm.reference_decode(model.params, model.cfg, prompt, 1)
    eng = serving.ServingEngine(model, small_config(eos_id=toks[0]))
    req = eng.submit(prompt, 8)
    eng.run()
    assert req.state == "finished"
    assert len(req.tokens_out) == 1 and req.tokens_out[0] == toks[0]


def test_graceful_drain_finishes_inflight_rejects_new(rng):
    """ISSUE 10 satellite: drain stops admitting (typed DrainingError on
    submit, queued requests shed REJECTED), finishes the in-flight
    requests, reclaims every page and closes the engine."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    reqs = [eng.submit(list(rng.randint(0, 64, 8)), 4) for _ in range(4)]
    eng.step()  # admit two into slots, two remain queued
    assert eng.scheduler.occupancy == 2 and eng.scheduler.queue_depth == 2
    eng.request_drain()
    with pytest.raises(serving.DrainingError):
        eng.submit([1, 2, 3], 2)
    summary = eng.drain(timeout_s=30.0)
    assert summary == {"finished": 2, "timed_out": 0, "failed": 0,
                       "rejected": 2}, summary
    states = sorted(r.state for r in reqs)
    assert states == ["finished", "finished", "rejected", "rejected"]
    assert eng.pool.num_used == 0 and eng.page_accounting_ok()
    assert eng._closed and eng.last_drain == summary
    # rejected requests never held slots or pages
    for r in reqs:
        if r.state == "rejected":
            assert not r.pages and r.slot is None


def test_drain_timeout_cuts_stragglers_loose(rng):
    """A drain past its budget retires the stragglers TIMEOUT — pages come
    back, the engine still closes (never hangs a rollout)."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    r1 = eng.submit(list(rng.randint(0, 64, 8)), 8)
    eng.step()
    assert r1.state == "running"
    summary = eng.drain(timeout_s=0.0)  # budget already spent
    assert summary["timed_out"] == 1 and r1.state == "timeout"
    assert not r1.pages and eng.pool.num_used == 0
    assert eng._closed


def test_drain_interrupts_run_loop(rng):
    """request_drain mid-run (the SIGTERM handler's path): the drive loop
    flips into drain at the next cycle instead of tearing down."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    reqs = [eng.submit(list(rng.randint(0, 64, 8)), 6) for _ in range(2)]
    eng.step()
    eng.request_drain()
    eng.run(max_steps=100)
    assert eng.last_drain is not None and eng.last_drain["finished"] == 2
    assert all(r.state == "finished" for r in reqs)
    assert eng._closed


def test_drain_is_idempotent(rng):
    """Double drain: the second call returns the recorded summary without
    re-running the shed/step loop (fleet respawn paths drain replicas
    that may already have drained themselves)."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    reqs = [eng.submit(list(rng.randint(0, 64, 8)), 4) for _ in range(2)]
    eng.step()  # admit into slots so drain FINISHES them (not shed)
    s1 = eng.drain(timeout_s=10.0)
    assert all(r.state == "finished" for r in reqs)
    s2 = eng.drain(timeout_s=10.0)
    assert s2 is s1, "a second drain re-ran instead of replaying"
    assert eng.last_drain is s1 and eng._closed
    assert eng.pool.num_used == 0


def test_drain_is_reentrant(rng):
    """A nested drain (signal handler / monitor thread firing while the
    drain decode loop runs) returns an in-progress snapshot instead of
    re-entering — and must NOT be recorded as the final summary."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    [eng.submit(list(rng.randint(0, 64, 8)), 4) for _ in range(2)]
    eng.step()  # admit into slots so the drain loop has work to step
    nested = []
    real_step = eng.step

    def step_and_reenter():
        nested.append(eng.drain())
        return real_step()

    eng.step = step_and_reenter
    summary = eng.drain(timeout_s=10.0)
    assert nested, "drain loop never stepped"
    for snap in nested:
        assert snap is not summary, "nested drain leaked the live summary"
        assert snap.get("finished", 0) <= summary["finished"]
    assert eng.last_drain is summary and summary["finished"] == 2


def test_close_is_idempotent_and_drain_after_close(rng):
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    r = eng.submit(list(rng.randint(0, 64, 8)), 3)
    eng.run()
    assert r.state == "finished"
    eng.close()
    eng.close()  # second close: no-op, no error
    assert eng._closed
    # drain on a closed-but-never-drained engine still produces a summary
    # exactly once (nothing in flight: all zeros) and stays idempotent
    s1 = eng.drain(timeout_s=1.0)
    assert s1["finished"] == 0 and s1["rejected"] == 0
    assert eng.drain() is s1


# -- the dispatch launched ahead (ISSUE 33) -----------------------------------
# step() launches dispatch N+1 on dispatch N's outputs, THEN reads N's tokens.

def _ahead_counts():
    from paddle_tpu.monitor import metrics as mx

    snap = mx.snapshot()
    return np.array([snap[n]["value"] for n in (
        "serving/decode_launched_ahead", "serving/decode_dispatches",
        "serving/retries", "serving/faults")])


def _toy(family):
    """``(model, check)``: a toy of the family and ``check(prompt, toks)``,
    which holds where ``toks`` is what the model's own greedy decode gives
    step by step: each token the argmax of the family's plain reference
    over the tokens before it (one teacher-forced pass)."""
    if family == "gpt2":
        model = get_model()

        def check(prompt, toks):
            assert decoder_lm.reference_tokens(
                model.params, model.cfg, prompt, toks) == toks
        return model, check
    # the sparse families' toys and references are their own test files'
    import test_kimi_k2
    import test_smallthinker

    mod = {"smallthinker": test_smallthinker, "kimi": test_kimi_k2}[family]
    model = mod.toy_model()

    def check(prompt, toks):
        first = len(prompt) - 1
        rows = mod.reference_rows(model, list(prompt) + toks[:-1],
                                  np.arange(first, first + len(toks)))
        assert list(rows.argmax(-1)) == toks
    return model, check


_TOYS = {}


@pytest.mark.parametrize("fuse", [1, 4])
@pytest.mark.parametrize("family", ["gpt2", "smallthinker", "kimi"])
def test_streams_admitted_over_several_cycles_equal_the_models_own_decode(
        family, fuse, rng):
    """Seven requests of mixed lengths through two slots: each is admitted
    behind a dispatch in flight and prefilled into pages a retired request
    gave back, and every stream is the model's own."""
    if family not in _TOYS:
        _TOYS[family] = _toy(family)
    model, check = _TOYS[family]
    vocab = model.cfg.vocab_size
    eng = serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(8, 16, 32),
        decode_fuse=fuse))
    stream = [(list(rng.randint(0, vocab, n)), m) for n, m in (
        (3, 9), (19, 5), (5, 14), (11, 2), (8, 1), (27, 11), (4, 7))]
    before = _ahead_counts()
    reqs = [eng.submit(p, m) for p, m in stream[:4]]
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(p, m) for p, m in stream[4:]]   # late arrivals
    eng.run()
    ahead, dispatches = (_ahead_counts() - before)[:2]
    assert 0 < ahead < dispatches
    assert eng.page_accounting_ok() and eng._unread is None
    eng.close()
    for (prompt, m), req in zip(stream, reqs):
        assert req.state == "finished" and len(req.tokens_out) == m
        check(prompt, req.tokens_out)


def test_one_long_request_launches_every_dispatch_but_the_first_ahead(rng):
    """Cycle k+1 launches dispatch k+1, then reads dispatch k: after j
    cycles the request holds the prefill's token and j-1 dispatches'
    tokens, and in the host tracer's record each launch begins before the
    sync of its cycle ends."""
    from paddle_tpu.monitor import tracer

    model = get_model()
    prompt = list(rng.randint(0, 64, 9))
    want, _ = decoder_lm.reference_decode(model.params, model.cfg, prompt, 12)
    eng = serving.ServingEngine(model, small_config())
    req = eng.submit(prompt, 12)
    before = _ahead_counts()
    tracer.clear_spans()
    tracer.start_tracing()
    try:
        cycles = 0
        while not eng.scheduler.idle():
            eng.step()
            cycles += 1
            if req.state == "running":
                # the sync of cycle j read dispatch j-1's outputs
                assert req.tokens_out == want[:cycles]
        spans = [s for s in tracer.get_spans() if s["cat"] == "engine"]
    finally:
        tracer.stop_tracing()
        tracer.clear_spans()
        eng.close()
    assert req.tokens_out == want
    ahead, dispatches = (_ahead_counts() - before)[:2]
    assert dispatches == 11 and ahead == dispatches - 1
    assert cycles == 12     # the last cycle reads, and launches nothing
    by_cycle = {}
    for s in spans:
        if s["name"] == "serving/step":
            by_cycle[s["args"]["cycle"]] = (s["ts_us"],
                                            s["ts_us"] + s["dur_us"])

    def of_cycle(name, k):
        lo, hi = by_cycle[k]
        return [s for s in spans if s["name"] == name
                and lo <= s["ts_us"] and s["ts_us"] + s["dur_us"] <= hi]

    assert not of_cycle("serving/decode.sync", 1)
    assert not of_cycle("serving/decode.launch", 12)
    for k in range(2, 12):
        launch, = of_cycle("serving/decode.launch", k)
        sync, = of_cycle("serving/decode.sync", k)
        assert launch["ts_us"] + launch["dur_us"] <= sync["ts_us"] \
            + sync["dur_us"] and launch["ts_us"] <= sync["ts_us"]


def test_a_deadline_that_expires_with_a_dispatch_in_flight(rng):
    """The request ends TIMEOUT with the tokens it had: the dispatch in
    flight was launched for it and gives it nothing; its pages go back
    once, and the request behind it is served whole in the same slot."""
    model = get_model()
    eng = serving.ServingEngine(model, small_config(slots=1))
    doomed = eng.submit(list(rng.randint(0, 64, 8)), 20, deadline_s=600.0)
    prompt = list(rng.randint(0, 64, 11))
    behind = eng.submit(prompt, 6)
    for _ in range(3):
        eng.step()
    assert eng._unread is not None and doomed.state == "running"
    had = list(doomed.tokens_out)
    assert len(had) == 3
    doomed.deadline_s = 0.0
    done = eng.step()
    assert doomed in done and doomed.state == "timeout"
    assert doomed.tokens_out == had and not doomed.pages
    assert eng.page_accounting_ok()
    assert behind.state == "running" and behind.slot == 0
    eng.run()
    assert doomed.tokens_out == had
    assert behind.state == "finished"
    want, _ = decoder_lm.reference_decode(model.params, model.cfg, prompt, 6)
    assert behind.tokens_out == want
    assert eng.pool.num_used == 0 and eng.page_accounting_ok()
    eng.close()


@pytest.mark.parametrize("kind", ["transient", "fatal"])
def test_a_fault_at_the_launch_with_a_dispatch_in_flight(kind, rng):
    """Injected at ``serving.decode`` while the dispatch before is unread.
    Transient: the launch is retried, every stream whole and nothing
    doubled. Fatal: the unread dispatch's tokens are handed over, then the
    batch FAILS once, and the queue is served after."""
    from paddle_tpu.reliability import FaultPlan, faults

    model = get_model()
    stream = [(list(rng.randint(0, 64, n)), m)
              for n, m in ((7, 9), (12, 6), (5, 8))]
    want = [decoder_lm.reference_decode(model.params, model.cfg, p, m)[0]
            for p, m in stream]
    eng = serving.ServingEngine(model, small_config(slots=2,
                                                    decode_retries=1))
    reqs = [eng.submit(p, m) for p, m in stream]
    eng.step()
    eng.step()
    assert eng._unread is not None
    assert [len(r.tokens_out) for r in reqs] == [2, 2, 0]
    before = _ahead_counts()
    with FaultPlan([faults.FaultSpec("serving.decode", kind, at=1)]):
        done = eng.step()
    _, _, retries, absorbed = _ahead_counts() - before
    if kind == "transient":
        assert (retries, absorbed) == (1, 0) and done == []
        assert [len(r.tokens_out) for r in reqs] == [3, 3, 0]
    else:
        assert (retries, absorbed) == (0, 1)
        assert done == reqs[:2] and all(r.state == "failed" for r in done)
        # no token lost: the dispatch in flight was read before the batch
        # failed
        assert [r.tokens_out for r in done] == [w[:3] for w in want[:2]]
        assert eng._unread is None and eng.page_accounting_ok()
    eng.run()
    eng.close()
    served = reqs if kind == "transient" else reqs[2:]
    assert all(r.state == "finished" for r in served)
    assert [r.tokens_out for r in served] == want[-len(served):]
    assert eng.pool.num_used == 0 and eng.health()["status"] == "ok"


def test_a_failure_that_surfaces_at_the_read_abandons_the_one_launched_ahead(
        rng):
    """Dispatch N fails at host materialization, after N+1 was launched on
    its outputs: N+1 is abandoned unread and the state goes back to what N
    was launched on. The cache N was given is donated and gone, so the
    ladder does what it always did there: no retry, the batch FAILS with
    the tokens it had, the cache is made anew and the queue is served."""
    model = get_model()
    stream = [(list(rng.randint(0, 64, n)), m) for n, m in ((9, 10), (6, 5))]
    want = [decoder_lm.reference_decode(model.params, model.cfg, p, m)[0]
            for p, m in stream]
    eng = serving.ServingEngine(model, small_config(slots=1))
    reqs = [eng.submit(p, m) for p, m in stream]
    eng.step()
    eng.step()
    real_sync, calls = eng._sync, []

    def sync_fails_once(d):
        calls.append(d)
        if len(calls) == 1:
            raise RuntimeError("UNAVAILABLE: injected at the sync")
        return real_sync(d)

    eng._sync = sync_fails_once
    before = _ahead_counts()
    assert eng.step() == reqs[:1]
    assert list(_ahead_counts() - before) == [1, 0, 0, 1]   # no retry
    assert reqs[0].state == "failed" and reqs[0].tokens_out == want[0][:2]
    assert eng._unread is None and eng.page_accounting_ok()
    eng.run()
    eng.close()
    assert len(calls) > 1 and reqs[1].state == "finished"
    assert reqs[1].tokens_out == want[1] and eng.health()["status"] == "ok"


@pytest.mark.parametrize("how", ["run", "drain", "close", "exit"])
def test_no_dispatch_is_left_unread_and_no_token_lost(how, rng):
    model = get_model()
    prompt = list(rng.randint(0, 64, 9))
    want, _ = decoder_lm.reference_decode(model.params, model.cfg, prompt, 9)
    eng = serving.ServingEngine(model, small_config())
    req = eng.submit(prompt, 9)
    if how == "run":
        assert eng.run(max_steps=3) == []
    else:
        for _ in range(3):
            eng.step()
        assert eng._unread is not None and len(req.tokens_out) == 3
        if how == "drain":
            assert eng.drain(timeout_s=0.0)["timed_out"] == 1
        elif how == "close":
            eng.close()
        else:
            with eng:
                pass
    # the prefill's token and all three dispatches'
    assert eng._unread is None and req.tokens_out == want[:4]
    if how == "run":
        eng.run()
        assert req.tokens_out == want
    assert eng.page_accounting_ok()
    eng.close()


# -- an admission is one device program and one read -------------------------

_ADMISSION_PATH = ("_prefill", "_prefill_cold", "_prefill_from_prefix",
                   "_admit_on_device", "_first_token", "_finish_prefill")


def _admission_engine(cache):
    """``(engine, stream)`` over each kind of cache an admission writes:
    ``stream`` a list of ``(prompt, max_new_tokens)`` whose admissions fall
    in several cycles (two slots)."""
    family = {"grouped": "smallthinker", "latent": "kimi"}.get(cache, "gpt2")
    if family not in _TOYS:
        _TOYS[family] = _toy(family)
    model = _TOYS[family][0]
    kw = {"contiguous": {"paged": False},
          "resume": {"num_pages": 32, "prefix_cache_pages": 8}}.get(cache, {})
    eng = serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(8, 16, 32), **kw))
    r = np.random.RandomState(7)
    vocab = model.cfg.vocab_size
    stream = [(list(r.randint(0, vocab, n)), m)
              for n, m in ((5, 6), (19, 3), (11, 1), (27, 4), (4, 5))]
    if cache == "resume":       # one prompt again and again: hits after one
        stream = [(stream[1][0], m) for _, m in stream]
    return eng, stream


@pytest.mark.parametrize("cache", ["paged", "grouped", "latent",
                                   "contiguous", "resume"])
def test_an_admission_launches_one_program(cache):
    """``serving/admission_programs`` moves by exactly one an admission on
    every cache the engine writes at admission (one group, two groups, the
    latent rows, the contiguous layout) and on a prefix-cache resume, whose
    page-table write and row copies ride in the resume executable; and
    ``serving/admission_ms`` observes each request's ``prefill_s``."""
    from paddle_tpu.serving import metrics as sm

    eng, stream = _admission_engine(cache)
    assert len(eng.pools) == {"grouped": 2, "contiguous": 0}.get(cache, 1)
    before = (sm.ADMISSION_PROGRAMS.value, sm.ADMISSION_MS.count,
              sm.ADMISSION_MS.sum, sm.PREFILL_MS.count)
    reqs = []
    for prompt, m in stream:    # one at a time: each a cycle of its own
        reqs.append(eng.submit(prompt, m))
        n0 = sm.ADMISSION_PROGRAMS.value
        eng.step()
        assert sm.ADMISSION_PROGRAMS.value - n0 == 1
        eng.run()
    eng.close()
    assert all(r.state == "finished" and len(r.tokens_out) == m
               for r, (_, m) in zip(reqs, stream))
    admissions = eng._prefills + eng._resumes
    assert admissions == len(stream)
    assert eng._resumes == (len(stream) - 1 if cache == "resume" else 0)
    assert sm.ADMISSION_PROGRAMS.value - before[0] == admissions
    assert sm.ADMISSION_MS.count - before[1] == admissions
    assert sm.PREFILL_MS.count - before[3] == admissions
    assert sm.ADMISSION_MS.sum - before[2] == pytest.approx(
        sum(r.prefill_s for r in reqs) * 1e3)
    if cache == "resume":       # a hit serves the cold prefill's stream
        assert all(r.tokens_out == reqs[0].tokens_out[:len(r.tokens_out)]
                   for r in reqs)


@pytest.mark.parametrize("cache", ["paged", "resume"])
def test_prefill_rows_count_the_prompts_and_their_buckets(cache):
    """``serving/prefill_rows.prompt`` and ``.bucket`` rise, a COLD
    prefill, by the prompt's rows and by the rows of the bucket it ran in
    (5, 19, 11, 27 and 4 rows in buckets of 8, 32, 16, 32 and 8: two
    thirds of what the prefills computed a row for was a prompt's); a
    prefix-cache resume counts into neither."""
    from paddle_tpu.serving import metrics as sm

    eng, stream = _admission_engine(cache)
    before = sm.PREFILL_ROWS_PROMPT.value, sm.PREFILL_ROWS_BUCKET.value
    for prompt, m in stream:
        eng.submit(prompt, m)
        eng.run()
    eng.close()
    rows = (sm.PREFILL_ROWS_PROMPT.value - before[0],
            sm.PREFILL_ROWS_BUCKET.value - before[1])
    assert rows == ((19, 32) if cache == "resume" else (66, 96))
    assert eng._prefills == (1 if cache == "resume" else 5)


def test_the_admission_path_holds_no_eager_device_call():
    """Between a ``serving/prefill`` span's open and close the engine calls
    one executable and reads one token: none of the path's functions
    touches ``jnp`` or an ``.at[...]`` update, and the executable's
    arguments are built with numpy. No option selects another path."""
    import inspect

    for name in _ADMISSION_PATH:
        src = inspect.getsource(getattr(serving.ServingEngine, name))
        assert "jnp." not in src and ".at[" not in src, name
    both = "".join(inspect.getsource(getattr(serving.ServingEngine, name))
                   for name in ("_prefill_cold", "_prefill_from_prefix"))
    assert both.count("self._admit_on_device(") == 2
    assert inspect.getsource(
        serving.ServingEngine._admit_on_device).count("exe(") == 1
    params = inspect.signature(serving.ServingConfig.__init__).parameters
    assert not [p for p in params if "arm" in p or "admission" in p]


# recorded on the parent of the PR that moved the arming into the prefill
# executable (6fd5412), where ten programs wrote what one writes now:
# RandomState(40) prompts of 3, 19, 5, 11, 8, 27, 4 tokens through two slots
_RECORDED = [
    ((9, 0.0, 0, None), [23] * 9),
    ((5, 0.8, 0, 11), [10, 12, 57, 34, 11]),
    ((14, 1.3, 5, 12),
     [15, 57, 1, 3, 60, 42, 39, 31, 58, 5, 24, 41, 41, 40]),
    ((2, 0.0, 0, None), [7, 7]),
    ((1, 0.7, 3, 13), [55]),
    ((11, 0.0, 0, None), [24] * 11),
    ((7, 1.0, 8, 14), [47, 35, 54, 62, 62, 8, 52]),
]


@pytest.mark.parametrize("fuse", [1, 4])
def test_greedy_and_sampled_streams_are_the_recorded_parents(fuse):
    """Greedy and sampled (``temperature``, ``top_k``, ``seed``) requests,
    admitted while a decode dispatch is unread: every token is the one the
    parent served, at ``decode_fuse`` 1 and 4."""
    r = np.random.RandomState(40)
    prompts = [list(map(int, r.randint(0, 64, n)))
               for n in (3, 19, 5, 11, 8, 27, 4)]
    eng = serving.ServingEngine(get_model(), serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(8, 16, 32),
        decode_fuse=fuse))

    def submit(i):
        (m, temp, top_k, seed), _ = _RECORDED[i]
        return eng.submit(prompts[i], m, temperature=temp, top_k=top_k,
                          seed=seed)

    reqs = [submit(i) for i in range(4)]
    unread_at_admission = 0
    for _ in range(3):
        unread_at_admission += eng._unread is not None \
            and eng.scheduler.queue_depth > 0
        eng.step()
    reqs += [submit(i) for i in range(4, 7)]
    while not eng.scheduler.idle():
        unread_at_admission += eng._unread is not None \
            and eng.scheduler.queue_depth > 0
        eng.step()
    eng.close()
    assert unread_at_admission >= 2
    assert [q.tokens_out for q in reqs] == [want for _, want in _RECORDED]


@pytest.mark.parametrize("ends", ["eos", "max_new_tokens"])
def test_a_request_that_ends_at_its_first_token_is_armed_not_live(
        ends, rng, attention_spy):
    """The executable arms the slot of a request that ends at its first
    token (EOS, or ``max_new_tokens == 1``) with ``active`` false: the
    slot keeps the prompt's length (the attention is handed the context a
    live slot would have, its own row included), every later dispatch is
    given 0 rows for it and emits nothing there, and the request beside it
    decodes its own stream."""
    model = get_model()
    ender = list(rng.randint(0, 64, 8))
    stayer = list(rng.randint(0, 64, 5))
    first, _ = decoder_lm.reference_decode(model.params, model.cfg, ender, 1)
    eos = first[0] if ends == "eos" else None
    eng = serving.ServingEngine(model, small_config(eos_id=eos))
    gone = eng.submit(ender, 6 if ends == "eos" else 1)
    # (with an EOS set the stayer's own stream must not hold it)
    want, _ = decoder_lm.reference_decode(model.params, model.cfg, stayer, 12)
    n = want.index(eos) + 1 if eos in want else 12
    stays = eng.submit(stayer, n)
    done = eng.step()
    assert done == [gone] and gone.state == "finished"
    assert gone.tokens_out == first
    state = {name: np.asarray(getattr(eng, name))
             for name in ("_active", "_len", "_gen")}
    assert not state["_active"][0] and state["_active"][1] == (n > 1)
    assert state["_len"][0] == len(ender) and state["_gen"][0] == 1
    eng.run()
    eng.close()
    assert stays.tokens_out == want[:n] and len(gone.tokens_out) == 1
    for active, kept, rows in attention_spy():
        assert not active[0] and kept[0] == len(ender) + 1
        assert not rows[0].any()
    assert eng.page_accounting_ok() and eng.pool.num_used == 0


def test_a_prefill_that_raises_leaves_the_slot_state_and_the_table(rng):
    """The eight state arrays are reassigned only after the executable
    returned, and the page table is written inside it: a call that raises
    leaves all nine as they were, and the prefill that follows serves the
    model's own stream."""
    from paddle_tpu.serving import engine as eng_mod

    model = get_model()
    eng = serving.ServingEngine(model, small_config(slots=2))
    first = eng.submit(list(rng.randint(0, 64, 9)), 12)
    eng.step()
    eng.step()
    held = eng._slot_state()
    table = np.asarray(eng._cache["pt"]).copy()
    real = eng._get_prefill_exe(16)

    def raises(*args):
        raise RuntimeError("injected before the executable ran")

    eng._prefill_exe[16] = raises
    prompt = list(rng.randint(0, 64, 6))
    late = eng.submit(prompt, 4)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    assert late.slot == 1 and late.tokens_out == []
    assert all(a is b for a, b in zip(eng._slot_state(), held))
    assert len(held) == len(eng_mod._SLOT_STATE) == 8
    assert np.array_equal(np.asarray(eng._cache["pt"]), table)
    assert late.prefill_s is not None       # the span closed all the same
    # the slot's tenant never ran: vacate it, then serve the prompt again
    eng._retire(1, state="failed")
    eng._prefill_exe[16] = real
    again = eng.submit(prompt, 4)
    eng.run()
    eng.close()
    want = [decoder_lm.reference_decode(model.params, model.cfg, p, m)[0]
            for p, m in ((first.prompt, 12), (prompt, 4))]
    assert [first.tokens_out, again.tokens_out] == want
    assert eng.page_accounting_ok() and eng.pool.num_used == 0


def test_a_dispatch_is_not_retried_across_an_arming(rng):
    """``_roll_back``'s rule reads as before: from the state a dispatch
    was launched on it can be launched again, unless a slot was armed
    since (the snapshot knows nothing of the new tenant)."""
    eng = serving.ServingEngine(get_model(), small_config(slots=2))
    eng.submit(list(rng.randint(0, 64, 7)), 12)
    eng.step()
    eng.step()

    def rolled_back():
        d = eng._unread
        live = (eng._cache, eng._len, eng._tok, eng._active, eng._gen)
        return eng._roll_back(d._replace(snap=live))

    assert rolled_back()
    late = eng.submit(list(rng.randint(0, 64, 5)), 3)
    assert eng._admit() == [] and late.slot == 1
    assert np.asarray(eng._active).tolist() == [True, True]
    assert not rolled_back()
    eng.run()
    eng.close()
    assert late.state == "finished" and len(late.tokens_out) == 3


# -- cache layers a layer: a looped model's steps ----------------------------

def _stepped_cache(layout):
    from paddle_tpu.serving.kv_cache import ContiguousKVCache, PagedKVCache

    if layout == "paged":
        ops = PagedKVCache(2, 2, 8, 2, 32, 8, 8, cache_steps=3)
        state = ops.set_page_table(ops.init_state(), 0,
                                   ops.prompt_dest([5, 2, 7, 0]))
        state = ops.set_page_table(state, 1, ops.prompt_dest([1, 3, 4, 6]))
        import jax.numpy as jnp

        return ops, state, [jnp.asarray(ops.prompt_dest([5, 2, 7, 0])),
                            jnp.asarray(ops.prompt_dest([1, 3, 4, 6]))]
    ops = ContiguousKVCache(2, 2, 8, 2, 32, cache_steps=3)
    return ops, ops.init_state(), [ops.prompt_dest(0), ops.prompt_dest(1)]


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_a_cache_step_as_an_int_and_as_a_traced_scalar_are_the_same_rows(
        rng, layout):
    """``step=`` says which of a layer's cache layers a call means: pool
    layer ``layer * cache_steps + step``. Written a step at a time, by
    Python ints and under ``jax.jit`` by a traced int32 scalar, the pools
    are bit-equal, each step's rows sit in its own pool layer, and
    ``context`` and ``decode_attention`` read exactly them."""
    import jax
    import jax.numpy as jnp

    ops, state, dests = _stepped_cache(layout)
    assert state["k"].shape[0] == 2 * 3
    rows = {(layer, t): [jnp.asarray(rng.randn(11, 2, 8), jnp.float32)
                         for _ in range(2)]
            for layer in range(2) for t in range(3)}
    new = {key: [jnp.asarray(rng.randn(2, 2, 8), jnp.float32)
                 for _ in range(2)] for key in rows}
    pos, active = jnp.asarray([11, 3]), jnp.asarray([True, False])

    def fill(state, step_of):
        for (layer, t), (k, v) in rows.items():
            state = ops.write_prompt(state, layer, k, v, dests[0], 11,
                                     step=step_of(t))
            state = ops.write_token(state, layer, *new[layer, t], pos,
                                    active, step=step_of(t))
        return state

    by_int = fill(state, lambda t: t)
    by_traced = state
    for (layer, t), (k, v) in rows.items():
        by_traced = jax.jit(
            lambda s, t, layer=layer, k=k, v=v, kn=new[layer, t]:
            ops.write_token(ops.write_prompt(s, layer, k, v, dests[0], 11,
                                             step=t),
                            layer, *kn, pos, active, step=t))(
                by_traced, jnp.int32(t))
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(by_int[key]),
                                      np.asarray(by_traced[key]))
    q = jnp.asarray(rng.randn(2, 2, 8), jnp.float32)
    for (layer, t), (k, v) in rows.items():
        ctx_k, _ = ops.context(by_int, layer, step=t)
        np.testing.assert_array_equal(np.asarray(ctx_k[0, :11]),
                                      np.asarray(k))
        np.testing.assert_array_equal(np.asarray(ctx_k[0, 11]),
                                      np.asarray(new[layer, t][0][0]))
        want = ops.decode_attention(by_int, layer, q, pos + 1, active,
                                    step=t)
        got = jax.jit(lambda s, t, layer=layer: ops.decode_attention(
            s, layer, q, pos + 1, active, step=t))(by_int, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    # one cache layer a layer: no step to name, and the layer stays an int
    plain = type(ops)(2, 2, 8, 2, 32, *((8, 8) if layout == "paged" else ()))
    assert plain._pool_layer(1, None) == 1


def test_a_snapshot_of_a_stepped_pool_round_trips_and_an_old_one_loads_as_one(
        rng):
    """``export_pages``/``import_pages`` carry every cache layer of a page
    (``n_layer x cache_steps``) and say so in the payload's ``cache_steps``;
    a payload from before the key is one cache layer a layer, and one of
    another number of steps is refused."""
    import jax.numpy as jnp

    from paddle_tpu.serving.kv_cache import PagedKVCache

    src = PagedKVCache(2, 2, 8, 2, 32, 8, 8, cache_steps=2)
    state = src.init_state()
    state = {**state,
             "k": jnp.asarray(rng.randn(*state["k"].shape), jnp.float32),
             "v": jnp.asarray(rng.randn(*state["v"].shape), jnp.float32)}
    meta, blobs = src.export_pages(state, [1, 3])
    assert meta["cache_steps"] == 2 and meta["n_layer"] == 2
    assert len(blobs[0]) == 4 * 16 * 16 * 4     # 2 x 2 layers of 2 pages
    dst = PagedKVCache(2, 2, 8, 2, 32, 8, 8, cache_steps=2)
    landed = dst.import_pages(dst.init_state(), [4, 2], meta, blobs)
    assert dst.export_pages(landed, [4, 2])[1] == blobs
    rows = np.r_[8:16, 24:32]
    np.testing.assert_array_equal(
        np.asarray(landed["k"])[:, np.r_[32:40, 16:24]],
        np.asarray(state["k"])[:, rows])
    one = PagedKVCache(2, 2, 8, 2, 32, 8, 8)
    with pytest.raises(ValueError, match="geometry mismatch"):
        one.import_pages(one.init_state(), [4, 2], meta, blobs)
    # a payload written before the key existed: one cache layer a layer
    old_meta, old_blobs = one.export_pages(one.init_state(), [0])
    assert old_meta.pop("cache_steps") == 1
    one.import_pages(one.init_state(), [5], old_meta, old_blobs)
    with pytest.raises(ValueError, match="geometry mismatch"):
        dst.import_pages(dst.init_state(), [5], old_meta, old_blobs)


# -- a compacting cache group: windows replaced by a summary a chunk ----------

def _compacting_model(seed=3):
    from paddle_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(
        vocab_size=40, n_layer=2, d_model=32, n_head=4, n_kv_head=4, d_ff=48,
        window=32, chunk=4, n_pred_heads=3, max_seq=160)
    return evabyte.EvaByteLM(cfg, params=evabyte.init_params(cfg, seed))


def test_a_compacting_group_is_served_end_to_end(rng):
    """The toy byte-level model through ``submit``/``run``: admission
    reserves the pages the cache's map says (not ``n / page_size``), they
    return at retirement, ``serving/eva_windows_closed`` and
    ``serving/eva_chunks_closed`` count what the lengths imply, the rows a
    layer read are of both kinds, and every head's logits a decoded row
    ride out as the probe."""
    from paddle_tpu.serving import metrics as sm

    model = _compacting_model()
    cfg = serving.ServingConfig(slots=2, page_size=4, max_seq=160,
                                prompt_buckets=(32, 64, 128),
                                group_pages={"eva": 60})
    # (prompt, new tokens): decode consumes positions [n, n + m - 1)
    plan = [(20, 50), (70, 30), (100, 28), (5, 3)]
    windows0 = sm.EVA_WINDOWS_CLOSED.sum
    chunks0 = sm.EVA_CHUNKS_CLOSED.sum
    summary = sm.attn_rows_read("eva_summary")
    exact = sm.attn_rows_read("eva_exact")
    context = sm.attn_rows_context("eva")
    before = (summary.sum, exact.sum, context.sum)
    with serving.ServingEngine(model, cfg) as eng:
        ops = eng.cache_ops
        assert [g.chunk for g in ops.groups] == [4]
        assert eng.pool.name == "eva" and ops.pages_per_slot == 20
        reqs = [eng.submit(rng.randint(0, 40, n).tolist(), m)
                for n, m in plan]
        eng.step()
        # two slots admitted: 70 and 58 positions, by the map (the closed
        # windows' 8 summaries, a window's 32 rows, 2 pages of waiting)
        assert [len(r.pages) for r in reqs[:2]] == [
            ops.pages_needed(0, 70), ops.pages_needed(0, 100)]
        assert [len(r.pages) for r in reqs[:2]] == [14, 16]
        assert eng.pool.num_used == 30
        assert sm.pages_used("eva").value == 30
        eng.run()
        assert all(r.state == "finished" for r in reqs)
        assert [len(r.tokens_out) for r in reqs] == [m for _, m in plan]
        assert eng.pool.num_used == 0 and eng.page_accounting_ok()
        tenants, stats = eng.last_decode_stats
        assert stats["eva_head_logits"].shape[-2:] == (3, 40)
    # a decode step at position p closes p's chunk where (p + 1) % 4 == 0
    # and its window where (p + 1) % 32 == 0
    consumed = [range(n, n + m - 1) for n, m in plan]
    assert sm.EVA_WINDOWS_CLOSED.sum - windows0 == sum(
        (p + 1) % 32 == 0 for r in consumed for p in r) == 3
    assert sm.EVA_CHUNKS_CLOSED.sum - chunks0 == sum(
        (p + 1) % 4 == 0 for r in consumed for p in r)
    assert summary.sum - before[0] == sum(
        8 * (p // 32) for r in consumed for p in r)
    assert exact.sum - before[1] == sum(
        p % 32 + 1 for r in consumed for p in r)
    assert context.sum - before[2] == sum(p + 1 for r in consumed for p in r)


@pytest.mark.parametrize("what,over", [
    ("the prefix cache", dict(prefix_cache_pages=4)),
    ("the int8 KV pool", dict(kv_dtype="int8")),
    ("the contiguous layout", dict(paged=False))])
def test_what_a_compacting_group_refuses_says_why(what, over):
    """At construction, with the reason: a compacted window cannot be
    rolled back and a page no longer holds the positions its place says."""
    model = _compacting_model()
    with pytest.raises(ValueError, match="%s.* is not supported over .*"
                       "compact" % re.escape(what)):
        serving.ServingEngine(model, serving.ServingConfig(
            slots=2, page_size=4, max_seq=160, **over))
    with serving.ServingEngine(model, serving.ServingConfig(
            slots=2, page_size=4, max_seq=160)) as eng:
        for call in (lambda: eng.cache_ops.export_pages(eng._cache, [1]),
                     lambda: eng.cache_ops.import_pages(eng._cache, [1], {},
                                                        [])):
            with pytest.raises(ValueError, match="compacting group"):
                call()


def test_a_row_choosing_latent_model_is_served_end_to_end(rng):
    """The toy DeepSeek-V3.2 through ``submit``/``run``: admission reserves
    ONE set of pages for the latent rows and the index keys beside them,
    they return at retirement, and the counters read what the lengths
    imply: a decode step at position p scores p + 1 rows of its slot and
    reads ``min(p + 1, index_topk)`` of them, in the first layer."""
    import test_deepseek_v32 as toy
    from paddle_tpu.serving import metrics as sm

    model = toy.toy_model()
    cfg = serving.ServingConfig(slots=2, page_size=16, max_seq=256,
                                prompt_buckets=(128,),
                                group_pages={"latent_sparse": 20})
    # (prompt, new tokens): decode consumes positions [n, n + m - 1)
    plan = [(5, 20), (70, 12), (100, 9), (12, 3)]
    read = sm.attn_rows_read("latent_sparse")
    context = sm.attn_rows_context("latent_sparse")
    before = (read.sum, context.sum, sm.INDEX_ROWS_SCORED.sum,
              sm.MOE_GROUPS_KEPT_WITH_HELD.count, read.count)
    with serving.ServingEngine(model, cfg) as eng:
        ops = eng.cache_ops
        assert eng.pool.name == "latent_sparse" and ops.index == (1, 16, 16)
        assert set(eng._cache) == {"c", "ik", "pt"}
        assert eng._cache["ik"].shape == (4, 20, 16, 16)
        reqs = [eng.submit(rng.randint(0, 96, n).tolist(), m)
                for n, m in plan]
        eng.step()
        # 25 and 82 rows: 2 and 6 pages, in whole runs of 4
        assert [len(r.pages) for r in reqs[:2]] == [4, 8]
        assert eng.pool.num_used == sm.pages_used("latent_sparse").value == 12
        assert sm.pages_padding("latent_sparse").value == 4
        eng.run()
        assert all(r.state == "finished" for r in reqs)
        assert [len(r.tokens_out) for r in reqs] == [m for _, m in plan]
        assert eng.pool.num_used == 0 and eng.page_accounting_ok()
        _, stats = eng.last_decode_stats
        assert np.asarray(stats["dsa_probe"]).shape[-1] == 1 + 16
    consumed = [range(n, n + m - 1) for n, m in plan]
    assert context.sum - before[1] == sm.INDEX_ROWS_SCORED.sum - before[2] \
        == sum(p + 1 for r in consumed for p in r)
    assert read.sum - before[0] == sum(min(p + 1, 16)
                                       for r in consumed for p in r)
    # an observation an expert layer (3 of the toy's 4) a decode step
    assert sm.MOE_GROUPS_KEPT_WITH_HELD.count - before[3] \
        == 3 * (read.count - before[4])

"""Fault injection + crash-safe training/serving tests (ISSUE 7).

Covers: the FaultPlan grammar and classification oracle, the subprocess
SIGTERM kill/resume drill (bit-identical loss trajectory), the injected-NaN
fault driving the CHECK_NUMERICS=2 watchdog end-to-end, run_steps' typed
feed errors, checkpoint durability satellites (torn-restore fallback,
trainer-0-only rotation), and the serving page-accounting invariant across
every retirement path (EOS / max_new / timeout / decode failure)."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.reliability import (FaultPlan, InjectedResourceExhausted,
                                    TransientFault, classify, faults)

_RUNNER = os.path.join(os.path.dirname(__file__), "reliability_runner.py")


# -- fault plan framework -----------------------------------------------------

def test_fault_plan_grammar_roundtrip():
    plan = FaultPlan.parse(
        "executor.dispatch@2=transient:3;serving.decode@1=latency:1:25;"
        "io.save_checkpoint@4=fatal")
    assert [s.site for s in plan.specs] == [
        "executor.dispatch", "serving.decode", "io.save_checkpoint"]
    assert plan.specs[0].times == 3
    assert plan.specs[1].ms == 25.0
    # visit counting: fires on visits [at, at+times)
    assert plan.poll("executor.dispatch") is None
    for _ in range(3):
        assert plan.poll("executor.dispatch").kind == "transient"
    assert plan.poll("executor.dispatch") is None
    assert plan.fired == 3 and plan.hits("executor.dispatch") == 5


def test_fault_plan_rejects_bad_entries():
    for bad in ("nonsense", "bogus.site@1=transient",
                "executor.dispatch@0=transient",
                "executor.dispatch@1=made_up_kind"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_env_fault_plan_and_fast_path(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FAULT_PLAN", raising=False)
    faults.clear()
    assert faults.current_plan() is None
    assert faults.poll("executor.dispatch") is None  # the no-plan fast path
    monkeypatch.setenv("PADDLE_TPU_FAULT_PLAN",
                       "executor.compile@1=transient")
    plan = faults.current_plan()
    assert plan is not None and plan.specs[0].site == "executor.compile"
    assert faults.current_plan() is plan  # cached per env value
    with pytest.raises(TransientFault):
        faults.fire("executor.compile")


def test_probabilistic_specs_are_seed_deterministic():
    """FaultSpec(p=...) fires per-visit from the plan's seeded RNG — the
    same seed replays the same firing schedule (the 'seedable' contract)."""
    def schedule(seed):
        plan = FaultPlan([faults.FaultSpec("executor.dispatch", "transient",
                                           p=0.5)], seed=seed)
        return [plan.poll("executor.dispatch") is not None
                for _ in range(32)]

    a, b = schedule(7), schedule(7)
    assert a == b, "same seed must replay the same schedule"
    assert any(a) and not all(a), a  # p=0.5 over 32 visits: mixed outcomes
    assert schedule(8) != a  # and the seed actually matters


def test_classify_oracle():
    from paddle_tpu.serving import BackpressureError, PagePoolExhausted

    assert classify(TransientFault("x")) == "transient"
    assert classify(InjectedResourceExhausted("RESOURCE_EXHAUSTED")) == "fatal"
    assert classify(BackpressureError("full")) == "backpressure"
    assert classify(PagePoolExhausted("no pages")) == "backpressure"
    assert classify(RuntimeError("UNAVAILABLE: connection reset")) == \
        "transient"
    assert classify(KeyboardInterrupt()) == "preemption"
    assert classify(ValueError("shape mismatch")) == "fatal"


# -- the subprocess kill/resume drill -----------------------------------------

def _run_runner(ckpt, total=10, fault_plan=None, timeout=120):
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    env.pop("PADDLE_TPU_FAULT_PLAN", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if fault_plan:
        env["PADDLE_TPU_FAULT_PLAN"] = fault_plan
    p = subprocess.run([sys.executable, _RUNNER, ckpt, str(total)], env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       text=True, timeout=timeout)
    losses = {int(s): h for s, h in
              re.findall(r"SUP_STEP:(\d+):([0-9a-f]{8})", p.stdout)}
    return p, losses


def test_sigterm_kill_resume_bit_identical(tmp_path):
    """SIGTERM mid-run_supervised (delivered through the real signal path
    by the fault plan's preempt kind): marked exit code 42, rotating
    checkpoint written; a restart resumes and the stitched loss trajectory
    is BIT-identical to an uninterrupted run — dropout masks included."""
    ref, ref_losses = _run_runner(str(tmp_path / "ref"))
    assert ref.returncode == 0, ref.stdout
    assert sorted(ref_losses) == list(range(10)), ref.stdout

    ck = str(tmp_path / "ck")
    first, first_losses = _run_runner(
        ck, fault_plan="executor.dispatch@3=preempt")
    assert first.returncode == 42, first.stdout  # EXIT_PREEMPTED
    # the SIGTERM lands mid-run; the in-flight fused chunk (2 steps) still
    # completes, so the covered prefix is a non-empty even-length range
    k = len(first_losses)
    assert 0 < k < 10 and k % 2 == 0, first.stdout
    assert sorted(first_losses) == list(range(k)), first.stdout
    assert "SUP_RESUMED" not in first.stdout

    second, second_losses = _run_runner(ck)
    assert second.returncode == 0, second.stdout
    assert ("SUP_RESUMED:%d" % k) in second.stdout, second.stdout
    assert sorted(second_losses) == list(range(k, 10)), second.stdout

    stitched = dict(first_losses)
    stitched.update(second_losses)
    assert stitched == ref_losses, \
        "kill/resume trajectory diverged from the uninterrupted run"


def test_supervisor_transient_retry_inprocess(tmp_path):
    from paddle_tpu.reliability import run_supervised

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=3))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    def source(start):
        def gen():
            s = start
            while True:
                r = np.random.RandomState(s)
                yield {"x": r.randn(4, 4).astype("float32")}
                s += 1
        return gen()

    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "transient",
                                       at=2, times=2)])
    with plan:
        res = run_supervised(exe, main, source, 6, [loss],
                             checkpoint_dir=str(tmp_path / "ck"),
                             fetch_every=2, backoff_s=0.0,
                             exit_on_preempt=False)
    assert res.steps_done == 6 and res.retries == 2, res

    # a fatal fault re-raises after recording the supervisor event
    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "fatal", at=1)])
    with plan:
        with pytest.raises(faults.InjectedFault):
            run_supervised(exe, main, source, 2, [loss],
                           checkpoint_dir=str(tmp_path / "ck2"),
                           exit_on_preempt=False)


# -- injected NaN -> numerics watchdog ----------------------------------------

def test_injected_nan_watchdog_names_originating_op(monkeypatch):
    """The 'nan' fault poisons a feed; the CHECK_NUMERICS=2 guarded step
    must attribute the first non-finite output to the originating op by
    <slot>:<type> — the full watchdog path driven end-to-end by a fault."""
    from paddle_tpu.core.enforce import EnforceNotMet

    monkeypatch.setenv("PADDLE_TPU_CHECK_NUMERICS", "2")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=3, act="relu"))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xs = np.ones((2, 4), "float32")
    exe.run(main, feed={"x": xs}, fetch_list=[loss])  # clean step
    # the plan is installed AFTER the clean step, so the poisoned run is
    # its first executor.dispatch visit
    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "nan", at=1)])
    with plan:
        with pytest.raises(EnforceNotMet,
                           match=r"first produced by op \d+:\w+"):
            exe.run(main, feed={"x": xs}, fetch_list=[loss])
    # (no "recovery" run: the poisoned step's NaN grads corrupted the
    # optimizer state — catching exactly that is the watchdog's job; the
    # production answer is the supervisor's checkpoint-and-restore)


# -- run_steps typed feed errors ----------------------------------------------

def test_run_steps_feed_failure_is_typed_and_flight_recorded(
        monkeypatch, tmp_path):
    from paddle_tpu.executor import FeedError

    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=3))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    def feeds():
        yield {"x": np.ones((2, 4), "float32")}
        raise RuntimeError("data pipeline exploded")

    with pytest.raises(FeedError, match=r"global step 1 \(position 1 of the "
                                        r"current 2-step chunk\).*data "
                                        r"pipeline exploded"):
        exe.run_steps(main, feeds(), steps=4, fetch_list=[loss],
                      fetch_every=2)
    dumps = [f for f in os.listdir(str(tmp_path)) if f.startswith("flight_")]
    assert dumps, "feed failure was not flight-recorded"


# -- checkpoint durability satellites -----------------------------------------

def _ckpt_model():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        logits = fluid.layers.fc(x, size=3,
                                 param_attr=fluid.ParamAttr(name="w"),
                                 bias_attr=fluid.ParamAttr(name="b"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _one_step(exe, main, loss, rng):
    exe.run(main, feed={"x": rng.randn(4, 4).astype("float32"),
                        "y": rng.randint(0, 3, (4, 1)).astype("int64")},
            fetch_list=[loss])


def test_torn_restore_falls_back_to_previous_serial(tmp_path, rng):
    """A truncated tensor file inside a _SUCCESS checkpoint must not raise
    mid-restore — load_checkpoint logs, falls back to the previous serial,
    and the scope ends fully consistent with it."""
    ck = str(tmp_path / "ck")
    main, startup, loss = _ckpt_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    _one_step(exe, main, loss, rng)
    fluid.io.save_checkpoint(exe, ck, main, trainer_args={"step": 1})
    w_good = fluid.global_scope().as_numpy("w").copy()
    _one_step(exe, main, loss, rng)
    fluid.io.save_checkpoint(exe, ck, main, trainer_args={"step": 2})
    # corrupt the NEWEST serial's tensor payload (truncation = torn write
    # that survived into a _SUCCESS-marked dir, e.g. lost page cache)
    newest = os.path.join(ck, "checkpoint_1", "w.npy")
    with open(newest, "wb") as f:
        f.write(b"\x93NUMPY")  # magic only: unreadable header
    _one_step(exe, main, loss, rng)  # drift the live weights
    args = fluid.io.load_checkpoint(exe, ck, main)
    assert args["step"] == 1, args  # fell back to serial 0
    np.testing.assert_array_equal(fluid.global_scope().as_numpy("w"), w_good)

    # every serial torn -> a hard, named error (never a silent fresh start)
    oldest = os.path.join(ck, "checkpoint_0", "w.npy")
    with open(oldest, "wb") as f:
        f.write(b"\x93NUMPY")
    with pytest.raises(RuntimeError, match="no readable checkpoint"):
        fluid.io.load_checkpoint(exe, ck, main)


def test_rotation_only_by_trainer_zero(tmp_path, rng):
    """Non-zero trainers never rotate (concurrent savers can't race-delete
    each other's serials); trainer 0 still enforces max_num_checkpoints."""
    ck = str(tmp_path / "ck")
    main, startup, loss = _ckpt_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    _one_step(exe, main, loss, rng)
    for step in range(4):
        fluid.io.save_checkpoint(exe, ck, main, trainer_id=1,
                                 trainer_args={"step": step},
                                 max_num_checkpoints=2)
    names = sorted(n for n in os.listdir(ck) if n.startswith("checkpoint_"))
    assert len(names) == 4, names  # trainer 1 rotated nothing
    fluid.io.save_checkpoint(exe, ck, main, trainer_id=0,
                             trainer_args={"step": 4},
                             max_num_checkpoints=2)
    names = sorted(n for n in os.listdir(ck) if n.startswith("checkpoint_"))
    assert names == ["checkpoint_3", "checkpoint_4"], names


def test_injected_save_fault_leaves_unpublished_tmp(tmp_path, rng):
    """A fault during save (post-payload, pre-publish) must leave only an
    unpublished .tmp dir — the resume path skips it cleanly."""
    ck = str(tmp_path / "ck")
    main, startup, loss = _ckpt_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    _one_step(exe, main, loss, rng)
    fluid.io.save_checkpoint(exe, ck, main, trainer_args={"step": 1})
    plan = FaultPlan([faults.FaultSpec("io.save_checkpoint", "fatal", at=1)])
    with plan:
        with pytest.raises(faults.InjectedFault):
            fluid.io.save_checkpoint(exe, ck, main, trainer_args={"step": 2})
    tmps = [n for n in os.listdir(ck) if n.startswith("checkpoint_1.tmp")]
    assert tmps, os.listdir(ck)  # staged but never published
    assert not os.path.isdir(os.path.join(ck, "checkpoint_1"))
    args = fluid.io.load_checkpoint(exe, ck, main)
    assert args["step"] == 1, args  # the torn tmp was never a candidate


# -- serving page accounting across every retirement path ---------------------

def test_serving_page_accounting_every_retirement_path(rng):
    from paddle_tpu import serving
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=1, d_model=16,
                                   n_head=2, max_seq=32)
    model = decoder_lm.DecoderLM(cfg, seed=0)

    def fresh(**kw):
        return serving.ServingEngine(model, serving.ServingConfig(
            slots=2, page_size=8, max_seq=32, **kw))

    def assert_balanced(eng, label):
        assert eng.pool.num_used == 0, "%s leaked pages" % label
        assert eng.page_accounting_ok(), label

    # 1. max_new retirement (and the immediate-finish prefill path)
    eng = fresh()
    r_full = eng.submit(list(rng.randint(0, 64, 6)), 4)
    r_one = eng.submit(list(rng.randint(0, 64, 6)), 1)
    eng.run(max_steps=100)
    assert r_full.state == "finished" and r_one.state == "finished"
    assert_balanced(eng, "max_new")
    # EOS retirement: replay a prompt with eos_id set to a token the greedy
    # decode deterministically emits mid-generation
    tok_mid = r_full.tokens_out[1]
    eng_eos = fresh(eos_id=int(tok_mid))
    r_eos = eng_eos.submit(list(r_full.prompt), 4)
    eng_eos.run(max_steps=100)
    assert r_eos.state == "finished"
    assert len(r_eos.tokens_out) < 4, "EOS did not stop generation early"
    assert_balanced(eng_eos, "eos")

    # 2. timeout retirement, queued AND running
    eng_t = fresh()
    r_q = eng_t.submit(list(rng.randint(0, 64, 6)), 4, deadline_s=0.0)
    r_r = eng_t.submit(list(rng.randint(0, 64, 6)), 4)
    eng_t.run(max_steps=100)
    assert r_q.state == "timeout" and not r_q.pages
    assert r_r.state == "finished"
    assert_balanced(eng_t, "timeout")

    # 3. decode-failure retirement: pages reclaimed, engine keeps serving
    eng_f = fresh(decode_retries=0)
    plan = FaultPlan([faults.FaultSpec("serving.decode", "fatal", at=1)])
    with plan:
        r_a = eng_f.submit(list(rng.randint(0, 64, 6)), 4)
        r_b = eng_f.submit(list(rng.randint(0, 64, 6)), 4)
        done = eng_f.run(max_steps=100)
    assert r_a.state == "failed" and r_a.error and not r_a.pages
    assert r_b.state in ("failed", "finished")
    assert len(done) == 2, done
    assert_balanced(eng_f, "decode-failure")
    # and the engine is still alive for new traffic
    r_after = eng_f.submit(list(rng.randint(0, 64, 6)), 3)
    eng_f.run(max_steps=100)
    assert r_after.state == "finished"
    assert_balanced(eng_f, "post-failure traffic")
    assert eng_f.health()["status"] == "ok"


@pytest.mark.parametrize("path", ["eos", "timeout", "fatal", "drain"])
def test_serving_page_accounting_with_a_dispatch_in_flight(path, rng):
    """ISSUE 33: ``step()`` launches dispatch N+1 before it reads N. Every
    way out of a slot while a dispatch is unread gives the pages back once,
    leaves no dispatch unread behind, and the request behind is served
    whole in the pages that came back."""
    from paddle_tpu import serving
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                   n_head=2, max_seq=32)
    model = decoder_lm.DecoderLM(cfg, seed=0)
    for _ in range(50):     # a stream whose second token is a new one
        first = list(rng.randint(0, 64, 5))
        alone, _ = decoder_lm.reference_decode(model.params, cfg, first, 12)
        if alone[1] != alone[0]:
            break
    second = list(rng.randint(0, 64, 9))
    want, _ = decoder_lm.reference_decode(model.params, cfg, second, 5)
    # an EOS the host cannot foresee: dispatch 1 ends the request on the
    # device while dispatch 2, launched ahead, has no live slot
    eos = {"eos_id": int(alone[1])} if path == "eos" else {}
    assert alone[1] != alone[0] and (path != "eos" or eos["eos_id"] not in want)
    eng = serving.ServingEngine(model, serving.ServingConfig(
        slots=1, page_size=8, max_seq=32, decode_retries=0, **eos))
    r1 = eng.submit(first, 12, deadline_s=600.0)
    r2 = None if path == "drain" else eng.submit(second, 5)
    for _ in range(1 if path == "eos" else 3):
        eng.step()
    assert eng._unread is not None
    if path == "eos":
        assert eng.step() == [r1] and r1.state == "finished"
        assert r1.tokens_out == alone[:2]
    elif path == "timeout":
        r1.deadline_s = 0.0
        assert eng.step() == [r1] and r1.state == "timeout"
        assert r1.tokens_out == alone[:3]
    elif path == "fatal":
        with FaultPlan([faults.FaultSpec("serving.decode", "fatal", at=1)]):
            assert eng.step() == [r1]
        # the dispatch in flight was read before its batch failed
        assert r1.state == "failed" and r1.tokens_out == alone[:4]
    else:
        summary = eng.drain(timeout_s=0.0)
        assert summary["timed_out"] == 1 and r1.state == "timeout"
        assert r1.tokens_out == alone[:4]
    if path == "timeout":   # the same cycle admitted r2 and launched for it
        assert r2.state == "running" and eng._unread.tenants == [r2]
    else:                   # read, or dropped with nobody to give it to
        assert eng._unread is None
    assert not r1.pages and eng.page_accounting_ok()
    if r2 is not None:
        eng.run(max_steps=100)
        assert r2.state == "finished" and r2.tokens_out == want
    assert eng._unread is None and eng.pool.num_used == 0
    assert eng.page_accounting_ok()
    eng.close()

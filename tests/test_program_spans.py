"""The program's own spans (README: Observability): the tree one
``ServingEngine.step()`` and one ``Executor.run`` record, in the host
tracer's list and in the host plane of a ``jax.profiler`` capture, and what
they cost a run that traces nothing (no record, same tokens)."""

import contextlib
import glob
import os
import signal

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models import decoder_lm
from paddle_tpu.monitor import metrics as mx, tracer
from paddle_tpu.reliability import FaultPlan, faults

# child -> parent, as ISSUE 25 wrote the tree
STEP_TREE = {
    "serving/step": None,
    "serving/expire": "serving/step",
    "serving/admit": "serving/step",
    "serving/prefill": "serving/admit",
    "serving/prefill.launch": "serving/prefill",
    "serving/prefill.sync": "serving/prefill",
    "serving/decode": "serving/step",
    "serving/decode.launch": "serving/decode",
    "serving/decode.sync": "serving/decode",
    "serving/retire": "serving/step",
}
RUN_TREE = {
    "executor/run": None,
    "executor/plan": "executor/run",
    "executor/place": "executor/run",
    "executor/step": "executor/run",
    "executor/writeback": "executor/run",
}
# three seeded requests through two slots, as the parent commit (524be9a)
# served them on this CPU backend
PARENT_TOKENS = [[61, 1, 1, 1, 1, 1], [61, 61, 61, 61],
                 [48, 48, 48, 48, 48, 48, 48]]

_MODEL = None


def _engine(**kw):
    global _MODEL
    if _MODEL is None:
        _MODEL = decoder_lm.DecoderLM(
            decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                     n_head=2, max_seq=64), seed=0)
    return serving.ServingEngine(_MODEL, serving.ServingConfig(
        slots=2, page_size=8, max_seq=64, prompt_buckets=(16,), **kw))


@pytest.fixture
def host_tracer():
    tracer.clear_spans()
    tracer.start_tracing()
    yield tracer
    tracer.stop_tracing()
    tracer.clear_spans()


@contextlib.contextmanager
def time_limit(seconds: int):
    """A limit of the case's own: a profiler that hangs fails this case
    and not the run's."""
    def expired(signum, frame):
        raise TimeoutError("the case ran over %d s" % seconds)

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def _engine_spans():
    return [s for s in tracer.get_spans() if s["cat"] == "engine"]


def _assert_nested(spans):
    """Every span lies inside the span its ``parent`` names."""
    for s in spans:
        if "parent" not in s:
            continue
        lo, hi = s["ts_us"], s["ts_us"] + s["dur_us"]
        assert any(p["name"] == s["parent"] and p["tid"] == s["tid"]
                   and p["ts_us"] <= lo and hi <= p["ts_us"] + p["dur_us"]
                   for p in spans), "%s escapes %s" % (s["name"], s["parent"])


# what each kind of cycle leaves out of the tree (ISSUE 33: a step launches
# dispatch N+1, THEN reads dispatch N)
PREFILL = {"serving/prefill", "serving/prefill.launch", "serving/prefill.sync"}
READ = {"serving/decode.sync", "serving/retire"}
CASES = {
    # the first dispatch after the engine held nobody: nothing to read yet
    "one_admission": (0, None, READ),
    # dispatch 2 is launched, then dispatch 1 is read
    "launched_ahead": (1, None, PREFILL),
    "retried_decode": (1, "transient", PREFILL),
    # every budget ends in the dispatch in flight: it is read, none launched
    "budget_ends": (2, None, PREFILL | {"serving/decode.launch"}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_step_records_the_tree(case, host_tracer):
    steps_before, fault, absent = CASES[case]
    eng = _engine()
    req = eng.submit([3, 1, 4, 1, 5], 3)
    try:
        for _ in range(steps_before):
            eng.step()
        had = len(req.tokens_out)
        tracer.clear_spans()
        if fault:
            faults.install(FaultPlan([faults.FaultSpec(
                "serving.decode", fault, at=1)]))
        eng.step()
        spans = _engine_spans()
        got, state = len(req.tokens_out), req.state
    finally:
        faults.clear()
        eng.close()
    names = [s["name"] for s in spans]
    launches = 2 if fault else 1
    assert sorted(names) == sorted(
        [n for n in STEP_TREE if n not in absent]
        + ["serving/decode.launch"] * (launches - 1))
    for s in spans:
        assert s.get("parent") == STEP_TREE[s["name"]], s
    _assert_nested(spans)
    by_name = {s["name"]: s for s in spans}
    assert by_name["serving/step"]["args"] == {
        "cycle": steps_before + 1, "occupancy": min(steps_before, 1),
        "queue": 0 if steps_before else 1}
    assert by_name["serving/decode"]["args"] == {"steps": 1}
    if case == "one_admission":
        assert by_name["serving/prefill"]["args"]["trace_id"] == req.trace_id
        assert by_name["serving/prefill"]["args"]["cause"] == "local"
        # the prefill's token alone: the dispatch is launched and unread
        assert got == had + 1 == 1
    else:
        # one dispatch's token a step, and the launch comes before the sync
        assert got == had + 1
        if "serving/decode.launch" not in absent:
            assert all(s["ts_us"] <= by_name["serving/decode.sync"]["ts_us"]
                       for s in spans if s["name"] == "serving/decode.launch")
    assert (state == "finished") == (case == "budget_ends")
    # close() read what the step had left unread: nothing is lost
    assert len(req.tokens_out) == min(got + 1, 3)


def test_untraced_run_records_nothing_counts_cycles_and_serves_the_same():
    tracer.stop_tracing()
    tracer.clear_spans()
    cycles = mx.snapshot()["serving/cycles"]["value"]
    eng = _engine()
    rng = np.random.RandomState(25)
    reqs = [eng.submit(list(rng.randint(0, 64, n)), m)
            for n, m in ((5, 6), (11, 4), (16, 7))]
    steps = 0
    while not eng.scheduler.idle():
        eng.step()
        steps += 1
    eng.close()
    assert tracer.get_spans() == []
    assert mx.snapshot()["serving/cycles"]["value"] - cycles == steps
    assert [r.tokens_out for r in reqs] == PARENT_TOKENS


def _host_plane_names(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def _serve_one_step():
    eng = _engine()
    eng.submit([3, 1, 4, 1, 5], 4)
    eng.step()          # compiles outside the capture
    return eng, eng.step, set(STEP_TREE) - {
        "serving/prefill", "serving/prefill.launch", "serving/prefill.sync"}


def _train_one_step():
    x = fluid.layers.data("x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(x, 2))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((2, 4), "float32")}

    def run():
        exe.run(feed=feed, fetch_list=[loss])

    run()               # compiles outside the capture
    return exe, run, set(RUN_TREE) | {"train"}


@pytest.mark.parametrize("build", [_serve_one_step, _train_one_step],
                         ids=["serve", "train"])
def test_a_profile_holds_the_same_names(build, tmp_path):
    """No host tracer here: any ``jax.profiler`` capture shows the spans,
    in the host plane and so on the device trace's clock."""
    import jax

    tracer.stop_tracing()
    with time_limit(120):
        owner, once, expected = build()
        try:
            with jax.profiler.trace(str(tmp_path)):
                once()
        finally:
            owner.close()
        names = _host_plane_names(str(tmp_path))
    assert expected <= names, sorted(expected - names)
    assert tracer.get_spans() == []

"""The ONE decode state machine (``engine._decode_cycle`` over the ``chunk``
executable), held over every served model.

Four properties a model, each on ONE engine a model (the module-scoped
``served`` fixture: one prefill bucket and the decode chunk are compiled
once, and every test drives the same engine from idle to idle, over
whatever rows the requests before left in the pools):

(a) a run whose dispatches are launched ahead gives the tokens and the
    captured logits of one whose every dispatch is read before the next is
    launched;
(b) a request's stream is the same alone as among neighbours that arrive,
    finish and hand their slot to another;
(c) each retirement path (EOS, ``max_new_tokens``, the context's end, a
    deadline, a failed batch) leaves ``page_accounting_ok()`` true and the
    slot serving the next request rightly;
(d) a transient fault at ``serving.decode`` (a ``FaultPlan``), retried in
    place, gives the streams of a run without it.

Tokens are compared exactly. Logits are compared inside ``TOL``: a slot's
rows meet other neighbours' rows in the batched products of two runs, and a
grouped product's order of summation follows the batch.
"""

import importlib

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.reliability import FaultPlan, faults
from paddle_tpu.serving import metrics as sm

MODELS = ["decoder_lm", "smallthinker", "kimi_k2", "laguna", "ling3_flash",
          "motif3", "glm5_flash", "falcon_h1", "ouro", "evabyte",
          "deepseek_v32", "nemotron3"]
EOS = 3
TOL = dict(rtol=2e-4, atol=2e-4)
# the one prompt bucket and the context budget: what each model's own tests
# serve it at, where they differ from (16, 64)
GEOMETRY = {"glm5_flash": (128, 256), "evabyte": (32, 160),
            "deepseek_v32": (128, 256)}


class Served:
    """A toy model's engine and the streams it gave requests that ran
    alone, by ``(prompt, max_new, temperature, seed)``."""

    def __init__(self, name):
        self.name = name
        self.bucket, self.max_seq = GEOMETRY.get(name, (16, 64))
        over = dict(slots=3, prompt_buckets=(self.bucket,),
                    max_seq=self.max_seq, eos_id=EOS, collect_logits=True)
        if name == "decoder_lm":
            from test_serving import get_model, small_config

            self.eng = serving.ServingEngine(get_model(),
                                             small_config(**over))
        elif name == "evabyte":
            from test_serving import _compacting_model

            self.eng = serving.ServingEngine(
                _compacting_model(),
                serving.ServingConfig(page_size=4, **over))
        else:
            mod = importlib.import_module("test_" + name)
            self.eng = mod._engine(mod.toy_model(), **over)
        self.vocab = self.eng.model.cfg.vocab_size
        self._alone = {}

    def prompt(self, rng, n):
        return [int(t) for t in rng.randint(0, self.vocab, n)]

    def submit(self, spec, **kw):
        prompt, max_new, temperature, seed = spec
        return self.eng.submit(list(prompt), max_new, temperature=temperature,
                               seed=seed, **kw)

    def drive(self, specs, late=(), after=2):
        """``specs`` submitted at once and ``late`` after ``after`` cycles,
        run to idle: ``[(tokens, logits [n, ...])]`` in that order."""
        eng = self.eng
        reqs = [self.submit(s) for s in specs]
        for _ in range(after if late else 0):
            eng.step()
        reqs += [self.submit(s) for s in late]
        eng.run()
        assert eng.scheduler.idle() and eng._unread is None
        assert eng.page_accounting_ok() and eng.pool.num_used == 0
        return [(list(r.tokens_out), np.stack(eng.captured_logits(r)))
                for r in reqs]

    def alone(self, spec):
        key = (tuple(spec[0]),) + tuple(spec[1:])
        if key not in self._alone:
            self._alone[key] = self.drive([spec])[0]
        return self._alone[key]

    def greedy(self, rng, n, max_new):
        """A greedy request of ``max_new`` tokens that its budget ends, not
        an EOS: ``(prompt, max_new, 0.0, 0)``."""
        for _ in range(20):
            spec = (self.prompt(rng, n), max_new, 0.0, 0)
            if len(self.alone(spec)[0]) == max_new:
                return spec
        raise AssertionError("every greedy stream of %s met token %d"
                             % (self.name, EOS))

    def ends_at_eos(self, rng):
        """A request that an EOS ends in a decode step (not its first
        token, which is the prefill's): drawn at a temperature that
        flattens the toy's logits, from seeds in order."""
        prompt = self.prompt(rng, 5)
        for seed in range(1, 400):
            spec = (prompt, 16, 80.0, seed)
            toks = self.alone(spec)[0]
            if 3 <= len(toks) < 16:
                assert toks[-1] == EOS and EOS not in toks[:-1]
                return spec
        raise AssertionError("no seed of %s drew token %d" % (self.name, EOS))


@pytest.fixture(scope="module", params=MODELS)
def served(request):
    s = Served(request.param)
    yield s
    s.eng.close()


def _same(got, want):
    assert len(got) == len(want)
    for (toks, logits), (toks_w, logits_w) in zip(got, want):
        assert toks == toks_w
        np.testing.assert_allclose(logits, logits_w, **TOL)


def _counts():
    return np.array([c.value for c in (sm.DECODE_LAUNCHED_AHEAD,
                                       sm.DECODE_DISPATCHES, sm.RETRIES,
                                       sm.FAULTS)])


def _stream(s, rng):
    """Five requests of mixed lengths for three slots: two wait for a
    slot, and the caller submits the last two late."""
    return [s.greedy(rng, n, m) for n, m in (
        (3, 9), (s.bucket, 5), (5, 14), (11, 2), (8, 7))]


def test_launched_ahead_equals_read_first(served, monkeypatch):
    stream = _stream(served, np.random.RandomState(1))
    before = _counts()
    ahead = served.drive(stream[:3], late=stream[3:])
    launched, dispatches = (_counts() - before)[:2]
    assert 0 < launched < dispatches
    monkeypatch.setattr(served.eng, "_launches_ahead", lambda prev: False)
    before = _counts()
    first = served.drive(stream[:3], late=stream[3:])
    assert (_counts() - before)[0] == 0
    _same(ahead, first)


def test_a_stream_is_the_same_alone_as_among_neighbours(served):
    rng = np.random.RandomState(2)
    mine = served.greedy(rng, 7, 16)
    # two that finish early and hand their slots to the two that wait, and
    # two that arrive while ``mine`` decodes
    early = [served.greedy(rng, n, m) for n, m in ((4, 2), (9, 4))]
    wait = [served.greedy(rng, n, m) for n, m in ((6, 5), (served.bucket, 3))]
    late = [served.greedy(rng, n, m) for n, m in ((3, 6), (10, 2))]
    among = served.drive([early[0], mine, early[1]] + wait, late=late,
                         after=3)
    _same(among, [served.alone(x)
                  for x in [early[0], mine, early[1]] + wait + late])


@pytest.fixture
def sync_fails_once(served, monkeypatch):
    """Arms the engine: the NEXT read of a dispatch raises, after the
    dispatch behind it was launched on its outputs."""
    real, state = served.eng._sync, {"armed": False}

    def sync(d):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("UNAVAILABLE: injected at the sync")
        return real(d)

    monkeypatch.setattr(served.eng, "_sync", sync)
    return lambda: state.update(armed=True)


def test_every_retirement_path_returns_the_pages_and_the_slot(
        served, sync_fails_once):
    """One request a path beside two bystanders that decode on, and behind
    them one that waits for a slot: it gets the slot that was vacated
    first, and its stream and the bystanders' are those they give alone
    (a failed batch takes the bystanders with it, and the queue is served
    after the cache is made anew)."""
    eng, rng = served.eng, np.random.RandomState(3)
    room = served.max_seq - served.bucket
    victims = {
        "eos": served.ends_at_eos(rng),
        "max_new_tokens": served.greedy(rng, 6, 5),
        "max_seq": served.greedy(rng, served.bucket, room),
        "deadline": served.greedy(rng, 6, 30),
        "failed": served.greedy(rng, 6, 30),
    }
    by = [served.greedy(rng, n, m) for n, m in ((9, 12), (4, 8))]
    nxt = served.greedy(rng, 8, 6)
    for path, spec in victims.items():
        kw = dict(deadline_s=600.0) if path == "deadline" else {}
        victim = served.submit(spec, **kw)
        others = [served.submit(x) for x in by]
        behind = served.submit(nxt)
        for _ in range(3):
            eng.step()
        before = _counts()
        if path == "deadline":
            had = list(victim.tokens_out)
            victim.deadline_s = 0.0
            assert victim in eng.step() and victim.state == "timeout"
            assert victim.tokens_out == had
        elif path == "failed":
            sync_fails_once()
            failed = eng.step()
            assert victim in failed and victim.state == "failed"
            assert all(r.state == "failed" for r in others)
            assert list((_counts() - before)[2:]) == [0, 1]
        while victim.state == "running":
            eng.step()
            assert eng.page_accounting_ok(), path
        assert not victim.pages and eng.page_accounting_ok(), path
        eng.run()
        assert eng.pool.num_used == 0 and eng._unread is None, path
        assert eng.health()["status"] == "ok", path
        want = served.alone(spec)[0]
        if path in ("deadline", "failed"):
            assert victim.tokens_out == want[:len(victim.tokens_out)], path
        else:
            assert victim.state == "finished", path
            assert victim.tokens_out == want, path
            assert victim.tokens_out[-1] == EOS if path == "eos" \
                else len(want) == spec[1], path
        if path == "max_seq":   # the context's last position was written
            assert len(spec[0]) + len(want) == served.max_seq
        served_after = [behind] + ([] if path == "failed" else others)
        for req, x in zip(served_after, [nxt] + by):
            assert req.state == "finished", path
            assert req.tokens_out == served.alone(x)[0], path


def test_a_transient_fault_retried_in_place_changes_no_stream(served):
    """The site fires inside the launch of the third decode dispatch, with
    the second unread: the launch is retried, nothing is failed, and every
    stream is whole. (What surfaces at the sync finds the donated cache
    gone and fails the batch: a path of the test above.)"""
    stream = _stream(served, np.random.RandomState(4))
    want = served.drive(stream[:3], late=stream[3:])
    before = _counts()
    with FaultPlan([faults.FaultSpec("serving.decode", "transient", at=3)]):
        got = served.drive(stream[:3], late=stream[3:])
    assert list((_counts() - before)[2:]) == [1, 0]
    _same(got, want)

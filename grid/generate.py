"""The one general traffic generator: a traffic file's parameters in,
requests or batches out. A new mix is a new data file, never new code.

What makes two runs offer the same work (the lesson of PR 22's refusal):

* lengths are STRATIFIED, not sampled: of N requests the i-th takes the
  (i + 0.5) / N quantile of its distribution, so every run offers the same
  multiset of prompt and of output lengths;
* the arrival instants belong to the traffic file: they are drawn from the
  file's own ``schedule_seed`` and ``--seed`` never moves them;
* ``--seed`` decides the token ids and which arrival gets which length (two
  independent permutations), and, in the drivers, the weights.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple

import numpy as np


def np_seed(seed: int) -> int:
    """``--seed`` may be a little over 2**31; numpy takes up to 2**32 - 1
    and the program's own seeds are 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def quantile(dist: Dict[str, Any], q: np.ndarray) -> np.ndarray:
    """Whole-number lengths at the quantiles ``q`` of ``dist``."""
    kind = dist["dist"]
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if kind == "uniform":
        x = lo + (hi - lo) * q
    elif kind == "log_uniform":
        x = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * q)
    else:
        raise ValueError("unknown length distribution %r" % kind)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The n lengths every run offers: the (i + 0.5) / n quantiles."""
    return quantile(dist, (np.arange(n) + 0.5) / max(n, 1))


def arrivals(spec: Dict[str, Any], horizon_s: float) -> np.ndarray:
    """Due instants in [0, horizon_s), seconds from the start of traffic.

    ``poisson``: exponential gaps at ``rate_per_s``. ``uniform``: one every
    1 / rate (the sweep's standing backlog). The instants are drawn one
    after another from ``schedule_seed``, so a longer horizon extends a
    shorter one.
    """
    rng = np.random.RandomState(int(spec["schedule_seed"]))
    mean_gap = 1.0 / float(spec["rate_per_s"])
    out: List[float] = []
    t = 0.0
    while True:
        if spec["process"] == "poisson":
            t += rng.exponential(mean_gap)
        elif spec["process"] == "uniform":
            t += mean_gap
        else:
            raise ValueError("unknown arrival process %r" % spec["process"])
        if t >= horizon_s:
            return np.asarray(out)
        out.append(t)


class Planned(NamedTuple):
    due_s: float          # seconds from the start of traffic
    prompt: List[int]
    max_new_tokens: int


def serve_plan(traffic: Dict[str, Any], vocab: int, seed: int,
               window_s: float, tail_s: float = 0.0) -> List[Planned]:
    """Every request of one run, in the order they are due.

    Traffic runs for ``preroll_s`` (warm-in, never judged), then the window
    of ``window_s``, then ``tail_s`` more (the traced stretch of a traced
    run). The requests due before the window's end are stratified as one
    set, so a traced run's window offers what an untraced one does; the
    tail's requests are a set of their own.
    """
    main_s = float(traffic["preroll_s"]) + window_s
    due = arrivals(traffic["arrivals"], main_s + tail_s)
    n_main = int(np.searchsorted(due, main_s))
    rng = np.random.RandomState(np_seed(seed))
    prompts, outputs = [], []
    for n in (n_main, len(due) - n_main):
        prompts.append(rng.permutation(stratified(traffic["prompt_len"], n)))
        outputs.append(rng.permutation(stratified(traffic["output_len"], n)))
    prompt_len = np.concatenate(prompts)
    output_len = np.concatenate(outputs)
    max_total = traffic.get("max_total")
    if max_total:
        output_len = np.minimum(output_len, int(max_total) - prompt_len)
    return [Planned(float(t), rng.randint(0, vocab, int(n)).tolist(), int(o))
            for t, n, o in zip(due, prompt_len, output_len)]


def train_ring(traffic: Dict[str, Any], vocab: int, rows: int, seq: int,
               seed: int) -> List[Dict[str, np.ndarray]]:
    """The ring of distinct host batches a training run feeds in turn.

    Full-length (packed) rows: every mask is 1 and a token is one target
    position. Token ids follow a Zipf law over the vocabulary (rank ** -a),
    as text does, so the loss has something to learn from a stream of
    random rows; the label of a position is the next target token.
    """
    rng = np.random.RandomState(np_seed(seed))
    p = np.arange(1, vocab - 1, dtype=np.float64) ** -float(
        traffic["zipf_exponent"])
    cdf = np.cumsum(p / p.sum())

    def draw(shape):
        # ids 0 and 1 stay free for padding and end-of-sentence
        ids = np.searchsorted(cdf, rng.random_sample(shape)) + 2
        return np.minimum(ids, vocab - 1).astype("int64")

    ring = []
    for _ in range(int(traffic["ring"])):
        trg = draw((rows, seq + 1))
        ring.append({"src": draw((rows, seq)),
                     "trg": np.ascontiguousarray(trg[:, :-1]),
                     "lbl": np.ascontiguousarray(trg[:, 1:, None]),
                     "smask": np.ones((rows, seq), "float32"),
                     "tmask": np.ones((rows, seq), "float32")})
    return ring

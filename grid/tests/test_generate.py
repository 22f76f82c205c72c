import collections

import numpy as np

from grid import generate, manifest


def _plans(seeds, window_s=8.0):
    cell = manifest.Cell("gpt2s-doc-steady")
    return cell.traffic, [generate.serve_plan(cell.traffic, 50257, s, window_s)
                          for s in seeds]


def test_every_seed_offers_the_same_lengths_and_instants():
    traffic, (a, b, c) = _plans([1, 2, 2 ** 31 + 11])
    assert len(a) == len(b) == len(c) > 20
    for x in (b, c):
        assert [p.due_s for p in x] == [p.due_s for p in a]
        for pick in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
            assert collections.Counter(map(pick, x)) \
                == collections.Counter(map(pick, a))
    # ... in another order, with other token ids
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert a[0].prompt != b[0].prompt
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    assert all(lo <= len(p.prompt) <= hi for p in a)
    assert all(len(p.prompt) + p.max_new_tokens <= traffic["max_total"]
               for p in a)


def test_same_seed_same_plan_and_a_longer_run_extends_a_shorter():
    _, (a, b) = _plans([5, 5])
    assert a == b
    _, (short,) = _plans([5], window_s=4.0)
    assert [p.due_s for p in a][:len(short)] == [p.due_s for p in short]


def test_a_traced_tail_leaves_the_window_as_it_was():
    cell = manifest.Cell("gpt2s-chat-sat")
    plain = generate.serve_plan(cell.traffic, 50257, 3, 6.0)
    traced = generate.serve_plan(cell.traffic, 50257, 3, 6.0, tail_s=2.0)
    assert len(traced) > len(plain)
    assert [(p.due_s, len(p.prompt), p.max_new_tokens)
            for p in traced[:len(plain)]] \
        == [(p.due_s, len(p.prompt), p.max_new_tokens) for p in plain]


def test_stratified_lengths_are_the_quantiles():
    got = generate.stratified({"dist": "uniform", "lo": 10, "hi": 20}, 5)
    assert got.tolist() == [11, 13, 15, 17, 19]
    logu = generate.stratified({"dist": "log_uniform", "lo": 32, "hi": 256},
                               1000)
    assert abs(np.median(logu) - (32 * 256) ** 0.5) < 1.0


def test_train_ring_is_seeded_and_packed():
    traffic = manifest.Cell("tfbase-train-1chip").traffic
    a = generate.train_ring(traffic, 300, 4, 16, 9)
    b = generate.train_ring(traffic, 300, 4, 16, 9)
    c = generate.train_ring(traffic, 300, 4, 16, 10)
    assert len(a) == traffic["ring"]
    assert all((a[i][k] == b[i][k]).all() for i in range(len(a)) for k in a[i])
    assert (a[0]["trg"] != c[0]["trg"]).any()
    assert (a[0]["trg"] != a[1]["trg"]).any()
    # the label of a position is the next target token
    assert (a[0]["lbl"][:, :-1, 0] == a[0]["trg"][:, 1:]).all()
    assert a[0]["tmask"].all() and a[0]["trg"].min() >= 2 \
        and a[0]["trg"].max() < 300

"""The serving sampler (``serving/engine.py::_sample_tokens``) and the
counters that say which of its tiers a dispatch takes.

The sampler chooses on the device among three tiers by what its LIVE rows
ask for: the argmax alone while no live row draws, the scaling and the
Gumbel draw while none of those cuts to a ``top_k``, the vocabulary sort
only beyond that. These tests hold the tiers to the straight-line sampler
they replaced (kept here verbatim as the reference: every row paid for the
sort), bit for bit on live rows, alone and inside a decode chunk's scan at
two fuse widths; hold the lowered program to keeping the sort and the RNG
off the greedy path; and drive ``ServingEngine`` for the greedy stream
beside sampling neighbours and for ``serving/sampler_dispatches.*``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.flags import set_flag
from paddle_tpu.models import decoder_lm
from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops.attention_ops import neg_inf
from paddle_tpu.serving.engine import _sample_tokens

B, V = 6, 64


def reference_sample_tokens(logits, temp, top_k, seed, position):
    """The sampler as it stood before it chose a tier: scale, sort, mask
    and draw for every row, then keep the greedy argmax where
    ``temp == 0``."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    scaled = logits.astype(jnp.float32) / jnp.maximum(
        temp.astype(jnp.float32), 1e-6)[:, None]
    k = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    srt = jax.lax.sort(scaled, dimension=-1)[:, ::-1]  # descending
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled >= kth, scaled, neg_inf(jnp.float32))

    def draw(seed_b, pos_b):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_b), pos_b)
        return jax.random.gumbel(key, (v,), jnp.float32)

    sampled = jnp.argmax(masked + jax.vmap(draw)(seed, position),
                         axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


# temp, top_k, live by row; `tier` is the one the step must take
# (0 argmax alone, 1 scale and draw, 2 sort too)
CASES = {
    "all_greedy": dict(
        temp=[0, 0, 0, 0, 0, 0], top_k=[0, 5, 0, 0, 3, 0],
        live=[1, 1, 1, 1, 1, 1], tier=0),
    "all_temperature_only": dict(
        temp=[0.7, 1.3, 0.9, 2.0, 0.5, 1.0], top_k=[0, 0, 0, 0, 0, 0],
        live=[1, 1, 1, 1, 1, 1], tier=1),
    "all_top_k": dict(
        temp=[0.7, 1.3, 0.9, 2.0, 0.5, 1.0], top_k=[1, 3, 5, 17, 64, 900],
        live=[1, 1, 1, 1, 1, 1], tier=2),
    "greedy_and_top_k_mixed": dict(
        temp=[0, 0.8, 0, 1.5, 0, 0.9], top_k=[0, 4, 7, 0, 0, 2],
        live=[1, 1, 1, 1, 1, 1], tier=2),
    "greedy_and_temperature_only_mixed": dict(
        temp=[0, 0.8, 0, 1.5, 0, 0.9], top_k=[0, 0, 7, 0, 0, 0],
        live=[1, 1, 1, 1, 1, 1], tier=1),
    # in the chunk test rows 1 and 3 die first: the steps go from the sort
    # to the draw to the argmax alone
    "sampling_rows_die_before_greedy_ones": dict(
        temp=[0, 0.8, 0, 1.5, 0, 0], top_k=[0, 4, 0, 0, 0, 0],
        live=[1, 1, 1, 1, 1, 1], tier=2),
    # a retired slot keeps its tenant's temp and top_k: it must not keep
    # the sort running for its greedy neighbours
    "dead_top_k_row_beside_live_greedy": dict(
        temp=[0, 0, 4.0, 0, 0, 0], top_k=[0, 0, 9, 0, 0, 0],
        live=[1, 1, 0, 1, 1, 1], tier=0),
    "dead_top_k_row_beside_live_temperature_only": dict(
        temp=[1.1, 0, 4.0, 0, 0.6, 0], top_k=[0, 0, 9, 0, 0, 0],
        live=[1, 1, 0, 1, 1, 0], tier=1),
}


def _rows(case):
    return (jnp.asarray(case["temp"], jnp.float32),
            jnp.asarray(case["top_k"], jnp.int32))


def _logits(rng, *lead):
    return jnp.asarray(rng.randn(*lead, B, V) * 3.0,
                       jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("name", sorted(CASES))
def test_live_rows_bit_identical_to_straight_line_sampler(name):
    case = CASES[name]
    live = np.asarray(case["live"], bool)
    temp, top_k = _rows(case)
    dead_row_drew = False
    for seed in (3, 2147483999, 77, 123456789):
        rng = np.random.RandomState(seed)
        args = (_logits(rng), temp, top_k,
                jnp.asarray(rng.randint(0, 2**31 - 1, B), jnp.int32),
                jnp.asarray(rng.randint(0, 4096, B), jnp.int32))
        got = np.asarray(jax.jit(_sample_tokens)(*args, jnp.asarray(live)))
        want = np.asarray(jax.jit(reference_sample_tokens)(*args))
        np.testing.assert_array_equal(got[live], want[live])
        if live.all():  # every row live is what no mask means
            np.testing.assert_array_equal(
                np.asarray(jax.jit(_sample_tokens)(*args)), want)
        # a dead row tells which tier ran: the argmax below the tier that
        # draws, the straight-line sampler's own token from there on
        greedy = np.asarray(jnp.argmax(args[0], axis=-1))
        if case["tier"] == 0:
            np.testing.assert_array_equal(got, greedy)
        else:
            still = np.asarray(case["temp"]) == 0
            np.testing.assert_array_equal(got[still], greedy[still])
        dead_row_drew |= bool((want[~live] != greedy[~live]).any())
    if not live.all() and case["tier"] == 0:
        # and the reference DID draw for the dead row, so the equality
        # above is the short tier's and not a coincidence of the draw
        assert dead_row_drew


STEPS = 8


def _decode(sampler, fuse, logits, temp, top_k, seed, lengths, active,
            maxnew):
    """``STEPS`` decode steps as the engine's chunk takes them, ``fuse`` to
    a dispatch, the model's forward replaced by ``logits[step]``: the
    sampler keyed by the row's length, a row that has emitted ``maxnew``
    tokens dead from then on with its ``temp`` and ``top_k`` left behind.
    Returns the tokens and which of them were emitted, ``[STEPS, B]``."""
    def chunk(lengths, tokens, active, gen, logits):
        def body(carry, lg):
            ln, tk, ac, gc = carry
            nxt = jnp.where(ac, sampler(lg, temp, top_k, seed, ln, ac), tk)
            gc, ln = gc + ac, ln + ac
            return (ln, nxt, ac & (gc < maxnew), gc), (nxt, ac)

        return jax.lax.scan(body, (lengths, tokens, active, gen), logits)

    carry = (lengths, jnp.zeros((B,), jnp.int32), active,
             jnp.zeros((B,), jnp.int32))
    toks, emitted = [], []
    step = jax.jit(chunk)
    for i in range(0, STEPS, fuse):
        carry, (t, e) = step(*carry, logits[i:i + fuse])
        toks.append(np.asarray(t))
        emitted.append(np.asarray(e))
    return np.concatenate(toks), np.concatenate(emitted)


@pytest.mark.parametrize("fuse", [1, 4])
@pytest.mark.parametrize("name", sorted(CASES))
def test_decode_chunk_bit_identical_to_straight_line_sampler(name, fuse):
    """Inside the chunk's scan, where rows die as the steps go (rows 1 and
    3 after three and five tokens, whatever they had asked for), every
    emitted token equals the straight-line sampler's, at both fuse
    widths."""
    case = CASES[name]
    temp, top_k = _rows(case)
    maxnew = jnp.asarray([STEPS, 3, STEPS, 5, STEPS, STEPS], jnp.int32)
    for seed in (11, 2147484001):
        rng = np.random.RandomState(seed)
        args = (_logits(rng, STEPS), temp, top_k,
                jnp.asarray(rng.randint(0, 2**31 - 1, B), jnp.int32),
                jnp.asarray(rng.randint(0, 4096, B), jnp.int32),
                jnp.asarray(case["live"], jnp.bool_), maxnew)
        want, emitted = _decode(
            lambda lg, t, k, s, p, live: reference_sample_tokens(
                lg, t, k, s, p), 1, *args)
        got, got_emitted = _decode(_sample_tokens, fuse, *args)
        np.testing.assert_array_equal(got_emitted, emitted)
        np.testing.assert_array_equal(got[emitted], want[emitted])
        assert emitted.sum() == sum(
            min(STEPS, int(m)) for m, lv in zip(maxnew, case["live"]) if lv)


def test_draw_depends_on_seed_and_position_only(rng):
    """A sampled row's token is its own (logits, temp, top_k, seed,
    position): neither its neighbours nor the tier the batch takes reach
    it."""
    temp, top_k = _rows(CASES["all_temperature_only"])
    seed = jnp.asarray(rng.randint(0, 2**31 - 1, B), jnp.int32)
    position = jnp.asarray(rng.randint(0, 4096, B), jnp.int32)
    logits = _logits(rng)
    alone = np.asarray(jax.jit(_sample_tokens)(logits, temp, top_k, seed,
                                               position))
    # row 3 now cuts to a top_k: the batch takes the sort tier
    crowded = np.asarray(jax.jit(_sample_tokens)(
        logits, temp, top_k.at[3].set(5), seed, position))
    keep = np.arange(B) != 3
    np.testing.assert_array_equal(alone[keep], crowded[keep])


def _case_regions(text):
    """The lines of the module before its one ``stablehlo.case``, the lines
    of each of the case's regions, and every line outside that case."""
    lines = text.splitlines()
    start = [i for i, ln in enumerate(lines) if "stablehlo.case" in ln]
    assert len(start) == 1, "expected one case op, found %d" % len(start)
    start = start[0]
    indent = re.match(r"\s*", lines[start]).group()
    regions, cur = [], []
    end = None
    for i in range(start + 1, len(lines)):
        ln = lines[i]
        if ln.startswith(indent + "}, {"):
            regions.append(cur)
            cur = []
        elif ln.startswith(indent + "})"):
            regions.append(cur)
            end = i
            break
        else:
            cur.append(ln)
    assert end is not None
    return lines[:start], regions, lines[:start] + lines[end + 1:]


def test_lowered_sampler_keeps_sort_and_rng_off_the_greedy_path():
    abstract = (jax.ShapeDtypeStruct((B, V), jnp.bfloat16),
                jax.ShapeDtypeStruct((B,), jnp.float32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_))
    text = jax.jit(_sample_tokens).lower(*abstract).as_text()
    before, regions, outside = _case_regions(text)
    assert len(regions) == 3

    def joined(lines):
        return "\n".join(lines)

    # the main path (what every step runs before it chooses): the argmax
    # and the two reductions over [B]; no sort, no RNG, no [B,V] division
    main_path = joined(before[[i for i, ln in enumerate(before)
                               if "@main" in ln][0]:])
    assert "stablehlo.sort" not in main_path
    assert not re.search(r"threefry|gumbel|rng", main_path)
    assert "stablehlo.divide" not in main_path
    # every sort of the module is inside the case, and in its last region
    assert "stablehlo.sort" not in joined(outside)
    assert "stablehlo.sort" not in joined(regions[0] + regions[1])
    assert joined(regions[2]).count("stablehlo.sort") == 1
    # all greedy: the tier returns the argmax it was handed
    body = [ln.strip() for ln in regions[0] if ln.strip()]
    assert len(body) == 1 and body[0].startswith("stablehlo.return"), body
    # temperature only: scales and draws
    assert "_gumbel" in joined(regions[1])
    assert "stablehlo.divide" in joined(regions[1])


# -- through ServingEngine ----------------------------------------------------

_MODEL = None
SAMPLING = {"temperature_only": dict(temperature=0.8, top_k=0, seed=11),
            "top_k": dict(temperature=0.8, top_k=5, seed=11)}


def _engine():
    global _MODEL
    if _MODEL is None:
        cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=2, d_model=32,
                                       n_head=2, max_seq=64)
        _MODEL = decoder_lm.DecoderLM(cfg, seed=0)
    return serving.ServingEngine(_MODEL, serving.ServingConfig(
        slots=3, page_size=8, max_seq=64, prompt_buckets=(16,)))


@pytest.fixture
def gather_path():
    set_flag("paged_attention_kernel", "off")
    yield
    set_flag("paged_attention_kernel", "auto")


def _tiers():
    snap = mx.snapshot()
    return np.asarray([snap["serving/sampler_dispatches." + t]["value"]
                       for t in ("greedy", "draw", "sort")])


@pytest.mark.parametrize("beside", ["temperature_only", "top_k", "both"])
def test_greedy_stream_equal_alone_and_beside_sampling(gather_path, rng,
                                                       beside):
    """A greedy request's tokens do not depend on the tier its neighbours
    put the step into, nor on their leaving while it runs on."""
    prompts = [list(rng.randint(0, 64, n)) for n in (6, 9, 4)]
    eng = _engine()
    alone = eng.submit(prompts[0], 20)
    eng.run()
    eng.close()
    eng = _engine()
    greedy = eng.submit(prompts[0], 20)
    kinds = sorted(SAMPLING) if beside == "both" else [beside]
    others = [eng.submit(p, 6, **SAMPLING[k])
              for p, k in zip(prompts[1:], kinds)]
    eng.run()
    eng.close()
    assert list(greedy.tokens_out) == list(alone.tokens_out)
    assert len(alone.tokens_out) == 20
    assert all(len(r.tokens_out) == 6 for r in others)


@pytest.mark.parametrize("kind", ["greedy", "temperature_only", "top_k"])
def test_sampler_dispatch_counters_follow_the_requests_in_flight(
        gather_path, rng, kind):
    """``serving/sampler_dispatches.{greedy,draw,sort}`` count each prefill
    and decode dispatch into the tier its requests select at launch: greedy
    traffic moves ``.greedy`` alone; a ``temperature > 0`` request moves
    its prefill and every dispatch it is in flight for to ``.draw``, with
    ``top_k > 0`` to ``.sort``; once it retired the count is ``.greedy``'s
    again, though its slot still holds its temperature and top_k on the
    device."""
    tier = {"greedy": 0, "temperature_only": 1, "top_k": 2}[kind]
    prompts = [list(rng.randint(0, 64, n)) for n in (6, 9)]
    eng = _engine()
    eng.submit(prompts[0], 24)
    t0 = _tiers()
    eng.step()  # the greedy request's prefill and its first dispatch
    np.testing.assert_array_equal(_tiers() - t0, [2, 0, 0])
    second = eng.submit(prompts[1], 6, **SAMPLING.get(kind, {}))
    in_flight = 0
    while second.state != serving.FINISHED:
        t1 = _tiers()
        eng.step()
        moved = _tiers() - t1
        assert moved[tier] == moved.sum() >= 1, moved
        in_flight += int(moved.sum())
    assert in_flight >= 6  # a prefill, five decode dispatches
    t2 = _tiers()
    eng.run()
    eng.close()
    after = _tiers() - t2
    assert after[0] >= 10, "the greedy request ran on"
    assert after[1] == after[2] == 0, \
        "a retired slot's stale temp/top_k kept counting"

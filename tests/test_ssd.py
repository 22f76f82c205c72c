"""Mamba-2's recurrence (``ops/pallas_kernels/ssd.py``): the chunk scan in
both forms (the kernel in the interpreter) and the decode step in both
forms against ``ssd_recurrence`` token by token, at small widths that keep
the served ratios (2 groups, ``N = 2 P``): padded rows, a length that is no
multiple of the chunk, a carried state, inactive slots; the gates; the
counter of the form chosen.

Tolerance: float32 on the CPU, the forms differ in the order of their sums
only. The worst difference read was 4.2e-5 on outputs up to 44 (300 rows,
decays down to e^-0.5 a step): ``TOL`` is relative to the largest output.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import metrics as mx
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import ssd

TOL = 5e-6      # of the largest output


def _case(rng, t, h=4, g=2, n=16, p=8):
    x = rng.randn(t, h, p).astype("float32")
    b, c = (rng.randn(t, g, n).astype("float32") for _ in range(2))
    a = -(rng.rand(t, h) * 0.5).astype("float32")
    return [jnp.asarray(v) for v in (x, b, c, a)]


SCAN_FORMS = {
    "xla": ssd.ssd_chunk_scan_xla,
    "kernel": functools.partial(ssd.ssd_chunk_scan_kernel, interpret=True)}


@pytest.fixture(params=sorted(SCAN_FORMS))
def scan(request):
    return SCAN_FORMS[request.param]


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=TOL * float(np.abs(want).max()))


@pytest.mark.parametrize("t", [1, 50, 128, 300])
def test_the_chunk_scan_equals_the_recurrence(t, scan, rng):
    """One row, under a chunk, a whole chunk, and a length that is no
    multiple of 128 (the tail the scan pads leaves the state alone)."""
    args = _case(rng, t)
    y, s = scan(*args)
    want_y, want_s = ssd.ssd_recurrence(*args)
    assert y.shape == (t, 4, 8) and s.shape == (4, 16, 8)
    _close(y, want_y)
    _close(s, want_s)


def test_padded_rows_leave_the_state_as_the_last_token_left_it(scan, rng):
    """A prompt of 77 rows in a bucket of 256: the rows past the length
    are given a log-decay of 0 and an input of 0, whatever their B and C
    hold, and the state is the 77 rows' own."""
    x, b, c, a = _case(rng, 256)
    valid = jnp.arange(256) < 77
    y, s = scan(jnp.where(valid[:, None, None], x, 0.0), b, c,
                jnp.where(valid[:, None], a, 0.0))
    want_y, want_s = ssd.ssd_recurrence(x[:77], b[:77], c[:77], a[:77])
    _close(y[:77], want_y)
    _close(s, want_s)


def test_the_scan_carries_a_state_and_survives_a_hard_decay(scan, rng):
    """From a given state, with steps that forget everything (e^-40) beside
    steps that forget nothing: every exponent is <= 0, nothing overflows."""
    x, b, c, _ = _case(rng, 200)
    a = jnp.asarray(np.where(rng.rand(200, 4) < 0.3, -40.0, 0.0)
                    .astype("float32"))
    s0 = jnp.asarray(rng.randn(4, 16, 8).astype("float32"))
    y, s = scan(x, b, c, a, s0)
    want_y, want_s = ssd.ssd_recurrence(x, b, c, a, s0)
    assert np.isfinite(np.asarray(y)).all()
    _close(y, want_y)
    _close(s, want_s)


def test_b_and_c_belong_to_the_group_of_the_head(rng):
    """Head h reads group ``h // (H / G)``: with the groups swapped the
    outputs differ, and equal the recurrence given the swap."""
    x, b, c, a = _case(rng, 40)
    y, _ = ssd.ssd_chunk_scan_xla(x, b, c, a)
    swapped, _ = ssd.ssd_chunk_scan_xla(x, b[:, ::-1], c[:, ::-1], a)
    assert float(jnp.abs(y - swapped).max()) > 0.1
    _close(swapped, ssd.ssd_recurrence(x, b[:, ::-1], c[:, ::-1], a)[0])


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0] * 5, [1] * 5,
                                  [0, 0, 0, 0, 1]])
def test_the_step_kernel_equals_plain_xla_and_skips_idle_slots(live, rng):
    """The decode step of a layer in the middle of the buffer, by the
    kernel (interpreted) and in plain XLA, against ONE step of the
    recurrence from the slot's state: an inactive slot's output is 0 and
    its state, and every other layer's, is bit for bit what it was."""
    states = jnp.asarray(rng.randn(3, 5, 4, 16, 8).astype("float32"))
    x, b, c, a = _case(rng, 5)
    active = jnp.asarray(live, bool)
    for form in (ssd.ssd_state_step_xla,
                 functools.partial(ssd.ssd_state_step, interpret=True)):
        y, out = form(states, 1, x, b, c, a, active)
        for slot, on in enumerate(live):
            if not on:
                assert not np.asarray(y[slot]).any()
                np.testing.assert_array_equal(np.asarray(out[1, slot]),
                                              np.asarray(states[1, slot]))
                continue
            want_y, want_s = ssd.ssd_recurrence(
                x[slot][None], b[slot][None], c[slot][None], a[slot][None],
                states[1, slot])
            _close(y[slot], want_y[0])
            _close(out[1, slot], want_s)
        for layer in (0, 2):
            np.testing.assert_array_equal(np.asarray(out[layer]),
                                          np.asarray(states[layer]))


def test_the_gates_name_what_they_refuse():
    assert ssd.ssd_state_step_gate(32, 256, 128, 2) is None    # as served
    assert ssd.ssd_chunk_scan_gate(32, 2, 256, 128) is None
    assert "whole (8, 128)" in ssd.ssd_state_step_gate(32, 256, 96, 2)
    # heads of half a lane tile ride side by side (Nemotron-3's geometry)
    assert ssd.ssd_state_step_gate(64, 128, 64, 8) is None
    assert ssd.ssd_chunk_scan_gate(64, 8, 128, 64) is None
    assert "whole tiles" in ssd.ssd_chunk_scan_gate(64, 8, 128, 96)
    assert "multiple of 8" in ssd.ssd_state_step_gate(24, 256, 128, 2)
    assert "KiB of VMEM" in ssd.ssd_state_step_gate(32, 512, 128, 2)
    assert "do not divide" in ssd.ssd_state_step_gate(30, 256, 128, 4)
    assert "128-lane" in ssd.ssd_chunk_scan_gate(32, 2, 64, 128)
    assert "128-lane" in ssd.ssd_chunk_scan_gate(32, 2, 256, 128, chunk=64)
    assert "more than a grid step" in ssd.ssd_chunk_scan_gate(64, 2, 128,
                                                              128)
    # the interpreter is not bound by the chip's tiling
    assert ssd.ssd_state_step_gate(4, 16, 8, 2, interpret=True) is None
    assert ssd.ssd_chunk_scan_gate(4, 2, 16, 8, interpret=True) is None
    with pytest.raises(ValueError, match="128-lane"):
        ssd.ssd_chunk_scan_kernel(*_case(np.random.RandomState(0), 8))


@pytest.mark.parametrize("form", ["blocked", "kernel"])
def test_the_scan_counts_the_form_it_chose_once_a_traced_call(
        form, rng, monkeypatch):
    """On the CPU the blocked form; on a TPU, where the gate takes the
    geometry, the kernel (here: its interpreter standing in)."""
    if form == "kernel":
        monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
        monkeypatch.setattr(ssd, "ssd_chunk_scan_gate",
                            lambda *a, **k: None)
        monkeypatch.setattr(
            ssd, "ssd_chunk_scan_kernel", functools.partial(
                ssd.ssd_chunk_scan_kernel, interpret=True))
    counter = mx.counter("ssd/scan_calls." + form)
    before = counter.value
    args = _case(rng, 130)
    run = jax.jit(lambda *a: ssd.ssd_chunk_scan(*a))
    y, _ = run(*args)
    run(*args)                       # traced once, run twice
    assert counter.value == before + 1
    _close(y, ssd.ssd_recurrence(*args)[0])

"""Render and gate the paddle_tpu.monitor.numerics observatory.

The CLI face of the streaming tensor-statistics layer
(``PADDLE_TPU_NUMERICS``, paddle_tpu/monitor/numerics.py): per-op range
stats, drift early-warnings, and the persisted amax/scale calibration
tables the int8 KV-page path is gated behind.

    python -m tools.numerics_report --selftest
        <5s, JAX_PLATFORMS=cpu — the ROADMAP/ci_smokes gate:
        (1) armed-stats parity: per-op absmax/mean/rms/zero-fraction from
            the packed device-side fetch match a numpy reference computed
            from the SAME step's fetched tensors on a canned MLP;
        (2) drift drill: an injected activation-scale ramp raises the
            typed :class:`NumericsDriftWarning` (and the
            ``numerics_drift`` flight event naming the ``<slot>:<type>``
            op) at least 2 chunks BEFORE the CHECK_NUMERICS=2 watchdog
            trips on the same ramp;
        (3) calibration round-trip: record/lookup amax+scale through the
            tune-table discipline (atomic publish, running max merge,
            corrupt-table lookups degrade to None, never raise);
        (4) int8 KV decode parity: quantized pages decode within the
            symmetric-int8 tolerance of fp pages at ragged lengths, and
            2x the pages fit under the fp byte budget.

    python -m tools.numerics_report --probe
        Run a tiny armed MLP step in-process and print the per-op stats
        table (what an armed trainer's registries look like).

    python -m tools.numerics_report --table [PATH]
        Render the calibration table at PATH (default: the active
        ``numerics.table_path()`` location).

    python -m tools.numerics_report --flight DUMP.json
        Render the ``numerics_last`` section of a flight-recorder dump —
        the per-op range history embedded next to a NaN trip.
"""

from __future__ import annotations

import json
import math
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_COLS = ("absmax", "mean", "rms", "zero_frac", "subnormal_frac",
         "overflow_frac", "count", "chunks")


def render_stats(snap: dict) -> str:
    """Fixed-width per-op table of a ``numerics.snapshot()`` dict (also
    accepts the ``numerics_last`` section of a flight dump)."""
    if not snap:
        return "(no numerics stats accumulated — is PADDLE_TPU_NUMERICS " \
               "armed?)"
    rows = [("op",) + _COLS]
    for label in sorted(snap, key=lambda s: (len(s.split(":")[0]), s)):
        st = snap[label]
        rows.append((label,) + tuple(
            "%.4g" % st[c] if isinstance(st.get(c), float)
            else str(st.get(c, "-")) for c in _COLS))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                     for r in rows)


def render_table(path=None) -> str:
    """Render the calibration table: one line per (fingerprint, slot,
    type) with amax / scale / bits."""
    from paddle_tpu.monitor import numerics

    path = path or numerics.table_path()
    entries = numerics.read_calibration(path)
    if not entries:
        return "%s: absent, corrupt or empty" % path
    lines = ["calibration table %s (%d entries):" % (path, len(entries))]
    for key in sorted(entries):
        cfg = entries[key].get("config", {})
        lines.append("  %-48s amax=%-12.6g scale=%-12.6g bits=%s"
                     % (key, cfg.get("amax", float("nan")),
                        cfg.get("scale", float("nan")), cfg.get("bits", "?")))
    return "\n".join(lines)


def _probe_once(scale_pow: float = 0.0):
    """One armed MLP train step; returns (numerics snapshot, {var name:
    fetched numpy array}) — the parity leg's two sides come from the SAME
    dispatch, so there is nothing scheduling-dependent to tolerate."""
    import numpy as np

    import paddle_tpu as fluid

    with fluid.unique_name.guard():
        with fluid.scope_guard(fluid.Scope()):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", shape=[8])
                h = fluid.layers.fc(x, size=8, act="relu")
                out = fluid.layers.mean(h)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(0)
            feed = {"x": (2.0 ** scale_pow
                          * rng.randn(4, 8)).astype("float32")}
            fetched = exe.run(main, feed=feed,
                              fetch_list=[out.name, h.name])
            from paddle_tpu.monitor import numerics

            return numerics.snapshot(), dict(zip((out.name, h.name), fetched))


def probe() -> int:
    os.environ.setdefault("PADDLE_TPU_NUMERICS", "1")
    snap, _ = _probe_once()
    print(render_stats(snap))
    return 0


# -- selftest ------------------------------------------------------------------


def _np_reference(arr):
    """The numpy twin of one packed stat row's derived fields."""
    import numpy as np

    a = np.asarray(arr, np.float64)
    av = np.abs(a)
    return {
        "absmax": float(av.max()),
        "mean": float(a.mean()),
        "rms": float(np.sqrt((a * a).mean())),
        "zero_frac": float((a == 0).mean()),
    }


def _selftest_parity():
    """Device-side packed stats == numpy reference on the fetched tensors
    of the same canned MLP step."""
    from paddle_tpu.monitor import numerics

    numerics.reset()
    snap, fetched = _probe_once()
    assert snap, "armed step accumulated no stats"
    relu = [l for l in snap if l.endswith(":relu")]
    assert len(relu) == 1, "expected one relu entry, got %r" % (sorted(snap),)
    got = snap[relu[0]]
    h = next(v for v in fetched.values() if v.size > 1)
    want = _np_reference(h)
    for fld, ref in want.items():
        assert math.isclose(got[fld], ref, rel_tol=1e-5, abs_tol=1e-7), (
            "stats parity: %s %s=%.8g, numpy reference %.8g"
            % (relu[0], fld, got[fld], ref))
    assert got["count"] == h.size, (got["count"], h.size)
    mean = [l for l in snap if l.endswith(":mean")]
    assert len(mean) == 1
    loss = next(v for v in fetched.values() if v.size == 1)
    assert math.isclose(snap[mean[0]]["absmax"], abs(float(loss)),
                        rel_tol=1e-5), "mean-op absmax != fetched loss"
    return len(snap)


def _selftest_drift(tmp):
    """The acceptance drill: an activation-scale ramp raises the typed
    drift warning (flight event carries the named op) >= 2 chunks before
    the CHECK_NUMERICS=2 watchdog trips on the same ramp."""
    import warnings

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.enforce import EnforceNotMet
    from paddle_tpu.monitor import device as dev, numerics

    numerics.reset()
    os.environ["PADDLE_TPU_CHECK_NUMERICS"] = "2"
    os.environ["PADDLE_TPU_FLIGHT_DIR"] = tmp
    try:
        with fluid.unique_name.guard():
            with fluid.scope_guard(fluid.Scope()):
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup):
                    x = fluid.layers.data("x", shape=[4])
                    h = fluid.layers.scale(x, scale=2.0)
                    out = fluid.layers.mean(h)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                warn_chunk = trip_chunk = None
                events = []
                with warnings.catch_warnings(record=True) as wlog:
                    warnings.simplefilter("always")
                    for i in range(40):
                        feed = {"x": np.full((2, 4), 2.0 ** (16 * i),
                                             "float32")}
                        try:
                            exe.run(main, feed=feed, fetch_list=[out])
                        except EnforceNotMet:
                            trip_chunk = i
                            break
                        if warn_chunk is None and any(
                                isinstance(w.message,
                                           numerics.NumericsDriftWarning)
                                for w in wlog):
                            warn_chunk = i
                            events = numerics.drain_drift_events()
        assert warn_chunk is not None, "ramp never raised a drift warning"
        assert trip_chunk is not None, "ramp never tripped the watchdog"
        assert warn_chunk <= trip_chunk - 2, (
            "drift warning must lead the watchdog by >= 2 chunks: "
            "warned at %d, tripped at %d" % (warn_chunk, trip_chunk))
        scale_evs = [e for e in events if e["op"].endswith(":scale")]
        assert scale_evs, "no drift event named the scale op: %r" % events
        assert scale_evs[0]["kind"] == "trending-toward-overflow"
        # the same event landed in the flight ring with the named op
        fr = dev.flight_recorder()
        assert fr is not None
        ring = [e for e in fr._entries
                if e.get("event") == "numerics_drift"
                and e.get("op", "").endswith(":scale")]
        assert ring, "numerics_drift flight event missing the named op"
        assert ring[0]["drift_kind"] == "trending-toward-overflow"
        return warn_chunk, trip_chunk
    finally:
        os.environ.pop("PADDLE_TPU_CHECK_NUMERICS", None)
        os.environ.pop("PADDLE_TPU_FLIGHT_DIR", None)


def _selftest_calibration(tmp):
    """Round-trip + corruption tolerance of the calibration table."""
    from paddle_tpu.monitor import numerics

    path = os.path.join(tmp, "calib.json")
    assert numerics.lookup_amax("fp0", "3", "matmul", path=path) is None
    numerics.record_calibration("fp0", "3", "matmul", 7.5, path=path)
    got = numerics.lookup_amax("fp0", "3", "matmul", path=path)
    assert got == 7.5, got
    scale = numerics.lookup_scale("fp0", "3", "matmul", path=path)
    assert math.isclose(scale, 7.5 / 127.0), scale
    # merge is a running max: a smaller later amax must not shrink it
    numerics.record_calibration("fp0", "3", "matmul", 2.0, path=path)
    assert numerics.lookup_amax("fp0", "3", "matmul", path=path) == 7.5
    numerics.record_calibration("fp0", "3", "matmul", 9.0, path=path)
    assert numerics.lookup_amax("fp0", "3", "matmul", path=path) == 9.0
    # the KV pair helpers the serving int8 gate consults
    fp = numerics.kv_fingerprint(2, 4, 16, "float32")
    assert numerics.kv_scale(fp, path=path) is None
    numerics.record_kv_calibration(fp, 3.0, 4.0, path=path)
    ks, vs = numerics.kv_scale(fp, path=path)
    assert math.isclose(ks, 3.0 / 127.0) and math.isclose(vs, 4.0 / 127.0)
    # the report renderer covers every entry
    txt = render_table(path)
    assert "amax=9" in txt and str(len(
        numerics.read_calibration(path))) in txt
    # corruption: truncated JSON degrades every lookup to None, no raise
    with open(path, "w") as f:
        f.write('{"format": "paddle_tpu.numerics/1", "entr')
    assert numerics.lookup_amax("fp0", "3", "matmul", path=path) is None
    assert numerics.kv_scale(fp, path=path) is None
    # foreign format tag is corruption too (a tune table is NOT a
    # calibration table, even though the file machinery is shared)
    from paddle_tpu.tune import table as tbl

    tbl.write_entries(path, {tbl.entry_key("k", "b", "d"): {"config": {}}})
    assert numerics.read_calibration(path) is None


def _selftest_int8_kv():
    """Quantized pages decode within the symmetric-int8 tolerance of fp
    pages at ragged lengths; double the pages fit under the fp budget."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving.kv_cache import Int8PagedKVCache, PagedKVCache

    n_layer, n_head, d_head = 2, 2, 8
    slots, max_ctx, ps, npg = 2, 32, 8, 8
    amax = 3.0
    rng = np.random.RandomState(0)
    fp = PagedKVCache(n_layer, n_head, d_head, slots, max_ctx, ps, npg)
    q8 = Int8PagedKVCache(n_layer, n_head, d_head, slots, max_ctx, ps, npg,
                          k_scale=amax / 127.0, v_scale=amax / 127.0)
    sf, si = fp.init_state(), q8.init_state()
    pt = np.arange(slots * (max_ctx // ps), dtype=np.int32).reshape(
        slots, max_ctx // ps)
    sf = {**sf, "pt": jnp.array(pt)}
    si = {**si, "pt": jnp.array(pt)}
    lens = (13, 5)  # ragged, page-straddling
    for slot, plen in enumerate(lens):
        dest = jnp.array(pt[slot])
        for layer in range(n_layer):
            k = jnp.array(rng.uniform(-amax, amax, (plen, n_head, d_head)),
                          jnp.float32)
            v = jnp.array(rng.uniform(-amax, amax, (plen, n_head, d_head)),
                          jnp.float32)
            sf = fp.write_prompt(sf, layer, k, v, dest, jnp.int32(plen))
            si = q8.write_prompt(si, layer, k, v, dest, jnp.int32(plen))
    # per-element context error bounded by half a quantization step
    step_tol = amax / 127.0 * 0.51
    for layer in range(n_layer):
        kf, vf = fp.context(sf, layer)
        ki, vi = q8.context(si, layer)
        assert float(jnp.abs(kf - ki).max()) <= step_tol
        assert float(jnp.abs(vf - vi).max()) <= step_tol
    # decode parity within tolerance on BOTH paths (gather context above,
    # fused decode_attention here)
    q = jnp.array(rng.randn(slots, n_head, d_head), jnp.float32)
    ctx_len = jnp.array(lens, jnp.int32)
    live = jnp.ones((slots,), jnp.bool_)
    of = fp.decode_attention(sf, 0, q, ctx_len, live, sm_scale=0.3)
    oi = q8.decode_attention(si, 0, q, ctx_len, live, sm_scale=0.3)
    err = float(jnp.abs(of - oi).max())
    assert err < 0.05, "int8 decode attention error %.4g" % err
    # the capacity win: int8 at 2x the pages still fits under the fp
    # byte budget (half the bf16 page bytes, a quarter of fp32)
    q8x2 = Int8PagedKVCache(n_layer, n_head, d_head, slots, max_ctx, ps,
                            2 * npg, k_scale=0.1, v_scale=0.1)
    fp_bytes = fp.cache_bytes(fp.init_state())
    i8x2_bytes = q8x2.cache_bytes(q8x2.init_state())
    assert i8x2_bytes <= fp_bytes, (i8x2_bytes, fp_bytes)
    assert q8x2.num_pages == 2 * fp.num_pages
    # uncalibrated scales are a hard constructor error (the gate that
    # keeps an uncalibrated grid from silently clipping)
    try:
        Int8PagedKVCache(n_layer, n_head, d_head, slots, max_ctx, ps, npg,
                         k_scale=0.0, v_scale=1.0)
        raise AssertionError("zero scale accepted")
    except ValueError:
        pass
    return err, i8x2_bytes, fp_bytes


def selftest() -> int:
    import tempfile
    import time

    t0 = time.time()
    os.environ["PADDLE_TPU_NUMERICS"] = "1"
    # The drills assert per-chunk behaviour (EMA ticks, parity over every
    # run) — disable the default every-4-chunks sampling cadence.
    os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
    os.environ.pop("PADDLE_TPU_NUMERICS_TABLE", None)
    try:
        n_ops = _selftest_parity()
        with tempfile.TemporaryDirectory(prefix="numerics_drift_") as tmp:
            warn_chunk, trip_chunk = _selftest_drift(tmp)
        with tempfile.TemporaryDirectory(prefix="numerics_calib_") as tmp:
            _selftest_calibration(tmp)
        err, i8x2, fpb = _selftest_int8_kv()
    finally:
        os.environ.pop("PADDLE_TPU_NUMERICS", None)
        os.environ.pop("PADDLE_TPU_NUMERICS_EVERY", None)
        from paddle_tpu.monitor import numerics

        numerics.reset()
    print("numerics_report selftest: OK (%.1fs)  stats parity over %d ops; "
          "drift warned chunk %d vs watchdog trip %d; calibration "
          "round-trip; int8 KV err %.4g with 2x pages %dB <= fp %dB"
          % (time.time() - t0, n_ops, warn_chunk, trip_chunk, err,
             i8x2, fpb))
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0 if argv else 2
    if argv[0] == "--selftest":
        return selftest()
    if argv[0] == "--probe":
        return probe()
    if argv[0] == "--table":
        print(render_table(argv[1] if len(argv) > 1 else None))
        return 0
    if argv[0] == "--flight":
        if len(argv) < 2:
            print("--flight needs a dump path", file=sys.stderr)
            return 2
        with open(argv[1]) as f:
            doc = json.load(f)
        snap = doc.get("numerics_last")
        if not snap:
            print("%s: no numerics_last section (dump written without "
                  "PADDLE_TPU_NUMERICS armed)" % argv[1])
            return 1
        print(render_stats(snap))
        return 0
    print("unknown flag %r" % argv[0], file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())

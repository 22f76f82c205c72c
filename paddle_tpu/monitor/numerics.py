"""paddle_tpu.monitor.numerics — device-side streaming tensor statistics.

The observability column so far answers *that* a step got slow
(``metrics``/``runlog``) or *that* a NaN appeared (``device``'s
CHECK_NUMERICS watchdog). This module sees tensor *values*: per-op range
statistics streamed off the device, drift detection that warns BEFORE the
watchdog cliff, and the amax/scale calibration tables low-precision paths
(the int8 KV-page write path in ``serving/kv_cache.py``) are gated behind.

Level-gated by ``PADDLE_TPU_NUMERICS`` (one env read per run):

``0``  off (default) — nothing traced, plan/compile caches unpolluted,
       losses bit-identical to a build without this module.
``1``  stats — the Executor compiles a stats variant of the step: every
       op's floating outputs fold a compact stat row (absmax, sum, sumsq,
       zero/subnormal/overflow-proximity counts, element count) into a
       packed ``[K, 7]`` auxiliary fetch riding the compiled step — ONE
       extra device→host copy per ``run``/``run_steps`` chunk, no
       per-tensor syncs. Op identity is the same ``<slot>:<type>`` stamp
       the watchdog and named scopes use. Host side: per-op ``numerics/*``
       gauges, a log-bucketed absmax range histogram, and an EMA drift
       detector — an op's absmax trending toward its dtype's max (or
       collapsing to zero) raises :class:`NumericsDriftWarning`, records a
       ``numerics_drift`` flight event and queues a typed early-warning
       the optional :class:`~paddle_tpu.reliability.sentinel
       .DivergenceSentinel` ``drift`` rule can trip on.
``2``  calibrate — level 1 plus persistent per-tensor amax/scale tables,
       written with the tune-table discipline (JSON keyed
       ``(program fingerprint, op slot, op type)``, atomic publish,
       never-raise lookups; the file machinery IS ``tune.table``'s,
       parameterized by format tag).

``tools/numerics_report.py`` is the CLI (``--selftest`` gates CI);
``benchmarks/diag_overhead.py --numerics`` measures the armed-stats
overhead against the ≤15% contract.
"""

from __future__ import annotations

import math
import os
import threading
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import metrics as _mx

__all__ = [
    "FORMAT", "NUM_STATS", "STAT_FIELDS", "OVERFLOW_FRACTION",
    "STATS_ENV_KEY",
    "stats_level",
    "fold_op_stats", "merge_stat_rows",
    "accumulate", "snapshot", "drain_drift_events", "reset",
    "NumericsDriftWarning",
    "table_path", "read_calibration", "record_calibration",
    "lookup_amax", "lookup_scale",
    "kv_fingerprint", "record_kv_calibration", "kv_scale",
]

# calibration-table format tag (tune.table validates it; see table_path)
FORMAT = "paddle_tpu.numerics/1"

# the env key stat rows accumulate under inside the traced name->array
# environment — the stats twin of interpreter.NUMERICS_ENV_KEY (same
# legal-aux-flow argument); defined here so executor + interpreter share it
STATS_ENV_KEY = "__numerics_stats__"

# packed per-op stat row layout (float32, device side):
#   0 absmax   max(|x|) over the op's floating outputs
#   1 sum      Σx       (f32 accumulation, bf16-safe)
#   2 sumsq    Σx²
#   3 zeros    count(x == 0)
#   4 subnorm  count(0 < |x| < dtype.tiny)
#   5 near_of  count(|x| > OVERFLOW_FRACTION * dtype.max)
#   6 count    element count
STAT_FIELDS = ("absmax", "sum", "sumsq", "zeros", "subnormal",
               "near_overflow", "count")
NUM_STATS = len(STAT_FIELDS)

# |x| beyond this fraction of the output dtype's finite max counts toward
# the overflow-proximity fraction (1/16 = within 4 doublings of the cliff)
OVERFLOW_FRACTION = 0.0625

_m_chunks = _mx.counter(
    "numerics/chunks",
    help="fetched stats chunks accumulated (one per run/run_steps dispatch "
         "with PADDLE_TPU_NUMERICS armed)")
_m_drift = _mx.counter(
    "numerics/drift_warnings",
    help="EMA drift early-warnings raised (absmax trending toward overflow "
         "or collapsing to zero) BEFORE the CHECK_NUMERICS watchdog trips")
_m_calib_writes = _mx.counter(
    "numerics/calibration_writes",
    help="atomic calibration-table publishes (PADDLE_TPU_NUMERICS=2)")
# absmax spans subnormals to bf16-overflow pressure — log-spaced buckets
# (metrics.log_buckets, the satellite this histogram exists to exercise)
_m_absmax = _mx.histogram(
    "numerics/absmax",
    buckets=_mx.log_buckets(1e-8, 1e4, per_decade=1),
    help="per-op per-chunk absmax samples, log-bucketed 1e-8..1e4")

_lock = threading.RLock()
# label -> last accumulated stats dict (the snapshot/flight-embed surface)
_last: Dict[str, Dict[str, Any]] = {}
# label -> EMA drift state
_ema: Dict[str, Dict[str, float]] = {}
# typed early warnings not yet drained by a sentinel
_pending: List[dict] = []
_warned: set = set()  # (label, kind) pairs already python-warned
# (fingerprint) -> {(slot, type): amax} pending calibration maxima
_calib: Dict[str, Dict[Tuple[str, str], float]] = {}
#: per-label resolved gauge tuples (accumulate() hot-path cache)
_gauges: Dict[str, tuple] = {}


class NumericsDriftWarning(UserWarning):
    """An op's activation range is drifting toward overflow (or collapsing
    to zero): the typed early warning raised ahead of the CHECK_NUMERICS
    watchdog. Carries ``label``/``kind``/``absmax``/``chunks_to_overflow``
    as attributes for programmatic consumers."""

    def __init__(self, label: str, kind: str, absmax: float,
                 chunks_to_overflow: Optional[float] = None):
        self.label = label
        self.kind = kind
        self.absmax = absmax
        self.chunks_to_overflow = chunks_to_overflow
        horizon = ("" if chunks_to_overflow is None else
                   " (~%.1f chunks to overflow)" % chunks_to_overflow)
        super().__init__(
            "numerics drift: op %s absmax %.4g %s%s — raise "
            "PADDLE_TPU_CHECK_NUMERICS tolerance work now, not after the "
            "watchdog trips" % (label, absmax, kind, horizon))


def stats_level() -> int:
    """``PADDLE_TPU_NUMERICS`` clamped to 0..2 (module docstring); read
    per call — the executor reads it once per run as part of plan-key
    construction, which is the whole level-0 cost."""
    raw = os.environ.get("PADDLE_TPU_NUMERICS", "0").strip()
    try:
        lvl = int(raw)
    except ValueError:
        lvl = 1 if raw.lower() in ("true", "yes", "on") else 0
    return max(0, min(2, lvl))


#: ``PADDLE_TPU_NUMERICS_EVERY`` — fold stats every Nth run/run_steps
#: chunk (default 4, chunk 0 always sampled). Per-op in-graph stat
#: reductions are memory-bound; sampling divides their steady-state cost
#: by N while the EMA drift detector and calibration maxima still see a
#: regular tick stream. Set to 1 to observe every chunk (the drift
#: drill and the parity tests do).
EVERY_ENV_KEY = "PADDLE_TPU_NUMERICS_EVERY"
DEFAULT_EVERY = 4


def stats_every() -> int:
    raw = os.environ.get(EVERY_ENV_KEY, "").strip()
    if not raw:
        return DEFAULT_EVERY
    try:
        return max(1, int(raw))
    except ValueError:
        return DEFAULT_EVERY


# -- device side (called at jit-trace time from the block interpreter) --------


def merge_stat_rows(a, b):
    """Merge two packed stat rows: absmax by max, everything else by sum.
    Used across an op's multiple outputs and across the gradient-
    accumulation scan's microbatches (executor ``_mb_step``)."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.maximum(a[:1], b[:1]), a[1:] + b[1:]])


_stat_reduce = None


def _build_stat_reduce():
    """Build the stat reduction lazily (keeps jax out of module import).

    All seven stats come out of ONE variadic ``lax.reduce`` — a single
    kernel per observed op.  That matters more than per-element speed:
    on XLA CPU each separate in-graph reduction kernel pays a cold-cache
    pass over the tensor plus dispatch, so seven ``jnp.sum``/``jnp.max``
    calls per op cost ~3-6x the fused form and blow the diag_overhead
    15% contract.  The reduce is wrapped in a ``custom_jvp`` with a zero
    tangent: stats are diagnostics, not part of the loss, and the
    variadic-reduce JVP rule rejects the symbolic zero tangents it would
    otherwise be handed under ``value_and_grad``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.custom_jvp
    def _reduce(vf, tiny, ovf):
        av = jnp.abs(vf)
        operands = (
            av,
            vf,
            vf * vf,
            (vf == 0).astype(jnp.float32),
            ((av < tiny) & (vf != 0)).astype(jnp.float32),
            (av > ovf).astype(jnp.float32),
        )
        inits = (jnp.float32(-jnp.inf),) + (jnp.float32(0),) * 5

        def _comp(a, b):
            return (jnp.maximum(a[0], b[0]), a[1] + b[1], a[2] + b[2],
                    a[3] + b[3], a[4] + b[4], a[5] + b[5])

        red = lax.reduce(operands, inits, _comp, (0,))
        return jnp.stack(list(red) + [jnp.float32(vf.size)])

    @_reduce.defjvp
    def _reduce_jvp(primals, tangents):
        out = _reduce(*primals)
        return out, jnp.zeros_like(out)

    return _reduce


def _stat_row(v):
    """Packed [7] stat row for one tensor: absmax, sum, sumsq, zeros,
    subnormal, near_overflow, count — all exact, one fused kernel."""
    import jax.numpy as jnp
    from jax import lax

    global _stat_reduce
    if _stat_reduce is None:
        _stat_reduce = _build_stat_reduce()
    fi = jnp.finfo(v.dtype)
    vf = lax.stop_gradient(v).astype(jnp.float32).ravel()
    return _stat_reduce(vf, jnp.float32(fi.tiny),
                        jnp.float32(OVERFLOW_FRACTION * float(fi.max)))


def fold_op_stats(op, env: Dict[str, Any], layout, pos: int) -> None:
    """Fold each floating output of ``op`` into one packed stat row
    appended to ``env[STATS_ENV_KEY]``; record ``(label, outputs,
    min-dtype-max)`` at the same index in ``layout`` (index-overwrite, the
    watchdog's retrace-stability idiom)."""
    import jax.numpy as jnp

    row = None
    outs = []
    fmax = None
    for name in op.output_arg_names:
        v = env.get(name)
        dt = getattr(v, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.floating):
            continue
        if v.size == 0:
            continue
        # Only the op's FIRST floating output -- its primary result -- is
        # folded.  Secondary outputs (optimizer moment buffers, auxiliary
        # softmax tensors) would triple the folded volume on optimizer ops
        # and blow the diag_overhead 15% contract without adding signal:
        # drift in optimizer state always shows up in the param output too.
        row = _stat_row(v)
        fmax = float(jnp.finfo(dt).max)
        outs.append(name)
        break
    if row is None:
        return
    rows = env.setdefault(STATS_ENV_KEY, [])
    k = len(rows)
    slot = op.attrs.get("__op_slot__")
    entry = ("%d:%s" % (pos if slot is None else slot, op.type),
             tuple(outs), fmax)
    if k < len(layout):
        layout[k] = entry
    else:
        layout.append(entry)
    rows.append(row)


# -- host side: accumulation + drift ------------------------------------------


def _drift_params() -> Tuple[float, float, float]:
    """(ema_decay, horizon_chunks, min_trend_bits) — env-tunable but the
    defaults are the contract the selftest drill pins."""
    def _f(name, default):
        try:
            return float(os.environ.get(name, "") or default)
        except ValueError:
            return default
    return (_f("PADDLE_TPU_NUMERICS_EMA", 0.5),
            _f("PADDLE_TPU_NUMERICS_HORIZON", 8.0),
            _f("PADDLE_TPU_NUMERICS_MIN_TREND", 0.25))


def _emit_drift(label: str, kind: str, absmax: float,
                chunks_to_overflow: Optional[float]) -> None:
    _m_drift.inc()
    ev = {"op": label, "kind": kind, "absmax": float(absmax),
          "chunks_to_overflow": chunks_to_overflow}
    _pending.append(ev)
    if len(_pending) > 256:  # bounded: a sentinel-less run must not leak
        del _pending[:len(_pending) - 256]
    try:
        from .device import flight_recorder

        fr = flight_recorder()
        if fr is not None:
            # "kind" would collide with record_event's own kind parameter
            fr.record_event("numerics_drift", op=label, drift_kind=kind,
                            absmax=float(absmax),
                            chunks_to_overflow=chunks_to_overflow)
    except Exception:
        pass
    if (label, kind) not in _warned:
        _warned.add((label, kind))
        warnings.warn(NumericsDriftWarning(label, kind, absmax,
                                           chunks_to_overflow),
                      stacklevel=3)


def _drift_update(label: str, absmax: float, fmax: Optional[float]) -> None:
    """One EMA tick per fetched chunk for one op: track log2(absmax) and
    its per-chunk trend; warn when the projected chunks-to-overflow drops
    inside the horizon, or when a previously-live range collapses to 0."""
    decay, horizon, min_trend = _drift_params()
    st = _ema.get(label)
    if not math.isfinite(absmax):
        # the watchdog owns non-finite attribution; drift is about the ramp
        return
    if absmax <= 0.0:
        if st is not None and st.get("log2", -1e9) > -20.0:
            _emit_drift(label, "collapsed-to-zero", absmax, None)
            _ema[label] = {"log2": -1e9, "trend": 0.0, "chunks": 0}
        return
    l2 = math.log2(absmax)
    if st is None or st.get("log2", -1e9) <= -1e8:
        _ema[label] = {"log2": l2, "trend": 0.0, "chunks": 1}
        return
    delta = l2 - st["log2"]
    st["log2"] = st["log2"] + decay * (l2 - st["log2"])
    st["trend"] = st["trend"] + decay * (delta - st["trend"])
    st["chunks"] += 1
    if fmax is None or st["chunks"] < 3:
        return  # need history before a trend is evidence
    trend = st["trend"]
    if trend > min_trend:
        to_go = (math.log2(fmax) - l2) / trend
        if to_go <= horizon:
            _emit_drift(label, "trending-toward-overflow", absmax, to_go)


def accumulate(arr, layout: Sequence[Tuple[str, tuple, Optional[float]]],
               fingerprint: Optional[str] = None,
               driver: str = "run") -> None:
    """Fold one fetched stats tensor into the host registries.

    ``arr``: float32 ``[K, NUM_STATS]`` (one step) or ``[steps, K,
    NUM_STATS]`` (a fused run_steps chunk — reduced to per-chunk
    aggregates here, so drift sees one EMA tick per chunk either way).
    ``layout``: the compiled step's trace-time record — row k is
    ``(label, output names, min dtype max)``. Never raises into the step
    (the step already succeeded; losing a stats sample is acceptable,
    killing the run is not)."""
    import numpy as np

    try:
        a = np.asarray(arr, np.float64)  # THE one device→host stats copy
        if a.ndim == 2:
            a = a[None]
        if a.ndim != 3 or a.shape[-1] != NUM_STATS:
            return
        # tolist() once: per-element float() on numpy scalars is ~10x the
        # cost and this path runs on every run()/run_steps chunk.
        absmax = a[:, :, 0].max(axis=0).tolist()
        sums = a[:, :, 1:].sum(axis=0).tolist()
        mx_on = _mx._enabled
        calibrate = stats_level() >= 2 and fingerprint is not None
        with _lock:
            _m_chunks.inc()
            for k in range(a.shape[1]):
                if k < len(layout):
                    label, outs, fmax = layout[k]
                else:
                    label, outs, fmax = "?%d:?" % k, (), None
                am = absmax[k]
                s, ss, zeros, sub, near, n = sums[k]
                if n <= 0.0:
                    # the all-zero placeholder a stats-armed step packs
                    # when the program has no floating outputs (e.g. a
                    # startup program of int fills) — not an op
                    continue
                n = max(n, 1.0)
                stats = {
                    "absmax": am,
                    "mean": s / n,
                    "rms": math.sqrt(max(ss / n, 0.0)),
                    "zero_frac": zeros / n,
                    "subnormal_frac": sub / n,
                    "overflow_frac": near / n,
                    "count": n,
                    "outputs": list(outs),
                    "dtype_max": fmax,
                    "driver": driver,
                }
                prev = _last.get(label)
                stats["chunks"] = (prev["chunks"] + 1) if prev else 1
                _last[label] = stats
                if mx_on:
                    gs = _gauges.get(label)
                    if gs is None:
                        # registry lookups + name formatting are the hot
                        # cost at one chunk per step; resolve each label's
                        # six gauges once and keep the objects.
                        pfx = "numerics/%s/" % label
                        gs = tuple(_mx.gauge(pfx + f) for f in (
                            "absmax", "mean", "rms", "zero_frac",
                            "subnormal_frac", "overflow_frac"))
                        _gauges[label] = gs
                    gs[0].set(am if math.isfinite(am) else 0.0)
                    gs[1].set(stats["mean"])
                    gs[2].set(stats["rms"])
                    gs[3].set(stats["zero_frac"])
                    gs[4].set(stats["subnormal_frac"])
                    gs[5].set(stats["overflow_frac"])
                    if math.isfinite(am) and am > 0:
                        _m_absmax.observe(am)
                _drift_update(label, am, fmax)
                if calibrate and math.isfinite(am):
                    slot, _, typ = label.partition(":")
                    pend = _calib.setdefault(fingerprint, {})
                    key = (slot, typ)
                    pend[key] = max(pend.get(key, 0.0), am)
            if calibrate:
                _flush_calibration()
    except Exception:  # pragma: no cover - belt and braces
        from ..log import vlog

        vlog(1, "numerics: stats accumulation failed for one chunk "
                "(driver=%s); sample dropped", driver)


def snapshot() -> Dict[str, dict]:
    """{op label: latest accumulated stats} — the flight-dump /
    run-ledger embed and the ``tools/numerics_report`` surface."""
    with _lock:
        return {k: dict(v) for k, v in _last.items()}


def drain_drift_events() -> List[dict]:
    """Return-and-clear the queued typed early warnings — the
    ``DivergenceSentinel(drift=True)`` rule's feed."""
    with _lock:
        out = list(_pending)
        del _pending[:]
    return out


def reset() -> None:
    """Drop accumulated stats, EMA state and pending warnings (tests)."""
    with _lock:
        _last.clear()
        _ema.clear()
        _gauges.clear()
        del _pending[:]
        _warned.clear()
        _calib.clear()


# -- calibration tables (tune-table discipline, parameterized format) ---------


def table_path() -> str:
    """Where the calibration table lives: ``PADDLE_TPU_NUMERICS_TABLE``
    wins; else ``numerics_calib.json`` in the persistent compile cache's
    directory (``JAX_COMPILATION_CACHE_DIR``, else the checkout's fixed
    ``.jax_cache``)."""
    p = os.environ.get("PADDLE_TPU_NUMERICS_TABLE", "").strip()
    if p:
        return p
    from ..compile_cache import compile_cache_dir

    return os.path.join(compile_cache_dir(), "numerics_calib.json")


def read_calibration(path: Optional[str] = None) -> Optional[Dict[str, dict]]:
    """Entries of the calibration table (mtime-cached, corruption logged
    once and tolerated — ``tune.table.read_entries`` with this module's
    format tag), or None when absent/corrupt/unconfigured."""
    from ..tune import table as _tbl

    return _tbl.read_entries(path or table_path(), fmt=FORMAT)


def record_calibration(fingerprint: str, slot: str, typ: str, amax: float,
                       *, bits: int = 8,
                       path: Optional[str] = None) -> str:
    """Merge one per-tensor amax into the table (running max against any
    existing entry; read-modify-write, atomic publish). The stored
    ``scale`` is the symmetric int-``bits`` quantization step
    ``amax / (2**(bits-1) - 1)``. Returns the table path."""
    from ..tune import table as _tbl

    path = path or table_path()
    qmax = float(2 ** (bits - 1) - 1)
    with _lock:
        entries = dict(read_calibration(path) or {})
        key = _tbl.entry_key(fingerprint, slot, typ)
        old = entries.get(key)
        if old is not None:
            try:
                amax = max(amax, float(old["config"].get("amax", 0.0)))
            except (TypeError, ValueError):
                pass
        entries[key] = {"config": {
            "amax": float(amax),
            "scale": float(amax) / qmax if amax > 0 else 0.0,
            "bits": int(bits),
        }}
        out = _tbl.write_entries(path, entries, fmt=FORMAT)
    if _mx._enabled:
        _m_calib_writes.inc()
    return out


def _flush_calibration() -> None:
    """Publish pending in-memory amax maxima (called under _lock from
    ``accumulate`` at level 2)."""
    path = table_path()
    for fp, pend in _calib.items():
        for (slot, typ), amax in pend.items():
            record_calibration(fp, slot, typ, amax, path=path)
    _calib.clear()


def lookup_amax(fingerprint: str, slot: str, typ: str,
                path: Optional[str] = None) -> Optional[float]:
    """Calibrated amax for ``(fingerprint, slot, type)`` or None. NEVER
    raises — a corrupt/absent table degrades to None, because consumers
    (the int8 KV gate) must come up regardless."""
    try:
        from ..tune import table as _tbl

        entries = read_calibration(path)
        if not entries:
            return None
        ent = entries.get(_tbl.entry_key(fingerprint, slot, typ))
        if ent is None:
            return None
        v = float(ent["config"]["amax"])
        return v if math.isfinite(v) and v > 0 else None
    except Exception:
        return None


def lookup_scale(fingerprint: str, slot: str, typ: str, *, bits: int = 8,
                 path: Optional[str] = None) -> Optional[float]:
    """Symmetric int-``bits`` quantization scale from the calibrated amax,
    or None when uncalibrated (the caller keeps its fp path)."""
    amax = lookup_amax(fingerprint, slot, typ, path=path)
    if amax is None:
        return None
    return amax / float(2 ** (bits - 1) - 1)


# -- KV-cache calibration (the serving int8 gate) -----------------------------


def kv_fingerprint(n_layer: int, n_head: int, d_head: int, dtype) -> str:
    """Stable identity for a model's KV tensors — the calibration-table
    fingerprint the serving engine keys its int8 gate on (a Program
    fingerprint doesn't exist for the AOT serving path)."""
    import hashlib

    h = hashlib.sha1(("kv|%d|%d|%d|%s" % (
        int(n_layer), int(n_head), int(d_head), str(dtype))).encode())
    return h.hexdigest()[:16]


def record_kv_calibration(fingerprint: str, k_amax: float, v_amax: float,
                          path: Optional[str] = None) -> Optional[str]:
    """Persist a KV-cache calibration pass's amax pair under
    ``(fingerprint, "kv", "k"/"v")``."""
    out = record_calibration(fingerprint, "kv", "k", float(k_amax), path=path)
    record_calibration(fingerprint, "kv", "v", float(v_amax), path=path)
    return out


def kv_scale(fingerprint: str,
             path: Optional[str] = None) -> Optional[Tuple[float, float]]:
    """(k_scale, v_scale) int8 steps from a calibrated KV amax pair, or
    None when either half is uncalibrated — the never-raise gate
    ``ServingConfig(kv_dtype="int8")`` consults before swapping in the
    quantized page pool."""
    ks = lookup_scale(fingerprint, "kv", "k", path=path)
    vs = lookup_scale(fingerprint, "kv", "v", path=path)
    if ks is None or vs is None or ks <= 0 or vs <= 0:
        return None
    return ks, vs

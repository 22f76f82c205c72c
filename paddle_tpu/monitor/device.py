"""paddle_tpu.monitor.device — device-side profiling, attribution & post-mortem.

PR 1 made the *host* observable (metrics registry, span tracer); everything
past ``jax.jit`` stayed a black box: one opaque step span, a NaN report that
could only name a fetch after a full-model host copy, and collectives nobody
counted. This module is the device-side layer, four pieces:

1. **Per-op attribution** — the block interpreter wraps every op impl in
   ``jax.named_scope("<slot>:<type>")`` (``PADDLE_TPU_OP_SCOPES=0``
   disables), so lowered HLO, xprof device traces and
   ``compiled.cost_analysis()`` carry Program-op identity. ``<slot>`` is the
   op's position in the SOURCE program, frozen by
   ``passes.analysis.stamp_op_slots`` before the trace-time optimizer
   mutates the clone — DCE/CSE renumbering never shifts reported identities.
   The Executor's ``prepare``/AOT path (and ``PADDLE_TPU_DEVICE_PROFILE=1``
   on a compile miss) publishes ``cost_analysis()`` + ``memory_analysis()``
   of the compiled step as the ``device_profile/*`` gauges;
   :func:`step_report` is the per-op roofline table.

2. **In-graph numerics watchdog** — ``PADDLE_TPU_CHECK_NUMERICS``:
   ``0`` off; ``1`` the post-step check is ONE fused device-side
   ``isfinite`` reduction (a single scalar sync — replaces the legacy
   every-tensor-to-numpy scan, same error message); ``2`` compiles a
   guarded step variant where each op's floating outputs feed a per-op
   ``isfinite`` bit into one packed device-side mask fetched once per step,
   so a NaN/Inf is attributed to the ORIGINATING Program op by
   ``<slot>:<type>`` without per-tensor host syncs — including under the
   fused ``run_steps`` driver, where the mask comes back per fused step.
   ``FLAGS_check_nan_inf`` implies level >= 1.

3. **Collective traffic accounting** — the explicit collective emission
   sites (``parallel/pipeline.py`` / ``parallel/ring_attention.py``
   ppermutes, ``core/sparse.py`` all_to_alls) call
   :func:`record_collective` at TRACE time, so the
   ``collectives/<op>/bytes`` counters hold the per-device bytes ONE step
   moves through each compiled program (reset before measuring; a
   recompile records again). GSPMD-inserted collectives (dp grad
   all-reduce etc.) are not visible here — they show up in xprof and the
   ``device_profile`` totals instead.

4. **Flight recorder** — with ``PADDLE_TPU_FLIGHT_DIR`` set, the Executor
   records a ring buffer of the last N steps (feed shapes/dtypes, program
   fingerprint, opt-pass gate set, metrics snapshot) and dumps it as JSON
   on any step/tracing failure (EnforceNotMet included) for post-mortem
   debugging. Off (the default) it costs one attribute load per run.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import metrics as _mx

__all__ = [
    "op_scopes_enabled", "numerics_level", "profile_enabled",
    "compiled_analysis", "publish_compiled_analysis", "memory_report_from",
    "program_op_costs", "step_report", "op_scope_coverage",
    "lowered_scope_text",
    "check_numerics_mask",
    "record_collective", "collectives_snapshot",
    "FlightRecorder", "flight_recorder", "program_fingerprint",
]

def _env_on(name: str, default: str) -> bool:
    return os.environ.get(name, default).strip().lower() not in (
        "0", "false", "no", "off", "")


def op_scopes_enabled() -> bool:
    """``PADDLE_TPU_OP_SCOPES`` (default ON): wrap each op impl in
    ``jax.named_scope`` at trace time. Pure HLO metadata — zero per-step
    cost — so it is on by default; turn off only if scope names disturb
    an HLO-text-diffing workflow."""
    return _env_on("PADDLE_TPU_OP_SCOPES", "1")


def numerics_level() -> int:
    """``PADDLE_TPU_CHECK_NUMERICS`` clamped to 0..2 (module docstring);
    read per call so tests/REPLs can flip it without restarting."""
    raw = os.environ.get("PADDLE_TPU_CHECK_NUMERICS", "0").strip()
    try:
        lvl = int(raw)
    except ValueError:
        lvl = 1 if raw.lower() in ("true", "yes", "on") else 0
    return max(0, min(2, lvl))


def profile_enabled() -> bool:
    """``PADDLE_TPU_DEVICE_PROFILE=1``: publish cost/memory analysis gauges
    on every Executor compile miss (pays an extra lower+compile per
    specialization — debug opt-in). ``Executor.prepare`` publishes them
    unconditionally: it compiled AOT anyway."""
    return _env_on("PADDLE_TPU_DEVICE_PROFILE", "0")


def raw_device_kind() -> str:
    """``device_kind`` of the default backend's first device (e.g.
    ``"TPU v5 lite"``, ``"cpu"``) — the microarchitecture identity that
    keys tuned kernel configs (paddle_tpu.tune normalizes it)."""
    import jax

    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


# -- 1. compiled-step cost/memory attribution ---------------------------------

_g_flops = _mx.gauge("device_profile/flops",
                     help="XLA cost_analysis flops of the last analyzed "
                          "compiled step")
_g_bytes = _mx.gauge("device_profile/bytes_accessed",
                     help="XLA cost_analysis bytes accessed (HBM traffic "
                          "estimate) of the last analyzed compiled step")
_g_arg_b = _mx.gauge("device_profile/argument_bytes",
                     help="memory_analysis argument buffer bytes")
_g_out_b = _mx.gauge("device_profile/output_bytes",
                     help="memory_analysis output buffer bytes")
_g_tmp_b = _mx.gauge("device_profile/temp_bytes",
                     help="memory_analysis temp (scratch) buffer bytes")
_g_peak = _mx.gauge("device_profile/peak_hbm_bytes",
                    help="argument+output+temp-alias bytes: the compiled "
                         "step's peak device-memory footprint")
_c_analyses = _mx.counter("device_profile/analyses",
                          help="compiled-step cost/memory analyses published")


def _cost_dict(executable) -> Dict[str, float]:
    try:
        ca = executable.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
    return out


def _memory_dict(executable) -> Dict[str, float]:
    try:
        ma = executable.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    g = lambda k: float(getattr(ma, k, 0) or 0)
    out = {
        "argument_bytes": g("argument_size_in_bytes"),
        "output_bytes": g("output_size_in_bytes"),
        "temp_bytes": g("temp_size_in_bytes"),
        "alias_bytes": g("alias_size_in_bytes"),
        "generated_code_bytes": g("generated_code_size_in_bytes"),
    }
    out["peak_hbm_bytes"] = max(
        0.0, out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"])
    return out


def compiled_analysis(executable) -> Dict[str, Any]:
    """``{"cost": {...}, "memory": {...}}`` from a jax AOT-compiled
    executable (``lowered.compile()`` result). Backend gaps (a runtime
    without one of the analyses) yield empty sub-dicts, never a raise."""
    return {"cost": _cost_dict(executable), "memory": _memory_dict(executable)}


def publish_compiled_analysis(executable) -> Dict[str, Any]:
    """Mirror :func:`compiled_analysis` into the ``device_profile/*``
    gauges (last-analyzed-step semantics, like the pass-pipeline gauges)."""
    rep = compiled_analysis(executable)
    if _mx._enabled:
        cost, mem = rep["cost"], rep["memory"]
        if "flops" in cost:
            _g_flops.set(cost["flops"])
        if "bytes_accessed" in cost:
            _g_bytes.set(cost["bytes_accessed"])
        if mem:
            _g_arg_b.set(mem["argument_bytes"])
            _g_out_b.set(mem["output_bytes"])
            _g_tmp_b.set(mem["temp_bytes"])
            _g_peak.set(mem["peak_hbm_bytes"])
        if cost or mem:
            _c_analyses.inc()
    return rep


def memory_report_from(executable) -> Dict[str, float]:
    """The authoritative pre-run memory figure for a compiled step —
    what ``contrib.utils.memory_usage``'s docstring defers to."""
    return _memory_dict(executable) if executable is not None else {}


# -- analytic per-op cost table (the roofline rows) ---------------------------

# fwd flop-per-output-element factors for ops that aren't a plain map;
# everything absent costs 1 flop/element (elementwise) — these are
# first-order attribution weights, not a simulator.
_FLOPS_PER_ELEM = {
    "softmax": 5.0, "log_softmax": 5.0, "layer_norm": 8.0,
    "softmax_with_cross_entropy": 6.0, "cross_entropy": 2.0,
    "batch_norm": 4.0, "gelu": 8.0, "tanh": 4.0, "sigmoid": 4.0,
    "exp": 2.0, "log": 2.0, "sqrt": 2.0, "rsqrt": 2.0, "pow": 2.0,
    "dropout": 2.0,
}
_ZERO_FLOP_OPS = frozenset({
    "reshape", "reshape2", "transpose", "transpose2", "concat", "stack",
    "split", "slice", "assign", "cast", "fill_constant", "shape",
    "lookup_table", "gather", "one_hot", "squeeze", "squeeze2",
    "unsqueeze", "unsqueeze2", "flatten", "flatten2", "expand",
})


def _numel(shape, batch_size) -> int:
    n = 1
    for d in shape or ():
        if d is None:
            continue
        n *= batch_size * (-d) if d < 0 else d
    return n


def _var_bytes(block, name, batch_size) -> int:
    v = block._find_var_recursive(name)
    if v is None or v.shape is None:
        return 0
    from ..core.dtypes import to_jnp_dtype

    try:
        import numpy as np

        itemsize = np.dtype(to_jnp_dtype(v.dtype)).itemsize
    except Exception:
        itemsize = 4
    return _numel(v.shape, batch_size) * itemsize


def _op_flops(op, block, batch_size) -> float:
    """First-order forward flops for one symbolic op from static shapes."""
    t = op.type
    if t in _ZERO_FLOP_OPS:
        return 0.0
    outs = op.output_arg_names
    out_elems = 0
    for n in outs:
        v = block._find_var_recursive(n)
        if v is not None and v.shape is not None:
            out_elems = max(out_elems, _numel(v.shape, batch_size))
    if t in ("mul", "matmul", "matmul_v2"):
        # 2*M*K*N: out elems (M*N) times 2K from the contracted dim
        xn = op.inputs.get("X") or []
        k = 0
        if xn:
            xv = block._find_var_recursive(xn[0])
            if xv is not None and xv.shape:
                k = abs(xv.shape[-1] or 0)
        return 2.0 * out_elems * max(k, 1)
    if t in ("conv2d", "depthwise_conv2d"):
        wn = op.inputs.get("Filter") or []
        per_out = 1
        if wn:
            wv = block._find_var_recursive(wn[0])
            if wv is not None and wv.shape and len(wv.shape) == 4:
                _, cin, kh, kw = wv.shape
                per_out = 2 * abs(cin or 1) * abs(kh or 1) * abs(kw or 1)
        return float(out_elems * per_out)
    if t == "scaled_dot_product_attention":
        # 4*B*H*S^2*D ≈ 4 * out_elems * S (out is [B, S, H*D])
        xn = op.inputs.get("Q") or op.inputs.get("X") or []
        s = 1
        if xn:
            xv = block._find_var_recursive(xn[0])
            if xv is not None and xv.shape and len(xv.shape) >= 2:
                s = abs(xv.shape[-2] or 1) or 1
        return 4.0 * out_elems * s
    if t.startswith("reduce_") or t in ("mean", "sum"):
        ins = op.input_arg_names
        in_elems = max((_numel(getattr(block._find_var_recursive(n), "shape",
                                       None), batch_size)
                        for n in ins), default=out_elems)
        return float(in_elems)
    return _FLOPS_PER_ELEM.get(t, 1.0) * out_elems


def program_op_costs(program, batch_size: int = 1) -> List[Dict[str, Any]]:
    """Analytic per-op flops/bytes rows for block 0 from static var shapes
    (``-1`` batch dims substituted with ``batch_size``).

    These are ATTRIBUTION WEIGHTS — the measured truth is the compiled
    step's aggregate ``cost_analysis`` (XLA fuses across ops); the rows
    apportion that total over Program ops, and ``intensity`` (flops/byte)
    says which side of the roofline each op lives on. Rows carry the
    stable ``slot`` identity (``__op_slot__`` when stamped, position
    otherwise) matching named scopes and watchdog reports."""
    from ..core.interpreter import SKIP_OPS

    block = program.global_block
    rows: List[Dict[str, Any]] = []
    for i, op in enumerate(block.ops):
        if op.type in SKIP_OPS:
            continue
        flops = _op_flops(op, block, batch_size)
        nbytes = sum(_var_bytes(block, n, batch_size)
                     for n in op.input_arg_names)
        nbytes += sum(_var_bytes(block, n, batch_size)
                      for n in op.output_arg_names)
        rows.append({
            "slot": int(op.attrs.get("__op_slot__", i)),
            "type": op.type,
            "out": (op.output_arg_names or [""])[0],
            "flops": float(flops),
            "bytes": float(nbytes),
            "intensity": float(flops) / nbytes if nbytes else 0.0,
        })
    return rows


def step_report(program, executable=None, batch_size: int = 1,
                top: int = 0) -> Dict[str, Any]:
    """The JSON ``device_profile`` section: measured compiled totals
    (when ``executable`` is a jax AOT executable) + analytic per-op rows
    sorted by flops. ``top`` truncates the row list (0 = all)."""
    rows = sorted(program_op_costs(program, batch_size),
                  key=lambda r: -r["flops"])
    total_f = sum(r["flops"] for r in rows) or 1.0
    for r in rows:
        r["flops_frac"] = round(r["flops"] / total_f, 4)
    out: Dict[str, Any] = {
        "n_ops": len(rows),
        "analytic_total_flops": total_f,
        "op_costs": rows[:top] if top else rows,
    }
    if executable is not None:
        out.update(compiled_analysis(executable))
    return out


def lowered_scope_text(lowered) -> str:
    """Pre-optimization HLO/StableHLO text WITH scope metadata for a jax
    ``Lowered``. ``lowered.as_text()`` strips debug locations (and XLA's
    backend passes fuse most per-instruction metadata away from the
    compiled text), so the full-coverage artifact is the MLIR asm with
    debug info — every instruction's ``loc("...<slot>:<type>...")``."""
    try:
        return lowered.compiler_ir().operation.get_asm(
            enable_debug_info=True)
    except Exception:
        return lowered.as_text()


def op_scope_coverage(hlo_text: str) -> Dict[str, int]:
    """Parse HLO/MLIR text metadata for the ``<slot>:<type>`` named
    scopes: {scope label: instruction count}. Accepts compiled-HLO text
    (``executable.as_text()``, ``op_name="..."`` metadata — post-fusion,
    partial coverage) and :func:`lowered_scope_text` output
    (``loc("...")`` debug locations — full pre-optimization coverage).
    The presence/coverage check the tests use.
    Autodiff re-derives forward ops under ``jvp(<scope>)`` /
    ``transpose(jvp(<scope>))`` path segments — those count toward the
    same ``<slot>:<type>`` scope (it IS the same Program op's work)."""
    import re

    cov: Dict[str, int] = {}
    for m in re.finditer(r'(?:op_name="([^"]*)"|loc\("([^"]*)")', hlo_text):
        for seg in (m.group(1) or m.group(2)).split("/"):
            s = re.search(r"(?:^|\()(\d+:[A-Za-z0-9_.]+)\)*$", seg)
            if s:
                cov[s.group(1)] = cov.get(s.group(1), 0) + 1
    return cov


# -- 2. numerics watchdog (host side) -----------------------------------------

def check_numerics_mask(mask, layout: Sequence[Tuple[str, tuple]],
                        driver: str = "run") -> None:
    """Validate the packed per-op isfinite mask a guarded step fetched.

    ``mask``: bool [K] (one step) or [steps, K] (a fused run_steps chunk).
    ``layout``: the compiled step's trace-time record — entry k is
    ``(label, output names)`` for mask bit k. All-finite is one tiny
    device->host transfer and no further work; a failure walks the mask on
    host and raises EnforceNotMet naming the originating Program op."""
    import numpy as np

    arr = np.asarray(mask)  # the once-per-step sync (a few bytes)
    if arr.all():
        return
    from ..core.enforce import EnforceNotMet

    arr2 = arr.reshape(1, -1) if arr.ndim == 1 else arr
    bad = []
    for s in range(arr2.shape[0]):
        for k in np.flatnonzero(~arr2[s]):
            label, outs = (layout[k] if k < len(layout)
                           else ("?%d:?" % k, ()))
            bad.append((s, label, outs))
    first_step, first_label, first_outs = bad[0]
    step_part = (" (step %d of the fused chunk)" % first_step
                 if arr2.shape[0] > 1 else "")
    also = ""
    if len(bad) > 1:
        others = sorted({label for _, label, _ in bad[1:]})
        also = "\n  downstream non-finite ops (propagation): %s" % (
            ", ".join(others[:8]) + ("..." if len(others) > 8 else ""))
    raise EnforceNotMet(
        "PADDLE_TPU_CHECK_NUMERICS=2: non-finite values first produced by "
        "op %s (outputs %s)%s during %s%s\n"
        "(op identity is <source-op-index>:<type>; inspect it with "
        "tools/dump_program.py)"
        % (first_label, list(first_outs), step_part, driver, also))


# -- 3. collective traffic accounting -----------------------------------------

def record_collective(op: str, axis: Optional[str], array,
                      per_step_calls: int = 1) -> None:
    """Account one traced collective emission site.

    Called at TRACE time (``array`` is usually a tracer — only
    shape/dtype are read), so each compile records the bytes ONE step
    moves per device through this site; ``per_step_calls`` multiplies for
    sites inside a ``lax.scan`` body that executes N times per step.
    Counters: ``collectives/<op>/bytes``, ``collectives/<op>/calls`` and,
    with ``axis``, ``collectives/<op>/<axis>/bytes``."""
    if not _mx._enabled:
        return
    shape = getattr(array, "shape", None)
    dtype = getattr(array, "dtype", None)
    if shape is None or dtype is None:
        return
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 4
    total = n * itemsize * max(1, int(per_step_calls))
    _mx.counter("collectives/%s/bytes" % op,
                help="per-device bytes one step moves through traced "
                     "%s sites (recorded at trace time)" % op).inc(total)
    _mx.counter("collectives/%s/calls" % op).inc(max(1, int(per_step_calls)))
    if axis:
        _mx.counter("collectives/%s/%s/bytes" % (op, axis)).inc(total)


def collectives_snapshot() -> Dict[str, int]:
    """{counter name: value} of every non-zero ``collectives/*`` counter —
    the MULTICHIP-JSON / dryrun reporting surface."""
    out = {}
    for name, snap in _mx.snapshot().items():
        if name.startswith("collectives/") and snap.get("value"):
            out[name] = int(snap["value"])
    return out


# -- 4. flight recorder -------------------------------------------------------

def program_fingerprint(program) -> str:
    """Stable short hash of a Program's structure (op types + wiring),
    memoized per (program, version)."""
    cached = getattr(program, "_fp_cache", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    import hashlib

    h = hashlib.sha1()
    for blk in program.blocks:
        for op in blk.ops:
            h.update(op.type.encode())
            for slot in sorted(op.inputs):
                h.update(("|i:%s=%s" % (slot, op.inputs[slot])).encode())
            for slot in sorted(op.outputs):
                h.update(("|o:%s=%s" % (slot, op.outputs[slot])).encode())
    fp = h.hexdigest()[:16]
    program._fp_cache = (program._version, fp)
    return fp


class FlightRecorder:
    """Ring buffer of the last N step records, dumped to JSON on crash.

    One recorder per ``PADDLE_TPU_FLIGHT_DIR`` value per process; thread
    safe (reader threads may be mid-step when the main loop crashes)."""

    def __init__(self, dirpath: str, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("PADDLE_TPU_FLIGHT_STEPS", "16"))
            except ValueError:
                capacity = 16
        self.dir = dirpath
        self.capacity = max(1, capacity)
        self._entries: List[dict] = []
        self._lock = threading.Lock()
        self._seq = 0
        self._dumped = 0

    def record_step(self, driver: str, program, feed_specs, fetch_names,
                    extra: Optional[dict] = None) -> None:
        """Append one pre-dispatch step record (the crash will have it)."""
        from ..passes.pipeline import DEFAULT_PASS_NAMES, opt_level, pass_enabled

        entry = {
            "t": time.time(),
            "seq": self._seq,
            "driver": driver,
            "program": program_fingerprint(program),
            "program_version": program._version,
            "n_ops": len(program.global_block.ops),
            "feed": [(n, str(d), list(s)) for n, d, s in feed_specs],
            "fetch": list(fetch_names),
            "opt_level": opt_level(),
            "pass_gates_off": [n for n in DEFAULT_PASS_NAMES
                               if not pass_enabled(n)],
            "metrics": _mx.snapshot(),
        }
        if extra:
            entry.update(extra)
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._entries.append(entry)
            if len(self._entries) > self.capacity:
                del self._entries[:len(self._entries) - self.capacity]

    def record_event(self, kind: str, **payload) -> None:
        with self._lock:
            self._entries.append({"t": time.time(), "event": kind, **payload})
            if len(self._entries) > self.capacity:
                del self._entries[:len(self._entries) - self.capacity]

    def dump(self, reason: str, exc: Optional[BaseException] = None) -> str:
        """Write the ring + final metrics snapshot; returns the path.
        The dump embeds the process ``run_id`` and the most recent
        telemetry interval delta so it is self-contained AND joinable
        against the run ledger and the telemetry JSONL ring."""
        os.makedirs(self.dir, exist_ok=True)
        with self._lock:
            self._dumped += 1
            path = os.path.join(
                self.dir, "flight_%d_%d.json" % (os.getpid(), self._dumped))
            doc = {
                "reason": reason,
                "t": time.time(),
                "pid": os.getpid(),
                "run_id": None,
                "exception": (None if exc is None
                              else "%s: %s" % (type(exc).__name__, exc)),
                "env": {k: v for k, v in os.environ.items()
                        if k.startswith(("PADDLE_TPU_", "FLAGS_"))},
                "entries": list(self._entries),
                "metrics_final": _mx.snapshot(),
            }
        try:
            from .runlog import run_id

            doc["run_id"] = run_id()
        except Exception:
            pass
        try:
            from .telemetry import active_exporter

            exp = active_exporter()
            last = exp.last_sample if exp is not None else None
            if last is not None:
                doc["telemetry_last"] = {
                    "seq": last.seq, "t": last.t, "dt_s": last.dt_s,
                    "deltas": last.deltas}
        except Exception:
            pass
        try:
            # with PADDLE_TPU_NUMERICS armed, embed the per-op range
            # history — a NaN dump then shows the offending op's absmax
            # trajectory, not just the trip bit
            from . import numerics as _numerics

            if _numerics.stats_level() >= 1:
                snap = _numerics.snapshot()
                if snap:
                    doc["numerics_last"] = snap
        except Exception:
            pass
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        return path


_recorder: Optional[FlightRecorder] = None
_recorder_dir: Optional[str] = None


def flight_recorder() -> Optional[FlightRecorder]:
    """The process recorder, or None when ``PADDLE_TPU_FLIGHT_DIR`` is
    unset (the hot-path cost of the whole subsystem is then this env read
    + branch). A changed dir mid-process starts a fresh ring."""
    global _recorder, _recorder_dir
    d = os.environ.get("PADDLE_TPU_FLIGHT_DIR")
    if not d:
        return None
    if _recorder is None or _recorder_dir != d:
        _recorder = FlightRecorder(d)
        _recorder_dir = d
    return _recorder

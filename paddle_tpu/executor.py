"""Executor: traces a Program into one jit-compiled XLA step.

Fluid's ``Executor::Run`` (reference: ``framework/executor.cc:186,398``)
interprets ops one by one against a Scope, paying per-op dispatch +
InferShape + kernel-lookup every step. Here the op loop runs ONCE, at trace
time, inside ``jax.jit``: every op impl is a pure JAX function over a
name→array environment, so the whole step — forward, jax.grad backward,
optimizer updates — compiles to a single fused XLA executable. State
(persistable vars) is threaded functionally with buffer donation, giving
in-place param updates in HBM.

Feed/fetch semantics, the program cache (keyed like Fluid's
``executor.py:224,310`` cache plus feed shapes for XLA's static-shape
requirement), and scope handling mirror ``python/paddle/fluid/executor.py``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache as _cc
from . import ops as _ops  # noqa: F401 — registers all op impls
from .core.dtypes import to_jnp_dtype
from .core.framework import (Program, Variable, default_main_program,
                             grad_var_name, in_test_mode)
from .flags import flags as _flags
from .core.interpreter import NUMERICS_ENV_KEY as _NUMERICS_ENV_KEY, run_block_ops
from .core.place import Place, get_device
from .core.registry import OpContext, get_op_impl
from .core.scope import Scope, global_scope
from .monitor import GRAD_NORM_VAR, device as _dev, metrics as _mx, tracer as _tr
from .monitor import numerics as _num
from .monitor.numerics import NUM_STATS as _NUM_STATS, \
    STATS_ENV_KEY as _STATS_ENV_KEY
from .parallel.mesh import valid_sharding
from .reliability import faults as _faults

__all__ = ["Executor", "FeedError", "FetchHandle", "TraceContext",
           "aot_compile"]


class FeedError(RuntimeError):
    """The feed source raised while ``run_steps`` assembled a fused chunk.

    Typed (and flight-recorded by the run_steps crash path) so a data-side
    failure names the global step and the position inside the chunk instead
    of surfacing as a bare stack from ``lax.scan`` input prep."""

# Instruments are module-level handles: looked up once, so the per-run cost
# with metrics ON is a few lock+add ops, and with metrics OFF a single
# branch inside each instrument call (no lock, no allocation) — the
# acceptance bar for the hot path.
_m_runs = _mx.counter("executor/runs", help="Executor.run invocations")
_m_cache_hit = _mx.counter("executor/cache_hit",
                           help="program-cache hits (reused _CompiledStep)")
_m_cache_miss = _mx.counter("executor/cache_miss",
                            help="program-cache misses (new specialization)")
_m_step_ms = _mx.histogram("executor/step_time_ms",
                           help="wall time of one cached step dispatch")
_m_compile_ms = _mx.histogram(
    "executor/compile_time_ms",
    help="trace+XLA-compile wall time of a cache-miss first step")
_m_trace_ms = _mx.histogram(
    "executor/trace_setup_ms",
    help="host time to build a _CompiledStep specialization")
_m_feed_bytes = _mx.counter("executor/feed_bytes",
                            help="bytes handed to the step as feeds")
_m_fetch_bytes = _mx.counter("executor/fetch_bytes",
                             help="bytes fetched back to host")
_m_plan_hit = _mx.counter("executor/plan_hit",
                          help="dispatch-plan cache hits (near-zero Python "
                               "bookkeeping per step)")
_m_plan_miss = _mx.counter("executor/plan_miss",
                           help="dispatch-plan cache misses (full per-run "
                                "bookkeeping)")
_m_chain_dispatches = _mx.counter(
    "executor/run_steps_dispatches",
    help="fused multi-step dispatches issued by Executor.run_steps")
_m_chain_steps = _mx.counter(
    "executor/run_steps_steps",
    help="train steps rolled into run_steps dispatches")
_m_chain_ms = _mx.histogram(
    "executor/run_steps_chunk_ms",
    help="host dispatch wall time of one fused run_steps chunk")
_m_hbm_used = _mx.gauge("device/hbm_bytes_in_use",
                        help="memory_stats bytes_in_use, summed over devices")
_m_hbm_limit = _mx.gauge("device/hbm_bytes_limit",
                         help="memory_stats bytes_limit, summed over devices")
_m_grad_norm = _mx.gauge("optimizer/grad_global_norm",
                         help="pre-clip global grad norm (PADDLE_TPU_GRAD_NORM=1)")

_mem_stats_ok: Optional[bool] = None  # None = not probed yet
_HBM_SAMPLE_EVERY = 32  # sample memory_stats on miss + every Nth run


_mem_devices = None  # cached jax.local_devices() once the probe succeeds


def _update_hbm_gauges() -> None:
    """Refresh HBM gauges from device memory_stats(); probes capability once
    (CPU backends may not implement it) and then never raises per step."""
    global _mem_stats_ok, _mem_devices
    if _mem_stats_ok is False:
        return
    try:
        if _mem_devices is None:
            _mem_devices = jax.local_devices()
        used = limit = 0
        got = False
        for d in _mem_devices:
            stats = d.memory_stats()
            if not stats:
                continue
            got = True
            used += stats.get("bytes_in_use", 0)
            limit += stats.get("bytes_limit", 0)
        if not got:
            _mem_stats_ok = False
            return
        _mem_stats_ok = True
        _m_hbm_used.set(used)
        if limit:
            _m_hbm_limit.set(limit)
    except Exception:
        _mem_stats_ok = False


def _nbytes(arrays) -> int:
    total = 0
    for a in arrays:
        nb = getattr(a, "nbytes", None)
        if nb is None:
            nb = np.asarray(a).nbytes
        total += nb
    return total

class FetchHandle:
    """Deferred fetch result: ``run(..., return_numpy=False)`` returns one.

    Holds the step's fetched ``jax.Array``\\ s, which may still be computing
    on an async backend — so steady-state training can dispatch step N+1
    while step N's device work is in flight. All host-side resolve work (the
    numpy conversion, the opt-in ``PADDLE_TPU_GRAD_NORM`` gauge read and the
    ``executor/fetch_bytes`` accounting) is deferred to :meth:`numpy`, which
    is the only method that forces a device→host transfer.

    The sequence protocol (``len``/index/unpack) hands back the raw device
    arrays WITHOUT a sync, so existing ``loss, = exe.run(...,
    return_numpy=False)`` call sites keep their non-blocking behavior.
    """

    __slots__ = ("_values", "_names", "_aux", "_np", "_aux_done")

    def __init__(self, values, names, aux=None):
        self._values = list(values)
        self._names = tuple(names)
        self._aux = aux  # hidden grad-norm fetch (device scalar) or None
        self._np = None
        self._aux_done = aux is None

    @property
    def names(self):
        return self._names

    @property
    def raw(self):
        """The fetched device arrays, no sync."""
        return list(self._values)

    def __len__(self):
        return len(self._values)

    def __getitem__(self, i):
        return self._values[i]

    def __iter__(self):
        return iter(self._values)

    def _consume_aux(self):
        """Mirror the hidden grad-norm fetch into its gauge (one scalar
        device sync; only on the resolve path, never at dispatch)."""
        if self._aux_done:
            return
        self._aux_done = True
        if not _mx._enabled:
            return
        try:
            _m_grad_norm.set(float(np.asarray(self._aux).ravel()[-1]))
        except (TypeError, ValueError):
            pass

    def done(self) -> bool:
        """True once every fetched array's device computation finished
        (non-blocking; conservatively True on backends without is_ready)."""
        for v in self._values:
            ready = getattr(v, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    def block(self):
        """Wait for the device work behind the fetches; returns self."""
        jax.block_until_ready(self._values)
        self._consume_aux()
        return self

    def numpy(self):
        """Resolve to host numpy arrays (syncs; cached after first call)."""
        if self._np is None:
            out = [np.asarray(v) for v in self._values]
            self._consume_aux()
            if _mx._enabled and out:
                _m_fetch_bytes.inc(_nbytes(out))
            self._np = out
        return list(self._np)

    # the "resolve path" name used in docs; same operation
    resolve = numpy

    def __del__(self):
        # A dropped handle must not silently lose the grad-norm sample the
        # user opted into; this is a scalar sync at GC time, best-effort.
        try:
            self._consume_aux()
        except Exception:
            pass


@jax.jit
def _finite_all(vals):
    """ONE fused device-side isfinite reduction over a list of float
    arrays → a scalar bool. The whole NaN check is then a single
    scalar device sync instead of the legacy full-model host copy
    (every fetch AND state entry through np.asarray, per step)."""
    ok = jnp.bool_(True)
    for v in vals:
        ok = jnp.logical_and(ok, jnp.isfinite(v).all())
    return ok


def _enforce_step_flags(fetch_names, fetches, state):
    """FLAGS_benchmark device sync (reference: operator.cc:942) and the
    FLAGS_check_nan_inf post-step check (operator.cc:947) — the one epilogue
    both drivers (run() and run_steps) must apply identically.

    The NaN check is a fused device-side reduction (see ``_finite_all``);
    its scalar fetch is the only sync, and after FLAGS_benchmark's
    block_until_ready it is free — the two flags compose without a second
    sync or any host copy. Only the (rare) failure path walks the values on
    host to recover the legacy error message's offending label.
    ``PADDLE_TPU_CHECK_NUMERICS>=1`` arms the same check without the legacy
    flag; level 2's per-op mask (checked before this) already attributed
    the op, so this stays the fetch/state-level backstop."""
    if _flags.benchmark:
        jax.block_until_ready((state, fetches))
    if _flags.check_nan_inf or _dev.numerics_level() >= 1:
        labeled = list(zip(fetch_names, fetches)) + list(state.items())
        vals = [v for _, v in labeled
                if getattr(v, "dtype", None) is not None
                and jnp.issubdtype(v.dtype, jnp.floating)]
        if not vals or bool(_finite_all(vals)):  # one scalar device sync
            return
        for label, val in labeled:
            arr = np.asarray(val)
            if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
                raise RuntimeError(
                    "FLAGS_check_nan_inf: non-finite values in %r after op "
                    "execution" % label)
        raise RuntimeError(
            "FLAGS_check_nan_inf: non-finite values after op execution")


def _safe_flight_dump(fr, reason, exc):
    """Crash-path flight-recorder dump: an unwritable PADDLE_TPU_FLIGHT_DIR
    (or a serialization hiccup) must never REPLACE the step error the dump
    exists to explain."""
    if fr is None:
        return
    try:
        fr.dump(reason, exc)
    except Exception as dump_err:
        from .log import vlog

        vlog(0, "flight-recorder dump failed (%r); original error preserved",
             dump_err)


def _mesh_repl(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def _mesh_batch_spec(mesh, leading_step_axis=False):
    """PartitionSpec for feed batches: the batch axis shards over ``data``;
    ``leading_step_axis`` prepends a replicated axis for run_steps' stacked
    (step, batch, ...) chain feeds. One definition so the single-step and
    chain drivers can never lay feeds out differently."""
    from jax.sharding import PartitionSpec as P

    if "data" not in mesh.axis_names:
        return P()
    return P(None, "data") if leading_step_axis else P("data")


def _abstractify(tree):
    """Pytree → ShapeDtypeStructs (ShapeDtypeStructs pass through)."""
    return jax.tree_util.tree_map(
        lambda v: v if isinstance(v, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(tuple(getattr(v, "shape", ())),
                                  getattr(v, "dtype", np.float32)),
        tree)


def _step_label(program) -> str:
    """A Program's name in the compile log: ``step[<fingerprint, 8 hex>]``
    of the program the caller handed over (not of its optimized clone)."""
    return "step[%s]" % _dev.program_fingerprint(program)[:8]


def _timed_lower_compile(jitted_fn, args, label):
    """(lowered, executable) under ``label`` in the compile log
    (compile_cache.log(): what JAX traces, lowers, compiles or loads here
    is ONE entry of that name), the seam's own length routed to the
    executor/compile_time_ms histogram — the one AOT timing convention
    shared by Executor.prepare and aot_compile."""
    _faults.fire("executor.compile")  # chaos drills: injected compile failure
    with _cc.label(label) as seam:
        lowered = jitted_fn.lower(*args)
        aot = lowered.compile()
    if _mx._enabled:
        _m_compile_ms.observe(seam.seconds * 1e3)
    return lowered, aot


def aot_compile(fn, abstract_args, donate_argnums=(), static_argnums=(),
                label=None):
    """AOT lower + XLA-compile ``fn`` at abstract shapes WITHOUT running it
    — ``Executor.prepare``'s artifact path exposed for non-Program drivers
    (the serving decode engine compiles its per-bucket prefill fns and the
    fused decode step through here).

    ``abstract_args`` is a tuple of pytrees of arrays or
    ``ShapeDtypeStruct``\\ s (only shapes/dtypes are read). Compile time
    lands in ``executor/compile_time_ms``; the executable persists across
    processes in the compile cache (compile_cache.py), so a serving restart
    skips every prefill/decode compile. Returns the compiled executable
    (call it with concrete arrays; ``donate_argnums`` buffers are consumed).
    ``label`` names the executable in the compile log
    (``compile_cache.log()``; the serving engine passes ``prefill[<bucket>]``,
    ``chunk[fuse=<k>]``, ``resume[<bucket>]``); without
    one it is the function's own name.
    """
    jitted = jax.jit(fn, donate_argnums=donate_argnums,
                     static_argnums=static_argnums)
    static = set(static_argnums if isinstance(static_argnums, (tuple, list))
                 else (static_argnums,))
    # static args must reach the trace as their CONCRETE values, not shape
    # structs — only the traced (dynamic) positions are abstractified
    args = tuple(a if i in static else _abstractify(a)
                 for i, a in enumerate(abstract_args))
    _, aot = _timed_lower_compile(
        jitted, args, label or getattr(fn, "__name__", "aot_compile"))
    return aot


_UserCompiledProgram = None  # lazily bound CompiledProgram class (import cycle)


class TraceContext:
    """Per-trace state: RNG derivation, test mode, mesh, current op position."""

    def __init__(self, program: Program, is_test: bool, base_rng, mesh=None):
        self.program = program
        self.is_test = is_test
        self.base_rng = base_rng
        self.mesh = mesh
        self.current_op_idx = 0
        self._key_table = None
        self._n_ops = 0
        # device-side observability (monitor/device.py): op-identity named
        # scopes (trace-time-only cost, resolved once per trace) and the
        # numerics-watchdog layout list the owning _CompiledStep arms
        self.op_scopes = _dev.op_scopes_enabled()
        self.watch = None

    def op_rng(self, ctx: OpContext):
        # RNG-stability contract (passes/analysis.py): an optimizer pass may
        # delete or move ops, which would shift every later op's positional
        # key. The pipeline stamps each stochastic op's ORIGINAL position
        # into __rng_slot__ before mutating; honoring it here keeps the
        # optimized program's RNG stream bit-identical to OPT_LEVEL=0.
        idx = ctx.attr("__rng_slot__")
        if idx is None:
            idx = self.current_op_idx
        seed = ctx.attr("seed", 0) or self.program.random_seed
        if seed:
            # explicit per-op seed: a constant key XLA constant-folds
            return jax.random.fold_in(jax.random.PRNGKey(seed), idx)
        # Derive the main-block per-op keys with one batched split instead of
        # a scalar fold_in per RNG-consuming op: each scalar fold_in is ~113
        # unfusable scalar u32 entry instructions (a full threefry chain),
        # and a BERT step with ~50 dropout sites carried ~5,700 of them —
        # the batched table is one vectorized threefry plus slices that fuse
        # into the consumers (benchmarks/diag_bert_kernels.py).
        # Sub-block ops (while/cond bodies) run at offset 10_000*block_idx
        # (ops/control_flow_ops.py) — far past the table, where JAX's static
        # indexing would silently CLAMP to the last row and hand every such
        # op the same key — so anything past the table keeps the scalar
        # fold_in (distinct key per index; those ops trace once inside the
        # loop body, so the scalar chains stay rare).
        if self._key_table is None:
            # jax.random.split(key, n) keys depend on n, so an optimized
            # program must build the table at the SOURCE program's size
            # (_rng_table_n, stamped by the pipeline) for stamped slots to
            # resolve to the same keys as the unoptimized program.
            self._n_ops = getattr(self.program, "_rng_table_n",
                                  len(self.program.global_block.ops) + 8)
            self._key_table = jax.random.split(self.base_rng, self._n_ops)
        if idx < self._n_ops:
            return self._key_table[idx]
        return jax.random.fold_in(self.base_rng, idx)


def _canon(value, dtype_name: str):
    target = to_jnp_dtype(dtype_name)
    canonical = jax.dtypes.canonicalize_dtype(target)
    if isinstance(value, jax.ShapeDtypeStruct):
        # abstract feed (Executor.prepare): only shape/dtype matter
        return (value if value.dtype == canonical
                else jax.ShapeDtypeStruct(value.shape, canonical))
    if isinstance(value, jax.Array):
        # already on device (e.g. via DevicePrefetcher) — never round-trip to host
        return value if value.dtype == canonical else value.astype(canonical)
    arr = np.asarray(value)
    if arr.dtype != canonical:
        arr = arr.astype(canonical)
    return arr


class _CompiledStep:
    """A specialization of (program, feed sig, fetch list, state names).

    With a mesh: state replicated, feeds sharded on the ``data`` axis —
    XLA/GSPMD inserts the gradient psum over ICI (the TPU-native
    ParallelExecutor+NCCL path, SURVEY.md §7).
    """

    def __init__(self, program: Program, feed_names: Tuple[str, ...],
                 fetch_names: Tuple[str, ...], state_names: Tuple[str, ...],
                 is_test: bool, jit: bool = True, mesh=None,
                 accumulation_steps: int = 1, numerics: bool = False,
                 stats: bool = False):
        self.program = program
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.state_names = state_names
        self.is_test = is_test
        self.mesh = mesh
        # PADDLE_TPU_CHECK_NUMERICS=2: this specialization is the GUARDED
        # variant — every op's floating outputs feed an isfinite bit into a
        # packed mask appended as a hidden trailing fetch; watch_layout maps
        # mask bit k -> (op label, output names), written at trace time
        # (index-overwrite, so jit retraces never desync it).
        self.numerics = bool(numerics)
        self.watch_layout: list = []
        # PADDLE_TPU_NUMERICS>=1: streaming tensor statistics — every op's
        # floating outputs fold one packed stat row into a [K, NUM_STATS]
        # hidden trailing fetch (monitor.numerics); stats_layout maps row
        # k -> (op label, output names, dtype max), same index-overwrite
        # discipline as watch_layout.
        self.stats = bool(stats)
        self.stats_layout: list = []

        bw = program._backward_info
        block = program.global_block
        ops = block.ops
        marker_idx = None
        if bw is not None:
            for i, op in enumerate(ops):
                if op.type == "backward_marker":
                    marker_idx = i
                    break
        accum = max(1, int(accumulation_steps)) if marker_idx is not None else 1

        # AMP: run the forward in bf16/fp16 against fp32 master weights
        # (the TPU-native float16.h story; enabled via paddle_tpu.amp).
        amp_dtype = getattr(program, "_amp_dtype", None)
        if amp_dtype is not None:
            amp_dtype = to_jnp_dtype(amp_dtype)

        def _amp_cast_tree(d):
            if amp_dtype is None:
                return d
            return {
                k: (v.astype(amp_dtype)
                    if hasattr(v, "dtype") and v.dtype == jnp.float32 else v)
                for k, v in d.items()
            }

        seed_const = program.random_seed or 0
        self._out_state_sh = None  # set below when jit+mesh; guards jit=False

        def step(state, feeds, step_idx):
            # key derivation is part of the compiled step (fused, zero host
            # cost per run); step_idx is the only changing input
            rng_key = jax.random.fold_in(jax.random.PRNGKey(seed_const), step_idx)
            trace = TraceContext(program, is_test, rng_key, mesh=mesh)
            if self.numerics:
                trace.watch = self.watch_layout
            if self.stats:
                trace.stats_watch = self.stats_layout
            if bw is None or marker_idx is None:
                env = dict(state)
                env.update(feeds)
                if amp_dtype is not None:
                    # Cast a COPY of the env for the forward; the fp32 master
                    # state must survive an eval/fetch run un-degraded. Only
                    # vars an op actually rewrote (tracer identity changed)
                    # flow back, cast to their original dtype.
                    env = _amp_cast_tree(env)
                    before = dict(env)  # hold refs so identity compare is sound
                    run_block_ops(ops, env, trace)
                    for k in list(env):
                        if k not in state:
                            continue
                        v = env[k]
                        if before.get(k) is v:
                            env[k] = state[k]
                        elif (hasattr(v, "dtype") and hasattr(state[k], "dtype")
                              and v.dtype != state[k].dtype):
                            env[k] = v.astype(state[k].dtype)
                else:
                    run_block_ops(ops, env, trace)
            else:
                loss_name = bw["loss"]
                param_to_grad = bw["param_to_grad"]
                all_param_names = [p for p in param_to_grad if p in state]
                block0 = program.global_block
                sparse_names = [
                    p for p in all_param_names
                    if getattr(block0._find_var_recursive(p), "is_sparse_param", False)
                ]
                param_names = [p for p in all_param_names if p not in sparse_names]
                params = {n: state[n] for n in param_names}
                rest = {n: v for n, v in state.items() if n not in params}
                fwd_ops = ops[:marker_idx]
                post_ops = ops[marker_idx + 1 :]

                def fwd(params_in, virtuals_in, feeds_in):
                    env = dict(rest)
                    env.update(_amp_cast_tree(params_in))
                    env.update(_amp_cast_tree(feeds_in))
                    if virtuals_in:
                        env["__sparse_virtual__"] = virtuals_in
                    run_block_ops(fwd_ops, env, trace)
                    loss = jnp.sum(env[loss_name].astype(jnp.float32))
                    return loss, env

                virtuals = {}
                if sparse_names:
                    # Sparse path (SelectedRows equivalent, core/sparse.py):
                    # an abstract probe discovers each table's per-step row
                    # count; zero "virtual rows" become extra grad leaves so
                    # the table itself is never densely differentiated.
                    if accum != 1:
                        raise NotImplementedError(
                            "is_sparse embeddings + gradient accumulation is "
                            "not supported yet (per-microbatch row shapes)")
                    collect = {}

                    def probe(params_in, feeds_in):
                        env = dict(rest)
                        env.update(params_in)
                        env.update(feeds_in)
                        env["__sparse_collect__"] = collect
                        run_block_ops(fwd_ops, env, trace)
                        return 0

                    jax.eval_shape(probe, params, feeds)
                    missing = [p for p in sparse_names if p not in collect]
                    if missing:
                        raise ValueError(
                            "params marked is_sparse but never looked up "
                            "sparsely: %s" % missing)
                    vd = amp_dtype
                    virtuals = {
                        w: jnp.zeros(shape, vd if (vd is not None and
                                                   dt == jnp.float32) else dt)
                        for w, (shape, dt) in collect.items()
                    }

                if accum == 1:
                    if virtuals:
                        (loss_val, env), (grads, vgrads) = jax.value_and_grad(
                            fwd, argnums=(0, 1), has_aux=True)(
                                params, virtuals, feeds)
                    else:
                        (loss_val, env), grads = jax.value_and_grad(
                            fwd, has_aux=True)(params, {}, feeds)
                else:
                    # Gradient accumulation (the reference's multi_batch_merge
                    # pass, ir/multi_batch_merge_pass.cc): split the feed batch
                    # into microbatches, average grads before the optimizer.
                    # lax.scan keeps trace size and compile time CONSTANT in
                    # accumulation_steps (one traced microbatch, not N); the
                    # first microbatch runs outside the scan to seed the
                    # carry structure (grads + the activation env post_ops
                    # read from).
                    mb = {
                        n: v.reshape((accum, v.shape[0] // accum) + v.shape[1:])
                        for n, v in feeds.items()
                    }
                    sub0 = {n: v[0] for n, v in mb.items()}
                    (loss_sum, env), grads = jax.value_and_grad(
                        fwd, has_aux=True)(params, {}, sub0)

                    def _mb_step(carry, sub):
                        g_acc, l_acc, env_prev = carry
                        (li, env_i), gi = jax.value_and_grad(
                            fwd, has_aux=True)(params, {}, sub)
                        if self.numerics:
                            # AND the watchdog bits across microbatches —
                            # carrying only env_i would drop every earlier
                            # microbatch's forward bits and misattribute a
                            # mid-accumulation NaN to the optimizer ops
                            prev = env_prev.get(_NUMERICS_ENV_KEY)
                            cur = env_i.get(_NUMERICS_ENV_KEY)
                            if prev and cur:
                                env_i[_NUMERICS_ENV_KEY] = [
                                    jnp.logical_and(a, b)
                                    for a, b in zip(prev, cur)]
                        if self.stats:
                            # merge stat rows across microbatches the same
                            # way (absmax by max, sums add) so a chunk's
                            # stats cover every microbatch, not just the
                            # last one
                            prev = env_prev.get(_STATS_ENV_KEY)
                            cur = env_i.get(_STATS_ENV_KEY)
                            if prev and cur:
                                env_i[_STATS_ENV_KEY] = [
                                    _num.merge_stat_rows(a, b)
                                    for a, b in zip(prev, cur)]
                        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, gi)
                        return (g_acc, l_acc + li, env_i), None

                    (grads, loss_sum, env), _ = jax.lax.scan(
                        _mb_step, (grads, loss_sum, env),
                        {n: v[1:] for n, v in mb.items()})
                    grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                    env[loss_name] = loss_sum / accum
                # restore fp32 master params for the optimizer ops (the env
                # holds their amp-cast forward copies)
                env.update(params)
                for p in param_names:
                    env[param_to_grad[p]] = grads[p]
                for p in sparse_names:
                    from .core.sparse import SparseGrad

                    env[param_to_grad[p]] = SparseGrad(
                        env["__sparse_ids__" + p], vgrads[p])
                env[bw.get("loss_grad") or grad_var_name(loss_name)] = jnp.ones_like(
                    jnp.sum(env[loss_name]))
                run_block_ops(post_ops, env, trace, offset=marker_idx + 1)

            new_state = {}
            for n in self.state_names:
                val = env.get(n, state.get(n))
                if (self._out_state_sh is not None and val is not None
                        and hasattr(val, "dtype")):
                    # pin output layout: params replicated, annotated vars (TP
                    # params, ZeRO-1 optimizer shards) sharded — donation holds
                    # and ZeRO-1 accumulators never silently gather
                    val = jax.lax.with_sharding_constraint(
                        val, self._out_state_sh[n])
                new_state[n] = val
            fetches = [env[f] for f in self.fetch_names]
            if self.numerics:
                # the packed watchdog mask rides as the LAST hidden fetch
                # (after the grad-norm probe, which is part of fetch_names);
                # run()/run_steps pop it first and attribute failures via
                # watch_layout
                bits = env.get(_NUMERICS_ENV_KEY)
                fetches.append(jnp.stack(bits) if bits
                               else jnp.ones((1,), jnp.bool_))
            if self.stats:
                # the packed stat rows ride as the VERY last hidden fetch
                # (after the watchdog mask when both are armed); run()/
                # run_steps pop in reverse append order
                rows = env.get(_STATS_ENV_KEY)
                fetches.append(jnp.stack(rows) if rows
                               else jnp.zeros((1, _NUM_STATS), jnp.float32))
            return new_state, fetches

        # the raw (unjitted) step closure: _CompiledStepChain scans over it
        # to fuse k steps into one dispatch (Executor.run_steps)
        self._step_fn = step
        self.jitted = bool(jit)

        if jit and mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = _mesh_repl(mesh)
            batch_spec = _mesh_batch_spec(mesh)
            feed_sh = {n: NamedSharding(mesh, batch_spec) for n in feed_names}
            # State shardings come from the arrays themselves (the executor
            # device_puts them per Variable.sharding annotations). Output state
            # is pinned to the same layout — params replicated, annotated vars
            # (TP params, ZeRO-1 optimizer shards) sharded — so buffer
            # donation holds and ZeRO-1 accumulators never silently gather.
            out_state_sh = {}
            for n in state_names:
                v = program.global_block._find_var_recursive(n)
                spec = getattr(v, "sharding", None) if v is not None else None
                if valid_sharding(spec, mesh):
                    out_state_sh[n] = NamedSharding(mesh, P(*spec))
                else:
                    out_state_sh[n] = repl
            self._out_state_sh = out_state_sh
            self.fn = jax.jit(
                step,
                in_shardings=(None, feed_sh, repl),
                donate_argnums=(0,),
            )
        elif jit:
            self.fn = jax.jit(step, donate_argnums=(0,))
        else:
            self.fn = step

    def __call__(self, state, feeds, step_idx):
        return self.fn(state, feeds, step_idx)


class _CompiledStepChain:
    """``length`` consecutive steps of a ``_CompiledStep`` fused into ONE
    dispatched call.

    ``lax.scan`` rolls the base step over feed batches stacked on a new
    leading axis — the same stack-and-scan shape plumbing the gradient
    accumulation path uses for microbatches, except here each scan iteration
    is a FULL step (forward, backward, optimizer update) threading the state
    carry, so host dispatch cost drops to 1/length while the traced program
    (and its RNG stream: ``fold_in(key, step_idx)`` with the step index
    carried through the scan) stays identical to ``length`` separate runs.
    Per-step fetches come back stacked on the leading axis.
    """

    def __init__(self, base: _CompiledStep, length: int):
        self.base = base
        self.length = int(length)
        step_fn = base._step_fn

        def chain(state, stacked_feeds, step_idx0):
            def body(carry, feeds):
                st, idx = carry
                new_st, fetches = step_fn(st, feeds, idx)
                return (new_st, idx + jnp.uint32(1)), fetches

            # explicit length: a feedless (state-only) program hands scan an
            # empty xs pytree, which otherwise cannot infer the step count
            (state, _), fetches = jax.lax.scan(
                body, (state, jnp.uint32(step_idx0)), stacked_feeds,
                length=self.length)
            return state, fetches

        if base.jitted and base.mesh is not None:
            from jax.sharding import NamedSharding

            mesh = base.mesh
            repl = _mesh_repl(mesh)
            # axis 0 is the step axis; the per-step batch axis (1) shards
            # over ``data`` exactly like the single-step driver
            spec = _mesh_batch_spec(mesh, leading_step_axis=True)
            feed_sh = {n: NamedSharding(mesh, spec) for n in base.feed_names}
            self.fn = jax.jit(chain, in_shardings=(None, feed_sh, repl),
                              donate_argnums=(0,))
        elif base.jitted:
            self.fn = jax.jit(chain, donate_argnums=(0,))
        else:
            self.fn = chain

    def __call__(self, state, stacked_feeds, step_idx0):
        return self.fn(state, stacked_feeds, step_idx0)


class _DispatchPlan:
    """Memoized per-run Python bookkeeping for one (program version, feed
    names/dtypes, fetch list) shape of ``Executor.run``.

    A cache-hit step skips the per-feed ``block.var`` + dtype
    canonicalization machinery, the feed-signature build, the persistable
    walk and the specialization-key construction — the bookkeeping that
    dominated host dispatch time — and goes straight to the cached
    ``_CompiledStep``. Plans live on the Program (keyed by version, see
    ``Executor._resolve_plan``), so a version bump invalidates them and they
    die with the Program.
    """

    __slots__ = ("feed_specs", "fetch_names", "run_fetch_names",
                 "grad_norm_fetch", "numerics", "stats", "state_names",
                 "avail_names", "compiled", "key", "put_specs", "batch_sh",
                 "mesh_repl")

    def __init__(self, feed_specs, fetch_names, run_fetch_names,
                 grad_norm_fetch, numerics, stats, state_names, avail_names,
                 compiled, key, put_specs=None, batch_sh=None, mesh_repl=None):
        self.feed_specs = feed_specs  # tuple of (name, np.dtype, shape)
        self.fetch_names = fetch_names
        self.run_fetch_names = run_fetch_names
        self.grad_norm_fetch = grad_norm_fetch
        self.numerics = numerics  # guarded variant: watchdog mask fetch last
        self.stats = stats  # stats variant: packed stat rows fetch after it
        self.state_names = state_names
        self.avail_names = avail_names  # state vars present at plan build
        self.compiled = compiled
        self.key = key  # the _CompiledStep cache key (chain keys derive from it)
        self.put_specs = put_specs  # mesh only: {name: NamedSharding}
        self.batch_sh = batch_sh
        self.mesh_repl = mesh_repl


class Executor:
    """reference: python/paddle/fluid/executor.py:262."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place
        self._cache: Dict[tuple, Any] = {}
        self._dev = None  # get_device(place), resolved lazily once
        self._dev_resolved = False
        # Per-program state (persistable-name tuples, dispatch plans, the
        # step counter feeding the per-step RNG) is cached ON each Program:
        # recomputed only on version bump, freed with the Program. An
        # executor-held dict keyed by id(program) would grow one entry per
        # program forever and could silently serve stale state after id()
        # reuse — the bug close() used to leave behind in _step_counters.

    def close(self):
        """Parity with executor.py:388 (pserver notify): drop every cached
        specialization. Per-program bookkeeping (dispatch plans, step
        counters) lives on the Program objects and dies with them."""
        self._cache.clear()

    # -- helpers --------------------------------------------------------------
    @staticmethod
    def _fetch_names(fetch_list) -> Tuple[str, ...]:
        names = []
        for f in fetch_list or []:
            names.append(f.name if isinstance(f, Variable) else str(f))
        return tuple(names)

    @staticmethod
    def _persistable_names(program: Program, scope: Scope) -> Tuple[str, ...]:
        names = set()
        for v in program.list_vars():
            if v.persistable:
                names.add(v.name)
        # vars already in scope that program ops read (e.g. created by startup)
        return tuple(sorted(names))

    def _gather_state(self, program: Program, scope: Scope, names) -> Dict[str, Any]:
        state = {}
        for n in names:
            val = scope.find_var(n)
            if val is not None:
                state[n] = val
        return state

    @staticmethod
    def _unwrap_program(program, scope):
        """(plain program, mesh, accumulation_steps) from a possibly-wrapped
        CompiledProgram — the shared front door of run_steps and prepare
        (run() instead routes through CompiledProgram._run)."""
        global _UserCompiledProgram
        if _UserCompiledProgram is None:
            from .compiler import CompiledProgram as _cp

            _UserCompiledProgram = _cp
        mesh = None
        accumulation_steps = 1
        if isinstance(program, _UserCompiledProgram):
            cp = program
            cp._apply_build_passes(scope)
            mesh = cp._mesh()
            cp._apply_reduce_strategy(mesh)
            if cp._build_strategy is not None:
                accumulation_steps = getattr(
                    cp._build_strategy, "gradient_accumulation_steps", 1)
            program = cp._program
        if program is None:
            program = default_main_program()
        return program, mesh, accumulation_steps

    @staticmethod
    def _next_step_index(program: Program, n: int = 1):
        """Per-step PRNG: only a uint32 step index crosses the host/device
        boundary; the fold_in runs inside the compiled step (this eager key
        construction used to cost ~70% of per-step host overhead). The
        counter lives on the Program so it dies with it and a fused
        ``run_steps`` chunk advances it by the number of steps it rolled."""
        step = getattr(program, "_tpu_step_counter", 0)
        program._tpu_step_counter = step + n
        return np.uint32(step)

    def _device(self):
        if not self._dev_resolved:
            self._dev = get_device(self.place)
            self._dev_resolved = True
        return self._dev

    @staticmethod
    def _maybe_optimize(program: Program, fetch_names, scope):
        """Default trace-time optimizer (passes/, PADDLE_TPU_OPT_LEVEL,
        default 1): returns the memoized optimized clone for this (program
        version, fetch set) — the clone is what plan resolution and tracing
        see, so the optimized program participates in the dispatch-plan and
        compile-cache keys and a cache-hit run never re-enters a pass. The
        per-step RNG counter stays on the SOURCE program (callers pass the
        source to _next_step_index), keeping the RNG stream shared across
        fetch-set variants exactly as at opt level 0."""
        from .passes.pipeline import maybe_optimize

        return maybe_optimize(program, fetch_names, scope)

    # -- the public API -------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        global _UserCompiledProgram
        if _UserCompiledProgram is None:
            from .compiler import CompiledProgram as _cp

            _UserCompiledProgram = _cp
        if isinstance(program, _UserCompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy)

        return self._run_impl(
            program, feed, fetch_list, scope, return_numpy, use_program_cache
        )

    def _run_impl(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        mesh=None,
        accumulation_steps: int = 1,
    ):
        if program is None:
            program = default_main_program()
        # the profiler's own step view: one marker a run, numbered by the
        # program's step index; executor/run and its children lie inside it
        with jax.profiler.StepTraceAnnotation(
                "train", step_num=getattr(program, "_tpu_step_counter", 0)), \
                _tr.span("executor/run", cat="executor"):
            return self._run_step(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache, mesh,
                                  accumulation_steps)

    @staticmethod
    def _first_call(compiled, src_program, state, feeds, rng_key,
                    fetch_names):
        """The miss path's call: trace, lower, compile (or load) and first
        step, ONE ``step[..]`` entry of the compile log. A program that is
        fed nothing and gives nothing back makes state from nothing (a
        startup program: the weights made on the device), so its first
        call is a ``startup/weights`` phase."""
        with _cc.label(_step_label(src_program)), \
                _tr.span("executor/compile_and_step", cat="executor"):
            if feeds or fetch_names:
                return compiled(state, feeds, rng_key)
            with _cc.phase("startup/weights"):
                return compiled(state, feeds, rng_key)

    def _run_step(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, mesh, accumulation_steps):
        if scope is None:
            scope = global_scope()
        feed = dict(feed or {})
        # py_reader-fed programs: drain one batch per run for each started
        # reader whose vars aren't explicitly fed (reference: the in-graph
        # `read` op popping the blocking queue; raises EOFException at end).
        for reader in getattr(program, "_py_readers", ()):
            if not reader._started:
                continue
            fed = [n for n in reader.var_names if n in feed]
            if not fed:
                for n, v in reader.next_feed().items():
                    feed[n] = v
            elif len(fed) != len(reader.var_names):
                # Mixing an explicit partial feed with queue data would
                # silently consume a queued batch and pair unrelated rows.
                raise ValueError(
                    "run(): feed covers only %s of started py_reader vars %s; "
                    "feed all of them or none" % (fed, list(reader.var_names)))
        fetch_names = self._fetch_names(fetch_list)

        # default trace-time optimizer: all bookkeeping below (plans, the
        # specialization cache, tracing) keys on the optimized clone; only
        # the step counter stays on the source program
        src_program = program
        program = self._maybe_optimize(program, fetch_names, scope)

        # the hot-path guard reads the module flag directly: with metrics
        # off the registry costs this attribute load + branch per run; the
        # spans cost their annotation (well under a microsecond each)
        mx_on = _mx._enabled

        with _tr.span("executor/plan", cat="executor"):
            plan, feeds, state, was_miss = self._resolve_plan(
                program, feed, fetch_names, scope, mesh, accumulation_steps,
                mx_on, use_program_cache)
        compiled = plan.compiled

        rng_key = self._next_step_index(src_program)
        # the hand-over of the feeds to the device(s)
        with _tr.span("executor/place", cat="executor"):
            state, feeds = self._place(plan, state, feeds, mesh)
        fr = _dev.flight_recorder()  # None unless PADDLE_TPU_FLIGHT_DIR set
        if fr is not None:
            # fingerprint the SOURCE program (the one the user can inspect;
            # watchdog slots are source-relative); the optimized clone's
            # fingerprint rides along for compile-cache correlation
            fr.record_step(
                "run", src_program, plan.feed_specs, fetch_names,
                extra={"optimized": _dev.program_fingerprint(program)})
        t_step = time.perf_counter() if mx_on else 0.0
        try:
            spec = _faults.fire("executor.dispatch")
            if spec is not None and spec.kind == "nan":
                feeds = _faults.poison_feeds(feeds)
            if was_miss:
                new_state, fetches = self._first_call(
                    compiled, src_program, state, feeds, rng_key,
                    fetch_names)
            else:
                with _tr.span("executor/step", cat="executor"):
                    new_state, fetches = compiled(state, feeds, rng_key)
            if mx_on:
                # A cache-miss first call pays jit trace + XLA compile;
                # report it separately so the steady-state step histogram
                # stays clean. On async backends the hit-path number is
                # dispatch wall time (add FLAGS_benchmark for a per-step
                # device sync).
                dt_ms = (time.perf_counter() - t_step) * 1e3
                (_m_compile_ms if was_miss else _m_step_ms).observe(dt_ms)
                _m_runs.inc()
                if feeds:
                    _m_feed_bytes.inc(_nbytes(feeds.values()))
                # HBM gauges are a coarse signal; sampling on miss + every
                # Nth run keeps the per-device memory_stats() calls off the
                # steady-state dispatch path
                if was_miss or int(_m_runs.value) % _HBM_SAMPLE_EVERY == 0:
                    _update_hbm_gauges()
            if was_miss and compiled.jitted and _dev.profile_enabled():
                self._publish_device_profile(compiled, new_state, feeds)
            if plan.stats:
                # stat rows ride after the watchdog mask, so they pop first;
                # accumulate BEFORE check_numerics_mask so the trip chunk's
                # range history still lands in the registries/flight dump
                _num.accumulate(fetches[-1], compiled.stats_layout,
                                fingerprint=_dev.program_fingerprint(
                                    src_program),
                                driver="run")
                fetches = fetches[:-1]
            mask = None
            if plan.numerics:
                # the packed per-op isfinite mask is the LAST hidden fetch
                mask = fetches[-1]
                fetches = fetches[:-1]
            aux = None
            if plan.grad_norm_fetch:
                # opt-in (PADDLE_TPU_GRAD_NORM=1 at graph-build time): the
                # gauge read is a scalar device sync, so it rides the
                # FetchHandle's resolve path instead of blocking the
                # dispatch loop here
                aux = fetches[-1]
                fetches = fetches[:-1]
            # write the new state back BEFORE the numerics checks: donation
            # consumed the scope's old buffers at dispatch, so raising first
            # would leave the scope pointing at deleted arrays — writing the
            # (possibly non-finite) state keeps a watchdog failure
            # recoverable/inspectable, mirroring run_steps' finally-flush
            with _tr.span("executor/writeback", cat="executor"):
                for n, v in new_state.items():
                    if v is not None:
                        scope.set_var(n, v)
                if mask is not None:
                    _dev.check_numerics_mask(mask, compiled.watch_layout)
                _enforce_step_flags(fetch_names, fetches, new_state)
        except Exception as e:
            if fr is None:
                fr = _dev.flight_recorder()
            _safe_flight_dump(fr, "executor.run", e)
            raise

        if not fetch_names:
            if aux is not None:
                # no user fetches to hang a handle on — keep the old eager
                # gauge behavior instead of dropping the sample
                FetchHandle((), (), aux)._consume_aux()
            return []
        handle = FetchHandle(fetches, fetch_names, aux)
        if return_numpy:
            return handle.numpy()
        return handle

    # -- dispatch-plan machinery ----------------------------------------------
    def _resolve_plan(self, program, feed, fetch_names, scope, mesh,
                      accumulation_steps, mx_on, use_program_cache,
                      sample_stats=True):
        """(plan, canonical feeds, state, was_compile_miss) for this run.

        The hit path does near-zero bookkeeping: one dict lookup on the
        Program-resident plan table plus a cheap per-feed shape/dtype check;
        anything that doesn't match falls through to the full (slow) path,
        which rebuilds the plan in place.
        """
        block = program.global_block
        is_test = in_test_mode()
        # Opt-in grad-norm gauge: the probe var is non-persistable (kept out
        # of checkpoints and the state signature), so it reaches the host as
        # a hidden extra fetch appended to the user's fetch list.
        grad_norm_fetch = bool(mx_on and GRAD_NORM_VAR in block.vars
                               and GRAD_NORM_VAR not in fetch_names)
        # PADDLE_TPU_CHECK_NUMERICS=2 compiles a GUARDED step variant (per-op
        # isfinite mask, _CompiledStep numerics=True) — part of both cache
        # keys so flipping the env var mid-process re-specializes instead of
        # silently reusing the unguarded step
        numerics = _dev.numerics_level() >= 2
        # PADDLE_TPU_NUMERICS>=1 compiles the STATS variant (packed per-op
        # stat rows, _CompiledStep stats=True) — this read is the entire
        # level-0 cost, and it joins both cache keys for the same
        # no-silent-reuse reason as the watchdog flag. Armed, only every
        # Nth chunk runs the stats variant (PADDLE_TPU_NUMERICS_EVERY,
        # chunk 0 always sampled): both variants sit side by side in the
        # plan/compile caches, so steady state alternates between two
        # cache hits and the per-op reduction cost is paid 1/N of the time
        stats = _num.stats_level() >= 1
        if stats and sample_stats:
            every = _num.stats_every()
            if every > 1:
                k = getattr(program, "_numerics_chunk", 0)
                program._numerics_chunk = k + 1
                stats = (k % every) == 0
        feed_names = tuple(sorted(feed))
        mesh_id = id(mesh) if mesh is not None else None
        # shapes are part of the key so alternating batch shapes (the last
        # partial batch of every epoch, train/eval interleave) each keep
        # their own plan instead of thrashing one slot; non-array feeds
        # (shape None) fall through to the per-feed spec check on hit
        feed_shapes = tuple(getattr(feed[n], "shape", None)
                            for n in feed_names)
        plan_key = (feed_names, feed_shapes, fetch_names, is_test, mesh_id,
                    accumulation_steps, grad_norm_fetch, numerics, stats)

        plans = None
        if use_program_cache:
            # plans live ON the Program (keyed by version) so they die with
            # it — an executor-held dict keyed by id(program) leaks entries
            # per mutation and can serve stale state after id() reuse
            entry = getattr(program, "_dispatch_plans", None)
            if entry is None or entry[0] != program._version:
                entry = (program._version, {})
                program._dispatch_plans = entry
            plans = entry[1]
            plan = plans.get(plan_key)
            if plan is not None:
                feeds = self._feeds_from_plan(plan, feed)
                if feeds is not None:
                    state = self._gather_plan_state(plan, scope)
                    if state is not None:
                        if mx_on:
                            _m_plan_hit.inc()
                            _m_cache_hit.inc()
                        return plan, feeds, state, False

        # ---- slow path: full per-run bookkeeping ----
        if mx_on:
            _m_plan_miss.inc()
        feeds = {}
        feed_sig = []
        feed_specs = []
        for name in feed_names:
            var = block.var(name) if block.has_var(name) else None
            if var is not None:
                dtype = var.dtype
            else:
                v0 = feed[name]
                dt0 = getattr(v0, "dtype", None)
                dtype = str(dt0) if dt0 is not None else np.asarray(v0).dtype.name
            arr = _canon(feed[name], dtype)
            feeds[name] = arr
            feed_sig.append((name, arr.shape, str(arr.dtype)))
            feed_specs.append((name, np.dtype(arr.dtype), arr.shape))

        cached = getattr(program, "_pnames_cache_entry", None)
        if cached is not None and cached[0] == program._version:
            state_names = cached[1]
        else:
            state_names = self._persistable_names(program, scope)
            program._pnames_cache_entry = (program._version, state_names)
        # state vars that actually exist (startup creates them on first run);
        # iteration follows the pre-sorted state_names so no per-step re-sort
        state = {}
        svars = scope.vars
        for n in state_names:
            v = svars.get(n)
            if v is None and scope.parent is not None:
                v = scope.find_var(n)
            if v is not None:
                state[n] = v
        avail_state_names = tuple(state)

        run_fetch_names = (fetch_names + (GRAD_NORM_VAR,)
                           if grad_norm_fetch else fetch_names)
        is_training_or_has_feed = bool(feeds) or bool(fetch_names)
        key = (
            id(program),
            program._version,
            tuple(feed_sig),
            run_fetch_names,
            avail_state_names,
            is_test,
            mesh_id,
            accumulation_steps,
            numerics,
            stats,
        )
        compiled = self._cache.get(key) if use_program_cache else None
        was_miss = compiled is None
        if compiled is None:
            from .log import vlog

            vlog(1, "Executor: compiling new step specialization "
                    "(program v%s, %d feeds, fetch=%s, test=%s)",
                 program._version, len(feed_sig), list(fetch_names), is_test)
            if mx_on:
                _m_cache_miss.inc()
            t_build = time.perf_counter() if mx_on else 0.0
            with _tr.span("executor/trace_setup", cat="executor",
                          args={"program_version": program._version,
                                "n_feeds": len(feed_sig)}):
                compiled = _CompiledStep(
                    program,
                    feed_names,
                    run_fetch_names,
                    state_names,
                    is_test=is_test,
                    jit=is_training_or_has_feed,
                    mesh=mesh,
                    accumulation_steps=accumulation_steps,
                    numerics=numerics,
                    stats=stats,
                )
            if mx_on:
                _m_trace_ms.observe((time.perf_counter() - t_build) * 1e3)
            if use_program_cache:
                self._cache[key] = compiled
        elif mx_on:
            _m_cache_hit.inc()

        put_specs = batch_sh = mesh_repl = None
        if mesh is not None:
            # Mesh layout is a function of (program version, mesh) — memoize
            # the annotation walk on the plan instead of re-walking every
            # program var per run. Placement itself stays per-run (values
            # change); see _place.
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh_repl = _mesh_repl(mesh)
            put_specs = {}
            for v in program.list_vars():
                spec = getattr(v, "sharding", None)
                if valid_sharding(spec, mesh):
                    put_specs[v.name] = NamedSharding(mesh, P(*spec))
            batch_sh = NamedSharding(mesh, _mesh_batch_spec(mesh))

        plan = _DispatchPlan(tuple(feed_specs), fetch_names, run_fetch_names,
                             grad_norm_fetch, numerics, stats, state_names,
                             avail_state_names, compiled, key, put_specs,
                             batch_sh, mesh_repl)
        if plans is not None:
            plans[plan_key] = plan
        return plan, feeds, state, was_miss

    @staticmethod
    def _feeds_from_plan(plan, feed):
        """Canonicalize ``feed`` against the plan's recorded dtypes; None on
        any shape mismatch (caller falls back to the slow path)."""
        feeds = {}
        for name, dt, shp in plan.feed_specs:
            v = feed[name]
            if isinstance(v, jax.ShapeDtypeStruct):
                if v.dtype != dt:
                    v = jax.ShapeDtypeStruct(v.shape, dt)
            else:
                if not isinstance(v, jax.Array):
                    v = np.asarray(v)
                if v.dtype != dt:
                    v = v.astype(dt)
            if v.shape != shp:
                return None
            feeds[name] = v
        return feeds

    @staticmethod
    def _gather_plan_state(plan, scope):
        state = {}
        svars = scope.vars
        parent = scope.parent
        for n in plan.state_names:
            v = svars.get(n)
            if v is None and parent is not None:
                v = scope.find_var(n)
            if v is not None:
                state[n] = v
        if tuple(state) != plan.avail_names:
            # scope membership changed since the plan was built (a var
            # loaded/erased — including same-COUNT swaps from partial
            # checkpoint loads) — rebuild so the specialization key, which
            # is keyed on the exact available-state tuple, stays honest
            return None
        return state

    def _place(self, plan, state, feeds, mesh):
        if mesh is not None:
            # Lay out state across the mesh: replicated by default (the Fluid
            # BCastParamsToDevices moment, parallel_executor.cc:340), or per
            # Variable.sharding annotation (model-parallel params, sharded
            # embeddings). Feeds shard on the data axis. No-op when already
            # laid out correctly.
            repl = plan.mesh_repl
            specs = plan.put_specs
            state = {k: jax.device_put(v, specs.get(k, repl))
                     for k, v in state.items()}
            feeds = {k: jax.device_put(v, plan.batch_sh)
                     for k, v in feeds.items()}
        else:
            dev = self._device()
            if state:
                # State rides a donate_argnums=(0,) jit. Host (numpy)
                # entries — the scope right after a checkpoint load — MUST
                # become jax-OWNED copies first: on the CPU backend a
                # zero-copy device_put would alias the numpy buffer, and
                # donating an aliased buffer lets the async execution keep
                # using memory Python frees the moment the scope swaps in
                # the step's outputs (observed as rare corrupted/NaN state
                # in the first chunk after a restore).
                # Then commit the state where the feeds go. The startup
                # program's outputs are uncommitted, the step's own outputs
                # are committed (its feeds are), and jit specializes on
                # that: without this the SECOND step compiles the whole
                # program again (chip_smoke.py's train phase counts compiles
                # after the first step). Same device: the buffer is shared,
                # not copied. Committed jax.Arrays pass through untouched —
                # the steady-state carry costs nothing.
                def owned(v):
                    if not isinstance(v, jax.Array):
                        v = jnp.array(v)
                    if dev is not None and not v.committed:
                        v = jax.device_put(v, dev)
                    return v

                state = {k: owned(v) for k, v in state.items()}
            if dev is not None and feeds:
                # jax.Arrays already on the right device skip the device_put —
                # re-placing them every step costs real host time. Arrays
                # committed elsewhere (e.g. fetched from a CPU executor) still
                # get moved like before.
                feeds = {k: v if isinstance(v, jax.Array) and dev in v.devices()
                         else jax.device_put(v, dev)
                         for k, v in feeds.items()}
        return state, feeds

    # -- fused multi-step driver ----------------------------------------------
    def run_steps(
        self,
        program: Optional[Program] = None,
        feed_iter=None,
        steps: Optional[int] = None,
        fetch_list: Optional[Sequence] = None,
        fetch_every: int = 1,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """Drive up to ``steps`` training steps, fusing ``fetch_every``
        consecutive steps into ONE dispatched call (a ``lax.scan`` over feed
        batches stacked on a new leading axis), so host dispatch cost per
        step drops to 1/``fetch_every`` and state never round-trips through
        the scope between fused steps.

        ``feed_iter`` yields one feed dict per step — a plain iterator, a
        generator, or a :class:`~paddle_tpu.reader.DevicePrefetcher` (its
        batches are drained directly; if run_steps is what starts it, it
        also stops it on return, so an early exit at ``steps`` never leaves
        the worker thread pinning device buffers — pre-start it or use its
        context manager to keep ownership). When omitted, started
        ``py_reader``\\ s bound to the program are drained instead, stopping
        cleanly at EOF. ``steps=None`` runs until the feed source is
        exhausted. A feed-shape change between chunks (the final partial
        batch of an epoch) transparently re-resolves the dispatch plan,
        like ``run()``'s per-shape plans.

        Returns per-step fetch rows (``return_numpy=True``: a list of
        ``[np.ndarray, ...]`` rows, bit-identical to ``steps`` individual
        ``run()`` calls) or one :class:`FetchHandle` per fused dispatch
        (``return_numpy=False``; a multi-step chunk's handle resolves to
        arrays whose leading axis is that chunk's step count, a
        single-step chunk's to plain per-fetch arrays like ``run()``).
        """
        program, mesh, accumulation_steps = self._unwrap_program(program, scope)
        if scope is None:
            scope = global_scope()
        fetch_names = self._fetch_names(fetch_list)
        k = max(1, int(fetch_every))
        # readers and the step counter stay bound to the source program; the
        # optimized clone owns plans/specializations (same split as run())
        src_program = program
        program = self._maybe_optimize(program, fetch_names, scope)

        owned_prefetcher = None
        if feed_iter is None:
            readers = [r for r in getattr(src_program, "_py_readers", ())
                       if r._started]
            if not readers:
                raise ValueError(
                    "run_steps() needs a feed_iter or a started py_reader "
                    "bound to the program")
            from .reader.py_reader import EOFException

            def _drain_readers():
                while True:
                    f = {}
                    try:
                        for r in readers:
                            f.update(r.next_feed())
                    except EOFException:
                        return
                    yield f

            feed_iter = _drain_readers()
        else:
            from .reader.prefetcher import DevicePrefetcher

            if (isinstance(feed_iter, DevicePrefetcher)
                    and feed_iter._thread is None):
                # we start it (via iter below), so we own its lifecycle:
                # stop it on exit so an early return at ``steps`` doesn't
                # leave the worker blocked holding device buffers. A
                # caller-started prefetcher (start() / context manager) is
                # the caller's to stop.
                owned_prefetcher = feed_iter
            feed_iter = iter(feed_iter)

        def _shape_sig(f):
            """(signature, feed) — list/scalar feed values (run() accepts
            them too) are converted to numpy ONCE here; the returned feed
            carries the converted arrays so canon never re-converts."""
            sig = []
            conv = None
            for n in sorted(f):
                v = f[n]
                shp = getattr(v, "shape", None)
                if shp is None:
                    v = np.asarray(v)
                    if conv is None:
                        conv = dict(f)
                    conv[n] = v
                    shp = v.shape
                sig.append((n, tuple(shp)))
            return tuple(sig), (conv if conv is not None else f)

        mx_on = _mx._enabled
        fr = _dev.flight_recorder()  # None unless PADDLE_TPU_FLIGHT_DIR set
        rows: List[Any] = []      # return_numpy=True: one row per step
        handles: List[FetchHandle] = []  # else: one handle per fused chunk
        state = None
        plan = None
        consumed = 0
        pending = None  # lookahead feed cut from the previous chunk
        try:
            while steps is None or consumed < steps:
                want = k if steps is None else min(k, steps - consumed)
                chunk = []
                sig0 = None
                while len(chunk) < want:
                    if pending is not None:
                        f, pending = pending, None
                    else:
                        try:
                            f = next(feed_iter)
                        except StopIteration:
                            break
                        except Exception as e:
                            # typed data-side error: names the step index
                            # within the chunk (and the global step), and
                            # rides the outer except into the flight dump
                            _faults.record_feed_error()
                            raise FeedError(
                                "run_steps(): feed source raised at global "
                                "step %d (position %d of the current "
                                "%d-step chunk): %s: %s"
                                % (consumed + len(chunk), len(chunk), want,
                                   type(e).__name__, e)) from e
                    try:
                        sig, f = _shape_sig(f)
                    except Exception as e:
                        _faults.record_feed_error()
                        raise FeedError(
                            "run_steps(): feed for global step %d (position "
                            "%d of the current %d-step chunk) could not be "
                            "converted to arrays: %s: %s"
                            % (consumed + len(chunk), len(chunk), want,
                               type(e).__name__, e)) from e
                    if chunk and sig != sig0:
                        # shape boundary (the epoch's final partial batch):
                        # cut the chunk here — stacking needs uniform
                        # shapes — and carry the odd feed into the next
                        # chunk, where the plan re-resolves for it
                        pending = f
                        break
                    sig0 = sig
                    chunk.append(f)
                if not chunk:
                    break

                chunk_was_miss = False
                if plan is not None:
                    try:
                        chunk_feeds = [self._canon_chunk_feed(plan, f)
                                       for f in chunk]
                    except ValueError:
                        # the feed shape changed mid-stream (the final
                        # partial batch of a real epoch): flush the live
                        # carry to the scope and re-resolve a plan for the
                        # new shape — mirrors run()'s per-shape plans. A
                        # shape mix WITHIN one chunk still raises below
                        # (it cannot be stacked).
                        for name, v in state.items():
                            if v is not None:
                                scope.set_var(name, v)
                        plan = None
                if plan is None:
                    # sample_stats=False: the resolved plan persists across
                    # the whole stream, so a sampled decision would freeze
                    # arbitrarily — armed run_steps chunks are always
                    # observed (one fused chunk is one EMA tick already)
                    plan, feeds0, state, chunk_was_miss = self._resolve_plan(
                        program, chunk[0], fetch_names, scope, mesh,
                        accumulation_steps, mx_on, True,
                        sample_stats=False)
                    chunk_feeds = [feeds0]
                    chunk_feeds += [self._canon_chunk_feed(plan, f)
                                    for f in chunk[1:]]
                    state, _ = self._place(plan, state, {}, mesh)

                n = len(chunk_feeds)
                step_idx0 = self._next_step_index(src_program, n)
                if n == 1:
                    _, stacked = self._place(plan, {}, chunk_feeds[0], mesh)
                    compiled = plan.compiled
                else:
                    stacked = {name: jnp.stack([f[name] for f in chunk_feeds])
                               for name, _, _ in plan.feed_specs}
                    if mesh is None:
                        _, stacked = self._place(plan, {}, stacked, mesh)
                    # with a mesh, the chain's in_shardings (step axis
                    # replicated, batch axis over ``data``) lay the stack out
                    compiled, chain_miss = self._chain_for(plan, n)
                    chunk_was_miss = chunk_was_miss or chain_miss

                if fr is not None:
                    fr.record_step(
                        "run_steps", src_program, plan.feed_specs,
                        fetch_names,
                        extra={"chunk_steps": n,
                               "optimized": _dev.program_fingerprint(program)})
                spec = _faults.fire("executor.dispatch")
                if spec is not None and spec.kind == "nan":
                    stacked = _faults.poison_feeds(stacked)
                t0 = time.perf_counter() if mx_on else 0.0
                with jax.profiler.StepTraceAnnotation(
                        "train", step_num=int(step_idx0)), \
                        _tr.span("executor/run_steps_chunk", cat="executor",
                                 args={"steps": n}):
                    state, fetches = compiled(state, stacked, step_idx0)
                if mx_on:
                    # a fresh specialization/chain pays its jit trace + XLA
                    # compile on this first call — route that to the compile
                    # histogram so the steady-state chunk histogram stays
                    # clean, mirroring run()'s miss/hit split
                    (_m_compile_ms if chunk_was_miss else _m_chain_ms).observe(
                        (time.perf_counter() - t0) * 1e3)
                    _m_chain_dispatches.inc()
                    _m_chain_steps.inc(n)
                    _m_feed_bytes.inc(_nbytes(stacked.values()))
                    # keep the HBM signal alive for pipeline-driven jobs,
                    # same sampling policy as run()
                    if int(_m_chain_dispatches.value) % _HBM_SAMPLE_EVERY \
                            in (1, 0):
                        _update_hbm_gauges()
                consumed += n

                if plan.stats:
                    # stat rows pop first (stacked [n, K, S] for a fused
                    # chunk); accumulated before the watchdog check so the
                    # trip chunk's range history still lands host-side
                    _num.accumulate(
                        fetches[-1], plan.compiled.stats_layout,
                        fingerprint=_dev.program_fingerprint(src_program),
                        driver="run_steps")
                    fetches = fetches[:-1]
                mask = None
                if plan.numerics:
                    # the per-op isfinite mask rides last; a fused chunk's is
                    # stacked [n, K], so a NaN is attributed to BOTH the
                    # originating op and the step inside the chunk — the old
                    # post-step scan saw only the k-th step's fetches
                    mask = fetches[-1]
                    fetches = fetches[:-1]
                aux = None
                if plan.grad_norm_fetch:
                    aux = fetches[-1]
                    fetches = fetches[:-1]
                if mask is not None:
                    _dev.check_numerics_mask(mask, plan.compiled.watch_layout,
                                             driver="run_steps")
                _enforce_step_flags(plan.fetch_names, fetches, state)
                if not fetch_names:
                    if aux is not None:
                        FetchHandle((), (), aux)._consume_aux()
                    continue
                handle = FetchHandle(fetches, fetch_names, aux)
                if not return_numpy:
                    handles.append(handle)
                elif n == 1:
                    rows.append(handle.numpy())
                else:
                    arrs = handle.numpy()
                    rows.extend([a[i] for a in arrs] for i in range(n))
        except Exception as e:
            if fr is None:
                fr = _dev.flight_recorder()
            _safe_flight_dump(fr, "executor.run_steps", e)
            raise
        finally:
            # Donation consumed the scope's old state buffers at the first
            # dispatch — write the live carry back even on an error mid-loop.
            # Best-effort: if the FAILING dispatch itself already consumed
            # the carry via donation, those arrays are deleted and writing
            # them would poison the scope — skip them (recoverability after
            # a post-donation failure is inherently limited, same as run()).
            if state is not None:
                for name, v in state.items():
                    if v is None:
                        continue
                    if isinstance(v, jax.Array):
                        deleted = getattr(v, "is_deleted", None)
                        if deleted is not None and deleted():
                            continue
                    scope.set_var(name, v)
            if owned_prefetcher is not None:
                # we started it; stopping releases the worker thread and its
                # buffered device batches when we return before exhaustion
                owned_prefetcher.stop()

        if not fetch_names:
            return []
        return rows if return_numpy else handles

    def _canon_chunk_feed(self, plan, feed):
        try:
            feeds = self._feeds_from_plan(plan, feed)
        except KeyError:  # a feed name vanished mid-stream
            feeds = None
        if feeds is None or len(feed) != len(plan.feed_specs):
            raise ValueError(
                "run_steps(): feed dict changed shape/dtype/names mid-stream; "
                "expected %s" % [(n, str(d), s) for n, d, s in plan.feed_specs])
        return feeds

    def _chain_for(self, plan, length: int):
        """(chain, was_miss) — the fused-chain specialization for ``plan``."""
        key = plan.key + ("chain", length)
        chain = self._cache.get(key)
        was_miss = chain is None
        if chain is None:
            from .log import vlog

            vlog(1, "Executor: building fused %d-step chain", length)
            if _mx._enabled:
                _m_cache_miss.inc()
            chain = _CompiledStepChain(plan.compiled, length)
            self._cache[key] = chain
        elif _mx._enabled:
            _m_cache_hit.inc()
        return chain, was_miss

    # -- AOT warmup -----------------------------------------------------------
    def prepare(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
    ):
        """Ahead-of-time build + XLA-compile the step specialization for
        ``feed`` WITHOUT executing it (the TVM-style AOT artifact path).

        ``feed`` values may be real arrays, ``jax.ShapeDtypeStruct``\\ s, or
        ``(shape, dtype)`` tuples — only shapes/dtypes matter. The XLA
        executable lands in the persistent cache (compile_cache.py), so a
        later process (``tools/warmup.py`` then the
        real job) skips the compile entirely. Accepts a ``CompiledProgram``
        like ``run()`` (its mesh specialization is what gets AOT-compiled).
        Returns the cached ``_CompiledStep``.
        """
        program, mesh, accumulation_steps = self._unwrap_program(program, scope)
        if scope is None:
            scope = global_scope()
        feed = dict(feed or {})
        fetch_names = self._fetch_names(fetch_list)
        # AOT-compile the OPTIMIZED program — the same object run() resolves,
        # so the warmed specialization (and persistent-cache entry) is the
        # one the real job hits
        label = _step_label(program)
        program = self._maybe_optimize(program, fetch_names, scope)
        block = program.global_block
        abstract = {}
        for name in sorted(feed):
            v = feed[name]
            if isinstance(v, jax.ShapeDtypeStruct):
                abstract[name] = v
                continue
            if isinstance(v, tuple) and len(v) == 2 and not hasattr(v, "dtype"):
                shape, dtype = v
            else:
                arr = v if hasattr(v, "shape") else np.asarray(v)
                shape, dtype = arr.shape, arr.dtype
            var = block.var(name) if block.has_var(name) else None
            target = to_jnp_dtype(var.dtype) if var is not None else dtype
            canonical = jax.dtypes.canonicalize_dtype(target)
            abstract[name] = jax.ShapeDtypeStruct(tuple(shape), canonical)

        # the plan machinery accepts abstract feeds, so prepare() and a later
        # run() at the same shapes share one plan + specialization entry
        plan, _, state, _ = self._resolve_plan(
            program, abstract, fetch_names, scope, mesh, accumulation_steps,
            _mx._enabled, True)
        compiled = plan.compiled
        if not compiled.jitted:
            return compiled
        # run() lays state and feeds out before the step sees them (_place)
        # and the compile key follows the layout: lower for the same one, or
        # the warmed executable is one run() never asks the cache for
        def laid_out(tree, sharding_of):
            return {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=sharding_of(k))
                    for k, v in tree.items()}

        abstract_state = _abstractify(state)
        if mesh is not None:
            abstract_state = laid_out(
                abstract_state,
                lambda k: plan.put_specs.get(k, plan.mesh_repl))
            abstract = laid_out(abstract, lambda k: plan.batch_sh)
        elif self._device() is not None:
            at_dev = jax.sharding.SingleDeviceSharding(self._device())
            abstract_state = laid_out(abstract_state, lambda k: at_dev)
            abstract = laid_out(abstract, lambda k: at_dev)
        lowered, aot = _timed_lower_compile(
            compiled.fn, (abstract_state, abstract,
                          jax.ShapeDtypeStruct((), np.dtype("uint32"))),
            label)
        # the AOT artifacts are the attribution surface: the executable's
        # cost_analysis/memory_analysis feed the device_profile/* gauges
        # (memory_report, monitor.stepstats read them), and the lowered
        # module keeps the FULL per-op named-scope coverage that XLA's
        # fusion passes strip from the compiled text
        # (monitor.device.lowered_scope_text) — free here, prepare() paid
        # the lower+compile anyway
        compiled._lowered = lowered
        compiled._aot = aot
        _dev.publish_compiled_analysis(aot)
        return compiled

    @staticmethod
    def _publish_device_profile(compiled, state, feeds):
        """``PADDLE_TPU_DEVICE_PROFILE=1`` compile-miss hook: AOT-lower this
        specialization at abstract shapes and publish the device_profile/*
        gauges. Costs an extra trace (+ an XLA compile, which the
        persistent cache serves where JAX's thresholds admitted it) — a
        debug opt-in, never on the default path, never raising into the
        step."""
        try:
            abstract_state, abstract_feeds = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(
                    tuple(getattr(v, "shape", ())),
                    getattr(v, "dtype", np.float32)),
                (state, feeds))
            aot = compiled.fn.lower(
                abstract_state, abstract_feeds,
                jax.ShapeDtypeStruct((), np.dtype("uint32"))).compile()
            compiled._aot = aot
            _dev.publish_compiled_analysis(aot)
        except Exception as e:
            from .log import vlog

            vlog(1, "device-profile analysis failed: %r", e)

    def memory_report(self, program=None, feed=None, fetch_list=None,
                      scope=None):
        """The authoritative pre-run memory figure for a compiled step:
        AOT-compile the (program, feed-spec) specialization WITHOUT running
        it and return ``compiled.memory_analysis()`` as a dict
        (``argument_bytes`` / ``output_bytes`` / ``temp_bytes`` /
        ``peak_hbm_bytes`` ...). ``feed`` takes the same abstract specs as
        :meth:`prepare` (``(shape, dtype)`` tuples suffice). Run the startup
        program first so parameters are part of the figure. This is the
        number ``contrib.utils.memory_usage``'s pre-trace estimate defers
        to, and the first thing to check after a RESOURCE_EXHAUSTED."""
        compiled = self.prepare(program, feed, fetch_list, scope)
        return _dev.memory_report_from(getattr(compiled, "_aot", None))

    # Fluid parity alias
    def infer_from_program(self, *a, **kw):
        return self.run(*a, **kw)

"""Tunable registrations: the knobs ``tools/autotune.py`` can sweep.

Each :class:`Tunable` names a table kernel key and supplies (a) the default
shape points to sweep on this backend, (b) the candidate config space at a
shape, (c) the hardcoded-default config (so every sweep reports a
before/after against what the code would have done untuned), (d) a
``build`` that returns a timeable ``(fn, args)`` and (e) analytic cost
features for the pre-timing prune.

Registered here:

* ``flash_attention`` — Pallas flash BlockSizes (block_q x block_k), the
  knob the round-4 hand sweep found 3.57x in;
* ``sparse_adam`` — ids-per-grid-step of the row-DMA sparse Adam/SGD
  kernel (how many row DMAs ride one gather wave);
* ``softmax_xent`` — (batch, vocab) tile sizes of the streamed
  softmax-with-cross-entropy kernel;
* ``pass_gates`` — per-program ``PADDLE_TPU_PASS_*`` gate selection,
  measured END-TO-END on the optimized clone's step time (a pass that
  costs more than it saves on a given program gets turned off for it);
* ``paged_attention`` — ``block_pages`` of the ragged paged-attention
  decode kernel (KV pages DMA'd per online-softmax wave).

On CPU every tunable still builds and times (Pallas interpret mode / XLA
CPU) so CI exercises the full mechanism; TPU numbers land via the same CLI
on hardware. Heavy imports stay inside methods — this module must be cheap
to import and cycle-free (ops import ``tune.table`` lazily at trace time).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence

from . import table as _table

__all__ = ["Tunable", "register_tunable", "get_tunable",
           "registered_tunables"]


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


class Tunable:
    """One searchable knob. Subclasses define the space; the driver
    (:func:`paddle_tpu.tune.search`) does the measuring and persisting."""

    kernel: str = "?"

    def default_shapes(self) -> List[dict]:
        """Shape points ``tools/autotune.py --all`` sweeps on this backend
        (small on CPU — mechanism coverage; realistic on TPU)."""
        raise NotImplementedError

    def bucket(self, shape: dict) -> str:
        raise NotImplementedError

    def candidates(self, shape: dict) -> List[dict]:
        raise NotImplementedError

    def default_config(self, shape: dict) -> dict:
        """What the code does today with no table — the sweep's baseline."""
        raise NotImplementedError

    def build(self, shape: dict, config: dict):
        """``(fn, args)`` such that ``fn(*args)`` executes one measurable
        unit of work under ``config`` (first call may trace+compile; the
        driver excludes it from timing)."""
        raise NotImplementedError

    def cost(self, shape: dict, config: dict) -> dict:
        """Analytic features for pruning (``vmem_bytes`` is the one the
        driver acts on)."""
        return {}

    def cleanup(self) -> None:
        """Release anything ``build`` left open (engines, scopes)."""

    def shape_label(self, shape: dict) -> str:
        return ",".join("%s=%s" % (k, shape[k]) for k in sorted(shape))


_REGISTRY: Dict[str, Callable[[], "Tunable"]] = {}


def register_tunable(name: str):
    """Class decorator: make ``name`` resolvable via :func:`get_tunable`
    (and sweepable via ``tools/autotune.py --kernel name``)."""

    def deco(factory):
        _REGISTRY[name] = factory
        return factory

    return deco


def get_tunable(name: str) -> Tunable:
    if name not in _REGISTRY:
        raise KeyError("unknown tunable %r (registered: %s)"
                       % (name, ", ".join(sorted(_REGISTRY))))
    return _REGISTRY[name]()


def registered_tunables() -> List[str]:
    return sorted(_REGISTRY)


# -- flash attention BlockSizes ----------------------------------------------


@register_tunable("flash_attention")
class FlashAttentionTunable(Tunable):
    """block_q x block_k tiles of the vendored Pallas flash kernel. The
    space mirrors the round-4 hand sweep (benchmarks/sweep_flash_blocks.py)
    that found 512x512; oversized tiles whose f32 probs block would blow
    VMEM are pruned analytically before timing."""

    kernel = "flash_attention"
    _BLOCKS = (128, 256, 512, 1024, 2048)

    def default_shapes(self):
        if _on_tpu():
            return [dict(b=1, h=8, s=s, d=64, causal=True, dtype="bfloat16")
                    for s in (2048, 4096, 8192)]
        # interpret-mode mechanism shapes: small enough for seconds on CPU
        return [dict(b=1, h=1, s=256, d=64, causal=True, dtype="float32"),
                dict(b=1, h=1, s=512, d=64, causal=True, dtype="float32")]

    def bucket(self, shape):
        return _table.bucket_seq(shape["s"], shape["s"])

    def _blocks_for(self, s: int):
        return [bq for bq in self._BLOCKS if s % bq == 0 and bq <= s]

    def candidates(self, shape):
        blocks = self._blocks_for(shape["s"])
        return [{"block_q": bq, "block_k": bk}
                for bq in blocks for bk in blocks]

    def default_config(self, shape):
        # the untuned fallback: largest of (512, 256, 128) dividing s —
        # attention_ops._pick_block, NOT the table-consulting lookup
        from ..ops.attention_ops import _pick_block

        b = _pick_block(shape["s"])
        return {"block_q": b, "block_k": b}

    def cost(self, shape, config):
        bq, bk, d = config["block_q"], config["block_k"], shape["d"]
        # per-grid-step VMEM working set, f32: the probs/ds block (bq x bk)
        # plus q/o tiles (bq x d) and k/v tiles (bk x d)
        return {"vmem_bytes": 4 * (bq * bk + 2 * bq * d + 2 * bk * d)}

    def make_block_sizes(self, config, sq: int, sk: int):
        # the SHARED (bq, bk) -> BlockSizes mapping — candidates are
        # measured under exactly the assignment _tuned_block_sizes serves
        from ..ops.attention_ops import _block_sizes_for

        return _block_sizes_for(min(int(config["block_q"]), sq),
                                min(int(config["block_k"]), sk))

    def build(self, shape, config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops.pallas_kernels import flash_attention as fa

        b, h, s, d = shape["b"], shape["h"], shape["s"], shape["d"]
        dtype = jnp.dtype(shape.get("dtype", "float32"))
        causal = bool(shape.get("causal", True))
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(b, h, s, d), dtype)
                   for _ in range(3))
        bs = self.make_block_sizes(config, s, s)
        sm = 1.0 / float(d) ** 0.5
        if _on_tpu():
            # fwd+bwd — the hand-tuned numbers this subsystem replaces were
            # fwd+bwd medians, so the table ranks the same quantity
            def loss(q, k, v):
                o = fa.flash_attention(q, k, v, causal=causal, sm_scale=sm,
                                       block_sizes=bs)
                return o.astype(jnp.float32).sum()

            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            return step, (q, k, v)

        # CPU: interpret-mode forward (the interpreter runs the REAL kernel
        # body; bwd interpret is minutes-slow, and mechanism coverage only
        # needs the config to flow into a measured, parity-checkable call)
        def fwd(q, k, v):
            prev = fa.INTERPRET
            fa.INTERPRET = True
            try:
                return fa.flash_attention(q, k, v, causal=causal,
                                          sm_scale=sm, block_sizes=bs)
            finally:
                fa.INTERPRET = prev

        return fwd, (q, k, v)


# -- sparse-adam row blocks ---------------------------------------------------


@register_tunable("sparse_adam")
class SparseAdamTunable(Tunable):
    """ids-per-grid-step of the row-DMA sparse Adam kernel: how many
    3-table row gathers ride one DMA wave before the VPU block runs."""

    kernel = "sparse_adam"
    _BLOCKS = (8, 16, 32, 64, 128, 256)

    def default_shapes(self):
        if _on_tpu():
            # the compiled kernel takes whole 128-lane rows only
            # (sparse_adam.sparse_rows_gate)
            return [dict(vocab=1_000_000, dim=128, n=4096),
                    dict(vocab=1_000_000, dim=128, n=16384)]
        return [dict(vocab=512, dim=16, n=256),
                dict(vocab=2048, dim=16, n=1024)]

    def bucket(self, shape):
        return _table.bucket_rows(shape["n"], shape["dim"])

    def candidates(self, shape):
        cap = max(8, -(-shape["n"] // 8) * 8)
        return [{"block": b} for b in self._BLOCKS if b <= cap]

    def default_config(self, shape):
        from ..ops.pallas_kernels.sparse_adam import _BLOCK

        return {"block": min(_BLOCK, max(8, -(-shape["n"] // 8) * 8))}

    def cost(self, shape, config):
        # 4 VMEM scratch tiles of [block, dim] f32 (p/m/v + grad rows)
        return {"vmem_bytes": 4 * 4 * config["block"] * shape["dim"]}

    def build(self, shape, config):
        import jax.numpy as jnp
        import numpy as np

        from ..core.sparse import merge_rows
        from ..ops.pallas_kernels.sparse_adam import sparse_adam_rows

        vocab, dim, n = shape["vocab"], shape["dim"], shape["n"]
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, vocab, (n,)).astype(np.int32))
        rows = jnp.asarray(rng.randn(n, dim).astype(np.float32))
        uniq, merged = merge_rows(ids, rows, vocab)
        p = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
        m = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.1)
        v = jnp.asarray(np.abs(rng.randn(vocab, dim)).astype(np.float32))
        fn = functools.partial(
            sparse_adam_rows, lr_t=0.01, interpret=not _on_tpu(),
            block=int(config["block"]))
        return (lambda: fn(p, m, v, uniq, merged)), ()


# -- softmax-xent tiles -------------------------------------------------------


@register_tunable("softmax_xent")
class SoftmaxXentTunable(Tunable):
    """(batch-rows, vocab-lanes) tile of the streamed softmax-with-
    cross-entropy kernel — the knob trading VMEM residency of the running
    max/sumexp accumulators against per-tile grid overhead at V=32k."""

    kernel = "softmax_xent"
    _BN = (64, 128, 256, 512)
    _BV = (512, 1024, 2048, 4096)

    def default_shapes(self):
        if _on_tpu():
            return [dict(n=4096, v=32768)]
        return [dict(n=128, v=1024)]

    def bucket(self, shape):
        return _table.bucket_nv(shape["n"], shape["v"])

    def candidates(self, shape):
        n, v = shape["n"], shape["v"]
        return [{"block_n": bn, "block_v": bv}
                for bn in self._BN if bn <= max(8, n)
                for bv in self._BV if bv <= max(128, v)]

    def default_config(self, shape):
        from ..ops.pallas_kernels import softmax_xent as sx

        bn, bv = sx._shrink_tiles(shape["n"], shape["v"], sx._BN, sx._BV)
        return {"block_n": bn, "block_v": bv}

    def cost(self, shape, config):
        bn, bv = config["block_n"], config["block_v"]
        # the [bn, bv] f32 logits tile + three [bn, 1] accumulators
        return {"vmem_bytes": 4 * (bn * bv + 3 * bn)}

    def build(self, shape, config):
        import jax.numpy as jnp
        import numpy as np

        from ..ops.pallas_kernels import softmax_xent as sx

        n, v = shape["n"], shape["v"]
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(n, v).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, v, (n, 1)).astype(np.int32))
        bn, bv = sx._shrink_tiles(n, v, int(config["block_n"]),
                                  int(config["block_v"]))
        plog, plab, n_pad, v_pad = sx._pad_to(logits, labels, bn, bv)
        interp = not _on_tpu()

        def fwd():
            return sx._call_fwd(plog, plab, bn, bv, interp, 0.0, v)

        return fwd, ()


# -- paged-attention wave width ----------------------------------------------


@register_tunable("paged_attention")
class PagedAttentionTunable(Tunable):
    """``block_pages`` of the ragged paged-attention decode kernel: KV
    pages DMA'd per online-softmax wave. Wider waves amortize DMA issue
    and rescale cost but grow the kernel's two K and two V wave buffers
    (and waste work on short ragged contexts whose last wave is mostly
    masked); the engine's trace-time ``_block_pages`` lookup serves
    whatever this sweep persists.

    On a TPU the shapes are the serve cells' own (PERF.md section 4): bf16
    pools, the cell's slots, KV heads, query heads a KV head
    (``q_per_kv``) and pages a slot, and ``live`` = (shortest, longest,
    how many) live contexts of a decode step there, the other slots
    holding no request. One timed unit is ``calls`` kernel calls in one
    program, as a decode step makes one a layer."""

    kernel = "paged_attention"

    def default_shapes(self):
        if _on_tpu():
            cell = dict(page_size=16, d_head=128, dtype="bfloat16",
                        slots=16, calls=32)
            return [
                # laguna-s-ep2-serve: full layers, then the 512-row rings
                dict(cell, max_ctx=16384, n_head=8, q_per_kv=6,
                     live=(2400, 7200, 16)),
                dict(cell, max_ctx=512, n_head=8, q_per_kv=9,
                     live=(512, 512, 16)),
                # smallthinker-21b-a3b-serve: global layers, 4,096-row rings
                dict(cell, max_ctx=16384, n_head=4, q_per_kv=7,
                     live=(700, 5400, 12)),
                dict(cell, max_ctx=4096, n_head=4, q_per_kv=7,
                     live=(700, 4096, 12)),
                # gpt2-small-serve, as a loaded server would run it
                dict(cell, slots=32, max_ctx=1024, n_head=12, d_head=64,
                     q_per_kv=1, live=(100, 900, 24)),
                # ouro-2.6b-serve: one query head a KV head of a whole lane
                # tile, 2,048-lane rows, 7 of 12 slots live
                dict(cell, slots=12, max_ctx=1024, n_head=16, q_per_kv=1,
                     live=(190, 662, 7)),
            ]
        # interpret-mode mechanism shape: seconds on CPU
        return [dict(slots=4, max_ctx=64, page_size=8, n_head=2, d_head=16)]

    def bucket(self, shape):
        return _table.bucket_ctx(shape["max_ctx"],
                                 shape["n_head"] * shape["d_head"])

    @staticmethod
    def _itemsize(shape):
        import jax.numpy as jnp

        return jnp.dtype(shape.get("dtype", "float32")).itemsize

    def candidates(self, shape):
        from ..ops.pallas_kernels.paged_attention import _wave_fits

        pps = shape["max_ctx"] // shape["page_size"]
        fits = _wave_fits(shape["page_size"],
                          shape["n_head"] * shape["d_head"],
                          self._itemsize(shape))
        out, bp = [], 1
        while bp <= min(pps, fits):  # wider ones are clamped to these
            out.append({"block_pages": bp})
            bp *= 2
        return out

    def default_config(self, shape):
        from ..ops.pallas_kernels.paged_attention import _default_block_pages

        pps = shape["max_ctx"] // shape["page_size"]
        return {"block_pages": _default_block_pages(
            shape["page_size"], pps, shape["n_head"] * shape["d_head"],
            self._itemsize(shape))}

    def cost(self, shape, config):
        # the two K and two V wave buffers the kernel keeps resident
        return {"vmem_bytes": 4 * self._itemsize(shape)
                * config["block_pages"] * shape["page_size"]
                * shape["n_head"] * shape["d_head"]}

    def build(self, shape, config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops.pallas_kernels.paged_attention import paged_decode_attention

        slots, ps = shape["slots"], shape["page_size"]
        h, d, max_ctx = shape["n_head"], shape["d_head"], shape["max_ctx"]
        g, calls = shape.get("q_per_kv", 1), shape.get("calls", 1)
        dtype = jnp.dtype(shape.get("dtype", "float32"))
        pps = max_ctx // ps
        num_pages = slots * pps  # full-occupancy pool, like the engine's
        kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
        # one layer as the pool stores it, [rows, H*D]: the tuner times the
        # kernel, not a reshape (drawn on the device: a cell's layer is a
        # quarter of a billion values)
        k_pool = jax.random.normal(kk, (num_pages * ps, h * d), dtype)
        v_pool = jax.random.normal(kv, (num_pages * ps, h * d), dtype)
        q = jax.random.normal(kq, (slots, g * h, d), dtype)
        pt = jnp.asarray(np.random.RandomState(0).permutation(num_pages)
                         .reshape(slots, pps).astype(np.int32))
        # the ragged mix the engine actually sees: a spread of live lengths
        lo, hi, live = shape.get("live", (1, max_ctx, slots))
        ctx = np.zeros(slots, np.int32)
        ctx[:live] = np.linspace(lo, hi, live).round()
        ctx = jnp.asarray(ctx)
        fn = functools.partial(
            paged_decode_attention, page_size=ps,
            sm_scale=1.0 / float(d) ** 0.5,
            block_pages=int(config["block_pages"]),
            interpret=not _on_tpu())

        @jax.jit
        def step(q, k_pool, v_pool, pt, ctx):  # arguments, not constants
            def body(i, acc):
                # the lengths move round the slots, or the compiler finds
                # the call the same in every turn and lifts it out
                return acc + fn(q, k_pool, v_pool, pt,
                                jnp.roll(ctx, i)).astype(jnp.float32)

            return jax.lax.fori_loop(0, calls, body,
                                     jnp.zeros(q.shape, jnp.float32))

        return step, (q, k_pool, v_pool, pt, ctx)


# -- pass gates (end-to-end measured) ----------------------------------------


@register_tunable("pass_gates")
class PassGatesTunable(Tunable):
    """Per-program ``PADDLE_TPU_PASS_*`` gate selection. Unlike the kernel
    tunables this measures END-TO-END step time of the optimized clone —
    the only honest metric for graph passes, whose value depends on what
    the rest of the pipeline and XLA do with their output. The memo in
    ``passes.pipeline.maybe_optimize`` keys on the active gate set, so each
    candidate gets its own optimized clone + compile (warmup, excluded) and
    cache-hit steady-state timing.

    Shapes are workload descriptors (JSON-safe): the canned MLP demo, or
    ``{"workload": "model", "model_dir": DIR}`` for a saved inference model
    (``tools/autotune.py --model``)."""

    kernel = "pass_gates"

    def __init__(self):
        self._built: Dict[str, tuple] = {}

    def default_shapes(self):
        return [dict(workload="mlp_demo", batch=32)]

    def _workload(self, shape):
        """(scope, exe, program, feed, fetch_list) for the descriptor,
        built once per shape and reused across candidates so every gate
        set sees identical work."""
        key = repr(sorted(shape.items()))
        if key in self._built:
            return self._built[key]
        import numpy as np

        import paddle_tpu as fluid

        batch = int(shape.get("batch", 32))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            if shape.get("workload") == "model":
                prog, feed_names, fetch_targets = fluid.io.load_inference_model(
                    shape["model_dir"], exe)
                rng = np.random.RandomState(0)
                feed = {}
                for nm in feed_names:
                    var = prog.global_block.var(nm)
                    shp = tuple(batch if (d or 0) < 0 else d
                                for d in (var.shape or ()))
                    feed[nm] = rng.randn(*shp).astype("float32")
                fetch = [t.name for t in fetch_targets]
            else:
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup):
                    x = fluid.layers.data("x", shape=[32])
                    y = fluid.layers.data("y", shape=[1], dtype="int64")
                    h = fluid.layers.fc(x, size=64, act="relu")
                    logits = fluid.layers.fc(h, size=10)
                    loss = fluid.layers.mean(
                        fluid.layers.softmax_with_cross_entropy(logits, y))
                    fluid.optimizer.SGD(0.1).minimize(loss)
                exe.run(startup)
                rng = np.random.RandomState(0)
                feed = {"x": rng.randn(batch, 32).astype("float32"),
                        "y": rng.randint(0, 10, (batch, 1)).astype("int64")}
                prog, fetch = main, [loss]
        built = (scope, exe, prog, feed, fetch)
        self._built[key] = built
        return built

    def bucket(self, shape):
        from ..monitor.device import program_fingerprint

        _, _, prog, _, _ = self._workload(shape)
        return "prog" + program_fingerprint(prog)[:12]

    def candidates(self, shape):
        from ..passes.pipeline import DEFAULT_PASS_NAMES

        # all-on plus each-single-off: enough to catch "this pass costs
        # more than it saves HERE" without a 2^6 sweep; a full subset
        # search can ride the same driver later if a workload warrants it
        return ([{"disable": []}]
                + [{"disable": [n]} for n in DEFAULT_PASS_NAMES])

    def default_config(self, shape):
        return {"disable": []}

    def build(self, shape, config):
        import paddle_tpu as fluid
        from ..passes.pipeline import pass_gate_overrides

        scope, exe, prog, feed, fetch = self._workload(shape)
        disabled = tuple(config.get("disable") or ())

        def step():
            with pass_gate_overrides(disabled):
                with fluid.scope_guard(scope):
                    return exe.run(prog, feed=feed, fetch_list=fetch)

        return step, ()

    def cleanup(self):
        self._built.clear()

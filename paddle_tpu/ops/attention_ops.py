"""Fused attention op.

The reference has NO fused attention — transformer models compose it from
primitive ops in Python (reference: tests/unittests/dist_transformer.py,
SURVEY.md §5.7). On TPU the fused kernel is the single most important op for
transformer throughput: this op lowers to the project-vendored Pallas TPU
flash-attention kernel (ops/pallas_kernels/flash_attention.py) when running
on TPU hardware, with an XLA-composed fallback elsewhere (CPU tests, odd
shapes, attention dropout). Segment-ids support is the XLA-native replacement for
Fluid's LoD variable-length batching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import OpContext, register_op


@functools.lru_cache(maxsize=1)
def _flash_fn():
    # project-owned vendored kernels (ops/pallas_kernels/flash_attention.py)
    # — a JAX upgrade can no longer change the kernels under us. A function
    # so that tests can put a fake in the kernel's place.
    from .pallas_kernels.flash_attention import SegmentIds, flash_attention

    return flash_attention, SegmentIds


def _on_tpu() -> bool:
    return jax.default_backend() not in ("cpu", "gpu")


def neg_inf(dtype) -> jnp.ndarray:
    """THE masking constant for every attention implementation in this
    package (composed sdpa, decode_attention, and the paged-attention
    Pallas kernel's reference check share it, so bf16/f32 masking semantics
    cannot drift between them). Scaled to the dtype — ``-0.7 * finfo.max``,
    the same convention as the vendored flash kernel's DEFAULT_MASK_VALUE —
    so it stays finite in bf16/f16 (a raw ``-1e30`` overflows f16 to -inf
    and then ``-inf - max`` NaNs the softmax) while ``exp()`` of it still
    underflows to exactly 0.0: masked positions contribute exactly nothing.
    """
    dt = jnp.dtype(dtype)
    return jnp.asarray(neg_inf_value(dt), dt)


def neg_inf_value(dtype) -> float:
    """:func:`neg_inf` as a host-side Python float — for call sites that
    bake the constant into a kernel as a static parameter (the paged-
    attention Pallas kernel), where a traced array would not do."""
    return -0.7 * float(jnp.finfo(jnp.dtype(dtype)).max)


def paged_kernel_mode():
    """Resolve ``FLAGS_paged_attention_kernel`` for this trace: None = the
    XLA gather + :func:`decode_attention` path, "compiled"/"interpret" =
    the ragged paged-attention Pallas kernel
    (pallas_kernels/paged_attention.py). "auto" compiles on TPU and keeps
    the gather path elsewhere — the interpreter is a correctness tool, not
    a fast CPU path (mirrors optimizer_ops._sparse_kernel_mode)."""
    from ..flags import flags

    mode = str(flags.paged_attention_kernel).lower()
    if mode in ("0", "off", "false", "no"):
        return None
    if mode == "interpret":
        return "interpret"
    on_tpu = jax.default_backend() == "tpu"
    if mode in ("1", "on", "true", "yes"):
        return "compiled" if on_tpu else "interpret"
    return "compiled" if on_tpu else None  # auto


def _pick_block(s: int):
    """Largest v5e-tuned tile (512 optimal, r4 sweep) that divides ``s``.
    Single source of truth for both sdpa and ring-attention block compute."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    raise ValueError(
        "flash-attention sequence length %d is not a multiple of 128 "
        "(the caller's gate should have rejected it)" % s)


def _divisor_block(want: int, s: int, fallback: int) -> int:
    """Largest power-of-two tile <= ``want`` that divides ``s`` (>=128);
    ``fallback`` when none does. Tuned entries are bucketed coarsely, so a
    512 tuned for s=8192 must legally serve s=384 by clamping to 128."""
    b = 1 << (max(int(want), 128).bit_length() - 1)
    while b >= 128:
        if s % b == 0:
            return b
        b //= 2
    return fallback


def _block_sizes_for(bq: int, bk: int):
    """The (bq, bk) -> full BlockSizes mapping (fwd + both backward
    kernels share the same tiles) — ONE definition, used by the trace-time
    lookup below AND the autotuner's flash candidate builds, so tuned
    entries are always measured under the exact block assignment they will
    later serve."""
    from .pallas_kernels.flash_attention import BlockSizes

    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )


def _tuned_block_sizes(sq: int, sk: int):
    """Tile sizes for the Pallas flash kernel: tuned table -> shipped
    seeds -> hardcoded fallback (paddle_tpu.tune).

    The hardcoded fallback encodes the round-4 hand sweep on the real v5e
    chip (benchmarks/sweep_flash_blocks.py): 512x512 optimal — 2.65 ms vs
    17.2 ms all-128 default vs 12.8 ms composed at b1 h8 s8192 d64 causal
    bf16 fwd+bwd, a 4.8x win; larger tiles amortize grid/DMA overhead and
    keep the MXU fed, beyond 512 the VMEM working set thrashes. The same
    numbers now also live in ``tune/shipped.json`` keyed tpu-v5e, and
    ``tools/autotune.py`` re-derives them per (shape-bucket, device_kind)
    by measurement — so other device kinds get their own optimum instead
    of inheriting v5e's. Blocks must divide the sequence lengths, so
    tuned/shorter shapes clamp to the largest working divisor; a corrupt
    or missing table silently yields the fallback (lookup never raises).
    """
    bq, bk = _pick_block(sq), _pick_block(sk)
    try:
        from .. import tune

        cfg, _src = tune.lookup("flash_attention", tune.bucket_seq(sq, sk))
        if cfg:
            bq = _divisor_block(int(cfg.get("block_q", bq)), sq, bq)
            bk = _divisor_block(int(cfg.get("block_k", bk)), sk, bk)
    except Exception:  # table layer must never take down a training trace
        pass
    return _block_sizes_for(bq, bk)


def _flash_ok(q, k, causal) -> bool:
    """Gate for the LONG path's Pallas kernel (online softmax over key
    blocks): blocking constraints (seq multiples of 128) AND a measured
    perf crossover. With the v5e-tuned BlockSizes (see _tuned_block_sizes)
    the round-4 sweep (benchmarks/sweep_flash_crossover.py, b* h8 d64
    causal bf16 fwd+bwd, loop-difference timing) measured flash speedup
    over composed: S=1024 0.80x, S=2048 1.61x, S=4096 3.46x, S=8192 4.15x,
    S=16384 3.25x. The crossover is ~S=2048, which is the
    FLAGS_flash_attention_min_seq default; below it the composed path's
    single fused HLO beats that kernel's fixed grid overhead, above it the
    O(S) memory AND the tiling win compound. (The composed path OOMs around
    S~24k single-chip, so flash is also the only viable path there.)

    What that sweep did NOT cover: dropout (the composed side drew no
    threefry mask and kept none for the backward), segment ids, more than
    one batch row a grid step (``block_b`` 1), and any kernel but the long
    one. A key length that is one tile has its own kernels and its own
    predicate, :func:`_single_tile_ok`, which :func:`sdpa` alone asks;
    this gate and its callers (:func:`gqa_causal_attention`,
    :func:`mla_causal_attention`) read as they did."""
    if not _on_tpu():
        return False
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq != sk:
        # the kernel's causal masking assumes square q/k lengths
        return False
    from ..flags import get_flag

    if max(sq, sk) < int(get_flag("flash_attention_min_seq")):
        return False
    return sq % 128 == 0 and sk % 128 == 0 and q.dtype in (jnp.float32, jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_dropout(q, k, v, seed, causal, sm_scale, rate):
    """Flash attention WITH in-kernel attention-probs dropout (r5).

    The vendored kernels regenerate the keep-mask from a counter-based hash
    of absolute (b, h, q, k) coordinates (_dropout_keep_tile), so forward
    and both backward kernels agree without materializing the [B,H,S,S]
    mask — the capability the stock kernels lack and the reason sdpa
    previously fell back to composed O(S^2) attention whenever attention
    dropout was on."""
    out, _ = _flash_dropout_fwd(q, k, v, seed, causal, sm_scale, rate)
    return out


def _flash_dropout_fwd(q, k, v, seed, causal, sm_scale, rate):
    from .pallas_kernels import flash_attention as fa

    bq = _pick_block(q.shape[2])
    bk = _pick_block(k.shape[2])
    o, l, m = fa._flash_attention_impl(
        q, k, v, None, None, True, causal, sm_scale, 1, bq, bk, bk, False,
        dropout_rate=rate, dropout_seed=seed)
    return o, (q, k, v, o, l, m, seed)


def _flash_dropout_bwd(causal, sm_scale, rate, res, do):
    import numpy as np

    from .pallas_kernels import flash_attention as fa

    q, k, v, o, l, m, seed = res
    bq = _pick_block(q.shape[2])
    bk = _pick_block(k.shape[2])
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    do = do.astype(q.dtype)
    dk, dv = fa._flash_attention_bwd_dkv(
        q, k, v, None, None, l, m, do, di,
        block_q_major=bq, block_q=bq, block_k_major=bk, block_k=bk,
        sm_scale=sm_scale, causal=causal,
        mask_value=fa.DEFAULT_MASK_VALUE, debug=False,
        dropout_rate=rate, dropout_seed=seed)
    dq, _ = fa._flash_attention_bwd_dq(
        q, k, v, None, None, l, m, do, di,
        block_q_major=bq, block_k_major=bk, block_k=bk,
        sm_scale=sm_scale, causal=causal,
        mask_value=fa.DEFAULT_MASK_VALUE, debug=False,
        dropout_rate=rate, dropout_seed=seed)
    seed_ct = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, seed_ct


_flash_dropout.defvjp(_flash_dropout_fwd, _flash_dropout_bwd)


# The fewest (row, head) pairs for which a key length of one tile takes the
# single-tile kernels. Below it a call is a few microseconds of chip work
# either way (GPT-2 small's prefill holds 12 pairs) and the composed lines
# stay; the table behind the number is in PERF.md section 6 under PR 45
# (benchmarks/diag_short_attention.py).
SINGLE_TILE_MIN_PAIRS = 64


def _single_tile_ok(q, k, causal, bias=None) -> bool:
    """Whether :func:`sdpa` computes this call in the single-tile kernels
    (ops/pallas_kernels/short_attention.py): the chip, no additive bias,
    S_q and S_k multiples of 128 and at most 512 (the whole key length is
    one tile), heads of 64 or 128 that fill whole lane tiles, and enough
    (row, head) pairs to be worth a kernel. Dropout and segment ids do not
    enter: the kernels take both."""
    if (not _on_tpu() or bias is not None or q.ndim != 4
            or q.shape[0] * q.shape[1] < SINGLE_TILE_MIN_PAIRS):
        return False
    from .pallas_kernels import short_attention as sa  # Pallas: a second

    return sa.supported(q.shape, k.shape, q.dtype, causal)


def _single_tile(q, k, v, seg_q, seg_kv, causal, sm_scale, rate, rng, mesh):
    """The single-tile kernels over the whole batch, or over each chip's
    rows where the batch is split over ``mesh``'s ``data`` axis: the
    compiler does not partition a ``tpu_custom_call`` (it would gather
    every row to every chip), so the call is mapped over the shards, and
    each shard moves the hash's batch coordinate by its first row so that
    two chips do not drop the same elements of their rows."""
    from .pallas_kernels import short_attention as sa

    seed = None
    if rate > 0.0:
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(rng, (1,), jnp.uint32), jnp.int32)

    def call(q, k, v, seg_q, seg_kv, seed):
        return sa.single_tile_attention(q, k, v, seg_q, seg_kv, seed, causal,
                                        float(sm_scale), float(rate))

    n = mesh.shape.get("data", 1) if mesh is not None else 1
    if n == 1 or q.shape[0] % n:
        return call(q, k, v, seg_q, seg_kv, seed)
    from jax.sharding import PartitionSpec as P

    rows = q.shape[0] // n

    def shard(q, k, v, seg_q, seg_kv, seed):
        if seed is not None:
            seed = sa.shard_seed(seed, jax.lax.axis_index("data") * rows)
        return call(q, k, v, seg_q, seg_kv, seed)

    row, seg = P("data"), None if seg_q is None else P("data")
    return jax.shard_map(
        shard, mesh=mesh, in_specs=(row, row, row, seg, seg, P()),
        out_specs=row, check_vma=False)(q, k, v, seg_q, seg_kv, seed)


def _count(path: str, calls: str = "attention/sdpa_calls",
           of: str = "sdpa") -> None:
    """One more call of ``of`` traced into ``path``: trace-time counters
    (an executable's calls count once, when it is traced)."""
    from ..monitor import metrics

    metrics.counter(
        "%s.%s" % (calls, path),
        help="%s calls traced into the %s path (counted where %s "
             "chooses: once a call of a traced program, not once a run)"
             % (of, path, of)).inc()


def sdpa(q, k, v, bias=None, segment_ids_q=None, segment_ids_kv=None,
         causal=False, sm_scale=1.0, dropout_rate=0.0, dropout_rng=None,
         mesh=None):
    """Scaled dot-product attention over [B, H, S, D] tensors. Three
    paths, chosen on the arguments' shapes alone: the single-tile kernels
    where the whole key length is one tile (:func:`_single_tile_ok`), the
    long path's flash kernels from ``FLAGS_flash_attention_min_seq`` on
    (:func:`_flash_ok`), else the composed lines. ``mesh`` is the device
    mesh of the trace, if any: the single-tile call is mapped over the
    shards of its ``data`` axis."""
    if _single_tile_ok(q, k, causal, bias):
        _count("single_tile")
        rate = dropout_rate if dropout_rng is not None else 0.0
        return _single_tile(q, k, v, segment_ids_q, segment_ids_kv, causal,
                            sm_scale, rate, dropout_rng, mesh)
    use_flash = dropout_rate == 0.0 and _flash_ok(q, k, causal)
    if (dropout_rate > 0.0 and dropout_rng is not None and bias is None
            and segment_ids_q is None and segment_ids_kv is None
            and _flash_ok(q, k, causal)):
        # in-kernel dropout path: same gate as flash, tight scope (no
        # bias/segments); seed derives from the op's per-step key
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(dropout_rng, (1,), jnp.uint32), jnp.int32)
        try:
            out = _flash_dropout(q, k, v, seed, causal, float(sm_scale),
                                 float(dropout_rate))
            _count("flash")
            return out
        except Exception as e:
            # honor the same never-hide contract as the no-dropout path:
            # falling back means an ~S^2 memory/perf cliff (note the try
            # wraps the forward TRACE; the custom-vjp backward compiles
            # from the same kernels, so a trace-time pass here covers it)
            from ..flags import get_flag

            if get_flag("strict_fused_attention"):
                raise RuntimeError(
                    "Pallas flash-with-dropout failed for shapes q=%s k=%s "
                    "(causal=%s): %s" % (q.shape, k.shape, causal, e)) from e
            import warnings

            warnings.warn(
                "flash-with-dropout failed (%s: %s); composed fallback. Set "
                "FLAGS_strict_fused_attention=1 to make this an error."
                % (type(e).__name__, e), RuntimeWarning, stacklevel=2)
    if use_flash:
        flash, SegmentIds = _flash_fn()
        seg = None
        if segment_ids_q is not None:
            seg = SegmentIds(q=segment_ids_q, kv=segment_ids_kv)
        try:
            bs = _tuned_block_sizes(q.shape[2], k.shape[2])
            out = flash(q, k, v, ab=bias, segment_ids=seg, causal=causal,
                        sm_scale=sm_scale, block_sizes=bs)
            _count("flash")
            return out
        except Exception as e:
            # A failed flash call means a ~S² perf regression — never hide it.
            from ..flags import get_flag

            if get_flag("strict_fused_attention"):
                raise RuntimeError(
                    "Pallas flash-attention failed for shapes q=%s k=%s "
                    "(causal=%s): %s" % (q.shape, k.shape, causal, e)) from e
            import warnings

            warnings.warn(
                "Pallas flash-attention failed (%s: %s); falling back to the "
                "composed O(S^2) attention. Set FLAGS_strict_fused_attention=1 "
                "to make this an error." % (type(e).__name__, e),
                RuntimeWarning, stacklevel=2)
    _count("composed")
    return _composed(q, k, v, bias, segment_ids_q, segment_ids_kv, causal,
                     sm_scale, dropout_rate, dropout_rng)


def _composed(q, k, v, bias, segment_ids_q, segment_ids_kv, causal, sm_scale,
              dropout_rate, dropout_rng):
    """The composed path: every [B, H, S_q, S_k] tensor is the compiler's
    to fuse, write and re-read."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if bias is not None:
        scores = scores + bias
    if segment_ids_q is not None:
        mask = segment_ids_q[:, None, :, None] == segment_ids_kv[:, None, None, :]
        scores = jnp.where(mask, scores, neg_inf(scores.dtype))
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cm, scores, neg_inf(scores.dtype))
    # dtype-preserving softmax by default: every f32-accumulation variant
    # measured COSTS HBM on the Transformer bench (diag_overhead.py, r4) —
    # forcing bf16-probs residuals via custom_vjp +1.9 GB/step, f32-cast
    # softmax +5 GB (XLA saves the f32 output for the backward) — while
    # XLA's own residual choice beats both. FLAGS_attention_softmax_f32
    # buys the f32 softmax at that cost for accuracy-sensitive runs;
    # per-op agreement vs f32 is ~1e-2 either way (ADVICE r3).
    from ..flags import get_flag

    if get_flag("attention_softmax_f32"):
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1) \
            .astype(scores.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        # where-on-pred keeps the saved residual at 1 byte/element (see
        # tensor_ops.dropout_op)
        probs = jnp.where(
            keep, probs * jnp.asarray(1.0 / (1.0 - dropout_rate), probs.dtype),
            jnp.zeros((), probs.dtype))
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def decode_attention(q, ctx_k, ctx_v, ctx_len, sm_scale=1.0):
    """Single-position attention for autoregressive decode over a gathered
    KV context (paddle_tpu.serving).

    ``q`` [B,H,D] is the current position's query per batch slot; ``ctx_k``/
    ``ctx_v`` [B,L,H,D] is the slot's cache context — a paged gather
    (serving.kv_cache.PagedKVCache.context) or a contiguous cache slice feed
    the SAME math here, which is what makes the two layouts bit-comparable.
    ``ctx_len`` [B] counts the valid leading positions (prompt + generated,
    INCLUDING the current token, whose k/v the caller wrote before calling).
    Invalid positions are masked with :func:`neg_inf` (exp underflows to
    exactly 0.0), so cache garbage beyond ``ctx_len`` (stale rows from a
    retired request, unreserved pages) contributes exactly nothing —
    independent of layout. Returns [B,H,D].

    This is the XLA fallback path of the serving stack's ragged paged
    attention; pallas_kernels/paged_attention.py fuses the page gather into
    the attention inner loop behind the same signature contract (armed via
    ``FLAGS_paged_attention_kernel``, see :func:`paged_kernel_mode` and
    ``serving.kv_cache.PagedKVCache.decode_attention``).
    """
    if q.shape[1] != ctx_k.shape[2]:
        # grouped queries: q [B, H*G, D], query head n on KV head n // G
        b, hq, d = q.shape
        h = ctx_k.shape[2]
        scores = jnp.einsum("bhgd,blhd->bhgl", q.reshape(b, h, hq // h, d),
                            ctx_k) * sm_scale
        mask = (jnp.arange(ctx_k.shape[1])[None, None, None, :]
                < ctx_len[:, None, None, None])
        scores = jnp.where(mask, scores, neg_inf(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgl,blhd->bhgd", probs, ctx_v).reshape(b, hq, d)
    scores = jnp.einsum("bhd,blhd->bhl", q, ctx_k) * sm_scale
    mask = jnp.arange(ctx_k.shape[1])[None, None, :] < ctx_len[:, None, None]
    scores = jnp.where(mask, scores, neg_inf(scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", probs, ctx_v)


@register_op("scaled_dot_product_attention")
def sdpa_op(ctx: OpContext):
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    if getattr(ctx.trace, "shapes_only", False):
        # shape inference while the program is built (at a placeholder
        # batch): tracing a kernel's body there is seconds of every start
        ctx.set_output("Out", jax.ShapeDtypeStruct(
            q.shape[:3] + v.shape[3:], q.dtype))
        return
    bias = ctx.input("Bias")
    seg_q = ctx.input("SegmentIdsQ")
    seg_kv = ctx.input("SegmentIdsKV")
    causal = ctx.attr("causal", False)
    sm_scale = ctx.attr("sm_scale", 1.0)
    p = 0.0 if ctx.is_test else ctx.attr("dropout_rate", 0.0)
    rng = ctx.rng() if p > 0.0 else None
    mesh = getattr(ctx.trace, "mesh", None)
    if mesh is None:
        from ..parallel.mesh import get_mesh

        mesh = get_mesh()
    ctx.set_output("Out", sdpa(q, k, v, bias, seg_q, seg_kv, causal, sm_scale,
                               p, rng, mesh=mesh))


def gqa_causal_attention(q, k, v, sm_scale=1.0):
    """Causal attention of ONE sequence with grouped queries: ``q`` [S, Hq,
    D], ``k``/``v`` [S, Hkv, D], query head n reading KV head ``n // (Hq //
    Hkv)``. Where the flash kernel's gate admits the shape (the chip, S >=
    ``FLAGS_flash_attention_min_seq``) K and V are repeated over their
    query heads and handed to :func:`sdpa`, so no S x S tensor exists at
    long S; below it the scores are composed here, grouped, with the
    softmax in float32. Returns [S, Hq, D]."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    with jax.named_scope("attn/global"):
        qh = q.transpose(1, 0, 2)[None]
        if _flash_ok(qh, qh, True):
            kr = jnp.repeat(k, g, axis=1).transpose(1, 0, 2)[None]
            vr = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)[None]
            o = sdpa(qh, kr, vr, causal=True, sm_scale=sm_scale)
            return o[0].transpose(1, 0, 2)
        sc = jnp.einsum("qhgd,khd->hgqk", q.reshape(s, hkv, g, d), k,
                        preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                       neg_inf(jnp.float32))
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype).reshape(s, hq, v.shape[-1])


def windowed_causal_attention(q, k, v, window: int, sm_scale=1.0,
                              block_q: int = 512):
    """Causal attention of ONE sequence in which position i sees ``j <= i``
    with ``i - j < window``, with grouped queries as in
    :func:`gqa_causal_attention`. At ``S <= window`` the window hides
    nothing and this IS the causal attention. Beyond it the queries go in
    blocks of ``block_q`` rows, each against the ``window + block_q`` keys
    that can reach it: O(S x window) work, and the largest score tensor is
    [Hkv, G * block_q, window + block_q], never S x S. Softmax in
    float32. ``v`` may be narrower than ``q`` and ``k`` (latent attention's
    expanded heads: 128 against 192). On a TPU, where
    ``window_prefill_gate`` takes the shapes, the attention beyond the
    window is ONE ``window_prefill_attention`` kernel call
    (pallas_kernels/window_prefill.py: no score reaches HBM, a query block
    reads the key tiles of its band alone, tiles that follow the window);
    ``attn/window_prefill_calls.kernel`` and ``.blocked`` count which.
    Returns [S, Hq, Dv]."""
    from .pallas_kernels import window_prefill

    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if s <= window:
        return gqa_causal_attention(q, k, v, sm_scale)
    kernel = _on_tpu() and window_prefill.window_prefill_gate(
        hq, hkv, d, v.shape[-1], s, window, q.dtype.itemsize) is None
    _count("kernel" if kernel else "blocked", "attn/window_prefill_calls",
           "windowed_causal_attention")
    with jax.named_scope("attn/window"):
        if kernel:
            return window_prefill.window_prefill_attention(
                q, k, v, int(window), sm_scale=float(sm_scale))
        bq = block_q
        while s % bq:
            bq //= 2
        span = window + bq
        # keys of block b: absolute positions [b*bq - window, b*bq + bq)
        kp = jnp.pad(k, ((window, 0), (0, 0), (0, 0))).transpose(1, 0, 2)
        vp = jnp.pad(v, ((window, 0), (0, 0), (0, 0))).transpose(1, 0, 2)
        qb = q.reshape(s // bq, bq, hkv, g, d)
        rows = jnp.arange(bq)[:, None]
        cols = jnp.arange(span)[None, :] - window   # relative to b*bq

        def block(args):
            b, qi = args                     # qi [bq, Hkv, G, D]
            kb = jax.lax.dynamic_slice_in_dim(kp, b * bq, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, b * bq, span, axis=1)
            sc = jnp.einsum("qhgd,hkd->hgqk", qi, kb,
                            preferred_element_type=jnp.float32) * sm_scale
            ok = (cols <= rows) & (rows - cols < window) \
                & (cols + b * bq >= 0)
            sc = jnp.where(ok[None, None], sc, neg_inf(jnp.float32))
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hgqk,hkd->qhgd", p.astype(vb.dtype), vb,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

        out = jax.lax.map(block, (jnp.arange(s // bq), qb))
        return out.reshape(s, hq, v.shape[-1])


def mla_causal_attention(q, k_nope, k_rope, v, sm_scale=1.0):
    """Causal attention of ONE sequence with latent-attention heads,
    EXPANDED: ``q`` [S, H, Dn + Dr], ``k_nope`` [S, H, Dn], ``k_rope`` [S,
    Dr] (the one rotary key every head shares), ``v`` [S, H, Dv]. The key
    of head n is ``[k_nope_n | k_rope]``; the queries and keys are wider
    than the values (192 against 128 at DeepSeek-V3's sizes). Where the
    flash kernel's gate admits the length (the chip, S >=
    ``FLAGS_flash_attention_min_seq``) q, k and v are zero-padded to one
    head width of whole lane tiles, which that kernel needs (zero lanes
    add nothing to a score, and the padding of the result is cut), so no S
    x S tensor exists at long S; below it the scores are composed here,
    with the softmax in float32. Returns [S, H, Dv]."""
    s, h, _ = q.shape
    dv = v.shape[-1]
    with jax.named_scope("attn/mla"):
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None, :],
                                      (s, h, k_rope.shape[-1]))], axis=-1)
        qh = q.transpose(1, 0, 2)[None]
        if _flash_ok(qh, qh, True):
            wide = -(-max(q.shape[-1], dv) // 128) * 128

            def padded(x):
                x = jnp.pad(x, ((0, 0), (0, 0), (0, wide - x.shape[-1])))
                return x.transpose(1, 0, 2)[None]

            o = sdpa(padded(q), padded(k), padded(v), causal=True,
                     sm_scale=sm_scale)
            return o[0].transpose(1, 0, 2)[..., :dv]
        sc = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc,
                       neg_inf(jnp.float32))
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)


def mla_decode_attention(q, ctx_rows, ctx_len, rank: int, sm_scale=1.0,
                         row_valid=None):
    """Single-position latent attention, ABSORBED, over gathered rows:
    ``q`` [B, H, W] (each head's absorbed query over the row's lanes),
    ``ctx_rows`` [B, L, W] (every head reads the same rows), ``ctx_len``
    [B]. Scores over all W lanes, the weighted sum over the first ``rank``
    (the latent): [B, H, rank]. The XLA path the latent paged kernel
    (ops/pallas_kernels/mla_attention.py) replaces, with the same masking
    constant and a float32 softmax. ``row_valid`` [B, L] bool (the sparse
    read): of the rows below the length, those that count."""
    sc = jnp.einsum("bhw,blw->bhl", q, ctx_rows,
                    preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(ctx_rows.shape[1])[None, None, :] \
        < ctx_len[:, None, None]
    if row_valid is not None:
        mask = mask & row_valid[:, None, :]
    sc = jnp.where(mask, sc, neg_inf(jnp.float32))
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhl,blr->bhr", p.astype(ctx_rows.dtype),
                   ctx_rows[..., :rank], preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def mla_rows_attention(q, rows, held, rank: int, sm_scale=1.0):
    """:func:`mla_decode_attention` over a TABLE of gathered rows: ``q``
    [B, H, W] absorbed, ``rows`` [B, K, W] the rows a slot chose, ``held``
    [B, K] bool which of them count (a slot that holds none comes back
    finite and is nobody's to read). [B, H, rank]. The softmax by hand,
    its maximum behind a barrier: left to the compiler inside a decode
    step, the maximum and its broadcast over the K rows became ONE float
    ``reduce-window`` of K taps a score (``f32[32,128,2048]`` twice a
    layer, 5 ms of a step on a v5e; PERF.md section 6, PR 62), as
    :func:`_rows_masked_attention`'s did in a prefill."""
    sc = jnp.einsum("bhw,bkw->bhk", q, rows,
                    preferred_element_type=jnp.float32) * sm_scale
    sc = jnp.where(held[:, None, :], sc, neg_inf(jnp.float32))
    top = jax.lax.optimization_barrier(jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.exp(sc - top)
    total = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhk,bkr->bhr", p.astype(rows.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return (o / total).astype(q.dtype)


# -- learned sparse attention (DeepSeek's lightning indexer over pooled keys) --

def dsa_index_scores(q_idx, w_idx, keys, closed, score_dtype=jnp.float32):
    """The index scores of one position a slot: ``q_idx`` [B, Hi, L] the
    index queries, ``w_idx`` [B, Hi] float32 their weights, ``keys`` one
    pooled index key of L lanes a block: [N, L] (the same for every
    query), [B, N, L], or PACKED [B, P, G * L] (a cache page's G keys side
    by side in one row, block ``p G + g`` in lanes ``g L..``: scored a
    lane slice at a time, so that the gathered rows are never re-laid),
    ``closed`` [B] the blocks that may be scored (those before it are
    closed). ``I(b) = sum_j w_j ReLU(q_j . K_b)`` [B, N] float32, the
    masking constant at and past ``closed``. A key a ROW is a block of
    one. ``score_dtype``: the precision the heads' products are rounded
    to and their weighted sum is ACCUMULATED in (float32 as every
    configuration states; a lower one is a control's)."""
    with jax.named_scope("attn/dsa_index"):
        lanes = q_idx.shape[-1]
        w = w_idx.astype(jnp.float32)

        def rounded(x):
            if jnp.dtype(score_dtype) == jnp.float32:
                return x
            info = jnp.finfo(score_dtype)
            return jax.lax.reduce_precision(x, info.nexp, info.nmant)

        def scored(k):
            sc = jnp.einsum("bhl,nl->bhn" if k.ndim == 2 else "bhl,bnl->bhn",
                            q_idx, k, preferred_element_type=jnp.float32)
            if jnp.dtype(score_dtype) == jnp.float32:
                return jnp.einsum("bh,bhn->bn", w, jax.nn.relu(sc))
            # a lower precision ACCUMULATES in it: the running sum over
            # the heads rounded after every head's term
            terms = rounded(w[:, :, None] * jax.nn.relu(rounded(sc)))
            return jax.lax.fori_loop(
                0, terms.shape[1],
                lambda h, acc: rounded(acc + terms[:, h]),
                jnp.zeros(terms.shape[:1] + terms.shape[2:], jnp.float32))

        packed = keys.shape[-1] // lanes
        if packed == 1:
            score = scored(keys)
        else:
            score = jnp.stack(
                [scored(keys[..., g * lanes:(g + 1) * lanes])
                 for g in range(packed)], axis=-1).reshape(keys.shape[0], -1)
        live = jnp.arange(score.shape[-1])[None, :] < closed[:, None]
        return jnp.where(live, score, neg_inf(jnp.float32))


def dsa_select(scores, own_block, top_blocks: int):
    """The blocks a query reads: ``scores`` [B, N] (:func:`dsa_index_scores`:
    the masking constant where a block may not be chosen), ``own_block``
    [B] the block the query's position lies in, always read. The
    ``top_blocks - 1`` scored blocks of highest score join it (every one
    where there are fewer), a tie going to the lower block
    (``lax.top_k``'s order). Returns ``(chosen [B, N] bool, picked [B,
    top_blocks - 1] int32)``: ``picked`` the scored blocks chosen,
    ascending, -1 where there were fewer."""
    with jax.named_scope("attn/dsa_select"):
        b, n = scores.shape
        k = min(int(top_blocks) - 1, n)
        low = neg_inf(jnp.float32)
        vals, idx = jax.lax.top_k(scores, k)
        # no scatter of the k indices (the chip takes them one at a time):
        # a block is chosen where it scores above the k-th, and of those
        # that score the same as the k-th, the lower ones that still fit
        kth = vals[:, -1:]
        above = scores > kth
        tie = (scores == kth) & (scores > low)
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        chosen = above | (tie & (jnp.cumsum(tie, axis=-1) <= room))
        blocks = jnp.arange(n, dtype=jnp.int32)[None, :]
        chosen = chosen | (blocks == own_block[:, None])
        picked = jnp.sort(jnp.where(vals > low, idx.astype(jnp.int32), n),
                          axis=-1)
        return chosen, jnp.where(picked < n, picked, -1)


def dsa_select_rows(scores, topk: int):
    """The ROWS a query reads where the choice is of single rows and none
    is forced in: ``scores`` [B, N] float32 (the masking constant where a
    row may not be chosen). The ``topk`` rows of highest score (every one
    that may be chosen where there are fewer), a tie going to the lower
    row: ``lax.top_k``'s set, exactly, WITHOUT a sort, which is what
    ``top_k`` at k = 2,048 of 10,240 is on the chip. The k-th largest score
    is found by bisection on the integer image of the float32 scores (32
    compare-and-count passes over ``[B, N]``), a row is chosen where it
    scores above it, and of those that score the same the lowest that still
    fit (a second bisection, on the row index: no cumulative sum, which the
    chip's compiler makes a reduce-window of N taps). Returns ``chosen``
    [B, N] bool."""
    with jax.named_scope("attn/dsa_select"):
        b, n = scores.shape
        k = min(int(topk), n)
        u32 = jnp.uint32
        bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
        # a float32's order as an unsigned integer's: negatives reversed,
        # then the sign bit turned
        image = jax.lax.bitcast_convert_type(
            jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits), u32) \
            ^ u32(0x80000000)

        def value_bit(i, kth):
            cand = kth | jnp.left_shift(u32(1), (31 - i).astype(u32))
            enough = jnp.sum(image >= cand[:, None], axis=-1) >= k
            return jnp.where(enough, cand, kth)

        kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((b,), u32))
        above = image > kth[:, None]
        tie = (image == kth[:, None]) & (scores > neg_inf(jnp.float32))
        room = k - jnp.sum(above, axis=-1)
        rows = jnp.arange(n, dtype=jnp.int32)[None, :]
        width = max(n - 1, 1).bit_length()

        def row_bit(i, first):
            # the largest row index with fewer than ``room`` ties before it
            cand = first | jnp.left_shift(jnp.int32(1), width - 1 - i)
            few = jnp.sum(tie & (rows < cand[:, None]), axis=-1) < room
            return jnp.where(few, cand, first)

        last = jax.lax.fori_loop(0, width, row_bit,
                                 jnp.zeros((b,), jnp.int32))
        return above | (tie & (rows <= last[:, None]))


def dsa_chosen_rows(chosen, topk: int):
    """The TABLE of a choice: ``chosen`` [B, N] bool (:func:`dsa_select_rows`:
    at most ``topk`` true a row) as ``(rows [B, topk] int32, held [B, topk]
    bool)``: the chosen rows ascending, 0 where ``held`` is false (fewer
    than ``topk`` were chosen). Exact and WITHOUT a sort or a scatter, which
    is what a compaction is on the chip by ``top_k``'s indices or by
    ``nonzero``: the N rows are 128-lane chunks; the j-th chosen row lies
    in the chunk at which the chunks' running count first passes j (a
    compare against 128 counts), and in it at the lane whose running count
    is what is left of j (the chunk's lanes fetched by a one-hot product
    and counted by a triangular one: 0/1 values, exact in bfloat16 with
    float32 sums)."""
    with jax.named_scope("attn/dsa_select"):
        b, n = chosen.shape
        k = min(int(topk), n)
        lanes = 128
        m = jnp.pad(chosen, ((0, 0), (0, -n % lanes))).reshape(b, -1, lanes)
        ends = jnp.cumsum(jnp.sum(m, axis=-1, dtype=jnp.int32), axis=-1)
        slot = jnp.arange(k, dtype=jnp.int32)
        before = ends[:, None, :] <= slot[None, :, None]       # [B, k, C]
        chunk = jnp.sum(before, axis=-1, dtype=jnp.int32)
        start = jnp.max(jnp.where(before, ends[:, None, :], 0), axis=-1)
        held = slot[None, :] < ends[:, -1:]
        bf, f32 = jnp.bfloat16, jnp.float32
        onehot = jnp.arange(m.shape[1])[None, None, :] == chunk[:, :, None]
        in_chunk = jnp.einsum("bkc,bcl->bkl", onehot.astype(bf), m.astype(bf),
                              preferred_element_type=f32)
        lane = jnp.arange(lanes)
        upto = (lane[:, None] <= lane[None, :]).astype(bf)
        count = jnp.einsum("bkl,lm->bkm", in_chunk.astype(bf), upto,
                           preferred_element_type=f32)
        want = (slot[None, :] - start + 1).astype(f32)[:, :, None]
        hit = (in_chunk > 0) & (count == want)
        rows = chunk * lanes + jnp.sum(jnp.where(hit, lane, 0), axis=-1,
                                       dtype=jnp.int32)
        return jnp.where(held, rows, 0), held


def _rows_masked_attention(q, k, v, mask_of, per_row, sm_scale, bq: int,
                           kernel: bool, length, whole: int):
    """Attention of ONE sequence in which row t reads the rows ``mask_of(i,
    *block of per_row)`` [bq, S] bool says (the caller's causal triangle in
    it), ``bq`` query rows at a time: by ONE ``dsa_prefill_attention``
    kernel over the rows' masks as ``int8 [S, S]`` where ``kernel``, else
    the BLOCKED form (the float32 scores of ``bq`` rows against the whole
    sequence held at a time). A query block does only what a token can
    read, under ONE ``lax.switch`` in either form: a block that starts at
    or past ``length`` (an int32 scalar or None, the rows that are the
    sequence's) asks ``mask_of`` nothing, reads nothing and comes back as
    ZEROS; a block whose last row lies under ``whole`` (the rows that read
    their whole prefix, whatever ``mask_of`` would score) is the causal
    triangle itself, ``mask_of`` not asked; every other block is
    ``mask_of``'s. Rows past the length in the last live block are finite
    and nobody's to read."""
    from .pallas_kernels import dsa_prefill

    s = q.shape[0]
    cols = jnp.arange(s)[None, :]
    length = s if length is None else length

    def attend(qb, mask):
        with jax.named_scope("attn/dsa_sparse"):
            sc = jnp.einsum("qhd,khd->hqk", qb, k,
                            preferred_element_type=jnp.float32) * sm_scale
            sc = jnp.where(mask[None], sc, neg_inf(jnp.float32))
            # the softmax by hand, its maximum behind a barrier: left to
            # the compiler, the maximum and its broadcast over the S keys
            # become ONE reduce-window of 2 S - 1 taps a score (23 ms a
            # block of 128 rows at S = 8,192 on a v5e, 1.5 s a layer)
            top = jax.lax.optimization_barrier(
                jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - top)
            total = jnp.sum(p, axis=-1)                         # [H, bq]
            o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return (o / total.T[:, :, None]).astype(q.dtype)

    def block(i, rest, dead, under):
        """Block ``i``'s part: ``dead`` past the length, else ``under`` its
        rows' mask."""
        rows = i * bq + jnp.arange(bq)
        kind = jnp.where(i * bq >= length, 0,
                         jnp.where((i + 1) * bq <= whole, 1, 2))
        return jax.lax.switch(
            kind, (lambda *_: dead,
                   lambda *_: under(cols <= rows[:, None]),
                   lambda *rest: under(mask_of(i, *rest))), *rest)

    def split(x):
        return x.reshape((s // bq, bq) + x.shape[1:])

    blocks = jnp.arange(s // bq)
    if kernel:
        mask = jax.lax.map(
            lambda a: block(a[0], a[1:], jnp.zeros((bq, s), jnp.int8),
                            lambda m: m.astype(jnp.int8)),
            (blocks,) + tuple(split(x) for x in per_row))
        with jax.named_scope("attn/dsa_sparse"):
            return dsa_prefill.dsa_prefill_attention(
                q, k, v, mask.reshape(s, s), length,
                sm_scale=float(sm_scale))
    out = jax.lax.map(
        lambda a: block(a[0], a[2:], jnp.zeros((bq,) + v.shape[1:], q.dtype),
                        functools.partial(attend, a[1])),
        (blocks, split(q)) + tuple(split(x) for x in per_row))
    return out.reshape((s,) + out.shape[2:])


def dsa_causal_attention(q, k, v, q_idx, w_idx, k_pool, kpool: int,
                         top_blocks: int, sm_scale=1.0, block_q: int = 256,
                         length=None):
    """Causal attention of ONE sequence in which every query row reads the
    rows its indexer chose: ``q``/``k`` [S, H, D], ``v`` [S, H, Dv]
    EXPANDED; ``q_idx`` [S, Hi, L], ``w_idx`` [S, Hi] the rows' index
    queries and weights, ``k_pool`` [S / kpool, L] one pooled index key a
    block of ``kpool`` rows. Row t reads the rows <= t of its own block and
    the ``top_blocks - 1`` blocks of highest index score among those CLOSED
    before its own (all of them where there are fewer; :func:`dsa_select`'s
    rule). A selection depends on its query, so the mask is a ROW's own,
    made ``block_q`` query rows at a time. On a TPU, where
    ``dsa_prefill_gate`` takes the shapes, the rows' masks are kept as
    ``int8 [S, S]`` and the attention is ONE ``dsa_prefill_attention``
    kernel call (pallas_kernels/dsa_prefill.py: no score reaches HBM, no
    key tile past a query block is read). Elsewhere the BLOCKED form: the
    float32 scores of ``block_q`` query rows against the whole sequence are
    held at a time, never the [S, S] of all (on a v5e at S = 8,192 and 64
    heads of 256: 131, 106 and 93 ms a layer at 128, 256 and 512 rows a
    block; PERF.md, PR 47). ``dsa/prefill_calls.kernel`` and ``.blocked``
    count which. ``length`` (an int32 scalar; None: S): the rows that are
    the sequence's, the rest its bucket's padding. A query block past it
    is neither scored, chosen nor attended and comes back as ZEROS
    (:func:`_rows_masked_attention`; the kernel's query blocks are its own,
    larger: zeros from the first of THOSE that starts past the length), and
    a block whose rows all lie under ``top_blocks x kpool`` keeps every
    closed block, so it is the causal triangle with no score computed.
    Returns [S, H, Dv]."""
    from .pallas_kernels import dsa_prefill

    s, n_head, d = q.shape
    bq = _divisor_block(block_q, s, s)
    cols = jnp.arange(s)[None, :]
    kernel = _on_tpu() and dsa_prefill.dsa_prefill_gate(
        n_head, d, v.shape[-1], s, kpool, q.dtype.itemsize) is None
    _count("kernel" if kernel else "blocked", "dsa/prefill_calls",
           "dsa_causal_attention")

    def mask_of(i, qib, wib):
        rows = i * bq + jnp.arange(bq)
        own = rows // kpool
        chosen, _ = dsa_select(
            dsa_index_scores(qib, wib, k_pool, own), own, top_blocks)
        with jax.named_scope("attn/dsa_sparse"):
            return jnp.repeat(chosen, kpool, axis=1) & (cols <= rows[:, None])

    return _rows_masked_attention(q, k, v, mask_of, (q_idx, w_idx), sm_scale,
                                  bq, kernel, length, top_blocks * kpool)


def dsa_rows_causal_attention(q, k, v, q_idx, w_idx, k_idx, topk: int,
                              sm_scale=1.0, block_q: int = 256,
                              select=None, score_dtype=jnp.float32,
                              length=None):
    """:func:`dsa_causal_attention` where the choice is of single ROWS and
    none is forced in (DeepSeek-V3.2's own form): ``k_idx`` [S, L] one
    index key a row; row t reads the ``topk`` rows s <= t of highest index
    score (its whole prefix where t + 1 <= ``topk``: the mask is then the
    causal triangle), by :func:`dsa_select_rows` (``select``: another rule
    with its signature, a control's). On a TPU the same ONE
    ``dsa_prefill_attention`` kernel a layer, for which ``q`` and ``k``
    [S, H, D] are zero-padded to whole lane tiles where a head's D is not
    (128 + 64 rotary lanes -> 256: a third more of q and k in HBM for the
    length of the call, and a quarter of the first product's lanes
    multiplied for nothing: zero lanes add nothing to a score); elsewhere
    the blocked form at D as it is. The index scores, too, are ONE kernel
    a layer there (``dsa_index.dsa_index_scores_prefill``: ``[S, S]``
    float32 and no ``[block_q, Hi, S]`` products through HBM;
    ``dsa/prefill_index_calls.kernel|blocked`` count which). ``length`` (an
    int32 scalar; None: S): the rows that are the sequence's. A query
    block past it is neither scored, chosen nor attended and comes back as
    ZEROS, and a block whose rows all lie under ``topk`` is the causal
    triangle with no score computed and no selection run, in both forms
    (:func:`_rows_masked_attention`); the scores' kernel is told both, and
    computes only the query blocks between. On every row under the length
    the result is what it is with no length given. Returns [S, H, Dv]."""
    from .pallas_kernels import dsa_index, dsa_prefill

    s, n_head, d = q.shape
    bq = _divisor_block(block_q, s, s)
    cols = jnp.arange(s)[None, :]
    wide = -(-d // 128) * 128
    kernel = _on_tpu() and dsa_prefill.dsa_prefill_gate(
        n_head, wide, v.shape[-1], s, 1, q.dtype.itemsize) is None
    _count("kernel" if kernel else "blocked", "dsa/prefill_calls",
           "dsa_rows_causal_attention")
    select = select or dsa_select_rows
    # the scores of every row against every row before it by ONE kernel
    # where the chip takes the shapes and the configuration's float32
    # scores are asked for; else a query block's at a time in XLA
    scored = _on_tpu() and jnp.dtype(score_dtype) == jnp.float32 \
        and dsa_index.dsa_index_prefill_gate(
            q_idx.shape[1], q_idx.shape[2], s) is None
    _count("kernel" if scored else "blocked", "dsa/prefill_index_calls",
           "dsa_rows_causal_attention")

    def chosen_of(i, scores):
        rows = i * bq + jnp.arange(bq)
        causal = cols <= rows[:, None]
        chosen = select(jnp.where(causal, scores, neg_inf(jnp.float32)),
                        topk)
        with jax.named_scope("attn/dsa_sparse"):
            return chosen & causal

    def mask_of(i, qib, wib):
        rows = i * bq + jnp.arange(bq)
        return chosen_of(i, dsa_index_scores(qib, wib, k_idx, rows + 1,
                                             score_dtype))

    if kernel and wide != d:
        q, k = (jnp.pad(x, ((0, 0), (0, 0), (0, wide - d))) for x in (q, k))
    if scored:
        # the scores' kernel computes the query blocks the switch reads:
        # from the first that holds a row at or past ``topk`` to the last
        # that holds a row of the sequence
        with jax.named_scope("attn/dsa_index"):
            scores = dsa_index.dsa_index_scores_prefill(
                q_idx, w_idx, k_idx,
                None if length is None else -(-length // bq) * bq,
                first=topk // bq * bq)
        return _rows_masked_attention(q, k, v, chosen_of, (scores,), sm_scale,
                                      bq, kernel, length, topk)
    return _rows_masked_attention(q, k, v, mask_of, (q_idx, w_idx), sm_scale,
                                  bq, kernel, length, topk)


def differential_combine(o, lam, n_kv: int):
    """Grouped differential attention's subtraction, after attention
    (Differential Transformer V2's form): ``o`` [..., H, D] holds the
    outputs of ``H = n_kv * G`` heads, the ``G`` heads of a KV head side
    by side, of which the first ``G - 1`` are SIGNAL heads and the last is
    the group's NOISE head; ``lam`` [..., n_kv * (G - 1)] a weight a signal
    head. Returns ``o_s - lam_s * o_noise(group of s)`` [..., n_kv * (G -
    1), D] in ``o``'s type, the subtraction in float32. ``D`` is whatever
    the heads' outputs are: the values after the up-projection, or the
    latent before it (a group's heads share one up-projection, which is
    linear, so the two orders agree)."""
    lead, (h, d) = o.shape[:-2], o.shape[-2:]
    g = h // n_kv
    of = o.astype(jnp.float32).reshape(lead + (n_kv, g, d))
    lf = lam.astype(jnp.float32).reshape(lead + (n_kv, g - 1, 1))
    y = of[..., :g - 1, :] - lf * of[..., g - 1:, :]
    return y.reshape(lead + (n_kv * (g - 1), d)).astype(o.dtype)

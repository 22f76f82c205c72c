"""Tensor manipulation, fill, and random ops.

Fluid equivalents live across ``operators/reshape_op.cc``, ``concat_op.cc``,
``fill_constant_op.cc``, ``uniform_random_op.cc`` etc. Random ops use
counter-based JAX PRNG keys (deterministic, replay-safe under jit) instead of
the reference's per-device curand generators.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtypes import to_jnp_dtype
from ..core.registry import OpContext, register_op
from . import keep_hash


def _resolve_shape(shape, x=None):
    """Resolve a Fluid shape attr (may contain -1 and 0) against input x."""
    shape = list(shape)
    if x is not None:
        for i, s in enumerate(shape):
            if s == 0 and i < x.ndim:  # 0 means "copy from input" in fluid reshape
                shape[i] = x.shape[i]
    return shape


@register_op("reshape", "reshape2")
def reshape_op(ctx: OpContext):
    x = ctx.input("X")
    shape_tensor = ctx.input("Shape") if ctx.has_input("Shape") else None
    if shape_tensor is not None:
        shape = [int(s) for s in np.asarray(shape_tensor)]
    else:
        shape = _resolve_shape(ctx.attr("shape"), x)
    out = x.reshape(shape)
    ctx.set_output("Out", out)
    if ctx.has_output("XShape"):
        ctx.set_output("XShape", jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op("squeeze", "squeeze2")
def squeeze_op(ctx: OpContext):
    x = ctx.input("X")
    axes = ctx.attr("axes", [])
    if axes:
        axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
        out = jnp.squeeze(x, axis=axes) if axes else x
    else:
        out = jnp.squeeze(x)
    ctx.set_output("Out", out)
    if ctx.has_output("XShape"):
        ctx.set_output("XShape", jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op("unsqueeze", "unsqueeze2")
def unsqueeze_op(ctx: OpContext):
    x = ctx.input("X")
    out = x
    for a in sorted(ctx.attr("axes")):
        out = jnp.expand_dims(out, a)
    ctx.set_output("Out", out)
    if ctx.has_output("XShape"):
        ctx.set_output("XShape", jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op("flatten", "flatten2")
def flatten_op(ctx: OpContext):
    from .math_ops import _dim_prod

    x = ctx.input("X")
    axis = ctx.attr("axis", 1)
    lead = _dim_prod(x.shape[:axis]) if axis > 0 else 1
    ctx.set_output("Out", x.reshape(lead, -1))
    if ctx.has_output("XShape"):
        ctx.set_output("XShape", jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op("transpose", "transpose2")
def transpose_op(ctx: OpContext):
    x = ctx.input("X")
    ctx.set_output("Out", jnp.transpose(x, ctx.attr("axis")))
    if ctx.has_output("XShape"):
        ctx.set_output("XShape", jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op("concat")
def concat_op(ctx: OpContext):
    xs = ctx.inputs("X")
    ctx.set_output("Out", jnp.concatenate(xs, axis=ctx.attr("axis", 0)))


@register_op("split")
def split_op(ctx: OpContext):
    x = ctx.input("X")
    axis = ctx.attr("axis", 0)
    num = ctx.attr("num", 0)
    sections = ctx.attr("sections", [])
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    ctx.set_outputs("Out", outs)


@register_op("stack")
def stack_op(ctx: OpContext):
    ctx.set_output("Y", jnp.stack(ctx.inputs("X"), axis=ctx.attr("axis", 0)))


@register_op("unstack")
def unstack_op(ctx: OpContext):
    x = ctx.input("X")
    axis = ctx.attr("axis", 0)
    outs = [jnp.squeeze(s, axis) for s in jnp.split(x, x.shape[axis], axis=axis)]
    ctx.set_outputs("Y", outs)


@register_op("slice")
def slice_op(ctx: OpContext):
    x = ctx.input("Input")
    axes = ctx.attr("axes")
    starts = ctx.attr("starts")
    ends = ctx.attr("ends")
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    ctx.set_output("Out", x[tuple(idx)])


@register_op("strided_slice")
def strided_slice_op(ctx: OpContext):
    x = ctx.input("Input")
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(ctx.attr("axes"), ctx.attr("starts"), ctx.attr("ends"), ctx.attr("strides")):
        idx[a] = slice(s, e, st)
    ctx.set_output("Out", x[tuple(idx)])


@register_op("gather")
def gather_op(ctx: OpContext):
    x, index = ctx.input("X"), ctx.input("Index")
    ctx.set_output("Out", jnp.take(x, index.reshape(-1), axis=0))


@register_op("gather_nd")
def gather_nd_op(ctx: OpContext):
    x, index = ctx.input("X"), ctx.input("Index")
    ctx.set_output("Out", x[tuple(jnp.moveaxis(index, -1, 0))])


@register_op("scatter")
def scatter_op(ctx: OpContext):
    x, ids, updates = ctx.input("X"), ctx.input("Ids"), ctx.input("Updates")
    ids = ids.reshape(-1)
    if ctx.attr("overwrite", True):
        out = x.at[ids].set(updates)
    else:
        out = x.at[ids].add(updates)
    ctx.set_output("Out", out)


@register_op("expand")
def expand_op(ctx: OpContext):
    x = ctx.input("X")
    times = ctx.attr("expand_times")
    ctx.set_output("Out", jnp.tile(x, times))


@register_op("expand_as")
def expand_as_op(ctx: OpContext):
    x, target = ctx.input("X"), ctx.input("target_tensor")
    times = [t // s for s, t in zip(x.shape, target.shape)]
    ctx.set_output("Out", jnp.tile(x, times))


@register_op("tile")
def tile_op(ctx: OpContext):
    ctx.set_output("Out", jnp.tile(ctx.input("X"), ctx.attr("repeat_times")))


@register_op("pad")
def pad_op(ctx: OpContext):
    x = ctx.input("X")
    paddings = ctx.attr("paddings")
    pad_value = ctx.attr("pad_value", 0.0)
    pairs = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    ctx.set_output("Out", jnp.pad(x, pairs, constant_values=pad_value))


@register_op("pad2d")
def pad2d_op(ctx: OpContext):
    x = ctx.input("X")  # NCHW
    p = ctx.attr("paddings", [0, 0, 0, 0])  # top,bottom,left,right
    mode = ctx.attr("mode", "constant")
    value = ctx.attr("pad_value", 0.0)
    pairs = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        out = jnp.pad(x, pairs, constant_values=value)
    elif mode == "reflect":
        out = jnp.pad(x, pairs, mode="reflect")
    else:
        out = jnp.pad(x, pairs, mode="edge")
    ctx.set_output("Out", out)


@register_op("pad_constant_like")
def pad_constant_like_op(ctx: OpContext):
    x, y = ctx.input("X"), ctx.input("Y")
    pairs = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    ctx.set_output("Out", jnp.pad(y, pairs, constant_values=ctx.attr("pad_value", 0.0)))


@register_op("crop")
def crop_op(ctx: OpContext):
    x = ctx.input("X")
    offsets = ctx.attr("offsets")
    shape = ctx.attr("shape")
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    ctx.set_output("Out", x[idx])


@register_op("reverse")
def reverse_op(ctx: OpContext):
    x = ctx.input("X")
    out = x
    for a in ctx.attr("axis"):
        out = jnp.flip(out, a)
    ctx.set_output("Out", out)


@register_op("one_hot")
def one_hot_op(ctx: OpContext):
    ids = ctx.input("X")
    depth = ctx.attr("depth")
    out = jax.nn.one_hot(ids.reshape(ids.shape[:-1]) if ids.shape[-1] == 1 else ids, depth, dtype=jnp.float32)
    ctx.set_output("Out", out)


@register_op("shape")
def shape_op(ctx: OpContext):
    x = ctx.input("Input")
    ctx.set_output("Out", jnp.asarray(x.shape, dtype=jnp.int32))


@register_op("top_k")
def top_k_op(ctx: OpContext):
    x = ctx.input("X")
    k = ctx.attr("k", 1)
    values, indices = jax.lax.top_k(x, k)
    ctx.set_output("Out", values)
    ctx.set_output("Indices", indices)


@register_op("argsort")
def argsort_op(ctx: OpContext):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    indices = jnp.argsort(x, axis=axis)
    ctx.set_output("Indices", indices)
    ctx.set_output("Out", jnp.sort(x, axis=axis))


@register_op("arg_max")
def arg_max_op(ctx: OpContext):
    ctx.set_output("Out", jnp.argmax(ctx.input("X"), axis=ctx.attr("axis", -1)))


@register_op("arg_min")
def arg_min_op(ctx: OpContext):
    ctx.set_output("Out", jnp.argmin(ctx.input("X"), axis=ctx.attr("axis", -1)))


@register_op("where")
def where_op(ctx: OpContext):
    ctx.set_output("Out", jnp.where(ctx.input("Condition"), ctx.input("X"), ctx.input("Y")))


@register_op("multiplex")
def multiplex_op(ctx: OpContext):
    ids = ctx.input("Ids").reshape(-1)
    xs = jnp.stack(ctx.inputs("X"), axis=0)  # [k, n, d]
    ctx.set_output("Out", xs[ids, jnp.arange(xs.shape[1])])


@register_op("is_empty")
def is_empty_op(ctx: OpContext):
    x = ctx.input("X")
    ctx.set_output("Out", jnp.asarray(x.size == 0))


# -- fill / init ops ----------------------------------------------------------


def _init_out_sharding(ctx: OpContext):
    """NamedSharding for an init op whose output var carries a mesh-axis
    annotation (parallel.sharded_embedding / propagated Adam moments) while
    a mesh is active — trace mesh first, then the global ``mesh_guard``
    mesh (startup programs run eagerly, before any CompiledProgram mesh
    exists). Returns None when the init should stay single-device."""
    name = ctx.output_name("Out")
    if name is None:
        return None
    try:
        var = ctx.var(name)
    except Exception:
        return None
    spec = getattr(var, "sharding", None)
    if not spec or all(a is None for a in spec):
        return None
    mesh = getattr(ctx.trace, "mesh", None)
    from ..parallel.mesh import get_mesh, valid_sharding

    if mesh is None:
        mesh = get_mesh()
    if mesh is None or not valid_sharding(spec, mesh):
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec))


def _run_init(ctx: OpContext, thunk):
    """Run an init thunk, shard-by-shard when the output is annotated: the
    thunk jits with sharded ``out_shardings`` so XLA partitions the
    fill/RNG and each device materializes only its [V/n, D] shard —
    numerics identical to the unsharded init (same program, partitioned),
    peak memory V/n rows per device. This is what lets a V=1e8 CTR table
    (p+m+v ≈ 13 GB) instantiate on a mesh where the single-device
    fill_constant hits RESOURCE_EXHAUSTED at trace time (BENCH_r05)."""
    sh = _init_out_sharding(ctx)
    if sh is None:
        return thunk()
    import jax as _jax

    return _jax.jit(thunk, out_shardings=sh)()


@register_op("fill_constant")
def fill_constant_op(ctx: OpContext):
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    shape = ctx.attr("shape", [])
    value = ctx.attr("value", 0.0)
    ctx.set_output("Out", _run_init(
        ctx, lambda: jnp.full(shape, value, dtype=dtype)))


@register_op("fill_constant_batch_size_like")
def fill_constant_batch_size_like_op(ctx: OpContext):
    x = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = x.shape[in_idx]
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    ctx.set_output("Out", jnp.full(shape, ctx.attr("value", 0.0), dtype=dtype))


@register_op("fill_zeros_like")
def fill_zeros_like_op(ctx: OpContext):
    ctx.set_output("Out", jnp.zeros_like(ctx.input("X")))


@register_op("assign")
def assign_op(ctx: OpContext):
    ctx.set_output("Out", ctx.input("X"))


@register_op("assign_value")
def assign_value_op(ctx: OpContext):
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    shape = ctx.attr("shape")
    values = ctx.attr("values")
    ctx.set_output("Out", jnp.asarray(values, dtype=dtype).reshape(shape))


@register_op("range")
def range_op(ctx: OpContext):
    start, end, step = ctx.input("Start"), ctx.input("End"), ctx.input("Step")
    ctx.set_output("Out", jnp.arange(float(start), float(end), float(step)))


@register_op("linspace")
def linspace_op(ctx: OpContext):
    s, e, n = ctx.input("Start"), ctx.input("Stop"), ctx.input("Num")
    ctx.set_output("Out", jnp.linspace(float(s), float(e), int(n)))


# -- random ops ---------------------------------------------------------------


@register_op("uniform_random", "uniform_random_batch_size_like")
def uniform_random_op(ctx: OpContext):
    shape = list(ctx.attr("shape"))
    if ctx.has_input("Input"):
        x = ctx.input("Input")
        shape[ctx.attr("output_dim_idx", 0)] = x.shape[ctx.attr("input_dim_idx", 0)]
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    key = ctx.rng()
    ctx.set_output("Out", _run_init(ctx, lambda: jax.random.uniform(
        key, shape, dtype=jnp.float32, minval=lo, maxval=hi).astype(dtype)))


@register_op("gaussian_random", "gaussian_random_batch_size_like")
def gaussian_random_op(ctx: OpContext):
    shape = list(ctx.attr("shape"))
    if ctx.has_input("Input"):
        x = ctx.input("Input")
        shape[ctx.attr("output_dim_idx", 0)] = x.shape[ctx.attr("input_dim_idx", 0)]
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    key = ctx.rng()
    ctx.set_output("Out", _run_init(ctx, lambda: (
        mean + std * jax.random.normal(key, shape, dtype=jnp.float32)
    ).astype(dtype)))


@register_op("truncated_gaussian_random")
def truncated_gaussian_random_op(ctx: OpContext):
    shape = ctx.attr("shape")
    dtype = to_jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    key = ctx.rng()
    ctx.set_output("Out", _run_init(ctx, lambda: (
        mean + std * jax.random.truncated_normal(
            key, -2.0, 2.0, shape, dtype=jnp.float32)
    ).astype(dtype)))


@register_op("randint")
def randint_op(ctx: OpContext):
    shape = ctx.attr("shape")
    out = jax.random.randint(ctx.rng(), shape, ctx.attr("low", 0), ctx.attr("high"))
    ctx.set_output("Out", out)


def _count_draw() -> None:
    """One more dropout traced (trace-time: an executable's sites count
    once, when it is traced, as ``attention/sdpa_calls.*`` do)."""
    from ..monitor import metrics

    metrics.counter(
        "dropout/draws.hash",
        help="dropout ops traced with the coordinate-hash mask "
             "(ops/keep_hash.py; once a site of a traced program, not "
             "once a run)").inc()


@register_op("dropout")
def dropout_op(ctx: OpContext):
    """Reference: operators/dropout_op.cc. Two impl modes:
    downgrade_in_infer (default): train out = x*mask, infer out = x*(1-p);
    upscale_in_train: train out = x*mask/(1-p), infer out = x.
    """
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if ctx.is_test:
        if impl == "upscale_in_train":
            ctx.set_output("Out", x)
        else:
            ctx.set_output("Out", x * jnp.asarray(1.0 - p, x.dtype))
        return
    if p == 0.0:
        ctx.set_output("Out", x)
        ctx.set_output("Mask", jnp.ones_like(x))
        return
    # The mask is the attention kernels' coordinate hash (keep_hash.py) over
    # the element's position in the whole array and the op's key. XLA stores
    # no such mask: it draws it again in every fusion that consumes the
    # dropped tensor (forward, backward where, weight-gradient products), so
    # the draw has to be cheap. It stays PRED through the where, so a mask
    # that IS saved is 1 byte an element.
    keep = keep_hash.keep_mask(ctx.rng(), x.shape, p)
    _count_draw()
    if impl == "upscale_in_train":
        out = jnp.where(keep, x * jnp.asarray(1.0 / (1.0 - p), x.dtype),
                        jnp.zeros((), x.dtype))
    else:
        out = jnp.where(keep, x, jnp.zeros((), x.dtype))
    ctx.set_output("Out", out)
    ctx.set_output("Mask", keep.astype(x.dtype))


@register_op("shuffle_channel")
def shuffle_channel_op(ctx: OpContext):
    x = ctx.input("X")
    group = ctx.attr("group")
    n, c, h, w = x.shape
    ctx.set_output("Out", x.reshape(n, group, c // group, h, w).swapaxes(1, 2).reshape(n, c, h, w))


@register_op("label_smooth")
def label_smooth_op(ctx: OpContext):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 0.1)
    k = x.shape[-1]
    prior = ctx.input("PriorDist")
    if prior is None:
        prior = jnp.full((k,), 1.0 / k, x.dtype)
    ctx.set_output("Out", (1.0 - eps) * x + eps * prior)


@register_op("pixel_shuffle")
def pixel_shuffle_op(ctx: OpContext):
    x = ctx.input("X")  # NCHW
    r = ctx.attr("upscale_factor")
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w).transpose(0, 1, 4, 2, 5, 3).reshape(
        n, c // (r * r), h * r, w * r
    )
    ctx.set_output("Out", out)


@register_op("space_to_depth")
def space_to_depth_op(ctx: OpContext):
    x = ctx.input("X")
    b = ctx.attr("blocksize")
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // b, b, w // b, b).transpose(0, 3, 5, 1, 2, 4).reshape(n, c * b * b, h // b, w // b)
    ctx.set_output("Out", out)


@register_op("load")
def load_op(ctx: OpContext):
    """Reference: operators/load_op.cc. The file is read at trace time (the
    trace-once analog of the per-run load; re-tracing reloads) from the
    .npy/.npz format written by paddle_tpu.io.save_vars. For a combined
    .npz archive the entry matching the output variable's name is loaded."""
    import numpy as np

    path = ctx.attr("file_path")
    data = np.load(path, allow_pickle=False)
    if isinstance(data, np.lib.npyio.NpzFile):
        key = ctx.op.outputs["Out"][0]
        if key not in data:
            raise KeyError(
                "load: %r has no entry %r (archive keys: %s)"
                % (path, key, sorted(data.files)))
        arr = data[key]
    else:
        arr = data
    out = jnp.asarray(arr)
    if ctx.attr("load_as_fp16", False):
        out = out.astype(jnp.float16)
    ctx.set_output("Out", out)

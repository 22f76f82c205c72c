"""NN ops: softmax/losses, normalization, conv/pool, embedding.

Fluid equivalents: ``operators/softmax_op.cc`` (+cudnn),
``softmax_with_cross_entropy_op.cc``, ``batch_norm_op.cc``,
``layer_norm_op.cc``, ``conv_op.cc``/``conv_cudnn_op.cu.cc``,
``pool_op.cc``, ``lookup_table_op.cc``. Convs lower through
``lax.conv_general_dilated`` straight onto the MXU — the role cuDNN plays in
the reference. Data layout is NCHW at the API (Fluid parity); XLA is free to
relayout internally for the TPU's preferred tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import OpContext, register_op


@register_op("softmax")
def softmax_op(ctx: OpContext):
    x = ctx.input("X")
    ctx.set_output("Out", jax.nn.softmax(x, axis=ctx.attr("axis", -1)))


@register_op("log_softmax")
def log_softmax_op(ctx: OpContext):
    ctx.set_output("Out", jax.nn.log_softmax(ctx.input("X"), axis=ctx.attr("axis", -1)))


def _xent_from_probs(probs, label, soft_label, ignore_index=-100):
    if soft_label:
        return -jnp.sum(label * jnp.log(jnp.maximum(probs, 1e-20)), axis=-1, keepdims=True)
    lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
    lbl = lbl.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, jnp.maximum(lbl, 0)[..., None], axis=-1)
    loss = -jnp.log(jnp.maximum(picked, 1e-20))
    mask = (lbl != ignore_index)[..., None]
    return jnp.where(mask, loss, jnp.zeros_like(loss))


@register_op("cross_entropy", "cross_entropy2")
def cross_entropy_op(ctx: OpContext):
    probs = ctx.input("X")
    label = ctx.input("Label")
    ctx.set_output(
        "Y",
        _xent_from_probs(
            probs, label, ctx.attr("soft_label", False), ctx.attr("ignore_index", -100)
        ),
    )


def fused_xent_gate(shape, dtype, smooth: float = 0.0,
                    soft_label: bool = False, ignore_index: int = -100):
    """None when ``softmax_with_cross_entropy`` takes the Pallas kernel
    (pallas_kernels/softmax_xent.py) for these logits, else the rule that
    keeps it on the XLA path — the one decision the op and chip_smoke.py's
    train phase read. On TPU: hard labels, no ignore_index, 2D+ float
    logits with a wide vocab (small vocabs gain nothing over the XLA
    fusion), and no label smoothing over a ragged vocab: measured on v5e
    (16384×30000 bf16 fwd+bwd) the pad copy makes pallas 92.8ms vs XLA
    82.7ms — XLA fuses the single-pass smoothing formula just as well."""
    if jax.default_backend() in ("cpu", "gpu"):
        return "the %s backend" % jax.default_backend()
    if soft_label or ignore_index != -100:
        return "soft labels or an ignore_index"
    v = int(shape[-1])
    if smooth and v % 128:
        return "label smoothing over a vocab (%d) not a multiple of 128" % v
    from .pallas_kernels import softmax_xent_supported

    n = 1
    for d in shape[:-1]:
        n *= int(d)
    if len(shape) < 2 or v < 4096 or not softmax_xent_supported(n, v, dtype):
        return "logits %s %s below the kernel's shapes" % (
            tuple(shape), jnp.dtype(dtype).name)
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hard_label_xent(logits, lbl, smooth, ignore):
    """Closed-form CE over hard int labels, with optional label smoothing.

    The residuals are the (bf16) logits + a per-row logsumexp instead of the
    f32 log-probabilities autodiff would save: two exp passes total
    (fwd logsumexp, bwd softmax) and the [N, V]-sized saved buffer stays in
    the input dtype — with a 30k vocab this removes ~2GB of f32 HBM traffic
    per step vs differentiating through jax.nn.log_softmax."""
    loss, _ = _hard_label_xent_fwd(logits, lbl, smooth, ignore)
    return loss


def _hard_label_xent_fwd(logits, lbl, smooth, ignore):
    f = logits.astype(jnp.float32)
    m = jnp.max(f, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(f - m), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(f, jnp.maximum(lbl, 0)[..., None], axis=-1)
    loss = lse - picked
    if smooth:
        k = logits.shape[-1]
        sum_logp = jnp.sum(f, axis=-1, keepdims=True) - k * lse
        loss = (1.0 - smooth) * loss + (smooth / k) * (-sum_logp)
    loss = jnp.where((lbl != ignore)[..., None], loss, jnp.zeros_like(loss))
    return loss, (logits, lbl, lse)


def _hard_label_xent_bwd(smooth, ignore, res, g):
    logits, lbl, lse = res
    f = logits.astype(jnp.float32)
    p = jnp.exp(f - lse)
    k = logits.shape[-1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, f.shape, f.ndim - 1)
              == lbl[..., None])
    if smooth:
        d = p - (1.0 - smooth) * onehot - (smooth / k)
    else:
        d = p - onehot
    g = jnp.where((lbl != ignore)[..., None], g, jnp.zeros_like(g))
    return (g * d).astype(logits.dtype), None


_hard_label_xent.defvjp(_hard_label_xent_fwd, _hard_label_xent_bwd)


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy_op(ctx: OpContext):
    """One log_softmax pass serves plain CE, soft labels, AND label
    smoothing (``label_smoothing`` attr) — with a wide vocab the logits array
    dominates HBM traffic, so everything is derived from a single read. The
    softmax itself runs in fp32 even under bf16 AMP (logsumexp over 30k
    classes is precision-critical)."""
    logits = ctx.input("Logits")
    label = ctx.input("Label")
    soft_label = ctx.attr("soft_label", False)
    smooth = float(ctx.attr("label_smoothing", 0.0) or 0.0)
    out_dtype = logits.dtype
    if fused_xent_gate(logits.shape, logits.dtype, smooth, soft_label,
                       ctx.attr("ignore_index", -100)) is None:
        # Pallas fused path (pallas_kernels/softmax_xent.py): forward writes
        # only O(N) outputs; backward computes softmax-onehot (with the
        # closed-form label-smoothing term) on the fly.
        from .pallas_kernels import fused_softmax_xent

        v = logits.shape[-1]
        lead = logits.shape[:-1]
        lbl2d = label.reshape(-1, 1)
        loss = fused_softmax_xent(logits.reshape(-1, v), lbl2d, False, smooth)
        ctx.set_output("Loss", loss.reshape(*lead, 1).astype(out_dtype))
        if ctx.has_output("Softmax"):
            # derived lazily (reference grad kernel also treats Softmax as a
            # value, not a grad path); dead unless consumed, then XLA DCEs it
            f32 = logits.astype(jnp.float32)
            sm = jnp.exp(f32 - jax.scipy.special.logsumexp(f32, axis=-1, keepdims=True))
            ctx.set_output("Softmax", jax.lax.stop_gradient(sm).astype(out_dtype))
        return
    if not soft_label and not ctx.has_output("Softmax"):
        # hard labels, no softmax requested: closed-form custom-vjp path
        # (residuals are bf16 logits + lse, not f32 log-probs)
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        lbl = lbl.astype(jnp.int32)
        loss = _hard_label_xent(logits, lbl, float(smooth),
                                int(ctx.attr("ignore_index", -100)))
        ctx.set_output("Loss", loss.astype(out_dtype))
        return
    log_p = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if soft_label:
        loss = -jnp.sum(label * log_p, axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        lbl = lbl.astype(jnp.int32)
        picked = jnp.take_along_axis(log_p, jnp.maximum(lbl, 0)[..., None], axis=-1)
        loss = -picked
        if smooth:
            # q = (1-eps)·onehot + eps/K  ⇒  CE = (1-eps)·nll + eps/K·Σ(-logp)
            k = logits.shape[-1]
            loss = (1.0 - smooth) * loss + (smooth / k) * (
                -jnp.sum(log_p, axis=-1, keepdims=True))
        ignore = ctx.attr("ignore_index", -100)
        loss = jnp.where((lbl != ignore)[..., None], loss, jnp.zeros_like(loss))
    if ctx.has_output("Softmax"):
        ctx.set_output("Softmax", jnp.exp(log_p).astype(out_dtype))
    ctx.set_output("Loss", loss.astype(out_dtype))


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_xent_op(ctx: OpContext):
    x = ctx.input("X")
    label = ctx.input("Label")
    # max(x,0) - x*z + log(1+exp(-|x|)) — numerically stable
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = ctx.attr("ignore_index", -100)
    loss = jnp.where(label == ignore, jnp.zeros_like(loss), loss)
    if ctx.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / n
    ctx.set_output("Out", loss)


@register_op("log_loss")
def log_loss_op(ctx: OpContext):
    p = ctx.input("Predicted")
    y = ctx.input("Labels")
    eps = ctx.attr("epsilon", 1e-4)
    ctx.set_output("Loss", -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps))


@register_op("huber_loss")
def huber_loss_op(ctx: OpContext):
    x, y = ctx.input("X"), ctx.input("Y")
    d = ctx.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * r * r, d * (ar - 0.5 * d))
    ctx.set_output("Residual", r)
    ctx.set_output("Out", loss)


@register_op("smooth_l1_loss")
def smooth_l1_loss_op(ctx: OpContext):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ctx.has_input("InsideWeight"):
        diff = diff * ctx.input("InsideWeight")
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ctx.has_input("OutsideWeight"):
        loss = loss * ctx.input("OutsideWeight")
    ctx.set_output("Diff", diff)
    ctx.set_output("Out", jnp.sum(loss, axis=tuple(range(1, loss.ndim)), keepdims=False).reshape(x.shape[0], 1))


@register_op("hinge_loss")
def hinge_loss_op(ctx: OpContext):
    logits, labels = ctx.input("Logits"), ctx.input("Labels")
    ctx.set_output("Loss", jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0))


@register_op("rank_loss")
def rank_loss_op(ctx: OpContext):
    label = ctx.input("Label")
    left, right = ctx.input("Left"), ctx.input("Right")
    d = left - right
    ctx.set_output("Out", jnp.log1p(jnp.exp(d)) - label * d)


@register_op("bpr_loss")
def bpr_loss_op(ctx: OpContext):
    x = ctx.input("X")
    label = ctx.input("Label").reshape(-1).astype(jnp.int32)
    pos = jnp.take_along_axis(x, label[:, None], axis=-1)
    diff = x - pos
    loss = jnp.mean(jnp.log1p(jnp.exp(diff)), axis=-1, keepdims=True)
    ctx.set_output("Y", loss)


@register_op("margin_rank_loss")
def margin_rank_loss_op(ctx: OpContext):
    label, x1, x2 = ctx.input("Label"), ctx.input("X1"), ctx.input("X2")
    margin = ctx.attr("margin", 0.0)
    out = jnp.maximum(-label * (x1 - x2) + margin, 0.0)
    ctx.set_output("Out", out)
    ctx.set_output("Activated", (out > 0).astype(x1.dtype))


# -- normalization ------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, scale, bias, reduce_axes, eps):
    y, _ = _bn_train_fwd(x, scale, bias, reduce_axes, eps)
    return y


def _bn_stats(x, reduce_axes):
    # f32 ACCUMULATION directly off the bf16 input — never materializes an
    # f32 copy of the activation (jnp.mean(x.astype(f32)) does, and its VJP
    # then drags f32 [N,C,H,W] cotangents through the whole backward)
    n = 1
    for a in reduce_axes:
        n *= x.shape[a]
    mean = jnp.sum(x, axis=reduce_axes, dtype=jnp.float32) / n
    var = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=reduce_axes,
                  dtype=jnp.float32) / n - jnp.square(mean)
    return mean, var, n


def _bn_train_fwd(x, scale, bias, reduce_axes, eps):
    mean, var, _ = _bn_stats(x, reduce_axes)
    inv = jax.lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    ch_axis = [a for a in range(x.ndim) if a not in reduce_axes][0]
    bshape[ch_axis] = x.shape[ch_axis]
    xhat = (x - mean.astype(x.dtype).reshape(bshape)) * inv.astype(x.dtype).reshape(bshape)
    y = (xhat * scale.astype(x.dtype).reshape(bshape)
         + bias.astype(x.dtype).reshape(bshape))
    return y, (x, scale, mean, inv)


def _bn_train_bwd(reduce_axes, eps, res, dy):
    # classic fused BN backward (reference: batch_norm_op.cc grad kernel):
    # dx = (γ·inv/N)·(N·dy − Σdy − x̂·Σ(dy·x̂)) — two f32-accumulated
    # reductions and one elementwise pass, all in x.dtype
    x, scale, mean, inv = res
    ch_axis = [a for a in range(x.ndim) if a not in reduce_axes][0]
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    n = 1
    for a in reduce_axes:
        n *= x.shape[a]
    xhat = (x - mean.astype(x.dtype).reshape(bshape)) * inv.astype(x.dtype).reshape(bshape)
    dy_sum = jnp.sum(dy, axis=reduce_axes, dtype=jnp.float32)
    dyxhat_sum = jnp.sum((dy * xhat).astype(jnp.float32), axis=reduce_axes,
                         dtype=jnp.float32)
    dscale = dyxhat_sum
    dbias = dy_sum
    coef = (scale.astype(jnp.float32) * inv / n).astype(x.dtype)
    dx = coef.reshape(bshape) * (
        n * dy
        - dy_sum.astype(x.dtype).reshape(bshape)
        - xhat * dyxhat_sum.astype(x.dtype).reshape(bshape))
    return dx, dscale.astype(scale.dtype), dbias.astype(scale.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register_op("batch_norm")
def batch_norm_op(ctx: OpContext):
    """Reference: operators/batch_norm_op.cc. NCHW/NHWC via data_layout attr.

    Training: normalize by batch stats; MeanOut/VarianceOut are the running
    stats updated with momentum (Fluid aliases them onto Mean/Variance — here
    the functional env rebinds the same names).
    """
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var = ctx.input("Mean"), ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    layout = ctx.attr("data_layout", "NCHW")
    use_global = ctx.attr("use_global_stats", False) or ctx.is_test

    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]

    cdt = jnp.float32
    if use_global:
        use_mean, use_var = mean.astype(cdt), var.astype(cdt)
        ctx.set_output("MeanOut", mean)
        ctx.set_output("VarianceOut", var)
        inv = jax.lax.rsqrt(use_var + eps).astype(x.dtype)
        y = (x - use_mean.astype(x.dtype).reshape(bshape)) * inv.reshape(bshape)
        y = (y * scale.astype(x.dtype).reshape(bshape)
             + bias.astype(x.dtype).reshape(bshape))
        ctx.set_output("Y", y)
    else:
        # custom-vjp fused path: f32-accumulated stats straight off the bf16
        # input and the closed-form BN backward — autodiff through the stats
        # otherwise drags f32 [N,C,H,W] cotangents through the graph
        # (measured ~30% of ResNet-50 step HBM traffic)
        bmean, bvar, _ = _bn_stats(x, reduce_axes)
        bmean = jax.lax.stop_gradient(bmean)
        bvar = jax.lax.stop_gradient(bvar)
        ctx.set_output("MeanOut", (momentum * mean.astype(cdt) + (1 - momentum) * bmean).astype(mean.dtype))
        ctx.set_output("VarianceOut", (momentum * var.astype(cdt) + (1 - momentum) * bvar).astype(var.dtype))
        ctx.set_output("SavedMean", bmean.astype(mean.dtype))
        ctx.set_output("SavedVariance", bvar.astype(var.dtype))
        ctx.set_output("Y", _bn_train(x, scale, bias, reduce_axes, eps))


def _ln_stats(x, axes):
    n = 1
    for a in axes:
        n *= x.shape[a]
    mean = jnp.sum(x, axis=axes, keepdims=True, dtype=jnp.float32) / n
    var = (jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes,
                   keepdims=True, dtype=jnp.float32) / n - jnp.square(mean))
    return mean, var, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_train(x, scale, bias, axis, eps):
    """Layer norm with the closed-form backward — same HBM rationale as
    _bn_train: f32 accumulation off the bf16 input, residuals in x.dtype."""
    y, _ = _ln_train_fwd(x, scale, bias, axis, eps)
    return y


def _ln_train_fwd(x, scale, bias, axis, eps):
    axes = tuple(range(axis, x.ndim))
    mean, var, _ = _ln_stats(x, axes)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    norm_shape = x.shape[axis:]
    y = xhat
    if scale is not None:
        y = y * scale.astype(x.dtype).reshape(norm_shape)
    if bias is not None:
        y = y + bias.astype(x.dtype).reshape(norm_shape)
    return y, (x, scale, bias, mean, inv)


def _ln_train_bwd(axis, eps, res, dy):
    x, scale, bias, mean, inv = res
    axes = tuple(range(axis, x.ndim))
    lead = tuple(range(axis))
    n = 1
    for a in axes:
        n *= x.shape[a]
    norm_shape = x.shape[axis:]
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    dscale = (jnp.sum((dy * xhat).astype(jnp.float32), axis=lead)
              .reshape(-1) if scale is not None else None)
    dbias = (jnp.sum(dy, axis=lead, dtype=jnp.float32).reshape(-1)
             if bias is not None else None)
    dyh = dy * scale.astype(dy.dtype).reshape(norm_shape) if scale is not None else dy
    s1 = jnp.sum(dyh, axis=axes, keepdims=True, dtype=jnp.float32)
    s2 = jnp.sum((dyh * xhat).astype(jnp.float32), axis=axes, keepdims=True,
                 dtype=jnp.float32)
    coef = (inv / n).astype(x.dtype)
    dx = coef * (n * dyh - s1.astype(x.dtype) - xhat * s2.astype(x.dtype))
    return (dx,
            dscale.astype(scale.dtype) if scale is not None else None,
            dbias.astype(bias.dtype) if bias is not None else None)


_ln_train.defvjp(_ln_train_fwd, _ln_train_bwd)


@register_op("layer_norm")
def layer_norm_op(ctx: OpContext):
    """Reference: operators/layer_norm_op.cc — normalize over dims >= begin_norm_axis."""
    x = ctx.input("X")
    axis = ctx.attr("begin_norm_axis", 1)
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(axis, x.ndim))
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean, var, _ = _ln_stats(x, axes)
    ctx.set_output("Y", _ln_train(x, scale, bias, axis, eps))
    ctx.set_output("Mean", jax.lax.stop_gradient(
        mean.reshape(x.shape[:axis]).reshape(-1)))
    ctx.set_output("Variance", jax.lax.stop_gradient(
        var.reshape(x.shape[:axis]).reshape(-1)))


@register_op("group_norm")
def group_norm_op(ctx: OpContext):
    x = ctx.input("X")  # NCHW
    groups = ctx.attr("groups")
    eps = ctx.attr("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape(n, groups, c // groups, *x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    ctx.set_output("Y", y)
    ctx.set_output("Mean", mean.reshape(n, groups))
    ctx.set_output("Variance", var.reshape(n, groups))


@register_op("instance_norm")
def instance_norm_op(ctx: OpContext):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    if scale is not None:
        bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        y = y * scale.reshape(bshape) + bias.reshape(bshape)
    ctx.set_output("Y", y)


@register_op("lrn")
def lrn_op(ctx: OpContext):
    x = ctx.input("X")  # NCHW
    n_size = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n_size // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = jnp.zeros_like(x)
    for i in range(n_size):
        acc = acc + pad[:, i : i + x.shape[1]]
    mid = k + alpha * acc
    ctx.set_output("MidOut", mid)
    ctx.set_output("Out", x / jnp.power(mid, beta))


@register_op("data_norm")
def data_norm_op(ctx: OpContext):
    x = ctx.input("X")
    size = ctx.input("BatchSize")
    bsum = ctx.input("BatchSum")
    bsq = ctx.input("BatchSquareSum")
    means = bsum / size
    scales = jax.lax.rsqrt(bsq / size - jnp.square(means) + 1e-4)
    ctx.set_output("Means", means)
    ctx.set_output("Scales", scales)
    ctx.set_output("Y", (x - means) * scales)


@register_op("affine_channel")
def affine_channel_op(ctx: OpContext):
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    layout = ctx.attr("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    ctx.set_output("Out", x * scale.reshape(bshape) + bias.reshape(bshape))


# -- conv / pool --------------------------------------------------------------


def _conv_nd(ctx: OpContext, nd: int, transpose: bool = False):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # OIHW (layout-independent param storage)
    strides = tuple(ctx.attr("strides", [1] * nd))
    paddings = ctx.attr("paddings", [0] * nd)
    dilations = tuple(ctx.attr("dilations", [1] * nd))
    groups = ctx.attr("groups", 1) or 1
    pad = [(p, p) for p in paddings]
    spatial = "DHW"[-nd:]
    # NHWC is the TPU-preferred activation layout (channels on the 128-lane
    # minor dim); params stay OIHW so checkpoints are layout-portable
    fmt = ctx.attr("data_format", "NCHW")
    lhs_spec = ("N" + spatial + "C") if fmt in ("NHWC", "NDHWC") else "NC" + spatial
    rhs_spec = "OI" + spatial
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, (lhs_spec, rhs_spec, lhs_spec))
    if not transpose:
        # No preferred_element_type widening: the TPU MXU already accumulates
        # bf16 convs in fp32 internally, and the f32 hint breaks jax.grad
        # (the transpose conv then mixes a f32 cotangent with bf16 operands).
        out = jax.lax.conv_general_dilated(
            x,
            w,
            window_strides=strides,
            padding=pad,
            rhs_dilation=dilations,
            dimension_numbers=dn,
            feature_group_count=groups,
        )
    else:
        # conv_transpose: fluid filter layout is [in_c, out_c/g, H, W]
        w_t = jnp.swapaxes(w, 0, 1)  # → [out_c/g, in_c, H, W]
        w_t = jnp.flip(w_t, axis=tuple(range(2, 2 + nd)))
        out = jax.lax.conv_general_dilated(
            x,
            w_t,
            window_strides=(1,) * nd,
            padding=[
                (d * (k - 1) - p, d * (k - 1) - p)
                for k, p, d in zip(w.shape[2:], paddings, dilations)
            ],
            lhs_dilation=strides,
            rhs_dilation=dilations,
            dimension_numbers=dn,
            feature_group_count=groups,
        )
    ctx.set_output("Output", out)


@register_op("conv2d", "depthwise_conv2d")
def conv2d_op(ctx):
    _conv_nd(ctx, 2)


@register_op("conv3d")
def conv3d_op(ctx):
    _conv_nd(ctx, 3)


@register_op("conv2d_transpose", "depthwise_conv2d_transpose")
def conv2d_transpose_op(ctx):
    _conv_nd(ctx, 2, transpose=True)


@register_op("conv3d_transpose")
def conv3d_transpose_op(ctx):
    _conv_nd(ctx, 3, transpose=True)


def _pool_nd(ctx: OpContext, nd: int):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    ksize = list(ctx.attr("ksize", [1] * nd))
    strides = list(ctx.attr("strides", [1] * nd))
    paddings = list(ctx.attr("paddings", [0] * nd))
    nhwc = ctx.attr("data_format", "NCHW") in ("NHWC", "NDHWC")
    sp0 = 1 if nhwc else 2  # first spatial axis
    red = jnp.max if ptype == "max" else jnp.mean
    if ctx.attr("global_pooling", False) or (
            ctx.attr("adaptive", False) and all(k == 1 for k in ksize)):
        axes = tuple(range(sp0, sp0 + nd))
        ctx.set_output("Out", red(x, axis=axes, keepdims=True))
        return
    if ctx.attr("adaptive", False):
        # Adaptive pooling (reference: nn.py adaptive_pool2d/3d lowering to
        # pool ops with adaptive=True): ksize holds the OUTPUT sizes; window
        # d covers [floor(i·in/out), ceil((i+1)·in/out)). Divisible dims use
        # a reshape+reduce (one fused XLA op); ragged dims unroll a static
        # per-output-slice loop (output sizes are small, e.g. 7).
        out = x
        for d, osize in enumerate(int(k) for k in ksize):
            axis = sp0 + d
            insize = out.shape[axis]
            if insize % osize == 0:
                k = insize // osize
                shp = out.shape[:axis] + (osize, k) + out.shape[axis + 1:]
                out = red(out.reshape(shp), axis=axis + 1)
            else:
                sl = [slice(None)] * out.ndim
                pieces = []
                for i in range(osize):
                    sl[axis] = slice((i * insize) // osize,
                                     -((-(i + 1) * insize) // osize))
                    pieces.append(red(out[tuple(sl)], axis=axis))
                out = jnp.stack(pieces, axis=axis)
        ctx.set_output("Out", out)
        return
    if nhwc:
        window = (1,) + tuple(ksize) + (1,)
        stride = (1,) + tuple(strides) + (1,)
        pad = ((0, 0),) + tuple((p, p) for p in paddings) + ((0, 0),)
    else:
        window = (1, 1) + tuple(ksize)
        stride = (1, 1) + tuple(strides)
        pad = ((0, 0), (0, 0)) + tuple((p, p) for p in paddings)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, stride, pad)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, stride, pad)
        if ctx.attr("exclusive", True) and any(p > 0 for p in paddings):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, stride, pad)
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    ctx.set_output("Out", out)


@register_op("pool2d")
def pool2d_op(ctx):
    _pool_nd(ctx, 2)


@register_op("pool3d")
def pool3d_op(ctx):
    _pool_nd(ctx, 3)


# -- embedding ----------------------------------------------------------------


@register_op("lookup_table", "lookup_table_v2")
def lookup_table_op(ctx: OpContext):
    """Reference: operators/lookup_table_op.cc. Ids [..., 1] int → [..., D].

    ``is_sparse=True`` reproduces the SelectedRows gradient path
    (core/sparse.py): the table is read through ``stop_gradient`` and a
    zero "virtual rows" tensor [N, D] (an extra differentiated input the
    executor threads in) is added to the gathered rows, so the backward
    yields an O(N·D) rows gradient and the O(V·D) dense scatter-add never
    exists in the graph. Dense mode keeps the plain differentiable gather.
    Sharded embeddings live in paddle_tpu/parallel.
    """
    w = ctx.input("W")
    ids = ctx.input("Ids")
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1 and ctx.op.type == "lookup_table"
    if squeeze_last:
        ids = ids.reshape(ids.shape[:-1])
    ids = ids.astype(jnp.int32)
    padding_idx = ctx.attr("padding_idx", -1)

    w_name = ctx.op.inputs["W"][0]
    env = ctx.env
    collect = env.get("__sparse_collect__")
    if collect is not None and ctx.attr("is_sparse", False):
        d = w.shape[1]
        if w_name in collect:
            raise NotImplementedError(
                "sparse embedding table %r is looked up more than once in one "
                "program — use is_sparse=False for shared tables" % w_name)
        collect[w_name] = ((int(np.prod(ids.shape)), d), w.dtype)
    # clamp BOTH ends for the gather: jnp.take's single-device default
    # clips, but a row-sharded table turns the gather into per-shard gathers
    # where XLA's out-of-bounds semantics are undefined (garbage/NaN) —
    # explicit clipping keeps mesh and single-device behavior identical for
    # stray ids
    virtuals = env.get("__sparse_virtual__") or {}
    if w_name in virtuals:
        flat_raw = ids.reshape(-1)
        flat_ids = jnp.clip(flat_raw, 0, w.shape[0] - 1)
        gathered = jnp.take(jax.lax.stop_gradient(w), flat_ids, axis=0)
        gathered = gathered.astype(virtuals[w_name].dtype) + virtuals[w_name]
        out = gathered.reshape(ids.shape + (w.shape[1],))
        # the optimizer-facing id list maps masked ids (< 0, output zeroed
        # below ⇒ zero grad row) to V — the merge_rows invalid index — so
        # the row-wise update DROPS them instead of lazily decaying row 0's
        # moments every step
        env["__sparse_ids__" + w_name] = jnp.where(
            flat_raw < 0, jnp.asarray(w.shape[0], flat_ids.dtype), flat_ids)
    else:
        out = jnp.take(w, jnp.clip(ids, 0, w.shape[0] - 1), axis=0)
    out = jnp.where((ids >= 0)[..., None], out, jnp.zeros_like(out))
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], jnp.zeros_like(out), out)
    ctx.set_output("Out", out)


# -- metrics ------------------------------------------------------------------


@register_op("accuracy")
def accuracy_op(ctx: OpContext):
    """Reference: operators/metrics/accuracy_op.cc — takes top-k Indices + Label."""
    indices = ctx.input("Indices")
    label = ctx.input("Label")
    lbl = label.reshape(-1, 1)
    correct = jnp.any(indices == lbl, axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.int32))
    total = jnp.asarray(lbl.shape[0], jnp.int32)
    ctx.set_output("Accuracy", num_correct.astype(jnp.float32) / lbl.shape[0])
    ctx.set_output("Correct", num_correct)
    ctx.set_output("Total", total)


@register_op("auc")
def auc_op(ctx: OpContext):
    """Streaming AUC via histogram stats (reference: operators/metrics/auc_op.cc)."""
    preds = ctx.input("Predict")
    label = ctx.input("Label").reshape(-1)
    stat_pos = ctx.input("StatPos")
    stat_neg = ctx.input("StatNeg")
    num_buckets = stat_pos.shape[-1]
    pos_prob = preds[:, 1] if preds.ndim == 2 and preds.shape[1] == 2 else preds.reshape(-1)
    bucket = jnp.clip((pos_prob * num_buckets).astype(jnp.int32), 0, num_buckets - 1)
    is_pos = (label > 0).astype(stat_pos.dtype)
    new_pos = stat_pos.reshape(-1).at[bucket].add(is_pos)
    new_neg = stat_neg.reshape(-1).at[bucket].add(1 - is_pos)
    # AUC = P(score_pos > score_neg): for each neg bucket, count positives in
    # strictly higher buckets plus half the same-bucket ties.
    tot_pos = jnp.sum(new_pos)
    pos_below_incl = jnp.cumsum(new_pos)
    pos_above = tot_pos - pos_below_incl
    auc_sum = jnp.sum(new_neg * (pos_above + new_pos * 0.5))
    tot_neg = jnp.sum(new_neg)
    auc = jnp.where(tot_pos * tot_neg > 0, auc_sum / jnp.maximum(tot_pos * tot_neg, 1.0), 0.0)
    ctx.set_output("AUC", auc.astype(jnp.float32))
    ctx.set_output("StatPosOut", new_pos.reshape(stat_pos.shape))
    ctx.set_output("StatNegOut", new_neg.reshape(stat_neg.shape))


@register_op("mean_iou")
def mean_iou_op(ctx: OpContext):
    preds = ctx.input("Predictions").reshape(-1)
    labels = ctx.input("Labels").reshape(-1)
    num_classes = ctx.attr("num_classes")
    cm = jnp.zeros((num_classes, num_classes), jnp.float32).at[labels, preds].add(1.0)
    inter = jnp.diag(cm)
    union = jnp.sum(cm, 0) + jnp.sum(cm, 1) - inter
    valid = union > 0
    iou = jnp.where(valid, inter / jnp.maximum(union, 1.0), 0.0)
    ctx.set_output("OutMeanIou", jnp.sum(iou) / jnp.maximum(jnp.sum(valid), 1))


# -- interpolation ------------------------------------------------------------


def _interp(ctx: OpContext, method: str):
    x = ctx.input("X")  # NCHW
    out_h = ctx.attr("out_h", 0)
    out_w = ctx.attr("out_w", 0)
    if ctx.has_input("OutSize"):
        sz = np.asarray(ctx.input("OutSize"))
        out_h, out_w = int(sz[0]), int(sz[1])
    scale = ctx.attr("scale", 0.0)
    if (not out_h or out_h <= 0) and scale:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    out = jax.image.resize(x, (x.shape[0], x.shape[1], out_h, out_w), method=method)
    ctx.set_output("Out", out.astype(x.dtype))


@register_op("bilinear_interp")
def bilinear_interp_op(ctx):
    _interp(ctx, "bilinear")


@register_op("nearest_interp")
def nearest_interp_op(ctx):
    _interp(ctx, "nearest")

"""Optimizer update ops.

Fluid's optimizers are per-parameter device kernels mutating params in place
(reference: ``operators/optimizers/`` — sgd_op.cc, momentum_op.cc,
adam_op.cc, ...). Here each is a functional update; the Executor donates the
state buffers to the jitted step so XLA updates params in place in HBM —
the same zero-copy effect without mutation semantics.

Every op reads Param/Grad/LearningRate (+ accumulators) and writes
ParamOut (+ accumulator outs), exactly mirroring the reference op signatures
so the Python Optimizer layer stays Fluid-shaped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import OpContext, register_op


def _lr(ctx):
    lr = ctx.input("LearningRate")
    return lr.reshape(()) if hasattr(lr, "reshape") else jnp.asarray(lr)


def _sparse(g):
    from ..core.sparse import SparseGrad

    return g if isinstance(g, SparseGrad) else None


def _sparse_kernel_mode():
    """Resolve ``FLAGS_sparse_update_kernel`` for this trace: None = XLA
    scatter path, "compiled"/"interpret" = the row-DMA Pallas kernel
    (pallas_kernels/sparse_adam.py). "auto" compiles on TPU and keeps the
    scatter path elsewhere — the interpreter is a correctness tool, not a
    fast CPU path."""
    from ..flags import flags

    mode = str(flags.sparse_update_kernel).lower()
    if mode in ("0", "off", "false", "no"):
        return None
    if mode == "interpret":
        return "interpret"
    on_tpu = jax.default_backend() == "tpu"
    if mode in ("1", "on", "true", "yes"):
        return "compiled" if on_tpu else "interpret"
    return "compiled" if on_tpu else None  # auto


def _table_mesh_sharding(ctx, param):
    """(mesh, axis) when this op's Param table is row-sharded over a live
    mesh axis (parallel.sharded_embedding annotation) — the signal to route
    the update through core.sparse.sharded_rows_update instead of a global
    scatter (which would gather the table)."""
    mesh = getattr(ctx.trace, "mesh", None)
    if mesh is None:
        return None
    names = ctx.op.inputs.get("Param")
    if not names:
        return None
    try:
        var = ctx.var(names[0])
    except Exception:
        return None
    spec = getattr(var, "sharding", None)
    from ..parallel.mesh import valid_sharding

    if not spec or spec[0] is None or not valid_sharding(spec, mesh):
        return None
    axis = spec[0]
    n = mesh.shape[axis]
    if n <= 1:
        return None
    if param.shape[0] % n:
        # uneven rows can't take the shard-local path; the global-scatter
        # fallback re-materializes the full table per step — loud, because
        # at the V this feature exists for that IS the OOM being avoided
        import warnings

        warnings.warn(
            "sparse table %r: V=%d not divisible by mesh axis %r (n=%d); "
            "falling back to the full-table scatter update. Pad the vocab "
            "to a multiple of the axis size to keep updates shard-local."
            % (names[0], param.shape[0], axis, n))
        return None
    return mesh, axis


def _use_alltoall(n_ids, n_shards):
    from ..flags import flags

    return bool(flags.ctr_alltoall_update) and n_ids % n_shards == 0


def _kernel_for(param, *moments):
    """(kmode, interpret) when the row-DMA kernel should carry this update
    — FLAGS gate resolved AND the kernel's static gate admits the table
    (f32 tables and moments; compiled, a row width of whole 128-lane
    tiles); (None, False) means the scatter formulation."""
    return sparse_update_path(param.shape, param.dtype,
                              *(t.dtype for t in moments))[:2]


def sparse_update_path(shape, dtype, *moment_dtypes):
    """``(kmode, interpret, why)`` of the sparse-row update for a ``shape``
    table as ``FLAGS_sparse_update_kernel`` and the kernel's static gate
    (pallas_kernels.sparse_adam.sparse_rows_gate) resolve it: ``kmode``
    "compiled"/"interpret" with ``why`` None, or None (the XLA scatter
    path) with ``why`` the flag's value or the gate's rule."""
    from .pallas_kernels.sparse_adam import sparse_rows_gate

    kmode = _sparse_kernel_mode()
    if kmode is None:
        from ..flags import flags

        return None, False, ("FLAGS_sparse_update_kernel=%s on the %s backend"
                             % (flags.sparse_update_kernel,
                                jax.default_backend()))
    interp = kmode == "interpret"
    why = sparse_rows_gate(shape[0], shape[1], dtype, interpret=interp)
    if why is None and any(jnp.dtype(d) != jnp.float32
                           for d in moment_dtypes):
        why = "moments are not float32"
    if why is not None:
        return None, False, "gate: " + why
    return kmode, interp, None


@register_op("sgd")
def sgd_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    sg = _sparse(g)
    if sg is not None:
        # SelectedRows branch (reference: sgd_op.h sparse path): touch only
        # the looked-up rows; duplicate ids accumulate in the scatter-add.
        lr = _lr(ctx).astype(p.dtype)
        sharded = _table_mesh_sharding(ctx, p)
        if sharded is not None:
            from ..core.sparse import merge_rows, sharded_rows_update

            mesh, axis = sharded
            uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype),
                                      p.shape[0])
            kmode, interp = _kernel_for(p)

            def _upd(tabs, lid, rows_l, lr_s):
                (p_l,) = tabs
                if kmode is not None:
                    # the row-DMA kernel runs per shard on the local
                    # [V/n, D] slice; foreign/pad ids arrive as the local
                    # OOB (== shard rows) and the kernel drops their writes
                    from .pallas_kernels.sparse_adam import sparse_sgd_rows

                    return (sparse_sgd_rows(p_l, lid, rows_l, lr_s,
                                            interpret=interp),)
                return (p_l.at[lid].add(-lr_s * rows_l),)

            (p_new,) = sharded_rows_update(
                (p,), uniq, merged, _upd, mesh, axis, scalars=(lr,),
                alltoall=_use_alltoall(uniq.shape[0], mesh.shape[axis]))
            ctx.set_output("ParamOut", p_new)
            return
        kmode, interp = _kernel_for(p)
        if kmode is not None:
            # one row-DMA kernel instead of the XLA scatter pass
            # (SPARSE_PROFILE.md §1/§4); merge first — the kernel wants
            # unique rows, and XLA drops the merge padding's OOB id just
            # like the scatter would
            from ..core.sparse import merge_rows
            from .pallas_kernels.sparse_adam import sparse_sgd_rows

            uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype),
                                      p.shape[0])
            ctx.set_output("ParamOut", sparse_sgd_rows(
                p, uniq, merged, lr, interpret=interp))
            return
        ctx.set_output("ParamOut", p.at[sg.ids].add(
            -lr * sg.rows.astype(p.dtype)))
        return
    ctx.set_output("ParamOut", p - _lr(ctx).astype(p.dtype) * g.astype(p.dtype))


@register_op("momentum")
def momentum_op(ctx: OpContext):
    p, g, v = ctx.input("Param"), ctx.input("Grad"), ctx.input("Velocity")
    lr = _lr(ctx).astype(p.dtype)
    mu = jnp.asarray(ctx.attr("mu"), p.dtype)
    sg = _sparse(g)
    if sg is not None:
        # lazy rows-only momentum (untouched rows keep stale velocity — the
        # reference's SelectedRows momentum has the same semantics)
        from ..core.sparse import merge_rows

        uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype), p.shape[0])
        v_rows = mu * v[uniq] + merged
        if ctx.attr("use_nesterov", False):
            step_rows = (merged + mu * v_rows) * lr
        else:
            step_rows = lr * v_rows
        ctx.set_output("ParamOut", p.at[uniq].add(-step_rows))
        ctx.set_output("VelocityOut", v.at[uniq].set(v_rows))
        return
    v_new = mu * v + g.astype(p.dtype)
    if ctx.attr("use_nesterov", False):
        p_new = p - (g.astype(p.dtype) + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    ctx.set_output("ParamOut", p_new)
    ctx.set_output("VelocityOut", v_new)


@register_op("lars_momentum")
def lars_momentum_op(ctx: OpContext):
    p, g, v = ctx.input("Param"), ctx.input("Grad"), ctx.input("Velocity")
    lr = _lr(ctx).astype(p.dtype)
    mu = jnp.asarray(ctx.attr("mu"), p.dtype)
    coeff = ctx.attr("lars_coeff", 0.001)
    decay = ctx.attr("lars_weight_decay", 0.0005)
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (pn > 0) & (gn > 0), lr * coeff * pn / (gn + decay * pn), lr
    )
    v_new = mu * v + local_lr * (g + decay * p)
    ctx.set_output("ParamOut", p - v_new)
    ctx.set_output("VelocityOut", v_new)


@register_op("adam")
def adam_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    m, v = ctx.input("Moment1"), ctx.input("Moment2")
    b1p, b2p = ctx.input("Beta1Pow"), ctx.input("Beta2Pow")
    lr = _lr(ctx)
    b1 = jnp.asarray(ctx.attr("beta1", 0.9), jnp.float32)
    b2 = jnp.asarray(ctx.attr("beta2", 0.999), jnp.float32)
    eps = jnp.asarray(ctx.attr("epsilon", 1e-8), jnp.float32)
    lr_t = lr * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
    sg = _sparse(g)
    if sg is not None:
        # lazy-mode sparse adam (reference: adam_op.h SelectedRows branch,
        # lazy_mode): moments decay and params move ONLY on touched rows.
        from ..core.sparse import merge_rows

        uniq, merged = merge_rows(sg.ids, sg.rows.astype(jnp.float32),
                                  p.shape[0])
        ctx.set_output("Beta1PowOut", b1p * b1)
        ctx.set_output("Beta2PowOut", b2p * b2)
        sharded = _table_mesh_sharding(ctx, p)
        b1f = float(ctx.attr("beta1", 0.9))
        b2f = float(ctx.attr("beta2", 0.999))
        epsf = float(ctx.attr("epsilon", 1e-8))
        if sharded is not None:
            # row-sharded table (parallel.sharded_embedding): shard-local
            # rows-only updates — param AND both moments stay [V/n, D] per
            # device, nothing ever gathers the table
            from ..core.sparse import sharded_rows_update

            mesh, axis = sharded
            kmode, interp = _kernel_for(p, m, v)

            def _upd(tabs, lid, rows_l, lr_s):
                p_l, m_l, v_l = tabs
                if kmode is not None:
                    # the two tentpole halves compose: the row-DMA kernel
                    # runs per shard on the local [V/n, D] slices (foreign/
                    # pad ids arrive as the local OOB == shard rows, whose
                    # writes the kernel drops)
                    from .pallas_kernels.sparse_adam import sparse_adam_rows

                    return sparse_adam_rows(p_l, m_l, v_l, lid, rows_l,
                                            lr_s, b1f, b2f, epsf,
                                            interpret=interp)
                m_old, v_old = m_l[lid], v_l[lid]
                m_rows = b1 * m_old + (1 - b1) * rows_l
                v_rows = b2 * v_old + (1 - b2) * jnp.square(rows_l)
                step = lr_s * m_rows / (jnp.sqrt(v_rows) + eps)
                return (p_l.at[lid].add(-step.astype(p_l.dtype)),
                        m_l.at[lid].add(m_rows - m_old),
                        v_l.at[lid].add(v_rows - v_old))

            p_new, m_new, v_new = sharded_rows_update(
                (p, m, v), uniq, merged, _upd, mesh, axis,
                scalars=(lr_t,),
                alltoall=_use_alltoall(uniq.shape[0], mesh.shape[axis]))
            ctx.set_output("ParamOut", p_new)
            ctx.set_output("Moment1Out", m_new)
            ctx.set_output("Moment2Out", v_new)
            return
        kmode, interp = _kernel_for(p, m, v)
        if kmode is not None:
            # one row-DMA Pallas kernel replaces the three ~30 GB/s scatter
            # fusions (SPARSE_PROFILE.md §1 → §4)
            from .pallas_kernels.sparse_adam import sparse_adam_rows

            p_new, m_new, v_new = sparse_adam_rows(
                p, m, v, uniq, merged, lr_t,
                beta1=b1f, beta2=b2f, epsilon=epsf, interpret=interp)
            ctx.set_output("ParamOut", p_new)
            ctx.set_output("Moment1Out", m_new)
            ctx.set_output("Moment2Out", v_new)
            return
        m_old, v_old = m[uniq], v[uniq]
        m_rows = b1 * m_old + (1 - b1) * merged
        v_rows = b2 * v_old + (1 - b2) * jnp.square(merged)
        step = lr_t * m_rows / (jnp.sqrt(v_rows) + eps)
        ctx.set_output("ParamOut", p.at[uniq].add(-step.astype(p.dtype)))
        # express the moment writes as scatter-ADDs of the delta rather than
        # scatter-sets: on v5e the set-combiner scatter kernel measures ~2x
        # the add-combiner on a [1e6,10] table (2.7 vs 1.3 ms per scatter in
        # the DeepFM step), and the old rows are already gathered
        ctx.set_output("Moment1Out", m.at[uniq].add(m_rows - m_old))
        ctx.set_output("Moment2Out", v.at[uniq].add(v_rows - v_old))
        return
    gf = g.astype(jnp.float32)
    m_new = b1 * m + (1 - b1) * gf
    v_new = b2 * v + (1 - b2) * jnp.square(gf)
    # Reference adam_op.h: lr_t = lr * sqrt(1-beta2^t)/(1-beta1^t)
    p_new = p.astype(jnp.float32) - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    ctx.set_output("ParamOut", p_new.astype(p.dtype))
    ctx.set_output("Moment1Out", m_new)
    ctx.set_output("Moment2Out", v_new)
    # Fluid updates beta pows in a separate scale op; we fold it here and
    # also expose the outs for parity when wired.
    ctx.set_output("Beta1PowOut", b1p * b1)
    ctx.set_output("Beta2PowOut", b2p * b2)


@register_op("adamw")
def adamw_op(ctx: OpContext):
    p = ctx.input("Param")
    coeff = ctx.attr("weight_decay", 0.01)
    lr = _lr(ctx)
    adam_op(ctx)
    p_out = ctx.env[ctx.output_name("ParamOut")]
    ctx.set_output("ParamOut", (p_out.astype(jnp.float32) - lr * coeff * p.astype(jnp.float32)).astype(p.dtype))


@register_op("adamax")
def adamax_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    m, inf = ctx.input("Moment"), ctx.input("InfNorm")
    b1p = ctx.input("Beta1Pow")
    lr = _lr(ctx)
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf, jnp.abs(g))
    lr_t = lr / (1 - b1p.reshape(()))
    ctx.set_output("ParamOut", p - lr_t * m_new / (inf_new + eps))
    ctx.set_output("MomentOut", m_new)
    ctx.set_output("InfNormOut", inf_new)
    ctx.set_output("Beta1PowOut", b1p * b1)


@register_op("adagrad")
def adagrad_op(ctx: OpContext):
    p, g, moment = ctx.input("Param"), ctx.input("Grad"), ctx.input("Moment")
    lr = _lr(ctx)
    eps = ctx.attr("epsilon", 1e-6)
    sg = _sparse(g)
    if sg is not None:
        from ..core.sparse import merge_rows

        uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype), p.shape[0])
        m_rows = moment[uniq] + jnp.square(merged)
        ctx.set_output("ParamOut", p.at[uniq].add(
            -lr * merged / (jnp.sqrt(m_rows) + eps)))
        ctx.set_output("MomentOut", moment.at[uniq].set(m_rows))
        return
    m_new = moment + jnp.square(g)
    ctx.set_output("ParamOut", p - lr * g / (jnp.sqrt(m_new) + eps))
    ctx.set_output("MomentOut", m_new)


@register_op("decayed_adagrad")
def decayed_adagrad_op(ctx: OpContext):
    p, g, moment = ctx.input("Param"), ctx.input("Grad"), ctx.input("Moment")
    lr = _lr(ctx)
    decay = ctx.attr("decay", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    m_new = decay * moment + (1 - decay) * jnp.square(g)
    ctx.set_output("ParamOut", p - lr * g / (jnp.sqrt(m_new) + eps))
    ctx.set_output("MomentOut", m_new)


@register_op("adadelta")
def adadelta_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    avg_sq_g, avg_sq_u = ctx.input("AvgSquaredGrad"), ctx.input("AvgSquaredUpdate")
    rho = ctx.attr("rho", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    ctx.set_output("ParamOut", p + update)
    ctx.set_output("AvgSquaredGradOut", g2)
    ctx.set_output("AvgSquaredUpdateOut", u2)


@register_op("rmsprop")
def rmsprop_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    ms, mom = ctx.input("MeanSquare"), ctx.input("Moment")
    lr = _lr(ctx)
    rho = ctx.attr("decay", 0.9)
    eps = ctx.attr("epsilon", 1e-10)
    mu = ctx.attr("momentum", 0.0)
    centered = ctx.attr("centered", False)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    if centered:
        mg = ctx.input("MeanGrad")
        mg_new = rho * mg + (1 - rho) * g
        mom_new = mu * mom + lr * g / jnp.sqrt(ms_new - jnp.square(mg_new) + eps)
        ctx.set_output("MeanGradOut", mg_new)
    else:
        mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    ctx.set_output("ParamOut", p - mom_new)
    ctx.set_output("MeanSquareOut", ms_new)
    ctx.set_output("MomentOut", mom_new)


def _soft_threshold(prox, lr, l1, l2):
    """The proximal-operator shrinkage shared by proximal_gd/adagrad
    (reference: operators/optimizers/proximal_gd_op.h:49): L1 soft-threshold
    then L2 shrink. l1/l2 are static attrs, so the branch folds at trace."""
    if l1 > 0:
        return (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                / (1.0 + lr * l2))
    return prox / (1.0 + lr * l2)


@register_op("proximal_gd")
def proximal_gd_op(ctx: OpContext):
    """reference: operators/optimizers/proximal_gd_op.cc (dense-only there;
    the sparse rows-only variant here matches sgd's SelectedRows idiom)."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    lr = _lr(ctx).astype(p.dtype)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    sg = _sparse(g)
    if sg is not None:
        from ..core.sparse import merge_rows

        uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype), p.shape[0])
        prox_rows = p[uniq] - lr * merged
        ctx.set_output("ParamOut",
                       p.at[uniq].set(_soft_threshold(prox_rows, lr, l1, l2)))
        return
    prox = p - lr * g.astype(p.dtype)
    ctx.set_output("ParamOut", _soft_threshold(prox, lr, l1, l2))


@register_op("proximal_adagrad")
def proximal_adagrad_op(ctx: OpContext):
    """reference: operators/optimizers/proximal_adagrad_op.h:30."""
    p, g, moment = ctx.input("Param"), ctx.input("Grad"), ctx.input("Moment")
    lr = _lr(ctx).astype(p.dtype)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    sg = _sparse(g)
    if sg is not None:
        from ..core.sparse import merge_rows

        uniq, merged = merge_rows(sg.ids, sg.rows.astype(p.dtype), p.shape[0])
        m_rows = moment[uniq] + jnp.square(merged)
        prox_rows = p[uniq] - lr * merged / jnp.sqrt(m_rows)
        ctx.set_output("ParamOut",
                       p.at[uniq].set(_soft_threshold(prox_rows, lr, l1, l2)))
        ctx.set_output("MomentOut", moment.at[uniq].set(m_rows))
        return
    m_new = moment + jnp.square(g.astype(p.dtype))
    prox = p - lr * g.astype(p.dtype) / jnp.sqrt(m_new)
    ctx.set_output("ParamOut", _soft_threshold(prox, lr, l1, l2))
    ctx.set_output("MomentOut", m_new)


@register_op("ftrl")
def ftrl_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    sq_accum, lin_accum = ctx.input("SquaredAccumulator"), ctx.input("LinearAccumulator")
    lr = _lr(ctx)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    lr_power = ctx.attr("lr_power", -0.5)
    new_accum = sq_accum + jnp.square(g)
    if lr_power == -0.5:
        lin_new = lin_accum + g - (jnp.sqrt(new_accum) - jnp.sqrt(sq_accum)) / lr * p
    else:
        lin_new = lin_accum + g - (jnp.power(new_accum, -lr_power) - jnp.power(sq_accum, -lr_power)) / lr * p
    x = l1 * jnp.sign(lin_new) - lin_new
    if lr_power == -0.5:
        y = jnp.sqrt(new_accum) / lr + 2 * l2
    else:
        y = jnp.power(new_accum, -lr_power) / lr + 2 * l2
    p_new = jnp.where(jnp.abs(lin_new) > l1, x / y, jnp.zeros_like(p))
    ctx.set_output("ParamOut", p_new)
    ctx.set_output("SquaredAccumOut", new_accum)
    ctx.set_output("LinearAccumOut", lin_new)


@register_op("lamb")
def lamb_op(ctx: OpContext):
    p, g = ctx.input("Param"), ctx.input("Grad")
    m, v = ctx.input("Moment1"), ctx.input("Moment2")
    b1p, b2p = ctx.input("Beta1Pow"), ctx.input("Beta2Pow")
    lr = _lr(ctx)
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-6)
    wd = ctx.attr("weight_decay", 0.01)
    gf = g.astype(jnp.float32)
    m_new = b1 * m + (1 - b1) * gf
    v_new = b2 * v + (1 - b2) * jnp.square(gf)
    m_hat = m_new / (1 - b1p.reshape(()))
    v_hat = v_new / (1 - b2p.reshape(()))
    update = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p.astype(jnp.float32)
    w_norm = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
    u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
    ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
    ctx.set_output("ParamOut", (p.astype(jnp.float32) - lr * ratio * update).astype(p.dtype))
    ctx.set_output("Moment1Out", m_new)
    ctx.set_output("Moment2Out", v_new)
    ctx.set_output("Beta1PowOut", b1p * b1)
    ctx.set_output("Beta2PowOut", b2p * b2)

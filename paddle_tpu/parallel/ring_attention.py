"""Ring attention — sequence/context parallelism over the mesh ``sp`` axis.

The reference has no sequence parallelism (SURVEY §5.7: LoD is its only long-
sequence story). This is the TPU-native long-context design: the sequence
dim is sharded across devices; each device computes attention for its Q shard
while K/V blocks rotate around the ICI ring via ``lax.ppermute``, merging
per-block results with streaming (online) softmax — memory per device is
O(S/n · S/n) per step instead of O(S²), and comm overlaps compute around the
ring. Differentiable (lax.scan carries, not while_loop), so it is the
training path for long sequences.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.registry import OpContext, register_op
from ..monitor.device import record_collective as _record_collective

__all__ = ["ring_attention"]

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# Ring + flash composition (round 4).
#
# The ring's per-step block computation is itself an attention over
# [B, H, S_local, S_local]; with the v5e-tuned Pallas flash kernel winning
# from S~2048 (ops/attention_ops.py), the block compute should ride it too.
# Structure: an FA2-style blockwise attention with a custom VJP —
#   fwd: each ring step computes a NORMALIZED block output plus its
#        softmax stats (l, m) via the Pallas kernel's save_residuals path,
#        merged into running (acc, l, m) by online softmax; K/V rotate via
#        ppermute. Saves (q, k, v, out, lse) — O(S_local) residuals.
#   bwd: a second ring pass; per block the FA2 backward with the GLOBAL
#        lse (the Pallas bwd kernels compute p = exp(logits - m)/l, so
#        passing m=lse, l=1 yields exact global probabilities). dK/dV
#        accumulators travel around the ring WITH their block and arrive
#        home after n steps; dQ accumulates locally.
# Off-TPU (CPU tests, dryrun) the same ring/merge/backward code runs with a
# composed per-block reference, so the sp=4 math is testable on the
# virtual CPU mesh while the kernel path is exercised on real hardware.
# --------------------------------------------------------------------------


def _block_sizes_for(s_loc: int):
    from ..ops.attention_ops import _pick_block

    try:
        return _pick_block(s_loc)
    except ValueError:
        return None


@functools.lru_cache(maxsize=1)
def _ring_flash_available() -> bool:
    """The block kernels are vendored into ops/pallas_kernels/flash_attention
    .py (project-owned since r5 — a JAX upgrade can no longer change their
    semantics under us); this only checks that Pallas itself imports. TPU
    parity of the flash vs composed block paths is asserted by
    tests/test_ring_flash_parity.py."""
    import warnings

    try:
        from ..ops.pallas_kernels import flash_attention  # noqa: F401

        return True
    except Exception as e:  # pragma: no cover - pallas unavailable
        warnings.warn(
            "ring attention: Pallas flash block kernels unavailable (%s); "
            "using the composed block path" % e,
            RuntimeWarning, stacklevel=2)
        return False


def _use_flash_blocks(q, s_loc: int) -> bool:
    from ..flags import get_flag
    from ..ops.attention_ops import _on_tpu

    if not _on_tpu():
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if _block_sizes_for(s_loc) is None:
        return False
    if s_loc < int(get_flag("ring_flash_min_block")):
        return False
    return _ring_flash_available()


def _block_fwd_flash(q, k_blk, v_blk, causal, sm_scale):
    """Pallas flash over one block pair; returns (o_normalized, l, m)."""
    from ..ops.pallas_kernels import flash_attention as fa

    bq = _block_sizes_for(q.shape[2])
    bk = _block_sizes_for(k_blk.shape[2])
    return fa._flash_attention_impl(
        q, k_blk, v_blk, None, None, True, causal, sm_scale, 1, bq, bk, bk,
        False)


def _block_fwd_ref(q, k_blk, v_blk, causal, sm_scale):
    """Composed-reference block attention with the same (o, l, m) contract."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * sm_scale
    if causal:
        sl = s.shape[-1]
        cm = jnp.tril(jnp.ones((sl, sl), bool))
        s = jnp.where(cm, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.astype(q.dtype), l, m


def _block_bwd_flash(q, k_blk, v_blk, lse, do, di, causal, sm_scale):
    """Pallas FA2 block backward with global lse; returns (dq, dk, dv)."""
    from ..ops.pallas_kernels import flash_attention as fa

    bq = _block_sizes_for(q.shape[2])
    bk = _block_sizes_for(k_blk.shape[2])
    ones = jnp.ones_like(lse)
    dk, dv = fa._flash_attention_bwd_dkv(
        q, k_blk, v_blk, None, None, ones, lse, do, di,
        block_q_major=bq, block_q=bq, block_k_major=bk, block_k=bk,
        sm_scale=sm_scale, causal=causal,
        mask_value=fa.DEFAULT_MASK_VALUE, debug=False)
    dq, _ = fa._flash_attention_bwd_dq(
        q, k_blk, v_blk, None, None, ones, lse, do, di,
        block_q_major=bq, block_k_major=bk, block_k=bk,
        sm_scale=sm_scale, causal=causal,
        mask_value=fa.DEFAULT_MASK_VALUE, debug=False)
    return dq, dk, dv


def _block_bwd_ref(q, k_blk, v_blk, lse, do, di, causal, sm_scale):
    """Composed-reference FA2 block backward (p = exp(scaled logits - lse))."""
    qf = q.astype(jnp.float32)
    kf = k_blk.astype(jnp.float32)
    vf = v_blk.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
    if causal:
        sl = s.shape[-1]
        cm = jnp.tril(jnp.ones((sl, sl), bool))
        s = jnp.where(cm, s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - di[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    # stay f32: per-block contributions feed the ring's f32 accumulators
    # (a bf16 round-trip per block would grow error ~sqrt(n_blocks))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring_blockwise(axis_name, causal, sm_scale, use_flash, q, k, v):
    out, _ = _ring_blockwise_fwd(axis_name, causal, sm_scale, use_flash,
                                 q, k, v)
    return out


def _ring_blockwise_fwd(axis_name, causal, sm_scale, use_flash, q, k, v):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    block_fwd = _block_fwd_flash if use_flash else _block_fwd_ref

    def full_blk(k_blk, v_blk):
        return block_fwd(q, k_blk, v_blk, False, sm_scale)

    def diag_blk(k_blk, v_blk):
        return block_fwd(q, k_blk, v_blk, True, sm_scale)

    def skip_blk(k_blk, v_blk):
        return (jnp.zeros_like(q), jnp.zeros((b, h, s_loc), jnp.float32),
                jnp.full((b, h, s_loc), _NEG_INF, jnp.float32))

    def step(carry, i):
        k_blk, v_blk, acc, l, m = carry
        src = (my - i) % n
        if causal:
            idx = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_b, l_b, m_b = lax.switch(idx, (full_blk, diag_blk, skip_blk),
                                       k_blk, v_blk)
        else:
            o_b, l_b, m_b = full_blk(k_blk, v_blk)
        m_new = jnp.maximum(m, m_b)
        a = l * jnp.exp(m - m_new)
        bb = l_b * jnp.exp(m_b - m_new)
        acc = acc * a[..., None] / jnp.maximum(a + bb, 1e-30)[..., None] \
            + o_b.astype(jnp.float32) * (bb / jnp.maximum(a + bb, 1e-30))[..., None]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, acc, a + bb, m_new), None

    # byte accounting for the scan-body rotations: 2 buffers x n hops/step
    _record_collective("ppermute", axis_name, k, per_step_calls=n)
    _record_collective("ppermute", axis_name, v, per_step_calls=n)

    acc0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    (kf, vf, acc, l, m), _ = lax.scan(step, (k, v, acc0, l0, m0),
                                      jnp.arange(n))
    out = acc.astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, (q, k, v, out, lse)


def _ring_blockwise_bwd(axis_name, causal, sm_scale, use_flash, res, do):
    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    block_bwd = _block_bwd_flash if use_flash else _block_bwd_ref
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    do = do.astype(q.dtype)

    def full_blk(k_blk, v_blk):
        # f32 on every branch: the switch requires matching dtypes and the
        # ring accumulators are f32 (flash bwd kernels emit input dtype)
        return tuple(x.astype(jnp.float32) for x in
                     block_bwd(q, k_blk, v_blk, lse, do, di, False, sm_scale))

    def diag_blk(k_blk, v_blk):
        return tuple(x.astype(jnp.float32) for x in
                     block_bwd(q, k_blk, v_blk, lse, do, di, True, sm_scale))

    def skip_blk(k_blk, v_blk):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k_blk.shape, jnp.float32),
                jnp.zeros(v_blk.shape, jnp.float32))

    def step(carry, i):
        k_blk, v_blk, dk_blk, dv_blk, dq_acc = carry
        src = (my - i) % n
        if causal:
            idx = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            dq_b, dk_b, dv_b = lax.switch(idx, (full_blk, diag_blk, skip_blk),
                                          k_blk, v_blk)
        else:
            dq_b, dk_b, dv_b = full_blk(k_blk, v_blk)
        dq_acc = dq_acc + dq_b
        dk_blk = dk_blk + dk_b
        dv_blk = dv_blk + dv_b
        # dK/dV accumulators travel with their block; after n rotations the
        # fully-summed gradients are back on the block's home device.
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        dk_next = lax.ppermute(dk_blk, axis_name, perm)
        dv_next = lax.ppermute(dv_blk, axis_name, perm)
        return (k_next, v_next, dk_next, dv_next, dq_acc), None

    # bwd ring rotates K/V (input dtype) and travels the dK/dV
    # accumulators (f32) — 4 buffers x n hops/step
    _record_collective("ppermute", axis_name, k, per_step_calls=n)
    _record_collective("ppermute", axis_name, v, per_step_calls=n)
    _record_collective("ppermute", axis_name,
                       jax.ShapeDtypeStruct(k.shape, jnp.float32),
                       per_step_calls=n)
    _record_collective("ppermute", axis_name,
                       jax.ShapeDtypeStruct(v.shape, jnp.float32),
                       per_step_calls=n)

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    (kf, vf, dk, dv, dq), _ = lax.scan(step, (k, v, dk0, dv0, dq0),
                                       jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_blockwise.defvjp(_ring_blockwise_fwd, _ring_blockwise_bwd)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                   causal: bool = False, sm_scale: float = 1.0,
                   batch_axis: Optional[str] = None):
    """Sequence-parallel attention over logically-global [B, H, S, D] arrays
    whose S dim is sharded on ``axis_name``. Call under jit with the mesh.

    Per-block compute rides the v5e-tuned Pallas flash kernel when the local
    shard qualifies (TPU, S_local >= FLAGS_ring_flash_min_block, 128-aligned)
    and the composed reference otherwise — both through the same FA2-style
    custom-VJP ring, so backward memory is O(S_local) residuals either way
    (the pre-r4 autodiff-through-scan path saved per-step score blocks)."""

    if batch_axis is None:
        batch_axis = "data" if "data" in mesh.axis_names else None
    spec = P(batch_axis, None, axis_name, None)
    n = mesh.shape[axis_name]
    s_loc = q.shape[2] // max(n, 1)
    use_flash = _use_flash_blocks(q, s_loc)
    fn = functools.partial(_ring_blockwise, axis_name, causal, sm_scale,
                           use_flash)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


@register_op("ring_attention")
def ring_attention_op(ctx: OpContext):
    """Graph-level op: uses the trace mesh's ``sp`` axis; falls back to the
    fused single-device attention when no sp axis is available."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    causal = ctx.attr("causal", False)
    sm_scale = ctx.attr("sm_scale", 1.0)
    mesh = getattr(ctx.trace, "mesh", None)
    if mesh is None or "sp" not in mesh.axis_names:
        from ..ops.attention_ops import sdpa

        ctx.set_output("Out", sdpa(q, k, v, causal=causal, sm_scale=sm_scale))
        return
    ctx.set_output("Out", ring_attention(q, k, v, mesh, causal=causal,
                                         sm_scale=sm_scale))

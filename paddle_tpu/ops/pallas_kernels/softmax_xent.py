"""Fused softmax-with-cross-entropy Pallas TPU kernel.

Motivation (SURVEY.md §7 "custom Pallas kernels where XLA underperforms";
reference op: operators/softmax_with_cross_entropy_op.cc, which runs two
separate CUDA kernels — softmax then xent — through a [N, V] intermediate):
with a 30k+ vocabulary the XLA lowering of ``log_softmax + take_along_axis``
materializes [N, V] log-probabilities in HBM on the forward pass and reads
them back in the backward. This kernel streams each [N-tile, V-tile] block
exactly once per pass (online softmax), writing only O(N) outputs forward
(loss + logsumexp residual) and computing ``softmax - onehot`` on the fly in
the backward — HBM traffic drops from ~5·N·V to ~2·N·V elements per
fwd+bwd step.

Layout notes: grid is (N/BN, V/BV) with V minor, so the VMEM scratch
accumulators (running max / sumexp / label logit) persist across a row of V
tiles (TPU grid execution is sequential, last axis fastest). All math in
f32 on the VPU regardless of input dtype (bf16 logits upcast per tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BN = 256   # batch-tile rows (multiple of 8 for f32 sublanes)
_BV = 2048  # vocab-tile lanes (multiple of 128)

_NEG = -1e30


def _fwd_kernel(labels_ref, logits_ref, loss_ref, lse_ref, m_ref, s_ref, z_ref,
                *, smooth=0.0, v_true=0):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        s_ref[:] = jnp.zeros_like(s_ref)
        z_ref[:] = jnp.zeros_like(z_ref)

    tile = logits_ref[:].astype(jnp.float32)            # [BN, BV]
    m_prev = m_ref[:]                                    # [BN, 1]
    m_new = jnp.maximum(m_prev, jnp.max(tile, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    s_ref[:] = s_ref[:] * corr + jnp.sum(jnp.exp(tile - m_new), axis=1, keepdims=True)
    m_ref[:] = m_new

    # gather the label logit if it falls inside this vocab tile; with label
    # smoothing, fold in this tile's share of (ε/V)·Σx in the same pass
    # (loss = lse - (1-ε)·x_label - (ε/V)·Σx), masking the -1e30 pad columns
    lab = labels_ref[:].astype(jnp.int32)                # [BN, 1]
    col0 = j * tile.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + col0
    hit = cols == lab                                    # [BN, BV]
    zlab = jnp.sum(jnp.where(hit, tile, 0.0), axis=1, keepdims=True)
    if smooth:
        real = cols < v_true
        zsum = jnp.sum(jnp.where(real, tile, 0.0), axis=1, keepdims=True)
        z_ref[:] = z_ref[:] + (1.0 - smooth) * zlab + (smooth / v_true) * zsum
    else:
        z_ref[:] = z_ref[:] + zlab

    @pl.when(j == nv - 1)
    def _():
        lse = m_ref[:] + jnp.log(s_ref[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - z_ref[:]


def _bwd_kernel(labels_ref, logits_ref, lse_ref, g_ref, dlogits_ref,
                *, smooth=0.0, v_true=0):
    j = pl.program_id(1)
    tile = logits_ref[:].astype(jnp.float32)
    p = jnp.exp(tile - lse_ref[:])                       # softmax probs
    lab = labels_ref[:].astype(jnp.int32)
    col0 = j * tile.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + col0
    onehot = (cols == lab).astype(jnp.float32)
    if smooth:
        # d/dx[(1-ε)·nll + (ε/V)·Σ(-logp)] = p - (1-ε)·onehot - ε/V
        d = p - (1.0 - smooth) * onehot - (smooth / v_true)
    else:
        d = p - onehot
    dlogits_ref[:] = (g_ref[:] * d).astype(dlogits_ref.dtype)


def softmax_xent_supported(n: int, v: int, dtype) -> bool:
    """Gate: shapes and dtypes the kernel tiles cleanly."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    return n >= 8 and v >= 128


def _shrink_tiles(n, v, bn, bv):
    """Clamp requested tiles to the problem: small batches shrink the row
    tile to the next power of two (>=8), small vocabs shrink the lane tile
    to the 128-multiple cover."""
    bn = bn if n >= bn else max(8, 1 << (n - 1).bit_length())
    bv = bv if v >= bv else max(128, -(-v // 128) * 128)
    return bn, bv


def _tile_sizes(n, v):
    """(bn, bv) for this shape: tuned table -> shipped -> the hardcoded
    ``_BN``/``_BV`` defaults (paddle_tpu.tune, kernel key
    ``softmax_xent``). Tuned values are sanitized to the sublane/lane
    multiples the grid needs; the lookup never raises, so a corrupt table
    degrades to the defaults."""
    bn, bv = _BN, _BV
    try:
        from ...tune import table as _tt

        cfg, _src = _tt.lookup("softmax_xent", _tt.bucket_nv(n, v))
        if cfg:
            bn = max(8, (int(cfg.get("block_n", bn)) // 8) * 8)
            bv = max(128, (int(cfg.get("block_v", bv)) // 128) * 128)
    except Exception:
        bn, bv = _BN, _BV
    return _shrink_tiles(n, v, bn, bv)


def _pad_to(logits, labels, bn, bv):
    """Pad [N, V] logits/labels out to the (bn, bv) grid: pad vocab lanes
    carry ``_NEG`` so their exp underflows to exactly 0, pad rows are
    harmless label-0 rows sliced off by the callers."""
    n, v = logits.shape
    n_pad = -(-n // bn) * bn - n
    v_pad = -(-v // bv) * bv - v
    if v_pad:
        logits = jnp.pad(logits, ((0, 0), (0, v_pad)), constant_values=_NEG)
    if n_pad:
        logits = jnp.pad(logits, ((0, n_pad), (0, 0)), constant_values=0.0)
        labels = jnp.pad(labels, ((0, n_pad), (0, 0)), constant_values=0)
    return logits, labels, n_pad, v_pad


def _pad(logits, labels):
    n, v = logits.shape
    bn, bv = _tile_sizes(n, v)
    logits, labels, n_pad, v_pad = _pad_to(logits, labels, bn, bv)
    return logits, labels, bn, bv, n_pad, v_pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_softmax_xent(logits, labels, interpret: bool = False,
                       smooth: float = 0.0):
    """loss[N,1] = CE(softmax(logits), labels) with hard int labels [N,1];
    ``smooth`` applies label smoothing in the same streamed pass."""
    loss, _ = _fwd(logits, labels, interpret, smooth)
    return loss


def _call_fwd(logits, labels, bn, bv, interpret, smooth, v_true):
    n, v = logits.shape
    grid = (n // bn, v // bv)
    acc = lambda: pltpu.VMEM((bn, 1), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, smooth=smooth, v_true=v_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[acc(), acc(), acc()],
        interpret=interpret,
        name="softmax_xent_fwd",
    )(labels, logits)


def _fwd(logits, labels, interpret, smooth=0.0):
    n, v = logits.shape
    labels = labels.reshape(n, 1)
    plog, plab, bn, bv, n_pad, v_pad = _pad(logits, labels)
    loss, lse = _call_fwd(plog, plab, bn, bv, interpret, float(smooth), v)
    if n_pad:
        loss, lse = loss[:n], lse[:n]
    return loss, lse


def _fused_fwd(logits, labels, interpret, smooth):
    loss, lse = _fwd(logits, labels, interpret, smooth)
    return loss, (logits, labels, lse)


def _fused_bwd(interpret, smooth, res, g):
    logits, labels, lse = res
    n, v = logits.shape
    labels = labels.reshape(n, 1)
    g = g.reshape(n, 1).astype(jnp.float32)
    plog, plab, bn, bv, n_pad, v_pad = _pad(logits, labels)
    if n_pad:
        lse = jnp.pad(lse, ((0, n_pad), (0, 0)), constant_values=0.0)
        g = jnp.pad(g, ((0, n_pad), (0, 0)), constant_values=0.0)
    pn, pv = plog.shape
    grid = (pn // bn, pv // bv)
    dlogits = pl.pallas_call(
        functools.partial(_bwd_kernel, smooth=float(smooth), v_true=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pn, pv), logits.dtype),
        interpret=interpret,
        name="softmax_xent_bwd",
    )(plab, plog, lse, g)
    if n_pad or v_pad:
        dlogits = dlogits[:n, :v]
    return dlogits, None


fused_softmax_xent.defvjp(_fused_fwd, _fused_bwd)

"""Attention whose whole key length is ONE tile, forward and backward.

At a short sequence (Transformer-base trains at S = 256) a head's whole
``[S_q, S_k]`` score tile is a few hundred KB of float32: it fits the
chip's fast memory many times over, and the long path's machinery (a
running maximum over key blocks, two backward kernels that each recompute
the tile, row statistics of 128 lanes a row) only costs. Here a grid step
holds a few batch rows of a few heads and walks them, unrolled; a head's
scores, mask, softmax (float32, one pass), dropout and both products
happen on the tile in VMEM, and no ``[B, H, S, S]`` tensor is ever written
to HBM. The backward is ONE kernel: it recomputes the tile from q and k
(row maxima and sums too: two reductions are cheaper than a statistics
tensor whose rows would each need a lane tile of their own) and gives dq,
dk and dv from it.

The kernels read and write ``[B, S, H * D]`` rows, the layout a projection
leaves and the next one takes, not ``[B, H, S, D]``: a head-major copy
would be a transpose on either side of every call (13 ms of the training
step when this file's first form asked for them) and, at D = 64, rows
that each fill half a lane tile of HBM. Two heads of 64 share a tile of
128 lanes and nothing is shifted apart: see :func:`_heads_of_tile`.

Dropout is the long path's (``flash_attention._dropout_keep_at``): a hash
of the element's absolute (batch, head, q, k) coordinates and the seed, so
the forward, the backward and a reference outside the kernel regenerate
the same keep mask. It applies to the NORMALISED probabilities, scaled by
``1 / (1 - rate)``; the row's ``1 / l`` and that scale are per-row factors,
so they ride on the ``[S, D]`` side of each product and not on the tile.

``tests/test_short_attention.py`` holds output and gradients against the
composed reference at the same keep mask (interpret mode);
``tests/test_chip_compile.py`` compiles the kernels for a described v5e;
``benchmarks/diag_short_attention.py`` times them on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (DEFAULT_MASK_VALUE, NUM_LANES, NUM_SUBLANES,
                              _dropout_coords, _dropout_keep_at)

FWD_NAME = "single_tile_attention_fwd"
BWD_NAME = "single_tile_attention_bwd"

# The longest key (and query) length one tile holds. At 512 a pair's
# float32 tile is 1 MiB and the backward keeps about six of them live.
MAX_SEQ = 512

# tests set this to run the real kernel bodies on the CPU
INTERPRET = False

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def supported(q_shape, k_shape, dtype, causal) -> bool:
    """Whether the kernels take ``[B, H, S, D]`` operands of these shapes
    at all (what is worth taking is the caller's question:
    ``attention_ops._single_tile_ok``): whole lane tiles of keys and
    queries, one tile of them, and heads that fill a lane tile alone or
    in pairs."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, h, sq, d = q_shape
    sk = k_shape[2]
    if causal and sq != sk:
        return False
    return (sq % NUM_LANES == 0 and sk % NUM_LANES == 0
            and max(sq, sk) <= MAX_SEQ and d in (64, NUM_LANES)
            and (h * d) % NUM_LANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


def default_blocks(heads: int, head_dim: int):
    """(rows, heads) a grid step: one row and up to 512 lanes of its heads
    (all eight of Transformer-base's), which lets a row's mask be built
    once for them; PERF.md section 6, PR 45, has the chip's table."""
    per_tile = NUM_LANES // head_dim
    tiles = heads // per_tile
    t = max(n for n in range(1, min(tiles, 4) + 1) if tiles % n == 0)
    return 1, t * per_tile


def _mask_tile(segq_ref, segkv_ref, b, sq, sk, slack):
    """What row ``b`` of the block may attend to: the same for every head
    of the row. ``slack`` is the call's run-time word for causality, 0
    where a query sees no later key and ``sk`` where it sees them all: a
    word and not a constant of the kernel, so that a model's causal and
    plain attentions of one shape share ONE kernel text, traced once."""
    rows = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    mask = cols <= rows + slack
    if segq_ref is not None:
        qs = jnp.tile(segq_ref[b], (1, sk // NUM_LANES))   # [sq, sk]
        mask = jnp.logical_and(mask, qs == segkv_ref[b, :1])
    return mask


def _exp_tile(q, k, mask, sm_scale):
    """exp(s - rowmax) [sq, sk] and 1 / rowsum [sq, 1], float32."""
    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    if sm_scale != 1.0:
        s = s * sm_scale
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    return e, 1.0 / jnp.sum(e, axis=1, keepdims=True)


def _heads_of_tile(d):
    """The heads that share a lane tile, each with the lanes it owns (None
    where a head is the whole tile). Operands are ``[S, H * D]`` rows as
    the projections leave them, so two heads of 64 lie side by side in
    one tile of 128 lanes. Nothing is shifted: a product that must read
    ONE head takes an operand with the other head's lanes zeroed, and so
    contracts over (or fills) that head's lanes alone, at the cost the
    matrix unit pays for a 64-wide operand anyway."""
    if d == NUM_LANES:
        return [(0, None)]
    lane = lax.broadcasted_iota(jnp.int32, (1, NUM_LANES), 1)
    return [(i, (lane >= i * d) & (lane < (i + 1) * d))
            for i in range(NUM_LANES // d)]


def _only(own, x):
    """``x`` [S, 128] with the lanes outside ``own`` zeroed."""
    return x if own is None else jnp.where(own, x, jnp.zeros((), x.dtype))


# A lane tile's work is a function of VALUES (the tile's q, k and v, the
# row's mask, the hash's coordinates and three scalars), jitted on its own:
# a kernel that holds four tiles traces it once and calls it four times.
# Mosaic inlines the calls, so the kernel's code is the unrolled code; what
# is saved is Python's tracing, a third of a millisecond an operation, paid
# at every start of a program (module comment above ``_forward``).
_TILE_STATIC = ("sm_scale", "rate", "d")


@functools.partial(jax.jit, static_argnames=_TILE_STATIC)
def _fwd_tile(q, k, v, mask, coords, seed, row, head0, *, sm_scale, rate, d):
    out = None
    for i, own in _heads_of_tile(d):
        e, inv = _exp_tile(q, _only(own, k), mask, sm_scale)
        if rate > 0.0:
            keep = _dropout_keep_at(coords, rate, seed, row, head0 + i)
            e = jnp.where(keep, e, 0.0)
            inv = inv * (1.0 / (1.0 - rate))
        o = lax.dot(e.astype(v.dtype), _only(own, v),
                    preferred_element_type=jnp.float32) * inv
        out = o if out is None else out + o
    return out


@functools.partial(jax.jit, static_argnames=_TILE_STATIC)
def _bwd_tile(q, k, v, o, do, mask, coords, seed, row, head0, *, sm_scale,
              rate, d):
    do = do.astype(jnp.float32)
    # di = rowsum(do * o) a head: what the normalisation gives back
    do_o = do * o.astype(jnp.float32)
    dq = dk = dv = None
    for i, own in _heads_of_tile(d):
        qi, ki = _only(own, q), _only(own, k)
        e, inv = _exp_tile(q, ki, mask, sm_scale)
        t = jnp.sum(_only(own, do_o), axis=1, keepdims=True) * inv
        # with pd = keep * e * c the dropped probabilities (c = 1/l over
        # 1-rate, a row's factor): dv = pd.T @ do = (keep*e).T @ (c*do),
        # and ds = p * (keep * dp / (1-rate) - di) = e * (keep * ((c*do) @
        # v.T) - di / l)
        c = inv * (1.0 / (1.0 - rate)) if rate > 0.0 else inv
        doc = (_only(own, do) * c).astype(v.dtype)
        dp = lax.dot_general(doc, v, _NT, preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _dropout_keep_at(coords, rate, seed, row, head0 + i)
            ek = jnp.where(keep, e, 0.0)
            dp = jnp.where(keep, dp, 0.0)
        else:
            ek = e
        ds = (e * (dp - t)).astype(q.dtype)
        # each lies in its head's lanes and is zero in the other's
        dvi = lax.dot_general(ek.astype(v.dtype), doc, _TN,
                              preferred_element_type=jnp.float32)
        dqi = lax.dot(ds, ki, preferred_element_type=jnp.float32)
        dki = lax.dot_general(ds, qi, _TN, preferred_element_type=jnp.float32)
        dq = dqi if dq is None else dq + dqi
        dk = dki if dk is None else dk + dki
        dv = dvi if dv is None else dv + dvi
    if sm_scale != 1.0:
        dq, dk = dq * sm_scale, dk * sm_scale
    return dq, dk, dv


def _tiles(ctl_ref, q_ref, k_ref, segq_ref, segkv_ref, rate, d):
    """The walk both kernels make over their block: (row, lane tile, the
    row's mask, the hash's coordinates, seed, the row's and the tile's
    first head's coordinates of the hash)."""
    bb, sq, lanes = q_ref.shape
    sk = k_ref.shape[1]
    # program ids are read here: inside a jitted body the interpreter cannot
    b0 = pl.program_id(0) * bb
    h0 = pl.program_id(1) * (lanes // d)
    coords = _dropout_coords(0, 0, (sq, sk)) if rate > 0.0 else None
    for b in range(bb):
        mask = _mask_tile(segq_ref, segkv_ref, b, sq, sk, ctl_ref[1])
        for j in range(lanes // NUM_LANES):
            yield (b, slice(j * NUM_LANES, (j + 1) * NUM_LANES), mask, coords,
                   ctl_ref[0], b0 + b, h0 + j * (NUM_LANES // d))


def _fwd_kernel(ctl_ref, q_ref, k_ref, v_ref, segq_ref, segkv_ref, o_ref, *,
                sm_scale, rate, d):
    for b, tile, *rest in _tiles(ctl_ref, q_ref, k_ref, segq_ref, segkv_ref,
                                 rate, d):
        out = _fwd_tile(q_ref[b, :, tile], k_ref[b, :, tile],
                        v_ref[b, :, tile], *rest, sm_scale=sm_scale,
                        rate=rate, d=d)
        o_ref[b, :, tile] = out.astype(o_ref.dtype)


def _bwd_kernel(ctl_ref, q_ref, k_ref, v_ref, o_ref, do_ref, segq_ref,
                segkv_ref, dq_ref, dk_ref, dv_ref, *, sm_scale, rate, d):
    for b, tile, *rest in _tiles(ctl_ref, q_ref, k_ref, segq_ref, segkv_ref,
                                 rate, d):
        dq, dk, dv = _bwd_tile(
            q_ref[b, :, tile], k_ref[b, :, tile], v_ref[b, :, tile],
            o_ref[b, :, tile], do_ref[b, :, tile], *rest, sm_scale=sm_scale,
            rate=rate, d=d)
        dq_ref[b, :, tile] = dq.astype(dq_ref.dtype)
        dk_ref[b, :, tile] = dk.astype(dk_ref.dtype)
        dv_ref[b, :, tile] = dv.astype(dv_ref.dtype)


def control(seed, causal, sk):
    """The call's two run-time words, int32 [2]: the dropout seed (0 where
    nothing is dropped) and the causal slack (:func:`_mask_tile`)."""
    seed = jnp.zeros((), jnp.int32) if seed is None \
        else jnp.asarray(seed, jnp.int32).reshape(())
    return jnp.stack([seed, jnp.asarray(0 if causal else sk, jnp.int32)])


def _specs(q, k, seg_q, seg_kv, blocks, d):
    """Grid, and the operands and block specs every call shares."""
    batch, sq, width = q.shape
    sk = k.shape[1]
    bb, bh = blocks
    lanes = bh * d
    if width % lanes or lanes % NUM_LANES:
        raise ValueError("a block of %d heads of %d does not tile %d lanes"
                         % (bh, d, width))
    grid = (pl.cdiv(batch, bb), width // lanes)
    q_spec = pl.BlockSpec((bb, sq, lanes), lambda b, h: (b, 0, h))
    kv_spec = pl.BlockSpec((bb, sk, lanes), lambda b, h: (b, 0, h))
    ctl_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    segq = segkv = segq_spec = segkv_spec = None
    if seg_q is not None:
        # a query's id down a lane tile, a key's id across eight sublanes:
        # the layouts the tile compares without a transpose
        segq = lax.broadcast_in_dim(seg_q.astype(jnp.int32),
                                    (batch, sq, NUM_LANES), (0, 1))
        segkv = lax.broadcast_in_dim(seg_kv.astype(jnp.int32),
                                     (batch, NUM_SUBLANES, sk), (0, 2))
        segq_spec = pl.BlockSpec((bb, sq, NUM_LANES), lambda b, h: (b, 0, 0))
        segkv_spec = pl.BlockSpec((bb, NUM_SUBLANES, sk),
                                  lambda b, h: (b, 0, 0))
    return (grid, q_spec, kv_spec, ctl_spec, (segq, segq_spec),
            (segkv, segkv_spec))


def _params(n_tiles_live, q, k, blocks, d, n_io):
    """Room for the double-buffered blocks and the live float32 tiles,
    with headroom."""
    s = max(q.shape[1], k.shape[1])
    io = 2 * n_io * blocks[0] * s * blocks[1] * d * q.dtype.itemsize
    need = io + n_tiles_live * q.shape[1] * k.shape[1] * 4 + (4 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=int(min(max(need, 32 << 20), 100 << 20)))


def _nbytes(*xs):
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in xs if x is not None)


# The two calls are jitted on their own so that a program holding many
# attentions of one shape (18 in Transformer-base, causal or not by a
# run-time word) traces each kernel body and lowers it to Mosaic ONCE a
# direction, not once a layer: the trace is Python's work, paid at every
# start whether or not the executable then comes from the compile cache (an
# unrolled body is a few hundred operations; 36 of them read 40 s of a
# start on the chip's host). ``interpret`` is an argument so that the cache
# tells the interpreter's trace from the compiler's.
_STATIC = ("sm_scale", "rate", "blocks", "d", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, seg_q, seg_kv, ctl, *, sm_scale, rate, blocks, d,
             interpret):
    batch, sq, width = q.shape
    sk = k.shape[1]
    grid, q_spec, kv_spec, ctl_spec, (segq, segq_spec), \
        (segkv, segkv_spec) = _specs(q, k, seg_q, seg_kv, blocks, d)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, rate=rate, d=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[ctl_spec, q_spec, kv_spec, kv_spec, segq_spec, segkv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=FWD_NAME,
        compiler_params=_params(4, q, k, blocks, d, 4),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * width * sq * sk,
            transcendentals=batch * (width // d) * sq * sk,
            bytes_accessed=_nbytes(q, k, v, q, segq, segkv)),
    )(ctl, q, k, v, segq, segkv)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, o, do, seg_q, seg_kv, ctl, *, sm_scale, rate, blocks,
              d, interpret):
    batch, sq, width = q.shape
    sk = k.shape[1]
    grid, q_spec, kv_spec, ctl_spec, (segq, segq_spec), \
        (segkv, segkv_spec) = _specs(q, k, seg_q, seg_kv, blocks, d)
    kernel = functools.partial(_bwd_kernel, sm_scale=sm_scale, rate=rate, d=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[ctl_spec, q_spec, kv_spec, kv_spec, q_spec, q_spec,
                  segq_spec, segkv_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        name=BWD_NAME,
        compiler_params=_params(8, q, k, blocks, d, 8),
        cost_estimate=pl.CostEstimate(
            flops=10 * batch * width * sq * sk,
            transcendentals=batch * (width // d) * sq * sk,
            bytes_accessed=_nbytes(q, k, v, q, q, q, k, v, segq, segkv)),
    )(ctl, q, k, v, o, do.astype(q.dtype), segq, segkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def attention_rows(q, k, v, seg_q, seg_kv, ctl, sm_scale, rate, blocks, d):
    """The kernels' own layout: ``q`` [B, S_q, H * D], ``k`` and ``v`` [B,
    S_k, H * D], rows as the projections leave them (head ``h`` in lanes
    ``h * D`` on), and the result likewise; ``ctl`` is :func:`control`'s.
    See :func:`single_tile_attention`."""
    return _vjp_fwd(q, k, v, seg_q, seg_kv, ctl, sm_scale, rate, blocks, d)[0]


def _vjp_fwd(q, k, v, seg_q, seg_kv, ctl, sm_scale, rate, blocks, d):
    o = _forward(q, k, v, seg_q, seg_kv, ctl, sm_scale=sm_scale, rate=rate,
                 blocks=blocks, d=d, interpret=INTERPRET)
    return o, (q, k, v, o, seg_q, seg_kv, ctl)


def _int_zero(x):
    return None if x is None else np.zeros(x.shape, jax.dtypes.float0)


def _vjp_bwd(sm_scale, rate, blocks, d, res, do):
    q, k, v, o, seg_q, seg_kv, ctl = res
    dq, dk, dv = _backward(q, k, v, o, do, seg_q, seg_kv, ctl,
                           sm_scale=sm_scale, rate=rate, blocks=blocks, d=d,
                           interpret=INTERPRET)
    return dq, dk, dv, _int_zero(seg_q), _int_zero(seg_kv), _int_zero(ctl)


attention_rows.defvjp(_vjp_fwd, _vjp_bwd)


def single_tile_attention(q, k, v, seg_q, seg_kv, seed, causal, sm_scale,
                          rate, blocks=None):
    """``softmax(mask(q k^T * sm_scale))``, dropped at ``rate``, times v:
    ``q`` [B, H, S_q, D], ``k`` and ``v`` [B, H, S_k, D]; ``seg_q`` [B,
    S_q] and ``seg_kv`` [B, S_k] integer ids (a query sees the keys of its
    own id) or both None; ``seed`` an int32 [1] array (None at rate 0);
    ``blocks`` the (rows, heads) of a grid step. Batch row ``b`` of THIS
    call is coordinate ``b`` of the hash: a caller that holds a shard of a
    larger batch moves the seed by its first row (:func:`shard_seed`).

    The kernels read and write ``[B, S, H * D]`` rows. A model makes its
    ``[B, H, S, D]`` operands by splitting and transposing just such rows,
    and merges the result back the same way, so the transposes here undo
    the model's and the compiler drops both: the projections' output
    reaches the kernel as it lies, and no head-major copy (whose rows of
    64 would each fill half a lane tile of HBM) is made."""
    b, h, sq, d = q.shape
    if blocks is None:
        blocks = default_blocks(h, d)

    def rows(x):
        return jnp.swapaxes(x, 1, 2).reshape(b, x.shape[2], h * d)

    if rate > 0.0 and seed is None:
        raise ValueError("dropout_rate > 0 requires a seed")
    if causal and sq != k.shape[2]:
        raise ValueError("causal attention needs S_q == S_k")
    o = attention_rows(rows(q), rows(k), rows(v), seg_q, seg_kv,
                       control(seed, causal, k.shape[2]), sm_scale, rate,
                       tuple(blocks), d)
    return jnp.swapaxes(o.reshape(b, sq, h, d), 1, 2)


def shard_seed(seed, first_row):
    """The seed a shard hands :func:`single_tile_attention` so that its
    row ``b`` hashes as row ``first_row + b`` of the whole batch: the hash
    reads the row only as ``seed + row * 0x9E3779B9`` (modulo 2**32)."""
    s = lax.bitcast_convert_type(jnp.asarray(seed, jnp.int32), jnp.uint32)
    s = s + jnp.asarray(first_row, jnp.uint32) * jnp.uint32(0x9E3779B9)
    return lax.bitcast_convert_type(s, jnp.int32)

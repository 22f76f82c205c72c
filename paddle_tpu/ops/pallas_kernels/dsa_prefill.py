"""The prefill's attention under a ROW's own mask (DeepSeek sparse
attention: ``attention_ops.dsa_causal_attention``): every query row of one
sequence reads the rows its indexer chose, so no two rows share a mask and
the vendored flash kernel (a causal triangle, segment ids) cannot say it.

:func:`dsa_prefill_attention` is ONE Pallas call a layer
(``dsa_prefill_attention`` in a device trace): an online softmax in
float32 over key tiles, the scores of one ``[block_q, block_k]`` tile of
one head in VMEM at a time, so that no ``[H, block_q, S]`` tensor reaches
HBM. The mask comes as data, ``int8 [S, S]`` (the selection repeated over
a block's rows AND the causal triangle: the caller's, letter for letter
what the blocked form applies), and a grid step takes a GROUP of heads, so
that a mask tile is fetched and unpacked once a group. Key tiles past a
query block's last row are neither computed nor copied: their grid steps
stay on the block's last tile. ``q``, ``k``, ``v`` and the result are the
caller's ``[S, H D]`` lanes: nothing is turned.

:func:`dsa_prefill_gate` says from the shapes alone whether the chip's
compiler takes the call, and why not; ``dsa_causal_attention`` asks it on
a TPU and keeps its blocked XLA form elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dsa_prefill_attention", "dsa_prefill_gate", "KERNEL_NAME"]

KERNEL_NAME = "dsa_prefill_attention"
_LANES = 128
_BLOCK_Q = 512              # query rows a grid step: K and V stream once a
_BLOCK_K = 512              # ... query block, S / 1024 times in all
_HEADS = 4                  # heads a grid step at the most
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = 40 << 20     # what a grid step's blocks and tiles may take


def _tile(want: int, s: int, unit: int) -> int:
    """The largest multiple of ``unit`` up to ``want`` that divides ``s``
    (0 where none does)."""
    b = min(int(want), s) // unit * unit
    while b > 0 and s % b:
        b -= unit
    return b


def _head_group(n_head: int, want: int = _HEADS) -> int:
    g = min(want, n_head)
    while n_head % g:
        g -= 1
    return g


def _vmem_bytes(bq: int, bk: int, g: int, d: int, d_v: int,
                itemsize: int) -> int:
    """A grid step's VMEM: q, k, v and the result twice (the pipeline's
    two buffers), the mask tile twice, the float32 accumulator, maximum
    and sum of each head, and four float32 score tiles of temporaries."""
    blocks = (bq * g * d + bk * g * d + bk * g * d_v + bq * g * d_v) \
        * itemsize + bq * bk
    return 2 * blocks + g * bq * (d_v + 2 * _LANES) * 4 + 4 * bq * bk * 4


def dsa_prefill_gate(n_head: int, d: int, d_v: int, s: int, kpool: int,
                     itemsize: int = 2, interpret: bool = False
                     ) -> Optional[str]:
    """None when the ``dsa_prefill_attention`` kernel takes ``s`` rows of
    ``n_head`` heads of ``d`` (values ``d_v``) under a selection by blocks
    of ``kpool`` rows, else the rule that excludes it (the chip compiler's
    tiling; the interpreter is bound by the first alone)."""
    if s % kpool:
        return "%d rows are not whole blocks of %d" % (s, kpool)
    if interpret:
        return None
    if d % _LANES or d_v % _LANES:
        return ("a head's q, k [., %d] and v [., %d] must be whole %d-lane "
                "tiles" % (d, d_v, _LANES))
    if s % _LANES:
        return "%d rows are not whole %d-row tiles of the mask" % (s, _LANES)
    bq, bk = _tile(_BLOCK_Q, s, _LANES), _tile(_BLOCK_K, s, _LANES)
    need = _vmem_bytes(bq, bk, _head_group(n_head), d, d_v, itemsize)
    if need > _VMEM_BUDGET:
        return ("a grid step's blocks, %d KiB, are more than %d KiB of VMEM"
                % (need >> 10, _VMEM_BUDGET >> 10))
    return None


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, heads, d, d_v, sm_scale, low, block_q, block_k):
    """One key tile of one query block of a group of ``heads`` heads:
    ``q_ref`` [bq, heads d], ``k_ref`` [bk, heads d], ``v_ref`` [bk, heads
    dv], ``mask_ref`` int8 [bq, bk]; the running maximum and sum (a row's
    value in every lane) and the accumulator stay in VMEM over the block's
    key tiles."""
    f32 = jnp.float32
    i, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, low, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    # a tile whose first key lies past the block's last row holds nothing
    # a row may read: the causal edge
    @pl.when(j * block_k < (i + 1) * block_q)
    def _():
        ok = mask_ref[...].astype(jnp.int32) != 0
        for h in range(heads):
            sc = jax.lax.dot_general(
                q_ref[:, h * d:(h + 1) * d], k_ref[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=f32
            ) * sm_scale                                     # [bq, bk]
            sc = jnp.where(ok, sc, low)
            m_prev = m_scr[h]                                # [bq, 128]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(sc - m_next[:, :1])
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = alpha[:, :1] * acc_scr[h] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[:, h * d_v:(h + 1) * d_v],
                preferred_element_type=f32)
            m_scr[h] = m_next

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            o_ref[:, h * d_v:(h + 1) * d_v] = (
                acc_scr[h] / l_scr[h][:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "heads", "interpret"))
def dsa_prefill_attention(q, k, v, mask, sm_scale: float = 1.0, *,
                          block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K,
                          heads: int = _HEADS, interpret: bool = False):
    """Attention of ONE sequence under ``mask``: ``q``/``k`` [S, H, D],
    ``v`` [S, H, Dv], ``mask`` int8 [S, S], nonzero where row ``t`` reads
    key ``u`` (the CALLER's causal triangle in it: the kernel only skips
    the key tiles that lie wholly past a query block, which no row of a
    causal mask reads; every row reads at least itself). The softmax in
    float32 with ``attention_ops``' masking constant, the weights cast to
    ``v``'s type before the second product. Returns [S, H, Dv] in ``q``'s
    type. Jitted, so that the layers of one executable lower ONE kernel
    text."""
    from ..attention_ops import neg_inf_value

    s, n_head, d = q.shape
    d_v = v.shape[-1]
    why_not = dsa_prefill_gate(n_head, d, d_v, s, 1, q.dtype.itemsize,
                               interpret=interpret)
    if why_not is not None:
        raise ValueError(why_not)
    unit = 1 if interpret else _LANES
    bq, bk = _tile(block_q, s, unit), _tile(block_k, s, unit)
    g = _head_group(n_head, heads)

    def last(i):                # the last key tile a query block reads
        return ((i + 1) * bq - 1) // bk

    q_spec = pl.BlockSpec((bq, g * d), lambda i, hg, j: (i, hg))
    o_spec = pl.BlockSpec((bq, g * d_v), lambda i, hg, j: (i, hg))
    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, heads=g, d=d, d_v=d_v, sm_scale=float(sm_scale),
            low=neg_inf_value(jnp.float32), block_q=bq, block_k=bk),
        grid=(s // bq, n_head // g, s // bk),
        in_specs=[
            q_spec,
            pl.BlockSpec((bk, g * d),
                         lambda i, hg, j: (jnp.minimum(j, last(i)), hg)),
            pl.BlockSpec((bk, g * d_v),
                         lambda i, hg, j: (jnp.minimum(j, last(i)), hg)),
            pl.BlockSpec((bq, bk),
                         lambda i, hg, j: (i, jnp.minimum(j, last(i))))],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_head * d_v), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, _LANES), jnp.float32),
                        pltpu.VMEM((g, bq, _LANES), jnp.float32),
                        pltpu.VMEM((g, bq, d_v), jnp.float32)],
        interpret=interpret, name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(q.reshape(s, n_head * d), k.reshape(s, n_head * d),
      v.reshape(s, n_head * d_v), mask)
    return out.reshape(s, n_head, d_v)

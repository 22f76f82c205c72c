"""Row-wise sparse optimizer update Pallas TPU kernel (Adam + SGD).

Motivation (benchmarks/SPARSE_PROFILE.md §1): the SelectedRows-equivalent
sparse path spends its whole overhead in three XLA kCustom scatter fusions —
the param scatter-add plus the two Adam-moment row updates on the [V, D]
tables — which run at ~30 GB/s effective vs ~500 GB/s for a dense
elementwise pass, and at most one of the three tables wins the VMEM
prefetch lottery. XLA's scatter lowering is the cost floor; no graph-level
rewrite moves it (the §1 negative results). This kernel replaces all three
scatters with ONE pass: the merged ``(ids, rows)`` gradient drives
dynamic-slice DMAs that pull only the touched rows of param/m/v from HBM
into VMEM, the Adam math runs vectorized on the VPU, and the updated rows
DMA straight back — so the HBM traffic is 6·N·D elements (3 gathers + 3
writebacks) no matter how large V grows, at row-DMA bandwidth instead of
scatter-pass bandwidth.

Design notes (the naive one-row-per-grid-step kernel priced out at ~20 ms,
SPARSE_PROFILE §4 round-5 residue — this is the batched-DMA design it
called for):

- grid is (N / BLOCK,) with BLOCK ids per step; ids ride in SMEM via
  ``PrefetchScalarGridSpec`` scalar prefetch so row addresses are known
  before the body runs;
- per step, 3·BLOCK row gathers start back-to-back (one DMA semaphore per
  table×row), so the DMA engines pipeline the tiny 4·D-byte transfers
  instead of serializing on a wait per row;
- the tables stay unblocked in ``pl.ANY``/HBM memory space and are
  input/output aliased — untouched rows are never copied;
- merge padding ids (``core/sparse.merge_rows`` pads with ``id == V``)
  gather row 0 (clamped, read-only harmless) but their writeback is
  predicated off, reproducing XLA's OOB-scatter drop semantics.

Row width: the chip's compiler lays a ``[V, D]`` table out in 128-lane
tiles and refuses a row DMA that is not a whole number of them (``Slice
shape along dimension 1 must be aligned to tiling (128)``), so the compiled
kernel takes ``D % 128 == 0`` only. :func:`sparse_rows_gate` states the
rule and ``FLAGS_sparse_update_kernel=auto`` keeps narrower tables (DeepFM's
width 10 and 1) on the XLA scatter path.

``interpret=True`` runs the same kernel through the Pallas interpreter on
CPU — that is what tier-1 parity tests and the ``--selftest`` CLI use (the
interpreter has no tiling, so it takes any width);
tests/test_chip_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "sparse_adam_rows",
    "sparse_sgd_rows",
    "sparse_rows_gate",
    "sparse_rows_supported",
]

_BLOCK = 128  # ids per grid step = DMAs in flight per gather wave
_LANES = 128


def sparse_rows_gate(vocab: int, dim: int, dtype,
                     interpret: bool = False) -> Optional[str]:
    """None when the kernel takes this table, else the rule that excludes
    it. f32 tables (the CTR workload) always; compiled, the row width must
    fill whole 128-lane tiles (see the module docstring) — the interpreter
    has no tiling and takes any width."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return "table dtype %s is not float32" % jnp.dtype(dtype).name
    if vocab < 1 or dim < 1:
        return "empty table [%d, %d]" % (vocab, dim)
    if not interpret and dim % _LANES:
        return "row width %d is not a multiple of %d lanes" % (dim, _LANES)
    return None


def sparse_rows_supported(vocab: int, dim: int, dtype,
                          interpret: bool = False) -> bool:
    return sparse_rows_gate(vocab, dim, dtype, interpret) is None


def _row_dma(table_ref, scr_ref, sem, row, slot):
    """Async copy of one [1, D] row between an HBM table and VMEM scratch."""
    return pltpu.make_async_copy(
        table_ref.at[pl.ds(row, 1), :],
        scr_ref.at[pl.ds(slot, 1), :],
        sem,
    )


def _row_dma_out(scr_ref, table_ref, sem, slot, row):
    return pltpu.make_async_copy(
        scr_ref.at[pl.ds(slot, 1), :],
        table_ref.at[pl.ds(row, 1), :],
        sem,
    )


def _adam_kernel(ids_ref, scal_ref, p_hbm, m_hbm, v_hbm, rows_ref,
                 p_out, m_out, v_out, p_scr, m_scr, v_scr, sems,
                 *, block, vocab, beta1, beta2, epsilon):
    i = pl.program_id(0)

    def start_gather(j, _):
        row = jnp.minimum(ids_ref[i * block + j], vocab - 1)
        _row_dma(p_hbm, p_scr, sems.at[0, j], row, j).start()
        _row_dma(m_hbm, m_scr, sems.at[1, j], row, j).start()
        _row_dma(v_hbm, v_scr, sems.at[2, j], row, j).start()
        return 0

    jax.lax.fori_loop(0, block, start_gather, 0)

    def wait_gather(j, _):
        row = jnp.minimum(ids_ref[i * block + j], vocab - 1)
        _row_dma(p_hbm, p_scr, sems.at[0, j], row, j).wait()
        _row_dma(m_hbm, m_scr, sems.at[1, j], row, j).wait()
        _row_dma(v_hbm, v_scr, sems.at[2, j], row, j).wait()
        return 0

    jax.lax.fori_loop(0, block, wait_gather, 0)

    # lazy-mode Adam on the touched rows, vectorized over the whole block
    # (identical math to ops/optimizer_ops.adam_op's SelectedRows branch)
    g = rows_ref[:]
    lr_t = scal_ref[0]
    m_new = beta1 * m_scr[:] + (1.0 - beta1) * g
    v_new = beta2 * v_scr[:] + (1.0 - beta2) * jnp.square(g)
    p_scr[:] = p_scr[:] - lr_t * m_new / (jnp.sqrt(v_new) + epsilon)
    m_scr[:] = m_new
    v_scr[:] = v_new

    def start_write(j, _):
        rid = ids_ref[i * block + j]
        row = jnp.minimum(rid, vocab - 1)

        @pl.when(rid < vocab)
        def _():
            _row_dma_out(p_scr, p_out, sems.at[0, j], j, row).start()
            _row_dma_out(m_scr, m_out, sems.at[1, j], j, row).start()
            _row_dma_out(v_scr, v_out, sems.at[2, j], j, row).start()

        return 0

    jax.lax.fori_loop(0, block, start_write, 0)

    def wait_write(j, _):
        rid = ids_ref[i * block + j]
        row = jnp.minimum(rid, vocab - 1)

        @pl.when(rid < vocab)
        def _():
            _row_dma_out(p_scr, p_out, sems.at[0, j], j, row).wait()
            _row_dma_out(m_scr, m_out, sems.at[1, j], j, row).wait()
            _row_dma_out(v_scr, v_out, sems.at[2, j], j, row).wait()

        return 0

    jax.lax.fori_loop(0, block, wait_write, 0)


def _sgd_kernel(ids_ref, scal_ref, p_hbm, rows_ref, p_out, p_scr, sems,
                *, block, vocab):
    i = pl.program_id(0)

    def start_gather(j, _):
        row = jnp.minimum(ids_ref[i * block + j], vocab - 1)
        _row_dma(p_hbm, p_scr, sems.at[0, j], row, j).start()
        return 0

    jax.lax.fori_loop(0, block, start_gather, 0)

    def wait_gather(j, _):
        row = jnp.minimum(ids_ref[i * block + j], vocab - 1)
        _row_dma(p_hbm, p_scr, sems.at[0, j], row, j).wait()
        return 0

    jax.lax.fori_loop(0, block, wait_gather, 0)

    p_scr[:] = p_scr[:] - scal_ref[0] * rows_ref[:]

    def start_write(j, _):
        rid = ids_ref[i * block + j]
        row = jnp.minimum(rid, vocab - 1)

        @pl.when(rid < vocab)
        def _():
            _row_dma_out(p_scr, p_out, sems.at[0, j], j, row).start()

        return 0

    jax.lax.fori_loop(0, block, start_write, 0)

    def wait_write(j, _):
        rid = ids_ref[i * block + j]
        row = jnp.minimum(rid, vocab - 1)

        @pl.when(rid < vocab)
        def _():
            _row_dma_out(p_scr, p_out, sems.at[0, j], j, row).wait()

        return 0

    jax.lax.fori_loop(0, block, wait_write, 0)


def _block_size(block, n_ids, dim=None):
    """ids-per-grid-step, shrunk for small batches and rounded up to the
    f32 sublane multiple so the VMEM scratch tiles cleanly.

    ``block=None`` (the kernel entry points' default) consults the tuned
    config table first (paddle_tpu.tune: shape-bucket + device_kind, with
    the shipped v5e 128-id seed), falling back to the hardcoded ``_BLOCK``
    — an explicit integer is always honored verbatim (modulo the rounding
    below), which is what keeps the autotuner's own sweep from looping
    through the table it is writing. The lookup never raises; a corrupt
    table logs once inside tune.table and lands here as the default."""
    if block is None:
        block = _BLOCK
        try:
            from ...tune import table as _tt

            cfg, _src = _tt.lookup(
                "sparse_adam", _tt.bucket_rows(n_ids, dim or 1))
            if cfg and int(cfg.get("block", 0)) > 0:
                block = int(cfg["block"])
        except Exception:
            pass
    b = min(int(block), max(8, n_ids))
    return -(-b // 8) * 8


def _pad_ids_rows(ids, rows, vocab, block):
    """Pad (ids, rows) to a multiple of ``block``; pad ids carry ``vocab``
    (the merge_rows invalid index) so the kernel's writeback predicate
    drops them."""
    n = ids.shape[0]
    npad = -(-n // block) * block - n
    if npad:
        ids = jnp.concatenate(
            [ids, jnp.full((npad,), vocab, ids.dtype)])
        rows = jnp.concatenate(
            [rows, jnp.zeros((npad,) + rows.shape[1:], rows.dtype)])
    return ids, rows


def sparse_adam_rows(param, moment1, moment2, ids, rows, lr_t,
                     beta1=0.9, beta2=0.999, epsilon=1e-8,
                     interpret: bool = False, block=None):
    """One-kernel lazy Adam over merged sparse rows.

    ``param``/``moment1``/``moment2``: [V, D] f32 tables (aliased in/out —
    untouched rows never move). ``ids``: [N] int32 merged unique row ids,
    padded entries == V. ``rows``: [N, D] f32 merged gradient rows.
    ``lr_t``: bias-corrected scalar step size ``lr·sqrt(1-β2^t)/(1-β1^t)``
    (the same folding adam_op does). ``block=None`` = tuned-table lookup
    with the hardcoded 128 fallback (see ``_block_size``). Returns
    (param, m, v) updated.
    """
    vocab, dim = param.shape
    ids = ids.astype(jnp.int32)
    rows = rows.astype(jnp.float32)
    block = _block_size(block, ids.shape[0], dim)
    ids, rows = _pad_ids_rows(ids, rows, vocab, block)
    n = ids.shape[0]
    scal = jnp.asarray(lr_t, jnp.float32).reshape((1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # param
            pl.BlockSpec(memory_space=pl.ANY),   # moment1
            pl.BlockSpec(memory_space=pl.ANY),   # moment2
            pl.BlockSpec((block, dim), lambda i, *_: (i, 0)),  # grad rows
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, dim), jnp.float32),
            pltpu.VMEM((block, dim), jnp.float32),
            pltpu.VMEM((block, dim), jnp.float32),
            pltpu.SemaphoreType.DMA((3, block)),
        ],
    )
    kernel = functools.partial(
        _adam_kernel, block=block, vocab=vocab,
        beta1=float(beta1), beta2=float(beta2), epsilon=float(epsilon))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(param.shape, param.dtype),
            jax.ShapeDtypeStruct(moment1.shape, moment1.dtype),
            jax.ShapeDtypeStruct(moment2.shape, moment2.dtype),
        ],
        # operand order incl. scalar-prefetch args: ids(0) scal(1) p(2)
        # m(3) v(4) rows(5)
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="sparse_adam_rows",
    )(ids, scal, param, moment1, moment2, rows)


def sparse_sgd_rows(param, ids, rows, lr, interpret: bool = False,
                    block=None):
    """One-kernel SGD over merged sparse rows: rows of ``param`` at ``ids``
    get ``-lr·rows``; padded ids (== V) are dropped. ``block=None`` =
    tuned-table lookup (see ``_block_size``). Returns param."""
    vocab, dim = param.shape
    ids = ids.astype(jnp.int32)
    rows = rows.astype(jnp.float32)
    block = _block_size(block, ids.shape[0], dim)
    ids, rows = _pad_ids_rows(ids, rows, vocab, block)
    n = ids.shape[0]
    scal = jnp.asarray(lr, jnp.float32).reshape((1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block, dim), lambda i, *_: (i, 0)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((block, dim), jnp.float32),
            pltpu.SemaphoreType.DMA((1, block)),
        ],
    )
    kernel = functools.partial(_sgd_kernel, block=block, vocab=vocab)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(param.shape, param.dtype)],
        input_output_aliases={2: 0},  # ids(0) scal(1) p(2) rows(3)
        interpret=interpret,
        name="sparse_sgd_rows",
    )(ids, scal, param, rows)
    return out


# -- selftest -----------------------------------------------------------------


def _selftest() -> int:
    """CPU interpret-mode parity vs the XLA scatter formulation — the CI
    smoke next to tools/dump_metrics --selftest (<5 s)."""
    import time

    t0 = time.time()
    rng = np.random.RandomState(0)
    vocab, dim, n = 1000, 10, 96
    raw_ids = rng.randint(0, vocab, (n,)).astype(np.int32)
    raw_ids[: n // 4] = raw_ids[n // 4 : n // 2]  # duplicates
    raw_rows = rng.randn(n, dim).astype(np.float32)

    from ...core.sparse import merge_rows

    uniq, merged = merge_rows(jnp.asarray(raw_ids), jnp.asarray(raw_rows),
                              vocab)
    p = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
    m = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.1)
    v = jnp.asarray(np.abs(rng.randn(vocab, dim)).astype(np.float32) * 0.1)
    b1, b2, eps, lr_t = 0.9, 0.999, 1e-8, 0.01

    # scatter reference (adam_op's SelectedRows branch verbatim)
    m_rows = b1 * m[uniq] + (1 - b1) * merged
    v_rows = b2 * v[uniq] + (1 - b2) * jnp.square(merged)
    ref_p = p.at[uniq].add(-(lr_t * m_rows / (jnp.sqrt(v_rows) + eps)))
    ref_m = m.at[uniq].add(m_rows - m[uniq])
    ref_v = v.at[uniq].add(v_rows - v[uniq])

    k_p, k_m, k_v = sparse_adam_rows(p, m, v, uniq, merged, lr_t,
                                     b1, b2, eps, interpret=True)
    for name, a, b in (("param", ref_p, k_p), ("m", ref_m, k_m),
                       ("v", ref_v, k_v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg="adam %s mismatch" % name)

    ref_sgd = p.at[uniq].add(-0.5 * merged)
    k_sgd = sparse_sgd_rows(p, uniq, merged, 0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(ref_sgd), np.asarray(k_sgd),
                               rtol=1e-6, atol=1e-6, err_msg="sgd mismatch")
    print("sparse_adam selftest OK (%.2fs): adam+sgd row-DMA kernel == "
          "scatter path on [%d,%d], %d ids (dups + merge padding)"
          % (time.time() - t0, vocab, dim, n))
    return 0


if __name__ == "__main__":
    import sys

    if "--selftest" in sys.argv:
        sys.exit(_selftest())
    print("usage: python -m paddle_tpu.ops.pallas_kernels.sparse_adam "
          "--selftest")
    sys.exit(2)

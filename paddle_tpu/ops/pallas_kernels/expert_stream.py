"""The routed experts' feed-forward over rows sorted by expert, fused three
deep and bound by the weight stream: ``ragged_dot`` x 3 as ONE kernel.

``ops/moe_ops.py`` sorts a pass's (token, expert) pairs by expert and
computes, for the rows ``[start_e, start_e + sizes[e])`` of each expert,

    out = (act(xs Wg_e) * (xs Wu_e)) Wd_e

or, of an UNGATED expert (``wg`` None: two matrices, Nemotron's relu^2
experts), ``out = act(xs Wu_e) Wd_e``: the same kernel without the gate's
operand, product and scratch, reading two matrices an expert. The up
matrices may be stored TRANSPOSED (``transposed_up``: ``[E, f, d]``, an
expert's ``Wu_e^T``), which is how a width ``f`` of no whole lane tiles is
to be stored: the chip's compiler lays a ``[E, d, 1856]`` array out with
``d`` minor by itself and puts a transposing copy of ALL of it in front of
every call that wants it row-major; stored ``[E, 1856, d]`` it is read as
it lies, in blocks ``[f, td]`` multiplied as ``xs Wu_e^T``.

A decode pass holds a few rows an expert (2.8 in the served cells), so its
time is the bytes of the TOUCHED experts' matrices over the HBM rate, and
the arithmetic is a few percent of that. The kernel is built around the
stream:

* the touched experts are compacted in front by a scalar-prefetched list
  (expert, first row, rows); the grid runs over all ``E`` experts and the
  steps past the last touched one stay on the block the step before held
  (no DMA) and skip their body, so an untouched expert costs a grid step
  and no bytes;
* the weights are read AS STORED, ``[E, d, f]`` and ``[E, f, d]``, in
  blocks of whole rows of a matrix (long contiguous runs), double-buffered
  by the pipeline against the block before: a matrix of at most
  ``_BLOCK_BYTES`` whole, so that an expert is one grid step; a larger one
  (Kimi-K2's 29 MB) in row blocks, ``[td, f]`` of ``Wg``/``Wu``
  accumulating gate and up in float32 scratch, then ``[tf, d]`` of ``Wd``
  accumulating the result; ``Wd``'s first block rides the last gate step,
  and its index map holds the block before until then, so every step
  fetches only what the next one needs. An expert width ``f`` that is not
  whole lane tiles (Nemotron's 1,856 = 14.5 x 128) is taken as it is: a
  ``[td, f]`` block's minor dimension is the array's whole width, and
  ``Wd``, whose ROWS then divide into no lane-aligned blocks of the
  activation's lanes, is read in COLUMN blocks ``[f, tc]`` instead, each
  the whole contraction for ``tc`` lanes of the result (no accumulator);
* an expert's rows are read as whole row tiles of ``xs`` on a lattice of
  ``tile`` rows (32 or 64, from the rows an even router would send an
  expert; a tile's products must end before the next block's DMA does,
  and 128 rows do not), masked at both ends where the result is written;
* bf16 operands, float32 accumulation; gate and up stay float32 until
  ``act(gate) * up`` is rounded ONCE to the operands' type for the product
  with ``Wd``; the result is rounded once more, as ``ragged_dot``'s is.

* an activation with numbers of its own for every expert (``act_params``
  [E, P]: PolyNorm's weights and bias) reads them as scalars from SMEM by
  the expert's id, beside the prefetched lists; it is applied to full-
  width float32 rows ``[tile, f]`` after the last gate block, so one that
  reduces over the expert's width needs no tiling of its own.

Rows past the last group come back 0. The kernel's name in a device trace
is ``ragged_dot_stream``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["expert_stream_ffn", "expert_stream_gate", "expert_stream_plan",
           "expert_ffn_reference", "KERNEL_NAME"]

KERNEL_NAME = "ragged_dot_stream"
_LANES = 128
_SUBLANES = 16             # rows of a bfloat16 tile
_BLOCK_BYTES = 8 << 20     # a matrix up to this is one block, whole
_VMEM_CAP = 100 << 20      # of a v5e core's 128 MiB
_VMEM_SLACK = 12 << 20     # the products' temporaries beside the buffers


def _row_tile(m: int, e: int) -> int:
    """Rows of a tile: 32, or 64 where an even router sends an expert over
    16 rows. Never 128: at Kimi-K2's widths a tile of 128 rows was measured
    2 to 3.7 times slower than one of 32 or 64, which read alike (its
    products outlast the next block's DMA: PERF.md, PR 42)."""
    return 32 if 2 * m <= 32 * e else 64


def _split(rows: int, cols: int, itemsize: int, limit: int) -> Optional[int]:
    """In how many row blocks a ``[rows, cols]`` matrix is read: the fewest
    equal blocks of whole lane tiles of rows within ``limit`` bytes."""
    for n in range(1, rows // _LANES + 1):
        if rows % (n * _LANES) == 0 \
                and rows // n * cols * itemsize <= limit:
            return n
    return None


def expert_stream_plan(m: int, e: int, d: int, f: int, dtype,
                       gated: bool = True) -> dict:
    """The static choices for a geometry: ``tile`` rows, the padded row
    count ``rows``, ``nkd`` blocks of ``Wg``/``Wu`` and ``nkf`` of ``Wd``
    (blocks of at most ``_BLOCK_BYTES``, halved until the buffers fit;
    ``down_cols``: ``Wd``'s are COLUMN blocks, where ``f`` is not whole
    lane tiles) and the ``vmem`` limit it asks for; ``fits`` says whether
    such blocks exist. ``gated`` False: two matrices an expert."""
    size = jnp.dtype(dtype).itemsize
    tile = _row_tile(m, e)
    rows = -(-m // tile) * tile
    rows_io = 2 * 2 * rows * d * size
    ups = 2 if gated else 1
    scratch = rows * (ups * f * 4 + f * size + d * 4)
    down_cols = f % _LANES != 0
    limit = _BLOCK_BYTES
    while True:
        nkd = _split(d, f, size, limit)
        # ``_split`` over Wd's transpose: blocks of whole lane tiles of
        # its COLUMNS, ``f`` values each
        nkf = _split(d, f, size, limit) if down_cols \
            else _split(f, d, size, limit)
        split = nkd is not None and nkf is not None
        nkd, nkf = nkd or 1, nkf or 1
        need = (2 * (ups * (d // nkd) * f + f * d // nkf) * size
                + rows_io + scratch)
        fits = split and need + _VMEM_SLACK <= _VMEM_CAP
        if fits or not split:
            break
        limit //= 2
    return {"tile": tile, "rows": rows, "nkd": nkd, "nkf": nkf, "fits": fits,
            "down_cols": down_cols,
            "vmem": min(_VMEM_CAP, need + _VMEM_SLACK)}


def expert_stream_gate(m: int, e: int, d: int, f: int, dtype,
                       interpret: bool = False, gated: bool = True
                       ) -> Optional[str]:
    """None when the compiled kernel takes this geometry, else the rule
    that excludes it. The shape rules are the chip compiler's tiling and
    do not bind the interpreter."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "operand dtype %s is not float32/bfloat16" % dt.name
    if interpret:
        return None
    if d % _LANES or f % _SUBLANES:
        return ("hidden size %d must be a multiple of %d and expert width "
                "%d of %d" % (d, _LANES, f, _SUBLANES))
    if not expert_stream_plan(m, e, d, f, dt, gated)["fits"]:
        return ("%d rows of [%d, %d] experts: no row blocks of whole lane "
                "tiles whose buffers fit %d bytes of VMEM"
                % (m, d, f, _VMEM_CAP))
    return None


def _kernel(ids_ref, start_ref, count_ref, n_ref, xs_ref, *rest, tile, nkd,
            nkf, activation, precision, n_params, gated, down_cols,
            transposed_up):
    if gated:
        wg_ref, *rest = rest
    wu_ref, wd_ref, *rest = rest
    if n_params:        # the activation's numbers of expert ids[i], in SMEM
        p_ref, *rest = rest
        act = lambda g: activation(
            g, [p_ref[ids_ref[i] * n_params + k] for k in range(n_params)])
    else:
        act = activation
    if gated:
        out_ref, g_ref, u_ref, h_ref, y_ref = rest
    else:               # the one product's float32 rows: u_ref alone
        out_ref, u_ref, h_ref, y_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)   # the interpreter has none
    td = xs_ref.shape[1] // nkd                 # in a branch
    tf = wd_ref.shape[1]
    tc = wd_ref.shape[2]
    first, rows_e = start_ref[i], count_ref[i]
    lo = first // tile
    n_tiles = (first + rows_e + tile - 1) // tile - lo

    def dot(a, b):
        return jnp.dot(a, b, precision=precision,
                       preferred_element_type=jnp.float32)

    def up(a, b):       # a Wu, with Wu as stored or as its transpose
        if not transposed_up:
            return dot(a, b)
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32)

    def over_tiles(body):
        def one(t, carry):
            at = pl.multiple_of((lo + t) * tile, tile)
            body(at, pl.ds(at, tile))
            return carry

        jax.lax.fori_loop(0, n_tiles, one, 0)

    def when(step):
        # one step an expert: no branch at all
        return (lambda fn: fn()) if nkd + nkf == 2 else pl.when(j == step)

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        for kd in range(nkd):
            def gate_up(at, rows, kd=kd):
                x = xs_ref[rows, kd * td:(kd + 1) * td]
                if gated:
                    g = up(x, wg_ref[0])
                u = up(x, wu_ref[0])
                if gated and kd:
                    g = g + g_ref[rows, :]
                if kd:
                    u = u + u_ref[rows, :]
                if kd == nkd - 1:
                    # the one rounding between the two products
                    h_ref[rows, :] = (act(g) * u if gated else act(u)
                                      ).astype(h_ref.dtype)
                else:
                    if gated:
                        g_ref[rows, :] = g
                    u_ref[rows, :] = u

            when(kd)(functools.partial(over_tiles, gate_up))

        for kf in range(nkf):
            def down(at, rows, kf=kf):
                if down_cols:   # the whole contraction for tc result lanes
                    cols = slice(kf * tc, (kf + 1) * tc)
                    y = dot(h_ref[rows, :], wd_ref[0])
                else:
                    cols = slice(None)
                    y = dot(h_ref[rows, kf * tf:(kf + 1) * tf], wd_ref[0])
                    if kf:
                        y = y + y_ref[rows, :]
                    if kf < nkf - 1:
                        y_ref[rows, :] = y
                        return
                # the tile's rows of OTHER experts keep what they hold
                r = at + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
                mine = (r >= first) & (r < first + rows_e)
                out_ref[rows, cols] = jnp.where(
                    mine, y, out_ref[rows, cols].astype(jnp.float32)
                ).astype(out_ref.dtype)

            when(nkd - 1 + kf)(functools.partial(over_tiles, down))


def _touched_first(sizes):
    """The scalar-prefetched lists: the touched experts in front, in
    expert order (``ids``, their first rows and row counts), the places
    past the last of them repeating it with no rows, and how many there
    are."""
    e = sizes.shape[0]
    touched = sizes > 0
    n = jnp.sum(touched).astype(jnp.int32)
    ids = jnp.argsort(jnp.logical_not(touched), stable=True).astype(jnp.int32)
    place = jnp.arange(e, dtype=jnp.int32)
    ids = jnp.where(place < n, ids, ids[jnp.maximum(n - 1, 0)])
    starts = (jnp.cumsum(sizes) - sizes)[ids]
    counts = jnp.where(place < n, sizes[ids], 0)
    return ids, starts.astype(jnp.int32), counts.astype(jnp.int32), \
        n.reshape(1)


@functools.partial(jax.jit, static_argnames=("activation", "interpret",
                                             "transposed_up"))
def expert_stream_ffn(xs, wg, wu, wd, sizes, activation=jax.nn.relu, *,
                      act_params=None, transposed_up: bool = False,
                      interpret: bool = False):
    """``(act(xs Wg_e) * (xs Wu_e)) Wd_e`` for the rows of each group, or
    ``act(xs Wu_e) Wd_e`` where ``wg`` is None (ungated experts).

    ``xs`` [M, d] sorted by expert; ``wg``/``wu`` [E, d, f] (``[E, f, d]``,
    each matrix transposed, with ``transposed_up``), ``wd`` [E, f, d];
    ``sizes`` [E] int32, the rows of each group (``sum(sizes) <= M``; the
    rows past the last group come back 0). ``act_params`` [E, P]: the
    activation is then ``act(gate [tile, f] float32, p)`` with ``p`` the P
    scalars of the rows' expert. Returns [M, d] in ``xs``'s type:
    ``jax.lax.ragged_dot``'s contract, three products deep.

    Jitted so that a model's layers share ONE trace and ONE lowering of
    the kernel in their executable (32 call sites lower in 0.07 s for
    1.6 s apart: a cell's set-up, PERF.md, PR 42)."""
    m, d = xs.shape
    e, f, _ = wd.shape
    gated = wg is not None
    ups = [wg, wu] if gated else [wu]
    up_shape = (e, f, d) if transposed_up else (e, d, f)
    if any(w.shape != up_shape for w in ups) \
            or wd.shape != (e, f, d) or sizes.shape != (e,):
        raise ValueError("xs %s, wg %s, wu %s, wd %s, sizes %s do not fit"
                         % (xs.shape, wg.shape if gated else None, wu.shape,
                            wd.shape, sizes.shape))
    if not gated and act_params is not None:
        raise ValueError("an ungated expert's activation has no numbers of "
                         "its own (act_params)")
    why = expert_stream_gate(m, e, d, f, xs.dtype, interpret=interpret,
                             gated=gated)
    if why is not None:
        raise ValueError("expert_stream_ffn: " + why)
    plan = expert_stream_plan(m, e, d, f, xs.dtype, gated)
    tile, rows, nkd, nkf = (plan[k] for k in ("tile", "rows", "nkd", "nkf"))
    down_cols = plan["down_cols"]
    steps = nkd + nkf - 1
    td = d // nkd
    down_shape = (1, f, d // nkf) if down_cols else (1, f // nkf, d)
    ids, starts, counts, n = _touched_first(sizes.astype(jnp.int32))

    def step(i, j, n_ref):
        # a place past the last touched expert stays where that one ended
        return jnp.where(i < n_ref[0], j, steps - 1)

    def up_block(i, j, ids_ref, s_ref, c_ref, n_ref):
        blk = jnp.minimum(step(i, j, n_ref), nkd - 1)
        return (ids_ref[i], 0, blk) if transposed_up else (ids_ref[i], blk, 0)

    def down_block(i, j, ids_ref, s_ref, c_ref, n_ref):
        jj = step(i, j, n_ref)
        early = jj < nkd - 1     # still the block of the expert before
        who = jnp.where(early, ids_ref[jnp.maximum(i - 1, 0)], ids_ref[i])
        blk = jnp.where(early, jnp.where(i > 0, nkf - 1, 0), jj - (nkd - 1))
        return (who, 0, blk) if down_cols else (who, blk, 0)

    whole = lambda i, j, *_: (0, 0)
    n_params = 0 if act_params is None else act_params.shape[1]
    if n_params and act_params.shape[0] != e:
        raise ValueError("act_params %s names other than the %d experts"
                         % (act_params.shape, e))
    kernel = functools.partial(
        _kernel, tile=tile, nkd=nkd, nkf=nkf, activation=activation,
        n_params=n_params, gated=gated, down_cols=down_cols,
        transposed_up=transposed_up,
        precision=(jax.lax.Precision.HIGHEST
                   if xs.dtype == jnp.float32 else None))
    smem = ([pl.BlockSpec(memory_space=pltpu.SMEM)] if n_params else [])
    numbers = ([act_params.astype(jnp.float32).reshape(-1)]
               if n_params else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(e, steps),
        in_specs=[pl.BlockSpec((rows, d), whole)]
        + [pl.BlockSpec((1, f, td) if transposed_up else (1, td, f),
                        up_block)] * len(ups)
        + [pl.BlockSpec(down_shape, down_block)] + smem,
        out_specs=pl.BlockSpec((rows, d), whole),
        scratch_shapes=[pltpu.VMEM((rows, f), jnp.float32)] * len(ups)
        + [pltpu.VMEM((rows, f), xs.dtype),
           pltpu.VMEM((rows, d), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan["vmem"]),
        cost_estimate=pl.CostEstimate(
            flops=2 * (len(ups) + 1) * m * d * f, transcendentals=0,
            bytes_accessed=((len(ups) + 1) * min(e, m) * d * f + 2 * m * d)
            * xs.dtype.itemsize),
        interpret=interpret, name=KERNEL_NAME,
    )(ids, starts, counts, n,
      jnp.pad(xs, ((0, rows - m), (0, 0))) if rows > m else xs, *ups, wd,
      *numbers)
    return out[:m] if rows > m else out


def expert_ffn_reference(xs, wg, wu, wd, sizes, activation=jax.nn.relu,
                         act_params=None, transposed_up: bool = False):
    """The plain statement in float32, an expert at a time: every row
    through that expert's three matrices, or two where ``wg`` is None
    (and, with ``act_params`` [E, P], the activation given that expert's P
    numbers), nothing rounded between, kept where the row is the
    expert's; the rows past the last group 0."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    x = xs.astype(f32)
    if transposed_up:
        wu = jnp.swapaxes(wu, 1, 2)
        wg = None if wg is None else jnp.swapaxes(wg, 1, 2)
    ends = jnp.cumsum(sizes)
    row = jnp.arange(xs.shape[0])[:, None]

    numbers = (jnp.zeros((sizes.shape[0], 0), f32) if act_params is None
               else act_params.astype(f32))

    def one(y, ew):
        lo, hi_, g_w, u_w, d_w, p = ew
        u = jnp.dot(x, u_w.astype(f32), precision=hi)
        if wg is None:
            h = activation(u)
        else:
            g = jnp.dot(x, g_w.astype(f32), precision=hi)
            h = u * (activation(g) if act_params is None
                     else activation(g, [p[k] for k in range(p.shape[0])]))
        mine = jnp.dot(h, d_w.astype(f32), precision=hi)
        return jnp.where((row >= lo) & (row < hi_), mine, y), None

    y, _ = jax.lax.scan(one, jnp.zeros(xs.shape, f32),
                        (ends - sizes, ends, wu if wg is None else wg, wu,
                         wd, numbers))
    return y

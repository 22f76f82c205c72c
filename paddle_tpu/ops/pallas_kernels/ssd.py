"""Mamba-2's recurrence (state-space duality, SSD; arXiv:2405.21060): a
linear recurrence with ONE scalar decay a head and an outer-product write
with no erase term. For head ``h`` of group ``g = h // (H / G)`` with
state ``S`` [N, P] (``dk = N`` the state size, ``dv = P`` the head's
channels; zero at a request's start) and a token's ``x`` [P] (the head's
input already times its step ``dt``), ``B``, ``C`` [N] (shared by the
heads of a group) and log-decay ``a = dt A`` (<= 0):

    S_t = exp(a_t) S_{t-1} + B_t x_t^T
    y_t = S_t^T C_t

(the ``D x`` skip, the step's softplus and the gated norm are the
model's: ``models/falcon_h1.py``). KDA's recurrence
(``pallas_kernels/kda.py``) decays a CHANNEL and erases along the key
before it writes; this one cannot be said in its kernels' bodies, and its
state is ``256 x 128`` a head where KDA's is ``128 x 128``. The forms here
follow that module's pattern:

* :func:`ssd_recurrence`: the equations token by token under
  ``lax.scan``: the tests' yardstick.
* :func:`ssd_state_step`: ONE decode step for every live slot of one
  layer, a Pallas kernel (``ssd_state_step`` in a device trace). The
  states ``[n_layer, slots, H, N, P]`` float32, in the cache's own order,
  are aliased in and out: a live slot's state streams through the chip
  once, a GROUP of heads a grid step (2 MiB at the published geometry, so
  ``B`` and ``C`` ride once a step as columns beside the heads' decays),
  all on the vector unit in float32: 2 x 4 bytes a state value against
  five operations, so the HBM rate bounds it. Live slots come first by a
  scalar-prefetched order and the steps past the last stay on its block:
  a slot that is not ``active`` is neither read nor written.
  :func:`ssd_state_step_xla` is the same step in plain XLA (every slot
  computed, the inactive put back): the CPU tests' second path and where
  :func:`ssd_state_step_gate` refuses a geometry.
* :func:`ssd_chunk_scan`: the prefill's chunk-wise form at chunks of
  ``CHUNK`` (128, the published ``mamba_chunk_size``). With ``g_t`` the
  running sum of ``a`` inside a chunk,

      Y = ((C B^T) * L) X + (e^g C) S_0,   L_ij = e^(g_i - g_j)  (j <= i)
      S_C = e^(g_C) S_0 + (B e^(g_C - g))^T X

  a decay-masked product and no triangular solve; every exponent is <= 0.
  ``C B^T`` is made once a GROUP and shared by its heads. On a TPU where
  :func:`ssd_chunk_scan_gate` takes the geometry the whole scan is ONE
  Pallas call a layer (``ssd_chunk_scan`` in a device trace: a grid of
  groups x chunks, a group's float32 state resident in VMEM from its
  first chunk to its last, the rows read as the caller's ``[T, H P]``
  lanes: nothing is turned but ``B``); elsewhere blocked ``jax.numpy``
  under the ``lax.scan`` that carries the ``[H, N, P]`` float32 state
  (:func:`ssd_chunk_scan_xla`, scope ``ssd_chunk_scan``).
  ``ssd/scan_calls.kernel`` and ``.blocked`` count the choice once a call
  of a traced program. Every product is float32 at the highest matmul
  precision: the state is float32 and only as good as what is written
  into it.

A head NARROWER than a lane tile (Nemotron-3's ``P`` = 64 over ``N`` =
128) is kept PACKED: the cache's state is ``[H / k, N, k P]`` with ``k =
128 / P`` consecutive heads of one group side by side in the lanes
(:func:`pack_state`, :func:`unpack_state`, :func:`state_shape`), so a
slot's layer is ``H N P`` float32 values in HBM and not twice that in
padding, and both kernels take it: the step kernel reads the pair's decays
as a ROW beside ``x`` (a decay a lane) and a whole slot a grid step (every
group's ``B`` and ``C`` as columns); the scan kernel multiplies a packed
pair's ``[C, 128]`` rows by each head's decay-masked ``C B^T`` and keeps
each head's own lanes (the MXU is 128 lanes wide either way). A head of
whole lane tiles (Falcon-H1's 128) is ``k`` = 1: the state, the blocks and
the operations as they were. What the callers hand in and get back is
``[H, N, P]`` whatever the cache keeps.

A position that must not touch the state (a prompt's padding) is given
``a`` = 0 and ``x`` = 0 by the caller.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_recurrence", "ssd_chunk_scan", "ssd_chunk_scan_xla",
           "ssd_chunk_scan_kernel", "ssd_chunk_scan_gate", "ssd_state_step",
           "ssd_state_step_xla", "ssd_state_step_gate", "state_pack",
           "state_shape", "pack_state", "unpack_state", "KERNEL_NAME",
           "SCAN_NAME", "CHUNK"]

KERNEL_NAME = "ssd_state_step"
SCAN_NAME = "ssd_chunk_scan"
CHUNK = 128
_LANES = 128
_HEAD_BLOCK = 16            # heads a grid step at the most: a group's
_BLOCK_BYTES = 2 << 20      # ... and their float32 states, at the most
_VMEM_LIMIT = 48 << 20      # both kernels: blocks of 2 MiB, in and out, twice
_HI = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    # asked through attention_ops, so that what steers its kernels onto a
    # described chip (tests/test_chip_compile.py) steers these too
    from .. import attention_ops

    return attention_ops._on_tpu()


def state_pack(d_head: int) -> int:
    """Heads side by side in a lane tile of the cache's state: 1 for a head
    of whole lane tiles (or one that divides none), ``128 / d_head``
    else."""
    return _LANES // d_head if d_head < _LANES and _LANES % d_head == 0 else 1


def state_shape(n_head: int, n_state: int, d_head: int, n_group: int = 1):
    """A slot's state in one layer AS THE CACHE KEEPS IT: ``[H / k, N, k
    P]`` with ``k`` = :func:`state_pack` where a group's heads divide by
    it, else ``[H, N, P]``."""
    k = state_pack(d_head)
    if n_head % n_group or (n_head // n_group) % k:
        k = 1
    return (n_head // k, n_state, k * d_head)


def pack_state(s, pack: int):
    """``s`` [..., H, N, P] as the cache keeps it, [..., H / pack, N, pack
    P]: head ``pack i + q`` in lanes ``[q P, (q + 1) P)`` of packed head
    ``i``."""
    if pack == 1:
        return s
    *lead, h, n, p = s.shape
    return jnp.moveaxis(s.reshape(*lead, h // pack, pack, n, p), -3, -2
                        ).reshape(*lead, h // pack, n, pack * p)


def unpack_state(s, pack: int):
    """:func:`pack_state`'s inverse: [..., H / pack, N, pack P] as [..., H,
    N, P]."""
    if pack == 1:
        return s
    *lead, hp, n, pp = s.shape
    return jnp.moveaxis(s.reshape(*lead, hp, n, pack, pp // pack), -2, -3
                        ).reshape(*lead, hp * pack, n, pp // pack)


def _head_block(n_head: int, n_group: int) -> int:
    """Heads a grid step takes: the largest divisor of a group's heads up
    to ``_HEAD_BLOCK`` (a block lies inside ONE group, whose ``B`` and
    ``C`` it shares)."""
    per_group = n_head // n_group
    hb = min(_HEAD_BLOCK, per_group)
    while per_group % hb:
        hb -= 1
    return hb


def ssd_recurrence(x, b, c, a, s0=None):
    """The recurrence token by token, float32. ``x`` [T, H, P], ``b``/``c``
    [T, G, N], ``a`` [T, H]; ``s0`` [H, N, P] or zeros. Returns ``(y [T, H,
    P], S [H, N, P])``."""
    f32 = jnp.float32
    x, b, c, a = (t.astype(f32) for t in (x, b, c, a))
    h, per = x.shape[1], x.shape[1] // b.shape[1]
    if s0 is None:
        s0 = jnp.zeros((h, b.shape[2], x.shape[2]), f32)

    def step(s, t):
        xt, bt, ct, at = t
        bt, ct = (jnp.repeat(v, per, axis=0) for v in (bt, ct))   # [H, N]
        s = s * jnp.exp(at)[:, None, None] + bt[:, :, None] * xt[:, None, :]
        return s, jnp.einsum("hn,hnp->hp", ct, s, precision=_HI)

    s, y = jax.lax.scan(step, s0.astype(f32), (x, b, c, a))
    return y, s


# -- the prefill's chunk scan ----------------------------------------------------

def _count(form: str) -> None:
    """One more chunk scan traced in ``form`` (trace-time, as
    ``kda/scan_calls.*``)."""
    from ...monitor import metrics

    metrics.counter(
        "ssd/scan_calls." + form,
        help="ssd_chunk_scan calls traced in the %s form (counted where "
             "ssd_chunk_scan chooses: once a call of a traced program, not "
             "once a run)" % form).inc()


def ssd_chunk_scan(x, b, c, a, s0=None, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens in ``ceil(T / chunk)`` sequential
    steps. Arguments and results as :func:`ssd_recurrence` (``y`` float32);
    equal to it to float32 round-off. ``a`` <= 0. On a TPU the
    ``ssd_chunk_scan`` kernel where :func:`ssd_chunk_scan_gate` takes the
    geometry, else the blocked ``jax.numpy`` form."""
    _, h, p = x.shape
    if _on_tpu() and ssd_chunk_scan_gate(h, b.shape[1], b.shape[2], p,
                                         chunk) is None:
        _count("kernel")
        return ssd_chunk_scan_kernel(x, b, c, a, s0, chunk=chunk)
    _count("blocked")
    return ssd_chunk_scan_xla(x, b, c, a, s0, chunk=chunk)


def _chunks(x, chunk: int):
    """``x`` [T, ...] as ``[ceil(T / chunk), chunk, ...]``, the tail
    zeros."""
    n = -(-x.shape[0] // chunk)
    x = jnp.pad(x, ((0, n * chunk - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((n, chunk) + x.shape[1:])


def _chunk(s0, xs):
    """One chunk of :func:`ssd_chunk_scan_xla`: ``xs`` = (x [C, H, P], b,
    c [C, G, N], a [C, H]) float32, ``s0`` [H, N, P]."""
    x, b, c, a = xs
    n_c, h, p = x.shape
    grp, n = b.shape[1:]
    per = h // grp
    g = jnp.cumsum(a, axis=0)                                # [C, H]
    gt = g.T                                                 # [H, C]
    tri = jnp.tril(jnp.ones((n_c, n_c), bool))
    decay = jnp.where(tri, jnp.exp(jnp.minimum(
        gt[:, :, None] - gt[:, None, :], 0.0)), 0.0)         # [H, C, C]
    cb = jnp.einsum("ign,jgn->gij", c, b, precision=_HI)     # [G, C, C]
    m = cb[:, None] * decay.reshape(grp, per, n_c, n_c)
    xg = x.reshape(n_c, grp, per, p)
    sg = s0.reshape(grp, per, n, p)
    y = jnp.einsum("gkij,jgkp->igkp", m, xg, precision=_HI) \
        + jnp.exp(g).reshape(n_c, grp, per, 1) * jnp.einsum(
            "ign,gknp->igkp", c, sg, precision=_HI)
    g_end = g[-1]                                            # [H]
    w = jnp.exp(g_end[None] - g).reshape(n_c, grp, per, 1)
    s1 = jnp.exp(g_end).reshape(grp, per, 1, 1) * sg + jnp.einsum(
        "ign,igkp->gknp", b, w * xg, precision=_HI)
    return s1.reshape(h, n, p), y.reshape(n_c, h, p)


def ssd_chunk_scan_xla(x, b, c, a, s0=None, chunk: int = CHUNK):
    """:func:`ssd_chunk_scan` as blocked ``jax.numpy`` under ``lax.scan``
    (scope ``ssd_chunk_scan``)."""
    f32 = jnp.float32
    t, h, p = x.shape
    if s0 is None:
        s0 = jnp.zeros((h, b.shape[2], p), f32)
    with jax.named_scope(SCAN_NAME):
        s, y = jax.lax.scan(_chunk, s0.astype(f32), tuple(
            _chunks(v.astype(f32), chunk) for v in (x, b, c, a)))
    return y.reshape(-1, h, p)[:t], s


def ssd_chunk_scan_gate(n_head: int, n_group: int, n_state: int, d_head: int,
                        chunk: int = CHUNK, interpret: bool = False
                        ) -> Optional[str]:
    """None when the ``ssd_chunk_scan`` kernel takes this geometry, else
    the rule that excludes it."""
    if n_head % n_group:
        return "%d heads do not divide into %d groups" % (n_head, n_group)
    if interpret:
        return None
    if chunk % _LANES:
        return "a chunk of %d rows is not whole %d-lane tiles" % (chunk,
                                                                  _LANES)
    pack = state_pack(d_head)
    if n_state % _LANES or (pack * d_head) % _LANES \
            or (n_head // n_group) % pack:
        return ("a head's B, C [., %d] must be whole %d-lane tiles and its x "
                "[., %d] whole tiles or a whole part of one, a group's %d "
                "heads whole tiles' worth"
                % (n_state, _LANES, d_head, n_head // n_group))
    hb = _head_block(n_head, n_group)
    if hb != n_head // n_group:
        return ("a group's %d heads are more than a grid step's %d"
                % (n_head // n_group, _HEAD_BLOCK))
    if hb * n_state * d_head * 4 > _BLOCK_BYTES:
        return ("a group's states %d x [%d, %d] are more than %d KiB of VMEM"
                % (hb, n_state, d_head, _BLOCK_BYTES >> 10))
    return None


def _scan_kernel(x_ref, b_ref, bt_ref, c_ref, g_ref, gt_ref, s_ref, y_ref,
                 s_out, *, hb, p, pack):
    """One chunk of one group's ``hb`` heads. ``x_ref``/``y_ref`` [C, hb P]
    (a head its P lanes), ``b_ref``/``c_ref`` [C, N], ``bt_ref`` [N, C],
    ``g_ref`` [C, H] and ``gt_ref`` [H, C] the running log-decay inside the
    chunk, every head's; the state ``[hb / pack, N, pack P]`` (``pack``
    heads side by side in a lane tile) stays in ``s_out`` from the group's
    first chunk to its last."""
    f32 = jnp.float32
    n_c = x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out[...] = s_ref[...]

    first = pl.program_id(0) * hb
    bm, cm, bt = b_ref[...], c_ref[...], bt_ref[...]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())), precision=_HI,
                             preferred_element_type=f32)     # [C, C]
    row = jax.lax.broadcasted_iota(jnp.int32, (n_c, n_c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_c, n_c), 1)
    heads_c = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
    heads_r = jax.lax.broadcasted_iota(jnp.int32, gt_ref.shape, 0)
    g_all, gt_all = g_ref[...], gt_ref[...]
    pp = pack * p
    if pack > 1:        # which head of a packed one a lane belongs to
        of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, pp), 1) // p
    for j in range(hb // pack):
        lanes = slice(j * pp, (j + 1) * pp)
        y = s1 = from_s0 = None
        for q in range(pack):
            i = j * pack + q
            # this head's running log-decay as a column and as a row
            g_col = jnp.sum(jnp.where(heads_c == first + i, g_all, 0.0),
                            axis=1, keepdims=True)           # [C, 1]
            g_row = jnp.sum(jnp.where(heads_r == first + i, gt_all, 0.0),
                            axis=0, keepdims=True)           # [1, C]
            g_end = g_row[:, n_c - 1:]                       # [1, 1]
            m = jnp.where(col <= row,
                          jnp.exp(jnp.minimum(g_col - g_row, 0.0)), 0.0) * cb
            if q == 0:
                xh = x_ref[:, lanes]                         # [C, pack P]
                s0 = s_out[j]                                # [N, pack P]
            y_q = jnp.dot(m, xh, precision=_HI, preferred_element_type=f32)
            carried = jnp.exp(g_col)
            if q == 0:      # C S_0: the packed heads' alike, made once
                from_s0 = jnp.dot(cm, s0, precision=_HI,
                                  preferred_element_type=f32)
            y_q = y_q + carried * from_s0
            # a packed head keeps its own lanes of the pair's products
            y = y_q if q == 0 else jnp.where(of_lane == q, y_q, y)
            if q == pack - 1:
                y_ref[:, lanes] = y
            s_q = jnp.exp(g_end) * s0 + jnp.dot(
                bt * jnp.exp(g_end - g_row), xh, precision=_HI,
                preferred_element_type=f32)
            s1 = s_q if q == 0 else jnp.where(of_lane == q, s_q, s1)
        s_out[j] = s1


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_kernel(x, b, c, a, s0=None, chunk: int = CHUNK, *,
                          interpret: bool = False):
    """:func:`ssd_chunk_scan` as ONE Pallas call (name ``ssd_chunk_scan``
    in a device trace): a grid of (groups, chunks), the chunks in turn. A
    grid step takes a chunk of a group's heads: ``x`` and ``y`` as ``[C,
    hb P]`` lanes of the caller's rows, the group's ``B`` (and its
    transpose, the one array turned in front of the call) and ``C``, the
    running log-decay (summed inside each chunk in front of the call: a
    ``[T, H]`` cumsum); the group's state stays in VMEM over the chunks and
    goes through HBM once a call. Jitted, so that the layers of one
    executable lower ONE kernel text."""
    f32 = jnp.float32
    t, h, p = x.shape
    grp, n = b.shape[1:]
    why_not = ssd_chunk_scan_gate(h, grp, n, p, chunk, interpret)
    if why_not is not None:
        raise ValueError(why_not)
    hb = _head_block(h, grp)
    pack = state_pack(p) if hb % state_pack(p) == 0 else 1
    blocks = h // hb
    per = (h // grp) // hb          # head blocks a group (1 on the chip)
    n_c = -(-t // chunk)
    rows = n_c * chunk

    def flat(v):
        v = v.astype(f32).reshape(t, -1)
        return jnp.pad(v, ((0, rows - t), (0, 0)))

    x2, b2, c2 = flat(x), flat(b), flat(c)
    g = jnp.cumsum(flat(a).reshape(n_c, chunk, h), axis=1).reshape(rows, h)
    if s0 is None:
        s0 = jnp.zeros((h, n, p), f32)
    state = pl.BlockSpec((hb // pack, n, pack * p), lambda j, k: (j, 0, 0))
    x_spec = pl.BlockSpec((chunk, hb * p), lambda j, k: (k, j))
    bc_spec = pl.BlockSpec((chunk, n), lambda j, k: (k, j // per))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, p=p, pack=pack),
        grid=(blocks, n_c),
        in_specs=[x_spec, bc_spec,
                  pl.BlockSpec((n, chunk), lambda j, k: (j // per, k)),
                  bc_spec,
                  pl.BlockSpec((chunk, h), lambda j, k: (k, 0)),
                  pl.BlockSpec((h, chunk), lambda j, k: (0, k)), state],
        out_specs=[x_spec, state],
        out_shape=[jax.ShapeDtypeStruct((rows, h * p), f32),
                   jax.ShapeDtypeStruct((h // pack, n, pack * p), f32)],
        input_output_aliases={6: 1}, interpret=interpret, name=SCAN_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(x2, b2, b2.T, c2, g, g.T, pack_state(s0.astype(f32), pack))
    return y.reshape(rows, h, p)[:t], unpack_state(s, pack)


# -- the decode step -------------------------------------------------------------

def ssd_state_step_xla(states, layer, x, b, c, a, active):
    """:func:`ssd_state_step` in plain XLA: every slot computed, the
    inactive ones put back as they were."""
    f32 = jnp.float32
    x, b, c, a = (t.astype(f32) for t in (x, b, c, a))
    per = x.shape[1] // b.shape[1]
    pack = x.shape[1] // states.shape[2]
    bh, ch = (jnp.repeat(t, per, axis=1) for t in (b, c))    # [B, H, N]
    s = unpack_state(states[layer], pack) * jnp.exp(a)[..., None, None] \
        + bh[..., :, None] * x[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", ch, s, precision=_HI)
    live = active[:, None, None, None]
    return (jnp.where(active[:, None, None], y, 0.0),
            states.at[layer].set(jnp.where(live, pack_state(s, pack),
                                           states[layer])))


def _step_blocks(n_head: int, n_group: int, n_state: int, d_head: int):
    """``(pack, hb, gb)`` of the step kernel: ``pack`` heads a packed head
    of the cache's state, ``hb`` packed heads of ONE group and ``gb``
    groups a grid step: a group's heads up to ``_HEAD_BLOCK``, and as many
    whole groups as ``_BLOCK_BYTES`` of state hold (Falcon-H1: one group
    of 16 heads, 2 MiB; Nemotron-3: a slot's eight groups of four packed
    pairs, 2 MiB)."""
    pack = n_head // state_shape(n_head, n_state, d_head, n_group)[0]
    hb = _head_block(n_head // pack, n_group)
    gb = 1
    if hb == n_head // pack // n_group:
        gb = max(1, min(n_group, _BLOCK_BYTES
                        // (hb * n_state * pack * d_head * 4)))
        while n_group % gb:
            gb -= 1
    return pack, hb, gb


def ssd_state_step_gate(n_head: int, n_state: int, d_head: int,
                        n_group: int = 1, interpret: bool = False
                        ) -> Optional[str]:
    """None when the compiled kernel takes this state geometry, else the
    rule that excludes it (the chip compiler's tiling; the interpreter is
    not bound by it)."""
    if n_head % n_group:
        return "%d heads do not divide into %d groups" % (n_head, n_group)
    if interpret:
        return None
    pack, hb, gb = _step_blocks(n_head, n_group, n_state, d_head)
    if n_state % 8 or (pack * d_head) % _LANES:
        return ("a head's state [%d, %d] must be whole (8, %d) float32 "
                "tiles, or whole parts of a lane tile side by side"
                % (n_state, d_head, _LANES))
    if (gb * hb) % 8 and gb * hb != n_head // pack:
        return ("a group's %d heads do not divide into blocks of a multiple "
                "of 8 sublanes" % (n_head // n_group))
    if hb * n_state * pack * d_head * 4 > _BLOCK_BYTES:
        return ("a block of %d heads' states [%d, %d] is more than %d KiB "
                "of VMEM" % (hb * pack, n_state, d_head, _BLOCK_BYTES >> 10))
    return None


def _step_kernel(layer_ref, idx_ref, n_ref, cols_ref, x_ref, *rest, hb, gb,
                 packed):
    # a packed head's decays ride as a ROW beside x: a decay a lane
    d_ref, s_ref, y_ref, s_out = rest if packed else (None,) + rest
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        cols = cols_ref[...]                      # [N, lanes]
        for g in range(gb):
            # [N, 1]: the group's B and C
            bcol, ccol = cols[:, 2 * g:2 * g + 1], cols[:, 2 * g + 1:2 * g + 2]
            for h in range(g * hb, (g + 1) * hb):
                s = s_ref[h] * (d_ref[h:h + 1, :] if packed else
                                cols[:, 2 * gb + h:2 * gb + h + 1]) \
                    + bcol * x_ref[h:h + 1, :]
                s_out[h] = s
                y_ref[h:h + 1, :] = jnp.sum(s * ccol, axis=0, keepdims=True)

    # nobody live: every step sits on one block, which goes back as it came
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        s_out[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssd_state_step(states, layer, x, b, c, a, active, *,
                   interpret: bool = False):
    """One decode step of layer ``layer`` for the slots marked ``active``.

    ``states`` [n_layer, B] + :func:`state_shape` float32 (``[H, N, P]`` a
    slot, or packed), donated: the result's second part is the same buffer
    with the live slots' states advanced. ``x`` [B, H, P] (the heads'
    inputs times their steps), ``b``/``c`` [B, G, N], ``a`` [B, H] the
    log-decay, any float type (computed in float32). Returns ``(y [B, H,
    P] float32, states)``; ``y`` of a slot that is not active is 0, its
    state untouched and unread."""
    f32 = jnp.float32
    _, nb, hp, n, pp = states.shape
    h, p = x.shape[1:]
    grp = b.shape[1]
    pack, hb, gb = _step_blocks(h, grp, n, p)
    if (hp, pp) != (h // pack, pack * p):
        raise ValueError("states %s are not %d heads of [%d, %d] as the "
                         "cache keeps them" % (states.shape, h, n, p))
    blk = gb * hb                   # packed heads a grid step
    nj = hp // blk
    per = (hp // grp) // hb if gb == 1 else 1   # blocks a group
    lanes = -(-(2 * gb + (0 if pack > 1 else blk)) // _LANES) * _LANES
    x, b, c, a = (t.astype(f32) for t in (x, b, c, a))
    # what scales S's ROWS rides as columns: for each block an [N, 2 gb +
    # blk] tile [B | C of each of its groups | e^a of each head, down every
    # row] (a packed head's decays differ by LANE: a row, below)
    bc = jnp.stack([b, c], axis=-1)                           # [B, G, N, 2]
    if gb == 1:
        bc = jnp.repeat(bc, per, axis=1)                      # [B, nj, N, 2]
    else:
        bc = jnp.moveaxis(bc.reshape(nb, nj, gb, n, 2), 2, 3).reshape(
            nb, nj, n, 2 * gb)
    parts = [bc]
    if pack == 1:
        parts.append(jnp.broadcast_to(jnp.exp(a).reshape(nb, nj, 1, blk),
                                      (nb, nj, n, blk)))
    cols = jnp.concatenate(parts, axis=-1)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, 0),
                          (0, lanes - cols.shape[-1])))
    rows = [x.reshape(nb, hp, pp)]
    if pack > 1:
        rows.append(jnp.repeat(jnp.exp(a), p, axis=1).reshape(nb, hp, pp))
    # live slots first; the steps past the last stay on its last block
    live = jnp.sum(active).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    idx = order[jnp.minimum(jnp.arange(nb), jnp.maximum(live - 1, 0))]

    def block(i, j, layer_ref, idx_ref, n_ref):
        return idx_ref[i], jnp.where(i < n_ref[0], j, nj - 1)

    def state_map(i, j, layer_ref, idx_ref, n_ref):
        slot, jj = block(i, j, layer_ref, idx_ref, n_ref)
        return layer_ref[0], slot, jj, 0, 0

    def col_map(i, j, *refs):
        slot, jj = block(i, j, *refs)
        return slot, jj, 0, 0

    def row_map(i, j, *refs):
        slot, jj = block(i, j, *refs)
        return slot, jj, 0

    state_spec = pl.BlockSpec((None, None, blk, n, pp), state_map)
    row_spec = pl.BlockSpec((None, blk, pp), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(nb, nj),
        in_specs=[pl.BlockSpec((None, None, n, lanes), col_map)]
        + [row_spec] * len(rows) + [state_spec],
        out_specs=[row_spec, state_spec])
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, gb=gb, packed=pack > 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nb, hp, pp), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        input_output_aliases={4 + len(rows): 1}, interpret=interpret,
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.asarray(layer, jnp.int32).reshape(1), idx.astype(jnp.int32),
      live.reshape(1), cols, *rows, states)
    return jnp.where(active[:, None, None], y.reshape(nb, h, p), 0.0), states

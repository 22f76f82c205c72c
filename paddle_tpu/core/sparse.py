"""Sparse gradients — the SelectedRows equivalent.

Reference: ``framework/selected_rows.h:32`` (rows + value block) and the
sparse optimizer kernels in ``operators/optimizers/`` (e.g. sgd_op.h's
SelectedRows branch, adam_op.h lazy mode).

XLA has no sparse tensors (SURVEY §7 hard parts): the TPU-native encoding is
an explicit ``(ids, rows)`` pair. For an embedding lookup of N ids into a
[V, D] table, the backward produces ``rows`` of shape [N, D] — O(N·D) HBM
traffic instead of the O(V·D) dense scatter-add, which is the entire point
at CTR-scale vocabularies (V ≥ 1e6, N a few thousand).

``merge_rows`` combines duplicate ids with static shapes (sort + segment
sum); the padded tail gets an out-of-range id, which XLA's scatter semantics
drop — so downstream row-wise optimizer updates are exact without a
dynamic-shape ``unique``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class SparseGrad:
    """Gradient of a row-gathered parameter: ``rows[i]`` is the gradient
    contribution of table row ``ids[i]``; duplicate ids accumulate."""

    def __init__(self, ids, rows):
        self.ids = ids
        self.rows = rows

    def tree_flatten(self):
        return (self.ids, self.rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return "SparseGrad(ids=%r, rows=%r)" % (self.ids, self.rows)


def route_rows_to_shards(ids, rows, n_shards, shard_size, axis_name,
                         invalid_index):
    """PS ``split_ids_op`` parity inside ``shard_map``: bucket this rank's
    (ids, rows) by owning table shard (``id // shard_size``) and exchange
    buckets with ``lax.all_to_all`` so every row lands on the rank that owns
    it. Exact: bucket capacity is the local N (worst case all ids belong to
    one shard), so nothing is ever dropped — the cost model vs the
    replicate-to-all alternative is benchmarks/COLLECTIVES.md §7. Returns
    (ids [n·N], rows [n·N, D]); empty slots carry ``invalid_index``.
    """
    n_loc = ids.shape[0]
    owner = jnp.clip(ids // shard_size, 0, n_shards - 1)
    order = jnp.argsort(owner)
    sid = jnp.take(ids, order)
    srows = jnp.take(rows, order, axis=0)
    sowner = jnp.take(owner, order)
    # position within the (sorted) owner group, then a flat scatter into
    # fixed-capacity buckets — the static-shape sort-based dispatch MoE uses
    pos = (jnp.arange(n_loc, dtype=sowner.dtype)
           - jnp.searchsorted(sowner, sowner, side="left"))
    flat = sowner * n_loc + pos
    bucket_ids = jnp.full((n_shards * n_loc,), invalid_index,
                          sid.dtype).at[flat].set(sid)
    bucket_rows = jnp.zeros((n_shards * n_loc,) + rows.shape[1:],
                            rows.dtype).at[flat].set(srows)
    from ..monitor.device import record_collective

    send_ids = bucket_ids.reshape(n_shards, n_loc)
    send_rows = bucket_rows.reshape((n_shards, n_loc) + rows.shape[1:])
    # trace-time byte accounting: these are the PS-style id/row exchange's
    # per-device per-step volumes (benchmarks/COLLECTIVES.md §7 — measured)
    record_collective("all_to_all", axis_name, send_ids)
    record_collective("all_to_all", axis_name, send_rows)
    recv_ids = jax.lax.all_to_all(send_ids, axis_name, 0, 0)
    recv_rows = jax.lax.all_to_all(send_rows, axis_name, 0, 0)
    return recv_ids.reshape(-1), recv_rows.reshape((-1,) + rows.shape[1:])


def sharded_rows_update(tables, ids, rows, update, mesh, axis,
                        scalars=(), alltoall=False):
    """Rows-only optimizer update on tables row-sharded over a mesh axis —
    the GSPMD-era replacement of the reference parameter server's sparse
    update path (``listen_and_serv`` + ``split_ids``/``send``): each shard
    holds V/n rows (and its own slice of the optimizer moments), receives
    only the gradient rows it owns, and updates them in place. The dense
    [V, D] gradient never exists anywhere.

    ``tables``: tuple of [V, D] arrays annotated/laid out as ``P(axis,
    None)``. ``ids``: [N] globally-merged unique row ids (pads == V).
    ``rows``: [N, D] merged gradient rows. ``update(tabs_loc, lid,
    rows_loc, *scalars)`` maps shard-local tables + local row ids
    (out-of-shard entries set past the shard bound, which XLA's OOB scatter
    semantics drop) to new shard-local tables. ``scalars`` are traced
    scalars the update reads (e.g. the bias-corrected step size) — explicit
    replicated args because shard_map can't close over tracers.

    ``alltoall=False`` replicates (ids, rows) to every shard of ``axis``
    (one all-gather; each shard filters to its own rows). ``alltoall=True``
    instead splits the id list over the shards and routes each row to its
    owner with :func:`route_rows_to_shards` — the explicit PS-style id
    exchange; requires N divisible by the axis size (callers fall back to
    the replicated form otherwise).
    """
    from jax.sharding import PartitionSpec as P


    vocab = tables[0].shape[0]
    n = mesh.shape[axis]
    shard_size = vocab // n
    t_spec = P(axis, *([None] * (tables[0].ndim - 1)))

    def body(ids_l, rows_l, *rest):
        scal = rest[:len(scalars)]
        tabs = rest[len(scalars):]
        k = jax.lax.axis_index(axis)
        if alltoall:
            ids_l, rows_l = route_rows_to_shards(
                ids_l, rows_l, n, shard_size, axis, vocab)
        lo = k * shard_size
        mine = (ids_l >= lo) & (ids_l < lo + shard_size)
        # out-of-shard rows map just past the shard: reads clamp (harmless,
        # masked by the dropped write), writes drop — same OOB contract
        # merge_rows relies on
        lid = jnp.where(mine, ids_l - lo, shard_size)
        rows_l = jnp.where(mine[:, None], rows_l, jnp.zeros_like(rows_l))
        return update(tabs, lid, rows_l, *scal)

    spec_in = (P(axis) if alltoall else P(),
               P(axis, None) if alltoall else P(None, None))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=spec_in + (P(),) * len(scalars)
                       + (t_spec,) * len(tables),
                       out_specs=(t_spec,) * len(tables), check_vma=False)
    return fn(ids, rows, *scalars, *tables)


def merge_rows(ids, rows, invalid_index):
    """Sum rows of duplicate ids. Returns (uniq_ids [N], merged [N, D]) where
    positions past the number of distinct ids carry ``invalid_index`` —
    feed them to ``.at[uniq].set/add`` and XLA drops them (OOB scatter).
    """
    order = jnp.argsort(ids)
    sid = jnp.take(ids, order)
    srows = jnp.take(rows, order, axis=0)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    merged = jax.ops.segment_sum(srows, seg, num_segments=ids.shape[0])
    uniq = jnp.full((ids.shape[0],), invalid_index, sid.dtype).at[seg].set(sid)
    return uniq, merged

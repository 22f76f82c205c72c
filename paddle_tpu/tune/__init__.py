"""paddle_tpu.tune — measured autotuning over the knobs we used to hand-tune.

The repo's two largest single wins were found by hand: the v5e
flash-attention BlockSizes sweep (3.57x over composed at S=8192) and the
trace-time pass-gate pipeline. This subsystem turns that manual loop into
infrastructure (TVM's measured schedule search, PAPERS.md):

* :mod:`~paddle_tpu.tune.table` — persistent config table keyed
  ``(kernel, shape-bucket, device_kind)``: runtime JSON next to the
  persistent compile cache, a checked-in ``shipped.json`` seeded with the
  hand-tuned v5e entries, hardcoded defaults as the final fallback. Corrupt
  tables log once and fall back — never crash a run.
* :mod:`~paddle_tpu.tune.search` — the measured search driver: analytic
  VMEM pruning, warmup + median-of-k timing with compile excluded,
  ``autotune/*`` counters, atomic table writes.
* :mod:`~paddle_tpu.tune.tunables` — the registered knobs: flash
  BlockSizes, sparse-adam row blocks, softmax-xent tiles, paged-attention
  page blocks, per-program pass gates (end-to-end measured).

Entry points: ``tools/autotune.py`` (sweep + write + before/after table);
``ops/attention_ops._tuned_block_sizes``, ``sparse_adam._block_size`` and
the softmax-xent tile choice consult :func:`lookup` at trace time;
``ServingConfig(decode_fuse="auto")`` and ``FleetConfig(replicas="auto")``
read the same table through the ``resolve_*`` functions.
"""

from .table import (  # noqa: F401
    bucket_ctx,
    bucket_nv,
    bucket_rows,
    bucket_seq,
    bucket_slots,
    device_kind,
    lookup,
    normalize_device_kind,
    pow2_floor,
    provenance_snapshot,
    record,
    reset_provenance,
    resolve_decode_fuse,
    resolve_fleet_roles,
    resolve_fleet_router,
    shipped_path,
    table_path,
)
from .search import SearchResult, median_time_ms, search  # noqa: F401

__all__ = [
    "bucket_ctx", "bucket_nv", "bucket_rows", "bucket_seq", "bucket_slots",
    "device_kind", "normalize_device_kind", "pow2_floor",
    "lookup", "record", "table_path", "shipped_path",
    "resolve_decode_fuse", "resolve_fleet_roles", "resolve_fleet_router",
    "provenance_snapshot", "reset_provenance",
    "SearchResult", "median_time_ms", "search",
    "Tunable", "register_tunable", "get_tunable", "registered_tunables",
]


def __getattr__(name):
    # tunables pull in ops/passes machinery — load them only when
    # someone actually asks for the registry (the CLI, tests), keeping
    # `import paddle_tpu.tune` cheap for the trace-time lookup path
    if name in ("Tunable", "register_tunable", "get_tunable",
                "registered_tunables"):
        from . import tunables as _t

        return getattr(_t, name)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))

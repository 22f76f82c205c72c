"""Runtime flag system (reference: gflags DEFINE_* + the FLAGS_* env
whitelist in python/paddle/fluid/__init__.py:128-160).

Flags are read from ``FLAGS_*`` environment variables at import (the
``--tryfromenv`` path of init.cc:44) and mutable at runtime via set_flag().
Only flags that mean something under XLA are wired; the rest are accepted
and ignored for script compatibility.
"""

from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["get_flag", "set_flag", "flags"]

_DEFAULTS: Dict[str, Any] = {
    # honored
    "check_nan_inf": False,          # post-step NaN/Inf scan (operator.cc:947)
    "benchmark": False,              # block_until_ready every step (operator.cc:942)
    "strict_fused_attention": False, # raise (not warn+fallback) if the Pallas
                                     # flash-attention call fails on TPU
    "flash_attention_min_seq": 2048, # perf crossover of the LONG path's
                                     # kernel: with v5e-tuned BlockSizes (r4
                                     # sweep) flash beats composed 1.6x at
                                     # S=2048 up to 4.2x at S=8192; composed
                                     # wins below (its single fused HLO beats
                                     # that kernel's fixed grid overhead at
                                     # short S). That sweep had no dropout, no
                                     # segment ids, block_b 1 and the long
                                     # kernel only: a key length of ONE tile
                                     # (S <= 512) has its own kernels and its
                                     # own predicate of shapes, which this
                                     # flag does not move
                                     # (attention_ops._single_tile_ok)
    "unfused_attention": False,      # layers.attention emits the reference-
                                     # style primitive composition (matmul/
                                     # scale/softmax/dropout/matmul) instead
                                     # of the fused op for non-causal, non-
                                     # segmented attention; the default
                                     # optimizer's flash_attention_rewrite
                                     # (PADDLE_TPU_OPT_LEVEL>=1) fuses it
                                     # back — the graph stays inspectable,
                                     # the kernel still gets hit
    "attention_softmax_f32": False,  # composed-attention softmax in f32:
                                     # +5 GB/step on Transformer-base (XLA
                                     # materializes the f32 probs for bwd);
                                     # default bf16 matches raw-JAX practice
    "ring_flash_min_block": 2048,    # ring attention: local shard length at
                                     # which the per-block compute switches
                                     # from composed to the Pallas flash
                                     # kernel (same crossover as above)
    "sparse_update_kernel": "auto",  # row-wise Pallas sparse-Adam/SGD kernel
                                     # (pallas_kernels/sparse_adam.py) instead
                                     # of the 3 XLA scatter fusions on
                                     # SelectedRows updates: "auto" = compiled
                                     # kernel on TPU, scatter elsewhere;
                                     # "on" = kernel everywhere (interpreted
                                     # off-TPU); "interpret" = force the
                                     # interpreter (parity tests); "off" =
                                     # always scatter
    "paged_attention_kernel": "auto", # ragged paged-attention Pallas decode
                                     # kernel (pallas_kernels/
                                     # paged_attention.py) instead of the XLA
                                     # page-gather + decode_attention in the
                                     # serving decode scan: "auto" = compiled
                                     # kernel on TPU, gather elsewhere;
                                     # "on" = kernel everywhere (interpreted
                                     # off-TPU); "interpret" = force the
                                     # interpreter (parity tests); "off" =
                                     # always gather
    "ctr_alltoall_update": False,    # sharded-table sparse updates route
                                     # (ids, rows) to owner shards with an
                                     # explicit lax.all_to_all (PS split_ids
                                     # parity) instead of replicating the
                                     # merged rows to every model shard;
                                     # exact (worst-case bucket capacity),
                                     # see benchmarks/COLLECTIVES.md §7
    "eager_delete_tensor_gb": 0.0,   # accepted; XLA buffer liveness handles it
    # accepted for compatibility, no-ops under XLA
    "fraction_of_gpu_memory_to_use": 0.92,
    "allocator_strategy": "naive_best_fit",
    "cpu_deterministic": True,       # XLA is deterministic by construction
    "sync_nccl_allreduce": False,
    "paddle_num_threads": 1,
    "init_allocated_mem": False,
    "limit_of_tmp_allocation": -1,
    "rpc_deadline": 180000,
}

_flags: Dict[str, Any] = {}


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, float):
        return float(raw)
    if isinstance(default, int):
        return int(raw)
    return raw


def _load_env():
    for name, default in _DEFAULTS.items():
        raw = os.environ.get("FLAGS_" + name)
        _flags[name] = _coerce(default, raw) if raw is not None else default


_load_env()


def get_flag(name: str):
    if name not in _flags:
        raise KeyError("unknown flag %r (known: %s)" % (name, sorted(_flags)))
    return _flags[name]


def set_flag(name: str, value):
    if name not in _flags:
        raise KeyError("unknown flag %r" % name)
    _flags[name] = value


class _Flags:
    def __getattr__(self, name):
        return get_flag(name)

    def __setattr__(self, name, value):
        set_flag(name, value)


flags = _Flags()

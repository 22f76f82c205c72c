"""Legacy ParallelExecutor API (reference:
python/paddle/fluid/parallel_executor.py — a deprecated wrapper the
reference itself routes to CompiledProgram + Executor; scripts that
instantiate it directly must keep running).

The TPU mapping is the same one CompiledProgram makes: a data-axis mesh over
the local devices with GSPMD inserting the gradient psum (the role NCCL
AllReduce op handles played, ``details/all_reduce_op_handle.cc:55``).
"""

from __future__ import annotations

import warnings
from typing import Optional

from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy
from .core.framework import default_main_program
from .core.place import CPUPlace, TPUPlace
from .core.scope import global_scope
from .executor import Executor
from .monitor import metrics as _mx, tracer as _tr

__all__ = ["ParallelExecutor"]

_m_runs = _mx.counter("parallel_executor/runs",
                      help="ParallelExecutor.run invocations (legacy wrapper)")


class ParallelExecutor:
    def __init__(self, use_cuda, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None):
        warnings.warn(
            "ParallelExecutor is deprecated. Please use CompiledProgram and "
            "Executor (compiler.py).", DeprecationWarning, stacklevel=2)
        build_strategy = build_strategy or BuildStrategy()
        build_strategy.num_trainers = num_trainers
        build_strategy.trainer_id = trainer_id
        self._program = main_program or default_main_program()
        self._scope = scope or global_scope()
        self._places = [TPUPlace(0)] if use_cuda else [CPUPlace()]
        self._compiled = CompiledProgram(self._program).with_data_parallel(
            loss_name=loss_name,
            build_strategy=build_strategy,
            exec_strategy=exec_strategy or ExecutionStrategy(),
            share_vars_from=getattr(share_vars_from, "_compiled", share_vars_from),
        )
        self._exe = Executor(self._places[0])

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        """reference: parallel_executor.py:123 (feed_dict is the deprecated
        alias feed wins over)."""
        if feed is None:
            feed = feed_dict
        _m_runs.inc()
        with _tr.span("parallel_executor/run", cat="executor"):
            return self._exe.run(self._compiled, feed=feed,
                                 fetch_list=fetch_list, scope=self._scope,
                                 return_numpy=return_numpy)

    @property
    def device_count(self) -> int:
        import jax

        return len(jax.devices())

"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle
Fluid's capabilities (reference: BrianZhu01/Paddle, surveyed in SURVEY.md),
built on JAX/XLA/Pallas/pjit.

Typical use mirrors Fluid:

    import paddle_tpu as fluid

    x = fluid.layers.data("x", shape=[784])
    y = fluid.layers.data("y", shape=[1], dtype="int64")
    out = fluid.layers.fc(x, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(out, y))
    fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    loss_val, = exe.run(feed={"x": xb, "y": yb}, fetch_list=[loss])
"""

import time as _time

_t_first_line = _time.perf_counter()

# Place the persistent XLA compile cache BEFORE anything can trigger a
# compile: JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache.
from . import compile_cache as _compile_cache  # noqa: E402,F401

_compile_cache.setup_compile_cache()

# startup/import, the first of the three start-up phases
# (compile_cache.phases()): this file's first line to its last, so the
# package's own imports and JAX's where nothing imported JAX before
_import_phase = _compile_cache.phase("startup/import").__enter__()
_import_phase.t0 = _t_first_line

# Sharding-invariant RNG: with the legacy threefry lowering, random values
# change when XLA partitions the generating computation — which would make a
# mesh-sharded table's shard-by-shard init (ops/tensor_ops._run_init) and a
# data-parallel dropout mask diverge from their single-device twins. The
# partitionable lowering keeps every random stream bit-identical no matter
# how GSPMD splits it (and is what later JAX releases default to), so loss
# parity between single-device and mesh runs includes the RNG. An explicit
# JAX_THREEFRY_PARTITIONABLE env setting wins — a host app pinning the
# legacy streams keeps them (and forfeits mesh/single-device RNG parity).
import os as _os

if "JAX_THREEFRY_PARTITIONABLE" not in _os.environ:
    import jax as _jax

    _jax.config.update("jax_threefry_partitionable", True)

from . import (  # noqa: F401
    amp,
    backward,
    clip,
    contrib,
    data,
    dataset,
    debugger,
    imperative,
    initializer,
    io,
    layers,
    log,
    metrics,
    monitor,
    nets,
    optimizer,
    parallel,
    passes,
    profiler,
    reader,
    regularizer,
    transpiler,
)
from .data_feeder import DataFeeder  # noqa: F401
from .flags import flags, get_flag, set_flag  # noqa: F401
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: F401
from .backward import append_backward, calc_gradient, gradients  # noqa: F401
from .core.framework import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    name_scope,
    program_guard,
    test_mode,
)
from .core import unique_name  # noqa: F401
from .parallel_executor import ParallelExecutor  # noqa: F401
from .core.pass_framework import (  # noqa: F401
    Pass,
    PassBuilder,
    get_pass,
    register_pass,
    registered_passes,
)
from .core.place import CPUPlace, CUDAPinnedPlace, TPUPlace, is_compiled_with_tpu  # noqa: F401
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .executor import Executor, FetchHandle  # noqa: F401
from .layers.layer_helper import ParamAttr, WeightNormParamAttr  # noqa: F401

# Fluid compatibility: CUDAPlace maps to the accelerator (TPU) place.
CUDAPlace = TPUPlace

__version__ = "0.1.0"

from .async_executor import AsyncExecutor  # noqa: F401
from .data_feed_desc import DataFeedDesc  # noqa: F401
from .reader.py_reader import EOFException  # noqa: F401

_import_phase.__exit__(None, None, None)
del _import_phase, _t_first_line

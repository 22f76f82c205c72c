"""Test config: force an 8-device virtual CPU mesh (SURVEY.md §4 implication c).

Tests never require real TPU hardware; sharding/collective tests use the
virtual devices, numeric tests run on CPU. The environment is set here,
before anything imports jax, and children inherit it.
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The run gets a cache directory of its own, placed the way any caller
# places it, so that the tuned/calibration tables that live there
# (tune.table_path, numerics.table_path) do not leak between the checkout's
# cache and the tests, in either direction. The executable cache itself is
# off: thousands of sub-second CPU compiles would each pay for a cache key
# and never be written (measured: a few percent of a run that has a time
# limit).
_cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_test_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'`; slow marks the opt-out extras
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + name generator."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.framework import switch_main_program, switch_startup_program
    from paddle_tpu.core.scope import Scope, scope_guard

    prev_main = switch_main_program(fluid.Program())
    prev_startup = switch_startup_program(fluid.Program())
    with unique_name.guard():
        with scope_guard(Scope()):
            yield
    switch_main_program(prev_main)
    switch_startup_program(prev_startup)


@pytest.fixture
def rng():
    return np.random.RandomState(42)

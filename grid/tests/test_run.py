"""The command itself: where JAX finds no TPU it exits non-zero and prints
no result, so no number from a CPU is ever written under a device metric's
name."""

import os
import subprocess
import sys

from conftest import ROOT


def test_exits_nonzero_on_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "grid.run", "--workload", "gpt2s-chat-sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_an_unknown_workload_is_an_error():
    p = subprocess.run(
        [sys.executable, "-m", "grid.run", "--workload", "nope", "--seed",
         "1", "--seconds", "1"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0 and "metrics" not in p.stdout

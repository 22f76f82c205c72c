"""Dump the public API surface as a stable, diffable spec (reference:
tools/print_signatures.py + paddle/fluid/API.spec + tools/diff_api.py).

Usage:  python tools/print_signatures.py > API.spec

Every public function/class in the listed modules is emitted as
``qualified.name (signature)``; classes additionally list their public
methods. The committed API.spec is enforced by tests/test_api_spec.py — an
intentional API change must regenerate the spec in the same commit.
"""

from __future__ import annotations

import inspect
import sys

MODULES = [
    "paddle_tpu",
    "paddle_tpu.compile_cache",
    "paddle_tpu.executor",
    "paddle_tpu.layers",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.control_flow",
    "paddle_tpu.layers.io",
    "paddle_tpu.layers.tensor",
    "paddle_tpu.layers.learning_rate_scheduler",
    "paddle_tpu.optimizer",
    "paddle_tpu.initializer",
    "paddle_tpu.regularizer",
    "paddle_tpu.clip",
    "paddle_tpu.io",
    "paddle_tpu.metrics",
    "paddle_tpu.monitor",
    "paddle_tpu.monitor.budgets",
    "paddle_tpu.monitor.device",
    "paddle_tpu.monitor.metrics",
    "paddle_tpu.monitor.numerics",
    "paddle_tpu.monitor.runlog",
    "paddle_tpu.monitor.slo",
    "paddle_tpu.monitor.stepstats",
    "paddle_tpu.monitor.telemetry",
    "paddle_tpu.monitor.tracer",
    "paddle_tpu.nets",
    "paddle_tpu.reader",
    "paddle_tpu.backward",
    "paddle_tpu.amp",
    "paddle_tpu.imperative",
    "paddle_tpu.parallel",
    "paddle_tpu.passes",
    "paddle_tpu.profiler",
    "paddle_tpu.transpiler",
    "paddle_tpu.contrib",
    "paddle_tpu.inference",
    "paddle_tpu.serving",
    "paddle_tpu.serving.phases",
    "paddle_tpu.serving.prefix_cache",
    "paddle_tpu.fleet",
    "paddle_tpu.fleet.autopsy",
    "paddle_tpu.fleet.protocol",
    "paddle_tpu.fleet.replica",
    "paddle_tpu.fleet.router",
    "paddle_tpu.fleet.trace",
    "paddle_tpu.fleet.slo",
    "paddle_tpu.fleet.events",
    "paddle_tpu.reliability",
    "paddle_tpu.reliability.faults",
    "paddle_tpu.reliability.supervisor",
    "paddle_tpu.reliability.sentinel",
    "paddle_tpu.data",
    "paddle_tpu.data.reader",
    "paddle_tpu.data.multislot",
    "paddle_tpu.tune",
    "paddle_tpu.tune.table",
    "paddle_tpu.tune.search",
    "paddle_tpu.tune.tunables",
    "paddle_tpu.dataset",
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(*args, **kwargs)"


def _public_names(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    return sorted(set(names))


def collect():
    import importlib

    lines = []
    for mod_name in MODULES:
        mod = importlib.import_module(mod_name)
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            qual = "%s.%s" % (mod_name, name)
            if inspect.isclass(obj):
                lines.append("%s %s" % (qual, _sig(obj.__init__)))
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_"):
                        continue
                    if callable(meth) or isinstance(meth, (staticmethod, classmethod)):
                        fn = meth.__func__ if isinstance(meth, (staticmethod, classmethod)) else meth
                        if callable(fn):
                            lines.append("%s.%s %s" % (qual, mname, _sig(fn)))
            elif callable(obj):
                lines.append("%s %s" % (qual, _sig(obj)))
    return sorted(set(lines))


if __name__ == "__main__":
    sys.stdout.write("\n".join(collect()) + "\n")

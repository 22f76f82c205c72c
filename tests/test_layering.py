"""The packages' order, kept: a package imports only packages BELOW it.

``LAYERS`` is the one table (README, "Architecture", draws it). Every
``import`` statement of every file under ``paddle_tpu/<package>`` is read
with ``ast`` at any depth of indentation: a function-level import hides a
cycle, it does not settle one. The upward edges that are older than the
table and not repaired yet stand in ``LEFT``, and a case fails when a listed
edge is gone, so that the list can only shrink (ROADMAP D12 names them).

The second half holds the served models to ``models/blocks.py``'s rule: no
underscore name crosses from one ``models/`` module to another, and nothing
there imports the benchmark (``grid``) or a reference.
"""

import ast
import functools
import os

import pytest

ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "paddle_tpu")

# lowest first; ``executor`` is the module ``executor.py``
LAYERS = ["core", "monitor", "ops", "layers", "parallel", "passes", "tune",
          "reliability", "executor", "serving", "models", "fleet"]

# (file, package it imports though that lies above its own, the lines on
# the tree of PR 46: told in the failure, not compared, since any edit of
# the file moves them)
LEFT = [
    ("core/framework.py", "layers", (89, 121)),
    ("core/interpreter.py", "monitor", (59,)),
    ("core/pass_framework.py", "monitor", (66,)),
    ("core/sparse.py", "monitor", (70,)),
    ("monitor/device.py", "passes", (516,)),
    ("monitor/runlog.py", "passes", (142,)),
    ("monitor/numerics.py", "tune", (492, 504, 544)),
    ("monitor/runlog.py", "tune", (151,)),
    ("ops/attention_ops.py", "parallel", (504,)),
    ("ops/optimizer_ops.py", "parallel", (68,)),
    ("ops/tensor_ops.py", "parallel", (317,)),
    ("ops/attention_ops.py", "tune", (135,)),
    ("ops/pallas_kernels/paged_attention.py", "tune", (184,)),
    ("ops/pallas_kernels/softmax_xent.py", "tune", (116,)),
    ("ops/pallas_kernels/sparse_adam.py", "tune", (226,)),
]

SERVED = ["smallthinker", "kimi_k2", "laguna", "ling3_flash", "motif3",
          "glm5_flash", "falcon_h1", "ouro", "evabyte", "deepseek_v32",
          "nemotron3"]


def _files(package):
    path = os.path.join(ROOT, package)
    if os.path.isfile(path + ".py"):
        yield package + ".py"
        return
    for base, _dirs, names in os.walk(path):
        for name in sorted(names):
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(base, name), ROOT)


@functools.lru_cache(maxsize=None)     # a file is parsed once a process
def _imports(rel):
    """``(line, module as a list of names, names imported from it)`` of
    each import statement of ``paddle_tpu/<rel>``, relative ones resolved."""
    with open(os.path.join(ROOT, rel)) as f:
        tree = ast.parse(f.read())
    here = ["paddle_tpu"] + rel[:-3].split(os.sep)[:-1]   # its package
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [(node.lineno, a.name.split("."), [])
                      for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module.split(".") if node.module else []
            if node.level:
                mod = here[:len(here) - node.level + 1] + mod
            found.append((node.lineno, mod, [a.name for a in node.names]))
    return found


def _targets(rel):
    """``(line, package)`` of each import of a ``paddle_tpu`` package."""
    for line, mod, names in _imports(rel):
        if mod[:1] != ["paddle_tpu"]:
            continue
        if len(mod) > 1:
            yield line, mod[1]
        else:                       # ``from .. import tune, flags``
            for name in names:
                yield line, name


def _upward(package):
    rank = {p: i for i, p in enumerate(LAYERS)}
    for rel in _files(package):
        for line, target in _targets(rel):
            if rank.get(target, -1) > rank[package]:
                yield rel, target, line


def test_the_table_names_what_exists():
    for package in LAYERS:
        assert list(_files(package)), package


@pytest.mark.parametrize("package", LAYERS)
def test_a_package_imports_only_what_lies_below_it(package):
    left = {(rel, target) for rel, target, _ in LEFT}
    found = [(rel, target, line) for rel, target, line in _upward(package)
             if (rel, target) not in left]
    assert not found, (
        "%s lies below what it imports here (LAYERS, lowest first: %s): %s"
        % (package, LAYERS, found))


@pytest.mark.parametrize("rel,target,lines", LEFT,
                         ids=["%s->%s" % (r, t) for r, t, _ in LEFT])
def test_the_list_of_what_is_left_only_shrinks(rel, target, lines):
    found = [line for _rel, to, line in _upward(rel.split("/")[0])
             if _rel == rel and to == target]
    assert found, (
        "%s no longer imports %s (it did at lines %s): take the entry out "
        "of LEFT and of ROADMAP D12" % (rel, target, lines))


@pytest.mark.parametrize("model", SERVED)
def test_a_served_model_takes_its_blocks_from_the_library(model):
    """No underscore name from another ``models/`` module, no ``grid``, no
    reference: what two models share is ``models/blocks.py``'s, under a
    public name, and the referee is not the program's library."""
    for line, mod, names in _imports(os.path.join("models", model + ".py")):
        dotted = ".".join(mod)
        assert mod[:1] != ["grid"] and "reference" not in dotted, (line,
                                                                   dotted)
        assert not any("reference" in n for n in names), (line, names)
        if mod[:2] == ["paddle_tpu", "models"]:
            private = [n for n in names if n.startswith("_")]
            assert not private, (
                "models/%s.py:%d imports %s from %s: a block two models "
                "share goes to models/blocks.py under a public name"
                % (model, line, private, dotted))


def test_the_library_imports_no_model_and_no_reference():
    for line, mod, names in _imports(os.path.join("models", "blocks.py")):
        dotted = ".".join(mod)
        assert mod[:1] != ["grid"] and "reference" not in dotted, (line,
                                                                   dotted)
        assert not (set(SERVED) | {"decoder_lm"}) & set(mod + names), (
            line, dotted, names)

"""Do two trees serve the same programs?

    python -m tools.compare_lowering --parent <checkout of the parent> \
        [--configs kimi-k2-ep32-serve,...]

For every served configuration of ``BENCHMARK.json`` that BOTH trees have,
each tree traces its engine's ``jit_prefill`` (every bucket of a cell's
traffic) and ``jit_chunk`` at the configuration's published widths, as the
chip would run them (the kernels armed: ``attention_ops._on_tpu``,
``moe_ops._on_tpu`` and ``paged_kernel_mode`` say "the chip"), and prints
one digest an executable of the traced program's text: the jaxpr, which
holds every operation, shape, constant and each Pallas kernel's own body,
and no source location, written out by :func:`canonical_text` (a
sub-program in full at each use: whether calls share one trace is not the
program). Equal digests: a change to shared code (a block
moved, a function generalised behind an argument nobody else passes) left
that configuration's arithmetic as it was. Unequal: the two texts are
written under ``--out`` for ``diff``.

Nothing is computed and nothing is as large as a model: the weights and
the pools are ``jax.eval_shape``'s shapes (the models' ``init_params``,
the caches' ``init_state`` and ``executor.aot_compile`` are stood in for
inside the child process; the repository's own files are as they are).
Each tree is traced by a child process of its own, from its own root, on
the CPU. PR 46 did this by a scratch script (PERF.md 7 (0-e)); PR 47,
which moved the KDA and residual-stream blocks and gave the latent cache
and kernel an index and a row mask, is its first user.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_tree(names: Optional[List[str]], keep_text: bool) -> Dict[str, Dict]:
    """``{config: {executable: digest}}`` (with ``keep_text``:
    ``{executable: text}``) of the tree this process runs from."""
    import jax

    from grid import manifest
    from paddle_tpu.ops import attention_ops, moe_ops
    from paddle_tpu.serving import engine as engine_mod
    from paddle_tpu.serving import kv_cache

    attention_ops._on_tpu = lambda: True
    attention_ops.paged_kernel_mode = lambda: "compiled"
    moe_ops._on_tpu = lambda: True
    for cls in (kv_cache.PagedKVCache, kv_cache.LatentPagedCache,
                kv_cache.Int8PagedKVCache, kv_cache.ContiguousKVCache):
        if "init_state" in vars(cls):
            cls.init_state = _abstract(cls.init_state)
    for cls in (kv_cache.PagedKVCache, kv_cache._KVCacheBase):
        for name in ("cache_bytes", "state_bytes"):
            if name in vars(cls):
                setattr(cls, name, lambda self, state: 0)
    for name in ("ring_bytes", "index_bytes"):
        if hasattr(kv_cache.LatentPagedCache, name):
            setattr(kv_cache.LatentPagedCache, name, lambda self, state: 0)
    traced = {}

    def record(fn, args, donate_argnums=(), label=None):
        text = canonical_text(jax.make_jaxpr(fn)(*args).jaxpr)
        traced[fn.__name__ + "." + hashlib.sha256(
            repr(jax.tree_util.tree_map(
                lambda a: (tuple(a.shape), str(a.dtype)), args)
            ).encode()).hexdigest()[:8]] = text
        return None

    engine_mod.aot_compile = record
    bench = manifest.benchmark()
    out = {}
    for entry in bench["configs"]:
        if names and entry["name"] not in names:
            continue
        cell = next(w["name"] for w in bench["workloads"]
                    if w["config"] == entry["name"])
        cell = manifest.Cell(cell)
        if not cell.kind.startswith("serve"):
            continue
        buckets = sorted({b for w in bench["workloads"]
                          if w["config"] == entry["name"]
                          for b in manifest.Cell(w["name"]).traffic[
                              "prompt_buckets"]})
        driver = manifest.driver(cell.kind)
        _abstract_weights()
        job = _Job(cell, buckets)
        traced.clear()
        driver.build(job).warmup()
        out[entry["name"]] = {
            name: text if keep_text
            else hashlib.sha256(text.encode()).hexdigest()
            for name, text in sorted(traced.items())}
    return out


def canonical_text(jaxpr) -> str:
    """A traced program written out with every sub-program in full where
    it is used and each one's variables numbered from its own start, so
    that two programs holding the same operations read the same whether or
    not their calls share one traced sub-program: ``jit``'s cache hands
    every call at one geometry the SAME kernel body, which JAX's own
    printer then binds to a name once and numbers by object."""
    import re

    from jax._src import core

    lines = []

    def walk(jaxpr, pad):
        names = {}

        def ref(v):
            kind = v.aval.str_short()
            if isinstance(v, core.Literal):
                return "%r:%s" % (v.val, kind)
            return "%s:%s" % (names.setdefault(v, "v%d" % len(names)), kind)

        lines.append("%s{ lambda %s ; %s . let" % (
            pad, " ".join(map(ref, jaxpr.constvars)),
            " ".join(map(ref, jaxpr.invars))))
        for eqn in jaxpr.eqns:
            plain, subs = [], []
            for key, val in sorted(eqn.params.items()):
                held = list(core.jaxprs_in_params({key: val}))
                if held:
                    subs += [(key, sub) for sub in held]
                else:
                    plain.append("%s=%s" % (key, val))
            ins = " ".join(map(ref, eqn.invars))
            lines.append("%s  %s = %s[%s] %s" % (
                pad, " ".join(map(ref, eqn.outvars)), eqn.primitive.name,
                " ".join(plain), ins))
            for key, sub in subs:
                lines.append("%s    %s:" % (pad, key))
                walk(sub, pad + "      ")
        lines.append("%s  in %s }" % (pad, " ".join(map(ref, jaxpr.outvars))))

    walk(jaxpr, "")
    return re.sub(r" at 0x[0-9a-f]+", "", "\n".join(lines))


def _abstract(fn):
    import jax

    return lambda *a, **kw: jax.eval_shape(lambda: fn(*a, **kw))


def _abstract_weights() -> None:
    """Every ``init_params`` a driver's ``build`` could import answers in
    shapes (but GPT-2 small's, which its driver calls inside a ``jit`` and
    which is 250 MB)."""
    import importlib
    import pkgutil

    import paddle_tpu.models as models

    for info in pkgutil.iter_modules(models.__path__):
        if info.name == "decoder_lm":
            continue
        mod = importlib.import_module("paddle_tpu.models." + info.name)
        init = vars(mod).get("init_params")
        if init is not None and not getattr(init, "_abstract", False):
            mod.init_params = _abstract(init)
            mod.init_params._abstract = True
            for cls in vars(mod).values():    # the class's bound copy too
                if isinstance(cls, type) and "init_params" in vars(cls):
                    cls.init_params = staticmethod(mod.init_params)


class _Job:
    """What a driver's ``build`` reads of a job."""

    seed = 7

    def __init__(self, cell, buckets):
        self.config = cell.config
        self.traffic = dict(cell.traffic, prompt_buckets=buckets)


def compare(parent: str, names: Optional[List[str]], out_dir: str
            ) -> List[str]:
    """The executables that differ between ``parent`` and this tree, as
    ``config/executable``; a configuration only one tree has is not
    compared."""
    sides = {}
    for side, root in (("parent", parent), ("change", ROOT)):
        sides[side] = _child(root, names, None)
    differ = []
    for cfg in sorted(set(sides["parent"]) & set(sides["change"])):
        a, b = sides["parent"][cfg], sides["change"][cfg]
        for exe in sorted(set(a) | set(b)):
            same = a.get(exe) == b.get(exe)
            print("%-32s %-24s %s" % (cfg, exe, "equal" if same else
                                      "DIFFERS"))
            if not same:
                differ.append("%s/%s" % (cfg, exe))
    if differ:
        os.makedirs(out_dir, exist_ok=True)
        for side, root in (("parent", parent), ("change", ROOT)):
            texts = _child(root, sorted({d.split("/")[0] for d in differ}),
                           True)
            for cfg, exes in texts.items():
                for exe, text in exes.items():
                    if "%s/%s" % (cfg, exe) in differ:
                        with open(os.path.join(
                                out_dir, "%s.%s.%s.txt" % (cfg, exe, side)),
                                "w") as f:
                            f.write(text)
    return differ


def _child(root: str, names: Optional[List[str]], keep_text) -> Dict:
    """``trace_tree`` of the tree at ``root``, in a process of its own
    (both trees are one package name). The tool's own file is this tree's:
    the parent may not have it."""
    cmd = [sys.executable, os.path.abspath(__file__), "--dump"]
    if names:
        cmd += ["--configs", ",".join(names)]
    if keep_text:
        cmd += ["--text"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    done = subprocess.run(cmd, cwd=root, env=env, stdout=subprocess.PIPE,
                          check=True)
    return json.loads(done.stdout.decode().strip().split("\n")[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a checkout of the tree to compare with")
    ap.add_argument("--configs", default="",
                    help="comma-separated configuration names (default: "
                         "every served one)")
    ap.add_argument("--out", default=os.path.join(ROOT, "grid_out",
                                                  "compare_lowering"))
    ap.add_argument("--dump", action="store_true",
                    help="(child) trace the tree at the working directory")
    ap.add_argument("--text", action="store_true",
                    help="(child) print the texts, not their digests")
    args = ap.parse_args(argv)
    names = [n for n in args.configs.split(",") if n] or None
    if args.dump:
        sys.path.insert(0, os.getcwd())
        print(json.dumps(trace_tree(names, args.text)))
        return 0
    if not args.parent:
        ap.error("--parent is required")
    differ = compare(os.path.abspath(args.parent), names, args.out)
    print("%d executables differ%s" % (
        len(differ), ": texts under %s" % args.out if differ else ""))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())

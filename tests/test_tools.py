"""The tool surface the documents promise: every smoke gate
``tools/ci_smokes.py`` lists resolves to a module with a ``--selftest``
entry, and README.md, ROADMAP.md's smoke paragraphs and the verify skill
name only tools that are files and only environment variables the source
reads."""

import importlib
import inspect
import os
import re

import pytest

from tools import ci_smokes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


@pytest.mark.parametrize("label", [label for label, _ in ci_smokes.GATES])
def test_ci_smoke_gate_resolves(label):
    gates = dict(ci_smokes.GATES)
    assert sorted(ci_smokes.BUDGETS) == sorted(gates)
    src = inspect.getsource(importlib.import_module(gates[label]))
    assert '"--selftest"' in src and '__name__ == "__main__"' in src


def _paragraph(text, start):
    """The paragraph of ``text`` that opens with ``start``."""
    return text[text.index(start):].split("\n\n", 1)[0]


def test_documents_name_only_what_exists():
    readme, roadmap = _read("README.md"), _read("ROADMAP.md")
    docs = {
        "README.md": readme,
        "ROADMAP.md Fast smoke": _paragraph(roadmap, "**Fast smoke"),
        "ROADMAP.md Chip smoke": _paragraph(roadmap, "**Chip smoke"),
        "verify skill": _read(".claude", "skills", "verify", "SKILL.md"),
    }
    missing = sorted(
        (doc, name) for doc, text in docs.items()
        for name in set(re.findall(r"\btools[./]([a-z_][a-z0-9_]*)", text))
        if not os.path.isfile(os.path.join(REPO, "tools", name + ".py")))
    assert not missing, "documents name tools that are not files: %r" % missing

    source = [_read("bench.py"), _read("chip_smoke.py")]
    for top in ("paddle_tpu", "tools", "grid"):
        for root, _dirs, files in os.walk(os.path.join(REPO, top)):
            source += [_read(root, f) for f in files if f.endswith(".py")]
    read = set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", "\n".join(source)))
    # a name that ends in an underscore is a prefix, in the README
    # (`PADDLE_TPU_PASS_*`) and in the source, which composes the pass
    # gates as "PADDLE_TPU_PASS_" + name
    prefixes = tuple(r for r in read if r.endswith("_"))
    unread = sorted(
        name for name in set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", readme))
        if not (name in read or name.startswith(prefixes)
                or name.endswith("_")
                and any(r.startswith(name) for r in read)))
    assert not unread, "README.md names variables nothing reads: %r" % unread


def test_compare_lowering_reads_a_shared_trace_as_the_program_it_is():
    """``canonical_text``: three calls that share ONE traced sub-program
    (``jit``'s cache) read as the same program as three that traced their
    own, which JAX's own printer tells apart (it binds a shared
    sub-program to a name once); a different operation still reads
    different."""
    import jax
    import jax.numpy as jnp

    from tools.compare_lowering import canonical_text

    def body(x, scale):
        return jax.lax.fori_loop(0, 3, lambda i, y: y * scale + i, x)

    shared = jax.jit(body, static_argnums=1, inline=True)

    def own(x):
        return body(body(body(x, 2.0), 2.0), 2.0)

    def cached(x):
        return shared(shared(shared(x, 2.0), 2.0), 2.0)

    def other(x):
        return shared(shared(shared(x, 2.0), 2.0), 3.0)

    x = jnp.ones((4,), jnp.float32)
    texts = {f.__name__: jax.make_jaxpr(f)(x) for f in (own, cached, other)}
    assert str(texts["own"]) != str(texts["cached"])
    canon = {k: canonical_text(v.jaxpr) for k, v in texts.items()}
    assert canon["own"] == canon["cached"] != canon["other"]
    assert " at 0x" not in canon["own"]


def test_compare_lowering_tells_an_executable_that_changed(monkeypatch,
                                                           tmp_path, capsys):
    """``tools/compare_lowering.py``: a real child traces the smallest
    served configuration's executables (the chunk and every bucket's
    prefill of both its cells, a digest each), and the comparison names
    exactly the executable whose text differs between two trees, writes
    both texts for ``diff`` and says so by its exit code."""
    from tools import compare_lowering as cl

    traced = cl._child(REPO, ["gpt2-small-serve"], None)
    exes = traced["gpt2-small-serve"]
    assert sum(name.startswith("chunk.") for name in exes) == 1
    assert sum(name.startswith("prefill.") for name in exes) >= 3
    assert all(re.fullmatch(r"[0-9a-f]{64}", d) for d in exes.values())

    def child(root, names, keep_text):
        return {"cfg": {"chunk.a": "same", "prefill.b": "text of " + root}}

    monkeypatch.setattr(cl, "_child", child)
    differ = cl.compare("/parent", None, str(tmp_path))
    assert differ == ["cfg/prefill.b"]
    assert "DIFFERS" in capsys.readouterr().out
    assert sorted(os.listdir(tmp_path)) == [
        "cfg.prefill.b.change.txt", "cfg.prefill.b.parent.txt"]
    monkeypatch.setattr(cl, "_child", lambda *a: {"cfg": {"chunk.a": "x"}})
    assert cl.compare("/parent", None, str(tmp_path)) == []
    assert cl.main(["--parent", "/parent"]) == 0

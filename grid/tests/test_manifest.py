"""The data files agree with BENCHMARK.json, every ``moves`` arrow lands,
and a later PR can add a cell, a configuration, a traffic mix and a metric
by adding files and entries, without touching a file that is there."""

import hashlib
import json
import os

from grid import manifest

from conftest import ROOT


def _cells(root=None):
    return [manifest.Cell(w["name"], root)
            for w in manifest.benchmark(root)["workloads"]]


def test_cells_and_manifest_agree():
    bench = manifest.benchmark()
    declared = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    cells = _cells()
    assert {c.name for c in cells} == {
        "tfbase-train-1chip", "gpt2s-chat-sat", "gpt2s-doc-steady",
        "tfbase-train-dp4", "gpt2s-chat-steady", "smallthinker-mixed-sat",
        "kimi-k2-longctx-sat", "laguna-s-code-sat"}
    for name, m in declared.items():
        reporting = [c.name for c in cells if name in c.cell["reports"]]
        assert reporting, name
        assert m.get("workloads", [c.name for c in cells]) == reporting, name
        spec = json.load(open(os.path.join(ROOT, "grid", "metrics",
                                           name + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec.get(key) == m.get(key), (name, key)
        assert callable(manifest.reader(spec["reader"]))
    for c in cells:
        assert "setup_s" in c.reported(False) and len(c.reported(False)) >= 2
        assert c.reported(True)
        assert len(c.cell["why"]) <= 200
        entry = next(e for e in bench["configs"]
                     if e["name"] == c.cell["config"])
        assert c.config["reduced"] == entry["reduced"] \
            and c.config["source"] == entry["source"]
        assert c.config["assumed"] and c.config["deployment"]


def test_every_moves_names_a_metric_its_cells_report():
    bench = manifest.benchmark()
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end, m["name"]
        for c in _cells():
            if m["name"] in c.cell["reports"]:
                assert m["moves"] in c.cell["reports"], (m["name"], c.name)


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "grid")):
        for f in files:
            p = os.path.join(dirpath, f)
            out[p] = hashlib.sha1(open(p, "rb").read()).hexdigest()
    return out


def test_adding_needs_only_new_files_and_new_entries(toy_root):
    before = _digest(toy_root)
    grid = os.path.join(toy_root, "grid")

    def add(path, doc):
        assert not os.path.exists(os.path.join(grid, path))
        with open(os.path.join(grid, path), "w") as f:
            json.dump(doc, f)

    add("configs/dummy-serve.json", dict(
        json.load(open(os.path.join(grid, "configs",
                                    "gpt2-small-serve.json"))),
        name="dummy-serve"))
    add("traffic/dummy-mix.json", dict(
        json.load(open(os.path.join(grid, "traffic", "chat-sat.json"))),
        arrivals={"process": "uniform", "rate_per_s": 8.0,
                  "schedule_seed": 3}))
    add("metrics/dummy_cycles.json", {
        "name": "dummy_cycles", "unit": "cycles", "better": "higher",
        "source": "program_counter", "layer": "engine host loop",
        "moves": "tpot_p50_ms", "reader": "dummy.cycles"})
    os.makedirs(os.path.join(grid, "readers"))
    with open(os.path.join(grid, "readers", "dummy.py"), "w") as f:
        f.write("def cycles(record, trace=None):\n"
                "    return len(record['cycles'])\n")
    add("cells/dummy-cell.json", {
        "name": "dummy-cell", "config": "dummy-serve",
        "traffic": "dummy-mix", "chips": 1, "why": "a dummy",
        "reports": ["tpot_p50_ms", "setup_s", "dummy_cycles"]})
    bench = manifest.benchmark(toy_root)
    bench["configs"].append({"name": "dummy-serve", "source": "none",
                             "file": "grid/configs/dummy-serve.json",
                             "reduced": [], "why": "a dummy"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-serve",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a dummy"})
    bench["per_layer"].append({"name": "dummy_cycles", "unit": "cycles",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine host loop",
                               "moves": "tpot_p50_ms",
                               "workloads": ["dummy-cell"]})
    with open(os.path.join(toy_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digest(toy_root)
    assert all(after[p] == h for p, h in before.items())   # nothing edited
    cell = manifest.Cell("dummy-cell", toy_root)
    assert cell.kind == "serve" and cell.traffic["arrivals"]["rate_per_s"] == 8.0
    assert cell.reported(True) == ["dummy_cycles"]
    # the copy's readers directory is not the one that is imported: the
    # reader is found by name once its file sits in grid/readers
    assert cell.metrics["dummy_cycles"]["reader"] == "dummy.cycles"
    # the cells that were there still load
    assert len(_cells(toy_root)) == 9


def test_benchmark_json_keeps_to_the_contracts_form():
    import re

    bench = manifest.benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["grid"] and 1 <= bench["run_seconds"] <= 51
    assert set(map(frozenset, map(dict.keys, bench["configs"]))) \
        == {frozenset({"name", "source", "file", "reduced", "why"})}
    assert set(map(frozenset, map(dict.keys, bench["workloads"]))) \
        == {frozenset({"name", "config", "traffic", "chips", "why"})}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    line = re.compile(r"^[^\t\n\r]{1,200}$")
    configs = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(configs)) == len(configs) == len(set(files)) <= 24
    for c in bench["configs"]:
        assert name.match(c["name"]) and c["file"].startswith("grid/")
        assert line.match(c["source"]) and line.match(c["why"])
        assert len(c["reduced"]) <= 16 and all(map(name.match, c["reduced"]))
    assert 1 <= len(bench["workloads"]) <= 24
    for w in bench["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert line.match(w["why"]) and w["chips"] in (1, 4)
        assert w["config"] in configs
    # a pair of configuration and traffic appears once (PR 24's refusal)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len(bench["command"]) <= 32 and all(map(line.match,
                                                   bench["command"]))
    # a full check with all 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = []
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    assert "setup_s" in names and len(names) == len(set(names))
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
        assert line.match(m.get("layer", "-"))
    # a file under paths is named from the characters of a name and "/"
    path = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for dirpath, dirs, names_ in os.walk(os.path.join(ROOT, "grid")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in names_:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert path.match(rel), rel
    assert len(json.dumps(bench)) < 64 * 1024

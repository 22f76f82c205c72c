"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell, a configuration, a traffic mix and a metric are each a data file
of their own, so a later PR adds one by adding files and one entry in
``BENCHMARK.json`` and never edits a file that is there:

    grid/cells/<cell>.json       config, traffic, chips, why, reports
    grid/configs/<config>.json   sizes as run, source, reduced, assumed, kind
    grid/traffic/<traffic>.json  every parameter of the mix, why, who
    grid/metrics/<metric>.json   unit, better, source, layer, moves, reader
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List

GRID_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(GRID_DIR)


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = None) -> Dict[str, Any]:
    return _load(os.path.join(root or ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, name: str, root: str = None):
        root = root or ROOT
        grid = os.path.join(root, "grid")
        bench = benchmark(root)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError("BENCHMARK.json has no workload %r (it has %s)"
                           % (name, [w["name"] for w in bench["workloads"]]))
        self.name = name
        self.chips = int(entry["chips"])
        self.cell = _load(os.path.join(grid, "cells", name + ".json"))
        for key in ("config", "traffic", "chips"):
            if self.cell[key] != entry[key]:
                raise ValueError(
                    "grid/cells/%s.json and BENCHMARK.json differ on %r: "
                    "%r != %r" % (name, key, self.cell[key], entry[key]))
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        self.config = _load(os.path.join(root, cfg_entry["file"]))
        self.traffic = _load(os.path.join(grid, "traffic",
                                          entry["traffic"] + ".json"))
        self.kind = self.config["kind"]
        declared = {m["name"]: m
                    for m in bench["end_to_end"] + bench["per_layer"]}
        self.end_to_end = [m["name"] for m in bench["end_to_end"]]
        self.metrics = {}
        for m in self.cell["reports"]:
            if m not in declared:
                raise KeyError("cell %s reports %r, which BENCHMARK.json "
                               "does not declare" % (name, m))
            self.metrics[m] = _load(os.path.join(grid, "metrics",
                                                 m + ".json"))

    def reported(self, traced: bool) -> List[str]:
        """The metrics of one run's last line: the cell's end-to-end ones
        with ``--trace 0``, its per-layer ones with ``--trace 1``."""
        return [m for m in self.cell["reports"]
                if (m in self.end_to_end) != traced]


def driver(kind: str):
    """``grid/drivers/<kind>.py``: the module that runs a configuration of
    that kind."""
    return importlib.import_module("grid.drivers." + kind)


def reader(spec: str) -> Callable:
    """``"<module>.<function>"`` under ``grid/readers/``."""
    module, _, fn = spec.rpartition(".")
    return getattr(importlib.import_module("grid.readers." + module), fn)

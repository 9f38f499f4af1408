"""Finds every piece of a cell by the names in ``BENCHMARK.json``.

- ``configs/<config>.json``: the model, its sizes as run, the nodes on the
  chip and the gossip contraction, what was cut and assumed; it names its
  reference (``reference/<name>.py``) and FLOP count (``flops/<name>.py``);
- ``traffic/<mix>.json``: algorithm, hyperparameters, topology, scenario,
  batch, sequence and chunk;
- ``limits/<workload>.json``: the limit of each number ``correct`` compares;
- ``layers/<metric>.py``: the reader of one per-layer metric;
- ``probes/<name>.py``: a program run alone, which readers share.

A new cell, configuration, mix or metric is new files plus entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
SPEC_PATH = os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list   # BENCHMARK.json entries; every cell reports them all
    per_layer: list    # a reader with nothing to read in a cell returns None


class Suite:
    """The benchmark's files under ``root`` (``bench/`` by default)."""

    def __init__(self, root: str = BENCH_DIR, spec_path: str = SPEC_PATH):
        self.root = root
        with open(spec_path) as f:
            self.spec = json.load(f)

    def data(self, kind: str, name: str) -> dict:
        path = os.path.join(self.root, kind, f"{name}.json")
        with open(path) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        """The code file ``<root>/<kind>/<name>.py``, loaded once."""
        path = os.path.join(self.root, kind, f"{name}.py")
        mod_name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, self.root))
        mod = sys.modules.get(mod_name)
        if mod is None or getattr(mod, "__file__", None) != path:
            spec = importlib.util.spec_from_file_location(mod_name, path)
            if spec is None:
                raise FileNotFoundError(path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
        return mod

    def cell(self, workload: str) -> Cell:
        entries = [w for w in self.spec["workloads"] if w["name"] == workload]
        if len(entries) != 1:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = entries[0]
        return Cell(
            name=workload,
            chips=int(w["chips"]),
            config=self.data("configs", w["config"]),
            traffic=self.data("traffic", w["traffic"]),
            limits=self.data("limits", workload),
            end_to_end=list(self.spec["end_to_end"]),
            per_layer=list(self.spec["per_layer"]),
        )

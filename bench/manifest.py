"""``BENCHMARK.json`` and the files it names, found by name.

A cell is ``bench/workloads/<cell>.json`` (its check), run on the
configuration ``bench/configs/<config>.json`` under the traffic
``bench/traffic/<traffic>.json``; a per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of them is adding a file and an
entry: no code here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
HERE = os.path.dirname(os.path.abspath(__file__))


class Manifest:
    def __init__(self, root: str):
        """``root``: the checkout, holding ``BENCHMARK.json``."""
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def _json(self, *parts) -> dict:
        with open(os.path.join(self.bench_dir, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        return self._json("traffic", cell["traffic"] + ".json")

    def check(self, cell: dict) -> dict:
        return self._json("workloads", cell["name"] + ".json")["check"]

    def _reported(self, kind: str, cell_name: str) -> list[dict]:
        return [m for m in self.data[kind]
                if cell_name in m.get("workloads", [cell_name])]

    def end_to_end(self, cell_name: str) -> list[dict]:
        return self._reported("end_to_end", cell_name)

    def per_layer(self, cell_name: str) -> list[dict]:
        return self._reported("per_layer", cell_name)

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Imports nothing heavier than ``json``: the command's parent uses it and
must stay off JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and the
    metrics it reports, each read from its own file."""

    def __init__(self, name, root=ROOT):
        bench = benchmark(root)
        found = [w for w in bench["workloads"] if w["name"] == name]
        if len(found) != 1:
            raise SystemExit(f"chip_bench: no workload {name!r} in "
                             f"BENCHMARK.json ({len(found)} entries)")
        self.name = name
        self.chips = found[0]["chips"]
        config = [c for c in bench["configs"]
                  if c["name"] == found[0]["config"]][0]
        self.config_name = config["name"]
        self.sizes = _load_json(os.path.join(root, config["file"]))
        self._config_dir = os.path.dirname(os.path.join(root, config["file"]))
        bench_dir = os.path.join(root, bench["paths"][0])
        self.traffic_name = found[0]["traffic"]
        self.traffic = _load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        self._metrics_dir = os.path.join(bench_dir, "metrics")

        def reported(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if reported(m)]
        self.per_layer = [m for m in bench["per_layer"] if reported(m)]

    def config_module(self):
        """The Python beside the configuration's sizes: ``<name>.py``, or the
        file named by the sizes' ``module`` key where two configurations share
        one model."""
        stem = self.sizes.get("module", self.config_name)
        path = os.path.join(self._config_dir, stem + ".py")
        spec = importlib.util.spec_from_file_location(
            "chip_bench_config_" + stem.replace("-", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, metric_name):
        """The parameters of one per-layer metric's reader."""
        return _load_json(os.path.join(self._metrics_dir,
                                       metric_name + ".json"))

"""``BENCHMARK.json`` and the files it names, found by name:

* configuration ``<c>``: ``bench/configs/<c>.json``; its ``kind`` names the
  plain reference ``bench/reference/<kind>.py``, the cost model
  ``bench/costs/<kind>.py`` and the program adapter
  ``bench/adapters/<kind>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a ``read(ctx)``.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with everything it needs."""

    def __init__(self, bench: dict, name: str, data_dir: str = BENCH_DIR):
        by = {w["name"]: w for w in bench["workloads"]}
        if name not in by:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(by)}")
        self.workload = w = by[name]
        self.name = name
        self.chips = int(w["chips"])
        self.config = dict(_json(os.path.join(data_dir, "configs",
                                              w["config"] + ".json")),
                           name=w["config"])
        self.mix = _json(os.path.join(data_dir, "traffic",
                                      w["traffic"] + ".json"))
        self.kind = self.config["kind"]

        def listed(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bench["end_to_end"] if listed(m)]
        self.per_layer = [m for m in bench["per_layer"] if listed(m)]

    def module(self, part: str):
        """``part`` in reference | costs | adapters, for this config's kind."""
        return load_module(os.path.join(BENCH_DIR, part,
                                        self.kind + ".py"),
                           f"{part}_{self.kind}")

    def reader(self, metric: str):
        return load_module(os.path.join(BENCH_DIR, "metrics",
                                        metric + ".py"), "metric_" + metric)


def load_bench(root: str) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))

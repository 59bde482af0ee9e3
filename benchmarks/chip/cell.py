"""A cell as data: `BENCHMARK.json`'s entry, with the files it names.

  configs/<config>.json   the model as run (sizes, AMC modes, source)
  traffic/<traffic>.json  the traffic mix's parameters (traffic.py)
  cells/<cell>.json       the serving shape (max_batch, max_seq), the
                          number of requests the comparison samples, and
                          the limit of each number compared
  metrics/<metric>.py     one reader per per-layer metric

Everything is found by the names in `BENCHMARK.json`; adding a cell, a
mix or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    shape: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: str

    def reader(self, metric: str):
        """The `read(ctx)` function of metrics/<metric>.py."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "metric_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, *, root: str = ROOT, bench_dir: str = HERE) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, its files read from
    `bench_dir`."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    from traffic import load as load_traffic
    return Cell(
        name=name, chips=w["chips"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_traffic(os.path.join(bench_dir, "traffic",
                                          w["traffic"] + ".json")),
        shape=_load_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)

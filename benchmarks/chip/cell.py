"""A cell as data: `BENCHMARK.json`'s entry, with the files it names.

  configs/<config>.json   the model as run (sizes, AMC modes, source),
                          and under `reference` the path, from the repo
                          root, of its plain reference module
  references/<name>.py    a plain reference, shared by the configurations
                          of one model family: `Dims.from_config(conf)`,
                          `Dims.from_arch(cfg)`, `check(cfg, conf)`,
                          `make_weights(dims, seed)`, `served_gaps(dims,
                          w, prompt, served, *, control=False)` and
                          `decode_token_flops(dims, context)`
  traffic/<traffic>.json  the traffic mix's parameters (traffic.py)
  cells/<cell>.json       the serving shape (max_batch, max_seq), the
                          number of requests the comparison samples, and
                          the limit of each number compared
  metrics/<metric>.py     one reader per per-layer metric

Everything is found by the names in `BENCHMARK.json`; adding a cell, a
mix, a metric, or a configuration of another family with its reference,
adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    reference: ModuleType
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


def _load_reference(config: str, conf: dict, root: str) -> ModuleType:
    """The module the configuration file names under `reference`, imported
    once per path in a process (so its compiled programs are reused); a
    configuration without one is an error."""
    if "reference" not in conf:
        raise KeyError(f"configuration {config!r} names no reference "
                       f"module (key 'reference' of its file)")
    path = os.path.realpath(os.path.join(root, conf["reference"]))
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config!r}: reference "
                                f"{conf['reference']!r} is not a file")
    name = "reference:" + path
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod          # dataclasses look their module up
        spec.loader.exec_module(mod)
    return sys.modules[name]


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
    conf = _load_json(os.path.join(root, cfg_entry["file"]))
    from traffic import load as load_traffic
    return Cell(
        name=name, chips=w["chips"], config=conf,
        reference=_load_reference(w["config"], conf, root),
        traffic=load_traffic(os.path.join(bench_dir, "traffic",
                                          w["traffic"] + ".json")),
        shape=_load_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)

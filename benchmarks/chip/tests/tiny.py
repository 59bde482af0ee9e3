"""A small cell for the CPU: granite-3-2b's family and code path at test
widths, defined by files in a temporary copy of the benchmark."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil

from conftest import BENCH

DENSE = "benchmarks/chip/references/dense_decoder.py"
TINY_CONFIG = {
    "repro_arch": "granite-3-2b", "reference": DENSE, "source": "test widths",
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 512, "rope_theta": 10000.0,
    "amc": {"weight_mode": "dual", "kv_mode": "int4",
            "pool_mode": "always-augmented", "page_size": 16},
    "reduced": ["test widths"]}
TRAFFIC = {
    "decode": {"arrivals": {"kind": "backlog"},
               "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8,
                                 "max": 60},
               "output_tokens": {"median": 20, "sigma": 0.4, "min": 6,
                                 "max": 40},
               "size_seed": 1, "n_requests": 64},
    "chat": {"arrivals": {"kind": "poisson", "rate_per_s": 4.0},
             "prompt_tokens": {"median": 24, "sigma": 0.8, "min": 4,
                               "max": 60},
             "output_tokens": {"median": 20, "sigma": 0.5, "min": 8,
                               "max": 40},
             "size_seed": 2, "n_requests": 64}}


def arch():
    from repro.configs import get_arch
    base = get_arch("granite-3-2b").reduced()
    return dataclasses.replace(base, n_kv_heads=2)


def add_config(root: str, b: dict, name: str, conf: dict) -> None:
    """Write configs/<name>.json under `root` and its entry into the
    BENCHMARK.json object `b`."""
    with open(os.path.join(root, "benchmarks", "chip", "configs",
                           name + ".json"), "w") as f:
        json.dump(conf, f)
    b["configs"].append({"name": name, "source": "test",
                         "file": f"benchmarks/chip/configs/{name}.json",
                         "reduced": [], "why": "test"})


def add_workload(root: str, b: dict, config: str, mix: str,
                 limit: float | None = 0.05) -> str:
    """Write the cell file of `<config>.<mix>` (the tiny serving shape, the
    traffic `tiny-<mix>`) and its entries into `b`; returns its name."""
    name = f"{config}.{mix}"
    with open(os.path.join(root, "benchmarks", "chip", "cells",
                           name + ".json"), "w") as f:
        json.dump({"max_batch": 4, "max_seq": 128, "sample_requests": 3,
                   "limits": {"max_logit_gap": limit}}, f)
    b["workloads"].append({"name": name, "config": config,
                           "traffic": f"tiny-{mix}", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            fam = "decode" if any(w.endswith(".decode")
                                  for w in m["workloads"]) else "chat"
            if fam == mix:
                m["workloads"].append(name)
    return name


def read_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def write_bench(root: str, b: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)


def make(tmp: str, limit: float | None = 0.05) -> str:
    """A benchmark root under `tmp` whose BENCHMARK.json adds the tiny
    cells `tiny.decode` and `tiny.chat`; returns the root."""
    bench = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    b = read_bench(os.path.dirname(os.path.dirname(BENCH)))
    add_config(tmp, b, "tiny", TINY_CONFIG)
    for mix, spec in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", f"tiny-{mix}.json"),
                  "w") as f:
            json.dump(spec, f)
        add_workload(tmp, b, "tiny", mix, limit)
    write_bench(tmp, b)
    return tmp


def load(root: str, name: str):
    import cell
    return cell.load(name, root=root,
                     bench_dir=os.path.join(root, "benchmarks", "chip"))

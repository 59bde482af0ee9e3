"""A small cell for the CPU: granite-3-2b's family and code path at test
widths, defined by files in a temporary copy of the benchmark."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil

from conftest import BENCH

TINY_CONFIG = {
    "repro_arch": "granite-3-2b", "source": "test widths",
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


def make(tmp: str, limit: float | None = 0.05) -> str:
    """A benchmark root under `tmp` whose BENCHMARK.json adds the tiny
    cells `tiny.decode` and `tiny.chat`; returns the root."""
    bench = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                           "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"})
    for mix, spec in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", f"tiny-{mix}.json"),
                  "w") as f:
            json.dump(spec, f)
        with open(os.path.join(bench, "cells", f"tiny.{mix}.json"),
                  "w") as f:
            json.dump({"max_batch": 4, "max_seq": 128, "sample_requests": 3,
                       "limits": {"max_logit_gap": limit}}, f)
        b["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                               "traffic": f"tiny-{mix}", "chips": 1,
                               "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            fam = "decode" if any(w.endswith(".decode")
                                  for w in m["workloads"]) else "chat"
            m["workloads"].append(f"tiny.{fam}")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return tmp


def load(root: str, name: str):
    import cell
    return cell.load(name, root=root,
                     bench_dir=os.path.join(root, "benchmarks", "chip"))

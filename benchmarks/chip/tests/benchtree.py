"""Builds a throwaway checkout for a test: a copy of the benchmark's files,
the test-sized configurations and mixes from ``fixtures/``, and a
BENCHMARK.json holding the cells a test names."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))


def build(tmp, cells, extra_files=()):
    """``cells``: (name, config, traffic, chips); configurations and mixes
    are looked up in the fixtures first, then in the benchmark itself.
    Returns (bench_path, root)."""
    tmp = str(tmp)
    root = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub in ("configs", "traffic"):
        src = os.path.join(HERE, "fixtures", sub)
        for f in os.listdir(src):
            shutil.copy(os.path.join(src, f), os.path.join(root, sub, f))
    for rel, text in extra_files:
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = {c for _, c, _, _ in cells}
    spec["configs"] = [
        {"name": c, "source": "test", "file": f"benchmarks/chip/configs/{c}.json",
         "reduced": [], "why": "test"} for c in sorted(names)
    ]
    spec["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": chips, "why": "test"}
        for n, c, t, chips in cells
    ]
    cellnames = [n for n, _, _, _ in cells]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = cellnames
    bench_path = os.path.join(tmp, "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(spec, f, indent=1)
    return bench_path, root

"""The benchmark's cells shrunk to a size a CPU test run holds: fewer
devices, candidates and samples, one local epoch, no VGG-16; the same
files, traffic and code paths otherwise."""

from __future__ import annotations

import argparse
import copy

from bench.harness import cells

# Built and tested here, not yet in BENCHMARK.json (no proven chip run).
PENDING = {"train-groupA": {"name": "train-groupA", "config": "paper-group-a",
                            "traffic": "fl-steady", "chips": 1}}


def tiny_cell(name: str, root: str = cells.ROOT) -> cells.Cell:
    known = {w["name"] for w in cells.benchmark(root)["workloads"]}
    c = (cells.load_cell(name, root) if name in known
         else cells.make_cell(PENDING[name], root))
    cfg = copy.deepcopy(c.config)
    cfg["scheduler"]["candidates"] = 32
    cfg["num_devices"], cfg["n_sel"] = 20, 4
    cfg["partition"]["parts_per_class"] = 4
    cfg["eval_samples"] = 64
    cfg["jobs"] = [dict(j, num_samples=40 * j["num_classes"], local_epochs=1)
                   for j in cfg["jobs"] if j["model"] != "paper-vgg16"]
    return cells.Cell(c.name, cfg, c.traffic, c.chips, c.end_to_end,
                      c.per_layer)


def args(name: str, seed: int = 2**33 + 7, seconds: float = 2.0,
         trace: int = 0) -> argparse.Namespace:
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)

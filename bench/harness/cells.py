"""Cells from data: ``BENCHMARK.json`` names each cell's configuration and
traffic mix; both are JSON files found by name, so a cell, a configuration
or a traffic mix is added by adding files.

    bench/configs/<config>.json   the deployment (sizes, jobs, scheduler, limits)
    bench/traffic/<traffic>.json  the mix (runtime, warm-up, window, sampling)

``build_spec`` is the one generator: it turns a configuration, a traffic
mix and a seed into the program's ``ExperimentSpec``. Every seed the
program sees is derived here from ``--seed``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple          # names of the end-to-end metrics it reports
    per_layer: tuple           # names of the per-layer metrics it reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"unknown workload {name!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    return make_cell(entry, root)


def make_cell(entry: dict, root: str = ROOT) -> Cell:
    """The cell of one ``workloads`` entry (name, config, traffic, chips)."""
    bench = benchmark(root)
    name = entry["name"]
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     entry["traffic"] + ".json"))
    return Cell(
        name=name, config=config, traffic=traffic, chips=int(entry["chips"]),
        end_to_end=tuple(m["name"] for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m["name"] for m in bench["per_layer"]
                        if _reports(m, name)))


def seeds(seed: int) -> Dict[str, int]:
    """Independent 32-bit streams from one ``--seed`` of any size."""
    names = ("pool", "scheduler", "engine", "runtime", "data", "weights",
             "sample")
    words = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(w) for n, w in zip(names, words)}


def build_spec(config: dict, traffic: dict, seed: int):
    """The program's ``ExperimentSpec`` for one run of a cell."""
    from repro.experiment.spec import (CostSpec, ExperimentSpec, FleetSpec,
                                       JobSpec, PoolSpec)

    s = seeds(seed)
    jobs = tuple(
        JobSpec(name=j["name"], model=j["model"],
                target_metric=traffic["target_metric"],
                max_rounds=traffic["max_rounds"],
                local_epochs=j["local_epochs"],
                batch_size=j.get("batch_size", 32), lr=j.get("lr", 0.05),
                convergence_rate=j.get("convergence_rate"))
        for j in config["jobs"])
    pool = config["pool"]
    sched = config["scheduler"]
    runtime = traffic["runtime"]
    if runtime == "synthetic":
        runtime_kwargs = {"seed": s["runtime"]}
    else:
        runtime_kwargs = {"config": config, "seed": int(seed)}
    return ExperimentSpec(
        name=f"bench-{config['name']}",
        jobs=jobs,
        pool=PoolSpec(num_devices=config["num_devices"], seed=s["pool"],
                      a_range=tuple(pool["a_range"]),
                      mu_range=tuple(pool["mu_range"]),
                      data_range=tuple(pool["data_range"])),
        cost=CostSpec(alpha=config["cost"]["alpha"],
                      beta=config["cost"]["beta"]),
        fleet=FleetSpec(candidates=sched["candidates"],
                        scoring_backend=sched["scoring_backend"],
                        search_backend=sched["search_backend"]),
        scheduler=sched["name"], scheduler_seed=s["scheduler"],
        runtime=runtime, runtime_kwargs=runtime_kwargs,
        non_iid=config["partition"]["classes_per_device"] > 0,
        n_sel=config["n_sel"], over_provision=config["over_provision"],
        engine_seed=s["engine"])

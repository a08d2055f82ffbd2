"""``correct`` for one run: the sampled window decisions re-scored in
float64, and, for the real-training traffic, the first rounds of every job
against the plain federated reference. The program's device state is freed
before the reference runs, so the reference never sets the memory peak.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import jax.numpy as jnp

from bench.harness import checks, data, reference


def ref_rounds(cell) -> int:
    return int(cell.config.get("reference_rounds", 3))


def collect(run, cell) -> dict:
    """Everything the comparison needs, on the host; frees the program."""
    out = {"decisions": run.decision_sample(), "cost": run.cost_terms(),
           "seed": run.seed, "train": None}
    if cell.traffic["runtime"] == "bench_real_fl":
        n = ref_rounds(cell)
        out["train"] = [
            {"init": run.init[j],
             "prog": {"params": [run.snapshots[j][0],
                                 run.snapshots[j][n - 1]],
                      "loss": run.losses(j, n)},
             "cohorts": run.cohorts(j, n)}
            for j in range(len(cell.config["jobs"]))]
    run.engine = run.ex = None
    gc.collect()
    return out


def reference_training(got: dict, cell, seed: int,
                       dtype=jnp.float32, cohort_fn=None) -> List[dict]:
    """The reference (or, with ``dtype=bfloat16``, the control) over the
    program's cohorts, job by job; ``cohort_fn`` lets a test plant a fault
    in the reference put in the program's place."""
    cfg = cell.config
    out = []
    for j, (job, g) in enumerate(zip(cfg["jobs"], got["train"])):
        x, y, ex, ey = data.make_data(job, cfg, seed, j)
        part = data.noniid_partition(job, cfg, seed, j)
        w = data.make_weights(job, seed, j)
        cohorts = g["cohorts"] if cohort_fn is None else [
            cohort_fn(c) for c in g["cohorts"]]
        r = reference.fl_rounds(w, x, y, ex, ey, part, cohorts, job, dtype)
        del x, y, ex, ey
        out.append({"params": [r["params"][0], r["params"][-1]],
                    "loss": r["loss"]})
    return out


def numbers(got: dict, ref_train) -> Dict[str, float]:
    nums = checks.decision_numbers(got["decisions"], got["cost"], got["seed"])
    if got["train"] is not None:
        nums.update(checks.training_numbers([
            dict(g, ref=r) for g, r in zip(got["train"], ref_train)]))
    return nums


def check(run, cell) -> dict:
    got = collect(run, cell)
    ref = (reference_training(got, cell, run.seed)
           if got["train"] is not None else None)
    return checks.verdict(numbers(got, ref), cell.config["limits"])

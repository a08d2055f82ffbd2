"""Readings that set the limits of ``correct``: the program's numbers, the
control's and the planted faults', over many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5

For every seed it builds the cell, warms up, runs a short window at the
cell's own load and prints one JSON line:

- ``program``: the numbers a benchmark run compares;
- ``control``: the same numbers with the plain reference computed one
  precision lower (bfloat16) put in the program's place;
- ``faults``: the numbers with a fault planted in the reference put in the
  program's place: ``half_batch`` (each round averages only the first half
  of its cohort) for training; ``altered_plan`` (one chosen device swapped
  for the slowest unchosen one), ``half_plan`` (half the devices dropped)
  and ``random_plan`` (a search that returns any valid plan drawn from the
  seed, with its cost reported truly) for scheduling. A state left
  unchanged reads 1 by definition.

The benchmark's own runs never run this; it needs the chip, like them.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def altered_plan(d: dict) -> dict:
    plan = d["plan"].copy()
    on = plan.nonzero()[0]
    off_avail = (~plan & d["available"]).nonzero()[0]
    if len(on) and len(off_avail):
        plan[on[0]] = False
        plan[off_avail[d["times"][off_avail].argmax()]] = True
    return dict(d, plan=plan)


def half_plan(d: dict) -> dict:
    plan = d["plan"].copy()
    on = plan.nonzero()[0]
    plan[on[len(on) // 2:]] = False
    return dict(d, plan=plan)


def random_plan(d: dict, cost: dict, rng) -> dict:
    from bench.harness import reference

    plan = np.zeros_like(d["plan"])
    plan[rng.choice(np.flatnonzero(d["available"]), d["n_sel"],
                    replace=False)] = True
    return dict(d, plan=plan, est=reference.plan_cost(
        d["times"], d["counts"], plan, **cost))


def readings(cell, seed: int, seconds: float) -> dict:
    import jax.numpy as jnp

    from bench.harness import checks, correctness, runner

    run = runner.CellRun(cell, seed)
    train = cell.traffic["runtime"] == "bench_real_fl"
    run.warm_up(snapshot_rounds=correctness.ref_rounds(cell) if train else 0)
    run.window(seconds)
    got = correctness.collect(run, cell)
    del run
    cost = got["cost"]
    out = {"seed": seed, "decisions": len(got["decisions"])}
    ref = ctl = half = None
    if train:
        ref = correctness.reference_training(got, cell, seed)
        ctl = correctness.reference_training(got, cell, seed,
                                             dtype=jnp.bfloat16)
        half = correctness.reference_training(
            got, cell, seed, cohort_fn=lambda c: c[: max(1, len(c) // 2)])
    out["program"] = correctness.numbers(got, ref)
    control = checks.decision_control(got["decisions"], cost, seed)
    rng = np.random.default_rng(seed + 1)
    faults = {
        "altered_plan": checks.decision_numbers(
            [altered_plan(d) for d in got["decisions"]], cost, seed),
        "half_plan": checks.decision_numbers(
            [half_plan(d) for d in got["decisions"]], cost, seed),
        "random_plan": checks.decision_numbers(
            [random_plan(d, cost, rng) for d in got["decisions"]],
            cost, seed)}
    if train:
        stand_in = lambda r: [dict(g, prog=p, ref=q) for g, p, q
                              in zip(got["train"], r, ref)]
        control.update(checks.training_numbers(stand_in(ctl)))
        faults["half_batch"] = checks.training_numbers(stand_in(half))
    out["control"] = control
    out["faults"] = faults
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import run as bench_run  # bench/run.py: the same cache and device checks

    bench_run._compile_cache()
    from bench.harness import cells

    cell = cells.load_cell(args.workload)
    bench_run.device_info(cell.chips)
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        r = readings(cell, int(s), args.seconds)
        r["wall_s"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

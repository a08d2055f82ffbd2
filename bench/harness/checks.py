"""The numbers that decide ``correct``, each held against its limit.

Training (the first rounds of every job, driven through the window's own
compiled round during set-up):

- ``loss_gap``: the largest relative gap between the program's eval loss
  after a round and the reference's, over the compared rounds and jobs;
- ``update1_gap``: the first round's update (the pseudo-gradient FedAvg
  hands the server), by the worst leaf: | |u_l| - |u_ref,l| | over
  max(|u_ref,l|, the median leaf's |u_ref|);
- ``change_gap``: the same for the parameters' change after the last
  compared round.

Leaves whose reference update is under a thousandth of the median leaf's
are left out of the two norms: they move by round-off alone.

Scheduling (a sample of the window's decisions):

- ``invalid_plans``: plans that are not a (K,) mask of exactly the
  requested number of available devices (exact: limit 0);
- ``plan_est_rel_err``: the largest relative gap between the estimated
  cost the search reported and the plan's cost re-scored in float64;
- ``plan_rank``: how well the search picks: for each sampled decision,
  the share of ``RANK_PLANS`` valid plans drawn at random (by the
  reference, from the run's seed) that cost less than the chosen plan, both
  in float64; the mean over the sample. A search that picks any valid plan
  reads about 0.5. The two numbers above hold the search to scoring its
  pick honestly; this one holds it to picking well.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.harness import reference

LEAF_FLOOR = 1e-3
RANK_PLANS = 256


def _norms(a: Sequence[np.ndarray], b: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([np.linalg.norm((x.astype(np.float64)
                                     - y.astype(np.float64)).ravel())
                     for x, y in zip(a, b)])


def norm_gap(prog_after, ref_after, before) -> float:
    """Worst-leaf gap of the change ``after - before`` (see module doc)."""
    ref = _norms(ref_after, before)
    prog = _norms(prog_after, before)
    if not (np.all(np.isfinite(ref)) and np.all(np.isfinite(prog))):
        return float("inf")
    med = float(np.median(ref))
    keep = ref >= LEAF_FLOOR * med
    denom = np.maximum(ref[keep], med)
    return float(np.max(np.abs(prog[keep] - ref[keep]) / denom))


def training_numbers(jobs: List[dict]) -> Dict[str, float]:
    """``jobs``: per job ``init``, ``prog`` ({"params": [...], "loss":
    [...]}, snapshots after round 1 and after the last compared round) and
    ``ref`` (the same from ``reference.fl_rounds``)."""
    out = {"loss_gap": 0.0, "update1_gap": 0.0, "change_gap": 0.0}
    for j in jobs:
        prog, ref = j["prog"], j["ref"]
        for lp, lr in zip(prog["loss"], ref["loss"]):
            gap = abs(lp - lr) / max(abs(lr), 1e-12)
            out["loss_gap"] = max(out["loss_gap"],
                                  gap if np.isfinite(gap) else np.inf)
        out["update1_gap"] = max(out["update1_gap"], norm_gap(
            prog["params"][0], ref["params"][0], j["init"]))
        out["change_gap"] = max(out["change_gap"], norm_gap(
            prog["params"][-1], ref["params"][-1], j["init"]))
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def plan_valid(d: dict) -> bool:
    plan, avail = np.asarray(d["plan"]), d["available"]
    return (plan.dtype == bool and plan.shape == avail.shape
            and int(plan.sum()) == d["n_sel"]
            and bool(np.all(avail[plan])))


def decision_numbers(decisions: List[dict], cost: dict,
                     seed: int) -> Dict[str, float]:
    """``cost``: alpha, beta, time_scale, fairness_scale; ``seed`` draws
    the random plans of ``plan_rank``."""
    rng = np.random.default_rng(seed)
    invalid, worst, ranks = 0, 0.0, []
    for d in decisions:
        if not plan_valid(d):
            invalid += 1
            continue
        ref = reference.plan_cost(d["times"], d["counts"], d["plan"], **cost)
        est = d["est"]
        gap = (abs(est - ref) / max(abs(ref), 1e-12)
               if est is not None and np.isfinite(est) else np.inf)
        worst = max(worst, gap)
        rand = reference.random_plan_costs(d["times"], d["counts"],
                                           d["available"], d["n_sel"],
                                           RANK_PLANS, rng, **cost)
        ranks.append(float(np.mean(rand < ref)))
    return {"invalid_plans": float(invalid), "plan_est_rel_err": worst,
            "plan_rank": float(np.mean(ranks)) if ranks else np.inf}


def decision_control(decisions: List[dict], cost: dict,
                     seed: int) -> Dict[str, float]:
    """The control: the bfloat16 reference put in the search's place."""
    swapped = [dict(d, est=reference.plan_cost_bf16(
        d["times"], d["counts"], d["plan"], **cost)) for d in decisions]
    return decision_numbers(swapped, cost, seed)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{name: {"value", "limit"}} for every number that has a limit; a
    number without one is an error in the configuration."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the configuration")
    return {k: {"value": float(v), "limit": float(limits[k])}
            for k, v in numbers.items()}


def is_correct(checks: dict) -> bool:
    return bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

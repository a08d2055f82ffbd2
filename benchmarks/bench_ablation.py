"""Paper appendix ablation: the data-fairness term (beta) on vs off.

Claim under test: without fairness (beta=0) the scheduler degenerates toward
greedy/fast-device selection — faster rounds but an accuracy ceiling under
non-IID; with fairness both speed AND final accuracy hold.
Also sweeps the cost-combination form (the paper reports the linear
combination beats sum-of-squares and multiplicative variants).

Each (alpha, beta) cell is the same ``ExperimentSpec`` with a different
``CostSpec`` — the ablation axis is declarative.
"""

from __future__ import annotations

import numpy as np

from repro.experiment import CostSpec, ExperimentSpec, JobSpec, PoolSpec


def _run(alpha, beta, seed=1):
    spec = ExperimentSpec(
        name=f"ablation-a{alpha}-b{beta}",
        jobs=tuple(JobSpec(name=f"j{i}", target_metric=0.8, max_rounds=150)
                   for i in range(3)),
        pool=PoolSpec(num_devices=100, seed=seed),
        cost=CostSpec(alpha=alpha, beta=beta),
        scheduler="bods", runtime="synthetic",
        runtime_kwargs={"seed": 2}, n_sel=10)
    res = spec.run()
    s = res.summary
    acc = float(np.mean([v["best_accuracy"] for v in s.values()]))
    t2t = [v["time_to_target"] for v in s.values()]
    rt_mean = float(np.mean([r.round_time for r in res.records]))
    return acc, t2t, res.makespan, rt_mean


def main():
    print("\n== Ablation: fairness term (BODS) ==")
    for alpha, beta, label in [(4.0, 0.25, "alpha=4, beta=0.25 (default)"),
                               (4.0, 0.0, "alpha=4, beta=0 (no fairness)"),
                               (0.0, 1.0, "alpha=0 (fairness only)")]:
        acc, t2t, mk, rt = _run(alpha, beta)
        hit = sum(t is not None for t in t2t)
        print(f"{label:34s} mean_best_acc={acc:.3f} jobs_hit_target={hit}/3 "
              f"makespan={mk/60:8.1f}min mean_round={rt:6.0f}s")
        print(f"CSV,ablation,{label.replace(' ', '_').replace(',', '')},"
              f"{acc:.4f},{hit},{mk:.0f}")


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

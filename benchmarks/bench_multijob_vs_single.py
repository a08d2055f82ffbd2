"""Paper Table 5: MJ-FL parallel execution vs sequential single-job FL.

Sequential baseline: each job runs ALONE on the full pool (FedAvg/random
selection), one after another; total time = sum of per-job times. MJ-FL runs
the same jobs in parallel on the shared pool. Both arms are the same
``ExperimentSpec`` with a different job tuple / scheduler name.
"""

from __future__ import annotations

from repro.experiment import ExperimentSpec, JobSpec, PoolSpec


def _spec(n_jobs: int, scheduler: str, seed: int = 1, n_sel: int = 10,
          target: float = 0.8, max_rounds: int = 150) -> ExperimentSpec:
    return ExperimentSpec(
        name=f"mj-vs-sj-{n_jobs}job-{scheduler}",
        jobs=tuple(JobSpec(name="job", target_metric=target,
                           max_rounds=max_rounds) for _ in range(n_jobs)),
        pool=PoolSpec(num_devices=100, seed=seed),
        scheduler=scheduler, runtime="synthetic",
        runtime_kwargs={"seed": 2}, n_sel=n_sel)


def main():
    print("\n== Table 5: MJ-FL (parallel) vs SJ-FL (sequential) ==")
    # Sequential: jobs one at a time; total = sum of makespans.
    seq_total = sum(_spec(1, "random", seed=1 + i).run().makespan
                    for i in range(3))
    rows = [("SJ-FL sequential (random)", seq_total)]
    for sched in ("random", "bods", "rlds"):
        rows.append((f"MJ-FL parallel ({sched})", _spec(3, sched).run().makespan))
    base = rows[0][1]
    for name, t in rows:
        print(f"{name:32s} total={t/60:9.1f} min  speedup_vs_seq={base/t:5.2f}x")
        print(f"CSV,mj_vs_sj,{name.replace(' ', '_')},{t:.0f},{base/t:.3f}")


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

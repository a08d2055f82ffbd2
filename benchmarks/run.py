"""Benchmark entrypoint: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all tables
  PYTHONPATH=src python -m benchmarks.run --only groups,ablation

Emits human tables + machine CSV lines (prefix "CSV,").
Table map: groups -> paper Tables 1-2 (+Figs 3,5,6,7 trajectories as CSV),
mj_vs_sj -> Table 5, ablation -> appendix fairness ablation.
These are results, not speed: the speed benchmark is ``bench/run.py``.

Every engine-backed section is spec-driven: each cell is a declarative
``repro.experiment.ExperimentSpec`` (see ``benchmarks/common.py``), so any
table cell can be re-run standalone, e.g.:

  PYTHONPATH=src python -m repro.experiment.cli preset paper-group-a \\
      --arg scheduler=rlds --arg non_iid=true --run
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="groups,mj_vs_sj,ablation")
    args = ap.parse_args()
    picks = set(args.only.split(","))
    t0 = time.time()

    if "groups" in picks:
        from benchmarks import bench_groups
        bench_groups.main()
    if "mj_vs_sj" in picks:
        from benchmarks import bench_multijob_vs_single
        bench_multijob_vs_single.main()
    if "ablation" in picks:
        from benchmarks import bench_ablation
        bench_ablation.main()

    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

"""REAL-training benchmark (slow path, ~15-30 min on this container's CPU):
two-job groups with actual vmap'd local SGD + FedAvg under each scheduler.

  PYTHONPATH=src python -m benchmarks.bench_real_fl [--rounds 15]

The paper's Tables 1-2 setting in miniature: simulated wall-clock, REAL
accuracy. The scheduler-plane benchmark (bench_groups.py) is the fast
default; this one validates that the ordering holds under real learning.
Each scheduler arm is the ``real-fl-two-job`` preset with a different
scheduler name.
"""

from __future__ import annotations

import argparse

from repro.experiment import get_preset


def run(scheduler: str, rounds: int, devices: int = 40, seed: int = 5):
    spec = get_preset("real-fl-two-job", scheduler=scheduler, rounds=rounds,
                      num_devices=devices, seed=seed,
                      lenet_target=0.95, cnn_target=0.85)
    return spec.run().summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--schedulers", default="random,greedy,bods")
    args = ap.parse_args()
    print("\n== Real-FL scheduler comparison (2 jobs, non-IID, "
          f"{args.rounds} rounds) ==")
    for sched in args.schedulers.split(","):
        s = run(sched, args.rounds)
        cells = " ".join(
            f"{n}: acc={v['best_accuracy']:.3f} t={v['makespan']/60:.0f}m"
            for n, v in s.items())
        print(f"{sched:8s} {cells}")
        for n, v in s.items():
            print(f"CSV,real_fl,{sched},{n},{v['best_accuracy']:.4f},"
                  f"{v['makespan']:.0f}")


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

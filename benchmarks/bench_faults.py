"""Fault-tolerance benchmark: robust-aggregation overhead and screening
parity.

Three measurements, written to ``BENCH_faults.json`` (gates enforced in CI
bench-smoke):

1. **Robust overhead** — the same fused FL workload run through
   ``FusedMultiRuntime`` with ``robust=False`` vs ``robust=True`` (no
   corruption injected, so the trajectories must stay numerically
   IDENTICAL). The in-jit screening (finite check + masked-median norm
   test + guarded FedAvg) must cost <= ``--max-overhead`` (default 5%)
   median per-round wall time.
2. **Rejection parity** — the jitted ``rejection_mask`` vs the numpy
   ``rejection_mask_host`` reference over randomized cohorts with NaN
   lanes, norm outliers, and zero-weight padding: zero mismatches allowed.
3. **Chaos completion** — the ``fault-injection`` preset (dropouts +
   crashes + stragglers + domain outages + corrupted uploads) must finish
   with every recorded metric finite, and must actually have injected
   faults (dropped > 0, corrupt > 0).

  PYTHONPATH=src python -m benchmarks.bench_faults           # full size
  PYTHONPATH=src python -m benchmarks.bench_faults --smoke   # CI-sized
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

PyTree = dict


def _setup_fused(num_devices: int, samples: int, seed: int = 0):
    from repro.config.base import JobConfig
    from repro.configs.paper_models import lenet5
    from repro.data.synthetic import make_classification_dataset
    from repro.fl.partition import noniid_partition

    cfg = dataclasses.replace(
        lenet5(), name="bench", input_shape=(16, 16, 1),
        cnn_spec=(("convp", 8, 3), ("convp", 16, 3), ("flatten",),
                  ("fc", 64)))
    x, y = make_classification_dataset(samples, cfg.input_shape,
                                       cfg.num_classes, noise=1.0, seed=seed)
    ex, ey = make_classification_dataset(120, cfg.input_shape,
                                         cfg.num_classes, noise=1.0,
                                         seed=seed + 50)
    part = noniid_partition(y, num_devices, seed=seed)
    job = JobConfig(job_id=0, model=cfg, target_metric=2.0,
                    local_epochs=5, batch_size=8, lr=0.05)
    return [job], [(x, y, part, ex, ey)]


def bench_overhead(num_devices: int, samples: int, rounds: int,
                   warmup: int) -> dict:
    """Interleave plain and robust runtimes round-by-round (alternating
    which goes first) so machine drift hits both equally; the overhead is
    the median of the per-round paired ratios."""
    from repro.fl.runtime import FusedMultiRuntime

    rng = np.random.default_rng(7)
    cohorts = [rng.choice(num_devices, 8, replace=False)
               for _ in range(rounds + warmup)]

    jobs, datasets = _setup_fused(num_devices, samples)
    plain = FusedMultiRuntime(jobs, datasets, seed=0)
    jobs, datasets = _setup_fused(num_devices, samples)
    robust = FusedMultiRuntime(jobs, datasets, seed=0, robust=True)

    def timed(rt, ids, r):
        t0 = time.perf_counter()
        m = rt.run_round(0, ids, r)
        return time.perf_counter() - t0, m

    t_plain, t_robust, max_diff = [], [], 0.0
    for r, ids in enumerate(cohorts):
        pair = [(plain, t_plain), (robust, t_robust)]
        if r % 2:
            pair.reverse()
        out = {}
        for rt, bucket in pair:
            dt, m = timed(rt, ids, r)
            if r >= warmup:
                bucket.append(dt)
            out[rt is robust] = m
        # With no corruption injected the robust path must change NOTHING.
        max_diff = max(max_diff,
                       abs(out[True]["loss"] - out[False]["loss"])
                       + abs(out[True]["accuracy"] - out[False]["accuracy"]))
    ratios = np.asarray(t_robust) / np.asarray(t_plain)
    return {"plain_round_s": float(np.median(t_plain)),
            "robust_round_s": float(np.median(t_robust)),
            "overhead": float(np.median(ratios)) - 1.0,
            "metric_max_diff": max_diff, "rounds": rounds}


def bench_rejection_parity(trials: int) -> dict:
    import jax.numpy as jnp

    from repro.fl.aggregation import rejection_mask, rejection_mask_host

    rng = np.random.default_rng(11)
    mismatches = 0
    for _ in range(trials):
        n, d = int(rng.integers(4, 17)), int(rng.integers(3, 33))
        g = {"w": rng.normal(size=(d,)).astype(np.float32),
             "b": rng.normal(size=(2, d)).astype(np.float32)}
        s = {"w": g["w"][None] + 0.1 * rng.normal(size=(n, d)).astype(
                np.float32),
             "b": g["b"][None] + 0.1 * rng.normal(size=(n, 2, d)).astype(
                np.float32)}
        w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
        w[rng.random(n) < 0.2] = 0.0                        # bucket padding
        for i in range(n):                                  # inject faults
            u = rng.random()
            if u < 0.15:
                s["w"][i] = np.nan
            elif u < 0.3:
                s["b"][i] *= 100.0                          # norm outlier
        mult = float(rng.uniform(2.0, 6.0))
        host = rejection_mask_host(g, s, w, mult)
        fused = np.asarray(rejection_mask(g, s, jnp.asarray(w),
                                          jnp.float32(mult)))
        mismatches += int((host != fused).sum())
    return {"trials": trials, "mismatches": mismatches}


def bench_chaos_preset(num_devices: int, max_rounds: int) -> dict:
    from repro.experiment.presets import get_preset

    spec = get_preset("fault-injection", scheduler="random",
                      num_devices=num_devices)
    spec = spec.replace(jobs=tuple(
        dataclasses.replace(j, max_rounds=max_rounds) for j in spec.jobs))
    t0 = time.perf_counter()
    res = spec.run()
    wall = time.perf_counter() - t0
    finite = all(np.isfinite(r.accuracy) and np.isfinite(r.loss)
                 and np.isfinite(r.round_time) for r in res.records)
    dropped = int(sum(len(r.dropped) for r in res.records))
    corrupt = int(sum(len(r.corrupt_ids) for r in res.records))
    degraded = int(sum(1 for r in res.records if r.degraded))
    return {"rounds": len(res.records), "all_finite": finite,
            "dropped": dropped, "corrupt": corrupt,
            "degraded_rounds": degraded, "wall_s": wall}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer rounds/trials)")
    ap.add_argument("--out", default="BENCH_faults.json")
    ap.add_argument("--max-overhead", type=float, default=0.05,
                    help="fail if robust aggregation costs more than this "
                         "fraction of the plain fused round (median wall)")
    args = ap.parse_args(argv)

    if args.smoke:
        rounds, warmup, trials, chaos_rounds = 10, 3, 10, 8
        num_devices, samples = 20, 2400
    else:
        rounds, warmup, trials, chaos_rounds = 30, 3, 40, 30
        num_devices, samples = 40, 4800

    print("== robust-aggregation overhead (fused round, no corruption) ==")
    ov = bench_overhead(num_devices, samples, rounds, warmup)
    print(f"  plain {ov['plain_round_s'] * 1e3:8.2f}ms/round  "
          f"robust {ov['robust_round_s'] * 1e3:8.2f}ms/round  "
          f"overhead {ov['overhead'] * 100:+.2f}%  "
          f"metric diff {ov['metric_max_diff']:.2e}")

    print("== fused rejection vs host reference parity ==")
    par = bench_rejection_parity(trials)
    print(f"  {par['trials']} randomized cohorts, "
          f"{par['mismatches']} mismatches")

    print("== fault-injection preset (chaos completion) ==")
    chaos = bench_chaos_preset(num_devices=60, max_rounds=chaos_rounds)
    print(f"  {chaos['rounds']} rounds in {chaos['wall_s']:.1f}s: "
          f"dropped={chaos['dropped']} corrupt={chaos['corrupt']} "
          f"degraded={chaos['degraded_rounds']} "
          f"finite={chaos['all_finite']}")

    failures = []
    if ov["overhead"] > args.max_overhead:
        failures.append(f"robust overhead {ov['overhead'] * 100:.2f}% > "
                        f"{args.max_overhead * 100:.0f}% gate")
    if ov["metric_max_diff"] > 1e-6:
        failures.append(f"robust path diverged without corruption: "
                        f"metric diff {ov['metric_max_diff']:.3e}")
    if par["mismatches"]:
        failures.append(f"rejection parity broken: {par['mismatches']} "
                        f"fused-vs-host mismatches")
    if not chaos["all_finite"]:
        failures.append("fault-injection preset produced non-finite metrics")
    if chaos["dropped"] == 0 or chaos["corrupt"] == 0:
        failures.append("fault-injection preset injected no faults "
                        f"(dropped={chaos['dropped']}, "
                        f"corrupt={chaos['corrupt']})")

    out = {"smoke": args.smoke, "overhead": ov, "rejection_parity": par,
           "chaos": chaos,
           "gate": {"max_overhead": args.max_overhead,
                    "failures": failures}}
    with open(args.out, "w") as fobj:
        json.dump(out, fobj, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("bench_faults regression gate FAILED:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

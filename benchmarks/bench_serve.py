"""Online scheduler-service benchmark: incremental vs full plan rescoring.

Runs the SAME traffic trace (generated once, seeded) through two
``SchedulerService`` instances that differ only in ``rescore_mode`` and
measures per-admission decision latency (p50/p99), service throughput, and
plan-cost parity. Because both modes execute plans from the live scheduler
(rescoring is advisory), the realized round trajectories must be IDENTICAL
— the benchmark's hard parity gate — while incremental rescoring must beat
full per-arrival re-search on decision latency.

Gates (written to ``BENCH_serve.json``, enforced in CI bench-smoke):
- executed-cost parity: realized per-round costs match across modes
  (max |diff| <= 1e-9 — same plans, same rng, same trajectory);
- latency: incremental p50 * min_speedup <= full p50;
- advisory agreement: mean advisory rescore cost within ``--advisory-tol``
  relative difference (incremental scores the current plan, full searches
  a fresh one, so agreement is approximate by construction).

  PYTHONPATH=src python -m benchmarks.bench_serve            # full horizon
  PYTHONPATH=src python -m benchmarks.bench_serve --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.experiment.presets import get_preset
from repro.serve import SchedulerService, trace_from_spec


def run_mode(spec, trace, mode: str) -> dict:
    svc = SchedulerService(spec, rescore_mode=mode)
    report = svc.run(trace)
    lat = report.decision_latency
    advisory = [c for c in svc.rescore_costs if c > 0]
    return {
        "mode": mode,
        "p50_ms": lat["p50_s"] * 1e3,
        "p99_ms": lat["p99_s"] * 1e3,
        "decisions": lat["count"],
        "rounds": report.rounds_completed,
        "arrivals": report.arrivals,
        "readmissions": report.readmissions,
        "churn_events": report.churn_events,
        "tenant_fairness": report.tenant_fairness,
        "queue_depth_max": report.queue_depth_max,
        "mean_advisory_cost": (float(np.mean(advisory)) if advisory else 0.0),
        "realized_costs": [r.cost for r in svc.engine.records],
        "wall_s": report.wall_s,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (short horizon)")
    ap.add_argument("--scheduler", default="bods")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="fail unless full p50 latency >= incremental p50 * "
                         "this factor (CI uses 1.0 — incremental strictly "
                         "no slower; full runs report >=2x)")
    ap.add_argument("--advisory-tol", type=float, default=0.5,
                    help="max relative difference between the modes' mean "
                         "advisory rescore costs")
    args = ap.parse_args(argv)

    preset_kwargs = ({"horizon": 12_000.0, "num_devices": 50}
                     if args.smoke else {})
    spec = get_preset("online-smoke", scheduler=args.scheduler,
                      **preset_kwargs)
    # One trace, both modes: traffic held bit-identical.
    probe = SchedulerService(spec)
    trace = trace_from_spec(spec.arrivals, len(probe.templates),
                            probe.engine.pool.num_devices)

    print(f"== scheduler service: incremental vs full rescoring "
          f"({args.scheduler}, {len(trace)} traffic events) ==")
    rows = {}
    for mode in ("incremental", "full"):
        r = run_mode(spec, trace, mode)
        rows[mode] = r
        print(f"  {mode:>11}: p50={r['p50_ms']:8.2f}ms "
              f"p99={r['p99_ms']:8.2f}ms over {r['decisions']} decisions, "
              f"{r['rounds']} rounds, advisory cost "
              f"{r['mean_advisory_cost']:.3f}")

    inc, full = rows["incremental"], rows["full"]
    failures = []

    ci, cf = inc["realized_costs"], full["realized_costs"]
    if len(ci) != len(cf):
        failures.append(f"trajectory length diverged: incremental {len(ci)} "
                        f"rounds vs full {len(cf)}")
    else:
        max_diff = (float(np.max(np.abs(np.asarray(ci) - np.asarray(cf))))
                    if ci else 0.0)
        if max_diff > 1e-9:
            failures.append(f"executed-plan cost parity broken: max realized "
                            f"cost diff {max_diff:.3e} > 1e-9")

    if inc["p50_ms"] * args.min_speedup > full["p50_ms"]:
        failures.append(
            f"incremental p50 {inc['p50_ms']:.2f}ms * "
            f"{args.min_speedup:.2f} > full p50 {full['p50_ms']:.2f}ms "
            "(incremental rescoring must not be slower than full re-search)")

    if full["mean_advisory_cost"] > 0:
        rel = (abs(inc["mean_advisory_cost"] - full["mean_advisory_cost"])
               / full["mean_advisory_cost"])
        if rel > args.advisory_tol:
            failures.append(
                f"advisory cost divergence {rel:.3f} > {args.advisory_tol}")
    else:
        rel = 0.0

    speedup = (full["p50_ms"] / inc["p50_ms"] if inc["p50_ms"] > 0 else
               float("inf"))
    print(f"  parity: realized trajectories "
          f"{'identical' if not failures or 'parity' not in failures[0] else 'DIVERGED'}, "
          f"advisory reldiff {rel:.3f}, incremental x{speedup:.2f} "
          f"faster at p50")

    # Trajectories are bulky and identical across modes — keep one copy.
    full.pop("realized_costs")
    inc["realized_cost_sum"] = float(np.sum(inc.pop("realized_costs")))
    out = {
        "smoke": args.smoke,
        "scheduler": args.scheduler,
        "traffic_events": len(trace),
        "incremental": inc,
        "full": full,
        "p50_speedup": speedup,
        "advisory_reldiff": rel,
        "gate": {"min_speedup": args.min_speedup,
                 "advisory_tol": args.advisory_tol,
                 "failures": failures},
    }
    with open(args.out, "w") as fobj:
        json.dump(out, fobj, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("bench_serve regression gate FAILED:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

"""SLO resilience benchmark: the degradation ladder must be cheap, honest,
and inert when asked to be.

Four measurements, written to ``BENCH_slo.json`` (gates enforced in CI
bench-smoke):

1. **Inert parity** — a spec whose ``slo`` axis is present but all-default
   must produce a round-record trajectory BIT-IDENTICAL to the same spec
   with no ``slo`` axis at all (``effective_slo`` treats it as absent).
2. **Governor overhead** — the same seeded quickstart workload with an
   attached-but-never-degrading governor (``max_queue_depth`` huge, no
   deadline) vs ungoverned, interleaved trial-by-trial with alternating
   order; the paired-median overhead must stay <= ``--max-overhead`` unless
   the absolute difference is below the timing-noise floor. The governed
   records must match the ungoverned ones on every field except the
   governor's own annotations (``rung``).
3. **Deadline compliance** — an overloaded service run (``slo-overload``
   preset) with a wall-clock ``decision_deadline_ms``: after a warmup run
   (jit compile outside the measurement), EVERY decision must land within
   the deadline at whatever rung the governor picked.
4. **Degraded-plan quality + bounded shedding** — for the degraded
   decisions of (3), the chosen plan's Formula-2 cost on the SAME context
   must stay <= ``--max-cost-ratio`` x the full search's plan cost, and
   the shed fraction of arrivals must stay <= ``--max-shed-frac``.

  PYTHONPATH=src python -m benchmarks.bench_slo           # full size
  PYTHONPATH=src python -m benchmarks.bench_slo --smoke   # CI-sized
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

# Fields the governor itself stamps on records — the overhead arm compares
# trajectories modulo these (an attached governor annotates rung="full";
# an ungoverned run records None).
_GOVERNOR_FIELDS = ("rung",)


def _quickstart(max_rounds: int):
    from repro.experiment.presets import get_preset

    spec = get_preset("quickstart")
    return spec.replace(jobs=tuple(
        dataclasses.replace(j, max_rounds=max_rounds, target_metric=2.0)
        for j in spec.jobs))


def _timed_run(spec):
    ex = spec.build()
    t0 = time.perf_counter()
    res = ex.run()
    return time.perf_counter() - t0, res.records


def _records_identical(a, b, ignore=()) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
        for k, va in da.items():
            if k in ignore:
                continue
            vb = db[k]
            if isinstance(va, np.ndarray):
                if not np.array_equal(va, vb):
                    return False
            elif va != vb and not (va is None and vb is None):
                return False
    return True


def bench_inert(max_rounds: int) -> dict:
    """An all-default ``slo`` axis must change NOTHING."""
    spec = _quickstart(max_rounds)
    _, recs_off = _timed_run(spec)
    _, recs_inert = _timed_run(spec.replace(slo={}))
    return {"rounds": len(recs_off),
            "records_identical": _records_identical(recs_off, recs_inert)}


def bench_overhead(max_rounds: int, trials: int) -> dict:
    """Attached-but-idle governor vs none, paired and order-alternated.
    ``max_queue_depth`` huge + no deadline => queue depth 0 keeps every
    decision at the full rung, so the plans must be identical and the
    timing difference is pure governor bookkeeping."""
    spec_off = _quickstart(max_rounds)
    spec_on = spec_off.replace(slo={"max_queue_depth": 1_000_000})

    _timed_run(spec_off)  # warm the jit caches outside the timing

    t_off, t_on = [], []
    identical = True
    for t in range(trials):
        arms = [(spec_off, t_off), (spec_on, t_on)]
        if t % 2:
            arms.reverse()
        recs = {}
        for spec, bucket in arms:
            dt, r = _timed_run(spec)
            bucket.append(dt)
            recs[spec is spec_on] = r
        identical = identical and _records_identical(
            recs[False], recs[True], ignore=_GOVERNOR_FIELDS)
    ratios = np.asarray(t_on) / np.asarray(t_off)
    return {"ungoverned_s": float(np.median(t_off)),
            "governed_s": float(np.median(t_on)),
            "overhead": float(np.median(ratios)) - 1.0,
            "diff_s": float(np.median(t_on) - np.median(t_off)),
            "records_identical": identical,
            "trials": trials, "rounds_per_run": max_rounds}


def bench_ladder(deadline_ms: float, smoke: bool, max_scored: int) -> dict:
    """Overloaded service under a wall-clock deadline: compliance, degraded
    plan quality vs the full search on the same contexts, shed fraction."""
    from repro.experiment.presets import get_preset
    from repro.serve.service import SchedulerService
    from repro.serve.traffic import trace_from_spec

    kwargs = dict(horizon=6_000.0, num_devices=30) if smoke else {}
    spec = get_preset("slo-overload", **kwargs)
    spec = spec.replace(slo={"decision_deadline_ms": deadline_ms})
    service = SchedulerService(spec)
    trace = trace_from_spec(spec.arrivals, len(service.templates),
                            service.engine.pool.num_devices)

    service.run(trace)  # warmup: jit compile of the full search

    service = SchedulerService(spec)
    service.engine.governor.keep_decisions = True
    report = service.run(trace)
    gov = service.engine.governor
    log = gov.decision_log

    within = sum(1 for d in log if d["ms"] <= deadline_ms)
    degraded = [d for d in log if d["rung"] != "full"]

    # Re-score a bounded sample of degraded decisions against the full
    # search on the very same (post-masking) contexts.
    scheduler = service.engine.scheduler
    cost_model = service.engine.cost_model
    ratios = []
    for d in degraded[:max_scored]:
        ctx = d["ctx"]
        chosen = float(np.asarray(cost_model.cost_indices(
            ctx.expected_times, ctx.counts, d["idx"][None]))[0])
        full_idx = np.flatnonzero(scheduler.schedule(ctx))
        full = float(np.asarray(cost_model.cost_indices(
            ctx.expected_times, ctx.counts, full_idx[None]))[0])
        if full > 0:
            ratios.append(chosen / full)
    res = report.resilience or {}
    shed = int(res.get("shed_arrivals", 0))
    return {
        "deadline_ms": deadline_ms,
        "decisions": len(log),
        "within_deadline": within,
        "within_deadline_frac": within / len(log) if log else 0.0,
        "rung_counts": dict(gov.rung_counts),
        "degraded_decisions": len(degraded),
        "scored": len(ratios),
        "max_cost_ratio": float(max(ratios)) if ratios else None,
        "median_cost_ratio": float(np.median(ratios)) if ratios else None,
        "arrivals": int(report.arrivals),
        "shed_arrivals": shed,
        "deferrals": int(res.get("deferrals", 0)),
        "shed_frac": shed / report.arrivals if report.arrivals else 0.0,
        "breaker_trips": int(res.get("breaker_trips", 0)),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer trials/rounds, short horizon)")
    ap.add_argument("--out", default="BENCH_slo.json")
    ap.add_argument("--max-overhead", type=float, default=0.05,
                    help="fail if the idle governor costs more than this "
                         "fraction of the ungoverned run (median paired)")
    ap.add_argument("--noise-floor-s", type=float, default=0.05,
                    help="absolute per-run difference below which the "
                         "overhead gate cannot fail (timing noise)")
    ap.add_argument("--deadline-ms", type=float, default=250.0,
                    help="wall-clock decision deadline for the ladder arm "
                         "(generous: gates on 100%% compliance, warm jit)")
    ap.add_argument("--max-cost-ratio", type=float, default=2.0,
                    help="fail if any scored degraded decision's plan cost "
                         "exceeds this multiple of the full search's")
    ap.add_argument("--max-shed-frac", type=float, default=0.5,
                    help="fail if more than this fraction of arrivals is "
                         "shed under overload")
    ap.add_argument("--max-scored", type=int, default=200,
                    help="cap on degraded decisions re-scored in arm 4")
    args = ap.parse_args(argv)

    max_rounds, trials = (40, 5) if args.smoke else (80, 9)

    print("== inert parity (slo axis all-default vs absent) ==")
    inert = bench_inert(max_rounds)
    print(f"  {inert['rounds']} rounds  "
          f"records identical={inert['records_identical']}")

    print("== idle-governor overhead (paired, order-alternated) ==")
    ov = bench_overhead(max_rounds, trials)
    print(f"  ungoverned {ov['ungoverned_s'] * 1e3:8.1f}ms/run  "
          f"governed {ov['governed_s'] * 1e3:8.1f}ms/run  "
          f"overhead {ov['overhead'] * 100:+.2f}%  "
          f"records identical={ov['records_identical']}")

    print(f"== degradation ladder under overload "
          f"(deadline {args.deadline_ms:.0f}ms) ==")
    lad = bench_ladder(args.deadline_ms, args.smoke, args.max_scored)
    hist = " ".join(f"{k}={v}" for k, v in lad["rung_counts"].items() if v)
    print(f"  {lad['decisions']} decisions, rungs[{hist}]")
    print(f"  within deadline {lad['within_deadline']}/{lad['decisions']}  "
          f"shed {lad['shed_arrivals']}/{lad['arrivals']} "
          f"(deferred {lad['deferrals']})")
    if lad["scored"]:
        print(f"  degraded plan cost vs full search over {lad['scored']} "
              f"contexts: median x{lad['median_cost_ratio']:.3f} "
              f"max x{lad['max_cost_ratio']:.3f}")

    failures = []
    if not inert["records_identical"]:
        failures.append("inert slo axis perturbed the trajectory")
    if not ov["records_identical"]:
        failures.append("idle governor changed the chosen plans (records "
                        "diverged beyond the rung annotation)")
    if ov["overhead"] > args.max_overhead and ov["diff_s"] > args.noise_floor_s:
        failures.append(f"governor overhead {ov['overhead'] * 100:.2f}% > "
                        f"{args.max_overhead * 100:.0f}% gate "
                        f"(diff {ov['diff_s'] * 1e3:.1f}ms above the "
                        f"{args.noise_floor_s * 1e3:.0f}ms noise floor)")
    if lad["within_deadline_frac"] < 1.0:
        failures.append(
            f"{lad['decisions'] - lad['within_deadline']} of "
            f"{lad['decisions']} decisions missed the "
            f"{args.deadline_ms:.0f}ms deadline at their recorded rung")
    if lad["degraded_decisions"] == 0:
        failures.append("overload run never degraded — ladder inert, "
                        "quality gate vacuous")
    if lad["max_cost_ratio"] is not None \
            and lad["max_cost_ratio"] > args.max_cost_ratio:
        failures.append(f"degraded plan cost x{lad['max_cost_ratio']:.2f} "
                        f"> x{args.max_cost_ratio:.1f} of full search")
    if lad["shed_frac"] > args.max_shed_frac:
        failures.append(f"shed fraction {lad['shed_frac']:.2f} > "
                        f"{args.max_shed_frac:.2f} gate")

    out = {"smoke": args.smoke, "inert": inert, "overhead": ov,
           "ladder": lad,
           "gate": {"max_overhead": args.max_overhead,
                    "noise_floor_s": args.noise_floor_s,
                    "deadline_ms": args.deadline_ms,
                    "max_cost_ratio": args.max_cost_ratio,
                    "max_shed_frac": args.max_shed_frac,
                    "failures": failures}}
    with open(args.out, "w") as fobj:
        json.dump(out, fobj, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("bench_slo regression gate FAILED:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

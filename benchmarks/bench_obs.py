"""Observability benchmark: the tracing layer must be (nearly) free.

Three measurements, written to ``BENCH_obs.json`` (gates enforced in CI
bench-smoke):

1. **Enabled-vs-disabled overhead** — the same seeded quickstart workload
   run with the full ``obs`` axis on (span tracer + metrics JSONL +
   scheduler audit) vs off, interleaved trial-by-trial with alternating
   order so machine drift hits both arms equally; the overhead is the
   median of the paired wall-time ratios and must stay <= ``--max-overhead``
   (default 3%).
2. **Bitwise identity** — the traced and untraced runs' round records must
   be IDENTICAL field-for-field (spans touch no RNG and build no arrays,
   so observation must not perturb the computation).
3. **Span coverage** — the engine phase spans (``ctx_build``/``schedule``/
   ``dispatch``/``aggregate``/``record``) must cover >= ``--min-coverage``
   (default 90%) of the ``engine_run`` root span's wall-clock, so a trace
   actually accounts for where the time went.

The enabled run's per-phase stats land in the output's ``phases`` block,
which ``python -m repro.monitoring report --check-bench BENCH_obs.json``
uses as the regression baseline for later traces.

  PYTHONPATH=src python -m benchmarks.bench_obs           # full size
  PYTHONPATH=src python -m benchmarks.bench_obs --smoke   # CI-sized
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

import numpy as np


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


def _records_identical(a, b) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
        for k, va in da.items():
            vb = db[k]
            if isinstance(va, np.ndarray):
                if not np.array_equal(va, vb):
                    return False
            elif va != vb and not (va is None and vb is None):
                return False
    return True


def bench_overhead(max_rounds: int, trials: int, outdir: str) -> dict:
    """Interleave untraced and fully-observed runs (alternating which goes
    first); overhead is the median of the per-trial paired ratios. The two
    arms share the spec seeds, so their round records must match bitwise."""
    spec_off = _quickstart(max_rounds)
    spec_on = spec_off.replace(obs={
        "trace_path": os.path.join(outdir, "trace.json"),
        "metrics_path": os.path.join(outdir, "metrics.jsonl"),
        "audit_path": os.path.join(outdir, "audit.jsonl")})

    # Warm the jit caches (scheduler search compiles) outside the timing.
    _timed_run(spec_off)

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
        identical = identical and _records_identical(recs[False], recs[True])
    ratios = np.asarray(t_on) / np.asarray(t_off)
    return {"disabled_s": float(np.median(t_off)),
            "enabled_s": float(np.median(t_on)),
            "overhead": float(np.median(ratios)) - 1.0,
            "records_identical": identical,
            "trials": trials, "rounds_per_run": max_rounds}


def trace_report(outdir: str) -> dict:
    from repro.monitoring import report as rpt

    events = rpt.load_trace(os.path.join(outdir, "trace.json"))
    stats = rpt.phase_stats(events)
    return {"phases": stats,
            "coverage": rpt.coverage(stats),
            "recompiles": rpt.recompile_count(events),
            "rounds_per_sec": rpt.rounds_per_sec(stats)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer trials/rounds)")
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--max-overhead", type=float, default=0.03,
                    help="fail if full observability costs more than this "
                         "fraction of the untraced run (median paired wall)")
    ap.add_argument("--min-coverage", type=float, default=0.9,
                    help="fail if the engine phase spans cover less than "
                         "this fraction of the engine_run wall-clock")
    args = ap.parse_args(argv)

    # Longer runs amortize per-run fixed costs (session setup, trace write)
    # and more trials stabilize the paired median against machine noise.
    max_rounds, trials = (40, 5) if args.smoke else (80, 9)

    with tempfile.TemporaryDirectory(prefix="bench_obs_") as outdir:
        print("== enabled-vs-disabled overhead (paired, order-alternated) ==")
        ov = bench_overhead(max_rounds, trials, outdir)
        print(f"  disabled {ov['disabled_s'] * 1e3:8.1f}ms/run  "
              f"enabled {ov['enabled_s'] * 1e3:8.1f}ms/run  "
              f"overhead {ov['overhead'] * 100:+.2f}%  "
              f"records identical={ov['records_identical']}")

        print("== trace coverage (last enabled run) ==")
        rep = trace_report(outdir)
        cov = rep["coverage"]
        print(f"  coverage {cov * 100:.1f}%  recompiles={rep['recompiles']}  "
              f"rounds/sec={rep['rounds_per_sec']:.1f}")

    failures = []
    if ov["overhead"] > args.max_overhead:
        failures.append(f"obs overhead {ov['overhead'] * 100:.2f}% > "
                        f"{args.max_overhead * 100:.0f}% gate")
    if not ov["records_identical"]:
        failures.append("traced run's round records diverged from the "
                        "untraced run (observation perturbed the compute)")
    if cov is None or cov < args.min_coverage:
        failures.append(f"engine span coverage "
                        f"{(cov or 0.0) * 100:.1f}% < "
                        f"{args.min_coverage * 100:.0f}% gate")

    out = {"smoke": args.smoke, "overhead": ov, "phases": rep["phases"],
           "coverage": cov, "recompiles": rep["recompiles"],
           "rounds_per_sec": rep["rounds_per_sec"],
           "gate": {"max_overhead": args.max_overhead,
                    "min_coverage": args.min_coverage,
                    "failures": failures}}
    with open(args.out, "w") as fobj:
        json.dump(out, fobj, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("bench_obs regression gate FAILED:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

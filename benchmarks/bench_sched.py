"""Scheduler decision throughput: host vs fused search loops.

Sweeps scheduler x K (pool size) x search backend and measures DECISIONS
per second (one decision = one ``schedule(ctx)`` call on a fleet-realistic
context) plus the mean chosen-plan estimated cost at MATCHED search
budgets, then writes ``BENCH_sched.json`` so the perf trajectory of the
search subsystem (``repro/core/search.py``) is tracked per-PR.

Matched budgets: the fused arms are configured to spend exactly the same
number of cost evaluations per decision as the host arms (SA: 8 chains x
25 steps vs 200 sequential steps, with the cooling rate raised to the 8th
power so each short chain spans the same temperature range; GA/BODS: same
population/candidate knobs), so the recorded ``mean_cost`` columns are
directly comparable — the regression gate requires fused decisions to be
at least as good AND at least as fast as host ones.

``--shards N`` adds informational fused arms with the fleet axis sharded
across N host platform devices (``CostModel.num_shards`` -> the fused
searchers' shard_map chains; re-execs via ``repro.launch.bootstrap`` so
the devices exist). These rows are NOT gated — chain partitioning is
bitwise-identical to single-lane by construction, so the arms only track
the dispatch overhead / speedup of the sharded search path.

  PYTHONPATH=src python -m benchmarks.bench_sched            # full sweep
  PYTHONPATH=src python -m benchmarks.bench_sched --smoke    # CI-sized
  PYTHONPATH=src python -m benchmarks.bench_sched --shards 4 # + sharded arms
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# Size the host platform before anything imports jax (see bench_fleet).
from repro.launch.bootstrap import ensure_host_devices


def _peek_shards(argv) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--shards", type=int, default=1)
    ns, _ = ap.parse_known_args(argv)
    return max(1, ns.shards)


if __name__ == "__main__":
    ensure_host_devices(_peek_shards(sys.argv[1:]))  # may os.execve()

import numpy as np

from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulingContext

FULL_KS = [100, 1_000, 10_000]
SMOKE_KS = [100, 1_000]
SEARCHERS = ["sa", "genetic", "bods"]
BASELINES = ["greedy", "fedcs"]
# Throughput + cost gates apply to the searchers whose objective IS the
# chosen-plan cost; BODS is gated on cost parity only (no throughput
# gate), with a looser tolerance: its decisions are EI-driven
# (exploration is part of the objective), so chosen-plan cost parity with
# the host path is statistical rather than monotone.
GATED = ["sa", "genetic"]
BODS_COST_TOL = 1.10

SA_BUDGET = 200          # host: 200 sequential steps
SA_CHAINS = 8            # fused: 8 chains x 25 steps == the same budget


def search_kwargs(name: str, backend: str) -> dict:
    if name != "sa":
        return {}
    if backend == "host":
        return {"steps": SA_BUDGET}
    steps = SA_BUDGET // SA_CHAINS
    return {"steps": steps, "chains": SA_CHAINS,
            "cooling": 0.97 ** SA_CHAINS}


def make_scenario(K: int, seed: int, num_shards: int = 1):
    """A fleet-realistic decision point: 20% of the pool busy, non-trivial
    cumulative counts, calibrated cost normalizers."""
    n_sel = max(1, K // 100)
    pool = DevicePool.heterogeneous(K, 2, seed=seed)
    cm = CostModel(pool, alpha=4.0, beta=0.25, num_shards=num_shards)
    cm.calibrate([5.0, 5.0], n_sel=n_sel)
    rng = np.random.default_rng(seed + 1000)
    counts = rng.integers(0, 8, K).astype(np.float64)
    avail = np.ones(K, bool)
    avail[rng.choice(K, K // 5, replace=False)] = False
    times = pool.expected_times(0, 5.0)

    def ctx():
        return SchedulingContext(
            job=0, round_idx=0, tau=5.0, n_sel=n_sel,
            available=avail.copy(), counts=counts.copy(),
            expected_times=times)

    return cm, ctx, n_sel


def bench_decisions(name: str, backend: str, K: int, seed: int = 0,
                    min_s: float = 1.0, max_reps: int = 200,
                    num_shards: int = 1) -> dict:
    cm, ctx, n_sel = make_scenario(K, seed, num_shards=num_shards)
    kw = search_kwargs(name, backend)
    if name in SEARCHERS:
        kw["search_backend"] = backend
    sched = get_scheduler(name, cost_model=cm, seed=seed, **kw)
    sched.schedule(ctx())  # warm-up: jit compile + BODS bootstrap
    sched.schedule(ctx())
    costs, reps = [], 0
    t0 = time.perf_counter()
    while True:
        sched.schedule(ctx())
        costs.append(sched.last_estimated_cost)
        reps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= min_s or reps >= max_reps:
            break
    return {"scheduler": name, "backend": backend, "K": K, "n_sel": n_sel,
            "shards": num_shards,
            "reps": reps, "sec_per_decision": elapsed / reps,
            "decisions_per_sec": reps / elapsed,
            "mean_cost": float(np.mean(costs))}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (small K, fewer reps)")
    ap.add_argument("--out", default="BENCH_sched.json")
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="fail if fused decisions/sec < this multiple of "
                         "host at the largest K (CI uses 1.0 — no "
                         "regression vs host; full runs report >=10x)")
    ap.add_argument("--cost-tol", type=float, default=1.005,
                    help="fail if fused mean chosen-plan cost exceeds "
                         "host mean * this factor at matched budgets")
    ap.add_argument("--shards", type=int, default=1,
                    help="add informational fused arms with the fleet axis "
                         "sharded over this many host devices (not gated)")
    args = ap.parse_args(argv)

    Ks = SMOKE_KS if args.smoke else FULL_KS
    min_s = 0.5 if args.smoke else 1.5

    rows = []
    print("== scheduler decision throughput (host vs fused) ==")
    for K in Ks:
        for name in SEARCHERS:
            pair = {}
            for backend in ("host", "fused"):
                r = bench_decisions(name, backend, K, min_s=min_s)
                pair[backend] = r
                rows.append(r)
            h, f = pair["host"], pair["fused"]
            f["speedup_vs_host"] = f["decisions_per_sec"] / h["decisions_per_sec"]
            print(f"  K={K:>6} {name:>8}: host {h['decisions_per_sec']:8.2f}"
                  f" dec/s (cost {h['mean_cost']:.4f})  fused "
                  f"{f['decisions_per_sec']:8.2f} dec/s (cost "
                  f"{f['mean_cost']:.4f})  x{f['speedup_vs_host']:.1f}")
            if args.shards > 1:
                s = bench_decisions(name, "fused", K, min_s=min_s,
                                    num_shards=args.shards)
                s["speedup_vs_host"] = (s["decisions_per_sec"]
                                        / h["decisions_per_sec"])
                rows.append(s)
                print(f"  K={K:>6} {name:>8}: fused@{args.shards} "
                      f"{s['decisions_per_sec']:8.2f} dec/s (cost "
                      f"{s['mean_cost']:.4f})  x{s['speedup_vs_host']:.1f}")
        for name in BASELINES:
            r = bench_decisions(name, "host", K, min_s=min_s)
            rows.append(r)
            print(f"  K={K:>6} {name:>8}: {r['decisions_per_sec']:8.2f} "
                  f"dec/s (cost {r['mean_cost']:.4f})")

    # ---- regression gates (largest K of the sweep) ----
    K_gate = Ks[-1]
    failures = []
    for name in GATED + ["bods"]:
        h = next(r for r in rows if r["scheduler"] == name
                 and r["backend"] == "host" and r["K"] == K_gate)
        f = next(r for r in rows if r["scheduler"] == name
                 and r["backend"] == "fused" and r["K"] == K_gate
                 and r.get("shards", 1) == 1)
        if name in GATED:
            speedup = f["decisions_per_sec"] / h["decisions_per_sec"]
            if speedup < args.min_speedup:
                failures.append(
                    f"{name}: fused x{speedup:.2f} < required "
                    f"x{args.min_speedup:.2f} vs host at K={K_gate}")
        tol = args.cost_tol if name in GATED else BODS_COST_TOL
        if f["mean_cost"] > h["mean_cost"] * tol:
            failures.append(
                f"{name}: fused mean cost {f['mean_cost']:.4f} > host "
                f"{h['mean_cost']:.4f} * {tol} at K={K_gate} "
                "(matched budgets)")

    out = {
        "smoke": args.smoke,
        "Ks": Ks,
        "sa_budget": {"host_steps": SA_BUDGET, "fused_chains": SA_CHAINS,
                      "fused_steps": SA_BUDGET // SA_CHAINS},
        "decisions": rows,
        "gate": {"min_speedup": args.min_speedup,
                 "cost_tol": args.cost_tol, "K": K_gate,
                 "failures": failures},
    }
    with open(args.out, "w") as fobj:
        json.dump(out, fobj, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("bench_sched regression gate FAILED:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

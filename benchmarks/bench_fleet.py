"""Fleet-scale scoring benchmark: plans-scored/sec, round latency, sharding.

Sweeps the plan-scoring core over K (pool size) x P (candidate count) and
each backend, then drives a real ``fleet-scale`` experiment end-to-end per K
to measure round latency. Writes ``BENCH_fleet.json`` so the perf
trajectory of the scoring core is tracked per-PR (CI runs ``--smoke``).

Dense (P, K) arms are capped at ``DENSE_MAX_K`` devices: the K=1e6 arm
never materializes a dense membership matrix — above the cap only the
index form and the fleet-sharded path (``repro.core.shard``) run, with
candidates drawn in-graph by ``random_plan_indices_sharded``. Every arm
records its peak RSS (``VmHWM``, reset per arm via ``clear_refs``) so the
memory guard is visible in the JSON, not just the wall times.

``--shards N`` adds sharded arms (and re-execs through
``repro.launch.bootstrap`` so the host platform actually has N devices);
``--sharded-gate`` runs the CI regression gate instead of the full sweep:
single-lane vs shard_map at one K, gating score parity (<= 1e-5), sharded
throughput, and scaling efficiency.

  PYTHONPATH=src python -m benchmarks.bench_fleet            # full sweep
  PYTHONPATH=src python -m benchmarks.bench_fleet --shards 8 # + sharded arms
  PYTHONPATH=src python -m benchmarks.bench_fleet --smoke    # CI-sized
  PYTHONPATH=src python -m benchmarks.bench_fleet --sharded-gate --shards 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The host platform is sized at jax backend init (XLA_FLAGS), and
# repro.core.scoring imports jax at module import time — so peek at
# --shards and (maybe) re-exec BEFORE the heavy imports below.
from repro.launch.bootstrap import ensure_host_devices


def _peek_shards(argv) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--shards", type=int, default=1)
    ns, _ = ap.parse_known_args(argv)
    return max(1, ns.shards)


if __name__ == "__main__":
    ensure_host_devices(_peek_shards(sys.argv[1:]))  # may os.execve()

import numpy as np

from repro.core import scoring, shard
from repro.core.plans import indices_to_plans, random_plan_indices

FULL_KS = [100, 1_000, 10_000, 100_000, 1_000_000]
FULL_PS = [256, 4096]
SMOKE_KS = [100, 1_000]
SMOKE_PS = [64, 256]

# No dense (P, K) arm above this K, for ANY backend: at K=1e6 the bool
# membership matrix alone is P MB and the numpy f64 temporaries 32x that.
# Above the cap only index-form and sharded arms run, and candidates are
# drawn in-graph (sharded) instead of via the (P, |avail|) host key draw.
DENSE_MAX_K = 1 << 18

KW = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
          delta_fairness=True)


def _mem_budget_bytes() -> int:
    """~40% of physical RAM: the ceiling for dense-numpy scoring temporaries
    (the (P, K) float64 path peaks at ~32 bytes/element). Cells above the
    budget are skipped with a marker row instead of OOM-killing the sweep."""
    try:
        total = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        return int(total * 0.4)
    except (ValueError, OSError, AttributeError):  # pragma: no cover
        return 6 << 30


def _reset_peak_rss() -> None:
    """Reset the kernel's high-water RSS mark (VmHWM) so each arm records
    ITS OWN peak, not the process lifetime max. Linux-only; silently a
    no-op elsewhere (peak_rss_mb then reports the lifetime high water)."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
    except OSError:
        pass


def _peak_rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return float("nan")


def _time_call(fn, min_s: float = 0.3, max_reps: int = 50) -> tuple:
    fn()  # warm-up (jit compile + transfer paths)
    reps, t0 = 0, time.perf_counter()
    while True:
        fn()
        reps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= min_s or reps >= max_reps:
            break
    return elapsed / reps, reps


def _make_candidates(rng, available, n_sel, P, shards):
    """(P, n_sel) candidate ids. Above DENSE_MAX_K the single-lane host
    draw would materialize a (P, |avail|) float64 key matrix (~29 GB at
    K=1e6, P=4096) — use the sharded in-graph draw there instead."""
    K = available.shape[0]
    if K > DENSE_MAX_K:
        return shard.random_plan_indices_sharded(
            rng, available, n_sel, P, num_shards=max(shards, 1))
    return random_plan_indices(rng, available, n_sel, P)


def bench_scoring(Ks, Ps, backends, shards: int = 1) -> list:
    """plans-scored/sec per (K, P, backend, plan form[, shard count]).

    ``dense`` scores (P, K) bool plans (what the per-scheduler numpy loops
    historically consumed); ``index`` scores the (P, n_sel) device-id form
    the vectorized candidate generators produce natively — the fleet fast
    path. ``speedup_vs_numpy`` is always relative to dense-numpy (the
    pre-refactor scoring path) at the same K, P. With ``shards > 1``,
    sharded arms ride along and record ``max_abs_diff_vs_single`` against
    the single-lane jax scores of the same form.
    """
    rng = np.random.default_rng(0)
    budget = _mem_budget_bytes()
    rows = []
    for K in Ks:
        times = rng.uniform(1.0, 100.0, K)
        counts = rng.integers(0, 50, K).astype(np.float64)
        available = rng.random(K) < 0.9
        n_sel = max(1, K // 100)
        for P in Ps:
            idx = _make_candidates(rng, available, n_sel, P, shards)
            plans = indices_to_plans(idx, K) if K <= DENSE_MAX_K else None
            variants = [(b, "dense", 1) for b in backends]
            variants += [("numpy", "index", 1), ("jax", "index", 1)]
            if shards > 1:
                if K <= DENSE_MAX_K:
                    variants.append(("jax", "dense", shards))
                variants.append(("jax", "index", shards))
            base = None
            single = {}  # form -> single-lane jax scores (parity reference)
            for backend, form, n_sh in variants:
                tag = f"{backend}/{form}" + (f"@{n_sh}" if n_sh > 1 else "")
                if form == "dense" and (
                        K > DENSE_MAX_K
                        or (backend == "numpy" and P * K * 32 > budget)):
                    why = ("dense arms capped at DENSE_MAX_K"
                           if K > DENSE_MAX_K
                           else "dense f64 temporaries exceed ~40% RAM")
                    print(f"  K={K:>7} P={P:>5} {tag:>14}: skipped ({why})")
                    rows.append({"backend": backend, "form": form, "K": K,
                                 "P": P, "n_sel": n_sel, "shards": n_sh,
                                 "skipped": True})
                    continue
                if form == "dense":
                    fn = lambda: scoring.score_plans(
                        times, counts, plans, backend=backend,
                        num_shards=n_sh, **KW)
                else:
                    fn = lambda: scoring.score_plan_indices(
                        times, counts, idx, backend=backend,
                        num_shards=n_sh, **KW)
                _reset_peak_rss()
                per_call, reps = _time_call(fn)
                r = {"backend": backend, "form": form, "K": K, "P": P,
                     "n_sel": n_sel, "shards": n_sh, "reps": reps,
                     "sec_per_call": per_call, "plans_per_sec": P / per_call,
                     "peak_rss_mb": round(_peak_rss_mb(), 1)}
                if form == "index":
                    r["ns_per_element"] = per_call / (P * n_sel) * 1e9
                if backend == "numpy" and form == "dense":
                    base = r["plans_per_sec"]
                r["speedup_vs_numpy"] = (r["plans_per_sec"] / base
                                         if base else None)
                if backend == "jax":
                    if n_sh == 1:
                        single[form] = fn()
                    elif form in single:
                        ref = single[form]
                        r["max_rel_diff_vs_single"] = float(np.max(
                            np.abs(fn() - ref) / np.maximum(np.abs(ref),
                                                            1e-12)))
                rows.append(r)
                speedup = (f"x{r['speedup_vs_numpy']:.1f} vs numpy"
                           if r["speedup_vs_numpy"] is not None
                           else "no dense-numpy baseline")
                print(f"  K={K:>7} P={P:>5} {tag:>14}: "
                      f"{r['plans_per_sec']:>12.0f} plans/s "
                      f"({r['sec_per_call'] * 1e3:.2f} ms/call, {speedup}, "
                      f"peak {r['peak_rss_mb']:.0f} MB)")
    return rows


def bench_rounds(Ks, scheduler: str, backend: str, max_rounds: int) -> list:
    """End-to-end round latency through the experiment layer (fleet axis)."""
    from repro.experiment.presets import get_preset

    rows = []
    for K in Ks:
        spec = get_preset("fleet-scale", scheduler=scheduler, num_devices=K,
                          scoring_backend=backend, max_rounds=max_rounds)
        t0 = time.perf_counter()
        result = spec.run()
        wall = time.perf_counter() - t0
        n_rounds = len(result.records)
        sim_mean = float(np.mean(
            [v["mean_round_time"] for v in result.summary.values()]))
        rows.append({
            "K": K, "scheduler": scheduler, "backend": backend,
            "rounds": n_rounds, "wall_s": wall,
            "wall_s_per_round": wall / max(n_rounds, 1),
            "sim_mean_round_time_s": sim_mean,
        })
        print(f"  K={K:>6} {scheduler}/{backend}: {n_rounds} rounds in "
              f"{wall:.2f}s wall ({wall / max(n_rounds, 1) * 1e3:.0f} "
              f"ms/round), sim mean T={sim_mean:.1f}s")
    return rows


def run_sharded_gate(args) -> dict:
    """CI gate: single-lane vs shard_map scoring at one (K, P).

    Gates (at ``--gate-k``, default 1e5, on the dense form — the one whose
    per-shard work actually shrinks by K/N):

    - parity: sharded scores within RELATIVE 1e-5 of single-lane (both
      forms; the single lane scores fully in f32 in-graph while the
      sharded path combines f32 partials in f64, so agreement is bounded
      by f32 resolution — relative, not absolute);
    - throughput: sharded plans/s >= ``--min-throughput-ratio`` x
      single-lane (the required ratio is halved when the machine has only
      one core — sharding cannot beat a lane it timeshares with);
    - scaling efficiency: speedup / N_eff >= ``--min-efficiency``, with
      N_eff = min(shards, cpu cores) — the shards that can actually run
      concurrently.
    """
    import jax

    N = args.shards
    if N < 2:
        raise SystemExit("--sharded-gate needs --shards >= 2")
    if jax.device_count() < N:
        raise SystemExit(
            f"--sharded-gate needs {N} host devices, found "
            f"{jax.device_count()} (launch via repro.launch.bootstrap or "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={N})")
    K, P = args.gate_k, 256
    n_eff = min(N, os.cpu_count() or 1)
    rng = np.random.default_rng(0)
    times = rng.uniform(1.0, 100.0, K)
    counts = rng.integers(0, 50, K).astype(np.float64)
    available = rng.random(K) < 0.9
    n_sel = max(1, K // 100)
    idx = _make_candidates(rng, available, n_sel, P, N)
    plans = indices_to_plans(idx, K) if K <= DENSE_MAX_K else None

    arms, failures = {}, []
    forms = (["dense", "index"] if plans is not None else ["index"])
    for form in forms:
        for n_sh in (1, N):
            if form == "dense":
                fn = lambda: scoring.score_plans(
                    times, counts, plans, backend="jax", num_shards=n_sh,
                    **KW)
            else:
                fn = lambda: scoring.score_plan_indices(
                    times, counts, idx, backend="jax", num_shards=n_sh, **KW)
            _reset_peak_rss()
            per_call, reps = _time_call(fn, min_s=0.5)
            arms[(form, n_sh)] = {
                "form": form, "shards": n_sh, "K": K, "P": P, "n_sel": n_sel,
                "reps": reps, "sec_per_call": per_call,
                "plans_per_sec": P / per_call,
                "peak_rss_mb": round(_peak_rss_mb(), 1),
                "scores": fn()}
            tag = f"jax/{form}" + (f"@{n_sh}" if n_sh > 1 else "")
            print(f"  K={K:>7} P={P:>5} {tag:>14}: "
                  f"{P / per_call:>12.0f} plans/s "
                  f"({per_call * 1e3:.2f} ms/call)")

    for form in forms:
        ref = arms[(form, 1)]["scores"]
        diff = float(np.max(np.abs(arms[(form, N)]["scores"] - ref)
                            / np.maximum(np.abs(ref), 1e-12)))
        arms[(form, N)]["max_rel_diff_vs_single"] = diff
        if diff > 1e-5:
            failures.append(f"{form}: sharded-vs-single relative score "
                            f"diff {diff:.2e} > 1e-5")

    gate_form = "dense" if "dense" in forms else "index"
    t1 = arms[(gate_form, 1)]["sec_per_call"]
    tn = arms[(gate_form, N)]["sec_per_call"]
    speedup = t1 / tn
    efficiency = speedup / n_eff
    req_ratio = (args.min_throughput_ratio if n_eff > 1
                 else args.min_throughput_ratio / 2)
    if speedup < req_ratio:
        failures.append(
            f"{gate_form}: sharded throughput x{speedup:.2f} of single-lane "
            f"< required x{req_ratio:.2f} (N_eff={n_eff})")
    if efficiency < args.min_efficiency:
        failures.append(
            f"{gate_form}: scaling efficiency {efficiency:.2f} "
            f"(speedup x{speedup:.2f} / N_eff={n_eff}) < "
            f"{args.min_efficiency}")
    print(f"  gate[{gate_form}]: speedup x{speedup:.2f}, efficiency "
          f"{efficiency:.2f} (N_eff={n_eff}), "
          f"{'FAIL' if failures else 'ok'}")

    for a in arms.values():
        del a["scores"]
    return {
        "mode": "sharded-gate", "shards": N, "n_eff": n_eff,
        "gate_form": gate_form, "jax_backend": scoring._jax_backend_name(),
        "device_count": int(jax.device_count()),
        "arms": list(arms.values()),
        "gate": {"speedup": speedup, "efficiency": efficiency,
                 "min_throughput_ratio": req_ratio,
                 "min_efficiency": args.min_efficiency,
                 "failures": failures},
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (small K, fewer reps)")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--scheduler", default="bods",
                    help="scheduler for the end-to-end round-latency sweep")
    ap.add_argument("--shards", type=int, default=1,
                    help="add fleet-sharded arms with this many shards "
                         "(re-execs via repro.launch.bootstrap so the host "
                         "platform has the devices)")
    ap.add_argument("--sharded-gate", action="store_true",
                    help="run the CI sharded-scoring regression gate "
                         "instead of the full sweep")
    ap.add_argument("--gate-k", type=int, default=100_000,
                    help="fleet size for --sharded-gate")
    ap.add_argument("--min-throughput-ratio", type=float, default=1.0,
                    help="gate: sharded plans/s >= this x single-lane "
                         "(halved automatically on single-core hosts)")
    ap.add_argument("--min-efficiency", type=float, default=0.5,
                    help="gate: speedup / N_eff >= this")
    args = ap.parse_args(argv)

    if args.sharded_gate:
        out = run_sharded_gate(args)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"\nwrote {args.out}")
        if out["gate"]["failures"]:
            raise SystemExit("bench_fleet sharded gate FAILED:\n  "
                             + "\n  ".join(out["gate"]["failures"]))
        return

    Ks = SMOKE_KS if args.smoke else FULL_KS
    Ps = SMOKE_PS if args.smoke else FULL_PS
    # The pallas kernel runs only on a TPU backend (it raises elsewhere);
    # the JSON's jax_backend says which arms a run could have.
    backends = ["numpy", "jax"]
    if scoring._jax_backend_name() == "tpu":
        backends.append("pallas")

    print(f"== scoring core: plans-scored/sec (backends={backends}, "
          f"shards={args.shards}) ==")
    scoring_rows = bench_scoring(Ks, Ps, backends, shards=args.shards)

    round_Ks = [k for k in Ks if k <= 10_000]
    print("== end-to-end round latency (fleet-scale preset) ==")
    round_rows = bench_rounds(round_Ks, args.scheduler, "jax",
                              max_rounds=2 if args.smoke else 3)

    out = {
        "smoke": args.smoke,
        "jax_backend": scoring._jax_backend_name(),
        "shards": args.shards,
        "dense_max_k": DENSE_MAX_K,
        "Ks": Ks, "Ps": Ps,
        "scoring": scoring_rows,
        "rounds": round_rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

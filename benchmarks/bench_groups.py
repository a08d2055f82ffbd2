"""Paper Tables 1 & 2: convergence accuracy + time-to-target per scheduler,
Groups A and B, IID and non-IID (scheduler-plane benchmark on the calibrated
synthetic convergence model; the REAL-training variant is
``--real`` in benchmarks/bench_real_fl.py)."""

from __future__ import annotations

from benchmarks.common import GROUPS, SCHEDULERS, fmt_time, run_group


def run(group: str = "A", non_iid: bool = True, schedulers=None, seeds=(1, 2, 3)):
    import numpy as np

    schedulers = schedulers or SCHEDULERS
    dist = "non-IID" if non_iid else "IID"
    print(f"\n== Table {'1' if group == 'A' else '2'} (Group {group}, {dist}, "
          f"mean over {len(seeds)} seeds) ==")
    job_names = [s[0] for s in GROUPS[group]]
    header = f"{'method':8s} " + " ".join(f"{n:>18s}" for n in job_names)
    print(header + f"   {'makespan':>10s}   (best_acc / t2t_min)")
    rows = {}
    all_hit = {}
    for sched in schedulers:
        accs = {n: [] for n in job_names}
        t2ts = {n: [] for n in job_names}
        tt_makespans = []  # time at which ALL jobs reached their targets
        for seed in seeds:
            res = run_group(group, sched, non_iid, seed=seed)
            for name in job_names:
                v = res["summary"][name]
                accs[name].append(v["best_accuracy"])
                t2ts[name].append(v["time_to_target"])
            tt = [v["time_to_target"] for v in res["summary"].values()]
            tt_makespans.append(max(tt) if all(t is not None for t in tt)
                                else None)
        cells = []
        for name in job_names:
            hit = [t for t in t2ts[name] if t is not None]
            t2t = float(np.mean(hit)) if len(hit) == len(seeds) else None
            cells.append(f"{np.mean(accs[name]):.3f}/{fmt_time(t2t):>7s}")
            print(f"CSV,group{group},{dist},{sched},{name},"
                  f"{np.mean(accs[name]):.4f},"
                  f"{'' if t2t is None else f'{t2t:.0f}'}")
        ok = all(t is not None for t in tt_makespans)
        all_hit[sched] = ok
        rows[sched] = float(np.mean([t for t in tt_makespans if t is not None])) if ok else None
        mk = f"{rows[sched]/60:9.1f}m" if ok else "   (miss)"
        print(f"{sched:8s} " + " ".join(f"{c:>18s}" for c in cells) + f"   {mk}")
    # Rank only schedulers that hit EVERY job's target on EVERY seed —
    # finishing max_rounds fast while missing targets is not a win.
    qualified = {s: t for s, t in rows.items() if t is not None}
    if qualified and rows.get("random"):
        best = min(qualified, key=qualified.get)
        print(f"-> fastest all-targets makespan: {best} "
              f"({rows['random']/qualified[best]:.2f}x vs random)"
              + (f"; missed targets: {[s for s, ok in all_hit.items() if not ok]}"))
    return rows


def main():
    for group in ("A", "B"):
        for non_iid in (True, False):
            run(group, non_iid)


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()

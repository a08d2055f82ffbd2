"""Chaos smoke: CLI-level crash-consistency check for the scheduler service.

Drives ``python -m repro.serve`` as real subprocesses:

1. **Reference arm** — run an online spec WITH the faults axis end-to-end,
   dumping the engine's per-round records.
2. **Crash arm** — same spec with ``--checkpoint-dir``/``--checkpoint-every``
   and ``--crash-after N``: the process hard-kills itself with
   ``os._exit(137)`` mid-horizon (the ``kill -9`` equivalent — no cleanup,
   no flush), leaving only the atomically committed checkpoints behind.
3. **Resume arm** — ``--resume DIR`` restarts from the newest committed
   checkpoint and runs the remaining trace.

Gates (written to ``BENCH_chaos.json``, enforced in CI chaos-smoke):
- the crash arm really dies with exit code 137;
- the resumed run's full record trajectory is BIT-IDENTICAL to the
  uninterrupted reference (every field of every round, including device
  ids, dropped/corrupt sets, costs, and accuracies);
- every recorded metric is finite despite dropouts, crashes, stragglers,
  domain outages, and corrupted uploads.

  PYTHONPATH=src python -m benchmarks.chaos_smoke
  PYTHONPATH=src python -m benchmarks.chaos_smoke --overload

``--overload`` swaps the spec for the ``slo-overload`` preset (overload
traffic + faults + the full SLO resilience stack: degradation ladder,
shedding, circuit breakers, bounded retries, watchdog) and additionally
gates on the degradation histogram being non-empty — crash consistency
must hold WHILE the service is actively degrading, not just in steady
state. Output goes to ``BENCH_overload_chaos.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile


def _serve(args, cwd):
    # Each child needs the device: this parent may import repro (for the
    # spec) but must never start a JAX backend, which would hold the chip.
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise SystemExit("chaos_smoke: the parent started a JAX backend; "
                             "its repro.serve children could not reach "
                             "the device")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-m", "repro.serve"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True)


def _spec_json(overload: bool = False) -> dict:
    from repro.experiment.presets import get_preset
    from repro.faults import FaultSpec

    if overload:
        # The slo-overload preset: arrivals ~3x faster than the drain rate
        # over a faulty fleet, with the queue-depth degradation ladder,
        # shedding, breakers, bounded retries, and the watchdog all armed —
        # and NO wall-clock deadline, so the trajectory (including fired
        # rungs) must replay bit-identically across kill -9 + resume.
        return get_preset("slo-overload", horizon=8_000.0).to_dict()
    spec = get_preset("online-smoke", scheduler="bods", num_devices=40,
                      horizon=10_000.0, interarrival=700.0)
    spec = spec.replace(faults=FaultSpec(
        seed=3, dropout_rate=0.1, crash_rate=0.002, straggler_rate=0.1,
        num_domains=4, domain_outage_rate=0.02, corrupt_rate=0.05))
    return spec.to_dict()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--overload", action="store_true",
                    help="run the slo-overload preset instead: overload "
                         "traffic + faults + the full resilience stack; "
                         "adds a non-empty-degradation-histogram gate")
    ap.add_argument("--out", default=None,
                    help="output JSON (default BENCH_chaos.json, or "
                         "BENCH_overload_chaos.json with --overload)")
    ap.add_argument("--crash-after", type=int, default=7)
    ap.add_argument("--checkpoint-every", type=int, default=3)
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = ("BENCH_overload_chaos.json" if args.overload
                    else "BENCH_chaos.json")

    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        with open(spec_path, "w") as f:
            json.dump(_spec_json(args.overload), f)

        print("== reference arm (uninterrupted) ==")
        ref = _serve(["--spec", spec_path,
                      "--records-out", os.path.join(tmp, "ref.json")], tmp)
        if ref.returncode != 0:
            raise SystemExit(f"reference run failed:\n{ref.stderr}")

        print(f"== crash arm (--crash-after {args.crash_after}, "
              f"checkpoint every {args.checkpoint_every}) ==")
        ck = os.path.join(tmp, "ckpt")
        crash = _serve(["--spec", spec_path, "--checkpoint-dir", ck,
                        "--checkpoint-every", str(args.checkpoint_every),
                        "--crash-after", str(args.crash_after)], tmp)
        if crash.returncode != 137:
            failures.append(f"crash arm exited {crash.returncode}, "
                            f"expected 137 (kill -9 equivalent)\n"
                            f"{crash.stderr[-2000:]}")

        print("== resume arm (--resume) ==")
        res = _serve(["--resume", ck,
                      "--records-out", os.path.join(tmp, "res.json")], tmp)
        if res.returncode != 0:
            failures.append(f"resume run failed (exit {res.returncode}):\n"
                            f"{res.stderr[-2000:]}")

        records_ref = records_res = []
        rungs = {}
        if not failures:
            with open(os.path.join(tmp, "ref.json")) as f:
                records_ref = json.load(f)
            with open(os.path.join(tmp, "res.json")) as f:
                records_res = json.load(f)
            if records_ref != records_res:
                n = sum(1 for a, b in zip(records_ref, records_res)
                        if a != b)
                failures.append(
                    f"crash/resume trajectory DIVERGED from the "
                    f"uninterrupted reference: {len(records_ref)} vs "
                    f"{len(records_res)} rounds, {n} differing records")
            bad = [r for r in records_ref
                   for v in (r["accuracy"], r["loss"], r["round_time"])
                   if v is None or v != v or v in (float("inf"),
                                                  float("-inf"))]
            if bad:
                failures.append(f"{len(bad)} non-finite metrics under chaos")
            dropped = sum(len(r["dropped"]) for r in records_ref)
            corrupt = sum(len(r.get("corrupt_ids", []))
                          for r in records_ref)
            if dropped == 0 or corrupt == 0:
                failures.append(f"faults axis inert in chaos run "
                                f"(dropped={dropped}, corrupt={corrupt})")
            print(f"  {len(records_ref)} rounds bit-identical across "
                  f"kill -9 + resume; dropped={dropped} corrupt={corrupt}")
            if args.overload:
                for r in records_ref:
                    if r.get("rung") is not None:
                        rungs[r["rung"]] = rungs.get(r["rung"], 0) + 1
                degraded = sum(v for k, v in rungs.items() if k != "full")
                if degraded == 0:
                    failures.append(
                        "overload arm never degraded — the ladder was "
                        "inert (empty degradation histogram)")
                hist = " ".join(f"{k}={v}" for k, v in sorted(rungs.items()))
                print(f"  degradation histogram: {hist or 'EMPTY'}")

    out = {"overload": args.overload,
           "crash_after": args.crash_after,
           "checkpoint_every": args.checkpoint_every,
           "rounds": len(records_ref),
           "rung_counts": rungs,
           "gate": {"failures": failures}}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out}")
    if failures:
        raise SystemExit("chaos_smoke FAILED:\n  " + "\n  ".join(failures))


if __name__ == "__main__":
    main()

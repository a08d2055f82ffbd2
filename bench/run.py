"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration and traffic mix) comes from ``BENCHMARK.json`` and
the files it names. The run builds the experiment from ``--seed``, warms
up every compiled shape, measures ``--seconds`` of steady engine rounds,
then checks what the timed path produced against the plain references
(``bench/harness/checks.py``). With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` it profiles the start of the
window and carries the per-layer metrics, read by ``bench/metrics/<name>.py``.

It refuses to run (exit 2, no result) when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()


def _since_process_start() -> float:
    """Seconds from this process's creation to ``T_START`` (Linux
    ``/proc``; 0 where it is missing)."""
    import os

    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, IndexError, ValueError):
        return 0.0
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


T_BEFORE = _since_process_start()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _paths() -> None:
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _compile_cache() -> None:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path inside the checkout (the program's own cache
    set-up reads the same variable). Every program is cached, however
    quick its compile, so a warm run compiles nothing."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    from bench.harness import flops

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    peaks = flops.peaks(kind) if require_tpu else {"bf16_flops_per_s": 1.0}
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peaks": peaks, "devices": devs[:chips]}


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_metric(name: str, view, root: str = ROOT):
    """The per-layer metric ``name`` from its own reader file."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def run(args, require_tpu: bool = True, cell=None, trace_dir=None) -> dict:
    """One run; returns the result object (printed by ``main``)."""
    from bench.harness import cells, checks, correctness, runner
    from bench.harness.clock import CompileClock

    cell = cell or cells.load_cell(args.workload)
    dev = device_info(cell.chips, require_tpu)
    CompileClock.shared()
    run_ = runner.CellRun(cell, args.seed)
    session = None
    if args.trace:
        from bench.harness.trace import Session

        session = Session(trace_dir or os.path.join(
            ROOT, ".bench_trace", args.workload))
        session.spans_on()
    train = cell.traffic["runtime"] == "bench_real_fl"
    run_.warm_up(snapshot_rounds=correctness.ref_rounds(cell) if train else 0)
    setup_s = T_BEFORE + time.perf_counter() - T_START
    win = run_.window(args.seconds, trace=session,
                      trace_seconds=min(args.seconds,
                                        cell.traffic["trace_seconds"]))
    summary = runner.summarize_window(run_, win)
    print(f"bench: window {win['elapsed']:.3f} s, {summary['rounds']} rounds, "
          f"{summary['decisions']} decisions, compiles in window "
          f"{win['compiles']}, cache reads in window {win['cache_hits']}",
          file=sys.stderr)
    metrics, breakdown, extra_dev = {}, None, {}
    if session is not None:
        (_, d0), (_, d1) = win["traced"]
        cohorts = {f"{r.job}/{r.round_idx}": len(r.device_ids)
                   for r in run_.engine.records}
        view = session.reduce(cohorts, d1 - d0, cell.config, dev["peaks"])
        for name in cell.per_layer:
            v = read_metric(name, view)
            if v is not None:
                unit = next(m["unit"] for m in cells.benchmark()["per_layer"]
                            if m["name"] == name)
                metrics[name] = {"value": float(v), "unit": unit}
        breakdown = view.breakdown()
        extra_dev = {"busy_s": view.busy_s(), "window_s": view.window_s}
    else:
        e2e = {"rounds_per_s": (summary["rounds_per_s"], "rounds/s"),
               "setup_s": (setup_s, "s")}
        for name in cell.end_to_end:
            v, unit = e2e[name]
            metrics[name] = {"value": float(v), "unit": unit}
    mem = memory_peak(dev["devices"])
    t_check = time.perf_counter()
    verdict = correctness.check(run_, cell)
    del run_
    print(f"bench: setup {setup_s:.3f} s, check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    correct = checks.is_correct(verdict) and summary["failed"] == 0
    result = {"correct": correct, "attempted": summary["rounds"],
              "failed": summary["failed"], "metrics": metrics,
              "device": {"platform": dev["platform"], "kind": dev["kind"],
                         "count": dev["count"], "memory_peak_bytes": mem,
                         **extra_dev}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    _compile_cache()
    result = run(args)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

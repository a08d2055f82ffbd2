"""Smoke test of the main path on a TPU: fleet scheduling, FL training and
the online service, each through the entry points a user calls.

    python chip_smoke.py               # one chip: phases 1-4 below
    python chip_smoke.py --four-chips  # a four-chip host: the sharded fleet

One process owns the chip for the whole run. It refuses to run anything
when JAX finds no TPU (there is no CPU branch), places the persistent
compile cache first (``repro.launch.bootstrap.setup_compile_cache``), then
runs the phases in order. Every phase checks its output against a
reference and raises on a mismatch; a failed phase does not stop the
others, but makes the exit code non-zero.

1. scoring   — ``score_plans``/``score_plan_indices`` at K=100,000 devices
               and P=512 candidate plans, with the jax and pallas backends,
               on dense int8 plans and on index-form plans, against the
               float64 numpy backend (relative tolerance 1e-5).
2. scheduler — the ``fleet-scale`` preset at K=100,000 with fused BODS, two
               jobs, three rounds: every chosen plan holds ``n_sel``
               distinct available devices and a finite cost, and its cost
               re-scored in float64 numpy matches the estimate the fused
               search reported (relative 1e-5).
3. training  — ``real_fl`` with the paper's VGG-16 (32x32x3, ~34M
               parameters) and LeNet-5 at full width through the fused
               runtime, 100 devices, 10 per round, one local epoch, three
               rounds (lr 0.002 for VGG-16, 0.02 for LeNet-5): finite
               losses, accuracies in [0, 1], no recompiles after each
               job's first round, and round 1's global update
               within 5% (relative L2) of the same round run at
               ``jax.default_matmul_precision("highest")``.
4. service   — ``SchedulerService`` on the ``online-smoke`` preset with an
               inert ``slo`` axis over a 6,000 s horizon (a few dozen
               decisions): every decision is a valid plan and every
               admitted tenant completes rounds.

``--four-chips`` runs only the sharded fleet: the ``fleet-scale`` preset at
K=1,000,000 with ``num_shards=4`` under the ``shard_map`` executor, against
the same spec at ``num_shards=1``; the chosen plans must be identical.

Earlier lines report each phase's wall and compile seconds as smoke
timings (set-up cost included; not benchmark metrics). The last line is
one JSON object: ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SCORE_RTOL = 1e-5      # numpy float64 reference vs the float32 device paths
UPDATE_RTOL = 0.05     # default vs "highest" matmul precision, round-1 update


class SmokeFailure(AssertionError):
    """A phase's output disagreed with its reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def max_rel_err(out, ref) -> float:
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    check(np.all(np.isfinite(out)), "non-finite output")
    return float(np.max(np.abs(out - ref) / np.maximum(np.abs(ref), 1e-12)))


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit reports its retrieval time instead)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def record_decisions(scheduler) -> list:
    """Wrap ``scheduler.schedule`` to keep each decision with its context."""
    seen = []
    inner = scheduler.schedule

    def schedule(ctx):
        plan = inner(ctx)
        seen.append(dict(
            job=ctx.job, n_sel=ctx.n_sel, available=ctx.available.copy(),
            times=np.array(ctx.expected_times), counts=ctx.counts.copy(),
            plan=np.asarray(plan).copy(), est=scheduler.last_estimated_cost))
        return plan

    scheduler.schedule = schedule
    return seen


def check_plan(d: dict) -> None:
    plan = d["plan"]
    check(plan.dtype == bool and plan.shape == d["available"].shape,
          f"plan is {plan.dtype}{plan.shape}, not a (K,) bool mask")
    check(int(plan.sum()) == d["n_sel"],
          f"job {d['job']}: plan selects {int(plan.sum())} devices, "
          f"not n_sel={d['n_sel']}")
    check(bool(np.all(d["available"][plan])),
          f"job {d['job']}: plan selects unavailable devices")


# ---- phase 1: fleet scoring ---------------------------------------------

def phase_scoring(K: int = 100_000, P: int = 512,
                  backends=("jax", "pallas")) -> dict:
    from repro.core import scoring
    from repro.core.cost import CostModel
    from repro.core.devices import DevicePool
    from repro.core.plans import indices_to_plans, random_plan_indices

    rng = np.random.default_rng(0)
    pool = DevicePool.heterogeneous(K, 1, seed=0)
    n_sel = K // 100
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0], n_sel=n_sel)
    times = pool.expected_times(0, 5.0)
    counts = rng.integers(0, 50, K).astype(np.float64)
    idx = random_plan_indices(rng, rng.random(K) < 0.9, n_sel, P)
    dense = indices_to_plans(idx, K, dtype=np.int8)
    kw = dict(alpha=cm.alpha, beta=cm.beta, time_scale=cm.time_scale,
              fairness_scale=cm.fairness_scale,
              delta_fairness=cm.delta_fairness)

    ref = scoring.score_plans(times, counts, dense, backend="numpy", **kw)
    ref_idx = scoring.score_plan_indices(times, counts, idx, backend="numpy",
                                         **kw)
    check(max_rel_err(ref_idx, ref) <= 1e-12,
          "numpy dense and index references disagree")
    errs = {}
    for b in backends:
        errs[f"{b}/dense"] = max_rel_err(
            scoring.score_plans(times, counts, dense, backend=b, **kw), ref)
        errs[f"{b}/index"] = max_rel_err(
            scoring.score_plan_indices(times, counts, idx, backend=b, **kw),
            ref)
    for arm, err in errs.items():
        check(err <= SCORE_RTOL,
              f"{arm}: max relative error {err:.3e} > {SCORE_RTOL}")
    return {"K": K, "P": P, "max_rel_err": errs}


# ---- phase 2: scheduler decisions ---------------------------------------

def phase_scheduler(K: int = 100_000, rounds: int = 3) -> dict:
    from repro.experiment import get_preset

    spec = get_preset("fleet-scale", scheduler="bods", num_devices=K,
                      search_backend="fused", n_jobs=2, max_rounds=rounds)
    ex = spec.build()
    cm = ex.engine.cost_model
    decisions = record_decisions(ex.engine.scheduler)
    result = ex.run()
    check(len(decisions) == len(result.records) == 2 * rounds,
          f"{len(decisions)} decisions for {len(result.records)} rounds, "
          f"expected {2 * rounds}")
    worst = 0.0
    for d in decisions:
        check_plan(d)
        check(d["est"] is not None and np.isfinite(d["est"]),
              f"job {d['job']}: non-finite estimated cost {d['est']}")
        ref = cm.cost_batch(d["times"], d["counts"], d["plan"][None],
                            backend="numpy")
        worst = max(worst, max_rel_err([d["est"]], ref))
    check(worst <= SCORE_RTOL,
          f"fused BODS estimate vs numpy float64 re-score: {worst:.3e}")
    return {"K": K, "decisions": len(decisions), "n_sel": spec.effective_n_sel(),
            "max_rel_err_est": worst}


# ---- phase 3: real_fl training at the paper's widths ---------------------

# Plain SGD on VGG-16, which has no normalization layer, diverges at the
# lr=0.02 the real_fl presets use: the loss reaches 1.8e24 after one round
# and NaN after two, on the CPU as on the chip. 0.005 sits at the edge
# (round-0 loss 4.0); 0.002 trains.
LR = {"paper-vgg16": 0.002}


def _real_fl_spec(rounds: int, models=("paper-vgg16", "paper-lenet5"),
                  num_devices: int = 100, n_sel: int = 10):
    from repro.experiment import ExperimentSpec, JobSpec, PoolSpec

    # target 1.01 is unreachable, so every job runs all of its rounds.
    jobs = tuple(JobSpec(name=m, model=m, target_metric=1.01,
                         max_rounds=rounds, local_epochs=1, batch_size=32,
                         lr=LR.get(m, 0.02)) for m in models)
    return ExperimentSpec(name="chip-smoke-real-fl", jobs=jobs,
                          pool=PoolSpec(num_devices=num_devices, seed=5),
                          runtime="real_fl", non_iid=True, n_sel=n_sel)


def _host(tree):
    import jax

    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]


def _round1(spec):
    """Run the spec; return per job (init params, round-1 params, round-1
    cohort) plus the records and the recompile count after each round."""
    ex = spec.build()
    rt = ex.engine.runtime
    init = {j: _host(rt.params_of(j)) for j in range(len(spec.jobs))}
    first, trail = {}, []

    def on_round(rec):
        if rec.round_idx == 0:
            first[rec.job] = (_host(rt.params_of(rec.job)),
                              np.asarray(rec.device_ids).copy())
        trail.append((len(first), rt.recompiles))

    result = ex.run(on_round=on_round)
    return init, first, result.records, trail


def _l2(leaves) -> float:
    return float(np.sqrt(sum(np.sum(np.square(x, dtype=np.float64))
                             for x in leaves)))


def phase_training(rounds: int = 3, **spec_kw) -> dict:
    import jax

    spec = _real_fl_spec(rounds, **spec_kw)
    init, first, records, trail = _round1(spec)
    n_jobs = len(spec.jobs)
    check(len(records) == n_jobs * rounds,
          f"{len(records)} rounds recorded, expected {n_jobs * rounds}")
    for r in records:
        check(np.isfinite(r.loss), f"job {r.job} round {r.round_idx}: "
              f"loss {r.loss}")
        check(0.0 <= r.accuracy <= 1.0, f"job {r.job} round {r.round_idx}: "
              f"accuracy {r.accuracy}")
    warm = [c for seen, c in trail if seen == n_jobs]
    check(warm and warm[0] >= 1, f"recompile counter not counting: {trail}")
    check(warm[-1] == warm[0],
          f"recompiles grew after every job's first round: {trail}")

    one_round = tuple(dataclasses.replace(j, max_rounds=1) for j in spec.jobs)
    with jax.default_matmul_precision("highest"):
        _, first_hi, _, _ = _round1(spec.replace(jobs=one_round))
    update_err = {}
    for j, job in enumerate(spec.jobs):
        (p_def, ids_def), (p_hi, ids_hi) = first[j], first_hi[j]
        check(np.array_equal(ids_def, ids_hi),
              f"{job.name}: round-1 cohorts differ between the two runs")
        diff = _l2([a - b for a, b in zip(p_def, p_hi)])
        step = _l2([b - a for a, b in zip(init[j], p_hi)])
        update_err[job.name] = diff / max(step, 1e-30)
        check(update_err[job.name] <= UPDATE_RTOL,
              f"{job.name}: round-1 update differs from the 'highest' "
              f"precision run by {update_err[job.name]:.3e} (relative L2)")
    return {"rounds": len(records), "recompiles": warm[-1],
            "final_loss": {r.job: float(r.loss) for r in records},
            "round1_update_rel_err": update_err}


# ---- phase 4: the online service ----------------------------------------

def phase_service(horizon: float = 6_000.0) -> dict:
    from repro.experiment import get_preset
    from repro.experiment.slo import SLOSpec
    from repro.serve import SchedulerService, trace_from_spec

    spec = get_preset("online-smoke", horizon=horizon).replace(slo=SLOSpec())
    check(spec.effective_slo() is None, "slo axis is not inert")
    svc = SchedulerService(spec)
    decisions = record_decisions(svc.engine.scheduler)
    trace = trace_from_spec(spec.arrivals, len(svc.templates),
                            svc.engine.pool.num_devices)
    report = svc.run(trace)
    check(len(decisions) >= 24, f"only {len(decisions)} decisions")
    for d in decisions:
        check_plan(d)
    admitted = {n: t for n, t in svc.metrics.tenants.items() if t.admissions}
    check(admitted, "no tenant was admitted")
    idle = [n for n, t in admitted.items() if t.rounds == 0]
    check(not idle, f"admitted tenants without a round: {idle}")
    return {"decisions": len(decisions), "tenants": len(admitted),
            "rounds": report.rounds_completed}


# ---- --four-chips: the sharded fleet ------------------------------------

def phase_sharded_fleet(K: int = 1_000_000, rounds: int = 2,
                        num_shards: int = 4) -> dict:
    import jax

    from repro.core import shard
    from repro.experiment import get_preset
    from repro.monitoring import trace

    check(len(jax.devices()) == num_shards,
          f"{len(jax.devices())} devices, expected {num_shards}")
    base = get_preset("fleet-scale", scheduler="bods", num_devices=K,
                      search_backend="fused", n_jobs=2, max_rounds=rounds)
    plans = {}
    for n in (num_shards, 1):
        spec = base.replace(fleet={"num_shards": n})
        ex = spec.build()
        check(ex.engine.cost_model.num_shards == n,
              f"cost model has {ex.engine.cost_model.num_shards} shards")
        if n > 1:
            check(shard._resolve_executor("auto", n) == "shard_map",
                  "the auto executor is not shard_map")
        decisions = record_decisions(ex.engine.scheduler)
        trace.clear()
        trace.enable()
        try:
            ex.run()
        finally:
            trace.disable()
        used = {e["args"].get("shards") for e in trace.get_tracer().events()
                if e.get("name") == "bods_acquire"}
        trace.clear()
        check(used == {n}, f"num_shards={n}: BODS ran on {used} shards")
        for d in decisions:
            check_plan(d)
        plans[n] = [(d["job"], np.flatnonzero(d["plan"])) for d in decisions]
    same = [a[0] == b[0] and np.array_equal(a[1], b[1])
            for a, b in zip(plans[num_shards], plans[1])]
    check(len(plans[1]) == len(plans[num_shards]) and all(same),
          f"sharded vs single-lane plans differ at decisions "
          f"{[i for i, s in enumerate(same) if not s]}")
    return {"K": K, "num_shards": num_shards, "executor": "shard_map",
            "decisions": len(plans[1]), "identical": True}


PHASES = (("scoring", phase_scoring), ("scheduler", phase_scheduler),
          ("training", phase_training), ("service", phase_service))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-fleet phase on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.bootstrap import setup_compile_cache

    cache = setup_compile_cache() or os.environ["JAX_COMPILATION_CACHE_DIR"]
    print(f"smoke: compile cache at {cache}")
    clock = CompileClock()
    phases = ((("sharded_fleet", phase_sharded_fleet),) if args.four_chips
              else PHASES)
    failed = []
    for name, fn in phases:
        t0, c0, h0 = time.perf_counter(), clock.compile_s, clock.cache_hits
        try:
            info, status = fn(), "ok"
        except Exception as e:  # a failed phase must not hide the others
            traceback.print_exc()
            info, status = {"error": f"{type(e).__name__}: {e}"}, "FAILED"
            failed.append(name)
        print(f"smoke timing: phase={name} status={status} "
              f"wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={clock.compile_s - c0:.3f} "
              f"cache_hits={clock.cache_hits - h0}")
        print(f"smoke result: phase={name} {json.dumps(info, default=str)}",
              flush=True)
    print(json.dumps({"ok": not failed, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

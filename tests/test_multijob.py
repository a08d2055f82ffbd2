"""Multi-job engine invariants: occupancy exclusivity, async progress,
fault handling, straggler mitigation."""

import numpy as np
import pytest

from repro.config.base import ArchFamily, JobConfig, ModelConfig
from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.multijob import MultiJobEngine
from repro.core.schedulers import get_scheduler
from repro.fl.runtime import SyntheticRuntime


def tiny_jobs(n=3, target=0.75, max_rounds=40):
    mc = ModelConfig(name="t", family=ArchFamily.CNN, cnn_spec=(("flatten",),),
                     input_shape=(4, 4, 1), num_classes=10)
    return [JobConfig(job_id=i, model=mc, target_metric=target,
                      max_rounds=max_rounds) for i in range(n)]


def build(sched="random", n_jobs=3, seed=1, **engine_kw):
    pool = DevicePool.heterogeneous(50, n_jobs, seed=seed)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0] * n_jobs, n_sel=5)
    s = get_scheduler(sched, cost_model=cm, seed=0)
    rt = SyntheticRuntime(num_jobs=n_jobs, num_devices=50, seed=2)
    eng = MultiJobEngine(tiny_jobs(n_jobs), pool, cm, s, rt, n_sel=5, **engine_kw)
    return eng


def test_no_device_double_booked():
    """At no simulated instant may a device serve two jobs (paper constraint)."""
    eng = build()
    eng.run()
    # Reconstruct per-device busy intervals from the records.
    intervals = {}
    for r in eng.records:
        for k in r.device_ids:
            intervals.setdefault(int(k), []).append((r.t_start, r.t_end, r.job))
    for k, iv in intervals.items():
        iv.sort()
        for (s1, e1, j1), (s2, e2, j2) in zip(iv, iv[1:]):
            if j1 != j2:
                # different jobs may not overlap on a device; same-job rounds
                # are sequential by construction
                assert s2 >= s1 - 1e-9
                # the device was released at its own finish time <= e1
                # so a strictly earlier start of another job is impossible
                assert s2 >= s1


def test_all_jobs_progress_and_finish():
    eng = build()
    eng.run()
    s = eng.summary()
    assert len(s) == 3
    for v in s.values():
        assert v["rounds"] > 0
        assert v["best_accuracy"] > 0.3


def test_rounds_interleave_async():
    """Jobs run in PARALLEL: round intervals of different jobs must overlap."""
    eng = build()
    eng.run()
    r0 = [r for r in eng.records if r.job == 0]
    r1 = [r for r in eng.records if r.job == 1]
    overlaps = any(a.t_start < b.t_end and b.t_start < a.t_end
                   for a in r0[:10] for b in r1[:10])
    assert overlaps


def test_failure_injection_drops_devices_but_completes():
    eng = build(failure_rate=0.2, failure_cooldown=100.0)
    eng.run()
    dropped = sum(len(r.dropped) for r in eng.records)
    assert dropped > 0, "20% failure rate must drop some devices"
    for v in eng.summary().values():
        assert v["rounds"] > 0  # training survived the failures


def test_straggler_over_provisioning_reduces_round_time():
    eng_base = build(seed=7)
    eng_over = build(seed=7, over_provision=1.4)
    eng_base.run()
    eng_over.run()
    t_base = np.mean([r.round_time for r in eng_base.records])
    t_over = np.mean([r.round_time for r in eng_over.records])
    # dropping the slowest 40% tail must cut the mean round time
    assert t_over < t_base


def test_release_horizon_respects_queueing():
    """With horizon > 0, scheduled-but-busy devices serve AFTER their release
    (their effective round time includes the wait), and the engine completes."""
    eng = build("greedy", release_horizon=0.5)
    eng.run()
    # a device's rounds never overlap in effective time
    per_dev = {}
    for r in eng.records:
        for k in r.device_ids:
            per_dev.setdefault(int(k), []).append((r.t_start, r.t_end))
    for v in eng.summary().values():
        assert v["rounds"] > 0


def test_counts_match_records():
    eng = build()
    eng.run()
    counts = np.zeros((3, 50))
    for r in eng.records:
        counts[r.job][r.device_ids] += 1
    np.testing.assert_array_equal(counts, eng.counts)


def test_over_provision_exceeding_pool_is_clamped():
    """n_sel * over_provision > K used to retry-loop forever; now clamps."""
    with pytest.warns(RuntimeWarning, match="clamped"):
        eng = build(n_jobs=1, over_provision=20.0)  # 5 * 20 = 100 > K=50
    assert int(round(eng.n_sel * eng.over_provision)) <= eng.pool.num_devices
    eng.run()
    assert eng.summary()["t"]["rounds"] > 0


def test_permanent_device_loss_does_not_livelock():
    """Failing most of the pool forever must clamp/abandon, not spin."""
    eng = build(n_jobs=1)
    eng.pool.fail(np.arange(48))  # 2 reachable devices < n_sel=5, forever
    with pytest.warns(RuntimeWarning):
        eng.run()  # terminates (clamped selection or abandoned job)
    s = eng.summary()["t"]
    assert s["rounds"] >= 0  # summary stays well-defined either way


def test_total_device_loss_abandons_job():
    eng = build(n_jobs=1)
    eng.pool.fail(np.arange(50))  # nothing can ever free again
    with pytest.warns(RuntimeWarning, match="abandoning"):
        eng.run()
    s = eng.summary()["t"]
    assert s["rounds"] == 0
    assert s["mean_round_time"] == 0.0
    assert s["makespan"] == 0.0
    assert s["final_accuracy"] == 0.0


def test_summary_reports_mean_round_time():
    eng = build()
    eng.run()
    for v in eng.summary().values():
        assert v["mean_round_time"] == pytest.approx(
            v["total_round_time"] / v["rounds"])


# ---- the over-selection cut at fleet scale ---------------------------------

def _fleet_engine(traced, K=20_000, n_sel=30, rounds=3):
    """One job over K devices, ``n_sel`` a round over-selected by 1.3, a
    random scheduler; returns the engine after ``rounds`` rounds, each
    round's scheduled plan and drawn device times, and the tracer's events
    (empty untraced)."""
    from repro.monitoring import trace as trace_mod

    pool = DevicePool.heterogeneous(K, 1, seed=4)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0], n_sel=n_sel)
    sched = get_scheduler("random", cost_model=cm, seed=0)
    rt = SyntheticRuntime(num_jobs=1, num_devices=K, seed=2)
    eng = MultiJobEngine(tiny_jobs(1, max_rounds=rounds), pool, cm, sched,
                         rt, n_sel=n_sel, over_provision=1.3)
    plans, drawn = [], []
    schedule, sample = sched.schedule, pool.sample_times_into

    def keep_plan(ctx):
        plans.append(schedule(ctx).copy())
        return plans[-1]

    def keep_times(job, tau, out):
        drawn.append(sample(job, tau, out).copy())
        return out

    sched.schedule, pool.sample_times_into = keep_plan, keep_times
    trace_mod.clear()
    if traced:
        trace_mod.enable()
    try:
        eng.run()
    finally:
        trace_mod.disable()
    events = trace_mod.get_tracer().events()
    trace_mod.clear()
    return eng, plans, drawn, events


def test_over_selection_keeps_the_fastest_against_numpy():
    """K=20,000, a target of 30 over-selected by 1.3: each round schedules
    39 devices, keeps the 30 whose drawn times are smallest (plain numpy
    below), drops the other 9, and lasts as long as the slowest kept one.
    Scheduled devices are free at launch, so the wait adds nothing."""
    eng, plans, drawn, _ = _fleet_engine(traced=False)
    assert len(eng.records) == len(plans) == len(drawn) == 3
    for rec, plan, t in zip(eng.records, plans, drawn):
        sched_ids = np.flatnonzero(plan)
        assert sched_ids.size == 39
        fastest = sched_ids[np.argsort(t[sched_ids])[:30]]
        np.testing.assert_array_equal(np.sort(rec.device_ids),
                                      np.sort(fastest))
        np.testing.assert_array_equal(np.sort(rec.dropped),
                                      np.setdiff1d(sched_ids, fastest))
        assert rec.round_time == t[fastest].max()
        assert rec.round_time < t[sched_ids].max()


def test_traced_dispatch_counts_the_cut_and_times_the_draw():
    """Traced, ``dispatch`` carries the cut's counts and holds the
    ``pool_sample`` span; the records equal the untraced run's bit for
    bit."""
    untraced, *_ = _fleet_engine(traced=False)
    traced, _, _, events = _fleet_engine(traced=True)
    dispatch = [e for e in events if e["name"] == "dispatch"]
    sample = [e for e in events if e["name"] == "pool_sample"]
    assert len(dispatch) == len(sample) == 3
    for d, s in zip(dispatch, sample):
        assert d["args"] == {"job": 0, "round": d["args"]["round"],
                             "scheduled": 39, "kept": 30, "dropped": 9}
        assert s["args"] == {"k": 20_000}
        assert d["ts"] <= s["ts"] and (s["ts"] + s["dur"]
                                       <= d["ts"] + d["dur"] + 1e-3)
    assert len(untraced.records) == len(traced.records)
    for a, b in zip(untraced.records, traced.records):
        for f in ("t_start", "t_end", "round_time", "cost", "fairness",
                  "loss", "accuracy"):
            assert getattr(a, f) == getattr(b, f), f
        np.testing.assert_array_equal(a.device_ids, b.device_ids)
        np.testing.assert_array_equal(a.dropped, b.dropped)

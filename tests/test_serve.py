"""Online scheduler-service tests: traffic generation, admission control,
incremental-vs-full rescoring parity, dynamic engine job sets, pool churn
invalidation, and scheduler warm hand-off across retire/readmit."""

import json

import numpy as np
import pytest

from repro.config.base import ArchFamily, JobConfig, ModelConfig
from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.multijob import MultiJobEngine
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulingContext
from repro.experiment.presets import get_preset
from repro.experiment.spec import ArrivalsSpec, ExperimentSpec
from repro.fl.runtime import SyntheticRuntime
from repro.serve import (SchedulerService, load_trace, poisson_trace,
                         save_trace, trace_from_spec)
from repro.serve.metrics import LatencyStats, jain_fairness


def small_spec(**kw):
    """A CI-sized online spec (fast scheduler, short horizon)."""
    kw = {"scheduler": "greedy", "num_devices": 30, "horizon": 6_000.0,
          "interarrival": 600.0, "max_concurrent": 2, **kw}
    return get_preset("online-smoke", **kw)


# ---- traffic -------------------------------------------------------------

def test_trace_deterministic_in_seed():
    arr = ArrivalsSpec(seed=7, horizon=10_000.0, interarrival=500.0,
                       mean_lifetime=2_000.0, readmit_prob=0.5,
                       churn_interarrival=3_000.0)
    t1 = poisson_trace(arr, num_templates=2, num_devices=40)
    t2 = poisson_trace(arr, num_templates=2, num_devices=40)
    assert [e.to_dict() for e in t1] == [e.to_dict() for e in t2]
    t3 = poisson_trace(ArrivalsSpec(**{**arr.__dict__, "seed": 8}), 2, 40)
    assert [e.to_dict() for e in t1] != [e.to_dict() for e in t3]


def test_trace_sorted_and_well_formed():
    arr = ArrivalsSpec(seed=0, horizon=20_000.0, interarrival=800.0,
                       mean_lifetime=2_500.0, readmit_prob=0.5,
                       churn_interarrival=4_000.0, churn_fraction=0.05)
    trace = poisson_trace(arr, num_templates=3, num_devices=60)
    assert trace, "horizon/interarrival must produce events"
    times = [e.t for e in trace]
    assert times == sorted(times)
    arrives = [e for e in trace if e.kind == "arrive"]
    assert all(e.tenant and e.template in (0, 1, 2) for e in arrives)
    # Every depart names a tenant that arrived earlier.
    seen = set()
    for e in trace:
        if e.kind == "arrive":
            seen.add(e.tenant)
        elif e.kind == "depart":
            assert e.tenant in seen
    # Churn comes in out/in pairs over the same device set.
    outs = [tuple(e.devices) for e in trace if e.kind == "churn_out"]
    ins = [tuple(e.devices) for e in trace if e.kind == "churn_in"]
    assert sorted(outs) == sorted(ins) and len(outs) > 0


def test_trace_json_roundtrip(tmp_path):
    arr = ArrivalsSpec(seed=1, horizon=8_000.0, interarrival=700.0,
                       mean_lifetime=2_000.0, churn_interarrival=3_000.0,
                       drift=1.5)
    trace = poisson_trace(arr, num_templates=2, num_devices=30)
    path = str(tmp_path / "trace.json")
    save_trace(trace, path)
    back = load_trace(path)
    assert [e.to_dict() for e in back] == [e.to_dict() for e in trace]
    # trace mode replays the file verbatim
    arr2 = ArrivalsSpec(mode="trace", trace_path=path)
    replay = trace_from_spec(arr2, 2, 30)
    assert [e.to_dict() for e in replay] == [e.to_dict() for e in trace]


# ---- spec axis -----------------------------------------------------------

def test_spec_arrivals_axis_roundtrip():
    spec = small_spec()
    assert spec.arrivals is not None
    d = spec.to_dict()
    back = ExperimentSpec.from_dict(d)
    assert back.arrivals == spec.arrivals
    # nested replace merges into the existing ArrivalsSpec
    spec2 = spec.replace(arrivals={"horizon": 123.0})
    assert spec2.arrivals.horizon == 123.0
    assert spec2.arrivals.interarrival == spec.arrivals.interarrival


# ---- metrics -------------------------------------------------------------

def test_latency_stats_and_jain():
    ls = LatencyStats()
    for v in [0.01, 0.02, 0.03, 0.04]:
        ls.add(v)
    assert ls.count == 4
    assert 0.01 <= ls.p50 <= 0.04 and ls.p99 <= 0.04 + 1e-9
    assert jain_fairness([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_fairness([1.0, 0.0, 0.0]) == pytest.approx(1.0 / 3.0)
    assert jain_fairness([]) == 1.0


# ---- the service end to end ---------------------------------------------

def test_service_end_to_end_sustains_traffic():
    spec = small_spec()
    svc = SchedulerService(spec)
    report = svc.run()
    assert report.arrivals > 0 and report.rounds_completed > 0
    assert svc.metrics.decisions == sum(
        t.admissions for t in svc.metrics.tenants.values())
    # every admitted tenant's rounds were attributed (even tenants whose
    # in-flight round finished after retirement)
    admitted = [t for t in svc.metrics.tenants.values() if t.admissions]
    assert sum(t.rounds for t in admitted) == report.rounds_completed
    # parked catalogue templates never execute and never appear in summary
    summ = svc.engine.summary()
    live = [js for js in svc.engine.jobs if not js.parked]
    assert len(summ) == len(live) and len(live) > 0
    assert not any(r.job < len(svc.templates) for r in svc.engine.records)
    d = report.to_dict()
    assert json.loads(report.to_json())["arrivals"] == d["arrivals"]


def test_service_respects_admission_budget():
    spec = small_spec(interarrival=300.0)  # oversubscribed on purpose
    svc = SchedulerService(spec)
    peak = {"live": 0}
    orig = svc._admit

    def counting_admit(tenant, template, now):
        orig(tenant, template, now)
        peak["live"] = max(peak["live"], len(svc._live))

    svc._admit = counting_admit
    report = svc.run()
    assert peak["live"] <= spec.arrivals.max_concurrent
    assert report.rejections > 0 and report.queue_depth_max > 0


def test_service_readmission_uses_saved_state():
    spec = small_spec(scheduler="bods", interarrival=500.0)
    svc = SchedulerService(spec)
    report = svc.run()
    assert report.readmissions > 0
    # a readmitted tenant got a FRESH job id; ids are never reused
    jobs = list(svc._job_tenant)
    assert len(jobs) == len(set(jobs))


@pytest.fixture(scope="module")
def rescore_runs():
    """One BODS service per rescoring mode, over the same traffic trace."""
    spec = small_spec(scheduler="bods", horizon=4_000.0)
    probe = SchedulerService(spec)
    trace = trace_from_spec(spec.arrivals, len(probe.templates),
                            probe.engine.pool.num_devices)
    runs = {}
    for mode in ("incremental", "full"):
        runs[mode] = SchedulerService(spec, rescore_mode=mode)
        runs[mode].run(trace)
    return runs


def test_incremental_and_full_rescoring_execute_identically(rescore_runs):
    records = {mode: [(r.job, r.round_idx, r.cost, tuple(r.device_ids))
                      for r in svc.engine.records]
               for mode, svc in rescore_runs.items()}
    assert records["incremental"] == records["full"]


def test_incremental_and_full_advisory_costs_agree(rescore_runs):
    """Incremental rescoring scores the live plan, full rescoring searches
    a fresh one: their mean advisory costs agree within 50%."""
    means = {}
    for mode, svc in rescore_runs.items():
        advisory = [c for c in svc.rescore_costs if c > 0]
        assert advisory, mode
        means[mode] = float(np.mean(advisory))
    rel = abs(means["incremental"] - means["full"]) / means["full"]
    assert rel <= 0.5, means


def test_service_requires_arrivals_axis():
    spec = small_spec().replace(arrivals=None)
    with pytest.raises(ValueError, match="arrivals"):
        SchedulerService(spec)
    with pytest.raises(ValueError, match="rescore_mode"):
        SchedulerService(small_spec(), rescore_mode="bogus")


# ---- engine dynamic job set ---------------------------------------------

def _tiny_engine(n_jobs=2, sched="greedy", max_rounds=8):
    mc = ModelConfig(name="t", family=ArchFamily.CNN, cnn_spec=(("flatten",),),
                     input_shape=(4, 4, 1), num_classes=10)
    jobs = [JobConfig(job_id=i, model=mc, target_metric=0.99,
                      max_rounds=max_rounds) for i in range(n_jobs)]
    pool = DevicePool.heterogeneous(30, n_jobs, seed=3)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0] * n_jobs, n_sel=4)
    s = get_scheduler(sched, cost_model=cm, seed=0)
    rt = SyntheticRuntime(num_jobs=n_jobs, num_devices=30, seed=2)
    return MultiJobEngine(jobs, pool, cm, s, rt, n_sel=4)


def test_engine_add_job_mid_run():
    eng = _tiny_engine()
    for j in range(2):
        eng._launch(j, 0.0)
    eng.advance_until(eng._heap[0][0])  # complete the first round
    assert eng.clock > 0.0
    mc = eng.jobs[0].config.model
    cfg = JobConfig(job_id=2, model=mc, target_metric=0.99, max_rounds=4)
    job = eng.add_job(cfg, now=eng.clock)
    assert job == 2
    assert eng.pool.num_jobs == 3 and eng.counts.shape[0] == 3
    eng.run()  # drains everything
    summ = {k: v for k, v in eng.summary().items()}
    s0, s2 = summ["t"], summ["t#2"]  # keyed by model name (+#job on clash)
    assert s2["rounds"] >= 1
    assert s2["admitted_at"] > 0.0
    # unequal lifetimes: late job still summarized correctly
    assert s0["rounds"] == 8 and s2["rounds"] <= 4


def test_engine_retire_job_mid_run():
    eng = _tiny_engine(max_rounds=50)
    for j in range(2):
        eng._launch(j, 0.0)
    eng.advance_until(eng.clock + 1.0)
    assert eng.retire_job(1, now=eng.clock)
    assert not eng.retire_job(1, now=eng.clock)  # already retired
    eng.run()
    summ = eng.summary()
    s0, s1 = summ["t"], summ["t#1"]
    assert s1["retired"] and not s0["retired"]
    # the retired job's in-flight round completed but no new one launched
    assert s1["rounds"] < s0["rounds"]
    assert all(r.job != 1 or r.t_start <= eng.jobs[1].retired_at
               for r in eng.records)


def test_engine_done_callback_fires():
    eng = _tiny_engine(max_rounds=3)
    done = []
    eng.on_job_done = lambda job, now: done.append(job)
    eng.run()
    assert sorted(done) == [0, 1]


# ---- pool churn + cache invalidation (the stale-cache regression) --------

def test_pool_set_capabilities_invalidates_time_cache():
    pool = DevicePool.heterogeneous(20, 2, seed=0)
    before = pool.expected_times(0, 5.0).copy()
    v0 = pool.version
    # RAW writes bypass invalidation — this is the documented hazard the
    # mutator API exists to close: the memo keeps serving stale times.
    pool.a = pool.a.copy()
    pool.a[:5] *= 10.0
    np.testing.assert_array_equal(pool.expected_times(0, 5.0), before)
    # The mutator refreshes the memo and bumps the version.
    pool.set_capabilities(np.arange(5), a=pool.a[:5])
    after = pool.expected_times(0, 5.0)
    assert pool.version > v0
    assert (after[:5] > before[:5]).all()
    np.testing.assert_allclose(after[5:], before[5:])


def test_pool_depart_rejoin_roundtrip():
    pool = DevicePool.heterogeneous(20, 2, seed=1)
    base = pool.expected_times(0, 5.0).copy()
    pool.depart([3, 7])
    # membership churn rides on occupancy: departed devices are busy forever
    assert np.isinf(pool.busy_until[[3, 7]]).all()
    pool.rejoin([3, 7])
    assert np.isfinite(pool.busy_until[[3, 7]]).all()
    np.testing.assert_allclose(pool.expected_times(0, 5.0), base)
    # drifted rejoin changes the rejoined device's time model only
    pool.depart([3])
    pool.rejoin([3], a=pool.a[[3]] * 2.0)
    t2 = pool.expected_times(0, 5.0)
    assert t2[3] > base[3]
    np.testing.assert_allclose(np.delete(t2, 3), np.delete(base, 3))


def test_pool_add_job_grows_data_columns():
    pool = DevicePool.heterogeneous(15, 2, seed=2)
    col = pool.data_sizes[:, 1].copy()
    j = pool.add_job(col * 2.0)
    assert j == 2 and pool.num_jobs == 3
    np.testing.assert_allclose(
        pool.expected_times(2, 5.0), 2.0 * pool.expected_times(1, 5.0))


# ---- scheduler warm hand-off (retire -> readmit under a new job id) ------

LEARNERS = {
    "bods": {"num_candidates": 64, "init_points": 4},
    "rlds": {"pretrain_rounds": 0},
    "dnn": {"num_candidates": 64},
}


@pytest.mark.parametrize("name", sorted(LEARNERS))
def test_warm_handoff_identical_next_decision(name):
    """Transplanting a retired job's per-job state under a NEW job id (the
    service's readmission path) must reproduce the exact next decision the
    uninterrupted scheduler would have made."""
    pool = DevicePool.heterogeneous(24, 2, seed=5)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0, 5.0], n_sel=4)
    sched = get_scheduler(name, cost_model=cm, seed=0, **LEARNERS[name])

    def ctx(job, r, counts):
        return SchedulingContext(
            job=job, round_idx=r, tau=5.0, n_sel=4,
            available=np.ones(24, dtype=bool), counts=counts.copy(),
            expected_times=pool.expected_times(job, 5.0))

    counts = np.zeros((2, 24))
    for r in range(3):  # give the learners per-job history
        for j in (0, 1):
            c = ctx(j, r, counts[j])
            plan = sched.schedule(c)
            sched.observe(c, plan, float(sched.last_estimated_cost or 1.0))
            counts[j] += plan

    snap = sched.snapshot()
    plan_uninterrupted = sched.schedule(ctx(1, 3, counts[1]))

    # Retire job 1, readmit as job 2: fresh pool column (same data), grown
    # scheduler state, per-job slice transplanted, rng pinned via restore.
    sched.restore(snap)
    saved = sched.job_state_dict(1)
    pool.add_job(pool.data_sizes[:, 1].copy())
    sched.ensure_jobs(3)
    sched.load_job_state(2, saved)
    plan_readmitted = sched.schedule(ctx(2, 3, counts[1]))

    np.testing.assert_array_equal(plan_uninterrupted, plan_readmitted)


def test_snapshot_restore_pins_rng():
    pool = DevicePool.heterogeneous(24, 2, seed=5)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0, 5.0], n_sel=4)
    sched = get_scheduler("random", cost_model=cm, seed=0)
    c = SchedulingContext(job=0, round_idx=0, tau=5.0, n_sel=4,
                          available=np.ones(24, dtype=bool),
                          counts=np.zeros(24),
                          expected_times=pool.expected_times(0, 5.0))
    snap = sched.snapshot()
    p1 = sched.schedule(c)
    p2 = sched.schedule(c)
    sched.restore(snap)
    np.testing.assert_array_equal(sched.schedule(c), p1)
    np.testing.assert_array_equal(sched.schedule(c), p2)


# ---- CLI -----------------------------------------------------------------

def test_cli_smoke(tmp_path, capsys):
    from repro.serve.__main__ import main

    out = tmp_path / "report.json"
    trace = tmp_path / "trace.json"
    main(["--preset", "online-smoke", "--arg", "horizon=3000",
          "--arg", "num_devices=30", "--arg", "scheduler=greedy",
          "--save-trace", str(trace), "--out", str(out)])
    rep = json.loads(out.read_text())
    assert rep["rounds_completed"] > 0
    assert trace.exists()
    assert "latency" in capsys.readouterr().out

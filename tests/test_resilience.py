"""SLO-driven serve resilience tests (``repro.serve.resilience`` + the
``slo`` spec axis): spec round-trip and inertness, the degradation
ladder's queue/latency rung selection and plan repair, circuit-breaker
lifecycle and persistence, the stalled-round watchdog, bounded
launch/aggregation retries, and kill -9 resume of the full resilience
state on an actively degrading service."""

import dataclasses
import json
import warnings

import numpy as np
import pytest

from repro.core.schedulers.base import SchedulingContext
from repro.experiment.presets import get_preset
from repro.experiment.slo import SLOSpec
from repro.experiment.spec import ExperimentSpec
from repro.serve.resilience import (RUNGS, BreakerBoard, CircuitBreaker,
                                    DecisionGovernor, RoundWatchdog)
from repro.serve.service import SchedulerService, SimulatedCrash


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class FakeCost:
    """cost_indices stand-in: a plan's cost is its summed expected time."""

    def cost_indices(self, times, counts, idx):
        return np.asarray(times)[np.asarray(idx)].sum(axis=1)


class FakeScheduler:
    """Full-search stand-in: picks the SLOWEST n_sel available devices (so
    greedy/repair rungs are distinguishable from it)."""

    last_estimated_cost = 7.5

    def schedule(self, ctx):
        avail = ctx.available_indices()
        order = np.argsort(ctx.expected_times[avail], kind="stable")
        plan = np.zeros(ctx.available.shape[0], dtype=bool)
        plan[avail[order[-ctx.n_sel:]]] = True
        return plan


class FakeClock:
    """perf_counter stand-in advancing a fixed amount per call."""

    def __init__(self, step_s: float):
        self.t = 0.0
        self.step_s = step_s

    def __call__(self):
        self.t += self.step_s
        return self.t


def make_ctx(job=0, n_sel=3, k=10, available=None, round_idx=0):
    avail = np.ones(k, dtype=bool) if available is None else available
    return SchedulingContext(
        job=job, round_idx=round_idx, tau=1.0, n_sel=n_sel,
        available=avail, counts=np.zeros(k),
        expected_times=np.arange(k, dtype=float) + 1.0)


def governor(clock=None, **slo_kwargs):
    slo = SLOSpec(**slo_kwargs)
    kw = {} if clock is None else {"clock": clock}
    return DecisionGovernor(slo, FakeCost(), **kw)


def small_quickstart(max_rounds=8):
    spec = get_preset("quickstart", n_jobs=2, num_devices=30,
                      max_rounds=max_rounds)
    return spec.replace(jobs=tuple(
        dataclasses.replace(j, target_metric=2.0) for j in spec.jobs))


def record_tuples(records):
    return [(r.job, r.round_idx, r.t_start, r.t_end, r.round_time, r.cost,
             r.fairness, r.loss, r.accuracy, tuple(r.device_ids),
             tuple(r.dropped), tuple(r.corrupt_ids), tuple(r.failed_ids),
             r.degraded, r.rung, r.decision_ms) for r in records]


# ---------------------------------------------------------------------------
# SLOSpec: validation, inertness, JSON round-trip
# ---------------------------------------------------------------------------

def test_slospec_default_is_inert():
    assert SLOSpec().inert
    assert not SLOSpec(max_queue_depth=4).inert
    assert not SLOSpec(decision_deadline_ms=5.0).inert
    assert not SLOSpec(watchdog_rounds=3).inert
    assert not SLOSpec(breaker_threshold=2).inert
    assert not SLOSpec(max_launch_retries=1).inert
    assert not SLOSpec(max_agg_retries=1).inert


@pytest.mark.parametrize("bad", [
    dict(shed_policy="nope"), dict(decision_deadline_ms=0.0),
    dict(deadline_safety=0.0), dict(deadline_safety=1.5),
    dict(latency_window=0), dict(rung_probe_every=0),
    dict(retry_backoff=0.5), dict(breaker_failure_frac=0.0),
    dict(watchdog_rounds=-1), dict(max_agg_retries=-1),
])
def test_slospec_validation(bad):
    with pytest.raises(ValueError):
        SLOSpec(**bad)


def test_slo_axis_json_round_trip():
    spec = small_quickstart().replace(slo={
        "decision_deadline_ms": 12.0, "max_queue_depth": 5,
        "breaker_threshold": 2, "max_launch_retries": 3})
    again = ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again == spec
    assert isinstance(again.slo, SLOSpec)
    assert again.effective_slo() == spec.slo
    # an inert axis is treated as absent
    assert small_quickstart().replace(slo={}).effective_slo() is None


def test_inert_slo_axis_is_bit_identical():
    base = small_quickstart(max_rounds=5)
    recs_off = base.build().run().records
    recs_inert = base.replace(slo={}).build().run().records
    assert record_tuples(recs_off) == record_tuples(recs_inert)


# ---------------------------------------------------------------------------
# governor: rung selection, repair, decide
# ---------------------------------------------------------------------------

def test_queue_rung_ladder():
    gov = governor(max_queue_depth=4)
    for depth, rung in [(0, 0), (2, 0), (3, 1), (4, 1), (5, 2), (50, 2)]:
        gov.queue_depth = depth
        assert gov._queue_rung() == rung, depth


def test_latency_rung_picks_first_fitting_and_probes():
    gov = governor(decision_deadline_ms=10.0, deadline_safety=1.0,
                   rung_probe_every=3)
    gov._lat["full"].append(20.0)   # full doesn't fit the 10ms budget
    assert gov._latency_rung() == 1
    assert gov._latency_rung() == 1
    assert gov._latency_rung() == 0  # every 3rd forced degrade probes up
    assert gov._latency_rung() == 1


def test_repair_drops_trims_and_fills():
    gov = governor(max_queue_depth=4)
    ctx = make_ctx(n_sel=3)
    ctx.available[1] = False
    # unavailable member 1 dropped; survivors kept; the fastest available
    # non-member (0) fills the one-device shortfall
    np.testing.assert_array_equal(
        gov._repair(np.array([1, 5, 7]), ctx), [0, 5, 7])
    # oversized cached plan trimmed to the fastest n_sel
    np.testing.assert_array_equal(
        gov._repair(np.array([2, 4, 6, 8, 9]), ctx), [2, 4, 6])


def test_decide_full_rung_matches_scheduler():
    gov = governor(max_queue_depth=4)
    plan, rung, ms, est = gov.decide(FakeScheduler(), make_ctx(), now=0.0)
    assert rung == "full" and ms is None and est == 7.5
    np.testing.assert_array_equal(np.flatnonzero(plan), [7, 8, 9])
    np.testing.assert_array_equal(gov._last_good[0], [7, 8, 9])


def test_decide_degraded_rungs_and_cache_fallthrough():
    gov = governor(max_queue_depth=4)
    ctx = make_ctx()
    # queue over depth => rung 2; no cache needed for greedy
    gov.queue_depth = 5
    plan, rung, _, est = gov.decide(FakeScheduler(), ctx, now=0.0)
    assert rung == "greedy"
    np.testing.assert_array_equal(np.flatnonzero(plan), [0, 1, 2])
    assert est == pytest.approx(1.0 + 2.0 + 3.0)
    # upper-half depth => rung 1, repair-vs-greedy scored through cost_indices
    gov.queue_depth = 3
    plan, rung, _, est = gov.decide(FakeScheduler(), ctx, now=1.0)
    assert rung == "incremental"
    np.testing.assert_array_equal(np.flatnonzero(plan), [0, 1, 2])
    assert gov.rung_counts["greedy"] == 1
    assert gov.rung_counts["incremental"] == 1


def test_decide_measures_latency_with_injected_clock():
    clock = FakeClock(step_s=0.05)   # every decide measures 50ms
    gov = governor(clock=clock, decision_deadline_ms=10.0,
                   deadline_safety=1.0, rung_probe_every=1000)
    sched = FakeScheduler()
    _, rung, ms, _ = gov.decide(sched, make_ctx(), now=0.0)
    assert rung == "full" and ms == pytest.approx(50.0)
    assert gov.deadline_misses == 1
    # full's window now says 50ms > 10ms budget: degrade; each degraded
    # rung's own measurement then fails too, walking down the ladder.
    for expect in ("incremental", "greedy", "last_good", "last_good"):
        _, rung, _, _ = gov.decide(sched, make_ctx(), now=0.0)
        assert rung == expect
    assert set(RUNGS) == set(gov.rung_counts)


def test_governor_state_round_trip():
    gov = governor(max_queue_depth=4, breaker_threshold=2)
    gov.queue_depth = 5
    gov.decide(FakeScheduler(), make_ctx(), now=0.0)
    gov.breakers.tenant("t-1").record(False, 0.0)
    state = json.loads(json.dumps(gov.state_dict()))  # must be pure JSON
    gov2 = governor(max_queue_depth=4, breaker_threshold=2)
    gov2.load_state_dict(state)
    assert gov2.state_dict() == gov.state_dict()
    np.testing.assert_array_equal(gov2._last_good[0], gov._last_good[0])


# ---------------------------------------------------------------------------
# circuit breakers
# ---------------------------------------------------------------------------

def test_breaker_lifecycle():
    br = CircuitBreaker(threshold=2, cooldown=10.0)
    assert br.record(False, 0.0) is None
    assert br.record(True, 1.0) is None      # success resets the streak
    assert br.record(False, 2.0) is None
    assert br.record(False, 3.0) == "open"   # 2 consecutive failures
    assert br.trips == 1
    assert not br.allow(4.0)                 # cooling down
    assert br.allow(13.5)                    # cooldown elapsed: half-open
    assert br.state == "half_open"
    assert not br.allow(13.6)                # only ONE probe outstanding
    assert br.record(True, 14.0) == "closed"
    # reopen path: a failed probe trips again
    br.record(False, 20.0)
    br.record(False, 21.0)
    assert br.state == "open"
    assert br.allow(31.5) and br.state == "half_open"
    assert br.record(False, 32.0) == "open"
    assert br.trips == 3


def test_breaker_probe_rearms_after_silent_cooldown():
    br = CircuitBreaker(threshold=1, cooldown=5.0)
    br.record(False, 0.0)
    assert br.allow(6.0)          # probe armed at t=6
    assert not br.allow(7.0)      # probe outcome still outstanding
    assert br.allow(11.5)         # no outcome ever arrived: re-arm


def test_breaker_board_state_round_trip():
    board = BreakerBoard(threshold=1, cooldown=5.0)
    board.tenant("t-a").record(False, 1.0)
    board.domain(3).record(False, 2.0)
    assert board.open_counts() == dict(tenants_open=1, domains_open=1,
                                       trips=2)
    board2 = BreakerBoard(threshold=1, cooldown=5.0)
    board2.load_state_dict(json.loads(json.dumps(board.state_dict())))
    assert board2.state_dict() == board.state_dict()
    assert not board2.domain(3).allow(3.0)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

class _FakeJob:
    launched, done, parked = True, False, False


class _FakeEngine:
    def __init__(self):
        self.jobs = [_FakeJob(), _FakeJob()]
        self._heap = []
        self._in_flight = {1: {}}


def test_watchdog_counts_consecutive_stalls():
    eng = _FakeEngine()
    dog = RoundWatchdog(threshold=2)
    assert dog.check(eng) == []      # job 0 wedged once: below threshold
    assert dog.check(eng) == [0]     # twice consecutively: reported
    eng._heap.append((1.0, 0, "retry", 0))
    assert dog.check(eng) == []      # a pending event clears the stall
    assert dog.check(eng) == []      # ...and the counter restarted from 0
    dog2 = RoundWatchdog(threshold=2)
    eng._heap.clear()
    dog2.check(eng)
    dog2.load_state_dict(json.loads(json.dumps(dog2.state_dict())))
    assert dog2.check(eng) == [0]


# ---------------------------------------------------------------------------
# bounded retries on the engine
# ---------------------------------------------------------------------------

def test_bounded_launch_retries_clamp_instead_of_waiting():
    # 2 jobs want 6 of 10 devices each: the second always finds a shortage.
    spec = get_preset("quickstart", n_jobs=2, num_devices=10, max_rounds=4,
                      target=2.0).replace(n_sel=6)
    legacy = spec.build().run().records
    assert all(len(r.device_ids) + len(r.dropped) == 6 for r in legacy)
    recs = spec.replace(
        slo={"max_launch_retries": 1,
             "retry_base_delay": 5.0}).build().run().records
    assert len(recs) == len(legacy)
    clamped = [r for r in recs if len(r.device_ids) + len(r.dropped) < 6]
    assert clamped, "retry budget never clamped a shortage round"


def test_bounded_agg_retries_record_degraded_round():
    spec = small_quickstart(max_rounds=3).replace(slo={"max_agg_retries": 1})
    ex = spec.build()
    runtime = ex.engine.runtime
    orig = runtime.run_round
    calls = {"n": 0}

    def flaky(job_id, device_ids, round_idx):
        calls["n"] += 1
        if job_id == 1 and round_idx == 1:
            raise RuntimeError("injected aggregation failure")
        return orig(job_id, device_ids, round_idx)

    runtime.run_round = flaky
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        records = ex.run().records
    bad = [r for r in records if r.job == 1 and r.round_idx == 1]
    assert len(bad) == 1 and bad[0].degraded
    prev = next(r for r in records if r.job == 1 and r.round_idx == 0)
    assert bad[0].loss == prev.loss and bad[0].accuracy == prev.accuracy
    # the failing round was retried max_agg_retries+1 times before degrading
    assert calls["n"] == len(records) + 1


def test_agg_retry_never_swallows_jax_runtime_errors():
    """A compile failure or device OOM surfaces as ``JaxRuntimeError``: it
    propagates on the first attempt even with a retry budget, instead of
    becoming a degraded round."""
    from jax.errors import JaxRuntimeError

    spec = small_quickstart(max_rounds=2).replace(slo={"max_agg_retries": 1})
    ex = spec.build()
    calls = {"n": 0}

    def oom(job_id, device_ids, round_idx):
        calls["n"] += 1
        raise JaxRuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    ex.engine.runtime.run_round = oom
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        ex.run()
    assert calls["n"] == 1


def test_agg_failure_without_retry_budget_still_raises():
    spec = small_quickstart(max_rounds=2)
    ex = spec.build()

    def broken(job_id, device_ids, round_idx):
        raise RuntimeError("boom")

    ex.engine.runtime.run_round = broken
    with pytest.raises(RuntimeError, match="boom"):
        ex.run()


# ---------------------------------------------------------------------------
# the full stack: overloaded service, kill -9, bit-identical resume
# ---------------------------------------------------------------------------

def _overload_spec():
    return get_preset("slo-overload", horizon=5_000.0, num_devices=30)


def _deterministic_summary(svc):
    s = dict(svc.resilience_summary())
    s.pop("rung_latency_ms", None)   # wall clock: not replayable
    return s


def test_degrading_service_survives_kill9_bit_identically(tmp_path):
    spec = _overload_spec()
    ref = SchedulerService(spec)
    ref.run()
    ref_records = record_tuples(ref.engine.records)
    ref_summary = _deterministic_summary(ref)
    # the run must actually exercise the resilience stack
    assert ref_summary["degraded_rounds"] > 0
    assert ref_summary["shed_arrivals"] > 0
    assert all(r[-2] in RUNGS for r in ref_records)

    ck = str(tmp_path / "ck")
    svc = SchedulerService(spec, checkpoint_dir=ck, checkpoint_every=2,
                           crash_after=5)
    with pytest.raises(SimulatedCrash):
        svc.run()
    resumed = SchedulerService.resume(ck)
    resumed.run()
    assert record_tuples(resumed.engine.records) == ref_records
    assert _deterministic_summary(resumed) == ref_summary


def test_service_report_carries_resilience_block():
    report = SchedulerService(_overload_spec()).run()
    res = report.resilience
    assert res is not None
    assert sum(res["rung_counts"].values()) == res["decisions"]
    assert res["degraded_decisions"] > 0
    d = report.to_dict() if hasattr(report, "to_dict") else \
        dataclasses.asdict(report)
    assert d["resilience"]["rung_counts"] == res["rung_counts"]

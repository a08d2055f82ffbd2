"""Crash-consistent service resume tests: a service killed mid-horizon
(``SimulatedCrash`` — raised past the checkpoint boundary, exactly like a
hard kill) and resumed from the newest committed checkpoint must replay
the REMAINING trace to a trajectory BIT-IDENTICAL to an uninterrupted
run — records, fairness counts, tenant metrics, rescore costs, and the
summary, across schedulers (including the stateful BODS ring)."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.checkpoint import committed_steps
from repro.experiment.presets import get_preset
from repro.experiment.spec import _record_to_dict
from repro.faults import FaultSpec
from repro.serve.metrics import ServiceMetrics
from repro.serve.service import SchedulerService, SimulatedCrash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def service_spec(scheduler="bods", with_faults=True, num_devices=40):
    spec = get_preset("online-smoke", scheduler=scheduler,
                      num_devices=num_devices, horizon=8_000.0,
                      interarrival=600.0)
    if with_faults:
        spec = spec.replace(faults=FaultSpec(
            seed=3, dropout_rate=0.1, crash_rate=0.002, straggler_rate=0.1,
            num_domains=4, domain_outage_rate=0.02, corrupt_rate=0.05))
    return spec


def record_tuples(service):
    return [(r.job, r.round_idx, r.t_start, r.t_end, r.round_time, r.cost,
             r.fairness, r.loss, r.accuracy, tuple(r.device_ids),
             tuple(r.dropped), tuple(r.corrupt_ids), tuple(r.failed_ids),
             r.degraded, r.rung, r.decision_ms)
            for r in service.engine.records]


def run_reference(spec):
    svc = SchedulerService(spec)
    report = svc.run()
    return svc, report


def crash_and_resume(spec, tmp_path, crash_after, checkpoint_every=2):
    ck = str(tmp_path / f"ck_{crash_after}")
    svc = SchedulerService(spec, checkpoint_dir=ck,
                           checkpoint_every=checkpoint_every,
                           crash_after=crash_after)
    with pytest.raises(SimulatedCrash):
        svc.run()
    resumed = SchedulerService.resume(ck)
    report = resumed.run()
    return resumed, report


@pytest.mark.parametrize("scheduler", ["bods", "random"])
def test_crash_resume_bit_identical(scheduler, tmp_path):
    spec = service_spec(scheduler)
    ref, ref_report = run_reference(spec)
    ref_records = record_tuples(ref)
    assert len(ref_records) > 0

    # kill at several event boundaries: aligned with a checkpoint, one past
    # it, and deep into the horizon
    for crash_after in (4, 5, 11):
        resumed, report = crash_and_resume(spec, tmp_path, crash_after)
        assert record_tuples(resumed) == ref_records, crash_after
        np.testing.assert_array_equal(resumed.engine.counts,
                                      ref.engine.counts)
        assert report.rounds_completed == ref_report.rounds_completed
        assert report.arrivals == ref_report.arrivals
        assert report.departures == ref_report.departures
        assert report.readmissions == ref_report.readmissions
        assert report.tenant_fairness == ref_report.tenant_fairness
        assert resumed.rescore_costs == ref.rescore_costs
        assert resumed.engine.summary() == ref.engine.summary()
        assert {t: dataclasses.asdict(s)
                for t, s in resumed.metrics.tenants.items()} \
            == {t: dataclasses.asdict(s)
                for t, s in ref.metrics.tenants.items()}


def test_resume_without_faults_axis(tmp_path):
    spec = service_spec("random", with_faults=False)
    ref, _ = run_reference(spec)
    resumed, _ = crash_and_resume(spec, tmp_path, 5)
    assert record_tuples(resumed) == record_tuples(ref)


def test_resume_restores_cursor_and_trace(tmp_path):
    spec = service_spec("random")
    ck = str(tmp_path / "ck")
    svc = SchedulerService(spec, checkpoint_dir=ck, checkpoint_every=3,
                           crash_after=7)
    with pytest.raises(SimulatedCrash):
        svc.run()
    # newest committed step is the latest checkpoint boundary <= crash point
    steps = committed_steps(ck)
    assert steps and steps[-1] == 6
    resumed = SchedulerService.resume(ck)
    assert resumed._next_event == 6
    assert resumed.trace is not None
    assert [e.to_dict() for e in resumed.trace] \
        == [e.to_dict() for e in svc.trace]
    # the resumed service keeps checkpointing from where it left off
    resumed.run()
    assert committed_steps(ck)[-1] > 6


def test_checkpoints_are_gcd_to_keep_limit(tmp_path):
    spec = service_spec("random")
    ck = str(tmp_path / "ck")
    svc = SchedulerService(spec, checkpoint_dir=ck, checkpoint_every=1)
    svc.run()
    steps = committed_steps(ck)
    assert len(steps) <= svc._ckpt_manager.keep
    assert steps[-1] == svc._next_event


def test_service_metrics_state_round_trip():
    m = ServiceMetrics()
    m.arrivals, m.departures, m.rejections = 5, 3, 1
    ts = m.tenant("tenant-a", template=1)
    ts.rounds, ts.total_cost, ts.best_accuracy = 2, 3.5, 0.8
    ts.admissions, ts.queued_at = 1, 10.0
    m.decision_latency.add(0.01)
    m.sample_queue_depth(4)
    m2 = ServiceMetrics()
    m2.load_state(m.to_state())
    assert m2.to_state() == m.to_state()
    assert m2.tenants["tenant-a"].rounds == 2
    assert m2.tenants["tenant-a"].best_accuracy == 0.8
    assert m2.tenants["tenant-a"].queued_at == 10.0
    assert m2.decision_latency.samples == [0.01]


def test_crash_before_first_checkpoint_restarts_clean(tmp_path):
    """A crash before any checkpoint commits leaves nothing to resume —
    resume() must fail loudly, not silently restart from scratch."""
    spec = service_spec("random")
    ck = str(tmp_path / "ck")
    svc = SchedulerService(spec, checkpoint_dir=ck, checkpoint_every=50,
                           crash_after=2)
    with pytest.raises(SimulatedCrash):
        svc.run()
    assert committed_steps(ck) == []
    with pytest.raises(FileNotFoundError):
        SchedulerService.resume(ck)


def _serve_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)
    return subprocess.run([sys.executable, "-m", "repro.serve", *args],
                          cwd=cwd, env=env, capture_output=True, text=True)


@pytest.mark.parametrize("overload", [False, True],
                         ids=["faults", "slo-overload"])
def test_cli_kill9_and_resume_bit_identical(overload, tmp_path):
    """``python -m repro.serve --crash-after`` dies by ``os._exit(137)``;
    ``--resume`` then finishes the run with records identical to an
    uninterrupted one, with faults injected and every metric finite."""
    spec = (get_preset("slo-overload", horizon=5_000.0, num_devices=30)
            if overload else service_spec("bods"))
    ref, _ = run_reference(spec)
    ref_records = json.loads(json.dumps(
        [_record_to_dict(r) for r in ref.engine.records]))
    assert sum(len(r["dropped"]) for r in ref_records) > 0
    assert sum(len(r["corrupt_ids"]) for r in ref_records) > 0
    assert all(np.isfinite([r["accuracy"], r["loss"], r["round_time"]]).all()
               for r in ref_records)
    if overload:
        assert any(r["rung"] != "full" for r in ref_records)

    spec_path, ck = tmp_path / "spec.json", str(tmp_path / "ck")
    spec.save(str(spec_path))
    crash = _serve_cli(["--spec", str(spec_path), "--checkpoint-dir", ck,
                        "--checkpoint-every", "3", "--crash-after", "7"],
                       tmp_path)
    assert crash.returncode == 137, crash.stderr[-2000:]
    out = tmp_path / "records.json"
    resume = _serve_cli(["--resume", ck, "--records-out", str(out)],
                        tmp_path)
    assert resume.returncode == 0, resume.stderr[-2000:]
    assert json.loads(out.read_text()) == ref_records

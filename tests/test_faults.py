"""Fault-resilience tests: the ``FaultSpec`` axis, the replayable keyed
``FaultEngine`` schedule, engine-level fault semantics (quarantine
escalation, crashes, domain outages, deadline rounds, degraded fallbacks,
corruption screening), and the fused runtime's in-jit robust aggregation
parity with the host reference."""

import dataclasses

import numpy as np
import pytest

from repro.experiment import ExperimentSpec, JobSpec, PoolSpec
from repro.faults import FaultEngine, FaultSpec

K = 30


def fault_spec(**kw) -> FaultSpec:
    return FaultSpec(seed=5, **kw)


def run_spec(faults, scheduler="random", max_rounds=12, n_sel=4,
             num_devices=K, **overrides):
    spec = ExperimentSpec(
        jobs=tuple(JobSpec(name=f"j{i}", target_metric=0.99,
                           max_rounds=max_rounds) for i in range(2)),
        pool=PoolSpec(num_devices=num_devices, seed=3),
        scheduler=scheduler, runtime="synthetic",
        runtime_kwargs={"seed": 2}, n_sel=n_sel, faults=faults)
    spec = spec.replace(**overrides) if overrides else spec
    return spec.run()


# ---- FaultSpec (the axis) ------------------------------------------------

def test_fault_spec_round_trip_and_validation():
    fs = fault_spec(dropout_rate=0.2, crash_rate=0.01, straggler_rate=0.1,
                    num_domains=4, domain_outage_rate=0.05,
                    corrupt_rate=0.03, corrupt_mode="scale",
                    round_deadline=40.0)
    assert FaultSpec.from_dict(fs.to_dict()) == fs
    assert not fs.inert and FaultSpec().inert
    # domains without an outage rate inject nothing
    assert FaultSpec(num_domains=8).inert
    with pytest.raises(ValueError):
        FaultSpec(corrupt_mode="zeros")
    with pytest.raises(ValueError):
        FaultSpec(dropout_rate=1.5)
    with pytest.raises(ValueError):
        FaultSpec(backoff=0.5)


def test_experiment_axis_and_legacy_alias():
    fs = fault_spec(dropout_rate=0.2)
    spec = ExperimentSpec(jobs=(JobSpec(name="j"),), faults=fs,
                          failure_rate=0.9)
    # the axis wins over the deprecated alias when both are set
    assert spec.effective_faults() is fs
    restored = ExperimentSpec.from_json(spec.to_json())
    assert restored == spec and restored.faults == fs
    # alias alone maps onto fixed-cooldown uniform dropouts
    legacy = ExperimentSpec(jobs=(JobSpec(name="j"),), failure_rate=0.3,
                            failure_cooldown=90.0)
    eff = legacy.effective_faults()
    assert eff.dropout_rate == 0.3 and eff.cooldown == 90.0
    assert eff.backoff == 1.0 and eff.max_cooldown == 90.0
    assert ExperimentSpec(jobs=(JobSpec(name="j"),)).effective_faults() is None


# ---- FaultEngine (the replayable schedule) -------------------------------

def test_keyed_draws_are_replayable_and_order_independent():
    fs = fault_spec(dropout_rate=0.3, crash_rate=0.05, straggler_rate=0.2,
                    num_domains=4, domain_outage_rate=0.1, corrupt_rate=0.2)
    a, b = FaultEngine(fs, K), FaultEngine(fs, K)
    # query in different (job, round) orders: same schedule either way
    for job, r in [(0, 0), (1, 3), (0, 2)]:
        for x, y in zip(a.failure_masks(job, r),
                        reversed_list := list(b.failure_masks(job, r))):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(a.straggler_multipliers(job, r),
                                      b.straggler_multipliers(job, r))
    # corrupt masks agree across subsets (keyed over the full device axis)
    ids = np.array([3, 7, 11, 19])
    full = a.corrupt_mask(0, 5, np.arange(K))
    np.testing.assert_array_equal(b.corrupt_mask(0, 5, ids), full[ids])
    # distinct rounds draw distinct faults (not a constant schedule)
    assert any(not np.array_equal(a.failure_masks(0, r)[0],
                                  a.failure_masks(0, r + 1)[0])
               for r in range(5))


def test_domain_outages_are_correlated_and_win_over_transient():
    fs = fault_spec(dropout_rate=0.5, num_domains=3, domain_outage_rate=0.5)
    fe = FaultEngine(fs, K)
    hit_any = False
    for r in range(10):
        transient, _, domain_out = fe.failure_masks(0, r)
        # outage semantics win: no device is both transient and domain-out
        assert not (transient & domain_out).any()
        for d in range(3):
            members = fe.domain == d
            out = domain_out[members]
            assert out.all() or not out.any()   # whole domain or nothing
            hit_any = hit_any or out.any()
    assert hit_any


def test_escalating_quarantine_and_reset():
    fs = fault_spec(dropout_rate=0.5, cooldown=10.0, backoff=2.0,
                    max_cooldown=35.0)
    fe = FaultEngine(fs, K)
    dev = np.array([4])
    assert fe.quarantine_durations(dev) == [10.0]
    assert fe.quarantine_durations(dev) == [20.0]
    assert fe.quarantine_durations(dev) == [35.0]   # capped, not 40
    fe.record_success(dev)                           # readmission resets
    assert fe.quarantine_durations(dev) == [10.0]
    # state round-trips for checkpointing
    fe2 = FaultEngine(fs, K)
    fe2.load_state_dict(fe.state_dict())
    np.testing.assert_array_equal(fe2.strikes, fe.strikes)


def test_straggler_multipliers_scale_compute():
    fe = FaultEngine(fault_spec(straggler_rate=0.5, straggler_slowdown=4.0),
                     K)
    mult = fe.straggler_multipliers(0, 0)
    assert set(np.unique(mult)) <= {1.0, 4.0}
    assert (mult == 4.0).any() and (mult == 1.0).any()
    assert FaultEngine(fault_spec(), K).straggler_multipliers(0, 0) is None


# ---- engine semantics ----------------------------------------------------

def test_crashes_permanently_remove_devices():
    res = run_spec(fault_spec(crash_rate=0.05), max_rounds=15)
    pool = res.spec.build().engine.pool
    eng = res.spec.build().engine
    eng.run()
    assert np.isinf(eng.pool.busy_until).sum() > 0   # someone crashed for good
    # the run still completes with finite metrics
    assert all(np.isfinite(r.accuracy) for r in eng.records)


def test_all_failed_keeps_fastest_and_marks_degraded():
    res = run_spec(fault_spec(dropout_rate=1.0, cooldown=1.0), max_rounds=6)
    assert len(res.records) > 0
    for r in res.records:
        assert r.degraded
        assert len(r.device_ids) == 1                # the fastest reporter
    assert all(v["degraded_rounds"] > 0 for v in res.summary.values())


def test_round_deadline_partial_aggregation():
    slow = run_spec(fault_spec(round_deadline=1e9), max_rounds=8)
    tight_deadline = float(np.median([r.round_time for r in slow.records]))
    tight = run_spec(fault_spec(round_deadline=tight_deadline), max_rounds=8)
    assert all(r.round_time <= tight_deadline + 1e-9 for r in tight.records)
    # the cut stragglers show up as drops, not failures
    assert sum(len(r.dropped) for r in tight.records) > 0
    assert sum(len(r.dropped) for r in slow.records) == 0


def test_corruption_oracle_discard_excludes_fairness_counts():
    res = run_spec(fault_spec(corrupt_rate=0.4), max_rounds=10)
    eng = res.spec.build().engine
    eng.run()
    n_corrupt = sum(len(r.corrupt_ids) for r in eng.records)
    assert n_corrupt > 0
    assert all(v["corrupt_updates"] > 0 for v in eng.summary().values())
    # fairness counts only credit clean survivors: the synthetic runtime
    # does not screen, so record.device_ids excludes corrupt devices
    for r in eng.records:
        assert not np.intersect1d(r.device_ids, r.corrupt_ids).size
    total_counted = sum(len(r.device_ids) for r in eng.records)
    assert float(eng.counts.sum()) == float(total_counted)


def test_degraded_runs_stay_reproducible():
    fs = fault_spec(dropout_rate=0.3, crash_rate=0.01, straggler_rate=0.2,
                    num_domains=4, domain_outage_rate=0.1, corrupt_rate=0.1)
    r1, r2 = run_spec(fs), run_spec(fs)
    assert r1.summary == r2.summary
    for a, b in zip(r1.records, r2.records):
        np.testing.assert_array_equal(a.device_ids, b.device_ids)
        np.testing.assert_array_equal(a.dropped, b.dropped)
        np.testing.assert_array_equal(a.corrupt_ids, b.corrupt_ids)


def test_fault_injection_preset_injects_faults_and_stays_finite():
    from repro.experiment.presets import get_preset

    spec = get_preset("fault-injection", scheduler="random", num_devices=60)
    res = spec.replace(jobs=tuple(dataclasses.replace(j, max_rounds=8)
                                  for j in spec.jobs)).run()
    assert sum(len(r.dropped) for r in res.records) > 0
    assert sum(len(r.corrupt_ids) for r in res.records) > 0
    assert all(np.isfinite([r.accuracy, r.loss, r.round_time]).all()
               for r in res.records)


# ---- robust aggregation (fused runtime) ----------------------------------

def _tiny_fl_setup(num_jobs=1, num_dev=12, seed=0):
    from repro.config.base import JobConfig
    from repro.configs.paper_models import lenet5
    from repro.data.synthetic import make_classification_dataset
    from repro.fl.partition import noniid_partition

    cfg = dataclasses.replace(
        lenet5(), name="tiny", input_shape=(8, 8, 1),
        cnn_spec=(("convp", 4, 3), ("flatten",), ("fc", 16)))
    jobs, datasets = [], []
    for j in range(num_jobs):
        x, y = make_classification_dataset(600, cfg.input_shape,
                                           cfg.num_classes, noise=1.0,
                                           seed=seed + j)
        ex, ey = make_classification_dataset(60, cfg.input_shape,
                                             cfg.num_classes, noise=1.0,
                                             seed=seed + 50 + j)
        part = noniid_partition(y, num_dev, seed=seed + j)
        jobs.append(JobConfig(job_id=j, model=cfg, target_metric=2.0,
                              local_epochs=1, batch_size=4, lr=0.05))
        datasets.append((x, y, part, ex, ey))
    return jobs, datasets


def test_rejection_mask_matches_host_reference():
    import jax.numpy as jnp

    from repro.fl.aggregation import rejection_mask, rejection_mask_host

    rng = np.random.default_rng(0)
    for trial in range(10):
        n, d = int(rng.integers(3, 12)), int(rng.integers(2, 20))
        g = {"w": rng.normal(size=(d,)).astype(np.float32)}
        s = {"w": (g["w"][None]
                   + 0.1 * rng.normal(size=(n, d)).astype(np.float32))}
        w = rng.uniform(0.0, 2.0, size=n).astype(np.float32)
        for i in range(n):
            u = rng.random()
            if u < 0.2:
                s["w"][i] = np.inf
            elif u < 0.4:
                s["w"][i] *= 50.0
        host = rejection_mask_host(g, s, w, 4.0)
        fused = np.asarray(
            rejection_mask(g, s, jnp.asarray(w), jnp.float32(4.0)))
        np.testing.assert_array_equal(host, fused, err_msg=f"trial {trial}")


def test_rejection_mask_keeps_single_survivor():
    """Median-of-one degenerate: with ONE valid lane, its norm IS the
    median, so any mult < 1 would reject the only update available — the
    rule must keep it unconditionally (jit and host must agree)."""
    import jax.numpy as jnp

    from repro.fl.aggregation import rejection_mask, rejection_mask_host

    g = {"w": np.zeros((4,), np.float32)}
    s = {"w": np.stack([np.full((4,), 2.0, np.float32),     # nonzero norm
                        np.full((4,), np.inf, np.float32),  # non-finite
                        np.full((4,), 9.0, np.float32)])}   # zero weight
    for w in ([1.0, 1.0, 0.0],     # lane 1 killed by the finite guard
              [1.0, 0.0, 0.0]):    # lanes 1-2 not participating
        w = np.asarray(w, np.float32)
        host = rejection_mask_host(g, s, w, 0.5)
        fused = np.asarray(rejection_mask(g, s, jnp.asarray(w),
                                          jnp.float32(0.5)))
        np.testing.assert_array_equal(host, [True, False, False], err_msg=str(w))
        np.testing.assert_array_equal(fused, host, err_msg=str(w))


def test_robust_fedavg_guards():
    import jax.numpy as jnp

    from repro.fl.aggregation import robust_fedavg

    g = {"w": jnp.ones((4,), jnp.float32)}
    clean = jnp.stack([jnp.full((4,), v) for v in (1.1, 0.9, 1.05)])
    # a NaN lane must not poison the average (zeroed before FedAvg)
    s = {"w": clean.at[1].set(jnp.nan)}
    new, ok = robust_fedavg(g, s, jnp.ones(3), jnp.float32(4.0))
    assert np.asarray(ok).tolist() == [True, False, True]
    assert np.isfinite(np.asarray(new["w"])).all()
    np.testing.assert_allclose(np.asarray(new["w"]), 1.075, rtol=1e-6)
    # all lanes rejected -> keep the previous global params, not zeros
    s_bad = {"w": jnp.full((3, 4), jnp.nan)}
    new2, ok2 = robust_fedavg(g, s_bad, jnp.ones(3), jnp.float32(4.0))
    assert not np.asarray(ok2).any()
    np.testing.assert_array_equal(np.asarray(new2["w"]),
                                  np.asarray(g["w"]))


def test_fused_robust_screens_injected_corruption():
    from repro.fl.runtime import FusedMultiRuntime

    fs = fault_spec(corrupt_rate=0.4)
    jobs, datasets = _tiny_fl_setup()
    fe = FaultEngine(fs, 12)
    robust = FusedMultiRuntime(jobs, datasets, seed=0, robust=True,
                               fault_engine=fe)
    assert robust.handles_corruption
    jobs2, datasets2 = _tiny_fl_setup()
    plain = FusedMultiRuntime(jobs2, datasets2, seed=0)
    assert not plain.handles_corruption

    rng = np.random.default_rng(1)
    total_rej = 0
    for r in range(6):
        ids = rng.choice(12, 6, replace=False)
        m = robust.run_round(0, ids, r)
        # the runtime recomputes the engine's exact corrupt mask
        expected = int(fe.corrupt_mask(0, r, ids).sum())
        assert int(m["rejected"]) == expected, (r, m["rejected"], expected)
        total_rej += expected
        assert np.isfinite(m["loss"]) and np.isfinite(m["accuracy"])
    assert total_rej > 0 and robust.rejected_total == total_rej


def test_fused_robust_without_corruption_matches_plain_bitwise():
    from repro.fl.runtime import FusedMultiRuntime

    jobs, datasets = _tiny_fl_setup()
    plain = FusedMultiRuntime(jobs, datasets, seed=0)
    jobs2, datasets2 = _tiny_fl_setup()
    robust = FusedMultiRuntime(jobs2, datasets2, seed=0, robust=True)
    rng = np.random.default_rng(2)
    for r in range(4):
        ids = rng.choice(12, 5, replace=False)
        mp = plain.run_round(0, ids, r)
        mr = robust.run_round(0, ids, r)
        assert mp["loss"] == mr["loss"] and mp["accuracy"] == mr["accuracy"]
        assert mr["rejected"] == 0.0


def test_fused_robust_compile_stability():
    from repro.fl.runtime import FusedMultiRuntime, _fused_group_round

    jobs, datasets = _tiny_fl_setup(seed=9)
    fe = FaultEngine(fault_spec(corrupt_rate=0.3), 12)
    fused = FusedMultiRuntime(jobs, datasets, seed=0, buckets=(4, 8, 12),
                              robust=True, fault_engine=fe)
    before = _fused_group_round._cache_size()
    rng = np.random.default_rng(3)
    for r in range(12):
        n = int(rng.integers(1, 13))
        fused.run_round(0, rng.choice(12, n, replace=False), r)
    compiles = _fused_group_round._cache_size() - before
    assert compiles <= len(fused.buckets), (compiles, fused.buckets)

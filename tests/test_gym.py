"""Scheduler-gym tests: engine parity, rollout invariants, trainer smoke,
policy-zoo bit-exact round-trips, and the ExperimentSpec ``policy`` axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.multijob import MultiJobEngine
from repro.core.plans import random_plans
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulerBase
from repro.experiment.spec import ExperimentSpec, JobSpec, PoolSpec
from repro.gym import (CURRICULA, EnvConfig, PolicyZoo, TrainConfig,
                       batch_reset, batch_rollout, default_stages, evaluate,
                       reset, save_rlds_params, state_from_pool, step,
                       train_rlds)
from repro.gym.env import _apply_round


def small_cfg(**kw):
    return EnvConfig(**{"num_devices": 24, "num_jobs": 2, "n_sel": 3, **kw})


def make_ctx(pool, job=0, n_sel=3, counts=None, round_idx=0):
    from repro.core.schedulers.base import SchedulingContext

    K = pool.num_devices
    return SchedulingContext(
        job=job, round_idx=round_idx, tau=5.0, n_sel=n_sel,
        available=np.ones(K, dtype=bool),
        counts=counts if counts is not None else np.zeros(K),
        expected_times=pool.expected_times(job, 5.0))


# ---- environment basics --------------------------------------------------

def test_reset_shapes_and_calibration():
    cfg = small_cfg()
    state = reset(cfg, CURRICULA["default"], jax.random.PRNGKey(0))
    assert state.scen.a.shape == (24,) and state.scen.data.shape == (24, 2)
    assert state.scen.shift.shape == (2, 24)   # SoA fast path materialized
    assert state.counts.shape == (2, 24)
    assert float(state.scen.time_scale) > 0
    assert float(state.scen.fairness_scale) > 0
    assert int(state.job) == 0 and int(state.t) == 0
    # derived arrays agree with the raw coefficients
    np.testing.assert_allclose(
        np.asarray(state.scen.exp_base),
        np.asarray(state.scen.taus)[:, None] * np.asarray(state.scen.data).T
        * (np.asarray(state.scen.a) + 1.0 / np.asarray(state.scen.mu)),
        rtol=1e-5)


def test_batch_reset_scenarios_differ():
    cfg = small_cfg()
    states = batch_reset(cfg, CURRICULA["full"], jax.random.PRNGKey(1), 4)
    a = np.asarray(states.scen.a)
    assert a.shape == (4, 24)
    assert not np.allclose(a[0], a[1])  # independent scenario draws
    taus = np.asarray(states.scen.taus)
    assert taus.min() >= 1 and taus.max() <= 10


def test_step_updates_dynamics():
    cfg = small_cfg()
    state = reset(cfg, CURRICULA["default"], jax.random.PRNGKey(2))
    plan = jnp.zeros(24, bool).at[jnp.arange(3)].set(True)
    state2, out = step(cfg, state, plan)
    assert int(state2.job) == 1 and int(state2.t) == 1
    assert int(state2.round_idx[0]) == 1 and int(state2.round_idx[1]) == 0
    assert float(out.round_time) > 0 and np.isfinite(float(out.cost))
    # scheduled devices are busy until their own finish instants
    assert (np.asarray(state2.busy_until)[:3] > 0).all()
    assert np.allclose(np.asarray(state2.counts[0])[:3], 1.0)


def test_rollout_plans_valid_and_vmapped():
    """Every sampled plan: exactly n_sel devices, all available."""
    cfg = small_cfg()
    from repro.core.schedulers.rlds import init_policy

    params = init_policy(jax.random.PRNGKey(0))
    states = batch_reset(cfg, CURRICULA["flaky"], jax.random.PRNGKey(3), 3)
    _, tr = batch_rollout(cfg, params, states, 12)
    assert tr.plan.shape == (3, 12, 24)
    assert bool((tr.plan.sum(-1) == cfg.n_sel).all())
    assert not bool((tr.plan & ~tr.available).any())
    assert bool(jnp.isfinite(tr.cost).all())


# ---- engine parity (satellite: 1e-5 agreement on a fixed seed) -----------

class _Scripted(SchedulerBase):
    name = "scripted"

    def __init__(self, cost_model, plans):
        super().__init__(cost_model)
        self.plans = plans

    def schedule(self, ctx):
        return self.plans[ctx.round_idx]


class _StubRuntime:
    def run_round(self, job, device_ids, round_idx):
        return {"loss": 1.0, "accuracy": 0.0}


def test_gym_step_matches_engine_cost_model():
    """Gym round-time/fairness/cost == MultiJobEngine + CostModel to 1e-5
    when both consume the identical Formula-4 draws."""
    R, K, NSEL, TAU = 8, 40, 5, 3.0
    pool = DevicePool.heterogeneous(K, 1, seed=7)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([TAU], n_sel=NSEL)
    plans = random_plans(np.random.default_rng(3), np.ones(K, bool), NSEL, R)
    job = JobSpec(name="j", max_rounds=R, local_epochs=int(TAU)).to_job_config(0)
    engine = MultiJobEngine([job], pool, cm, _Scripted(cm, plans),
                            _StubRuntime(), n_sel=NSEL)
    engine.run()
    assert len(engine.records) == R

    # An identical pool replays the engine's exact exponential draws (the
    # engine consumed pool.rng once per round, K draws each).
    pool2 = DevicePool.heterogeneous(K, 1, seed=7)
    cfg = EnvConfig(num_devices=K, num_jobs=1, n_sel=NSEL,
                    alpha=4.0, beta=0.25)
    state = state_from_pool(pool2, cm, taus=[TAU])
    no_fail = jnp.ones(K)
    for r, rec in enumerate(engine.records):
        noise = pool2.rng.standard_exponential(K)
        state, out = _apply_round(cfg, state, jnp.asarray(plans[r]),
                                  jnp.asarray(noise, jnp.float32), no_fail)
        assert float(out.round_time) == pytest.approx(rec.round_time, rel=1e-5)
        assert float(out.fairness) == pytest.approx(rec.fairness,
                                                    rel=1e-5, abs=1e-6)
        assert float(out.cost) == pytest.approx(rec.cost, rel=1e-5, abs=1e-6)
    np.testing.assert_allclose(np.asarray(state.counts[0]), engine.counts[0])


def test_gym_cost_honors_absolute_fairness():
    """delta_fairness=False specs: the gym cost uses the absolute Formula-5
    variance, matching CostModel.cost (engine realized-cost form)."""
    K, NSEL = 30, 4
    pool = DevicePool.heterogeneous(K, 1, seed=5)
    cm = CostModel(pool, alpha=4.0, beta=0.25, delta_fairness=False)
    cm.calibrate([2.0], n_sel=NSEL)
    from repro.gym.env import config_from_cost_model

    cfg = config_from_cost_model(cm, n_sel=NSEL)
    assert cfg.delta_fairness is False
    state = state_from_pool(pool, cm, taus=[2.0])
    # seed some counts so absolute and delta fairness genuinely differ
    counts = np.zeros((1, K), np.float32)
    counts[0, :5] = 3.0
    state = state._replace(counts=jnp.asarray(counts))
    plan = np.zeros(K, bool)
    plan[10:10 + NSEL] = True
    noise = np.random.default_rng(0).standard_exponential(K)
    _, out = _apply_round(cfg, state, jnp.asarray(plan),
                          jnp.asarray(noise, jnp.float32), jnp.ones(K))
    times = 2.0 * pool.data_sizes[:, 0] * pool.a + noise * (
        2.0 * pool.data_sizes[:, 0] / pool.mu)
    expect = cm.cost(times, counts[0], plan)
    assert float(out.cost) == pytest.approx(expect, rel=1e-5, abs=1e-6)


# ---- trainer -------------------------------------------------------------

def test_train_rlds_runs_and_changes_params():
    stages = default_stages("default", num_devices=(24,), num_jobs=2)
    tcfg = TrainConfig(num_envs=4, rollout_len=6, iters=3, minibatches=2)
    params, logs = train_rlds(stages, tcfg, seed=0)
    assert len(logs) == 3
    assert all(np.isfinite(l["mean_cost"]) for l in logs)
    from repro.core.schedulers.rlds import init_policy

    fresh = jax.tree_util.tree_map(np.asarray,
                                   init_policy(jax.random.PRNGKey(1)))
    moved = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(np.asarray(a), b), params, fresh)
    assert any(jax.tree_util.tree_leaves(moved))
    ev = evaluate(stages[0][0], stages[0][1], params, seed=1,
                  episodes=4, steps=8)
    assert np.isfinite(ev["mean_cost"])


def test_trained_policy_no_worse_than_untrained():
    """A gym-trained RLDS policy must not score a higher mean cost than
    the untrained (random-init, no-pretrain) policy it replaces, on paired
    held-out scenarios (same eval seed, greedy top-k)."""
    from repro.core.schedulers.rlds import init_policy

    stages = default_stages("default", num_devices=(24,), num_jobs=2)
    params, _ = train_rlds(
        stages, TrainConfig(num_envs=8, rollout_len=8, iters=20), seed=0)
    cfg, scen = stages[0]
    untrained = init_policy(jax.random.PRNGKey(99))
    ev_t = evaluate(cfg, scen, params, seed=7, episodes=16, steps=32)
    ev_u = evaluate(cfg, scen, untrained, seed=7, episodes=16, steps=32)
    assert ev_t["mean_cost"] <= ev_u["mean_cost"], (ev_t, ev_u)


# ---- policy zoo ----------------------------------------------------------

def _pool_cm(K=24, M=2, n_sel=3, seed=0):
    pool = DevicePool.heterogeneous(K, M, seed=seed)
    cm = CostModel(pool)
    cm.calibrate([5.0] * M, n_sel=n_sel)
    return pool, cm


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("name", ["rlds", "dnn", "bods"])
def test_zoo_bit_exact_roundtrip(name, tmp_path):
    """state_dict -> zoo save -> load into a FRESH scheduler restores every
    array bit-for-bit (RLDS params/opt, DNN ring, BODS observation ring)."""
    pool, cm = _pool_cm()
    kwargs = {"pretrain_rounds": 2} if name == "rlds" else {}
    sched = get_scheduler(name, cost_model=cm, seed=3, **kwargs)
    # Push some real state through the scheduler before snapshotting.
    rng = np.random.default_rng(0)
    counts = np.zeros(24)
    for r in range(3):
        ctx = make_ctx(pool, n_sel=3, counts=counts, round_idx=r)
        plan = sched.schedule(ctx)
        sched.observe(ctx, plan, float(rng.random()))
        counts += plan

    zoo = PolicyZoo(str(tmp_path))
    zoo.save_scheduler("p", sched, meta={"note": "test"})
    fresh = get_scheduler(name, cost_model=cm, seed=99,
                          **({"pretrain_rounds": 0} if name == "rlds" else {}))
    meta = zoo.load_into("p", fresh)
    assert meta == {"note": "test"}
    _assert_trees_equal(sched.state_dict(), fresh.state_dict())


def test_zoo_kind_mismatch_and_unknown(tmp_path):
    pool, cm = _pool_cm()
    zoo = PolicyZoo(str(tmp_path))
    dnn = get_scheduler("dnn", cost_model=cm, seed=0)
    zoo.save_scheduler("d", dnn)
    rlds = get_scheduler("rlds", cost_model=cm, seed=0, pretrain_rounds=0)
    with pytest.raises(ValueError, match="kind"):
        zoo.load_into("d", rlds)
    with pytest.raises(FileNotFoundError, match="no policy"):
        zoo.load_into("nope", rlds)
    greedy = get_scheduler("greedy", cost_model=cm, seed=0)
    with pytest.raises(TypeError, match="state_dict"):
        zoo.load_into("d", greedy)
    assert zoo.names() == ["d"]
    assert zoo.info("d")["kind"] == "dnn"


# ---- lazy RLDS pre-training (satellite) ----------------------------------

def test_rlds_pretrain_is_lazy():
    pool, cm = _pool_cm()
    sched = get_scheduler("rlds", cost_model=cm, seed=0, pretrain_rounds=4)
    # Construction ran NO pre-training rounds: baselines still unset.
    assert not sched._pretrained
    assert np.isnan(sched.baselines).all()
    sched.schedule(make_ctx(pool, n_sel=3))
    assert sched._pretrained
    assert np.isfinite(sched.baselines).any()  # Algorithm 3 ran at first use


def test_rlds_warm_start_skips_pretraining():
    pool, cm = _pool_cm()
    donor = get_scheduler("rlds", cost_model=cm, seed=1, pretrain_rounds=0)
    sched = get_scheduler("rlds", cost_model=cm, seed=2, pretrain_rounds=300)
    sched.load_state_dict(donor.state_dict())
    assert sched._pretrained  # schedule() will never run the 300 rounds
    _assert_trees_equal(sched.params, donor.params)


# ---- ExperimentSpec policy axis ------------------------------------------

def test_spec_policy_axis_loads_gym_policy(tmp_path):
    """A gym-trained policy saved to the zoo loads into spec.build()'s live
    scheduler by name, bit-exactly, with constructor pre-training disabled."""
    stages = default_stages("default", num_devices=(30,), num_jobs=2)
    tcfg = TrainConfig(num_envs=4, rollout_len=4, iters=2, minibatches=2)
    params, _ = train_rlds(stages, tcfg, seed=0)
    zoo = PolicyZoo(str(tmp_path))
    save_rlds_params(zoo, "gym-pol", params, num_jobs=2,
                     meta={"curriculum": "default"})

    spec = ExperimentSpec(
        jobs=tuple(JobSpec(name=f"j{i}", target_metric=0.7, max_rounds=3)
                   for i in range(2)),
        pool=PoolSpec(num_devices=30, seed=3), scheduler="rlds",
        runtime="synthetic", runtime_kwargs={"seed": 2}, n_sel=4,
        policy="gym-pol", policy_dir=str(tmp_path))
    exp = spec.build()
    _assert_trees_equal(exp.engine.scheduler.params, params)
    assert exp.engine.scheduler._pretrained  # warm start replaced Algorithm 3
    result = exp.run()
    assert len(result.records) > 0


def test_spec_policy_axis_json_roundtrip(tmp_path):
    spec = ExperimentSpec(jobs=(JobSpec(name="j"),), scheduler="rlds",
                          policy="some-policy", policy_dir=str(tmp_path))
    restored = ExperimentSpec.from_json(spec.to_json())
    assert restored == spec
    assert restored.policy == "some-policy"


# ---- online-traffic (arrivals) curriculum --------------------------------

def test_arrivals_curriculum_draws_job_windows():
    from repro.gym.scenarios import sample_scenario

    spec = CURRICULA["arrivals"]
    key = jax.random.PRNGKey(0)
    draw = sample_scenario(key, spec, 24, 4)
    start, end = draw.job_start, draw.job_end
    assert start.shape == (4,) and end.shape == (4,)
    # job 0 anchors the episode: live from step 0, never departs
    assert float(start[0]) == 0.0 and not bool(jnp.isfinite(end[0]))
    lo, hi = spec.arrival_window
    assert bool(((start[1:] >= lo) & (start[1:] <= hi)).all())
    assert bool((end[1:] > start[1:]).all())
    # the closed-set default compiles the windows away
    d2 = sample_scenario(key, CURRICULA["default"], 24, 4)
    assert float(jnp.abs(d2.job_start).sum()) == 0.0
    assert not bool(jnp.isfinite(d2.job_end).any())


def test_inactive_job_round_is_noop():
    """A plan-masked (inactive-job) round must leave counts/time untouched
    and contribute zero cost — the empty-plan no-op the arrivals curriculum
    relies on."""
    from repro.gym.env import job_active, random_rollout

    cfg = small_cfg()
    state = reset(cfg, CURRICULA["default"], jax.random.PRNGKey(3))
    # Force every job inactive by pushing all arrivals past the horizon.
    far = jnp.full((cfg.num_jobs,), 1e9, jnp.float32)
    state = state._replace(scen=state.scen._replace(job_start=far))
    assert not bool(job_active(state))
    final, tr = jax.jit(random_rollout, static_argnums=(0, 2))(cfg, state, 6)
    np.testing.assert_array_equal(np.asarray(tr.cost), 0.0)
    np.testing.assert_array_equal(np.asarray(tr.round_time), 0.0)
    np.testing.assert_array_equal(np.asarray(final.counts),
                                  np.asarray(state.counts))


def test_arrivals_rollout_masks_inactive_jobs():
    from repro.core.schedulers.rlds import init_policy

    cfg = small_cfg(num_jobs=4)
    states = batch_reset(cfg, CURRICULA["arrivals"], jax.random.PRNGKey(4), 6)
    params = init_policy(jax.random.PRNGKey(5))
    finals, tr = batch_rollout(cfg, params, states, 40)
    plans = np.asarray(tr.plan)        # (E, T, K)
    jobs = np.asarray(tr.job)          # (E, T)
    start = np.asarray(states.scen.job_start)  # (E, M)
    end = np.asarray(states.scen.job_end)
    # Every round scheduled for a job outside its window must be empty.
    # The env's clock t equals the step index within the rollout here
    # (rollout starts at t=0).
    E, T = jobs.shape
    t = np.arange(T)[None, :]
    active = ((np.take_along_axis(start, jobs, axis=1) <= t)
              & (t < np.take_along_axis(end, jobs, axis=1)))
    assert (plans.sum(-1)[~active] == 0).all()
    # ...and the curriculum actually exercises inactivity AND activity.
    assert bool(active.any()) and bool((~active).any())

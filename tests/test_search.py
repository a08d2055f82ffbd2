"""Fused-search subsystem tests: vectorized repair properties, per-form
auto-dispatch pinning, fused plan invariants, host-vs-fused behavioural
parity at matched search budgets, and the SA small fixes.

Property tests run under hypothesis when available; without it they
degrade to a fixed-seed sweep (the pattern of tests/test_scoring.py).
"""

import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

from repro.core import scoring, search
from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.plans import (random_plans, repair_plans, validate_plan)
from repro.core.schedulers import bods as bods_mod
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulingContext


def make_ctx(pool, job=0, n_sel=5, occupied=None, counts=None, round_idx=0):
    K = pool.num_devices
    avail = np.ones(K, dtype=bool)
    if occupied is not None:
        avail[occupied] = False
    return SchedulingContext(
        job=job, round_idx=round_idx, tau=5.0, n_sel=n_sel,
        available=avail,
        counts=counts if counts is not None else np.zeros(K),
        expected_times=pool.expected_times(job, 5.0))


def scenario(K, seed, n_sel, busy_frac=0.2):
    pool = DevicePool.heterogeneous(K, 2, seed=seed)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0, 5.0], n_sel=n_sel)
    rng = np.random.default_rng(seed + 1000)
    counts = rng.integers(0, 8, K).astype(np.float64)
    occ = rng.choice(K, int(K * busy_frac), replace=False)
    return cm, pool, counts, occ


# ---- repair_plans properties ----------------------------------------------

def check_repair_feasible(seed, k, n_sel, p):
    rng = np.random.default_rng(seed)
    n_sel = min(n_sel, k)
    available = rng.random(k) < 0.6
    if available.sum() < n_sel:
        available[rng.choice(k, n_sel, replace=False)] = True
    raw = rng.random((p, k)) < 0.3
    out = repair_plans(rng, raw, available, n_sel)
    for r_raw, r in zip(raw, out):
        validate_plan(r, available, n_sel)
        keep = r_raw & available
        # Valid selections survive: kept entirely when under budget,
        # and nothing outside them is added when over budget.
        if keep.sum() <= n_sel:
            assert np.all(r[keep])
        else:
            assert np.all(keep[r])


def check_repair_idempotent(seed, k, n_sel):
    rng = np.random.default_rng(seed)
    n_sel = min(n_sel, k)
    available = rng.random(k) < 0.7
    if available.sum() < n_sel:
        available[rng.choice(k, n_sel, replace=False)] = True
    valid = random_plans(rng, available, n_sel, 6)
    assert np.array_equal(repair_plans(rng, valid, available, n_sel), valid)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**31), k=st.integers(5, 60),
           n_sel=st.integers(1, 8), p=st.integers(1, 10))
    def test_repair_plans_always_feasible(seed, k, n_sel, p):
        check_repair_feasible(seed, k, n_sel, p)

    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**31), k=st.integers(5, 60),
           n_sel=st.integers(1, 8))
    def test_repair_plans_idempotent_on_valid(seed, k, n_sel):
        check_repair_idempotent(seed, k, n_sel)
else:  # pragma: no cover - fixed-seed fallback
    def test_repair_plans_always_feasible():
        rng = np.random.default_rng(0)
        for _ in range(60):
            check_repair_feasible(int(rng.integers(2**31)),
                                  int(rng.integers(5, 60)),
                                  int(rng.integers(1, 8)),
                                  int(rng.integers(1, 10)))

    def test_repair_plans_idempotent_on_valid():
        rng = np.random.default_rng(1)
        for _ in range(60):
            check_repair_idempotent(int(rng.integers(2**31)),
                                    int(rng.integers(5, 60)),
                                    int(rng.integers(1, 8)))


def test_repair_plans_jax_matches_contract():
    """The in-graph twin obeys the same feasibility/idempotence contract."""
    import jax

    rng = np.random.default_rng(3)
    for t in range(10):
        K, n_sel = 40, 6
        avail = rng.random(K) < 0.6
        if avail.sum() < n_sel:
            avail[rng.choice(K, n_sel, replace=False)] = True
        raw = rng.random((8, K)) < 0.3
        key = jax.random.PRNGKey(t)
        out = np.asarray(search.repair_plans_jax(key, raw, avail, n_sel))
        for r_raw, r in zip(raw, out):
            validate_plan(r, avail, n_sel)
            keep = r_raw & avail
            if keep.sum() <= n_sel:
                assert np.all(r[keep])
            else:
                assert np.all(keep[r])
        valid = random_plans(rng, avail, n_sel, 4)
        fixed = np.asarray(search.repair_plans_jax(key, valid, avail, n_sel))
        assert np.array_equal(fixed, valid)


# ---- per-form auto dispatch (calibrated from BENCH_fleet.json) ------------

def test_auto_dispatch_per_form_thresholds():
    # Dense: numpy through the K=1e3/P=256 tie (2.56e5), jax by 4.1e5.
    assert scoring.resolve_backend("auto", 100 * 256, "dense") == "numpy"
    assert scoring.resolve_backend("auto", 1000 * 256, "dense") == "numpy"
    assert scoring.resolve_backend("auto", 100 * 4096, "dense") == "jax"
    assert scoring.resolve_backend("auto", 10_000 * 4096, "dense") == "jax"
    # Index: numpy's gather stays ahead through P*n_sel = 4.1e5 (K=1e4,
    # P=4096) and loses by 4.1e6 (K=1e5, P=4096).
    assert scoring.resolve_backend("auto", 4096 * 100, "index") == "numpy"
    assert scoring.resolve_backend("auto", 4096 * 1000, "index") == "jax"
    # The index threshold sits strictly above the dense one.
    assert scoring.AUTO_NUMPY_MAX_INDEX > scoring.AUTO_NUMPY_MAX_DENSE
    # Back-compat alias still names the dense threshold.
    assert scoring.AUTO_NUMPY_MAX == scoring.AUTO_NUMPY_MAX_DENSE


def test_index_form_dispatch_used_by_score_plan_indices():
    """(P, S) element counts between the two thresholds pick numpy for the
    index form and jax for an equal-sized dense problem."""
    mid = (scoring.AUTO_NUMPY_MAX_DENSE + scoring.AUTO_NUMPY_MAX_INDEX) // 2
    assert scoring.resolve_backend("auto", mid, "index") == "numpy"
    assert scoring.resolve_backend("auto", mid, "dense") == "jax"


# ---- in-graph cost parity against the scoring core ------------------------

@pytest.mark.parametrize("delta", [True, False])
def test_plan_costs_matches_scoring_core(delta):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    K, P, n_sel = 300, 32, 12
    times = rng.uniform(0.5, 80.0, K)
    counts = rng.integers(0, 40, K).astype(np.float64)
    plans = random_plans(rng, np.ones(K, bool), n_sel, P)
    idx = np.stack([np.flatnonzero(p) for p in plans]).astype(np.int32)
    kw = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
              delta_fairness=delta)
    want = scoring.score_plans(times, counts, plans, backend="numpy", **kw)
    counts_c = jnp.asarray(counts - counts.mean(), jnp.float32)
    t32 = jnp.asarray(times, jnp.float32)
    dense = np.asarray(search.plan_costs(
        t32, counts_c, jnp.asarray(plans), 4.0, 0.25, 3.0, 0.09, delta))
    byidx = np.asarray(search.plan_costs_idx(
        t32, counts_c, jnp.asarray(idx), 4.0, 0.25, 3.0, 0.09, delta))
    np.testing.assert_allclose(dense, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(byidx, want, rtol=2e-4, atol=2e-4)


# ---- fused plan invariants -------------------------------------------------

@pytest.mark.parametrize("name", ["sa", "genetic", "bods"])
def test_fused_plan_invariants(name):
    """The fused searchers return exactly n_sel available devices, always,
    across evolving occupancy/counts."""
    pool = DevicePool.heterogeneous(40, 2, seed=1)
    cm = CostModel(pool)
    cm.calibrate([5.0, 5.0], n_sel=4)
    sched = get_scheduler(name, cost_model=cm, seed=0,
                          search_backend="fused")
    rng = np.random.default_rng(0)
    counts = np.zeros(40)
    for r in range(6):
        occ = rng.choice(40, rng.integers(0, 20), replace=False)
        ctx = make_ctx(pool, n_sel=4, occupied=occ, counts=counts,
                       round_idx=r)
        plan = sched.schedule(ctx)
        validate_plan(plan, ctx.available, 4)
        sched.observe(ctx, plan, float(rng.random()))
        counts += plan


def test_fused_raises_when_pool_too_small():
    pool = DevicePool.heterogeneous(10, 1, seed=0)
    cm = CostModel(pool)
    for name in ("sa", "genetic"):
        sched = get_scheduler(name, cost_model=cm, seed=0,
                              search_backend="fused")
        ctx = make_ctx(pool, n_sel=5, occupied=np.arange(6))
        with pytest.raises(ValueError):
            sched.schedule(ctx)


def test_search_backend_rejects_unknown():
    pool = DevicePool.heterogeneous(10, 1, seed=0)
    cm = CostModel(pool)
    with pytest.raises(ValueError):
        get_scheduler("sa", cost_model=cm, seed=0, search_backend="gpu")


# ---- host-vs-fused behavioural parity (matched budgets, seeded) -----------

def _mean_chosen_cost(name, kw, seeds, K=80, n_sel=8, reps=2):
    cs = []
    for sd in seeds:
        cm, pool, counts, occ = scenario(K, sd, n_sel)
        sched = get_scheduler(name, cost_model=cm, seed=sd, **kw)
        for _ in range(reps):
            ctx = make_ctx(pool, n_sel=n_sel, occupied=occ, counts=counts)
            plan = sched.schedule(ctx)
            validate_plan(plan, ctx.available, n_sel)
            cs.append(sched.last_estimated_cost)
    return float(np.mean(cs))


def test_sa_parity_fused_no_worse_than_host():
    """Matched budget: 8 chains x 25 steps (cooling^8) vs 200 host steps.
    Multi-chain + greedy seeding should dominate the single host chain."""
    seeds = range(8)
    host = _mean_chosen_cost(
        "sa", dict(search_backend="host", steps=200), seeds)
    fused = _mean_chosen_cost(
        "sa", dict(search_backend="fused", steps=25, chains=8,
                   cooling=0.97 ** 8), seeds)
    assert fused <= host * 1.005, (fused, host)


def test_ga_parity_fused_no_worse_than_host():
    seeds = range(8)
    host = _mean_chosen_cost("genetic", dict(search_backend="host"), seeds)
    fused = _mean_chosen_cost("genetic", dict(search_backend="fused"), seeds)
    assert fused <= host * 1.005, (fused, host)


def test_bods_fused_comparable_and_beats_random():
    """BODS picks by EI (not pure cost), so parity is statistical: the
    fused acquisition must stay in the host path's cost band and well
    below random selection."""
    seeds = range(6)
    host = _mean_chosen_cost("bods", dict(search_backend="host"), seeds)
    fused = _mean_chosen_cost("bods", dict(search_backend="fused"), seeds)
    rand = _mean_chosen_cost("random", {}, seeds)
    assert fused <= host * 1.15, (fused, host)
    assert fused < rand, (fused, rand)


# ---- SA small fixes --------------------------------------------------------

def test_sa_host_no_free_device_completes():
    """available == n_sel: no swap is ever possible; the host path must
    return the (only) valid plan instead of breaking mid-schedule."""
    pool = DevicePool.heterogeneous(20, 1, seed=0)
    cm = CostModel(pool)
    cm.calibrate([5.0], n_sel=3)
    sched = get_scheduler("sa", cost_model=cm, seed=0, search_backend="host")
    ctx = make_ctx(pool, n_sel=3, occupied=np.arange(3, 20))
    plan = sched.schedule(ctx)
    validate_plan(plan, ctx.available, 3)
    # Fused path: swaps all mask out, plan still valid.
    schedf = get_scheduler("sa", cost_model=cm, seed=0,
                           search_backend="fused")
    plan = schedf.schedule(make_ctx(pool, n_sel=3, occupied=np.arange(3, 20)))
    validate_plan(plan, np.r_[np.ones(3, bool), np.zeros(17, bool)], 3)


def test_sa_metropolis_exponent_clamped():
    """Pathological cost spikes (t0 ~ 0 -> huge exponent) must not emit
    overflow RuntimeWarnings from np.exp."""
    pool = DevicePool.heterogeneous(30, 1, seed=0)
    cm = CostModel(pool, alpha=100.0, beta=50.0)  # uncalibrated: big costs
    sched = get_scheduler("sa", cost_model=cm, seed=0, search_backend="host",
                          steps=50, t0=1e-12)
    ctx = make_ctx(pool, n_sel=5)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        with np.errstate(over="raise", invalid="raise"):
            plan = sched.schedule(ctx)
    validate_plan(plan, ctx.available, 5)


# ---- batched all-jobs EI ---------------------------------------------------

def test_ei_scores_jobs_matches_per_job_loop():
    from repro.core.schedulers.bods import MAX_OBS, NUM_FEATURES, _ei_scores

    rng = np.random.default_rng(0)
    M, L, P, d = 3, MAX_OBS, 17, NUM_FEATURES
    F = rng.normal(size=(M, L, d)).astype(np.float32)
    resid = rng.normal(size=(M, L)).astype(np.float32)
    valid = (rng.random((M, L)) < 0.3).astype(np.float32)
    feats = rng.normal(size=(M, P, d)).astype(np.float32)
    cand = rng.normal(size=(M, P)).astype(np.float32)
    batched = np.asarray(search.ei_scores_jobs(
        F, resid, valid, feats, cand, 0.25))
    assert batched.shape == (M, P)
    for m in range(M):
        one = np.asarray(_ei_scores(F[m], resid[m], valid[m],
                                    feats[m], cand[m], 0.25))
        np.testing.assert_allclose(batched[m], one, rtol=1e-5, atol=1e-6)


def test_featurize_plans_matches_host_bods():
    """The in-graph phi(V) must match the host BODSScheduler._featurize
    formula-for-formula (the GP consumes both)."""
    import jax.numpy as jnp

    K, P, n_sel = 60, 16, 6
    cm, pool, counts, occ = scenario(K, 0, n_sel)
    ctx = make_ctx(pool, n_sel=n_sel, occupied=occ, counts=counts)
    sched = get_scheduler("bods", cost_model=cm, seed=0)
    rng = np.random.default_rng(1)
    plans = random_plans(rng, ctx.available, n_sel, P)
    want = sched._featurize(ctx, plans)
    counts_c = jnp.asarray(counts - counts.mean(), jnp.float32)
    got, _, _ = search.featurize_plans(
        jnp.asarray(ctx.expected_times, jnp.float32), counts_c,
        jnp.asarray(counts == 0), jnp.asarray(pool.mu, jnp.float32),
        jnp.asarray(plans), cm.time_scale, cm.fairness_scale, n_sel,
        cm.delta_fairness)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


# ---- experiment-layer wiring ----------------------------------------------

def test_spec_search_backend_axis_roundtrip():
    from repro.experiment.spec import ExperimentSpec, JobSpec

    spec = ExperimentSpec(jobs=(JobSpec(name="a", max_rounds=2),),
                          scheduler="sa")
    assert spec.build().engine.scheduler.search_backend == "fused"
    host = spec.replace(search_backend="host")
    assert host.build().engine.scheduler.search_backend == "host"
    assert ExperimentSpec.from_json(host.to_json()) == host
    nested = spec.replace(fleet={"search_backend": "host"})
    assert nested.effective_search_backend() == "host"
    assert nested.build().engine.scheduler.search_backend == "host"
    # Schedulers without the knob still build with the axis set.
    dnn = spec.replace(scheduler="dnn", search_backend="host")
    dnn.build()


def test_ctx_caches_computed_once():
    pool = DevicePool.heterogeneous(25, 1, seed=0)
    ctx = make_ctx(pool, n_sel=4, occupied=[1, 2])
    t32 = ctx.times32()
    assert t32.dtype == np.float32
    assert ctx.times32() is t32               # cached, not recomputed
    idx = ctx.available_indices()
    assert ctx.available_indices() is idx
    np.testing.assert_array_equal(idx, np.flatnonzero(ctx.available))


# ---- tracing the fused decisions -----------------------------------------

def _bods_decisions(traced, rounds=4, K=40):
    """Decisions of one fused BODS scheduler from a fixed seed (later
    rounds mutate the best observed plan); with ``traced`` the global
    tracer is on and its events are returned."""
    from repro.monitoring import trace as trace_mod

    cm, pool, counts, occ = scenario(K, 3, 4)
    sched = get_scheduler("bods", cost_model=cm, seed=5, num_candidates=32,
                          search_backend="fused")
    rng = np.random.default_rng(7)
    out = []
    trace_mod.clear()
    if traced:
        trace_mod.enable()
    try:
        for r in range(rounds):
            ctx = make_ctx(pool, n_sel=4, occupied=occ, counts=counts.copy(),
                           round_idx=r)
            plan = sched.schedule(ctx)
            out.append((plan.copy(), sched.last_estimated_cost))
            sched.observe(ctx, plan, float(rng.random()))
            counts += plan
    finally:
        trace_mod.disable()
    events = trace_mod.get_tracer().events()
    trace_mod.clear()
    return out, events


def test_traced_bods_decision_records_its_phases():
    _, events = _bods_decisions(traced=True)
    names = [e["name"] for e in events]
    assert names.count("bods_acquire") == 4
    assert names.count("bods_observe") == 4
    # The last decision and its observation (events are recorded as their
    # spans close).
    mine = events[len(events) - 11:]
    assert [e["name"] for e in mine] == [
        "bods_mutate", "bods_prepare", "bods_pack", "bods_put", "bods_stage",
        "bods_launch", "bods_wait", "bods_readback", "bods_acquire",
        "bods_readback", "bods_observe"]
    by = {e["name"]: e for e in mine}
    # K=40 devices, 8 of them busy (``scenario``), 32 // 4 = 8 mutants.
    assert by["bods_prepare"]["args"] == {"mutants": 8, "k": 40,
                                          "available": 32}
    assert by["bods_mutate"]["args"] == {"k": 40, "mutants": 8}
    assert by["bods_observe"]["args"] == {"k": 40}
    # One packed transfer of the MAX_OBS ring of NUM_FEATURES features.
    layout = search._bods_layout(40, bods_mod.MAX_OBS, bods_mod.NUM_FEATURES,
                                 8)
    assert by["bods_stage"]["args"] == {"arrays": 1,
                                        "bytes": layout.words * 4}
    assert by["bods_pack"]["args"] == {"words": layout.words}
    assert by["bods_put"]["args"] == {"bytes": layout.words * 4}
    reads = [e["args"] for e in mine if e["name"] == "bods_readback"]
    assert reads == [{"what": "plan", "reads": 2}, {"what": "ei", "reads": 1}]

    def inside(inner, outer):
        return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-3)

    acquire = by["bods_acquire"]
    for e in mine[4:8]:
        assert inside(e, acquire), e["name"]
    assert inside(by["bods_mutate"], by["bods_prepare"])
    assert inside(by["bods_pack"], by["bods_stage"])
    assert inside(by["bods_put"], by["bods_stage"])
    assert by["bods_pack"]["ts"] + by["bods_pack"]["dur"] <= (
        by["bods_put"]["ts"] + 1e-3)
    assert by["bods_prepare"]["ts"] + by["bods_prepare"]["dur"] <= (
        acquire["ts"] + 1e-3)
    assert mine[-2]["ts"] >= acquire["ts"] + acquire["dur"] - 1e-3
    assert mine[-1]["ts"] >= mine[-2]["ts"] + mine[-2]["dur"] - 1e-3


def test_traced_and_untraced_bods_decisions_are_identical():
    untraced, none = _bods_decisions(traced=False)
    traced, events = _bods_decisions(traced=True)
    assert none == [] and events
    for (p0, e0), (p1, e1) in zip(untraced, traced):
        np.testing.assert_array_equal(p0, p1)
        assert e0 == e1


def test_fused_bods_decision_at_fleet_scale_matches_float64_cost():
    """Fused BODS over K=20,000 devices with 512 candidates (the fleet
    cell's count), a selection of 39: a first decision, then one that
    mutates the best observed plan. Each plan is a (K,) mask of exactly 39
    available devices, and its estimate is within 1e-5 of Formula 2
    re-scored in float64 numpy."""
    K, n_sel = 20_000, 39
    cm, pool, counts, occ = scenario(K, 11, n_sel)
    assert cm.delta_fairness
    sched = get_scheduler("bods", cost_model=cm, seed=3, num_candidates=512,
                          search_backend="fused")
    for r in range(2):
        ctx = make_ctx(pool, n_sel=n_sel, occupied=occ, counts=counts.copy(),
                       round_idx=r)
        plan = sched.schedule(ctx)
        assert plan.dtype == bool and plan.shape == (K,)
        assert int(plan.sum()) == n_sel and ctx.available[plan].all()
        t = np.asarray(ctx.expected_times, np.float64)
        ref = (cm.alpha * t[plan].max() / cm.time_scale
               + cm.beta * (np.var(counts + plan) - np.var(counts))
               / cm.fairness_scale)
        assert abs(sched.last_estimated_cost - ref) <= 1e-5 * abs(ref)
        sched.observe(ctx, plan, 1.1 * ref)
        counts += plan


@pytest.mark.parametrize("name,factory,module", [
    ("bods", "_bods_fn", "jit_bods_acquire"),
    ("sa", "_sa_fn", "jit_sa_search"),
    ("genetic", "_ga_fn", "jit_ga_search"),
])
def test_search_programs_are_named_for_their_searcher(monkeypatch, name,
                                                      factory, module):
    calls = []
    real = getattr(search, factory)

    def capture(*key):
        fn = real(*key)

        def call(*args):
            calls.append((fn, args))
            return fn(*args)
        return call

    monkeypatch.setattr(search, factory, capture)
    pool = DevicePool.heterogeneous(30, 1, seed=0)
    cm = CostModel(pool)
    cm.calibrate([5.0], n_sel=4)
    sched = get_scheduler(name, cost_model=cm, seed=0,
                          search_backend="fused")
    sched.schedule(make_ctx(pool, n_sel=4))
    (fn, args), = calls
    head = fn.lower(*args).as_text().splitlines()[0]
    assert head.startswith(f"module @{module} "), head


# ---- the packed BODS inputs ------------------------------------------------

def _bods_host_inputs(K, L, d, n_mut, seed, use_base, draw=0):
    """Host inputs of one fused BODS decision as the scheduler hands them
    over: float64 times, counts and mu, float32 ring arrays."""
    rng = np.random.default_rng([K, L, n_mut, draw])
    valid = (rng.random(L) < 0.7).astype(np.float32)
    y = rng.normal(3.0, 1.0, L).astype(np.float32)
    return dict(
        seed=seed, times=rng.gamma(2.0, 3.0, K),
        counts=rng.integers(0, 9, K).astype(np.float64),
        avail=rng.random(K) < 0.8, mu=rng.uniform(0.3, 2.0, K),
        mutants=rng.random((n_mut, K)) < 0.1, use_base=use_base,
        F=rng.normal(size=(L, d)).astype(np.float32), y=y,
        est=(y + rng.normal(0, 0.3, L)).astype(np.float32), valid=valid,
        sd=float(y[valid > 0].std()) + 1e-6, alpha=4.0, beta=0.1,
        ts=1.0 / 3.0, fs=0.7, noise=0.1)


def _staged_one_by_one(x):
    """Reference: the decision's 17 inputs staged as 17 separate device
    arrays, one ``jnp`` conversion each."""
    import jax.numpy as jnp

    return (
        jnp.uint32(x["seed"]), jnp.asarray(x["times"], jnp.float32),
        jnp.asarray(search._center(x["counts"])),
        jnp.asarray(np.asarray(x["counts"]) == 0), jnp.asarray(x["avail"]),
        jnp.asarray(x["mu"], jnp.float32), jnp.asarray(x["mutants"]),
        jnp.asarray(bool(x["use_base"])), jnp.asarray(x["F"]),
        jnp.asarray((x["y"] - x["est"]) / x["sd"] * x["valid"],
                    jnp.float32),
        jnp.asarray(x["valid"], jnp.float32), jnp.float32(1.0 / x["sd"]),
        jnp.float32(x["alpha"]), jnp.float32(x["beta"]),
        jnp.float32(x["ts"]), jnp.float32(x["fs"]), jnp.float32(x["noise"]))


def _packed(layout, x):
    import jax

    return jax.device_put(layout.pack(
        x["seed"], x["times"], search._center(x["counts"]),
        np.asarray(x["counts"]) == 0, x["avail"], x["mu"], x["mutants"],
        x["use_base"], x["F"], (x["y"] - x["est"]) / x["sd"] * x["valid"],
        x["valid"], 1.0 / x["sd"], x["alpha"], x["beta"], x["ts"], x["fs"],
        x["noise"]))


def _assert_bit_equal(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype, (what, got.dtype, want.dtype)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert got.tobytes() == want.tobytes(), what


@pytest.mark.parametrize("K,L,d,n_mut", [(100, 256, 6, 32), (37, 16, 6, 8)])
@pytest.mark.parametrize("use_base", [True, False])
def test_bods_packed_inputs_round_trip_bit_exact(K, L, d, n_mut, use_base):
    import jax

    layout = search._bods_layout(K, L, d, n_mut)
    assert layout.words == 1 + 5 * K + n_mut * K + 1 + L * d + 2 * L + 6
    unpack = jax.jit(layout.unpack)
    for seed in (0, 1, 12345, 2**31 - 2):
        x = _bods_host_inputs(K, L, d, n_mut, seed, use_base, draw=seed)
        assert (search._center(x["counts"]) < 0).any()
        got = unpack(_packed(layout, x))
        want = _staged_one_by_one(x)
        assert len(got) == len(want) == len(layout.fields) == 17
        for (name, *_), g, w in zip(layout.fields, got, want):
            _assert_bit_equal(g, w, (name, seed))


@pytest.mark.parametrize("use_base", [True, False])
def test_packed_bods_program_matches_unpacked_body(monkeypatch, use_base):
    """The buffer ``bods_acquire`` stages unpacks to the inputs staged one
    by one; ``jit_bods_acquire`` on it decides exactly as the 17-argument
    body jitted on those inputs, and ``bods_acquire`` returns that
    decision."""
    import copy

    import jax

    K, L, d, P, n_mut, n_sel = 40, 24, 6, 32, 8, 4
    calls = []
    real = search._bods_fn

    def capture(*key):
        fn = real(*key)

        def call(packed):
            out = fn(packed)
            calls.append((key, packed, out))
            return out
        return call

    monkeypatch.setattr(search, "_bods_fn", capture)
    body = jax.jit(search._bods_body(P, n_mut, n_sel, False, True))
    for draw in range(3):
        x = _bods_host_inputs(K, L, d, n_mut, 0, use_base, draw=draw)
        x["avail"][:n_sel] = True
        base = np.zeros(K, bool)
        base[np.flatnonzero(x["avail"])[:n_sel]] = True
        rng = np.random.default_rng(100 + draw)
        twin = copy.deepcopy(rng)
        plan, est = search.bods_acquire(
            rng, x["times"], x["counts"], x["avail"], x["mu"], n_sel,
            F=x["F"], y=x["y"], est=x["est"], valid=x["valid"],
            base_plan=base if use_base else None, alpha=x["alpha"],
            beta=x["beta"], time_scale=x["ts"], fairness_scale=x["fs"],
            delta_fairness=False, num_candidates=P, n_mut=n_mut,
            local_search=True, gp_noise=x["noise"])
        # The host draws of bods_prepare, replayed: mutants, then the seed.
        x["mutants"] = (search._mutate_plan_host(twin, base, n_mut)
                        if use_base else np.zeros((n_mut, K), bool))
        x["seed"] = int(twin.integers(0, 2**31 - 1))
        (key, packed, got), = calls
        calls.clear()
        layout = key[-1]
        assert layout == search._bods_layout(K, L, d, n_mut)
        staged = _staged_one_by_one(x)
        for (name, *_), g, w in zip(layout.fields, jax.jit(layout.unpack)(
                packed), staged):
            _assert_bit_equal(g, w, (name, draw))
        want = body(*staged)
        for what, g, w in zip(("plan", "est", "ei"), got, want):
            _assert_bit_equal(g, w, (what, draw))
        np.testing.assert_array_equal(plan, np.asarray(got[0]))
        assert est == float(got[1])

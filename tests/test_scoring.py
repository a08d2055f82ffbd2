"""Scoring-core parity: numpy == jitted jax == Pallas kernel (interpret).

The batched plan-scoring core (repro/core/scoring.py) is the one inner loop
under every scheduler, so its three backends must agree bit-tightly across
shapes, ragged availability masks, empty plans, and both fairness modes.

Property tests run under hypothesis when available; without it they degrade
to a fixed-seed sweep so the parity contract is enforced either way.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

from repro.core import scoring
from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.plans import gumbel_topk_plans, random_plans, validate_plan

TOL = dict(rtol=1e-5, atol=1e-5)


def make_problem(rng, K, P, ragged=True, allow_empty=True, count_hi=50):
    times = rng.uniform(0.1, 100.0, K)
    counts = rng.integers(0, count_hi, K).astype(np.float64)
    density = rng.uniform(0.05, 0.6)
    plans = rng.random((P, K)) < density
    if ragged:  # knock out a random device subset across all plans
        mask = rng.random(K) < 0.8
        plans &= mask[None, :]
    if allow_empty and P > 1:
        plans[rng.integers(0, P)] = False
    return times, counts, plans


# ---- parity properties (hypothesis or fixed-seed sweep) --------------------

def check_numpy_jax_parity(seed, k, p, delta):
    rng = np.random.default_rng(seed)
    times, counts, plans = make_problem(rng, k, p)
    kw = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
              delta_fairness=delta)
    a = scoring.score_plans(times, counts, plans, backend="numpy", **kw)
    b = scoring.score_plans(times, counts, plans, backend="jax", **kw)
    np.testing.assert_allclose(a, b, **TOL)


def check_pallas_kernel_parity(seed, k, p, delta):
    rng = np.random.default_rng(seed)
    times, counts, plans = make_problem(rng, k, p)
    kw = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
              delta_fairness=delta)
    a = scoring.score_plans(times, counts, plans, backend="numpy", **kw)
    c = scoring.score_plans_pallas_interpret(times, counts, plans, **kw)
    np.testing.assert_allclose(a, c, **TOL)


def check_random_plans_valid(seed, n_sel, count):
    rng = np.random.default_rng(seed)
    available = rng.random(60) < 0.5
    if available.sum() < n_sel:
        available[:n_sel] = True
    plans = random_plans(rng, available, n_sel, count)
    assert plans.shape == (count, 60)
    for p in plans:
        validate_plan(p, available, n_sel)


def check_gumbel_topk_valid(seed, n_sel, count):
    rng = np.random.default_rng(seed)
    K = 40
    available = rng.random(K) < 0.6
    if available.sum() < n_sel:
        available[:n_sel] = True
    logits = rng.normal(size=(count, K))
    plans = gumbel_topk_plans(rng, logits, available, n_sel)
    for p in plans:
        validate_plan(p, available, n_sel)


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31), k=st.integers(1, 90),
           p=st.integers(1, 12), delta=st.booleans())
    def test_numpy_jax_parity(seed, k, p, delta):
        check_numpy_jax_parity(seed, k, p, delta)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31), k=st.integers(1, 70),
           p=st.integers(1, 8), delta=st.booleans())
    def test_pallas_kernel_parity(seed, k, p, delta):
        check_pallas_kernel_parity(seed, k, p, delta)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31), n_sel=st.integers(1, 10),
           count=st.integers(1, 16))
    def test_vectorized_random_plans_valid(seed, n_sel, count):
        check_random_plans_valid(seed, n_sel, count)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31), n_sel=st.integers(1, 8),
           count=st.integers(1, 12))
    def test_gumbel_topk_plans_valid(seed, n_sel, count):
        check_gumbel_topk_valid(seed, n_sel, count)

else:  # fixed-seed fallback sweep

    @pytest.mark.parametrize("seed", range(12))
    def test_numpy_jax_parity(seed):
        rng = np.random.default_rng(1000 + seed)
        check_numpy_jax_parity(seed, int(rng.integers(1, 90)),
                               int(rng.integers(1, 12)), bool(seed % 2))

    @pytest.mark.parametrize("seed", range(6))
    def test_pallas_kernel_parity(seed):
        rng = np.random.default_rng(2000 + seed)
        check_pallas_kernel_parity(seed, int(rng.integers(1, 70)),
                                   int(rng.integers(1, 8)), bool(seed % 2))

    @pytest.mark.parametrize("seed", range(10))
    def test_vectorized_random_plans_valid(seed):
        rng = np.random.default_rng(3000 + seed)
        check_random_plans_valid(seed, int(rng.integers(1, 10)),
                                 int(rng.integers(1, 16)))

    @pytest.mark.parametrize("seed", range(10))
    def test_gumbel_topk_plans_valid(seed):
        rng = np.random.default_rng(4000 + seed)
        check_gumbel_topk_valid(seed, int(rng.integers(1, 8)),
                                int(rng.integers(1, 12)))


# ---- deterministic edge cases ---------------------------------------------

def test_empty_plans_score_zero_time():
    times = np.linspace(1, 10, 20)
    counts = np.zeros(20)
    plans = np.zeros((3, 20), dtype=bool)
    for backend in ("numpy", "jax"):
        out = scoring.score_plans(times, counts, plans, alpha=1.0, beta=0.0,
                                  backend=backend)
        np.testing.assert_allclose(out, 0.0, atol=1e-7)
    out = scoring.score_plans_pallas_interpret(times, counts, plans,
                                               alpha=1.0, beta=0.0)
    np.testing.assert_allclose(out, 0.0, atol=1e-7)


def test_large_counts_no_cancellation():
    """Fleet regime: cumulative counts ~1e4 must not destroy f32 parity."""
    rng = np.random.default_rng(3)
    times, counts, plans = make_problem(rng, 256, 16, count_hi=10_000)
    kw = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
              delta_fairness=True)
    a = scoring.score_plans(times, counts, plans, backend="numpy", **kw)
    b = scoring.score_plans(times, counts, plans, backend="jax", **kw)
    c = scoring.score_plans_pallas_interpret(times, counts, plans, **kw)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)


def test_cost_model_batch_backends_agree():
    """CostModel.cost_batch is the same number on every backend."""
    pool = DevicePool.heterogeneous(64, 2, seed=0)
    rng = np.random.default_rng(1)
    counts = rng.integers(0, 8, 64).astype(float)
    plans = random_plans(rng, np.ones(64, bool), 6, 12)
    t = pool.expected_times(0, 5.0)
    cm = CostModel(pool, alpha=4.0, beta=0.25)
    cm.calibrate([5.0, 5.0], n_sel=6)
    ref = cm.cost_batch(t, counts, plans, backend="numpy")
    for backend in ("jax", "auto"):
        np.testing.assert_allclose(
            cm.cost_batch(t, counts, plans, backend=backend), ref, **TOL)
    # The pallas kernel itself, in interpret mode (its TPU backend raises
    # off-chip).
    np.testing.assert_allclose(scoring.score_plans_pallas_interpret(
        t, counts, plans, alpha=cm.alpha, beta=cm.beta,
        time_scale=cm.time_scale, fairness_scale=cm.fairness_scale,
        delta_fairness=cm.delta_fairness), ref, **TOL)


def test_round_time_and_fairness_batch_parity():
    rng = np.random.default_rng(7)
    times, counts, plans = make_problem(rng, 48, 9)
    rt_np = scoring.round_time_batch(times, plans, backend="numpy")
    rt_jx = scoring.round_time_batch(times, plans, backend="jax")
    np.testing.assert_allclose(rt_np, rt_jx, **TOL)
    for delta in (True, False):
        f_np = scoring.fairness_batch(counts, plans, delta_fairness=delta,
                                      backend="numpy")
        f_jx = scoring.fairness_batch(counts, plans, delta_fairness=delta,
                                      backend="jax")
        np.testing.assert_allclose(f_np, f_jx, **TOL)


def test_auto_dispatch_and_default_backend():
    assert scoring.resolve_backend("auto", 100) == "numpy"
    assert scoring.resolve_backend("auto", 10**7) == "jax"
    scoring.set_default_backend("jax")
    try:
        assert scoring.resolve_backend(None, 100) == "jax"
    finally:
        scoring.set_default_backend("auto")
    with pytest.raises(ValueError):
        scoring.resolve_backend("cuda", 1)


def test_pallas_requires_tpu_else_falls_back():
    """Off TPU an explicit ``pallas`` backend raises, naming the backend it
    found, instead of quietly scoring with the jax reference."""
    import jax

    if jax.default_backend() == "tpu":  # pragma: no cover - chip only
        assert scoring.resolve_backend("pallas", 10**6) == "pallas"
        return
    with pytest.raises(RuntimeError, match=jax.default_backend()):
        scoring.resolve_backend("pallas", 10**6)
    rng = np.random.default_rng(0)
    times, counts, plans = make_problem(rng, 32, 4)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        scoring.score_plans(times, counts, plans, backend="pallas")


def test_gumbel_topk_biases_toward_high_logits():
    rng = np.random.default_rng(0)
    K = 30
    logits = np.zeros(K)
    logits[:5] = 8.0  # strongly preferred
    hits = np.zeros(K)
    for _ in range(50):
        plans = gumbel_topk_plans(rng, np.tile(logits, (4, 1)),
                                  np.ones(K, bool), 5)
        hits += plans.sum(0)
    assert hits[:5].sum() > hits[5:].sum()

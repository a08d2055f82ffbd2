"""Batched plan-scoring core: one scoring path under every scheduler.

Every scheduler in this repo (BODS Alg. 1, RLDS, greedy/genetic/SA/FedCS/
random/DNN) reduces to the same inner loop — score P candidate plans over K
devices with Formula 2:

    cost(V) = alpha * max_{k in V} t_k / time_scale
            + beta  * [Var(c + v) (- Var(c))] / fairness_scale

``score_plans`` is that loop, batched, with three interchangeable backends:

- ``numpy``  — the seed implementation, bit-identical to the historical
  ``CostModel.cost_batch`` (small pools, zero dispatch overhead);
- ``jax``    — a jitted fused reduction (single pass over the (P, K) tile
  stream, no materialized float intermediates; ~10-100x numpy on 10k+
  device pools even on CPU);
- ``pallas`` — the tiled TPU kernel in ``repro.kernels.sched_score``
  (sufficient-statistics reduction; raises off-TPU — the CPU tests run it
  explicitly in interpret mode through ``score_plans_pallas_interpret``).

``backend="auto"`` (the default) picks numpy below a per-FORM element
threshold (``AUTO_NUMPY_MAX_DENSE`` / ``AUTO_NUMPY_MAX_INDEX``) and jax
above — the same size/backend dispatch the model kernels in
``repro/kernels/ops.py`` use, but calibrated separately for the dense
(P, K) sweep and the (P, n_sel) gather fast path (the index form's numpy
gather stays ahead of jit dispatch for ~4x more elements). The
process-wide default can be flipped with ``set_default_backend`` (the
experiment layer wires ``ExperimentSpec.fleet.scoring_backend`` through
``CostModel``).
"""

from __future__ import annotations

import functools
import threading
from typing import Optional, Tuple

import numpy as np


VALID_BACKENDS = ("auto", "numpy", "jax", "pallas")

# Below these many elements the numpy path wins: jit dispatch + host->device
# transfer costs more than the whole reduction. Calibrated per FORM from
# BENCH_fleet.json (CPU): dense numpy/jax cross over between P*K = 2.6e5
# (K=1e3, P=256: a tie) and 4.1e5 (jax clearly ahead); the index-form numpy
# gather is still ahead at P*n_sel = 4.1e5 (K=1e4, P=4096) and loses by
# 4.1e6 (K=1e5), so its threshold sits a factor of 4 higher.
AUTO_NUMPY_MAX_DENSE = 1 << 18
AUTO_NUMPY_MAX_INDEX = 1 << 20
# Back-compat alias (pre-calibration single threshold == the dense one).
AUTO_NUMPY_MAX = AUTO_NUMPY_MAX_DENSE
# Sharded fleets dispatch on PER-SHARD size, against a much smaller floor:
# a fleet someone bothered to shard should stay on the jax path (that is
# the whole point of sharding), so numpy only wins when the per-shard
# problem is genuinely tiny (below jit dispatch overhead). Comparing the
# per-shard count against the single-lane caps would do the opposite —
# make the numpy fallback MORE likely as shards are added.
MIN_SHARD_ELEMENTS = 1 << 12

_state = threading.local()


def set_default_backend(backend: str) -> None:
    if backend not in VALID_BACKENDS:
        raise ValueError(f"backend {backend!r} not in {VALID_BACKENDS}")
    _state.backend = backend


def get_default_backend() -> str:
    return getattr(_state, "backend", "auto")


def resolve_backend(backend: Optional[str], num_elements: int,
                    form: str = "dense", num_shards: int = 1) -> str:
    """Concrete backend for an ``num_elements``-sized scoring problem.

    ``form`` is ``dense`` (a (P, K) sweep) or ``index`` (a (P, n_sel)
    gather): the auto dispatch uses a separate measured crossover per form.
    With ``num_shards > 1`` the auto dispatch is SHARD-AWARE: it compares
    the per-shard element count against ``MIN_SHARD_ELEMENTS`` instead of
    the single-lane caps, so sharded fleets stay on the jax path.
    """
    b = backend if backend is not None else get_default_backend()
    if b not in VALID_BACKENDS:
        raise ValueError(f"backend {b!r} not in {VALID_BACKENDS}")
    if b == "auto":
        if num_shards and num_shards > 1:
            return ("numpy" if num_elements // num_shards <= MIN_SHARD_ELEMENTS
                    else "jax")
        cap = AUTO_NUMPY_MAX_INDEX if form == "index" else AUTO_NUMPY_MAX_DENSE
        return "numpy" if num_elements <= cap else "jax"
    if b == "pallas" and _jax_backend_name() != "tpu":
        raise RuntimeError(
            "scoring backend 'pallas' needs a TPU, but the default JAX "
            f"backend is {_jax_backend_name()!r}; use 'jax' or 'auto' here "
            "(tests run the kernel through score_plans_pallas_interpret)")
    return b


def _jax_backend_name() -> str:
    import jax

    return jax.default_backend()


# ---- jitted jax reference ----------------------------------------------

@functools.lru_cache(maxsize=None)
def _jax_score_fn(delta_fairness: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(times, counts, plans, alpha, beta, ts, fs):
        K = float(times.shape[0])  # float: K*K overflows int32 at K=100k
        sel = plans != 0
        masked = jnp.where(sel, times[None, :], -jnp.inf)
        t = jnp.max(masked, axis=1)
        t = jnp.where(jnp.isfinite(t), t, 0.0)
        # Fairness via sufficient statistics (v in {0,1}):
        #   sum(s) = sum(c) + n,  sum(s^2) = sum(c^2) + sum_{sel} (2c + 1)
        w = 2.0 * counts + 1.0
        n = jnp.sum(jnp.where(sel, 1.0, 0.0), axis=1)
        wsum = jnp.sum(jnp.where(sel, w[None, :], 0.0), axis=1)
        c1 = jnp.sum(counts)
        if delta_fairness:
            # Var(c+v) - Var(c), expanded: cancellation-free at any scale.
            f = wsum / K - (2.0 * c1 * n + n * n) / (K * K)
        else:
            c2 = jnp.sum(counts * counts)
            f = (c2 + wsum) / K - ((c1 + n) / K) ** 2
        return alpha * t / ts + beta * f / fs

    return score


@functools.lru_cache(maxsize=None)
def _jax_score_idx_fn(delta_fairness: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(times, counts_c, idx, alpha, beta, ts, fs):
        K = float(counts_c.shape[0])  # float: K*K overflows int32 at K=100k
        n = jnp.float32(idx.shape[1])
        t = jnp.max(times[idx], axis=1)
        w = 2.0 * counts_c + 1.0
        wsum = jnp.sum(w[idx], axis=1)
        c1 = jnp.sum(counts_c)
        if delta_fairness:
            f = wsum / K - (2.0 * c1 * n + n * n) / (K * K)
        else:
            c2 = jnp.sum(counts_c * counts_c)
            f = (c2 + wsum) / K - ((c1 + n) / K) ** 2
        return alpha * t / ts + beta * f / fs

    return score


@functools.lru_cache(maxsize=None)
def _jax_fairness_fn(delta_fairness: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fairness(counts_c, plans):
        K = float(counts_c.shape[0])
        sel = plans != 0
        w = 2.0 * counts_c + 1.0
        n = jnp.sum(jnp.where(sel, 1.0, 0.0), axis=1)
        wsum = jnp.sum(jnp.where(sel, w[None, :], 0.0), axis=1)
        c1 = jnp.sum(counts_c)
        if delta_fairness:
            return wsum / K - (2.0 * c1 * n + n * n) / (K * K)
        c2 = jnp.sum(counts_c * counts_c)
        return (c2 + wsum) / K - ((c1 + n) / K) ** 2

    return fairness


@functools.lru_cache(maxsize=None)
def _jax_round_time_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def round_time(times, plans):
        masked = jnp.where(plans != 0, times[None, :], -jnp.inf)
        t = jnp.max(masked, axis=1)
        return jnp.where(jnp.isfinite(t), t, 0.0)

    return round_time


# ---- traced-callable accessors (the gym's in-graph scoring path) --------
#
# The public API below is host-facing (numpy in/out). The scheduler gym
# (``repro.gym``) evaluates Formula 2/3 INSIDE its own jit/vmap/scan graphs,
# so it needs the underlying jitted callables directly: jax.Array in,
# jax.Array out, safe to call from traced code (an inner jit is inlined).
# Conventions match the wrappers: ``counts_c`` is mean-centered float32
# (variance is shift-invariant; centering keeps f32 cancellation-free),
# plans are (P, K) with nonzero = selected.

def jax_fairness_fn(delta_fairness: bool = False):
    """(counts_c, plans) -> (P,) Formula-5 fairness (or its increment)."""
    return _jax_fairness_fn(bool(delta_fairness))


def jax_round_time_fn():
    """(times, plans) -> (P,) Formula-3 round time (masked max, empty -> 0)."""
    return _jax_round_time_fn()


# ---- numpy reference (the seed semantics, bit-for-bit) ------------------

def _score_numpy(times, counts, plans, alpha, beta, ts, fs, delta_fairness):
    sel = plans.astype(bool)
    masked = np.where(sel, times[None, :], -np.inf)
    t = masked.max(axis=1)
    t = np.where(np.isfinite(t), t, 0.0) / ts
    f = np.var(counts[None, :] + plans, axis=1)
    if delta_fairness:
        f = f - np.var(counts)
    return alpha * t + beta * f / fs


def _score_from_stats(stats, counts, alpha, beta, ts, fs, delta_fairness):
    """(P, 3) kernel stats -> (P,) costs (cheap host-side combine)."""
    t_max = stats[:, 0].astype(np.float64)
    n = stats[:, 1].astype(np.float64)
    wsum = stats[:, 2].astype(np.float64)
    K = counts.shape[0]
    t = np.where(t_max > -1e29, t_max, 0.0) / ts
    c1 = float(np.sum(counts))
    if delta_fairness:
        f = wsum / K - (2.0 * c1 * n + n * n) / (K * K)
    else:
        c2 = float(np.sum(np.square(counts, dtype=np.float64)))
        f = (c2 + wsum) / K - ((c1 + n) / K) ** 2
    return alpha * t + beta * f / fs


# ---- public API ---------------------------------------------------------

def score_plans(times: np.ndarray, counts: np.ndarray, plans: np.ndarray,
                alpha: float = 1.0, beta: float = 1.0,
                time_scale: float = 1.0, fairness_scale: float = 1.0,
                delta_fairness: bool = True,
                backend: Optional[str] = None,
                num_shards: int = 1) -> np.ndarray:
    """Score P candidate plans: (K,) times, (K,) counts, (P, K) plans -> (P,).

    The one batched inner loop under every scheduler (Formula 2 over a
    candidate set). ``backend`` is ``numpy | jax | pallas | auto`` (None ->
    the process default, normally ``auto``). ``num_shards > 1`` shards the
    fleet (K) axis across host platform devices (``repro.core.shard``) —
    shard-local sufficient-statistics reductions with a cheap cross-shard
    combine; an explicit ``pallas`` backend is single-device, so it also
    routes to the sharded jax path when shards are requested.
    """
    times = np.asarray(times)
    counts = np.asarray(counts)
    plans = np.asarray(plans)
    if plans.ndim == 1:
        plans = plans[None, :]
    P, K = plans.shape
    b = resolve_backend(backend, P * K, num_shards=num_shards)
    if b == "numpy":
        return _score_numpy(times, counts, plans, alpha, beta,
                            time_scale, fairness_scale, delta_fairness)
    # Variance is shift-invariant: center counts once in f64 so the f32
    # backends never cancel two large sums (exact parity at fleet scale,
    # where cumulative counts grow without bound).
    counts_c = counts.astype(np.float64) - float(np.mean(counts))
    if num_shards and num_shards > 1:
        from repro.core import shard

        stats = shard.plan_stats_sharded(times, counts_c, plans, "dense",
                                         num_shards)
        return _score_from_stats(stats, counts_c, alpha, beta,
                                 time_scale, fairness_scale, delta_fairness)
    if b == "jax":
        import jax.numpy as jnp

        fn = _jax_score_fn(bool(delta_fairness))
        # int8 plan mirrors (plans.indices_to_plans(..., dtype=np.int8))
        # pass through without another (P, K) materialization.
        p8 = plans if plans.dtype == np.int8 else plans.astype(np.int8)
        out = fn(jnp.asarray(times, jnp.float32),
                 jnp.asarray(counts_c, jnp.float32),
                 jnp.asarray(p8),
                 jnp.float32(alpha), jnp.float32(beta),
                 jnp.float32(time_scale), jnp.float32(fairness_scale))
        return np.asarray(out, dtype=np.float64)
    # pallas (resolve_backend already verified the TPU backend)
    stats = plan_stats_pallas(times, counts_c, plans)
    return _score_from_stats(stats, counts_c, alpha, beta,
                             time_scale, fairness_scale, delta_fairness)


def score_plan_indices(times: np.ndarray, counts: np.ndarray,
                       idx: np.ndarray, alpha: float = 1.0, beta: float = 1.0,
                       time_scale: float = 1.0, fairness_scale: float = 1.0,
                       delta_fairness: bool = True,
                       backend: Optional[str] = None,
                       num_shards: int = 1) -> np.ndarray:
    """Score P candidate plans given in INDEX form: (P, n_sel) device ids.

    The fleet fast path: the vectorized candidate generators
    (``plans.random_plan_indices``, Gumbel top-k) produce exactly this shape
    before any dense scatter, and scoring it is P*n_sel gathered elements
    instead of a P*K dense sweep — the difference between ~2 and ~2000 ms
    at K=100k, P=4096. Semantically identical to ``score_plans`` on the
    scattered dense plans (each row selects its n_sel ids exactly once).
    ``num_shards > 1`` shards the fleet axis: each shard owns a K/N block
    of devices and masks the gather to the ids it owns.
    """
    times = np.asarray(times)
    counts = np.asarray(counts)
    idx = np.asarray(idx)
    if idx.ndim == 1:
        idx = idx[None, :]
    P, S = idx.shape
    K = counts.shape[0]
    if S == 0:
        if delta_fairness:
            return np.zeros(P, dtype=np.float64)
        return np.full(P, beta * float(np.var(counts)) / fairness_scale)
    b = resolve_backend(backend, P * S, form="index", num_shards=num_shards)
    if b == "numpy":
        t = times[idx].max(axis=1) / time_scale
        w = 2.0 * counts + 1.0
        wsum = w[idx].sum(axis=1)
        c1 = float(np.sum(counts))
        if delta_fairness:
            f = wsum / K - (2.0 * c1 * S + S * S) / (K * K)
        else:
            c2 = float(np.sum(np.square(counts, dtype=np.float64)))
            f = (c2 + wsum) / K - ((c1 + S) / K) ** 2
        return alpha * t + beta * f / fairness_scale
    import jax.numpy as jnp

    counts_c = counts.astype(np.float64) - float(np.mean(counts))
    if num_shards and num_shards > 1:
        from repro.core import shard

        stats = shard.plan_stats_sharded(times, counts_c, idx, "index",
                                         num_shards)
        return _score_from_stats(stats, counts_c, alpha, beta,
                                 time_scale, fairness_scale, delta_fairness)
    if b == "pallas":
        # The kernel streams dense int8 tiles: scatter the rows to them.
        # (``auto`` never picks this — the index gather is far cheaper.)
        from repro.core.plans import indices_to_plans

        stats = plan_stats_pallas(times, counts_c,
                                  indices_to_plans(idx, K, dtype=np.int8))
        return _score_from_stats(stats, counts_c, alpha, beta,
                                 time_scale, fairness_scale, delta_fairness)
    fn = _jax_score_idx_fn(bool(delta_fairness))
    out = fn(jnp.asarray(times, jnp.float32),
             jnp.asarray(counts_c, jnp.float32),
             jnp.asarray(idx.astype(np.int32)),
             jnp.float32(alpha), jnp.float32(beta),
             jnp.float32(time_scale), jnp.float32(fairness_scale))
    return np.asarray(out, dtype=np.float64)


def plan_stats_pallas(times: np.ndarray, counts: np.ndarray,
                      plans: np.ndarray, interpret: bool = False) -> np.ndarray:
    """Run the tiled Pallas reduction; (P, 3) [max_t, n_sel, sum(2c+1)]."""
    import jax.numpy as jnp

    from repro.kernels.sched_score import plan_stats

    w = 2.0 * np.asarray(counts, np.float32) + 1.0
    out = plan_stats(jnp.asarray(times, jnp.float32), jnp.asarray(w),
                     jnp.asarray(np.asarray(plans).astype(np.int8)),
                     interpret=interpret)
    return np.asarray(out)


def score_plans_pallas_interpret(times, counts, plans, alpha=1.0, beta=1.0,
                                 time_scale=1.0, fairness_scale=1.0,
                                 delta_fairness=True) -> np.ndarray:
    """Interpreter-mode Pallas scoring — the CPU validation path used by
    tests/test_scoring.py (TPU Pallas does not lower on the CPU backend)."""
    plans = np.asarray(plans)
    if plans.ndim == 1:
        plans = plans[None, :]
    stats = plan_stats_pallas(times, counts, plans, interpret=True)
    return _score_from_stats(stats, np.asarray(counts), alpha, beta,
                             time_scale, fairness_scale, delta_fairness)


def round_time_batch(times: np.ndarray, plans: np.ndarray,
                     backend: Optional[str] = None) -> np.ndarray:
    """(P,) Formula-3 round time (masked max; empty plan -> 0)."""
    times = np.asarray(times)
    plans = np.asarray(plans)
    if plans.ndim == 1:
        plans = plans[None, :]
    b = resolve_backend(backend, plans.size)
    if b == "numpy":
        masked = np.where(plans.astype(bool), times[None, :], -np.inf)
        out = masked.max(axis=1)
        return np.where(np.isfinite(out), out, 0.0)
    import jax.numpy as jnp

    fn = _jax_round_time_fn()
    out = fn(jnp.asarray(times, jnp.float32),
             jnp.asarray(plans.astype(np.int8)))
    return np.asarray(out, dtype=np.float64)


def fairness_batch(counts: np.ndarray, plans: np.ndarray,
                   delta_fairness: bool = False,
                   backend: Optional[str] = None) -> np.ndarray:
    """(P,) Formula-5 fairness (variance of counts + plan; optionally the
    per-round increment Var(c+v) - Var(c))."""
    counts = np.asarray(counts)
    plans = np.asarray(plans)
    if plans.ndim == 1:
        plans = plans[None, :]
    b = resolve_backend(backend, plans.size)
    if b == "numpy":
        f = np.var(counts[None, :] + plans, axis=1)
        if delta_fairness:
            f = f - np.var(counts)
        return f
    # Dedicated sum-only reduction (no wasted masked-max pass).
    import jax.numpy as jnp

    counts_c = counts.astype(np.float64) - float(np.mean(counts))
    fn = _jax_fairness_fn(bool(delta_fairness))
    out = fn(jnp.asarray(counts_c, jnp.float32),
             jnp.asarray(plans.astype(np.int8)))
    return np.asarray(out, dtype=np.float64)

"""Fused on-device scheduler search: the plan-SEARCH loops, jitted.

PR 2 made plan *evaluation* fast (one batched scoring call under every
scheduler); this module makes the *search* around it fast. The host
searchers step one proposal at a time through Python — SA performs
``steps`` sequential cost calls per decision, the GA repairs and mutates
children in per-individual loops — so at fleet scale (K = 1e4+) scheduler
decision latency dominates round time. Here the full loops run as jitted
``lax.scan`` programs:

- ``sa_search``   — C parallel simulated-annealing chains stepped under one
  ``lax.scan``: plans carried in INDEX form ((C, n_sel) device ids — the
  scoring core's fleet fast path, so each step is n_sel gathers instead of
  a K-wide sweep), swap/accept noise PRE-DRAWN on the host (the scan body
  contains zero PRNG), masked one-selected-for-one-free swaps, geometric
  cooling, running per-chain best, best-of-chains result. One jitted call
  per decision instead of ``steps`` host round-trips.
- ``ga_search``   — generations under ``lax.scan`` with vmapped tournament
  selection, slot-wise uniform crossover on the index form (each slot
  flips a coin to adopt the other parent's device at that slot, gated so
  only devices absent from this parent are adopted — children are
  duplicate-free and exactly ``n_sel``-sized by construction, so no
  repair/sort step runs mid-loop), swap mutation, elitism.
- ``bods_acquire`` — the full BODS acquisition (candidate generation:
  random + structured Gumbel-top-k over availability logits in-graph,
  plus host-prepared local-search mutants of the best observed plan run
  through the vectorized in-graph repair; featurization phi(V);
  Matern-5/2 GP posterior + Expected Improvement; argmax) in ONE jitted
  call per decision. ``ei_scores_jobs`` vmaps the GP posterior over the
  job axis so all M jobs' candidate sets score in one call.

Conventions shared with ``repro.core.scoring``: times/counts are float32
on device, counts are mean-centered in float64 on the host first (variance
is shift-invariant; centering keeps f32 cancellation-free), a plan is a
(K,) bool row with exactly ``n_sel`` True entries inside ``available`` —
equivalently an (n_sel,) row of distinct available device ids. Every
jitted builder is keyed on its STATIC shape knobs via ``lru_cache`` (the
per-experiment set is tiny: one compile per (steps, chains, n_sel)).

Both fused population inits seed one slot with the greedy plan (the
``n_sel`` fastest available devices — a standard memetic warm start): at a
matched evaluation budget the fused searchers then dominate the host path
on chosen-plan cost, which ``benchmarks/bench_sched.py`` gates on.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional, Tuple

import numpy as np

from repro.core.plans import plan_from_indices
from repro.monitoring.trace import enabled as trace_enabled
from repro.monitoring.trace import span

logger = logging.getLogger(__name__)


def _usable_search_shards(num_shards, rows: int, pairs: bool = False) -> int:
    """Shard count a fused searcher can actually use for ``rows`` parallel
    units (SA chains / GA population / BODS candidates): falls back to the
    single lane when the CPU platform lacks devices, when ``rows`` does not
    split evenly, or (``pairs``) when the per-shard block would break the
    GA's consecutive-pair crossover. Falling back changes NOTHING but the
    partitioning — the single-lane program is the num_shards=1 special
    case of the same math. On an accelerator, more shards than chips
    raise (``shard.check_shard_capacity``)."""
    from repro.core import shard

    n = int(num_shards or 1)
    if n <= 1:
        return 1
    reason = None
    if not shard.check_shard_capacity(n):
        reason = (f"num_shards={n} exceeds jax.device_count(); "
                  "launch via repro.launch.bootstrap to size the "
                  "host platform")
    if reason is None and rows % n:
        reason = f"{rows} search rows do not split across {n} shards"
    if reason is None and pairs and (rows // n) % 2:
        reason = (f"per-shard block {rows // n} is odd (pair crossover "
                  "needs even blocks)")
    if reason is not None:
        logger.debug("fused search falling back to single lane: %s", reason)
        return 1
    return n

# ---- traced building blocks ---------------------------------------------


def _fairness_from_stats(counts_c, n, wsum, delta_fairness: bool):
    """Formula-5 fairness from the centered sufficient statistics — the
    ONE copy of the variance expansion inside this module (shared by the
    dense/index cost paths and the BODS featurization; semantics identical
    to ``scoring._jax_score_fn``). ``n``: (P,) selected counts; ``wsum``:
    (P,) sums of 2*counts_c+1 over the selection."""
    import jax.numpy as jnp

    K = float(counts_c.shape[-1])
    c1 = jnp.sum(counts_c)
    if delta_fairness:
        return wsum / K - (2.0 * c1 * n + n * n) / (K * K)
    c2 = jnp.sum(counts_c * counts_c)
    return (c2 + wsum) / K - ((c1 + n) / K) ** 2


def _dense_stats(times, counts_c, plans):
    """(P, K) bool plans -> (round time t, n selected, wsum) — the masked
    max + fairness sufficient statistics, one pass."""
    import jax.numpy as jnp

    masked = jnp.where(plans, times, -jnp.inf)
    t = jnp.max(masked, axis=-1)
    t = jnp.where(jnp.isfinite(t), t, 0.0)
    w = 2.0 * counts_c + 1.0
    n = jnp.sum(plans, axis=-1).astype(jnp.float32)
    wsum = jnp.sum(jnp.where(plans, w, 0.0), axis=-1)
    return t, n, wsum


def plan_costs(times, counts_c, plans, alpha, beta, ts, fs,
               delta_fairness: bool):
    """(P, K) bool plans -> (P,) Formula-2 costs. Traced (safe under
    jit/vmap/scan); semantics identical to ``scoring._jax_score_fn``.
    ``counts_c`` must be mean-centered."""
    t, n, wsum = _dense_stats(times, counts_c, plans)
    f = _fairness_from_stats(counts_c, n, wsum, delta_fairness)
    return alpha * t / ts + beta * f / fs


def plan_costs_idx(times, counts_c, idx, alpha, beta, ts, fs,
                   delta_fairness: bool):
    """(P, n_sel) device-id plans -> (P,) Formula-2 costs (the index fast
    path: n_sel gathers per plan, never a K-wide sweep). Rows must hold
    distinct ids. Semantics identical to ``scoring._jax_score_idx_fn``."""
    import jax.numpy as jnp

    n = float(idx.shape[-1])
    t = jnp.max(times[idx], axis=-1)
    w = 2.0 * counts_c + 1.0
    wsum = jnp.sum(w[idx], axis=-1)
    f = _fairness_from_stats(counts_c, n, wsum, delta_fairness)
    return alpha * t / ts + beta * f / fs


def _gumbel_plans(key, logits, avail, n_sel: int):
    """(P, K) logits -> (P, K) bool plans: Gumbel top-k over the available
    set (the in-graph twin of ``plans.gumbel_topk_plans``)."""
    import jax
    import jax.numpy as jnp

    g = jnp.where(avail[None, :], logits + jax.random.gumbel(key, logits.shape),
                  -jnp.inf)
    _, idx = jax.lax.top_k(g, n_sel)
    plans = jnp.zeros(logits.shape, bool)
    plans = plans.at[jnp.arange(logits.shape[0])[:, None], idx].set(True)
    return plans & avail[None, :]


def repair_plans_jax(key, plans, avail, n_sel: int):
    """In-graph vectorized repair — jax twin of ``plans.repair_plans``.

    Priority top-k: valid selections keep rank over everything else (key
    1 + noise vs noise), occupied devices are masked out, noise tie-breaks
    pick the random extras to drop / random available devices to add.
    Idempotent on valid plans. Precondition: ``avail.sum() >= n_sel``.
    """
    import jax
    import jax.numpy as jnp

    keys = (plans & avail[None, :]) + jax.random.uniform(key, plans.shape)
    keys = jnp.where(avail[None, :], keys, -jnp.inf)
    _, idx = jax.lax.top_k(keys, n_sel)
    out = jnp.zeros(plans.shape, bool)
    out = out.at[jnp.arange(plans.shape[0])[:, None], idx].set(True)
    return out & avail[None, :]


def _swap_into(idx, pos, cand):
    """Propose ``idx[row, pos[row]] = cand[row]`` per row, masked where
    ``cand`` already sits in the row (a swap must introduce a NEW device).
    Returns (proposal, moved_mask)."""
    import jax.numpy as jnp

    collision = jnp.any(idx == cand[:, None], axis=-1)
    rows = jnp.arange(idx.shape[0])
    nxt = idx.at[rows, pos].set(cand)
    moved = ~collision
    return jnp.where(moved[:, None], nxt, idx), moved


def _greedy_indices(times: np.ndarray, avail_idx: np.ndarray,
                    n_sel: int) -> np.ndarray:
    """Host helper: ids of the n_sel fastest available devices."""
    t_av = times[avail_idx]
    cut = np.argpartition(t_av, n_sel - 1)[:n_sel]
    return avail_idx[cut].astype(np.int32)


def _init_indices(rng: np.random.Generator, avail_idx: np.ndarray,
                  n_sel: int, rows: int) -> np.ndarray:
    """``rows`` random n_sel-subsets of the available set: strided windows
    of ONE permutation at random offsets — O(A + rows * n_sel) instead of
    ``random_plan_indices``'s O(rows * A) per-row key draw (8 ms vs 0.1 ms
    at A = 8000, rows = 32). Uniform marginals, distinct-within-row; rows
    are windows of the same permutation, which for a population INIT is
    diversity-preserving (near-disjoint coverage of the pool)."""
    A = avail_idx.size
    perm = rng.permutation(A)
    offs = rng.integers(0, A, rows)
    pos = (offs[:, None] + np.arange(n_sel)[None, :]) % A
    return avail_idx[perm[pos]].astype(np.int32)


def _swap_noise(rng: np.random.Generator, avail_idx: np.ndarray,
                steps: int, rows: int, n_sel: int):
    """Pre-drawn swap/accept noise for ``steps`` scan iterations: the slot
    to vacate, the available device to propose (collisions with the current
    selection mask the move on-device), and the Metropolis uniform."""
    pos = rng.integers(0, n_sel, (steps, rows)).astype(np.int32)
    cand = avail_idx[rng.integers(0, avail_idx.size, (steps, rows))]
    u = rng.random((steps, rows)).astype(np.float32)
    return pos, cand.astype(np.int32), u


def _center(counts: np.ndarray) -> np.ndarray:
    counts = np.asarray(counts, dtype=np.float64)
    return (counts - float(counts.mean())).astype(np.float32)


def _check_avail(avail_idx: np.ndarray, n_sel: int) -> None:
    if avail_idx.size < n_sel:
        raise ValueError(
            f"need {n_sel} available devices, have {avail_idx.size}")


# ---- (a) batched multi-chain simulated annealing -------------------------


@functools.lru_cache(maxsize=None)
def _sa_fn(steps: int, chains: int, n_sel: int, delta_fairness: bool,
           num_shards: int = 1):
    import jax
    import jax.numpy as jnp

    def chains_run(init_idx, times, counts_c, pos, cand, accept_u,
                   alpha, beta, ts, fs, t0, cooling):
        # Anneal a block of chains; per-chain bests are returned so the
        # cross-chain argmin can run OUTSIDE the (possibly sharded) body.
        # Chains never interact mid-anneal, so partitioning this body over
        # the chain axis is bitwise-identical to the single lane.
        costs = plan_costs_idx(times, counts_c, init_idx, alpha, beta, ts,
                               fs, delta_fairness)

        def body(carry, xs):
            idx, costs, best_i, best_c, temp = carry
            pos_t, cand_t, u = xs
            nxt, moved = _swap_into(idx, pos_t, cand_t)
            nxt_cost = plan_costs_idx(times, counts_c, nxt, alpha, beta,
                                      ts, fs, delta_fairness)
            dc = nxt_cost - costs
            # Clamped Metropolis exponent: pathological cost spikes (huge
            # |dc| / tiny temp) stay finite instead of overflowing exp.
            acc_p = jnp.exp(jnp.clip(-dc / jnp.maximum(temp, 1e-9),
                                     -60.0, 0.0))
            accept = moved & ((dc < 0.0) | (u < acc_p))
            idx = jnp.where(accept[:, None], nxt, idx)
            costs = jnp.where(accept, nxt_cost, costs)
            better = costs < best_c
            best_i = jnp.where(better[:, None], idx, best_i)
            best_c = jnp.where(better, costs, best_c)
            # Cooling advances even on masked (collision / no-free-device)
            # steps, so the schedule stays consistent across chains and
            # with the host path's skip semantics.
            return (idx, costs, best_i, best_c, temp * cooling), None

        carry0 = (init_idx, costs, init_idx, costs, t0)
        (_, _, best_i, best_c, _), _ = jax.lax.scan(
            body, carry0, (pos, cand, accept_u))
        return best_i, best_c

    if num_shards > 1:
        from jax.sharding import PartitionSpec as P

        from repro.core.shard import fleet_mesh

        chains_run = jax.shard_map(
            chains_run, mesh=fleet_mesh(num_shards),
            in_specs=(P("fleet", None), P(None), P(None),
                      P(None, "fleet"), P(None, "fleet"), P(None, "fleet"),
                      P(), P(), P(), P(), P(), P()),
            out_specs=(P("fleet", None), P("fleet")),
            check_vma=False)

    # Named for the searcher, so the device trace reads ``jit_sa_search``.
    def sa_search(*args):
        best_i, best_c = chains_run(*args)
        ci = jnp.argmin(best_c)
        return best_i[ci], best_c[ci]

    return jax.jit(sa_search)


def sa_search(rng: np.random.Generator, times: np.ndarray, counts: np.ndarray,
              available: np.ndarray, n_sel: int, *, alpha: float, beta: float,
              time_scale: float, fairness_scale: float, delta_fairness: bool,
              steps: int, chains: int, t0: float, cooling: float,
              greedy_seed: bool = True,
              avail_idx: Optional[np.ndarray] = None,
              num_shards: int = 1) -> np.ndarray:
    """One fused multi-chain SA decision -> (K,) bool plan.

    ``chains`` plans anneal in parallel for ``steps`` scan iterations
    (``chains * steps`` cost evaluations in ONE jitted call); the best plan
    any chain ever visited is returned. All randomness is pre-drawn from
    ``rng`` on the host, so decisions are reproducible under the
    scheduler's seed and the scan body is PRNG-free. With ``num_shards``
    > 1 the chain axis partitions across host platform devices
    (bitwise-identical result: chains are independent and the noise is
    host-drawn once, regardless of shard count).
    """
    import jax.numpy as jnp

    avail = np.asarray(available, dtype=bool)
    if avail_idx is None:
        avail_idx = np.flatnonzero(avail)
    _check_avail(avail_idx, n_sel)
    init = _init_indices(rng, avail_idx, n_sel, chains)
    if greedy_seed:
        init[0] = _greedy_indices(np.asarray(times), avail_idx, n_sel)
    pos, cand, u = _swap_noise(rng, avail_idx, steps, chains, n_sel)
    shards = _usable_search_shards(num_shards, chains)
    fn = _sa_fn(int(steps), int(chains), int(n_sel), bool(delta_fairness),
                shards)
    with span("sa_search", chains=int(chains), steps=int(steps),
              shards=shards):
        best_idx, _ = fn(jnp.asarray(init), jnp.asarray(times, jnp.float32),
                         jnp.asarray(_center(counts)), jnp.asarray(pos),
                         jnp.asarray(cand), jnp.asarray(u),
                         jnp.float32(alpha), jnp.float32(beta),
                         jnp.float32(time_scale), jnp.float32(fairness_scale),
                         jnp.float32(t0), jnp.float32(cooling))
        plan = plan_from_indices(avail.shape[0], np.asarray(best_idx))
    return plan


# ---- (b) fused genetic algorithm -----------------------------------------


def _ga_children_block(pop, cost, ta, tb, cu_l, mu_l, mpos_l, mcand_l,
                       off, rows, n_sel: int, mutation_rate):
    """Rows ``[off, off + rows)`` of the next GA generation, computed from
    the FULL (P, S) population and (P,) costs but only the LOCAL slices of
    the crossover/mutation noise. The single lane calls this with
    ``off=0, rows=P``; the sharded executor calls it per shard with an even
    block so consecutive parent pairs never straddle shards — either way
    the math below is the same ops in the same order.

    Tournament selection (size 2) runs on the full index arrays (O(P*S),
    cheap) and the block is carved out of the parents; the expensive
    O(rows * S^2) membership matrices only ever see the local block.

    Slot-wise uniform crossover between consecutive parent pairs:
    slot j of a child takes the OTHER parent's j-th device iff
    the coin says swap and that device is a single (absent from
    this parent) — entries adopted from the other parent are
    then distinct from every kept entry, so children stay
    duplicate-free and exactly n_sel-sized with no repair/sort
    step (``lax.top_k`` costs ~1 ms/call on CPU and would
    dominate the loop). Unlike the host GA's bitwise crossover
    + repair, a shared device CAN be dropped when its slot swaps
    to a single — a deliberate trade for the sort-free form; the
    parity gate measures the outcome, not the operator. The two
    children use complementary coins, mirroring the host GA's
    shared crossover mask.
    """
    import jax
    import jax.numpy as jnp

    parents = jnp.where((cost[ta] <= cost[tb])[:, None], pop[ta], pop[tb])
    par_l = jax.lax.dynamic_slice_in_dim(parents, off, rows, 0)
    pairs = rows // 2
    p0, p1 = par_l[0:2 * pairs:2], par_l[1:2 * pairs:2]
    m0 = jnp.any(p0[:, :, None] == p1[:, None, :], axis=-1)
    m1 = jnp.any(p1[:, :, None] == p0[:, None, :], axis=-1)
    swap = cu_l < 0.5
    c0 = jnp.where(swap & ~m1, p1, p0)
    c1 = jnp.where(~swap & ~m0, p0, p1)
    children = jnp.stack([c0, c1], axis=1).reshape(2 * pairs, n_sel)
    if rows != 2 * pairs:  # odd block: last parent passes through
        children = jnp.concatenate([children, par_l[-1:]])
    # Mutation: swap one selected device for one free device.
    swapped, moved = _swap_into(children, mpos_l, mcand_l)
    apply = (mu_l < mutation_rate) & moved
    return jnp.where(apply[:, None], swapped, children)


@functools.lru_cache(maxsize=None)
def _ga_fn(population: int, generations: int, n_sel: int,
           delta_fairness: bool, num_shards: int = 1):
    import jax
    import jax.numpy as jnp

    P = population
    half = P // 2
    S = n_sel
    N = num_shards
    Pb = P // N  # rows this shard owns (P itself when unsharded)

    # Both executors are named for the searcher, so the device trace reads
    # ``jit_ga_search``.
    def ga_search(init_idx, times, counts_c, tourn_a, tourn_b, cross_u,
                  mut_u, mut_pos, mut_cand, alpha, beta, ts, fs,
                  mutation_rate):
        def body(carry, xs):
            pop, best_i, best_c = carry
            ta, tb, cu, mu, mpos, mcand = xs
            cost = plan_costs_idx(times, counts_c, pop, alpha, beta, ts,
                                  fs, delta_fairness)
            i = jnp.argmin(cost)
            better = cost[i] < best_c
            best_i = jnp.where(better, pop[i], best_i)
            best_c = jnp.where(better, cost[i], best_c)
            children = _ga_children_block(pop, cost, ta, tb, cu, mu, mpos,
                                          mcand, 0, P, S, mutation_rate)
            # Elitism: the best plan seen so far survives in slot 0.
            children = children.at[0].set(best_i)
            return (children, best_i, best_c), None

        carry0 = (init_idx, init_idx[0], jnp.float32(jnp.inf))
        (pop, best_i, best_c), _ = jax.lax.scan(
            body, carry0,
            (tourn_a, tourn_b, cross_u, mut_u, mut_pos, mut_cand))
        cost = plan_costs_idx(times, counts_c, pop, alpha, beta, ts, fs,
                              delta_fairness)
        i = jnp.argmin(cost)
        better = cost[i] < best_c
        return (jnp.where(better, pop[i], best_i),
                jnp.where(better, cost[i], best_c))

    if N == 1:
        return jax.jit(ga_search)

    # Data-parallel sharded executor: each shard scores and breeds its own
    # Pb-row block, with one tiled ``all_gather`` of (population, cost) per
    # generation so tournament selection and elitism see the GLOBAL state —
    # the recombination trajectory is exactly the single lane's. Noise
    # arrays stay replicated; each shard slices its rows (pair noise at
    # off/2 since crossover coins are drawn per PAIR).
    def run_shard(init_idx, times, counts_c, tourn_a, tourn_b, cross_u,
                  mut_u, mut_pos, mut_cand, alpha, beta, ts, fs,
                  mutation_rate):
        sid = jax.lax.axis_index("fleet")
        off = sid * Pb

        def body(carry, xs):
            pop_l, best_i, best_c = carry
            ta, tb, cu, mu, mpos, mcand = xs
            cost_l = plan_costs_idx(times, counts_c, pop_l, alpha, beta,
                                    ts, fs, delta_fairness)
            pop = jax.lax.all_gather(pop_l, "fleet", tiled=True)
            cost = jax.lax.all_gather(cost_l, "fleet", tiled=True)
            i = jnp.argmin(cost)
            better = cost[i] < best_c
            best_i = jnp.where(better, pop[i], best_i)
            best_c = jnp.where(better, cost[i], best_c)
            cu_l = jax.lax.dynamic_slice_in_dim(cu, sid * (Pb // 2),
                                                Pb // 2, 0)
            mu_l = jax.lax.dynamic_slice_in_dim(mu, off, Pb, 0)
            mpos_l = jax.lax.dynamic_slice_in_dim(mpos, off, Pb, 0)
            mcand_l = jax.lax.dynamic_slice_in_dim(mcand, off, Pb, 0)
            children = _ga_children_block(pop, cost, ta, tb, cu_l, mu_l,
                                          mpos_l, mcand_l, off, Pb, S,
                                          mutation_rate)
            # Elitism lives in GLOBAL slot 0, i.e. shard 0's local slot 0.
            children = children.at[0].set(
                jnp.where(sid == 0, best_i, children[0]))
            return (children, best_i, best_c), None

        carry0 = (init_idx, init_idx[0], jnp.float32(jnp.inf))
        (pop_l, best_i, best_c), _ = jax.lax.scan(
            body, carry0,
            (tourn_a, tourn_b, cross_u, mut_u, mut_pos, mut_cand))
        cost_l = plan_costs_idx(times, counts_c, pop_l, alpha, beta, ts,
                                fs, delta_fairness)
        pop = jax.lax.all_gather(pop_l, "fleet", tiled=True)
        cost = jax.lax.all_gather(cost_l, "fleet", tiled=True)
        i = jnp.argmin(cost)
        better = cost[i] < best_c
        return (jnp.where(better, pop[i], best_i)[None],
                jnp.where(better, cost[i], best_c)[None])

    from jax.sharding import PartitionSpec as Psp

    from repro.core.shard import fleet_mesh

    rep = Psp()
    sharded = jax.shard_map(
        run_shard, mesh=fleet_mesh(N),
        in_specs=(Psp("fleet", None), rep, rep, rep, rep, rep,
                  rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(Psp("fleet", None), Psp("fleet")),
        check_vma=False)

    def ga_search(*args):
        best_i, best_c = sharded(*args)
        # Every shard returns the same global best; row 0 is canonical.
        return best_i[0], best_c[0]

    return jax.jit(ga_search)


def ga_search(rng: np.random.Generator, times: np.ndarray, counts: np.ndarray,
              available: np.ndarray, n_sel: int, *, alpha: float, beta: float,
              time_scale: float, fairness_scale: float, delta_fairness: bool,
              population: int, generations: int, mutation_rate: float,
              greedy_seed: bool = True,
              avail_idx: Optional[np.ndarray] = None,
              num_shards: int = 1) -> np.ndarray:
    """One fused GA decision -> (K,) bool plan (all generations in ONE
    jitted ``lax.scan`` call; index-form population, pre-drawn noise).
    With ``num_shards`` > 1 the population breeds data-parallel across
    host platform devices (same trajectory: per-generation all_gather
    keeps selection/elitism global, noise is host-drawn once)."""
    import jax.numpy as jnp

    avail = np.asarray(available, dtype=bool)
    if avail_idx is None:
        avail_idx = np.flatnonzero(avail)
    _check_avail(avail_idx, n_sel)
    P, G = population, generations
    init = _init_indices(rng, avail_idx, n_sel, P)
    if greedy_seed:
        init[0] = _greedy_indices(np.asarray(times), avail_idx, n_sel)
    tourn = rng.integers(0, P, (2, G, P)).astype(np.int32)
    half = P // 2
    cross_u = rng.random((G, half, n_sel)).astype(np.float32)
    mut_u = rng.random((G, P)).astype(np.float32)
    mut_pos, mut_cand, _ = _swap_noise(rng, avail_idx, G, P, n_sel)
    shards = _usable_search_shards(num_shards, P, pairs=True)
    fn = _ga_fn(int(P), int(G), int(n_sel), bool(delta_fairness), shards)
    with span("ga_search", population=int(P), generations=int(G),
              shards=shards):
        best_idx, _ = fn(jnp.asarray(init), jnp.asarray(times, jnp.float32),
                         jnp.asarray(_center(counts)), jnp.asarray(tourn[0]),
                         jnp.asarray(tourn[1]), jnp.asarray(cross_u),
                         jnp.asarray(mut_u),
                         jnp.asarray(mut_pos), jnp.asarray(mut_cand),
                         jnp.float32(alpha), jnp.float32(beta),
                         jnp.float32(time_scale), jnp.float32(fairness_scale),
                         jnp.float32(mutation_rate))
        plan = plan_from_indices(avail.shape[0], np.asarray(best_idx))
    return plan


# ---- (c) batched BODS acquisition ----------------------------------------


def _matern52(sq):
    import jax.numpy as jnp

    r = jnp.sqrt(jnp.maximum(sq, 1e-12))
    return (1.0 + jnp.sqrt(5.0) * r + 5.0 * sq / 3.0) * jnp.exp(-jnp.sqrt(5.0) * r)


def gp_fit(F, resid, valid, noise):
    """Masked Matern-5/2 GP fit over the observation ring: returns the
    Cholesky factor, the dual weights ``K_nn^-1 (resid * m)``, and the
    float mask ``m``. Split out of ``ei_scores`` so the sharded BODS
    acquisition can fit ONCE per shard and score only its local candidate
    block against it."""
    import jax
    import jax.numpy as jnp

    m = valid.astype(jnp.float32)
    mm = m[:, None] * m[None, :]
    d_nn = jnp.sum((F[:, None, :] - F[None, :, :]) ** 2, -1)
    K_nn = _matern52(d_nn) * mm + (1.0 - mm) * jnp.eye(F.shape[0])
    K_nn = K_nn + (noise + 1e-6) * jnp.eye(F.shape[0])
    chol = jnp.linalg.cholesky(K_nn)
    w = jax.scipy.linalg.cho_solve((chol, True), resid * m)
    return chol, w, m


def gp_posterior(chol, w, m, F, cand_feats, cand_est):
    """Posterior (mean, stddev) of a candidate block under a ``gp_fit``
    model; the prior mean enters through ``cand_est``."""
    import jax
    import jax.numpy as jnp

    d_nc = jnp.sum((F[:, None, :] - cand_feats[None, :, :]) ** 2, -1)
    K_nc = _matern52(d_nc) * m[:, None]
    mu_c = cand_est + K_nc.T @ w              # posterior mean, candidates
    v = jax.scipy.linalg.solve_triangular(chol, K_nc, lower=True)
    var = jnp.maximum(1.0 - jnp.sum(v * v, axis=0), 1e-9)
    return mu_c, jnp.sqrt(var)


def ei_from_posterior(mu_c, sigma, best):
    """Expected Improvement of each candidate against incumbent ``best``
    (a plugin incumbent: pass ``jnp.min(mu_c)`` — or, sharded, the pmin
    over every shard's ``mu_c`` so all shards improve against the same
    global incumbent)."""
    import jax

    z = (best - mu_c) / sigma
    cdf = jax.scipy.stats.norm.cdf(z)
    pdf = jax.scipy.stats.norm.pdf(z)
    return (best - mu_c) * cdf + sigma * pdf


def ei_scores(F, resid, valid, cand_feats, cand_est, noise):
    """Expected Improvement under the masked Matern-5/2 GP posterior.

    Traced core shared by the host BODS scheduler (which jits it directly),
    the fused acquisition below (which inlines it into one decision graph),
    and ``ei_scores_jobs`` (which vmaps it over the job axis). Composed
    from ``gp_fit`` / ``gp_posterior`` / ``ei_from_posterior`` above (the
    sharded acquisition uses the pieces directly). See
    ``schedulers/bods.py`` for the modelling rationale (residual GP over a
    low-dimensional feature map, plugin incumbent within the round; the
    prior mean enters through ``cand_est``, so the observations' own
    estimates never appear here).

    F: (L, d) observed features; resid: (L,) realized-estimated residuals
    (normalized); valid: (L,) ring mask; cand_feats: (P, d);
    cand_est: (P,) estimated candidate costs (same normalization as
    ``resid``). Returns (P,) EI (higher = better).
    """
    import jax.numpy as jnp

    chol, w, m = gp_fit(F, resid, valid, noise)
    mu_c, sigma = gp_posterior(chol, w, m, F, cand_feats, cand_est)
    # WITHIN-ROUND plugin incumbent (see bods.py): the best posterior-mean
    # candidate of THIS round; EI arbitrates exploitation vs exploration
    # among the current feasible set.
    return ei_from_posterior(mu_c, sigma, jnp.min(mu_c))


@functools.lru_cache(maxsize=None)
def _ei_scores_jobs_fn():
    import jax

    return jax.jit(jax.vmap(ei_scores, in_axes=(0, 0, 0, 0, 0, None)))


def ei_scores_jobs(F, resid, valid, cand_feats, cand_est, noise):
    """EI for ALL M jobs in one call: every argument except ``noise`` gains
    a leading (M,) axis (each job's observation ring + candidate set); the
    Matern-GP posterior is vmapped over jobs instead of looped in Python.
    Returns (M, P) EI scores."""
    import jax.numpy as jnp

    return _ei_scores_jobs_fn()(
        jnp.asarray(F), jnp.asarray(resid),
        jnp.asarray(valid), jnp.asarray(cand_feats), jnp.asarray(cand_est),
        jnp.asarray(noise, jnp.float32))


def _norm01_traced(x, mask):
    """Traced twin of ``bods._norm01``: [0, 1]-normalize by the spread over
    ``mask``; a flat (or empty) reference set yields all-zeros, never NaN."""
    import jax.numpy as jnp

    lo = jnp.min(jnp.where(mask, x, jnp.inf))
    hi = jnp.max(jnp.where(mask, x, -jnp.inf))
    spread = hi - lo
    ok = jnp.isfinite(spread) & (spread >= 1e-9)
    safe = jnp.where(ok, spread, 1.0)
    return jnp.where(ok, jnp.clip((x - lo) / safe, 0.0, 1.0), 0.0)


def featurize_plans(times, counts_c, counts_zero, mu, plans, ts, fs,
                    n_sel: int, delta_fairness: bool):
    """Traced phi(V): (P, K) plans -> (P, 6) features, formula-for-formula
    the host ``BODSScheduler._featurize`` (est round time, fairness
    increment, mean selected time, capability-jitter exposure, novelty,
    occupancy — all O(1)-normalized). Also returns the normalized time and
    fairness terms so the caller can assemble Formula-2 estimates without
    a second pass."""
    import jax.numpy as jnp

    K = plans.shape[1]
    sel_t = jnp.where(plans, times, 0.0)
    t, n, wsum = _dense_stats(times, counts_c, plans)
    est_time = t / ts
    dfair = _fairness_from_stats(counts_c, n, wsum, delta_fairness) / fs
    nn = jnp.maximum(n, 1.0)
    mean_t = jnp.sum(sel_t, axis=1) / nn / ts
    jitter = jnp.max(
        jnp.where(plans, times / jnp.maximum(mu, 1e-9), 0.0), axis=1) / ts
    novelty = jnp.sum(plans & counts_zero[None, :], axis=1) / max(n_sel, 1)
    occupancy = n / float(K)
    feats = jnp.stack([est_time, dfair, mean_t, jitter, novelty, occupancy],
                      axis=1).astype(jnp.float32)
    return feats, est_time, dfair


# The acquisition body's 17 inputs in argument order, each with its word
# type in the packed buffer (``_BodsLayout``).
_BODS_FIELDS = (
    ("seed", "u32"), ("times", "f32"), ("counts_c", "f32"),
    ("counts_zero", "bool"), ("avail", "bool"), ("mu", "f32"),
    ("mutants", "bool"), ("use_base", "bool"), ("F", "f32"),
    ("resid", "f32"), ("valid", "f32"), ("inv_sd", "f32"), ("alpha", "f32"),
    ("beta", "f32"), ("ts", "f32"), ("fs", "f32"), ("noise", "f32"))


class _BodsLayout(NamedTuple):
    """Where each of the 17 inputs of a fused BODS decision sits in the one
    uint32 buffer that carries them to the device: ``(name, start, end,
    shape, kind)`` word slices in argument order, and the buffer's
    length."""

    fields: Tuple[Tuple[str, int, int, Tuple[int, ...], str], ...]
    words: int

    def pack(self, *values) -> np.ndarray:
        """Host side: the 17 values as one contiguous uint32 buffer. Float
        fields are rounded to float32 (as ``jnp.asarray(v, jnp.float32)``
        rounds) and stored bit-for-bit; booleans become 0/1 words; the seed
        is one word."""
        buf = np.empty(self.words, np.uint32)
        as_f32 = buf.view(np.float32)
        for (_, lo, hi, _, kind), v in zip(self.fields, values, strict=True):
            (as_f32 if kind == "f32" else buf)[lo:hi] = np.ravel(v)
        return buf

    def unpack(self, packed):
        """Traced inverse of ``pack``: static slices of the device buffer,
        floats recovered by bitcast, booleans as ``!= 0``."""
        import jax
        import jax.numpy as jnp

        as_f32 = jax.lax.bitcast_convert_type(packed, jnp.float32)
        out = []
        for _, lo, hi, shape, kind in self.fields:
            v = as_f32[lo:hi] if kind == "f32" else packed[lo:hi]
            out.append((v != 0 if kind == "bool" else v).reshape(shape))
        return tuple(out)


@functools.lru_cache(maxsize=None)
def _bods_layout(K: int, L: int, d: int, n_mut: int) -> _BodsLayout:
    """The packed layout for K devices, an L-long observation ring of d
    features, and ``n_mut`` local-search mutants."""
    shapes = ((), (K,), (K,), (K,), (K,), (K,), (n_mut, K), (), (L, d),
              (L,), (L,)) + ((),) * 6
    fields, lo = [], 0
    for shape, (name, kind) in zip(shapes, _BODS_FIELDS):
        hi = lo + int(np.prod(shape, dtype=np.int64))
        fields.append((name, lo, hi, shape, kind))
        lo = hi
    return _BodsLayout(tuple(fields), lo)


_RADIX_BITS = 4  # key bits a radix-select pass settles; 32 // it passes


def _topk_mask(keys, k: int):
    """(B, K) float32 -> (B, K) bool: each row's ``k`` largest keys, the
    mask ``jax.lax.top_k`` and a scatter of its indices give, without a
    sort.

    Keys map to order-preserving uint32 words (negatives have every bit
    flipped, the rest the sign bit set, so ``-inf`` is lowest and ``-0.0``
    sits just below ``+0.0``). A radix select then settles each row's
    k-th largest word ``T`` from the top bit down, ``_RADIX_BITS`` at a
    time: a pass counts the words at or above each candidate prefix and
    keeps the highest prefix that still counts ``k``. The passes are fixed
    in number, whatever the data. The mask is every word above ``T`` and,
    of the words equal to ``T``, the lowest-index ``k - count(above)``:
    ``top_k``'s tie rule. The ranking of ties runs only when some row
    holds more ties at ``T`` than it takes: it settles the index of each
    row's last tie taken from the top bit down, one counting pass a bit.
    Keys hold no NaN."""
    import jax
    import jax.numpy as jnp

    B, K = keys.shape
    bits = jax.lax.bitcast_convert_type(keys, jnp.uint32)
    # Drawn once: the passes below must not each recompute the keys.
    words = jax.lax.optimization_barrier(
        jnp.where(bits >> 31 != 0, ~bits, bits | jnp.uint32(1 << 31)))
    digits = jnp.arange(1, 1 << _RADIX_BITS, dtype=jnp.uint32)

    def settle(i, prefix):
        shift = (32 - _RADIX_BITS * (i + 1)).astype(jnp.uint32)
        cand = prefix[:, None] | (digits[None, :] << shift)        # (B, D)
        at_least = jnp.sum(words[:, :, None] >= cand[:, None, :], axis=1,
                           dtype=jnp.int32)                        # (B, D)
        digit = jnp.sum(at_least >= k, axis=1, dtype=jnp.uint32)
        return prefix | (digit << shift)

    T = jax.lax.fori_loop(0, 32 // _RADIX_BITS, settle,
                          jnp.zeros((B,), jnp.uint32))[:, None]
    above, tied = words > T, words == T
    take = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    spare = jnp.sum(tied, axis=1, dtype=jnp.int32) > take

    def rank_ties():
        # The highest index below which fewer than ``take`` ties lie is
        # the index of the last tie taken.
        idx = jnp.arange(K, dtype=jnp.int32)
        top = max(K - 1, 1).bit_length()

        def settle_index(i, last):
            cand = last | (1 << (top - 1 - i))
            fewer = jnp.sum(tied & (idx < cand[:, None]), axis=1,
                            dtype=jnp.int32) < take
            return jnp.where(fewer, cand, last)

        last = jax.lax.fori_loop(0, top, settle_index,
                                 jnp.zeros((B,), jnp.int32))[:, None]
        return above | (tied & (idx <= last) & (take[:, None] > 0))

    return jax.lax.cond(jnp.any(spare), rank_ties, lambda: above | tied)


def _bods_candidates(seed, ids, times, counts_c, avail, mutants, use_base, *,
                     n_rand: int, n_mut: int, n_sel: int, local_search: bool):
    """(B,) global candidate ids -> (B, K) bool plans, the n_sel devices of
    each candidate.

    PRNG is PER CANDIDATE (``fold_in`` of the decision seed by candidate
    id), so the candidate set is a pure function of (seed, id), invariant
    to how the candidate axis is partitioned across shards. Threefry keys
    on purpose: the fast ``rbg`` impl draws DIFFERENT bits for the same key
    under different vmap batch sizes, which would make the candidate set
    depend on the shard count. Layout matches the host path: ids
    [0, n_rand) uniform Gumbel top-k, the rest structured
    (availability-logit) Gumbel top-k, and when local search is armed and
    ``use_base`` holds, ids [0, n_mut) become repaired mutants of the
    incumbent.

    A plan is selected from its float32 keys (Gumbel-perturbed logits,
    ``-inf`` where unavailable) by ``_topk_mask``: order-preserving uint32
    words, a radix select of the n_sel-th largest and ``top_k``'s tie rule
    (lowest index first), so the plans are those of ``top_k`` bit for bit,
    without a sort. The (B, K) keys are drawn once, before the select's
    passes read them.
    Only the mutant rows are repaired: the head ``min(n_mut, B)`` rows of a
    block hold every id below n_mut, and a block that owns none (a later
    shard) skips the repair."""
    import jax
    import jax.numpy as jnp

    K = times.shape[0]
    t_norm = _norm01_traced(times, avail)
    c_norm = _norm01_traced(counts_c, jnp.ones(K, bool))
    base_key = jax.random.key(seed)

    def gumbel_keys(cid):
        kg, kw1, kw2, _ = jax.random.split(jax.random.fold_in(base_key, cid),
                                           4)
        w_time = jax.random.uniform(kw1, (), minval=0.0, maxval=6.0)
        w_fair = jax.random.uniform(kw2, (), minval=0.0, maxval=4.0)
        logits = jnp.where(cid >= n_rand,
                           -w_time * t_norm - w_fair * c_norm, 0.0)
        return jnp.where(avail, logits + jax.random.gumbel(kg, (K,)),
                         -jnp.inf)

    plans = _topk_mask(jax.vmap(gumbel_keys)(ids), n_sel) & avail
    head = ids[:min(n_mut, ids.shape[0])]
    if not local_search or head.shape[0] == 0:
        return plans

    def repair(plans):
        # Row-wise twin of ``repair_plans_jax`` on each mutant of the best
        # observed plan.
        def repair_keys(cid):
            kr = jax.random.split(jax.random.fold_in(base_key, cid), 4)[3]
            mut = mutants[jnp.minimum(cid, n_mut - 1)]
            return jnp.where(avail, (mut & avail) +
                             jax.random.uniform(kr, (K,)), -jnp.inf)

        rplans = _topk_mask(jax.vmap(repair_keys)(head), n_sel) & avail
        keep = plans[:head.shape[0]]
        return plans.at[:head.shape[0]].set(
            jnp.where((head < n_mut)[:, None], rplans, keep))

    return jax.lax.cond(use_base & (head[0] < n_mut), repair,
                        lambda plans: plans, plans)


def _bods_body(num_candidates: int, n_mut: int, n_sel: int,
               delta_fairness: bool, local_search: bool, num_shards: int = 1):
    """The acquisition over its 17 unpacked inputs (traced, not jitted):
    the whole decision for one lane, or with ``num_shards`` > 1 one shard's
    block, to run under ``shard_map`` on the ``fleet`` axis."""
    import jax
    import jax.numpy as jnp

    P = num_candidates
    n_rand = P // 4
    N = num_shards
    Pb = P // N  # candidates this shard owns (P itself when unsharded)

    def block(seed, ids, times, counts_c, counts_zero, avail, mu, mutants,
              use_base, F, resid, valid, inv_sd, alpha, beta, ts, fs,
              noise):
        """One candidate block end-to-end: generation, featurization, GP
        posterior. Returns (plans, est cost, posterior mean, stddev)."""
        cands = _bods_candidates(seed, ids, times, counts_c, avail, mutants,
                                 use_base, n_rand=n_rand, n_mut=n_mut,
                                 n_sel=n_sel, local_search=local_search)
        feats, est_time, dfair = featurize_plans(
            times, counts_c, counts_zero, mu, cands, ts, fs, n_sel,
            delta_fairness)
        cand_est = alpha * est_time + beta * dfair
        chol, w, m = gp_fit(F, resid, valid, noise)
        mu_c, sigma = gp_posterior(chol, w, m, F, feats, cand_est * inv_sd)
        return cands, cand_est, mu_c, sigma

    if N == 1:
        def acquire(seed, times, counts_c, counts_zero, avail, mu, mutants,
                    use_base, F, resid, valid, inv_sd, alpha, beta, ts, fs,
                    noise):
            ids = jnp.arange(P, dtype=jnp.int32)
            cands, cand_est, mu_c, sigma = block(
                seed, ids, times, counts_c, counts_zero, avail, mu, mutants,
                use_base, F, resid, valid, inv_sd, alpha, beta, ts, fs,
                noise)
            ei = ei_from_posterior(mu_c, sigma, jnp.min(mu_c))
            choice = jnp.argmax(ei)
            return cands[choice], cand_est[choice], ei[choice]

        return acquire

    # Candidate-axis sharding: each shard generates/featurizes/scores its
    # own Pb candidates (the per-candidate PRNG keeps the candidate SET
    # identical to the single lane), the plugin incumbent is the pmin of
    # posterior means across shards, and each shard emits its local EI
    # winner for a tiny host-side final argmax.
    def run_shard(seed, times, counts_c, counts_zero, avail, mu, mutants,
                  use_base, F, resid, valid, inv_sd, alpha, beta, ts, fs,
                  noise):
        sid = jax.lax.axis_index("fleet")
        ids = sid * Pb + jnp.arange(Pb, dtype=jnp.int32)
        cands, cand_est, mu_c, sigma = block(
            seed, ids, times, counts_c, counts_zero, avail, mu, mutants,
            use_base, F, resid, valid, inv_sd, alpha, beta, ts, fs, noise)
        best = jax.lax.pmin(jnp.min(mu_c), "fleet")
        ei = ei_from_posterior(mu_c, sigma, best)
        c = jnp.argmax(ei)
        return cands[c][None], cand_est[c][None], ei[c][None], ids[c][None]

    return run_shard


@functools.lru_cache(maxsize=None)
def _bods_fn(num_candidates: int, n_mut: int, n_sel: int,
             delta_fairness: bool, local_search: bool, num_shards: int,
             layout: _BodsLayout):
    """The jitted decision over ONE packed input buffer (``layout``):
    unpack in-graph, then run ``_bods_body``."""
    import jax
    import jax.numpy as jnp

    body = _bods_body(num_candidates, n_mut, n_sel, delta_fairness,
                      local_search, num_shards)

    # Both executors are named for the searcher, so the device trace reads
    # ``jit_bods_acquire``.
    if num_shards == 1:
        def bods_acquire(packed):
            return body(*layout.unpack(packed))

        return jax.jit(bods_acquire)

    from jax.sharding import PartitionSpec as Psp

    from repro.core.shard import fleet_mesh

    sharded = jax.shard_map(
        lambda packed: body(*layout.unpack(packed)),
        mesh=fleet_mesh(num_shards), in_specs=(Psp(),),
        out_specs=(Psp("fleet", None), Psp("fleet"), Psp("fleet"),
                   Psp("fleet")),
        check_vma=False)

    def bods_acquire(packed):
        plans, ests, eis, gids = sharded(packed)
        # Max EI wins; ties break to the LOWEST global candidate id,
        # matching the single lane's first-argmax semantics.
        order = jnp.where(eis == jnp.max(eis), gids,
                          jnp.iinfo(jnp.int32).max)
        wi = jnp.argmin(order)
        return plans[wi], ests[wi], eis[wi]

    return jax.jit(bods_acquire)


def _mutate_plan_host(rng: np.random.Generator, base: np.ndarray,
                      n_mut: int) -> np.ndarray:
    """Host twin of the BODS local-search proposal: n_mut copies of
    ``base``, each with 1-3 selected-for-unselected swaps (identical to the
    host scheduler's mutation loop; availability is restored in-graph by
    the vectorized repair)."""
    K = base.shape[0]
    mutants = np.broadcast_to(base, (n_mut, K)).copy()
    for i in range(n_mut):
        flips = rng.integers(1, 4)
        on, off = np.flatnonzero(mutants[i]), np.flatnonzero(~mutants[i])
        for _ in range(flips):
            if on.size and off.size:
                mutants[i][rng.choice(on)] = False
                mutants[i][rng.choice(off)] = True
    return mutants


def bods_acquire(rng: np.random.Generator, times: np.ndarray,
                 counts: np.ndarray, available: np.ndarray, mu: np.ndarray,
                 n_sel: int, *, F: np.ndarray, y: np.ndarray,
                 est: np.ndarray, valid: np.ndarray,
                 base_plan: Optional[np.ndarray], alpha: float, beta: float,
                 time_scale: float, fairness_scale: float,
                 delta_fairness: bool, num_candidates: int, n_mut: int,
                 local_search: bool, gp_noise: float,
                 avail_idx: Optional[np.ndarray] = None,
                 num_shards: int = 1) -> Tuple[np.ndarray, float]:
    """One fused BODS decision: (chosen (K,) bool plan, its estimated cost).

    Candidate generation, featurization, GP posterior and EI argmax run in
    one jitted call; only the observation-ring slicing, the residual
    normalization and the tiny local-search mutant loop stay on the host.
    The in-graph Gumbel draws use PER-CANDIDATE threefry keys folded from
    one decision seed (the (P, K) noise block is the one unavoidable
    K-wide draw in this module), so with ``num_shards`` > 1 the candidate
    axis partitions across host platform devices without changing the
    candidate set. Each candidate's plan is its n_sel largest keys, found
    without a sort (``_topk_mask``): the float32 keys map to
    order-preserving uint32 words, a radix select of fixed passes settles
    the n_sel-th largest, and ties there go to the lowest device index, as
    ``lax.top_k`` breaks them. Only the ``n_mut`` mutant rows are repaired,
    and only with a base plan; the ``bods_acquire`` span records ``select``
    and the ``repair_rows`` that ran.

    The decision's inputs cross to the device as ONE packed buffer of
    uint32 words (``_bods_layout``), in the body's argument order: the
    seed (one word); times, centred counts (float32); the zero-count mask,
    availability (0/1 words); mu (float32); the (n_mut, K) mutants and the
    ``use_base`` flag (0/1 words); the ring's (L, d) features F, its
    normalised residuals and valid mask (float32); then ``1/sd``, alpha,
    beta, time scale, fairness scale and GP noise (float32). Floats are
    rounded to float32 on the host and stored bit-for-bit; the program
    slices the buffer at static offsets, bitcasts the float words back and
    reads booleans as ``!= 0``. Every field is packed afresh each decision.

    Traced, the decision splits into ``bods_prepare`` (host work before the
    call; it counts the fleet's ``k`` devices and the ``available`` ones,
    and holds ``bods_mutate``, the host mutant draw) and, inside
    ``bods_acquire``, ``bods_stage`` (``bods_pack``, the packing, then
    ``bods_put``, the one host-to-device transfer), ``bods_launch`` (the
    dispatch), ``bods_wait`` (the device work; only when tracing, since the
    read-back blocks at the same point anyway) and ``bods_readback`` (the
    plan and its estimate; the EI check reads once more after the span).
    """
    import jax

    tracing = trace_enabled()
    use_base = base_plan is not None and local_search
    with span("bods_prepare",
              mutants=int(n_mut) if use_base else 0) as prepare:
        avail = np.asarray(available, dtype=bool)
        if avail_idx is None:
            avail_idx = np.flatnonzero(avail)
        prepare.annotate(k=int(avail.shape[0]), available=int(avail_idx.size))
        _check_avail(avail_idx, n_sel)
        sd = float(y[valid > 0].std()) + 1e-6 if valid.sum() else 1.0
        if use_base:
            with span("bods_mutate", k=int(avail.shape[0]),
                      mutants=int(n_mut)):
                mutants = _mutate_plan_host(
                    rng, np.asarray(base_plan, dtype=bool), n_mut)
        else:
            mutants = np.zeros((n_mut, avail.shape[0]), dtype=bool)
        shards = _usable_search_shards(num_shards, num_candidates)
        layout = _bods_layout(avail.shape[0], *np.shape(F), int(n_mut))
        fn = _bods_fn(int(num_candidates), int(n_mut), int(n_sel),
                      bool(delta_fairness), bool(local_search), shards,
                      layout)
        seed = int(rng.integers(0, 2**31 - 1))
    with span("bods_acquire", candidates=int(num_candidates),
              mutants=int(n_mut), shards=shards, select="radix",
              repair_rows=int(n_mut) if use_base else 0):
        with span("bods_stage") as stage:
            with span("bods_pack", words=layout.words):
                buf = layout.pack(
                    seed, times, _center(counts), np.asarray(counts) == 0,
                    avail, mu, mutants, use_base, F, (y - est) / sd * valid,
                    valid, 1.0 / sd, alpha, beta, time_scale, fairness_scale,
                    gp_noise)
            with span("bods_put", bytes=buf.nbytes):
                packed = jax.device_put(buf)
            if tracing:
                stage.annotate(arrays=1, bytes=packed.nbytes)
        with span("bods_launch"):
            plan, cand_est, ei = fn(packed)
        if tracing:
            with span("bods_wait"):
                jax.block_until_ready((plan, cand_est, ei))
        with span("bods_readback", what="plan", reads=2):
            out = np.asarray(plan), float(cand_est)
    with span("bods_readback", what="ei", reads=1):
        ei = float(ei)
    if not np.isfinite(ei):
        # argmax over a NaN posterior silently picks candidate 0.
        raise FloatingPointError(
            f"BODS acquisition: non-finite EI {ei} for the chosen "
            "candidate (GP posterior broke down)")
    return out

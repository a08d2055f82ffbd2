"""Tiled Pallas kernel for the fleet-scale plan-scoring reduction.

Scores P candidate scheduling plans over K devices in one pass (the inner
loop of every scheduler in this repo — Formula 2 = alpha * masked-max round
time + beta * fairness-variance increment). The (P, K) problem is tiled
(BLOCK_P, BLOCK_K); the kernel accumulates three sufficient statistics per
plan across the K grid dimension:

  col 0:  max_{k in V} t_k          (Formula 3, running max)
  col 1:  |V| = sum_k v_k           (selected count)
  col 2:  sum_{k in V} (2 c_k + 1)  (fairness increment numerator)

because the Formula-5 variance terms reduce exactly:

  sum(s)  = sum(c) + |V|                      with s = c + v, v in {0,1}
  sum(s²) = sum(c²) + sum_{k in V} (2 c_k + 1)

so Var(s) (and the delta form Var(s) - Var(c)) are closed-form in the three
per-plan accumulators plus two scalars of ``counts`` — no (P, K) float
intermediate ever exists. The cheap (P,)-sized cost combine runs in plain
jnp after the kernel (see ``repro.core.scoring``).

Plans stream through as int8 tiles (the natural layout for 100k-device
pools: a (4096, 100k) candidate set is 0.4 GB as int8, 1.6 GB as f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
STATS_LANES = 128  # output lane width (TPU tile); cols 0..2 carry the stats


def _score_kernel(times_ref, w_ref, plans_ref, stats_ref):
    k_idx = pl.program_id(1)
    # Widen before the compare: Mosaic cannot relayout an i1 mask built
    # straight from an int8 tile, so the mask comes from an int32 tile.
    p = plans_ref[...].astype(jnp.int32) != 0     # (BP, BK) bool
    t = times_ref[...].astype(jnp.float32)        # (1, BK)
    w = w_ref[...].astype(jnp.float32)            # (1, BK)

    tile_max = jnp.max(jnp.where(p, t, NEG_INF), axis=1)   # (BP,)
    tile_n = jnp.sum(jnp.where(p, 1.0, 0.0), axis=1)
    tile_w = jnp.sum(jnp.where(p, w, 0.0), axis=1)

    col = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape, 1)
    new = jnp.where(col == 0, tile_max[:, None],
                    jnp.where(col == 1, tile_n[:, None],
                              jnp.where(col == 2, tile_w[:, None], 0.0)))

    @pl.when(k_idx == 0)
    def _():
        stats_ref[...] = jnp.where(col == 0, NEG_INF, 0.0)

    old = stats_ref[...]
    stats_ref[...] = jnp.where(col == 0, jnp.maximum(old, new), old + new)


@functools.partial(jax.jit,
                   static_argnames=("block_p", "block_k", "interpret"))
def plan_stats(times: jnp.ndarray, weights: jnp.ndarray, plans: jnp.ndarray,
               block_p: int = 256, block_k: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """(K,) times, (K,) weights, (P, K) int8/bool plans -> (P, 3) f32 stats.

    stats[:, 0] = masked max time (NEG_INF for empty plans)
    stats[:, 1] = selected count
    stats[:, 2] = sum of weights over selected
    """
    P, K = plans.shape
    bp = min(block_p, max(8, P))
    bk = min(block_k, max(128, K))
    pad_p = (-P) % bp
    pad_k = (-K) % bk
    plans8 = plans.astype(jnp.int8)
    if pad_p or pad_k:
        plans8 = jnp.pad(plans8, ((0, pad_p), (0, pad_k)))
    t2 = times.astype(jnp.float32).reshape(1, K)
    w2 = weights.astype(jnp.float32).reshape(1, K)
    if pad_k:
        t2 = jnp.pad(t2, ((0, 0), (0, pad_k)))
        w2 = jnp.pad(w2, ((0, 0), (0, pad_k)))
    grid = (plans8.shape[0] // bp, plans8.shape[1] // bk)
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk), lambda i, k: (0, k)),
            pl.BlockSpec((1, bk), lambda i, k: (0, k)),
            pl.BlockSpec((bp, bk), lambda i, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bp, STATS_LANES), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((plans8.shape[0], STATS_LANES),
                                       jnp.float32),
        interpret=interpret,
    )(t2, w2, plans8)
    return out[:P, :3]

"""Fleet-axis sharding: million-device plan scoring across host devices.

The scoring core (``repro.core.scoring``) and the fused searchers
(``repro.core.search``) are single-lane jit programs; they top out around
K = 1e5 devices because every reduction walks the whole fleet axis on one
device. This module shards the FLEET (K) axis across the process's
devices — the chips of a TPU host, or forced CPU host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — see
``repro.launch.bootstrap``) — with ``shard_map``:

- **Scoring** (``plan_stats_sharded``) — each shard reduces its K/N block
  of the fleet to the per-plan sufficient statistics of Formula 2
  (masked-max round time, selected count, sum of fairness weights); the
  cross-shard combine is an O(N * P) max/sum over those partials, finished
  on the host in float64 by ``scoring._score_from_stats`` — the same
  combine the Pallas kernel path uses. Works on both plan forms: dense
  (P, K) membership and (P, n_sel) index rows (each shard owns the ids in
  ``[lo, lo + K/N)`` and masks the rest of the gather).
- **Plan repair / candidate generation** (``repair_plans_sharded``,
  ``random_plan_indices_sharded``, ``gumbel_topk_indices_sharded``) —
  shard-local priority top-k over the shard's block (noise drawn in-graph
  per shard), then a cross-shard top-k MERGE selects the global ``n_sel``:
  the global top-k of a row is always contained in the union of its
  per-shard top-k's.

Every sharded program has two executors with identical shard-local math:

- ``shard_map`` — the real thing, one program per mesh device (requires
  ``num_shards <= jax.device_count()``);
- ``emulate``  — the same blocked computation as a ``vmap`` over a
  reshaped leading shard axis on ONE device.

``executor="auto"`` picks ``shard_map`` when the process has enough
devices. On the CPU platform it emulates otherwise, so ``num_shards=8``
produces the same numbers on a laptop (serially) and on an 8-device host
platform (in parallel); on an accelerator, more shards than chips raise.
Tests exploit this: emulated parity runs in-process anywhere; a
subprocess test with forced host devices pins shard_map-vs-emulated
agreement.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

VALID_EXECUTORS = ("auto", "shard_map", "emulate")


def resolve_num_shards(num_shards, fleet_size: Optional[int] = None) -> int:
    """Normalize the ``num_shards`` knob to a concrete shard count.

    ``None``/``1`` -> 1 (single lane, no jax import); ``0`` or ``"auto"``
    -> ``jax.device_count()`` (the host-platform device pool the launch
    bootstrap sized). ``fleet_size`` caps the count so no shard is ever
    empty.
    """
    if num_shards is None:
        return 1
    if num_shards == "auto" or num_shards == 0:
        import jax

        n = int(jax.device_count())
    else:
        n = int(num_shards)
    if n < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards!r}")
    if fleet_size is not None:
        n = min(n, max(int(fleet_size), 1))
    return n


def shard_capacity() -> int:
    """Shard counts up to this run under the real ``shard_map`` executor."""
    import jax

    return int(jax.device_count())


def check_shard_capacity(num_shards: int) -> bool:
    """True when ``num_shards`` fit the process's devices. On an
    accelerator, more shards than chips raise: emulating them serially on
    one chip would hide a mis-sized launch. The CPU platform emulates."""
    import jax

    if num_shards <= shard_capacity():
        return True
    if jax.default_backend() != "cpu":
        raise ValueError(
            f"num_shards={num_shards} exceeds the {shard_capacity()} "
            f"{jax.default_backend()} device(s) of this process")
    return False


def _resolve_executor(executor: str, num_shards: int) -> str:
    if executor not in VALID_EXECUTORS:
        raise ValueError(f"executor {executor!r} not in {VALID_EXECUTORS}")
    if executor != "auto":
        return executor
    return "shard_map" if check_shard_capacity(num_shards) else "emulate"


def shard_sizes(K: int, num_shards: int) -> Tuple[int, int]:
    """(per-shard block size Kb, padded fleet size Kb * num_shards)."""
    Kb = -(-int(K) // int(num_shards))
    return Kb, Kb * int(num_shards)


@functools.lru_cache(maxsize=None)
def fleet_mesh(num_shards: int):
    """The 1-axis ``("fleet",)`` mesh over the first ``num_shards`` devices
    (cached — mesh identity matters for jit cache hits)."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(
            f"num_shards={num_shards} exceeds jax.device_count()="
            f"{len(devs)}; launch with repro.launch.bootstrap or use the "
            "emulate executor")
    return Mesh(np.asarray(devs[:num_shards]), ("fleet",))


# ---- shard-local sufficient statistics (Formula 2) ----------------------
#
# Shared bodies: the SAME function runs per mesh device under shard_map and
# per reshaped block under vmap emulation, so the two executors produce
# identical float32 partials. The combine (max/sum over the N partials)
# happens on the host in float64 either way.


def _partial_stats_dense(times_b, w_b, plans_b):
    """One shard's block: (Kb,) times, (Kb,) fairness weights, (P, Kb)
    membership -> (P, 3) [masked-max t, n selected, wsum]."""
    import jax.numpy as jnp

    sel = plans_b != 0
    t = jnp.max(jnp.where(sel, times_b[None, :], -jnp.inf), axis=1)
    n = jnp.sum(sel, axis=1).astype(jnp.float32)
    wsum = jnp.sum(jnp.where(sel, w_b[None, :], 0.0), axis=1)
    return jnp.stack([t, n, wsum], axis=1)


def _partial_stats_index(times_b, w_b, idx, lo):
    """Index-form twin: (P, n_sel) GLOBAL device ids against the shard's
    ``[lo, lo + Kb)`` block — out-of-block ids are masked, in-block ids
    gather through the clipped relative offset."""
    import jax.numpy as jnp

    Kb = times_b.shape[0]
    rel = idx - lo
    own = (rel >= 0) & (rel < Kb)
    relc = jnp.clip(rel, 0, Kb - 1)
    t = jnp.max(jnp.where(own, times_b[relc], -jnp.inf), axis=1)
    n = jnp.sum(own, axis=1).astype(jnp.float32)
    wsum = jnp.sum(jnp.where(own, w_b[relc], 0.0), axis=1)
    return jnp.stack([t, n, wsum], axis=1)


@functools.lru_cache(maxsize=None)
def _stats_fn(num_shards: int, form: str, executor: str):
    import jax
    import jax.numpy as jnp

    N = num_shards
    if executor == "shard_map":
        from jax.sharding import PartitionSpec as P

        mesh = fleet_mesh(N)
        if form == "dense":
            def body(times_b, w_b, plans_b):
                return _partial_stats_dense(times_b, w_b, plans_b)[None]

            fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("fleet"), P("fleet"), P(None, "fleet")),
                           out_specs=P("fleet", None, None))
        else:
            def body(times_b, w_b, idx):
                lo = jax.lax.axis_index("fleet") * times_b.shape[0]
                return _partial_stats_index(times_b, w_b, idx, lo)[None]

            fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("fleet"), P("fleet"), P(None, None)),
                           out_specs=P("fleet", None, None))
        return jax.jit(fn)

    if form == "dense":
        def run(times, w, plans):
            Kb = times.shape[0] // N
            tb = times.reshape(N, Kb)
            wb = w.reshape(N, Kb)
            pb = plans.reshape(plans.shape[0], N, Kb).transpose(1, 0, 2)
            return jax.vmap(_partial_stats_dense)(tb, wb, pb)
    else:
        def run(times, w, idx):
            Kb = times.shape[0] // N
            tb = times.reshape(N, Kb)
            wb = w.reshape(N, Kb)
            lo = (jnp.arange(N, dtype=idx.dtype) * Kb)
            return jax.vmap(_partial_stats_index,
                            in_axes=(0, 0, None, 0))(tb, wb, idx, lo)
    return jax.jit(run)


def plan_stats_sharded(times: np.ndarray, counts_c: np.ndarray, plans,
                       form: str, num_shards: int,
                       executor: str = "auto") -> np.ndarray:
    """Sharded Formula-2 sufficient statistics: (P, 3) [t_max, n, wsum].

    ``counts_c`` must be mean-centered (the scoring core's convention);
    ``plans`` is (P, K) membership when ``form == "dense"``, (P, n_sel)
    global device ids when ``form == "index"``. Feed the result to
    ``scoring._score_from_stats`` — exactly the Pallas kernel contract.
    """
    import jax.numpy as jnp

    N = int(num_shards)
    ex = _resolve_executor(executor, N)
    times = np.asarray(times)
    K = times.shape[0]
    Kb, Kpad = shard_sizes(K, N)
    t32 = np.asarray(times, np.float32)
    w32 = (2.0 * np.asarray(counts_c, np.float64) + 1.0).astype(np.float32)
    if Kpad != K:
        t32 = np.pad(t32, (0, Kpad - K))
        w32 = np.pad(w32, (0, Kpad - K))
    if form == "dense":
        p = np.asarray(plans)
        p8 = p if p.dtype == np.int8 else p.astype(np.int8)
        if Kpad != K:  # padded devices are never selected
            p8 = np.pad(p8, ((0, 0), (0, Kpad - K)))
        parts = _stats_fn(N, "dense", ex)(
            jnp.asarray(t32), jnp.asarray(w32), jnp.asarray(p8))
    elif form == "index":
        idx = np.asarray(plans)
        i32 = idx if idx.dtype == np.int32 else idx.astype(np.int32)
        parts = _stats_fn(N, "index", ex)(
            jnp.asarray(t32), jnp.asarray(w32), jnp.asarray(i32))
    else:
        raise ValueError(f"form {form!r} not in ('dense', 'index')")
    parts = np.asarray(parts, np.float64)          # (N, P, 3)
    return np.stack([parts[:, :, 0].max(axis=0),   # round time: max of maxes
                     parts[:, :, 1].sum(axis=0),   # n selected: sum
                     parts[:, :, 2].sum(axis=0)],  # wsum: sum
                    axis=1)


# ---- shard-local top-k with cross-shard merge ---------------------------
#
# The repair / candidate-generation primitives are all one shape: build a
# (P, K) priority-key matrix (valid selections outrank noise outranks
# occupied), take each row's top n_sel. Sharded, each shard draws ITS
# block's noise in-graph (key folded with the shard id), takes a local
# top-k, and the merge takes the top n_sel of the N stacked local winners
# — correct because a row's global top-k is contained in the union of its
# per-shard top-k's. Note the noise REALIZATION depends on the shard
# count (each block has its own fold_in stream): results are valid draws
# from the same distribution at any N, but not bit-identical across N.

_MODES = ("repair", "random", "gumbel")


@functools.lru_cache(maxsize=None)
def _noisy_topk_fn(num_shards: int, n_sel: int, executor: str, mode: str,
                   rows: int = 0):
    """``mode="random"`` takes no (P, K) operand at all: the key matrix is
    drawn in-graph per shard at the static ``rows`` count, so a
    million-device candidate draw never materializes a (P, K) host array
    (the single-lane ``plans.random_plan_indices`` allocates the full
    matrix). ``repair``/``gumbel`` carry one (P, K) operand (membership /
    logits) split across shards."""
    import jax
    import jax.numpy as jnp

    N = num_shards

    def local_keys(seed, sid, avail_b, mat_b):
        k = jax.random.fold_in(jax.random.key(seed, impl="rbg"), sid)
        if mode == "repair":
            keys = ((mat_b & avail_b[None, :])
                    + jax.random.uniform(k, mat_b.shape))
        elif mode == "random":
            keys = jax.random.uniform(k, (rows, avail_b.shape[0]))
        else:  # gumbel
            keys = mat_b + jax.random.gumbel(k, mat_b.shape)
        return jnp.where(avail_b[None, :], keys, -jnp.inf)

    def local_topk(keys_b, lo):
        Kb = keys_b.shape[1]
        m = min(n_sel, Kb)
        v, i = jax.lax.top_k(keys_b, m)
        gi = (i + lo).astype(jnp.int32)
        if m < n_sel:
            v = jnp.pad(v, ((0, 0), (0, n_sel - m)),
                        constant_values=-np.inf)
            gi = jnp.pad(gi, ((0, 0), (0, n_sel - m)))
        return v, gi

    def body(seed, sid, lo, avail_b, mat_b):
        keys = local_keys(seed, sid, avail_b, mat_b)
        v, gi = local_topk(keys, lo)
        return v, gi

    if executor == "shard_map":
        from jax.sharding import PartitionSpec as P

        mesh = fleet_mesh(N)

        def sm_body(seed, avail_b, mat_b):
            sid = jax.lax.axis_index("fleet")
            lo = sid * avail_b.shape[0]
            v, gi = body(seed, sid, lo, avail_b, mat_b)
            return v[None], gi[None]

        mat_spec = P() if mode == "random" else P(None, "fleet")
        inner = jax.shard_map(
            sm_body, mesh=mesh,
            in_specs=(P(), P("fleet"), mat_spec),
            out_specs=(P("fleet", None, None), P("fleet", None, None)))
    else:
        def inner(seed, avail, mat):
            Kb = avail.shape[0] // N
            ab = avail.reshape(N, Kb)
            sids = jnp.arange(N, dtype=jnp.int32)
            los = sids * Kb
            if mode == "random":
                mb, mat_ax = mat, None
            else:
                mb = mat.reshape(mat.shape[0], N, Kb).transpose(1, 0, 2)
                mat_ax = 0
            return jax.vmap(body, in_axes=(None, 0, 0, 0, mat_ax))(
                seed, sids, los, ab, mb)

    def run(seed, avail, mat):
        v, gi = inner(seed, avail, mat)                # (N, P, n_sel) x2
        P_ = v.shape[1]
        vm = v.transpose(1, 0, 2).reshape(P_, N * n_sel)
        gm = gi.transpose(1, 0, 2).reshape(P_, N * n_sel)
        _, pick = jax.lax.top_k(vm, n_sel)
        return jnp.take_along_axis(gm, pick, axis=1)

    return jax.jit(run)


def _topk_call(mode: str, seed: int, avail: np.ndarray, n_sel: int,
               num_shards: int, executor: str, mat=None,
               rows: Optional[int] = None) -> np.ndarray:
    import jax.numpy as jnp

    N = int(num_shards)
    ex = _resolve_executor(executor, N)
    avail = np.asarray(avail, dtype=bool)
    K = avail.shape[0]
    if int(avail.sum()) < n_sel:
        raise ValueError(
            f"need {n_sel} available devices, have {int(avail.sum())}")
    Kb, Kpad = shard_sizes(K, N)
    a = np.pad(avail, (0, Kpad - K)) if Kpad != K else avail
    seed32 = jnp.uint32(seed & 0xFFFFFFFF)
    if mode == "random":
        fn = _noisy_topk_fn(N, int(n_sel), ex, mode, rows=int(rows))
        out = fn(seed32, jnp.asarray(a), None)
    else:
        mat = np.asarray(mat, dtype=bool if mode == "repair" else np.float32)
        if Kpad != K:
            mat = np.pad(mat, ((0, 0), (0, Kpad - K)))
        fn = _noisy_topk_fn(N, int(n_sel), ex, mode)
        out = fn(seed32, jnp.asarray(a), jnp.asarray(mat))
    return np.asarray(out)


def repair_plans_sharded(rng: np.random.Generator, plans: np.ndarray,
                         available: np.ndarray, n_sel: int, num_shards: int,
                         executor: str = "auto") -> np.ndarray:
    """Fleet-sharded twin of ``plans.repair_plans``: (P, K) candidates ->
    (P, n_sel) repaired GLOBAL indices via shard-local priority top-k +
    cross-shard merge. Valid selections (selected & available) always
    outrank noise, so already-valid plans pass through unchanged (as a
    set); occupied devices are dropped, random available devices top up."""
    seed = int(rng.integers(0, 2**31 - 1))
    return _topk_call("repair", seed, available, int(n_sel), num_shards,
                      executor, mat=np.atleast_2d(plans))


def random_plan_indices_sharded(rng: np.random.Generator,
                                available: np.ndarray, n_sel: int,
                                count: int, num_shards: int,
                                executor: str = "auto") -> np.ndarray:
    """Fleet-sharded twin of ``plans.random_plan_indices``: uniform
    n_sel-subsets of the available set, (count, n_sel) global ids, with the
    (count, K) key draw split across shards (never materialized on the
    host — the single-lane host version allocates the full matrix)."""
    if count == 0 or n_sel == 0:
        return np.zeros((count, n_sel), dtype=np.int32)
    seed = int(rng.integers(0, 2**31 - 1))
    return _topk_call("random", seed, available, int(n_sel), num_shards,
                      executor, rows=int(count))


def gumbel_topk_indices_sharded(rng: np.random.Generator,
                                logits: np.ndarray, available: np.ndarray,
                                n_sel: int, num_shards: int,
                                executor: str = "auto") -> np.ndarray:
    """Fleet-sharded twin of ``plans.gumbel_topk_plans`` returning INDEX
    form: per-row Plackett-Luce draws over the available set, each shard
    drawing its own block's Gumbel noise in-graph."""
    seed = int(rng.integers(0, 2**31 - 1))
    return _topk_call("gumbel", seed, available, int(n_sel), num_shards,
                      executor, mat=np.atleast_2d(logits))

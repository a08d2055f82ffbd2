"""Job runtimes: what actually happens when a round's devices "train".

``FusedMultiRuntime`` — the fused, recompile-free training engine (the
default real-training path). Three ideas compound:

- **Bucketed cohort shapes.** The engine's over-provisioning, straggler
  drops, and fault injection change the cohort size ``n`` from round to
  round; a jit specialized on ``n`` recompiles every time it moves. Cohorts
  are padded up to a small set of power-of-two buckets with zero-weight
  masks, so each (job config, bucket, eval?) triple compiles exactly once
  and 20 rounds of jittery cohort sizes cost at most ``len(buckets)``
  compiles (``2 * len(buckets)`` when ``eval_every > 1`` puts both the
  eval and no-eval step variants in play).
- **One fused jitted step per round.** Device shards are gathered from
  device-resident ``(x, y, partition)`` arrays INSIDE jit, local SGD runs
  vmapped over the cohort lane, FedAvg uses mask-weighted REAL per-device
  partition sizes, and held-out eval happens in the same donated-params
  compiled call. ``eval_every`` skips the eval branch entirely on non-eval
  rounds (the engine then sees the last evaluated metrics).
- **Cross-job batched execution.** Jobs sharing a model config stack onto
  one extra vmap lane; the engine announces realized cohorts at launch time
  (``begin_round``) and the first result demand flushes every pending round
  of the group in ONE dispatch — with M jobs in flight, steady state batches
  up to M rounds per compiled call.

``FLJobRuntime`` — the historical one-job path (kept as the unfused
baseline benchmarks compare against): same math, but a fresh compile per
cohort size, host round-trips for the partition gather, and eager per-leaf
FedAvg dispatches.

``SyntheticRuntime`` — closed-form convergence model for scheduler-only
studies and fast tests: accuracy follows a saturating curve whose CEILING is
set by label coverage of the devices scheduled so far (non-IID: each device
holds 2 of C classes, so starving devices starves classes — the mechanism the
paper's fairness term addresses) and whose RATE follows Formula 13.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import JobConfig, ModelConfig
from repro.fl.aggregation import fedavg, robust_fedavg
from repro.models.cnn_zoo import cnn_apply, cnn_init, cnn_loss_and_accuracy
from repro.monitoring.trace import counter, span


@functools.partial(jax.jit, static_argnames=("cfg", "epochs", "batch_size", "lr"))
def _local_train_one(params, cfg: ModelConfig, x, y, epochs: int,
                     batch_size: int, lr: float):
    """SGD local update of one device. x: (W, ...), y: (W,). Devices holding
    fewer than ``batch_size`` samples train on one full-shard batch."""
    W = x.shape[0]
    if W == 0:
        # Width-0 shard (an empty device): nothing to train on — the local
        # update is the identity. Static-shape branch, so jit-safe.
        return params
    batch_size = min(batch_size, W)
    steps = max(W // batch_size, 1)
    xb = x[: steps * batch_size].reshape(steps, batch_size, *x.shape[1:])
    yb = y[: steps * batch_size].reshape(steps, batch_size)

    def loss_fn(p, bx, by):
        logits = cnn_apply(p, cfg, bx)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, by[:, None], axis=1).mean()

    def step(p, batch):
        bx, by = batch
        g = jax.grad(loss_fn)(p, bx, by)
        return jax.tree_util.tree_map(lambda pp, gg: pp - lr * gg, p, g), ()

    def epoch(p, _):
        p, _ = jax.lax.scan(step, p, (xb, yb))
        return p, ()

    params, _ = jax.lax.scan(epoch, params, None, length=epochs)
    return params


_local_train_batch = jax.jit(
    jax.vmap(_local_train_one, in_axes=(None, None, 0, 0, None, None, None)),
    static_argnames=("cfg", "epochs", "batch_size", "lr"))


# ---- cohort-size buckets ----

def default_buckets(num_devices: int, lo: int = 4) -> Tuple[int, ...]:
    """Powers of two from ``lo`` up, capped by (and always including) the
    pool size, so any cohort 1..num_devices maps to a bucket."""
    out, b = [], lo
    while b < num_devices:
        out.append(b)
        b *= 2
    out.append(num_devices)
    return tuple(sorted(set(out)))


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (buckets must be sorted and cover n)."""
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"cohort of {n} exceeds the largest bucket {buckets[-1]}")


# ---- the fused per-round step (one compiled call per (config, bucket)) ----

def _inject_corruption(p, locals_, corrupt, corrupt_mode: str, corrupt_scale):
    """Overwrite corrupted lanes' uploads: all-NaN params (``"nan"``) or a
    delta blown up by ``corrupt_scale`` (``"scale"``). ``corrupt``: (B,)."""

    def leaf(g, l):
        c = jnp.broadcast_to(
            corrupt.reshape((-1,) + (1,) * (l.ndim - 1)), l.shape)
        if corrupt_mode == "nan":
            bad = jnp.full_like(l, jnp.nan)
        else:
            bad = g[None] + corrupt_scale.astype(l.dtype) * (l - g[None])
        return jnp.where(c, bad, l)

    return jax.tree_util.tree_map(leaf, p, locals_)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "epochs", "batch_size", "lr", "do_eval",
                     "robust", "corrupt_mode"),
    donate_argnums=(0,))
def _fused_group_round(params, dev_ids, mask, active, x, y, partition, sizes,
                       eval_x, eval_y, corrupt, reject_mult, corrupt_scale,
                       cfg: ModelConfig, epochs: int,
                       batch_size: int, lr: float, do_eval: bool,
                       robust: bool, corrupt_mode: str):
    """Gather + local SGD + masked FedAvg + (optional) eval, fused.

    ``params``: (J, ...) stacked pytree (donated); ``dev_ids``: (J, B) padded
    cohorts; ``mask``: (J, B) 1/0 participation; ``active``: (J,) lanes with a
    pending round (inactive lanes keep their params bit-for-bit);
    ``x``/``y``: (J, N, ...) device-resident datasets; ``partition``:
    (J, K, W) index matrices; ``sizes``: (J, K) real per-device partition
    sizes (the FedAvg weights); ``eval_x``/``eval_y``: (J, E, ...) held-out
    sets. Returns (new_params, loss (J,), acc (J,), rejected (J,)) —
    loss/acc are NaN when ``do_eval`` is False (the branch is skipped
    entirely, not masked).

    ``robust`` (static) turns on in-jit fault screening: ``corrupt`` (J, B)
    lanes upload injected garbage (``corrupt_mode``/``corrupt_scale`` — the
    faults axis's corrupted-update model), then aggregation rejects
    non-finite and norm-outlier updates against a ``reject_mult`` x
    masked-median threshold (``repro.fl.aggregation.robust_fedavg``).
    ``rejected`` counts screened-out participating lanes per job (0 when
    ``robust`` is False — the plain path is compiled unchanged).
    """

    def one(p, ids, m, cj, xj, yj, pj, sj):
        idx = pj[ids]                                    # (B, W) in-jit gather
        dev_x, dev_y = xj[idx], yj[idx]                  # (B, W, ...)
        locals_ = jax.vmap(
            _local_train_one,
            in_axes=(None, None, 0, 0, None, None, None))(
                p, cfg, dev_x, dev_y, epochs, batch_size, lr)
        w = m * sj[ids]                                  # masked real sizes
        if not robust:
            return fedavg(locals_, w), jnp.zeros((), jnp.float32)
        locals_ = _inject_corruption(p, locals_, cj > 0, corrupt_mode,
                                     corrupt_scale)
        agg, ok = robust_fedavg(p, locals_, w, reject_mult)
        rej = jnp.sum((m > 0) & ~ok).astype(jnp.float32)
        return agg, rej

    J = active.shape[0]
    if J == 1:
        # Single-job group: drop the job lane entirely. The batched-matmul
        # forms the lane induces reduce in a different tiling order than the
        # plain matmuls (1-ULP drift that SGD amplifies); lane-free dispatch
        # keeps single-job groups BITWISE equal to the unfused baseline.
        lane0 = lambda tree: jax.tree_util.tree_map(lambda l: l[0], tree)
        relane = lambda tree: jax.tree_util.tree_map(lambda l: l[None], tree)
        new, rej = one(lane0(params), dev_ids[0], mask[0], corrupt[0], x[0],
                       y[0], partition[0], sizes[0])
        new, rejected = relane(new), rej[None]
    else:
        new, rejected = jax.vmap(one)(params, dev_ids, mask, corrupt, x, y,
                                      partition, sizes)
    keep = lambda nl, ol: jnp.where(
        active.reshape((-1,) + (1,) * (nl.ndim - 1)), nl, ol)
    new = jax.tree_util.tree_map(keep, new, params)
    if do_eval:
        if J == 1:
            l0, a0 = cnn_loss_and_accuracy(
                jax.tree_util.tree_map(lambda l: l[0], new), cfg,
                eval_x[0], eval_y[0])
            loss, acc = l0[None], a0[None]
        else:
            loss, acc = jax.vmap(
                lambda p, ex, ey: cnn_loss_and_accuracy(p, cfg, ex, ey))(
                    new, eval_x, eval_y)
    else:
        loss = jnp.full(active.shape, jnp.nan, jnp.float32)
        acc = jnp.full(active.shape, jnp.nan, jnp.float32)
    return new, loss, acc, rejected


@dataclasses.dataclass
class _FusedGroup:
    """Jobs sharing (model arch, local hyperparams, data shapes): one stacked
    param lane, one compiled step."""

    cfg: ModelConfig                 # canonical (name-stripped) config
    epochs: int
    batch_size: int
    lr: float
    job_ids: List[int]
    lane: Dict[int, int]             # job_id -> lane index
    params: object                   # (J, ...) stacked pytree
    x: jnp.ndarray                   # (J, N, ...)
    y: jnp.ndarray                   # (J, N)
    partition: jnp.ndarray           # (J, K, W) int32
    sizes: jnp.ndarray               # (J, K) f32
    eval_x: jnp.ndarray              # (J, E, ...)
    eval_y: jnp.ndarray              # (J, E)


class FusedMultiRuntime:
    """Fused, recompile-free multi-job runtime behind the engine protocol.

    ``begin_round`` (called by the engine at LAUNCH time with the realized
    survivor cohort) queues work; ``run_round`` (called at FINISH time)
    flushes every queued round — grouped by model config, padded to one
    shared cohort bucket, executed in one compiled dispatch per group — and
    returns that job's metrics. Works standalone too: ``run_round`` without a
    prior ``begin_round`` queues-and-flushes synchronously.

    ``datasets``: per-job ``(x, y, partition, eval_x, eval_y)`` tuples (or
    6-tuples with trailing per-device ``partition_sizes``). ``eval_every``:
    evaluate every k-th round of a job; skipped rounds report the last
    evaluated metrics (stale by < k rounds — target detection lags
    accordingly). A flush evaluates the whole group if ANY flushed lane is
    due (fresh metrics are used for every lane in that case).

    ``robust`` turns on in-jit fault screening (``TrainSpec.robust``):
    the runtime takes over corrupted-upload handling from the engine
    (``handles_corruption``) — it re-draws each round's corrupt mask from
    ``fault_engine`` (the replayable keyed schedule, so engine and runtime
    agree with zero plumbing), injects the garbage uploads itself, and
    rejects non-finite/outlier updates inside the fused round at a
    ``reject_mult`` x masked-median norm threshold. Per-round rejection
    counts ride on the metrics dict (``"rejected"``) and accumulate in
    ``rejected_total``.
    """

    def __init__(self, jobs: Sequence[JobConfig], datasets: Sequence[tuple],
                 seed: int = 0, buckets: Optional[Sequence[int]] = None,
                 eval_every: int = 1, robust: bool = False,
                 reject_mult: float = 4.0, fault_engine=None):
        if len(jobs) != len(datasets):
            raise ValueError("one dataset tuple per job required")
        if eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {eval_every}")
        self.eval_every = int(eval_every)
        self.robust = bool(robust)
        self.reject_mult = float(reject_mult)
        self.fault_engine = fault_engine
        self.rejected_total = 0.0
        # Cumulative jit recompiles of the fused step (tracked per flush
        # from the jit cache size; bucketing should keep this O(#buckets)).
        self.recompiles = 0
        self._queued: Dict[int, tuple] = {}      # job -> (ids, round_idx)
        self._results: Dict[tuple, dict] = {}    # (job, round) -> metrics
        self._last: Dict[int, dict] = {}         # job -> last evaluated
        self.groups: List[_FusedGroup] = []
        self._group_of: Dict[int, _FusedGroup] = {}

        by_key: Dict[tuple, list] = {}
        for jid, (job, ds) in enumerate(zip(jobs, datasets)):
            x, y, part, ex, ey = ds[:5]
            psz = ds[5] if len(ds) > 5 else None
            canon = dataclasses.replace(job.model, name="")
            key = (canon, job.local_epochs, job.batch_size, job.lr,
                   np.shape(x), np.shape(part), np.shape(ex))
            by_key.setdefault(key, []).append((jid, job, x, y, part, ex, ey,
                                               psz))

        num_devices = None
        for key, members in by_key.items():
            canon, epochs, bs, lr = key[0], key[1], key[2], key[3]
            job_ids = [m[0] for m in members]
            lane = {jid: i for i, jid in enumerate(job_ids)}
            params = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[cnn_init(canon, seed=seed + m[0]) for m in members])
            K, W = np.shape(members[0][4])
            num_devices = K if num_devices is None else max(num_devices, K)
            sizes = np.stack([
                np.full(K, W, np.float32) if m[7] is None
                else np.asarray(m[7], np.float32) for m in members])
            grp = _FusedGroup(
                cfg=canon, epochs=epochs, batch_size=bs, lr=lr,
                job_ids=job_ids, lane=lane, params=params,
                x=jnp.stack([jnp.asarray(m[2]) for m in members]),
                y=jnp.stack([jnp.asarray(m[3].astype(np.int32))
                             for m in members]),
                partition=jnp.stack([jnp.asarray(m[4].astype(np.int32))
                                     for m in members]),
                sizes=jnp.asarray(sizes),
                eval_x=jnp.stack([jnp.asarray(m[5]) for m in members]),
                eval_y=jnp.stack([jnp.asarray(m[6].astype(np.int32))
                                  for m in members]))
            self.groups.append(grp)
            for jid in job_ids:
                self._group_of[jid] = grp
        self.buckets = (tuple(sorted(set(buckets))) if buckets is not None
                        else default_buckets(num_devices))
        if self.buckets[-1] < num_devices:
            self.buckets = self.buckets + (num_devices,)

    # ---- engine protocol ----

    @property
    def handles_corruption(self) -> bool:
        """Robust mode screens corrupted uploads inside aggregation, so the
        engine must NOT oracle-discard them from the cohort."""
        return self.robust

    def begin_round(self, job_id: int, device_ids: np.ndarray,
                    round_idx: int) -> None:
        """Announce a launched round's REALIZED cohort (post drop/failure).
        Pure bookkeeping — training runs at the next flush."""
        self._queued[job_id] = (np.asarray(device_ids, np.int64), round_idx)

    def run_round(self, job_id: int, device_ids: np.ndarray, round_idx: int
                  ) -> Dict[str, float]:
        key = (job_id, round_idx)
        ids = np.asarray(device_ids, np.int64)
        if key not in self._results:
            queued = self._queued.get(job_id)
            if (queued is None or queued[1] != round_idx
                    or not np.array_equal(queued[0], ids)):
                # No announcement, or the announced cohort drifted: the
                # demanded cohort wins (nothing has been computed yet).
                self.begin_round(job_id, ids, round_idx)
            self._flush()
        rec, trained_ids, rej = self._results.pop(key)
        if not np.array_equal(trained_ids, ids):
            raise ValueError(
                f"job {job_id} round {round_idx} was trained on the cohort "
                f"announced via begin_round, which differs from the one "
                f"passed to run_round: {trained_ids} vs {ids}")
        # Sync happens HERE, per demand — a flush dispatches every pending
        # group asynchronously, so other jobs' rounds keep computing while
        # this one's metrics transfer and the engine does its bookkeeping.
        with span("metrics_sync", job=job_id, round=round_idx):
            _, loss, acc, ln = rec
            out = {"loss": float(loss[ln]), "accuracy": float(acc[ln])}
            if self.robust:
                out["rejected"] = float(rej[ln])
                self.rejected_total += out["rejected"]
        return out

    # ---- execution ----

    def _flush(self) -> None:
        queued, self._queued = self._queued, {}
        for grp in self.groups:
            pend = [(jid,) + queued[jid] for jid in grp.job_ids
                    if jid in queued]
            if not pend:
                continue
            J = len(grp.job_ids)
            B = bucket_for(max(len(ids) for _, ids, _ in pend), self.buckets)
            dev_ids = np.zeros((J, B), np.int32)
            mask = np.zeros((J, B), np.float32)
            corrupt = np.zeros((J, B), np.float32)
            active = np.zeros((J,), bool)
            do_eval = any(r % self.eval_every == 0 or jid not in self._last
                          for jid, _, r in pend)
            for jid, ids, r in pend:
                ln = grp.lane[jid]
                dev_ids[ln, : len(ids)] = ids
                mask[ln, : len(ids)] = 1.0
                active[ln] = True
                if self.robust and self.fault_engine is not None:
                    # The SAME keyed draw the engine made for this round.
                    corrupt[ln, : len(ids)] = self.fault_engine.corrupt_mask(
                        jid, r, ids)
            fspec = getattr(self.fault_engine, "spec", None)
            cache_size = getattr(_fused_group_round, "_cache_size", None)
            before = cache_size() if cache_size is not None else 0
            with span("fused_round", jobs=len(pend), bucket=B,
                      eval=bool(do_eval)):
                grp.params, loss, acc, rej = _fused_group_round(
                    grp.params, jnp.asarray(dev_ids), jnp.asarray(mask),
                    jnp.asarray(active), grp.x, grp.y, grp.partition,
                    grp.sizes, grp.eval_x, grp.eval_y, jnp.asarray(corrupt),
                    jnp.float32(self.reject_mult),
                    jnp.float32(fspec.corrupt_scale if fspec is not None
                                else 1.0),
                    cfg=grp.cfg, epochs=grp.epochs,
                    batch_size=grp.batch_size, lr=grp.lr, do_eval=do_eval,
                    robust=self.robust,
                    corrupt_mode=(fspec.corrupt_mode if fspec is not None
                                  else "nan"))
            if cache_size is not None:
                grew = cache_size() - before
                if grew > 0:
                    self.recompiles += grew
                    counter("jit_recompiles", self.recompiles)
            for jid, ids, r in pend:
                ln = grp.lane[jid]
                if do_eval:
                    # Unsynced device arrays: materialized at demand time.
                    rec = ("eval", loss, acc, ln)
                    self._last[jid] = rec
                else:
                    rec = self._last[jid]  # immutable snapshot (stale by < k)
                # The trained cohort rides along so a demand with a DIFFERENT
                # cohort fails loudly instead of mis-attributing metrics.
                self._results[(jid, r)] = (rec, ids, rej)

    # ---- introspection (tests / checkpointing) ----

    def params_of(self, job_id: int):
        """Unstacked param pytree of one job's lane."""
        grp = self._group_of[job_id]
        ln = grp.lane[job_id]
        return jax.tree_util.tree_map(lambda leaf: leaf[ln], grp.params)


class FLJobRuntime:
    """Unfused runtime for ONE job — the historical baseline path.

    Recompiles ``_local_train_batch`` for every distinct cohort size, gathers
    partitions through the host, and runs FedAvg eagerly; kept as the
    reference ``tests/test_train_runtime.py`` holds the fused engine
    to. FedAvg weights are the REAL per-device partition sizes
    (``partition_sizes``; defaults to the fixed partition width, under which
    all weights are equal).
    """

    def __init__(self, job: JobConfig, x: np.ndarray, y: np.ndarray,
                 partition: np.ndarray, eval_x: np.ndarray, eval_y: np.ndarray,
                 seed: int = 0, partition_sizes: Optional[np.ndarray] = None):
        self.job = job
        self.cfg = job.model
        self.x, self.y = jnp.asarray(x), jnp.asarray(y.astype(np.int32))
        self.partition = partition
        if partition_sizes is None:
            partition_sizes = np.full(partition.shape[0], partition.shape[1])
        self.partition_sizes = np.asarray(partition_sizes, np.float64)
        if self.partition_sizes.shape != (partition.shape[0],):
            raise ValueError(
                f"partition_sizes has shape {self.partition_sizes.shape}, "
                f"expected ({partition.shape[0]},)")
        self.eval_x, self.eval_y = jnp.asarray(eval_x), jnp.asarray(eval_y.astype(np.int32))
        self.params = cnn_init(self.cfg, seed=seed)
        self._eval = jax.jit(functools.partial(cnn_loss_and_accuracy, cfg=self.cfg))

    def run_round(self, job_id: int, device_ids: np.ndarray, round_idx: int
                  ) -> Dict[str, float]:
        idx = self.partition[np.asarray(device_ids)]          # (n, W)
        dev_x = self.x[jnp.asarray(idx)]                      # (n, W, ...)
        dev_y = self.y[jnp.asarray(idx)]
        locals_ = _local_train_batch(
            self.params, self.cfg, dev_x, dev_y,
            self.job.local_epochs, self.job.batch_size, self.job.lr)
        weights = jnp.asarray(self.partition_sizes[np.asarray(device_ids)],
                              jnp.float32)
        self.params = fedavg(locals_, weights)
        loss, acc = self._eval(self.params, x=self.eval_x, y=self.eval_y)
        return {"loss": float(loss), "accuracy": float(acc)}


class MultiRuntime:
    """Adapter: one FLJobRuntime per job behind the engine's JobRuntime protocol."""

    def __init__(self, runtimes):
        self.runtimes = list(runtimes)

    def run_round(self, job_id: int, device_ids: np.ndarray, round_idx: int):
        return self.runtimes[job_id].run_round(job_id, device_ids, round_idx)


DEFAULT_B0 = 0.15  # Formula 13 convergence rate when a job doesn't set one


class SyntheticRuntime:
    """Closed-form convergence: ceiling from class coverage, rate from Formula 13.

    acc_m(r) = ceiling_m * (1 - 1/(b0_m * r_eff + 1))  with r_eff the round
    count and ceiling_m = base + (1 - base) * coverage^p. coverage = fraction
    of the job's label classes seen in scheduled devices so far. Under IID
    (classes_per_device == num_classes) the ceiling is ~1 regardless, matching
    the paper's observation that fairness matters most under non-IID.

    ``b0`` is a scalar shared by all jobs or a (num_jobs,) array of per-job
    rates, so job complexity ordering (LeNet > CNN > VGG) converges at
    genuinely different speeds; ``None`` entries fall back to ``DEFAULT_B0``.
    """

    def __init__(self, num_jobs: int, num_devices: int, num_classes: int = 10,
                 classes_per_device: int = 2, b0=DEFAULT_B0,
                 base: float = 0.35, power: float = 1.5, seed: int = 0,
                 noise: float = 0.004):
        rng = np.random.default_rng(seed)
        self.num_classes = num_classes
        if num_devices > 4096:
            # Fleet pools: batched sampling-without-replacement (random keys
            # + argpartition) — one vectorized draw instead of num_devices
            # sequential rng.choice calls (milliseconds at K=100k). Same
            # distribution as the sequential draw; realizations differ, so
            # paper-scale pools keep the historical per-device stream below.
            keys = rng.random((num_devices, num_classes))
            self.device_classes = np.argpartition(
                keys, classes_per_device - 1, axis=1)[:, :classes_per_device]
        else:
            self.device_classes = np.stack([
                rng.choice(num_classes, size=classes_per_device, replace=False)
                for _ in range(num_devices)])
        self.seen = [np.zeros(num_classes, dtype=np.float64) for _ in range(num_jobs)]
        self.rounds = np.zeros(num_jobs, dtype=np.int64)
        if np.ndim(b0) > 0:
            b0 = np.array([DEFAULT_B0 if v is None else float(v) for v in b0])
            if b0.shape != (num_jobs,):
                raise ValueError(f"b0 has shape {b0.shape}, expected ({num_jobs},)")
        self.b0, self.base, self.power = b0, base, power
        self.noise = noise
        self.rng = rng

    def add_job(self, job_id: int, config=None, b0: Optional[float] = None
                ) -> None:
        """Dynamic job admission (scheduler-service hook): grow the per-job
        coverage/round state by one row. ``job_id`` must be the next index
        (or an existing row, which is RESET — a readmitted tenant starts a
        fresh model; its scheduler history transfers separately). A per-job
        ``b0`` promotes a scalar rate to a per-job array on first use."""
        if job_id > len(self.seen):
            raise ValueError(f"add_job out of order: job_id {job_id} with "
                             f"{len(self.seen)} existing jobs")
        if b0 is None and config is not None:
            b0 = getattr(config, "b0", None)
        if job_id == len(self.seen):
            self.seen.append(np.zeros(self.num_classes, dtype=np.float64))
            self.rounds = np.concatenate([self.rounds, np.zeros(1, np.int64)])
            if b0 is not None:
                b = np.asarray(self.b0, dtype=np.float64)
                if b.ndim == 0:
                    b = np.full(len(self.seen) - 1, float(b))
                self.b0 = np.concatenate([b, [float(b0)]])
            elif np.ndim(self.b0) > 0:
                self.b0 = np.concatenate([self.b0, [DEFAULT_B0]])
        else:
            self.seen[job_id][:] = 0.0
            self.rounds[job_id] = 0
            if b0 is not None:
                b = np.asarray(self.b0, dtype=np.float64)
                if b.ndim == 0:
                    b = np.full(len(self.seen), float(b))
                b[job_id] = float(b0)
                self.b0 = b

    def state_dict(self) -> Dict[str, np.ndarray]:
        """Array state for crash-consistent checkpointing (rng state rides
        separately in the manifest's JSON half)."""
        return {
            "seen": np.stack(self.seen) if self.seen
            else np.zeros((0, self.num_classes)),
            "rounds": self.rounds.copy(),
            "b0": np.asarray(self.b0, dtype=np.float64),
        }

    def load_state_dict(self, state: Dict[str, np.ndarray]) -> None:
        seen = np.asarray(state["seen"], dtype=np.float64)
        if seen.shape[0] != len(self.seen):
            raise ValueError(
                f"checkpoint has {seen.shape[0]} jobs, runtime has "
                f"{len(self.seen)} — re-add jobs before loading")
        self.seen = [seen[i].copy() for i in range(seen.shape[0])]
        self.rounds = np.asarray(state["rounds"], dtype=np.int64).copy()
        b0 = np.asarray(state["b0"], dtype=np.float64)
        self.b0 = float(b0) if b0.ndim == 0 else b0.copy()

    def run_round(self, job_id: int, device_ids: np.ndarray, round_idx: int):
        hit = self.device_classes[np.asarray(device_ids)].ravel()
        np.add.at(self.seen[job_id], hit, 1.0)
        self.rounds[job_id] += 1
        # Coverage = 1 - TV(seen-class distribution, uniform): schedulers that
        # starve devices starve their classes and cap below the uniform optimum.
        s = self.seen[job_id]
        p = s / max(s.sum(), 1e-9)
        tv = 0.5 * float(np.abs(p - 1.0 / self.num_classes).sum())
        cov = 1.0 - tv
        ceiling = self.base + (1 - self.base) * cov ** self.power
        r = float(self.rounds[job_id])
        b = np.asarray(self.b0, dtype=np.float64)
        b0 = float(b[job_id] if b.ndim else b)
        acc = ceiling * (1 - 1 / (b0 * r + 1.0))
        acc = float(np.clip(acc + self.rng.normal(0, self.noise), 0, 1))
        loss = float(-np.log(max(acc, 1e-3)))
        return {"loss": loss, "accuracy": acc}

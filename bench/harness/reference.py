"""Plain references that decide ``correct``. They import nothing of the
program and take nothing it made.

- ``fl_rounds``: federated training written out plainly: ``lax.conv``
  forward at ``highest`` precision, plain SGD over each device's shard in
  order, FedAvg weighted by shard size, eval loss on the held-out set. The
  devices of one cohort run as one block of rows (``vmap``), which changes
  no arithmetic. ``dtype=bfloat16`` gives the control: the same reference
  in the precision below the configuration's float32.
- ``plan_cost``: Formula 2 of one plan in float64 numpy (the fused
  search's estimate is compared with it); ``plan_cost_bf16`` is its
  control; ``random_plan_costs`` scores valid plans drawn at random, the
  yardstick of how well the search picks.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---- federated training --------------------------------------------------


def forward(params, layers, x, precision):
    for p, layer in zip(params, layers):
        kind = layer[0]
        if kind in ("conv", "convp"):
            x = lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision) + p["b"]
            x = jax.nn.relu(x)
            if kind == "convp":
                x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "fc":
            x = jax.nn.relu(jnp.dot(x, p["w"], precision=precision) + p["b"])
        else:
            raise ValueError(f"layer kind {kind!r} has no reference")
    head = params[-1]
    return jnp.dot(x, head["w"], precision=precision) + head["b"]


def mean_xent(params, layers, x, y, precision):
    logp = jax.nn.log_softmax(forward(params, layers, x, precision))
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def _local_train(params, x, y, idx, lr, layers, epochs, batch, precision):
    """One device: ``epochs`` passes of SGD over its shard ``idx`` in
    order, ``batch`` samples a step (the whole shard where it is
    smaller), the ragged tail dropped."""
    batch = min(batch, idx.shape[0])
    steps = idx.shape[0] // batch
    order = idx[: steps * batch].reshape(steps, batch)
    grad = jax.grad(mean_xent)

    def step(p, rows):
        g = grad(p, layers, x[rows], y[rows], precision)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

    def epoch(p, _):
        return lax.scan(step, p, order)[0], None

    return lax.scan(epoch, params, None, length=epochs)[0]


@functools.partial(jax.jit, static_argnames=("layers", "epochs", "batch",
                                             "precision"))
def _cohort_round(params, x, y, shards, sizes, lr, layers, epochs, batch,
                  precision):
    local = jax.vmap(lambda idx: _local_train(
        params, x, y, idx, lr, layers, epochs, batch, precision))(shards)
    w = (sizes / sizes.sum()).astype(jnp.float32)
    return jax.tree_util.tree_map(
        lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1,
                                precision=lax.Precision.HIGHEST
                                ).astype(l.dtype), local)


@functools.partial(jax.jit, static_argnames=("layers", "precision"))
def _eval_loss(params, ex, ey, layers, precision):
    return mean_xent(params, layers, ex, ey, precision).astype(jnp.float32)


def fl_rounds(params, x, y, ex, ey, partition: np.ndarray,
              cohorts: Sequence[np.ndarray], job: dict,
              dtype=jnp.float32) -> Dict[str, list]:
    """Run ``len(cohorts)`` rounds from ``params``; return the host params
    after each round and the eval loss after each round."""
    layers = tuple(tuple(l) for l in job["layers"])
    prec = (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)
    cast = lambda t: jax.tree_util.tree_map(lambda l: l.astype(dtype), t)
    p, x, ex = cast(params), x.astype(dtype), ex.astype(dtype)
    lr = jnp.asarray(job["lr"], dtype)
    out = {"params": [], "loss": []}
    for ids in cohorts:
        shards = jnp.asarray(partition[np.asarray(ids)])
        sizes = jnp.full((len(ids),), partition.shape[1], jnp.float32)
        p = _cohort_round(p, x, y, shards, sizes, lr, layers,
                          job["local_epochs"], job["batch_size"], prec)
        out["loss"].append(float(_eval_loss(p, ex, ey, layers, prec)))
        out["params"].append(host_leaves(p))
    return out


def host_leaves(tree) -> List[np.ndarray]:
    return [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(tree)]


# ---- plan cost (Formula 2) -----------------------------------------------


def plan_cost(times: np.ndarray, counts: np.ndarray, plan: np.ndarray, *,
              alpha: float, beta: float, time_scale: float,
              fairness_scale: float, dtype=np.float64) -> float:
    """alpha * max_{k in V} t_k / T + beta * (Var(c + v) - Var(c)) / F."""
    t = np.asarray(times).astype(dtype)
    c = np.asarray(counts).astype(dtype)
    v = np.asarray(plan, bool)
    round_time = t[v].max() if v.any() else dtype(0)
    dfair = np.var(c + v.astype(dtype)) - np.var(c)
    return float(dtype(alpha) * round_time / dtype(time_scale)
                 + dtype(beta) * dfair / dtype(fairness_scale))


def plan_cost_bf16(times, counts, plan, **kw) -> float:
    import ml_dtypes

    return plan_cost(times, counts, plan, dtype=ml_dtypes.bfloat16, **kw)


def random_plan_costs(times: np.ndarray, counts: np.ndarray,
                      available: np.ndarray, n_sel: int, n: int, rng,
                      **cost) -> np.ndarray:
    """Formula 2, in float64, of ``n`` valid plans drawn uniformly at random
    (``n_sel`` distinct available devices each)."""
    idx = np.flatnonzero(np.asarray(available, bool))
    picks = np.argsort(rng.random((n, idx.size)), axis=1)[:, :n_sel]
    plans = np.zeros((n, np.asarray(times).shape[0]), bool)
    np.put_along_axis(plans, idx[picks], True, axis=1)
    return np.array([plan_cost(times, counts, p, **cost) for p in plans])

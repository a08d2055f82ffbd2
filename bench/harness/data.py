"""Inputs and weights of the real-training cells, made from ``--seed``.

Data: class-prototype images (copied from the program's
``data/synthetic.py``): class c has a fixed random prototype P_c and a
sample is P_c + noise * N(0, 1). Labels are balanced and sorted by class,
so the paper's non-IID split (each class cut into ``parts_per_class``
equal parts, each device takes one part of each of two distinct classes)
gives every device the same shard width. Images and weights are made on
the device in one jitted call each.

``bench_real_fl`` is the runtime factory the cells run: the program's
``FusedMultiRuntime`` over these datasets, with these weights installed.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.cells import seeds


def job_key(seed: int, stream: str, job: int):
    return jax.random.fold_in(jax.random.key(seeds(seed)[stream]), job)


@functools.partial(jax.jit,
                   static_argnames=("n", "shape", "classes", "n_eval"))
def _make_data(key, noise, n: int, shape: tuple, classes: int, n_eval: int):
    kp, kx, ke, kl = jax.random.split(key, 4)
    protos = jax.random.normal(kp, (classes,) + shape, jnp.float32)
    y = (jnp.arange(n, dtype=jnp.int32) // (n // classes)).astype(jnp.int32)
    x = protos[y] + noise * jax.random.normal(kx, (n,) + shape, jnp.float32)
    ey = jax.random.randint(kl, (n_eval,), 0, classes, jnp.int32)
    ex = protos[ey] + noise * jax.random.normal(ke, (n_eval,) + shape,
                                                jnp.float32)
    return x, y, ex, ey


def make_data(job: dict, config: dict, seed: int, j: int):
    """(x, y, eval_x, eval_y) of job ``j`` on the device."""
    n, c = job["num_samples"], job["num_classes"]
    if n % c:
        raise ValueError(f"{job['name']}: {n} samples do not split evenly "
                         f"over {c} classes")
    return _make_data(job_key(seed, "data", j), jnp.float32(config["data_noise"]),
                      n=n, shape=tuple(job["input_shape"]), classes=c,
                      n_eval=config["eval_samples"])


def noniid_partition(job: dict, config: dict, seed: int, j: int) -> np.ndarray:
    """(K, W) sample indices: the paper's split over class-sorted labels,
    each shard shuffled so local batches mix its two classes."""
    K = config["num_devices"]
    part = config["partition"]
    c, ppc, cpd = (job["num_classes"], part["parts_per_class"],
                   part["classes_per_device"])
    per_class = job["num_samples"] // c
    width = per_class // ppc
    if width == 0:
        raise ValueError(f"{job['name']}: {per_class} samples per class "
                         f"cannot be cut into {ppc} parts")
    rng = np.random.default_rng([seeds(seed)["data"], j])
    out = np.empty((K, cpd * width), np.int32)
    base = np.arange(width, dtype=np.int32)
    for k in range(K):
        classes = rng.choice(c, size=cpd, replace=False)
        parts = rng.integers(0, ppc, size=cpd)
        idx = np.concatenate([cl * per_class + p * width + base
                              for cl, p in zip(classes, parts)])
        out[k] = rng.permutation(idx)
    return out


def _init_layers(key, layers, input_shape, num_classes):
    """He-normal weights, zero biases, in the program's param layout: one
    dict per layer (``{}`` for flatten) and a classifier head last."""
    params, ch, spatial = [], input_shape[-1], input_shape[0]
    keys = jax.random.split(key, len(layers) + 1)

    def dense(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) * np.sqrt(2.0 / fan_in)
        return {"w": w, "b": jnp.zeros(shape[-1:], jnp.float32)}

    for k, layer in zip(keys, layers):
        kind = layer[0]
        if kind in ("conv", "convp"):
            _, out_c, ks = layer
            params.append(dense(k, (ks, ks, ch, out_c), ks * ks * ch))
            ch = out_c
            spatial = spatial // 2 if kind == "convp" else spatial
        elif kind == "flatten":
            params.append({})
            ch = ch * spatial * spatial
        elif kind == "fc":
            params.append(dense(k, (ch, layer[1]), ch))
            ch = layer[1]
        else:
            raise ValueError(f"layer kind {kind!r} has no reference")
    params.append(dense(keys[-1], (ch, num_classes), ch))
    return params


_init_jit = jax.jit(_init_layers, static_argnums=(1, 2, 3))


def make_weights(job: dict, seed: int, j: int):
    layers = tuple(tuple(l) for l in job["layers"])
    return _init_jit(job_key(seed, "weights", j), layers, tuple(job["input_shape"]),
              job["num_classes"])


def check_model(job: dict, model) -> None:
    """The program's model for the job is the configuration's."""
    layers = tuple(tuple(l) for l in job["layers"])
    got = (tuple(model.cnn_spec), tuple(model.input_shape), model.num_classes)
    want = (layers, tuple(job["input_shape"]), job["num_classes"])
    if got != want:
        raise ValueError(f"{job['name']}: the program's model {got} is not "
                         f"the configuration's {want}")


def register() -> None:
    from repro.experiment.registry import RUNTIMES, register_runtime

    if "bench_real_fl" not in RUNTIMES:
        register_runtime("bench_real_fl")(bench_real_fl)


def bench_real_fl(spec, jobs: List, pool, *, config: dict, seed: int):
    from repro.fl.runtime import FusedMultiRuntime, default_buckets

    datasets = []
    for j, (job, cfg_job) in enumerate(zip(jobs, config["jobs"])):
        check_model(cfg_job, job.model)
        x, y, ex, ey = make_data(cfg_job, config, seed, j)
        part = noniid_partition(cfg_job, config, seed, j)
        datasets.append((x, y, part, ex, ey,
                         np.full(part.shape[0], part.shape[1], np.float32)))
    K = pool.num_devices
    n_hot = spec.effective_n_sel()
    sched = min(K, max(n_hot, int(round(n_hot * spec.over_provision))))
    rt = FusedMultiRuntime(
        jobs, datasets, buckets=tuple(sorted(set(default_buckets(K))
                                             | {n_hot, sched})))
    del datasets
    for j, cfg_job in enumerate(config["jobs"]):
        grp = next(g for g in rt.groups if j in g.job_ids)
        if len(grp.job_ids) != 1:
            raise ValueError("jobs sharing one fused lane are not supported "
                             "by the weight installer")
        w = jax.tree_util.tree_map(lambda l: l[None], make_weights(cfg_job,
                                                                   seed, j))
        if (jax.tree_util.tree_structure(w)
                != jax.tree_util.tree_structure(grp.params)):
            raise ValueError(f"{cfg_job['name']}: weight layout differs from "
                             "the program's")
        grp.params = w
    return rt

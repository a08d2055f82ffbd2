"""The comparison that decides ``correct`` fails when it should.

At a tiny size on the CPU: the control (the plain reference one precision
lower, put in the program's place) and the planted faults of
``bench/readings.py`` exceed a limit while the program stays inside every
limit; and with the timed path broken underneath, a whole run (past the
look for a chip) reports ``correct`` false, once for each fault a cell
can have.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run as bench_run
from bench import readings
from bench.tests.tiny import args, tiny_cell

SEED = 2**32 + 123


def _fails(numbers: dict, limits: dict) -> bool:
    return any(not (v <= limits[k]) for k, v in numbers.items())


@pytest.mark.parametrize("name", ["train-groupA", "groupA-sched"])
def test_control_and_faults_fail_program_passes(name):
    cell = tiny_cell(name)
    limits = cell.config["limits"]
    r = readings.readings(cell, SEED, 1.0)
    assert r["decisions"] > 0
    assert not _fails(r["program"], limits), r["program"]
    assert _fails(r["control"], limits), r["control"]
    for fault, nums in r["faults"].items():
        assert _fails(nums, limits), (fault, nums)


# ---- faults planted in the program, seen through a whole run ------------


def _patch_round(monkeypatch, change):
    import repro.fl.runtime as rt

    orig = rt._fused_group_round

    def broken(params, dev_ids, mask, *a, **kw):
        return change(orig, params, dev_ids, mask, *a, **kw)

    monkeypatch.setattr(rt, "_fused_group_round", broken)


def _unchanged(orig, params, dev_ids, mask, *a, **kw):
    keep = jax.tree_util.tree_map(jnp.copy, params)
    _, loss, acc, rej = orig(params, dev_ids, mask, *a, **kw)
    return keep, loss, acc, rej


def _half_batch(orig, params, dev_ids, mask, *a, **kw):
    n = jnp.sum(mask, axis=1, keepdims=True)
    pos = jnp.cumsum(mask, axis=1)
    return orig(params, dev_ids, mask * (pos <= jnp.ceil(n / 2)), *a, **kw)


def _loss_altered(orig, *a, **kw):
    new, loss, acc, rej = orig(*a, **kw)
    return new, loss * 1.5, acc, rej


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered])
def test_broken_training_round_is_not_correct(monkeypatch, fault):
    _patch_round(monkeypatch, fault)
    cell = tiny_cell("train-groupA")
    res = bench_run.run(args("train-groupA", seconds=1.0),
                        require_tpu=False, cell=cell)
    assert res["correct"] is False, res["checks"]


def _plan_altered(plan, est, times, avail):
    plan = plan.copy()
    on = plan.nonzero()[0]
    off = (~plan & avail).nonzero()[0]
    plan[on[0]] = False
    plan[off[times[off].argmax()]] = True
    return plan, est


def _plan_halved(plan, est, times, avail):
    plan = plan.copy()
    on = plan.nonzero()[0]
    plan[on[len(on) // 2:]] = False
    return plan, est


def _plan_random(plan, est, times, avail):
    """A search that returns any valid plan; its estimate is left to the
    caller to make true, so only the plan's quality can give it away."""
    rng = np.random.default_rng(int(times.sum() * 1e3) % 2**32)
    out = np.zeros_like(plan)
    out[rng.choice(avail.nonzero()[0], int(plan.sum()), replace=False)] = True
    return out, None


@pytest.mark.parametrize("fault", [_plan_altered, _plan_halved, _plan_random])
@pytest.mark.parametrize("name", ["train-groupA", "groupA-sched"])
def test_broken_search_is_not_correct(monkeypatch, name, fault):
    from bench.harness import reference
    from repro.core import search

    orig = search.bods_acquire

    def broken(rng, times, counts, available, *a, **kw):
        plan, est = orig(rng, times, counts, available, *a, **kw)
        plan, est = fault(plan, est, np.asarray(times, np.float64),
                          np.asarray(available, bool))
        if est is None:
            est = reference.plan_cost(
                times, counts, plan, alpha=kw["alpha"], beta=kw["beta"],
                time_scale=kw["time_scale"],
                fairness_scale=kw["fairness_scale"])
        return plan, est

    monkeypatch.setattr(search, "bods_acquire", broken)
    res = bench_run.run(args(name, seconds=1.0), require_tpu=False,
                        cell=tiny_cell(name))
    assert res["correct"] is False, res["checks"]

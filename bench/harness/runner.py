"""One run of one cell: build, warm up, measure, check.

The entry the window drives is ``ExperimentSpec.build()`` ->
``MultiJobEngine.advance_until``, in simulated-time chunks of
``chunk_time_scales`` times the cost model's time scale, until the wall
clock has run ``seconds``. Warm-up drives the same engine until every job
has finished ``warmup_rounds`` rounds and made ``warmup_decisions``
decisions, so every compiled shape is in use before the window opens.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.harness import cells
from bench.harness.clock import CompileClock


@dataclasses.dataclass
class Decision:
    job: int
    plan: np.ndarray
    est: Optional[float]
    ctx: object = None          # kept only for sampled decisions


class Recorder:
    """Wraps ``scheduler.schedule``: records every decision and keeps a
    seeded sample of contexts for the post-window check."""

    def __init__(self, scheduler, sample_seed: int, share: float):
        self.decisions: List[Decision] = []
        self.sampling = False
        self._rng = np.random.default_rng(sample_seed)
        self._share = share
        inner = scheduler.schedule

        def schedule(ctx):
            plan = inner(ctx)
            keep = self.sampling and self._rng.random() < self._share
            self.decisions.append(Decision(
                ctx.job, plan, scheduler.last_estimated_cost,
                ctx if keep else None))
            return plan

        scheduler.schedule = schedule


class CellRun:
    def __init__(self, cell: cells.Cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.traffic = cell.config, cell.traffic
        if self.traffic["runtime"] == "bench_real_fl":
            from bench.harness import data

            data.register()
        self.spec = cells.build_spec(self.cfg, self.traffic, seed)
        self.ex = self.spec.build()
        self.engine = self.ex.engine
        self.rec = Recorder(self.engine.scheduler, cells.seeds(seed)["sample"],
                            self.traffic["check_share"])
        self.snapshots: Dict[int, dict] = {}
        self.chunk = (self.traffic["chunk_time_scales"]
                      * self.engine.cost_model.time_scale)
        self._until = 0.0

    # ---- driving the engine -------------------------------------------

    def _drive(self, done: Callable[[], bool], on_round=None) -> None:
        eng = self.engine
        while not done():
            self._until += self.chunk
            eng.advance_until(self._until, on_round=on_round)

    def warm_up(self, snapshot_rounds: int = 0) -> None:
        """Launch every job and run until each has ``warmup_rounds``
        rounds and ``warmup_decisions`` decisions; keep host copies of the
        params after round 1 and round ``snapshot_rounds`` (and the initial
        params) for the training comparison."""
        eng, tr = self.engine, self.traffic
        rt = eng.runtime
        M = len(eng.jobs)
        if snapshot_rounds:
            from bench.harness.reference import host_leaves

            self.init = {j: host_leaves(rt.params_of(j)) for j in range(M)}

            def on_round(r):
                if r.round_idx in (0, snapshot_rounds - 1):
                    self.snapshots.setdefault(r.job, {})[r.round_idx] = (
                        host_leaves(rt.params_of(r.job)))
        else:
            on_round = None
        need_r = max(tr["warmup_rounds"], snapshot_rounds)
        need_d = tr["warmup_decisions"]
        for m in range(M):
            if not eng.jobs[m].launched:
                eng.launch_job(m, 0.0)

        def done():
            rounds = np.bincount([r.job for r in eng.records], minlength=M)
            decs = np.bincount([d.job for d in self.rec.decisions],
                               minlength=M)
            return bool(np.all(rounds >= need_r) and np.all(decs >= need_d))

        self._drive(done, on_round)

    def window(self, seconds: float, trace=None,
               trace_seconds: float = 0.0) -> dict:
        eng = self.engine
        clock = CompileClock.shared()
        c0 = clock.snapshot()
        n_rec, n_dec = len(eng.records), len(self.rec.decisions)
        self.rec.sampling = True
        traced = None
        for a in jax.live_arrays():   # open the window on an idle device
            a.block_until_ready()
        t0 = time.perf_counter()
        if trace is not None:
            trace.start()
            tr0 = (len(eng.records), len(self.rec.decisions))
            self._drive(lambda: time.perf_counter() - t0 >= trace_seconds)
            trace.stop()
            traced = (tr0, (len(eng.records), len(self.rec.decisions)))
        self._drive(lambda: time.perf_counter() - t0 >= seconds)
        elapsed = time.perf_counter() - t0
        self.rec.sampling = False
        c1 = clock.snapshot()
        self.window_records = eng.records[n_rec:]
        self.window_decisions = self.rec.decisions[n_dec:]
        return {"elapsed": elapsed, "compiles": c1[0] - c0[0],
                "cache_hits": c1[1] - c0[1], "traced": traced}

    # ---- correctness ----------------------------------------------------

    def decision_sample(self) -> List[dict]:
        out = []
        for d in self.window_decisions:
            if d.ctx is None:
                continue
            out.append(dict(job=d.job, n_sel=d.ctx.n_sel,
                            available=d.ctx.available,
                            times=d.ctx.expected_times, counts=d.ctx.counts,
                            plan=np.asarray(d.plan), est=d.est))
        return out[: self.traffic["check_max"]]

    def cost_terms(self) -> dict:
        cm = self.engine.cost_model
        if not cm.delta_fairness:
            raise ValueError("the float64 re-score follows the fairness "
                             "increment; this cost model uses the absolute "
                             "variance")
        return dict(alpha=cm.alpha, beta=cm.beta, time_scale=cm.time_scale,
                    fairness_scale=cm.fairness_scale)

    def cohorts(self, job: int, n: int) -> List[np.ndarray]:
        recs = sorted((r for r in self.engine.records if r.job == job),
                      key=lambda r: r.round_idx)[:n]
        return [np.asarray(r.device_ids) for r in recs]

    def losses(self, job: int, n: int) -> List[float]:
        recs = sorted((r for r in self.engine.records if r.job == job),
                      key=lambda r: r.round_idx)[:n]
        return [float(r.loss) for r in recs]


def summarize_window(run: CellRun, win: dict) -> dict:
    recs = run.window_records
    failed = sum(1 for r in recs
                 if not (np.isfinite(r.loss) and np.isfinite(r.accuracy)))
    return {
        "rounds": len(recs), "decisions": len(run.window_decisions),
        "failed": failed, "rounds_per_s": len(recs) / win["elapsed"],
    }

"""Fleet-axis sharding: scoring/search parity, SoA mirrors, spec plumbing.

The sharded paths (repro/core/shard.py + the ``num_shards`` plumbing
through scoring, the fused searchers, CostModel and FleetSpec) must be
invisible in the results: same scores as the single lane (within f32
resolution), same chosen plans from the searchers, valid plans out of the
sharded candidate ops — at any shard count, with or without real host
devices. In-process tests run the ``emulate`` executor (this process has
however many devices it has); one subprocess test forces an 8-device host
platform and pins the real ``shard_map`` executor against the single lane.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import scoring, search, shard
from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.plans import indices_to_plans, random_plan_indices
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulingContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KW = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
          delta_fairness=True)


def _problem(K=103, P=9, seed=0):
    """Non-power-of-two K so every shard count exercises the padding."""
    rng = np.random.default_rng(seed)
    times = rng.uniform(1.0, 100.0, K)
    counts = rng.integers(0, 50, K).astype(np.float64)
    avail = rng.random(K) < 0.8
    n_sel = max(2, int(avail.sum()) // 4)
    idx = random_plan_indices(rng, avail, n_sel, P)
    return times, counts, avail, n_sel, idx


def _rel(a, b):
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


# ---- sharded scoring parity (emulated executor, any machine) -------------


class TestShardedScoringParity:
    @pytest.mark.parametrize("N", [1, 2, 8])
    def test_index_form_matches_numpy(self, N):
        times, counts, avail, n_sel, idx = _problem()
        ref = scoring.score_plan_indices(times, counts, idx,
                                         backend="numpy", **KW)
        got = scoring.score_plan_indices(times, counts, idx, backend="jax",
                                         num_shards=N, **KW)
        assert _rel(got, ref) < 1e-5

    @pytest.mark.parametrize("N", [1, 2, 8])
    def test_dense_form_matches_numpy(self, N):
        times, counts, avail, n_sel, idx = _problem()
        plans = indices_to_plans(idx, times.shape[0])
        ref = scoring.score_plans(times, counts, plans,
                                  backend="numpy", **KW)
        got = scoring.score_plans(times, counts, plans, backend="jax",
                                  num_shards=N, **KW)
        assert _rel(got, ref) < 1e-5

    def test_forms_agree_sharded(self):
        times, counts, _, _, idx = _problem(K=257, P=5)
        plans = indices_to_plans(idx, 257)
        d = scoring.score_plans(times, counts, plans, backend="jax",
                                num_shards=4, **KW)
        i = scoring.score_plan_indices(times, counts, idx, backend="jax",
                                       num_shards=4, **KW)
        np.testing.assert_allclose(d, i, rtol=1e-5, atol=1e-7)

    def test_stats_executors_agree(self):
        """emulate and shard_map run the same shard-local math; with one
        device only N=1 can use shard_map, where both must be exact."""
        times, counts, _, _, idx = _problem(K=64, P=4)
        cc = counts - counts.mean()
        a = shard.plan_stats_sharded(times, cc, idx, "index", 1,
                                     executor="shard_map")
        b = shard.plan_stats_sharded(times, cc, idx, "index", 1,
                                     executor="emulate")
        np.testing.assert_array_equal(a, b)


# ---- shard-aware auto dispatch (satellite: resolve_backend) --------------


class TestResolveBackendShardAware:
    def test_single_lane_pins(self):
        assert scoring.resolve_backend("auto", 100) == "numpy"
        assert scoring.resolve_backend(
            "auto", scoring.AUTO_NUMPY_MAX_DENSE + 1) == "jax"
        assert scoring.resolve_backend(
            "auto", scoring.AUTO_NUMPY_MAX_INDEX, form="index") == "numpy"

    def test_sharded_fleet_stays_on_jax(self):
        # Single-lane dispatch would call 1<<19 index elements "numpy"
        # (< AUTO_NUMPY_MAX_INDEX); a sharded fleet must not fall back.
        n = 1 << 19
        assert scoring.resolve_backend("auto", n, form="index") == "numpy"
        assert scoring.resolve_backend("auto", n, form="index",
                                       num_shards=8) == "jax"

    def test_tiny_sharded_problem_still_numpy(self):
        # Per-shard work below jit dispatch overhead -> numpy wins even
        # when shards were requested.
        n = 8 * scoring.MIN_SHARD_ELEMENTS
        assert scoring.resolve_backend("auto", n, form="index",
                                       num_shards=8) == "numpy"
        assert scoring.resolve_backend("auto", n + 8, form="index",
                                       num_shards=8) == "jax"

    def test_explicit_backend_wins(self):
        assert scoring.resolve_backend("numpy", 1 << 22,
                                       num_shards=8) == "numpy"


# ---- sharded plan ops: validity contracts --------------------------------


class TestShardedPlanOps:
    @pytest.mark.parametrize("N", [1, 2, 8])
    def test_random_indices_valid(self, N):
        _, _, avail, n_sel, _ = _problem()
        out = shard.random_plan_indices_sharded(
            np.random.default_rng(1), avail, n_sel, 7, N)
        assert out.shape == (7, n_sel)
        for row in out:
            assert len(set(row.tolist())) == n_sel
            assert avail[row].all()

    @pytest.mark.parametrize("N", [1, 2, 8])
    def test_repair_preserves_valid_selections(self, N):
        rng = np.random.default_rng(2)
        _, _, avail, n_sel, _ = _problem()
        K = avail.shape[0]
        plans = np.zeros((5, K), bool)
        for i in range(5):
            plans[i, rng.choice(K, n_sel + 3, replace=False)] = True
        out = shard.repair_plans_sharded(rng, plans, avail, n_sel, N)
        for i in range(5):
            chosen = set(out[i].tolist())
            assert len(chosen) == n_sel and avail[out[i]].all()
            valid = set(np.flatnonzero(plans[i] & avail).tolist())
            # valid selections outrank noise: they survive up to n_sel
            assert len(chosen & valid) >= min(len(valid), n_sel)

    @pytest.mark.parametrize("N", [1, 2, 8])
    def test_gumbel_topk_valid(self, N):
        rng = np.random.default_rng(3)
        _, _, avail, n_sel, _ = _problem()
        logits = rng.normal(size=(6, avail.shape[0])).astype(np.float32)
        out = shard.gumbel_topk_indices_sharded(rng, logits, avail, n_sel, N)
        for row in out:
            assert len(set(row.tolist())) == n_sel and avail[row].all()

    def test_resolve_num_shards(self):
        assert shard.resolve_num_shards(None) == 1
        assert shard.resolve_num_shards(3) == 3
        assert shard.resolve_num_shards(8, fleet_size=5) == 5
        assert shard.resolve_num_shards("auto") >= 1
        with pytest.raises(ValueError):
            shard.resolve_num_shards(-2)


# ---- fused searchers: shard fallback must not change decisions -----------


class TestSearchShardFallback:
    def test_usable_shards_fallback_rules(self):
        f = search._usable_search_shards
        assert f(1, 32) == 1
        assert f(4, 30) == 1          # rows not divisible
        assert f(4, 32, pairs=True) == 4 or f(4, 32, pairs=True) == 1
        assert f(4, 12, pairs=True) == 1  # 12/4 = 3 rows/shard, odd pairs

    def test_too_many_shards_raise_on_an_accelerator(self, monkeypatch):
        """The CPU platform emulates missing shards; on a TPU the same
        request raises in the scoring executor and in the searchers."""
        import jax

        n = shard.shard_capacity() + 1
        assert shard._resolve_executor("auto", n) == "emulate"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="tpu device"):
            shard._resolve_executor("auto", n)
        with pytest.raises(ValueError, match="tpu device"):
            search._usable_search_shards(n, 32 * n)
        assert shard._resolve_executor("auto", 1) == "shard_map"

    def _scenario(self, K=96, seed=0):
        pool = DevicePool.heterogeneous(K, 2, seed=seed)
        rng = np.random.default_rng(seed + 7)
        counts = rng.integers(0, 8, K).astype(np.float64)
        avail = np.ones(K, bool)
        avail[rng.choice(K, K // 5, replace=False)] = False
        times = pool.expected_times(0, 5.0)

        def ctx():
            return SchedulingContext(
                job=0, round_idx=0, tau=5.0, n_sel=8,
                available=avail.copy(), counts=counts.copy(),
                expected_times=times)

        return pool, ctx

    @pytest.mark.parametrize("name", ["sa", "genetic", "bods"])
    def test_scheduler_decisions_unchanged_by_num_shards(self, name):
        """On a host without enough devices the searchers fall back to the
        single lane — same plans, same costs, no crash."""
        plans = {}
        for n_sh in (1, 4):
            pool, ctx = self._scenario()
            cm = CostModel(pool, alpha=4.0, beta=0.25, num_shards=n_sh)
            cm.calibrate([5.0, 5.0], n_sel=8)
            sched = get_scheduler(name, cost_model=cm, seed=0)
            plans[n_sh] = [sched.schedule(ctx()) for _ in range(3)]
        for a, b in zip(plans[1], plans[4]):
            np.testing.assert_array_equal(a, b)


# ---- DevicePool dtype knob + compact SoA mirrors -------------------------


class TestPoolDtypeAndMirrors:
    def test_time_dtype_knob(self):
        for dt in (np.float64, np.float32):
            pool = DevicePool.heterogeneous(32, 2, seed=0, time_dtype=dt)
            assert pool.busy_until.dtype == dt
            assert pool.expected_times_all([5.0, 5.0]).dtype == dt
            t = pool.sample_times(0, 5.0)
            assert t.dtype == dt
            mask = np.zeros(32, bool)
            mask[:3] = True
            pool.occupy(mask, 7.5)
            assert pool.busy_until.dtype == dt

    def test_bf16_mirror_tolerance(self):
        pool = DevicePool.heterogeneous(256, 2, seed=1)
        f32 = np.asarray(pool.expected_times(0, 5.0), np.float32)
        bf = pool.expected_times_bf16(0, 5.0)
        assert bf.dtype == np.float32  # accumulated back in f32
        rel = np.max(np.abs(bf - f32) / np.maximum(np.abs(f32), 1e-12))
        assert rel < 1e-2  # bf16 has ~3 decimal digits

    def test_bf16_mirror_rebuilt_after_churn(self):
        pool = DevicePool.heterogeneous(8, 1, seed=2)
        before = pool.expected_times_bf16(0, 5.0).copy()
        pool.set_capabilities(np.arange(8), a=np.full(8, 0.5))
        after = pool.expected_times_bf16(0, 5.0)
        assert not np.allclose(before, after)

    def test_int8_plan_mirror_scoring_parity(self):
        times, counts, avail, n_sel, idx = _problem(K=64, P=6)
        p_bool = indices_to_plans(idx, 64)
        p_i8 = indices_to_plans(idx, 64, dtype=np.int8)
        assert p_i8.dtype == np.int8
        a = scoring.score_plans(times, counts, p_bool, backend="jax", **KW)
        b = scoring.score_plans(times, counts, p_i8, backend="jax", **KW)
        np.testing.assert_array_equal(a, b)
        c = scoring.score_plans(times, counts, p_i8, backend="numpy", **KW)
        np.testing.assert_allclose(b, c, rtol=1e-5, atol=1e-7)


# ---- FleetSpec / CLI / CostModel plumbing --------------------------------


def _tiny_spec(**overrides):
    from repro.experiment.spec import ExperimentSpec, JobSpec, PoolSpec

    spec = ExperimentSpec(
        jobs=(JobSpec(name="j0", target_metric=0.75, max_rounds=10),),
        pool=PoolSpec(num_devices=30, seed=3), scheduler="random",
        runtime="synthetic", n_sel=4)
    return spec.replace(**overrides) if overrides else spec


class TestSpecPlumbing:
    def test_num_shards_json_round_trip(self):
        from repro.experiment.spec import ExperimentSpec

        spec = _tiny_spec(fleet={"num_shards": 2})
        back = ExperimentSpec.from_dict(json.loads(spec.to_json()))
        assert back.fleet.num_shards == 2
        assert back.effective_num_shards() == 2

    def test_auto_resolves_to_device_count(self):
        import jax

        spec = _tiny_spec(fleet={"num_shards": "auto"})
        assert spec.effective_num_shards() == min(
            jax.device_count(), spec.effective_num_devices())

    def test_cost_spec_plumbs_num_shards(self):
        from repro.experiment.spec import CostSpec

        pool = DevicePool.heterogeneous(16, 2, seed=0)
        cm = CostSpec(calibrate=False).build(pool, [5.0, 5.0], 4,
                                             num_shards=3)
        assert cm.num_shards == 3

    def test_cli_dotted_set_key(self):
        from repro.experiment.cli import _parse_kv

        out = _parse_kv(["fleet.num_shards=4", "fleet.n_sel=8",
                         "scheduler=sa"])
        assert out == {"fleet": {"num_shards": 4, "n_sel": 8},
                       "scheduler": "sa"}

    def test_cli_dotted_collision_rejected(self):
        from repro.experiment.cli import _parse_kv

        with pytest.raises(SystemExit):
            _parse_kv(["fleet=3", "fleet.num_shards=4"])


# ---- launch bootstrap (no re-exec in-process) ----------------------------


class TestBootstrap:
    def test_env_folds_existing_flags(self, monkeypatch):
        from repro.launch import bootstrap

        monkeypatch.setenv(
            "XLA_FLAGS",
            "--foo=1 --xla_force_host_platform_device_count=2")
        env = bootstrap.host_platform_env(8, tcmalloc=False)
        assert "--foo=1" in env["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        assert "device_count=2" not in env["XLA_FLAGS"]

    def test_no_tcmalloc_env_honored(self, monkeypatch):
        from repro.launch import bootstrap

        monkeypatch.setenv("REPRO_NO_TCMALLOC", "1")
        assert bootstrap.find_tcmalloc() is None

    def test_single_shard_is_noop(self):
        from repro.launch import bootstrap

        assert bootstrap.ensure_host_devices(1) is True

    def test_late_call_with_jax_imported_raises(self, monkeypatch):
        from repro.launch import bootstrap

        import jax

        need = jax.device_count() + 1
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        assert "jax" in sys.modules
        with pytest.raises(RuntimeError, match="before\\s+importing jax|"
                                               "before importing"):
            bootstrap.ensure_host_devices(need)

    def test_tpu_host_never_reexecs(self, monkeypatch):
        """On a TPU the chips are the devices: too few raise, and the
        process is never re-exec'd with forced CPU devices (a second
        process could not reach the chip the first one holds)."""
        from repro.launch import bootstrap

        import jax

        def execve(*a):
            raise AssertionError("re-exec on a TPU host")

        need = jax.device_count() + 1
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(os, "execve", execve)
        with pytest.raises(RuntimeError, match="tpu host"):
            bootstrap.ensure_host_devices(need)

    def test_compile_cache_env_is_left_alone(self, monkeypatch):
        from repro.launch import bootstrap

        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert bootstrap.compile_cache_dir() is None
        assert bootstrap.setup_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_defaults_to_fixed_checkout_dir(self, monkeypatch):
        from repro.launch import bootstrap

        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = bootstrap.compile_cache_dir()
        assert path == os.path.join(REPO, ".jax_cache")
        assert bootstrap.compile_cache_dir() == path  # no pid, time, tempdir
        before = jax.config.jax_compilation_cache_dir
        try:
            assert bootstrap.setup_compile_cache() == path
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


# ---- real shard_map vs single lane (8 forced host devices) ---------------

_SUBPROC = r"""
import sys
assert "jax" not in sys.modules
import numpy as np
import jax
assert jax.device_count() == 8, jax.device_count()

from repro.core import scoring, search
from repro.core.plans import indices_to_plans, random_plan_indices

KW = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
          delta_fairness=True)
rng = np.random.default_rng(0)
K, P = 4096, 32
times = rng.uniform(1.0, 100.0, K)
counts = rng.integers(0, 50, K).astype(np.float64)
avail = rng.random(K) < 0.9
n_sel = 64
idx = random_plan_indices(rng, avail, n_sel, P)
plans = indices_to_plans(idx, K)

ref_d = scoring.score_plans(times, counts, plans, backend="jax", **KW)
ref_i = scoring.score_plan_indices(times, counts, idx, backend="jax", **KW)
for N in (2, 8):
    for got, ref in [
        (scoring.score_plans(times, counts, plans, backend="jax",
                             num_shards=N, **KW), ref_d),
        (scoring.score_plan_indices(times, counts, idx, backend="jax",
                                    num_shards=N, **KW), ref_i),
    ]:
        rel = float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref),
                                                          1e-12)))
        assert rel < 1e-5, (N, rel)
print("SCORING_OK")

skw = dict(alpha=4.0, beta=0.25, time_scale=3.0, fairness_scale=0.09,
           delta_fairness=True)
base = {}
for N in (1, 2, 8):
    sa = search.sa_search(np.random.default_rng(1), times, counts, avail,
                          n_sel, steps=6, chains=8, t0=1.0, cooling=0.9,
                          num_shards=N, **skw)
    ga = search.ga_search(np.random.default_rng(2), times, counts, avail,
                          n_sel, population=16, generations=4,
                          mutation_rate=0.3, num_shards=N, **skw)
    if N == 1:
        base = {"sa": sa, "ga": ga}
    else:
        assert np.array_equal(sa, base["sa"]), f"sa diverged at N={N}"
        assert np.array_equal(ga, base["ga"]), f"ga diverged at N={N}"
print("SEARCH_OK")

from repro.core.cost import CostModel
from repro.core.devices import DevicePool
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import SchedulingContext

def run_bods(num_shards):
    pool = DevicePool.heterogeneous(512, 2, seed=3)
    cm = CostModel(pool, alpha=4.0, beta=0.25, num_shards=num_shards)
    cm.calibrate([5.0, 5.0], n_sel=16)
    r2 = np.random.default_rng(11)
    counts2 = r2.integers(0, 8, 512).astype(np.float64)
    av = np.ones(512, bool)
    av[r2.choice(512, 100, replace=False)] = False
    et = pool.expected_times(0, 5.0)
    sched = get_scheduler("bods", cost_model=cm, seed=0,
                          num_candidates=64, init_points=4)
    out = []
    for r in range(6):
        ctx = SchedulingContext(job=0, round_idx=r, tau=5.0, n_sel=16,
                                available=av.copy(), counts=counts2.copy(),
                                expected_times=et)
        out.append(sched.schedule(ctx))
    return out

p1, p8 = run_bods(1), run_bods(8)
for a, b in zip(p1, p8):
    assert np.array_equal(a, b), "bods diverged"
print("BODS_OK")
"""


@pytest.mark.slow
def test_shard_map_parity_eight_devices():
    """Real shard_map on 8 forced host devices: scoring within relative
    f32 tolerance of the single lane; SA/GA/BODS decisions identical."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": os.path.join(REPO, "src"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
    })
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("SCORING_OK", "SEARCH_OK", "BODS_OK"):
        assert marker in out.stdout, (marker, out.stdout, out.stderr[-2000:])

"""CPU tests of the benchmark harness: the FLOP counter and peak table,
the trace reduction on a trace with known answers, each cell at a tiny size
through the harness, files found by name, and the refusal without a TPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench.run as bench_run
from bench.harness import cells, flops, trace
from bench.tests.tiny import PENDING, args, tiny_cell

GROUP_A = cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                       "paper-group-a.json"))
JOBS = {j["name"]: j for j in GROUP_A["jobs"]}
CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
CELLS += [n for n in PENDING if n not in CELLS]


@pytest.mark.parametrize("model,macs", [
    ("paper-vgg16", 332_111_872),
    ("paper-lenet5", 693_000),
    ("paper-cnn-a-noniid", 5_847_168),
])
def test_forward_macs_pinned(model, macs):
    j = JOBS[model]
    assert flops.forward_macs(j["layers"], j["input_shape"],
                              j["num_classes"]) == macs


def test_train_flops_are_three_passes_over_trained_samples():
    lenet = JOBS["paper-lenet5"]
    shard = flops.shard_width(lenet, GROUP_A)
    assert shard == 2400
    # 2400 // 64 = 37 full batches a epoch; the ragged 32 are not trained.
    assert flops.trained_samples(lenet, shard) == 5 * 37 * 64
    assert flops.train_flops_per_device(lenet, shard) == (
        3 * 2 * 693_000 * 5 * 37 * 64)
    assert [flops.shard_width(JOBS[m], GROUP_A) for m in
            ("paper-vgg16", "paper-cnn-a-noniid")] == [500, 480]


def test_configured_layers_are_the_programs_models():
    from repro.config.registry import get_arch
    from bench.harness import data

    for j in GROUP_A["jobs"]:
        data.check_model(j, get_arch(j["model"]))


def test_peak_table_is_keyed_by_device_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (10, 12)]
    assert trace.union_length(iv) == 3 + 1 + 2
    assert trace.gaps(iv, 0, 11) == [(3, 5), (6, 10)]
    assert trace.gaps([], 2, 4) == [(2, 4)]


def _known_view():
    """A hand-made trace with known answers, in the records ``load_events``
    makes: 100 ms window, one acquisition with two ops under it, one round
    of engine spans, one op later."""
    ms = 1e6
    plane = "/device:TPU:0"

    def dev(line, name, module, s, e):
        return {"plane": plane, "line": line, "name": name, "module": module,
                "start_ns": s * ms, "end_ns": e * ms}

    device = [
        dev("XLA Modules", "jit_run(12)", None, 10, 40),
        dev("XLA Ops", "%sort.1 = (f32[8]{0}, s32[8]{0}) sort(f32[8]{0} %a)",
            None, 10, 30),
        dev("XLA Ops", "%fusion = f32[8]{0:T(128)} fusion(f32[8]{0} %b)",
            None, 25, 40),
        dev("XLA Ops", "%copy = f32[8]{0} copy(f32[8]{0} %c)", "jit_other",
            60, 70),
    ]
    spans = [{"name": n, "start_ns": s * ms, "end_ns": e * ms, "args": {}}
             for n, s, e in (("bods_acquire", 5, 45), ("record", 50, 55),
                             ("ctx_build", 56, 58), ("dispatch", 58, 75))]
    return trace.TraceView(device, spans, (0.0, 100 * ms), {}, 1,
                           {"jobs": [{}]}, {"bf16_flops_per_s": 197e12})


def test_trace_reduction_on_a_known_trace():
    view = _known_view()
    assert view.window_s == pytest.approx(0.1)
    assert view.busy_s() == pytest.approx(0.040)          # [10,40] + [60,70]
    assert view.device_s_under("bods_acquire") == pytest.approx(0.035)
    expect = {"device_idle_share": 60.0, "bods_device_ms": 35.0,
              "bods_acquire_ms": 40.0, "engine_host_ms": 24.0}
    for name, value in expect.items():
        assert bench_run.read_metric(name, view) == pytest.approx(value)
    bd = view.breakdown()
    assert bd["device_ops"] == [
        ["jit_run(12)/%sort.1 sort", pytest.approx(0.020)],
        ["jit_run(12)/%fusion fusion", pytest.approx(0.015)],
        ["jit_other/%copy copy", pytest.approx(0.010)]]
    assert bd["idle_gaps"] == [["none", pytest.approx(0.030)],
                               ["record", pytest.approx(0.020)],
                               ["bods_acquire", pytest.approx(0.010)]]


def test_reader_returns_nothing_when_there_is_nothing_to_read():
    view = trace.TraceView([], [], (0.0, 1e9), {}, 0, GROUP_A,
                           {"bf16_flops_per_s": 197e12})
    for m in cells.benchmark()["per_layer"]:
        assert bench_run.read_metric(m["name"], view) is None, m["name"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_at_tiny_size_and_is_correct(name):
    cell = tiny_cell(name)
    res = bench_run.run(args(name), require_tpu=False, cell=cell)
    assert res["correct"], res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == set(cell.end_to_end)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"invalid_plans", "plan_est_rel_err", "plan_rank"} <= set(
        res["checks"])
    assert set(res["checks"]) <= set(cell.config["limits"])


def test_traced_run_reports_per_layer_metrics_only(tmp_path):
    cell = tiny_cell("groupA-sched")
    res = bench_run.run(args("groupA-sched", trace=1), require_tpu=False,
                        cell=cell, trace_dir=str(tmp_path))
    assert res["correct"]
    assert set(res["metrics"]) <= set(cell.per_layer)
    assert {"engine_host_ms", "schedule_ms"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_new_cell_traffic_and_metric_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = cells.benchmark()
    traffic = cells.load_json(os.path.join(cells.BENCH_DIR, "traffic",
                                           "sched-steady.json"))
    (root / "bench" / "traffic" / "sched-sparse.json").write_text(
        json.dumps(dict(traffic, check_share=0.5)))
    (root / "bench" / "metrics" / "traced_rounds.py").write_text(
        "def read(view):\n    return float(view.decisions) or None\n")
    bench["workloads"].append({
        "name": "groupA-sparse", "config": "paper-group-a",
        "traffic": "sched-sparse", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "traced_rounds", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "rounds_per_s", "workloads": ["groupA-sparse"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("groupA-sparse", str(root))
    assert cell.traffic["check_share"] == 0.5
    assert cell.config["name"] == "paper-group-a"
    assert "traced_rounds" in cell.per_layer
    assert cell.end_to_end == ("rounds_per_s", "setup_s")
    view = trace.TraceView([], [], (0.0, 1e9), {}, 1, cell.config, {})
    assert bench_run.read_metric("traced_rounds", view, str(root)) == 1.0


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "groupA-sched", "--seed", str(2**40), "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no TPU" in p.stderr


def test_seeds_are_32_bit_streams_of_any_seed():
    for seed in (0, 2**31 + 5, 2**63 + 11):
        s = cells.seeds(seed)
        assert all(0 <= v < 2**32 for v in s.values())
        assert s == cells.seeds(seed)
    assert cells.seeds(1) != cells.seeds(2)


def test_partition_is_the_papers_non_iid_split():
    from bench.harness import data

    cfg = dict(GROUP_A, num_devices=30)
    job = JOBS["paper-cnn-a-noniid"]
    part = data.noniid_partition(job, cfg, 5, 1)
    per_class = job["num_samples"] // job["num_classes"]
    assert part.shape == (30, 480)
    for row in part:
        classes = np.unique(row // per_class)
        assert len(classes) == 2 and len(np.unique(row)) == 480
    np.testing.assert_array_equal(part, data.noniid_partition(job, cfg, 5, 1))

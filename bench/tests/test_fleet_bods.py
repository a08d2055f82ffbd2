"""CPU tests of the ``fleet-bods`` cell's additions: the readers of the
spans inside the fused BODS decision and the engine round, on a hand-made
trace with known answers, and one traced run of the cell's own files with
the fleet cut to a CPU's size.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import copy

import pytest

import bench.run as bench_run
from bench.harness import cells, trace
from bench.tests.tiny import args

NEW = ("bods_mutate_ms", "bods_pack_ms", "bods_put_ms", "bods_observe_ms",
       "pool_sample_ms")


def _view(names=None):
    """Two fused BODS decisions and two engine rounds in a 100 ms window,
    with the spans the fleet's readers read; ``names`` keeps only those
    spans (the parent program has none of the new ones)."""
    ms = 1e6
    phases = (("bods_prepare", 2, 8), ("bods_mutate", 3, 7),
              ("bods_acquire", 8, 30), ("bods_stage", 8, 20),
              ("bods_pack", 8, 12), ("bods_put", 12, 20),
              ("dispatch", 31, 36), ("pool_sample", 31, 34),
              ("record", 40, 44), ("bods_observe", 41, 43),
              ("bods_prepare", 50, 53), ("bods_mutate", 50, 51),
              ("bods_acquire", 53, 70), ("bods_stage", 53, 60),
              ("bods_pack", 53, 55), ("bods_put", 55, 59),
              ("dispatch", 71, 74), ("pool_sample", 71, 72),
              ("record", 80, 86), ("bods_observe", 81, 84),
              ("bods_pack", 99, 101))    # not inside the window: not read
    spans = [{"name": n, "start_ns": s * ms, "end_ns": e * ms, "args": {}}
             for n, s, e in phases if names is None or n in names]
    return trace.TraceView([], spans, (0.0, 100 * ms), {}, 2, {"jobs": [{}]},
                           {"bf16_flops_per_s": 197e12})


def test_fleet_readers_on_a_known_trace():
    view = _view()
    expect = {"bods_mutate_ms": 2.5, "bods_pack_ms": 3.0, "bods_put_ms": 6.0,
              "bods_observe_ms": 2.5, "pool_sample_ms": 2.0}
    for name, value in expect.items():
        assert bench_run.read_metric(name, view) == pytest.approx(value), name


def test_fleet_readers_return_nothing_without_their_spans():
    """On the parent's trace (decisions and rounds, none of the new spans)
    each reader leaves its metric out instead of raising."""
    bare = _view(("bods_prepare", "bods_acquire", "bods_stage", "dispatch",
                  "record"))
    for name in NEW:
        assert bench_run.read_metric(name, bare) is None, name


def test_fleet_bods_files_run_traced_and_correct_at_a_cut_fleet(tmp_path):
    """The cell's own configuration and traffic, the fleet cut to K=20,000
    and a target of 30 (39 scheduled at the file's 1.3), everything else as
    the files say: 512 candidates, the three tenants, the limits."""
    cell = cells.load_cell("fleet-bods")
    assert set(NEW) <= set(cell.per_layer)
    cfg = copy.deepcopy(cell.config)
    assert (cfg["num_devices"], cfg["n_sel"], cfg["over_provision"],
            cfg["scheduler"]["candidates"]) == (342_477, 300, 1.3, 512)
    cfg["num_devices"], cfg["n_sel"] = 20_000, 30
    cut = cells.Cell(cell.name, cfg, cell.traffic, cell.chips,
                     cell.end_to_end, cell.per_layer)
    res = bench_run.run(args("fleet-bods", trace=1), require_tpu=False,
                        cell=cut, trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["checks"]["invalid_plans"]["value"] == 0
    assert set(res["metrics"]) == set(cell.per_layer)
    for name in NEW:
        assert res["metrics"][name]["value"] > 0, name

"""CPU test of the readers of the fused BODS decision's phases on a
hand-made trace with known answers.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import pytest

import bench.run as bench_run
from bench.harness import trace


def _decision_view():
    """A hand-made trace of two fused BODS decisions split into their
    phases, with known answers: 100 ms window, two rounds, the
    acquisition compiled as ``jit_bods_acquire``."""
    ms = 1e6
    plane = "/device:TPU:0"

    def dev(line, name, module, s, e):
        return {"plane": plane, "line": line, "name": name, "module": module,
                "start_ns": s * ms, "end_ns": e * ms}

    device = [
        dev("XLA Modules", "jit_bods_acquire(7)", None, 8, 12),
        dev("XLA Ops", "%sort = f32[8]{0} sort(f32[8]{0} %a)", None, 8, 11),
        dev("XLA Modules", "jit_bods_acquire(7)", None, 49, 56),
        dev("XLA Ops", "%fusion = f32[8]{0} fusion(f32[8]{0} %b)", None,
            49, 55),
        dev("XLA Modules", "jit_other(1)", None, 80, 85),
    ]
    phases = (("schedule", 1, 17), ("bods_prepare", 2, 5),
              ("bods_acquire", 5, 15), ("bods_stage", 5, 7),
              ("bods_launch", 7, 8), ("bods_wait", 8, 13),
              ("bods_readback", 13, 15), ("bods_readback", 15, 16),
              ("dispatch", 17, 19), ("record", 20, 22),
              ("schedule", 39, 62), ("bods_prepare", 40, 44),
              ("bods_acquire", 44, 60), ("bods_stage", 44, 47),
              ("bods_launch", 47, 49), ("bods_wait", 49, 57),
              ("bods_readback", 57, 60), ("bods_readback", 60, 61),
              ("record", 70, 72), ("schedule", 98, 103))
    spans = [{"name": n, "start_ns": s * ms, "end_ns": e * ms, "args": {}}
             for n, s, e in phases]
    return trace.TraceView(device, spans, (0.0, 100 * ms), {}, 2,
                           {"jobs": [{}]}, {"bf16_flops_per_s": 197e12})


def test_decision_phase_readers_on_a_known_trace():
    view = _decision_view()
    expect = {"bods_prepare_ms": 3.5, "bods_stage_ms": 2.5,
              "bods_launch_ms": 1.5, "bods_wait_ms": 6.5,
              "bods_readback_ms": 3.5, "bods_program_device_ms": 5.5,
              "bods_acquire_ms": 13.0, "traced_round_ms": 50.0,
              # spans cover [1,19] [20,22] [39,62] [70,72] and [98,100]
              "unspanned_ms": 26.5}
    for name, value in expect.items():
        assert bench_run.read_metric(name, view) == pytest.approx(value), name
    # Idle gaps are named by the phase open during them.
    assert [view.host_span_at(t * 1e6) for t in (3, 6, 7.5, 12, 14, 15.5)] == [
        "bods_prepare", "bods_stage", "bods_launch", "bods_wait",
        "bods_readback", "bods_readback"]


def test_decision_phase_readers_return_nothing_without_their_spans():
    """The parent program has no phase spans and no ``jit_bods_acquire``
    module: each new reader leaves its metric out instead of raising."""
    view = _decision_view()
    bare = trace.TraceView(
        [d for d in view.device if "bods_acquire" not in d["name"]],
        [s for s in view.spans if s["name"] in ("schedule", "bods_acquire")],
        view.window, {}, 2, {"jobs": [{}]}, {"bf16_flops_per_s": 197e12})
    for name in ("bods_prepare_ms", "bods_stage_ms", "bods_launch_ms",
                 "bods_wait_ms", "bods_readback_ms",
                 "bods_program_device_ms"):
        assert bench_run.read_metric(name, bare) is None, name

"""Observability layer tests: span tracer, Perfetto schema, event bus,
the spec's ``obs`` axis, the recompile counter, and the report CLI."""

import dataclasses
import json

import numpy as np
import pytest

from repro.experiment.spec import ExperimentSpec, JobSpec, PoolSpec
from repro.monitoring import EventBus, ObsSession, ObsSpec, Tracer
from repro.monitoring import report as rpt
from repro.monitoring import trace as trace_mod
from repro.monitoring.__main__ import main as monitoring_cli


# ---- span tracer ----


def test_disabled_span_is_shared_noop_and_records_nothing():
    t = Tracer()
    s = t.span("a", job=1)
    assert s is t.span("b")          # the shared singleton, zero allocation
    with s:
        pass
    t.counter("c", 1.0)
    t.instant("i")
    assert t.num_events == 0


def test_spans_nest_and_record_complete_events():
    t = Tracer(enabled=True)
    with t.span("outer", job=3):
        with t.span("inner"):
            pass
    evs = t.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
    # proper nesting: inner's [ts, ts+dur] sits inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"job": 3}


def test_annotate_attaches_args_on_live_span_and_is_noop_when_disabled():
    t = Tracer()
    off = t.span("a")
    off.annotate(bytes=3)            # the shared singleton ignores it
    assert off is t.span("b") and t.num_events == 0
    t.enabled = True
    with t.span("stage", what="plan") as live:
        live.annotate(arrays=2, bytes=12)
    (ev,) = t.events()
    assert ev["args"] == {"what": "plan", "arrays": 2, "bytes": 12}


@pytest.mark.parametrize("on", [False, True])
def test_profiler_annotation_built_only_for_live_spans(monkeypatch, on):
    built = []

    class Annotation:
        def __init__(self, name, **args):
            built.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kv):
            built[-1][1].update(kv)

    monkeypatch.setattr(trace_mod, "_ANNOTATION", Annotation)
    monkeypatch.setattr(trace_mod.get_tracer(), "enabled", on)
    s = trace_mod.span("schedule", job=1)
    assert (s is trace_mod._NOOP) is not on
    with s as live:
        live.annotate(bytes=4)
    with Tracer(enabled=on).span("inner"):
        pass
    trace_mod.clear()
    assert built == ([("schedule", {"job": 1, "bytes": 4}), ("inner", {})]
                     if on else [])


def test_spans_land_on_the_profilers_clock(tmp_path):
    """Every live span is a TraceMe on the profiler's host plane whose start
    lies one constant offset from the tracer's stamp: the offset a
    ``bench_sync``-style annotation (opened between two clock reads) gives."""
    import glob
    import time

    import jax

    t = Tracer(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        a = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("clock_sync"):
            b = time.perf_counter_ns()
        for i in range(12):
            with t.span(f"phase{i}", i=i):
                with t.span(f"inner{i}"):
                    time.sleep(1e-4)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, (ev.start_ns, ev.duration_ns))
    s0, d0 = host["clock_sync"]
    off = s0 + d0 / 2 - (a + b) / 2
    evs = t.events()
    assert len(evs) == 24
    delta = [host[e["name"]][0] - e["ts"] * 1e3 for e in evs]
    assert max(delta) - min(delta) < 50e3, delta
    assert max(abs(d - off) for d in delta) < 50e3, (delta, off)


def test_global_tracer_enable_disable():
    trace_mod.clear()
    assert not trace_mod.enabled()
    with trace_mod.span("off"):
        pass
    assert trace_mod.get_tracer().num_events == 0
    trace_mod.enable()
    try:
        with trace_mod.span("on"):
            pass
        trace_mod.counter("jit_recompiles", 2)
    finally:
        trace_mod.disable()
    evs = trace_mod.get_tracer().events()
    assert [e["name"] for e in evs] == ["on", "jit_recompiles"]
    assert rpt.recompile_count(evs) == 2
    trace_mod.clear()


def test_perfetto_schema_roundtrip(tmp_path):
    t = Tracer(enabled=True)
    with t.span("phase", k=1):
        pass
    t.counter("ctr", 5.0)
    t.instant("mark", why="x")
    p = tmp_path / "sub" / "trace.json"   # save creates parent dirs
    t.save(str(p), process_name="proc")
    d = json.load(open(p))
    assert set(d) == {"traceEvents", "displayTimeUnit"}
    evs = d["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "proc"
    by_ph = {e["ph"]: e for e in evs}
    assert by_ph["X"]["name"] == "phase" and by_ph["X"]["args"] == {"k": 1}
    assert by_ph["C"]["args"]["ctr"] == 5.0
    assert by_ph["i"]["s"] == "t"
    # load_trace accepts both the object form and a bare event array
    assert rpt.load_trace(str(p)) == evs
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(evs))
    assert rpt.load_trace(str(bare)) == evs


# ---- event bus ----


def test_bus_fan_out_and_error_isolation():
    bus = EventBus()
    seen_a, seen_b = [], []
    bus.subscribe("round", seen_a.append)
    bus.subscribe("round", lambda _: 1 / 0)   # must not break the fan-out
    bus.subscribe("round", seen_b.append)
    with pytest.warns(RuntimeWarning, match="round"):
        assert bus.publish("round", "r0") == 2
    assert bus.publish("round", "r1") == 2    # warns once per sink
    assert seen_a == ["r0", "r1"] == seen_b
    assert bus.errors == 2
    assert bus.publish("other", "x") == 0     # no sinks: no-op


def test_bus_unsubscribe():
    bus = EventBus()
    seen = []
    bus.subscribe("t", seen.append)
    assert bus.unsubscribe("t", seen.append)
    assert not bus.unsubscribe("t", seen.append)
    assert bus.publish("t", 1) == 0 and seen == []


# ---- the obs spec axis ----


def _tiny_spec(**obs):
    return ExperimentSpec(
        jobs=(JobSpec(name="j0", max_rounds=6, target_metric=2.0),),
        pool=PoolSpec(num_devices=12), scheduler="greedy", n_sel=3,
        obs=ObsSpec(**obs))


def test_obsspec_json_roundtrip_and_replace_merge(tmp_path):
    spec = _tiny_spec(trace_path="t.json", flush_every=4)
    back = ExperimentSpec.from_dict(json.loads(spec.to_json()))
    assert back == spec and back.obs.flush_every == 4
    # dict-merge replace (the CLI's --set obs.metrics_path=... path)
    merged = spec.replace(obs={"metrics_path": "m.jsonl"})
    assert merged.obs.trace_path == "t.json"      # preserved
    assert merged.obs.metrics_path == "m.jsonl"   # merged in
    assert merged.obs.active
    # specs without an obs block (pre-axis JSONs) load with the default
    d = spec.to_dict()
    del d["obs"]
    assert ExperimentSpec.from_dict(d).obs == ObsSpec()


def test_obsspec_active():
    assert not ObsSpec().active
    assert ObsSpec(enabled=True).active
    assert ObsSpec(metrics_path="m.jsonl").active


def test_obs_run_emits_trace_metrics_audit(tmp_path):
    tp, mp, ap = (str(tmp_path / n) for n in ("t.json", "m.jsonl", "a.jsonl"))
    spec = _tiny_spec(trace_path=tp, metrics_path=mp, audit_path=ap)
    res = spec.run()
    assert not trace_mod.enabled()       # session released the tracer
    evs = rpt.load_trace(tp)
    stats = rpt.phase_stats(evs)
    for phase in rpt.ENGINE_PHASES + ("engine_run",):
        assert phase in stats, phase
    assert rpt.coverage(stats) >= 0.9
    metrics = rpt.load_metrics(mp)
    assert len(metrics) == len(res.records)
    assert {m["job"] for m in metrics} == {0}
    audit = [json.loads(l) for l in open(ap)]
    assert len(audit) == len(res.records)
    assert all(a["scheduler"] == "greedy" for a in audit)


def _assert_records_identical(plain, traced):
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for k, va in da.items():
            if isinstance(va, np.ndarray):
                assert np.array_equal(va, db[k]), k
            else:
                assert va == db[k] or (va is None and db[k] is None), k


def test_obs_disabled_run_is_bitwise_identical(tmp_path):
    plain = _tiny_spec().run()
    traced = _tiny_spec(trace_path=str(tmp_path / "t.json"),
                        metrics_path=str(tmp_path / "m.jsonl")).run()
    _assert_records_identical(plain.records, traced.records)


def test_full_obs_axis_on_warm_quickstart(tmp_path):
    """The quickstart (BODS) with its compiles warmed: the full obs axis
    leaves the round records bitwise identical, and the engine phase spans
    cover at least 90% of the ``engine_run`` wall-clock."""
    from repro.experiment.presets import get_preset

    spec = get_preset("quickstart")
    spec = spec.replace(jobs=tuple(
        dataclasses.replace(j, max_rounds=10, target_metric=2.0)
        for j in spec.jobs))
    plain = spec.run()
    tp = str(tmp_path / "t.json")
    traced = spec.replace(obs={
        "trace_path": tp, "metrics_path": str(tmp_path / "m.jsonl"),
        "audit_path": str(tmp_path / "a.jsonl")}).run()
    _assert_records_identical(plain.records, traced.records)
    assert rpt.coverage(rpt.phase_stats(rpt.load_trace(tp))) >= 0.9


def test_engine_bus_topics(tmp_path):
    spec = _tiny_spec(enabled=True)
    ex = spec.build()
    eng = ex.engine
    assert eng.events is not None and eng.obs is not None
    begun, rounds, done = [], [], []
    eng.events.subscribe("round_begin", begun.append)
    eng.events.subscribe("round", rounds.append)
    eng.events.subscribe("job_done", done.append)
    ex.run()
    assert len(begun) == len(rounds) > 0
    assert [d["job"] for d in done] == [0]
    assert all(b["est_cost"] is not None or True for b in begun)
    assert all(r.job == 0 for r in rounds)   # RoundRecord payloads


# ---- recompile counter ----


def test_runtime_recompile_counter_matches_jit_probe():
    from repro.config.base import JobConfig
    from repro.configs.paper_models import lenet5
    from repro.data.synthetic import make_classification_dataset
    from repro.fl.partition import noniid_partition
    from repro.fl.runtime import FusedMultiRuntime, _fused_group_round

    cfg = dataclasses.replace(
        lenet5(), name="tiny-obs", input_shape=(8, 8, 1),
        cnn_spec=(("flatten",), ("fc", 8)))
    x, y = make_classification_dataset(600, cfg.input_shape, cfg.num_classes,
                                       noise=1.0, seed=0)
    ex, ey = make_classification_dataset(60, cfg.input_shape, cfg.num_classes,
                                         noise=1.0, seed=1)
    part = noniid_partition(y, 12, seed=0)
    job = JobConfig(job_id=0, model=cfg, target_metric=2.0,
                    local_epochs=1, batch_size=4, lr=0.05)
    fused = FusedMultiRuntime([job], [(x, y, part, ex, ey)], seed=0,
                              buckets=(4, 8, 12))
    assert fused.recompiles == 0
    before = _fused_group_round._cache_size()
    rng = np.random.default_rng(5)
    for r in range(10):
        n = int(rng.integers(1, 13))
        fused.run_round(0, rng.choice(12, n, replace=False), r)
    assert fused.recompiles == _fused_group_round._cache_size() - before > 0


# ---- report CLI ----


def _fake_trace(tmp_path, name="trace.json", p50_scale=1.0):
    evs = [{"name": "engine_run", "ph": "X", "ts": 0.0, "dur": 4000.0,
            "pid": 1, "tid": 1, "args": {}}]
    for i in range(4):
        for phase in rpt.ENGINE_PHASES:
            evs.append({"name": phase, "ph": "X", "ts": i * 1000.0,
                        "dur": 190.0 * p50_scale, "pid": 1, "tid": 1,
                        "args": {"job": 0}})
    evs.append({"name": "jit_recompiles", "ph": "C", "ts": 500.0, "pid": 1,
                "tid": 1, "args": {"jit_recompiles": 3}})
    p = tmp_path / name
    p.write_text(json.dumps({"traceEvents": evs}))
    return str(p)


def test_report_cli_smoke(tmp_path, capsys):
    p = _fake_trace(tmp_path)
    out_json = tmp_path / "report.json"
    assert monitoring_cli(["report", p, "--json", str(out_json)]) == 0
    out = capsys.readouterr().out
    assert "engine_run" in out and "recompiles=3" in out
    assert "coverage" in out
    rep = json.load(open(out_json))
    assert rep["recompiles"] == 3
    assert rep["coverage"] == pytest.approx(0.95)
    # empty trace -> exit 1
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert monitoring_cli(["report", str(empty)]) == 1


def test_report_cli_diff(tmp_path, capsys):
    a = _fake_trace(tmp_path, "a.json")
    b = _fake_trace(tmp_path, "b.json", p50_scale=2.0)
    assert monitoring_cli(["report", a, "--diff", b]) == 0
    assert "ratio" in capsys.readouterr().out

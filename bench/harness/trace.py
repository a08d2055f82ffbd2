"""Device trace and host spans on one clock, and their reduction.

``Session`` runs the JAX profiler over part of the window and the
program's own span tracer beside it. The tracer stamps spans with
``time.perf_counter_ns``; a ``bench_sync`` annotation, opened between two
reads of that clock right after the profiler starts, gives the offset
onto the profiler's clock, so every span lands on the trace's timeline.

``load_events`` flattens an ``.xplane.pb`` into plain records (device ops
and modules, with their plane and line) that ``TraceView`` reduces: the
busy union, device time by module execution or by the host span an op
ran under, and idle gaps named by the host span open during each. The
reduction's test builds records of the same form by hand.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

SYNC = "bench_sync"


def load_events(xplane_path: str) -> dict:
    """{"device": [{plane, line, name, module, start_ns, end_ns}],
    "sync": (start_ns, end_ns) or None} from one profile."""
    import jax

    pd = jax.profiler.ProfileData.from_file(xplane_path)
    device, sync = [], None
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if not is_device:
                    if ev.name == SYNC and sync is None:
                        sync = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    continue
                module = None
                for k, v in ev.stats:
                    if k == "hlo_module":
                        module = v
                device.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "module": module, "start_ns": float(ev.start_ns),
                    "end_ns": float(ev.start_ns + ev.duration_ns)})
    return {"device": device, "sync": sync}


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle stretches of [lo, hi] not covered by any interval."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def op_label(name: str) -> str:
    """``%sort.1 sort`` from an op event named by its whole HLO
    instruction (``%sort.1 = (f32[...], ...) sort(...), ...``)."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def _attach_modules(device: List[dict]) -> None:
    """Give each op without a module name the module running around it on
    the same plane's ``XLA Modules`` line."""
    mods: Dict[str, list] = {}
    for d in device:
        if d["line"] == "XLA Modules":
            mods.setdefault(d["plane"], []).append(d)
    for lst in mods.values():
        lst.sort(key=lambda d: d["start_ns"])
    starts = {p: [d["start_ns"] for d in lst] for p, lst in mods.items()}
    for d in device:
        if d["module"] or d["line"] == "XLA Modules" or d["plane"] not in mods:
            continue
        i = bisect.bisect_right(starts[d["plane"]], d["start_ns"]) - 1
        if i >= 0:
            m = mods[d["plane"]][i]
            if d["start_ns"] <= m["end_ns"]:
                d["module"] = m["name"]


class TraceView:
    """One traced window: device records, host spans (already on the
    trace clock) and what the engine did meanwhile. Per-layer readers take
    their numbers from here."""

    def __init__(self, device: List[dict], spans: List[dict],
                 window: Tuple[float, float], cohorts: Dict[str, int],
                 decisions: int, config: dict, peaks: dict):
        self.window = window
        lo, hi = window
        self.device = [d for d in device if d["end_ns"] > lo
                       and d["start_ns"] < hi]
        _attach_modules(self.device)
        self.spans = spans
        self.cohorts = cohorts      # "job/round" -> devices that trained
        self.decisions = decisions
        self.config = config
        self.peaks = peaks

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def planes(self) -> List[str]:
        return sorted({d["plane"] for d in self.device})

    @property
    def n_planes(self) -> int:
        return max(1, len(self.planes()))

    def ops(self, plane: Optional[str] = None) -> List[dict]:
        """Op-level device records (the ``XLA Ops`` line, or every record
        of a plane that has no such line)."""
        out = [d for d in self.device
               if plane is None or d["plane"] == plane]
        lines = {d["line"] for d in out}
        if "XLA Ops" in lines:
            out = [d for d in out if d["line"] == "XLA Ops"]
        return out

    def clipped(self, recs: Sequence[dict]) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(max(r["start_ns"], lo), min(r["end_ns"], hi)) for r in recs]

    def busy_s(self) -> float:
        """Busy seconds, averaged over the device planes seen."""
        return sum(union_length(self.clipped(self.ops(p)))
                   for p in self.planes()) / self.n_planes / 1e9

    def spans_named(self, name: str) -> List[dict]:
        return [s for s in self.spans if s["name"] == name
                and s["start_ns"] >= self.window[0]
                and s["end_ns"] <= self.window[1]]

    def span_ms(self, names: Sequence[str]) -> float:
        return sum(s["end_ns"] - s["start_ns"] for n in names
                   for s in self.spans_named(n)) / 1e6

    def device_s_under(self, name: str) -> float:
        """Device seconds of ops that started inside a ``name`` span."""
        spans = sorted((s["start_ns"], s["end_ns"])
                       for s in self.spans_named(name))
        if not spans:
            return 0.0
        starts = [s for s, _ in spans]
        total = 0.0
        for r in self.ops():
            i = bisect.bisect_right(starts, r["start_ns"]) - 1
            if i >= 0 and r["start_ns"] <= spans[i][1]:
                total += r["end_ns"] - r["start_ns"]
        return total / 1e9 / self.n_planes

    def rounds(self) -> int:
        """Engine rounds recorded inside the window (``record`` spans)."""
        return len(self.spans_named("record"))

    def module_runs(self, prefix: str) -> List[dict]:
        """Executions (``XLA Modules`` records) of modules whose name starts
        with ``prefix`` that began inside the window."""
        lo, hi = self.window
        return [d for d in self.device if d["line"] == "XLA Modules"
                and d["name"].startswith(prefix) and lo <= d["start_ns"] < hi]

    def module_rounds(self, prefix: str) -> List[Tuple[dict, str]]:
        """(execution, "job/round") for the ``prefix`` executions in the
        trace.

        Rebuilt from the engine's spans, which are recorded from the start
        of warm-up: a ``dispatch`` span queues its job's round, each
        ``fused_round`` span launches the queued round of the lowest job id
        (the runtime flushes its one-job groups in job order), and the
        device runs programs in launch order. The window opens on an idle
        device, so the k-th execution in it is the k-th launch in it."""
        events = sorted(
            (s for s in self.spans if s["name"] in ("dispatch", "fused_round")),
            key=lambda s: s["start_ns"])
        queued, launches = [], []
        for s in events:
            if s["name"] == "dispatch":
                queued.append((s["args"]["job"], s["args"]["round"]))
            elif queued:
                queued.sort()
                job, rnd = queued.pop(0)
                if s["start_ns"] >= self.window[0]:
                    launches.append(f"{job}/{rnd}")
        runs = sorted((d for d in self.device if d["line"] == "XLA Modules"
                       and d["name"].startswith(prefix)),
                      key=lambda d: d["start_ns"])
        return list(zip(runs, launches))

    def breakdown(self, top: int = 10) -> dict:
        by_op: Dict[str, float] = {}
        for (s, e), r in zip(self.clipped(self.ops()), self.ops()):
            key = f"{r['module'] or '?'}/{op_label(r['name'])}"
            by_op[key] = by_op.get(key, 0.0) + (e - s) / 1e9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(gaps(self.clipped(self.ops()), *self.window),
                      key=lambda g: g[0] - g[1])[:top]
        named = [(self.host_span_at((s + e) / 2), (e - s) / 1e9)
                 for s, e in idle]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in named]}

    def host_span_at(self, t: float) -> str:
        """Innermost host span open at ``t`` ("none" outside all)."""
        best = None
        for s in self.spans:
            if s["start_ns"] <= t <= s["end_ns"] and (
                    best is None or s["start_ns"] >= best["start_ns"]):
                best = s
        return best["name"] if best else "none"


class Session:
    """Profiler plus span tracer over [start, stop]."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.offset_ns = 0.0
        self.window_ns = (0.0, 0.0)

    def spans_on(self) -> None:
        """Start the program's span tracer (before warm-up, so the spans
        that queued the window's first rounds are there too)."""
        from repro.monitoring import trace as mtrace

        mtrace.clear()
        mtrace.enable()

    def start(self) -> None:
        import jax

        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        a = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(SYNC):
            b = time.perf_counter_ns()
        self._sync_perf = (a + b) / 2
        self._t0 = time.perf_counter_ns()

    def stop(self) -> None:
        import jax

        from repro.monitoring import trace as mtrace

        self._t1 = time.perf_counter_ns()
        mtrace.disable()
        self._events = mtrace.get_tracer().events()
        mtrace.clear()
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return max(paths, key=os.path.getmtime)

    def reduce(self, cohorts: Dict[str, int], decisions: int, config: dict,
               peaks: dict) -> TraceView:
        ev = load_events(self.xplane())
        if ev["sync"] is None:
            raise RuntimeError(f"no {SYNC} annotation in the trace")
        off = (ev["sync"][0] + ev["sync"][1]) / 2 - self._sync_perf
        spans = [{"name": e["name"], "start_ns": e["ts"] * 1e3 + off,
                  "end_ns": (e["ts"] + e["dur"]) * 1e3 + off,
                  "args": e.get("args", {})}
                 for e in self._events if e.get("ph") == "X"]
        window = (self._t0 + off, self._t1 + off)
        with open(os.path.join(self.out_dir, "host.json"), "w") as f:
            json.dump({"spans": spans, "window": window, "cohorts": cohorts,
                       "decisions": decisions}, f)
        return TraceView(ev["device"], spans, window, cohorts, decisions,
                         config, peaks)

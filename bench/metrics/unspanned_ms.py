"""Milliseconds per engine round of the traced window in which no program
span is open: what the spans leave unexplained (the engine's event loop,
the harness's driving loop, anything else outside a span)."""

from bench.harness.trace import union_length


def read(view):
    rounds = len(view.spans_named("record"))
    if not rounds:
        return None
    lo, hi = view.window
    covered = union_length([(max(s["start_ns"], lo), min(s["end_ns"], hi))
                            for s in view.spans
                            if s["end_ns"] > lo and s["start_ns"] < hi])
    return (hi - lo - covered) / 1e6 / rounds

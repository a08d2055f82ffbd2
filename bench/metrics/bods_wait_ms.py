"""Host milliseconds per fused BODS decision in ``bods_wait``: blocked on
the acquisition's outputs (opened only while tracing; untraced, the plan's
read-back blocks at the same point)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_wait"):
        return None
    return view.span_ms(("bods_wait",)) / decisions

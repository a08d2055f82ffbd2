"""Host milliseconds per fused BODS decision in ``bods_prepare``: the
availability check, the residual scale, the host mutant loop, the program
lookup and the seed draw, before the ``bods_acquire`` span opens.
Decisions are the ``bods_acquire`` spans in the traced window."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_prepare"):
        return None
    return view.span_ms(("bods_prepare",)) / decisions

"""Host milliseconds per fused BODS decision in ``bods_launch``: the call
of the compiled acquisition until it returns (dispatch, not device work)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_launch"):
        return None
    return view.span_ms(("bods_launch",)) / decisions

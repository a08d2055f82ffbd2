"""Host milliseconds per fused BODS decision in ``bods_pack``: writing the
decision's inputs into the one uint32 buffer (inside ``bods_stage``; its
arg ``words`` is the buffer's length)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_pack"):
        return None
    return view.span_ms(("bods_pack",)) / decisions

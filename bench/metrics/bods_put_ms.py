"""Host milliseconds per fused BODS decision in ``bods_put``: the one
host-to-device transfer of the packed buffer (inside ``bods_stage``; its
arg ``bytes`` is the buffer's size)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_put"):
        return None
    return view.span_ms(("bods_put",)) / decisions

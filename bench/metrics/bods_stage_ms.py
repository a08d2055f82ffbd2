"""Host milliseconds per fused BODS decision in ``bods_stage``: the
host-to-device transfers of the acquisition's arguments (the span's
``arrays`` and ``bytes`` say how many and how large)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_stage"):
        return None
    return view.span_ms(("bods_stage",)) / decisions

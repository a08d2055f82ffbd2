"""Host milliseconds per fused BODS decision in ``bods_mutate``: the host
draw of the local-search mutants of the best observed plan (inside
``bods_prepare``; its args give the fleet's ``k`` and the ``mutants``).
Decisions are the ``bods_acquire`` spans in the traced window."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_mutate"):
        return None
    return view.span_ms(("bods_mutate",)) / decisions

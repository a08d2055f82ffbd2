"""Mean ``bods_acquire`` span per fused BODS decision: transfers in, the
on-device acquisition, and the chosen plan back on the host."""


def read(view):
    spans = view.spans_named("bods_acquire")
    if not spans:
        return None
    return view.span_ms(("bods_acquire",)) / len(spans)

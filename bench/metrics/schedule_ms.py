"""Mean ``schedule`` span per decision: the scheduler's whole call, host
work and device search together (with real training it also waits behind
queued device work)."""


def read(view):
    spans = view.spans_named("schedule")
    if not spans:
        return None
    return view.span_ms(("schedule",)) / len(spans)

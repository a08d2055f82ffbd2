"""Host milliseconds per engine round in ``bods_observe``: BODS turning the
round's realized cost into an observation (featurizing the K-wide plan,
inside ``record``), over the rounds recorded in the traced window."""


def read(view):
    rounds = view.rounds()
    if not rounds or not view.spans_named("bods_observe"):
        return None
    return view.span_ms(("bods_observe",)) / rounds

"""Host milliseconds per fused BODS decision in ``bods_readback``: the
device-to-host reads, both the plan with its estimate (``what="plan"``,
inside ``bods_acquire``) and the EI check after it (``what="ei"``)."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    if not decisions or not view.spans_named("bods_readback"):
        return None
    return view.span_ms(("bods_readback",)) / decisions

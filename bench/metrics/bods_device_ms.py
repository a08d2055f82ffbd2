"""Device milliseconds per fused BODS decision: device ops that started
inside a ``bods_acquire`` span. The acquisition compiles as a generic
``jit_run``, so ops are attributed by the host span around them, not by
module name."""


def read(view):
    spans = view.spans_named("bods_acquire")
    if not spans:
        return None
    return view.device_s_under("bods_acquire") * 1e3 / len(spans)

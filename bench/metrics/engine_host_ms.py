"""Mean host milliseconds per engine round in the engine's own spans:
context build, dispatch (fault draws, over-selection cut, occupancy) and
record (bookkeeping and the scheduler's observe), over the rounds recorded
in the traced window."""


def read(view):
    n = view.rounds()
    if not n:
        return None
    return view.span_ms(("ctx_build", "dispatch", "record")) / n

"""Milliseconds of the traced window per engine round recorded in it
(``record`` spans): the traced program's own round time. Beside the
untraced ``rounds_per_s``, ``traced_round_ms * rounds_per_s / 1000 - 1``
is the whole cost of tracing, profiler included."""


def read(view):
    rounds = len(view.spans_named("record"))
    if not rounds:
        return None
    return (view.window[1] - view.window[0]) / 1e6 / rounds

"""Host milliseconds per engine round in ``pool_sample``: the K realized
device times the engine draws for a launched round, with straggler
multipliers and the wait for busy devices added (inside ``dispatch``),
over the rounds recorded in the traced window."""


def read(view):
    rounds = view.rounds()
    if not rounds or not view.spans_named("pool_sample"):
        return None
    return view.span_ms(("pool_sample",)) / rounds

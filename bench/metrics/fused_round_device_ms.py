"""Mean device milliseconds of one fused FL round: whole executions of
``jit__fused_group_round`` that began in the traced window (one execution
trains one job's round)."""


def read(view):
    runs = view.module_runs("jit__fused_group_round")
    if not runs:
        return None
    return sum(r["end_ns"] - r["start_ns"] for r in runs) / len(runs) / 1e6

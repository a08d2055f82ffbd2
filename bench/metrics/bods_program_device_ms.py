"""Device milliseconds per fused BODS decision by module name: whole
executions of ``jit_bods_acquire`` that began in the traced window, over
the ``bods_acquire`` spans in it. Beside ``bods_device_ms``, which
attributes ops by the host span open when they start."""


def read(view):
    decisions = len(view.spans_named("bods_acquire"))
    runs = view.module_runs("jit_bods_acquire")
    if not decisions or not runs:
        return None
    return sum(r["end_ns"] - r["start_ns"] for r in runs) / decisions / 1e6

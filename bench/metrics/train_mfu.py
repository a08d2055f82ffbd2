"""Model FLOPs trained in the traced window over the window's length times
the chip's bf16 peak, in %. Each fused-round execution is credited with
its round's FLOPs (three forward passes over the samples its cohort
trained: ``bench/harness/flops.py``) in the share of it that ran inside
the window. Padded cohort lanes, the ragged tail of each shard and the
held-out evaluation count nothing."""

from bench.harness import flops


def read(view):
    jobs = view.config["jobs"]
    if "layers" not in jobs[0]:
        return None
    lo, hi = view.window
    total = 0.0
    for run, key in view.module_rounds("jit__fused_group_round"):
        inside = min(run["end_ns"], hi) - max(run["start_ns"], lo)
        if inside <= 0 or key not in view.cohorts:
            continue
        job = jobs[int(key.split("/")[0])]
        shard = flops.shard_width(job, view.config)
        work = view.cohorts[key] * flops.train_flops_per_device(job, shard)
        total += work * inside / (run["end_ns"] - run["start_ns"])
    if total == 0.0:
        return None
    return 100.0 * total / (view.window_s * view.peaks["bf16_flops_per_s"])

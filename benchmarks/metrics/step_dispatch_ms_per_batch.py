"""step_dispatch_ms_per_batch: see step_dispatch_ms_per_batch.json beside this file."""

from statistics import median

from harness import timeline


def read(run):
    tl = timeline.of_run(run)
    steps = tl.spans("train_step") if tl else []
    return median(s.dur for s in steps) / 1e6 if steps else None

"""fit_loop_ms_per_batch: see fit_loop_ms_per_batch.json beside this file."""

from statistics import median

from harness import timeline


def read(run):
    tl = timeline.of_run(run)
    held = tl.spans("consume") if tl else []
    return median(s.self_ns for s in held) / 1e6 if held else None

"""adaptive_step_share: see adaptive_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    adaptive = family_sum(run["counters"], "dmlc_fit_adaptive_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return adaptive / steps if adaptive is not None and steps else None

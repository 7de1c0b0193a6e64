"""run_sum_step_share: see run_sum_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    scan = family_sum(run["counters"], "dmlc_fit_run_sum_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return scan / steps if scan is not None and steps else None

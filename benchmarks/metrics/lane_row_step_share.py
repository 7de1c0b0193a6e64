"""lane_row_step_share: see lane_row_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    lane = family_sum(run["counters"], "dmlc_fit_lane_row_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return lane / steps if lane is not None and steps else None

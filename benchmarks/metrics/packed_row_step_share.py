"""packed_row_step_share: see packed_row_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    packed = family_sum(run["counters"], "dmlc_fit_packed_row_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return packed / steps if packed is not None and steps else None

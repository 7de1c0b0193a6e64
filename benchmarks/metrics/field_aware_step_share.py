"""field_aware_step_share: see field_aware_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    aware = family_sum(run["counters"], "dmlc_fit_field_aware_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return aware / steps if aware is not None and steps else None

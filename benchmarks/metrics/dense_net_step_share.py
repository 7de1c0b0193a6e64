"""dense_net_step_share: see dense_net_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    dense = family_sum(run["counters"], "dmlc_fit_dense_net_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return dense / steps if dense is not None and steps else None

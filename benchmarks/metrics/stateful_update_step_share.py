"""stateful_update_step_share: see stateful_update_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    stateful = family_sum(
        run["counters"], "dmlc_fit_stateful_update_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return stateful / steps if stateful is not None and steps else None

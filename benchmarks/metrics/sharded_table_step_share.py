"""sharded_table_step_share: see sharded_table_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    sharded = family_sum(run["counters"], "dmlc_fit_sharded_table_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return sharded / steps if sharded is not None and steps else None

"""step_compiles_in_window: see step_compiles_in_window.json beside this file."""

from harness.window import family_sum


def read(run):
    return family_sum(run["counters"], "dmlc_xla_compiles_total", "") or 0.0

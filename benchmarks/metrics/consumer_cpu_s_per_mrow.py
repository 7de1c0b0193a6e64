"""consumer_cpu_s_per_mrow: see consumer_cpu_s_per_mrow.json beside this file."""

from harness.window import family_sum


def read(run):
    ns = family_sum(
        run["counters"], 'dmlc_stage_cpu_ns{stage="consumer"}', ":sum")
    return ns / 1e9 / (run["rows"] / 1e6) if ns and run["rows"] else None

"""feed_host_wait_share: see feed_host_wait_share.json beside this file."""

from harness.window import family_sum


def read(run):
    wait = family_sum(run["counters"], "dmlc_feed_host_wait_ns", ":sum")
    return None if wait is None else wait / 1e9 / run["window_s"]

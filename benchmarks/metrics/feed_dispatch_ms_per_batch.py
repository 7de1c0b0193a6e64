"""feed_dispatch_ms_per_batch: see feed_dispatch_ms_per_batch.json beside this file."""

from harness.window import family_sum


def read(run):
    ns = family_sum(run["counters"], "dmlc_feed_dispatch_ns", ":sum")
    n = family_sum(run["counters"], "dmlc_feed_dispatch_ns", ":count")
    return None if not n else ns / n / 1e6

"""feed_put_ms_per_batch: see feed_put_ms_per_batch.json beside this file."""

from harness.window import family_sum


def read(run):
    ns = family_sum(run["counters"], "dmlc_feed_put_ns", ":sum")
    n = family_sum(run["counters"], "dmlc_feed_batches_total")
    return ns / n / 1e6 if ns is not None and n else None

"""put_unlanded_share: see put_unlanded_share.json beside this file."""

from harness.window import family_sum


def read(run):
    late = family_sum(run["counters"], "dmlc_feed_unlanded_deliveries_total")
    n = family_sum(run["counters"], "dmlc_feed_batches_total")
    return late / n if late is not None and n else None

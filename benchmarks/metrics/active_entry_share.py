"""active_entry_share: see active_entry_share.json beside this file."""

from harness.window import family_sum


def read(run):
    active = family_sum(run["counters"], "dmlc_fit_active_entries_total")
    entries = family_sum(run["counters"], "dmlc_fit_entries_total")
    return active / entries if active is not None and entries else None

"""rows_per_s: see rows_per_s.json beside this file."""


def read(run):
    return run["rows"] / run["window_s"]

"""setup_s: see setup_s.json beside this file."""


def read(run):
    return run["setup_s"]

"""device_idle_share: see device_idle_share.json beside this file."""


def read(run):
    tr = run["trace"]
    return None if not tr else 1.0 - tr["busy_s"] / tr["window_s"]

"""parse_wait_share: see parse_wait_share.json beside this file."""


def read(run):
    wait = run["pipeline"].get("consumer_wait_ns")
    return None if wait is None else wait / 1e9 / run["window_s"]

"""collective_exposed_share: see collective_exposed_share.json beside this file."""


def read(run):
    tr = run["trace"]
    if not tr or run["chips"] < 2 or not tr["program_s"]:
        return None
    return tr["collective_exposed_s"] / tr["program_s"]

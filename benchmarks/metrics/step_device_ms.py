"""step_device_ms: see step_device_ms.json beside this file."""


def read(run):
    return run["trace"]["step_device_ms"] if run["trace"] else None

"""drain_wake_ms: see drain_wake_ms.json beside this file."""

from statistics import median

from harness import timeline


def wakes(run):
    """ms from the device's last run to the host's wake-up, a restart."""
    tl = timeline.of_run(run)
    if tl is None:
        return []
    out = []
    for drain in tl.spans("drain_wait"):
        ended = [r[1] for r in tl.runs if r[1] <= drain.end]
        if ended:
            out.append((drain.end - max(ended)) / 1e6)
    return out


def note(run):
    return {"ms": wakes(run)}


def read(run):
    found = wakes(run)
    return median(found) if found else None

"""restart_idle_ms: see restart_idle_ms.json beside this file."""

from statistics import median

from harness import timeline, xplane

DRAIN = "loss_readback"  # the fit loop's one wait for the device


def gaps(run):
    """[(idle interval, {span name: ns of it})] of the window's restarts."""
    tl = timeline.of_run(run)
    if tl is None:
        return []
    idle = xplane.subtract([[tl.lo, tl.hi]], tl.busy())
    out = []
    for drain in tl.spans(DRAIN):
        # the device has drained when the wait ends
        gap = next((g for g in idle if g[1] > drain.end), None)
        if gap is None or gap[1] >= tl.hi:  # the window ends in it
            continue
        by_name, named = {}, 0.0
        for span in tl.threads[drain.thread]:
            if span.end <= gap[0] or span.start >= gap[1]:
                continue
            ns = xplane.total(xplane.clip(span.self_intervals(), *gap))
            if ns:
                by_name[span.name] = by_name.get(span.name, 0.0) + ns
                named += ns
        by_name["none"] = gap[1] - gap[0] - named
        out.append((gap, by_name))
    return out


def note(run):
    """The split by host span, ms a restart (the mean, when the window
    holds several restarts)."""
    found = gaps(run)
    split = {}
    for _, by_name in found:
        for name, ns in by_name.items():
            split[name] = split.get(name, 0.0) + ns / 1e6 / len(found)
    return {"restarts": len(found), "split_ms": split}


def read(run):
    found = gaps(run)
    return median((g[1] - g[0]) / 1e6 for g, _ in found) if found else None

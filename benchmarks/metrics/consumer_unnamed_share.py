"""consumer_unnamed_share: see consumer_unnamed_share.json beside this file."""

from harness import timeline, xplane


def cover(run):
    """(window ns, {span name: self ns}) of the consumer thread."""
    tl = timeline.of_run(run)
    spans = timeline.listened(run, tl, "train_step") if tl else None
    if not spans:
        return None
    by_name = {}
    for s in spans:
        by_name[s.name] = by_name.get(s.name, 0.0) + s.self_ns
    return tl.hi - tl.lo, by_name, spans


def note(run):
    window, by_name, _ = cover(run)
    return {"self_share": {k: v / window for k, v in sorted(by_name.items())}}


def read(run):
    found = cover(run)
    if found is None:
        return None
    window, _, spans = found
    named = xplane.union((s.start, s.end) for s in spans
                         if not s.name.startswith("bench."))
    return 1.0 - xplane.total(named) / window

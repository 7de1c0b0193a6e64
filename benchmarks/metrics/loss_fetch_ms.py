"""loss_fetch_ms: see loss_fetch_ms.json beside this file."""

from statistics import median

from harness import timeline


def fetches(run):
    tl = timeline.of_run(run)
    return tl.spans("loss_fetch") if tl else []


def note(run):
    found = fetches(run)
    return {"ms": [s.dur / 1e6 for s in found],
            "scalars": [s.args.get("scalars") for s in found]}


def read(run):
    found = fetches(run)
    return median(s.dur for s in found) / 1e6 if found else None

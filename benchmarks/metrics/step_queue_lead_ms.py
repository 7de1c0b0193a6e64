"""step_queue_lead_ms: see step_queue_lead_ms.json beside this file."""

from statistics import median

from harness import timeline


def leads(run):
    tl = timeline.of_run(run)
    if tl is None:
        return []
    return [(r[0] - s.end) / 1e6
            for s, r in tl.launches("train_step", drain="loss_readback")]


def note(run):
    found = leads(run)
    return {"steps": len(found), "min_ms": min(found), "max_ms": max(found)}


def read(run):
    found = leads(run)
    return median(found) if found else None

"""epoch_restart_ms: see epoch_restart_ms.json beside this file."""

from statistics import median


def read(run):
    spans = run["spans"]
    first_steps = sorted(s["ts"] for s in spans if s["name"] == "train_step")
    gaps = []
    for restart in (s for s in spans if s["name"] == "bench.restart"):
        # the pass before it: the bench.epoch span that ended last
        ended = [s["ts"] + s["dur"] for s in spans
                 if s["name"] == "bench.epoch"
                 and s["ts"] + s["dur"] <= restart["ts"]]
        after = [ts for ts in first_steps if ts >= restart["ts"]]
        if ended and after:
            gaps.append((after[0] - max(ended)) / 1e3)  # us -> ms
    return median(gaps) if gaps else None

"""step_lead_steps: see step_lead_steps.json beside this file."""

from harness import timeline
from harness.window import family_sum


def note(run):
    """The lead inside the traced part of the window alone, from the
    ``inflight`` the program's train_step spans carry: what the profiler
    makes of it, beside the whole window's mean."""
    tl = timeline.of_run(run)
    seen = [s.args["inflight"] for s in tl.spans("train_step")
            if "inflight" in s.args] if tl else []
    return {"traced_steps": len(seen),
            "traced_mean": sum(seen) / len(seen) if seen else None}


def read(run):
    steps = family_sum(run["counters"], "dmlc_fit_inflight_steps", ":sum")
    n = family_sum(run["counters"], "dmlc_fit_inflight_steps", ":count")
    return steps / n if n else None

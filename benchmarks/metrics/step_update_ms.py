"""step_update_ms: see step_update_ms.json beside this file."""

from harness import timeline

PHASES = ("step.scatter", "step.update")


def phases(run):
    """{phase: ms per run of the step's program}, in the window."""
    tl = timeline.of_run(run)
    if tl is None or not run["trace"].get("step_program"):
        return {}
    step = run["trace"]["step_program"]
    runs = [r for r in tl.runs if r[3] == step
            and r[0] >= tl.lo and r[1] <= tl.hi]
    scopes = tl.op_scopes()
    spent, at = {}, 0
    for name, start, end in tl.ops:  # both in time order
        while at < len(runs) and runs[at][1] <= start:
            at += 1
        if at == len(runs):
            break
        if start < runs[at][0]:
            continue  # outside a whole run of the step
        parts = scopes.get(name, "").split("/")
        phase = next((p for p in parts if p.startswith("step.")), "other")
        spent[phase] = spent.get(phase, 0.0) + (end - start)
    if not any(p.startswith("step.") for p in spent):
        return {}
    return {p: ns / 1e6 / len(runs) for p, ns in sorted(spent.items())}


def note(run):
    return {"ms_per_run": phases(run)}


def read(run):
    found = phases(run)
    return sum(found.get(p, 0.0) for p in PHASES) if found else None

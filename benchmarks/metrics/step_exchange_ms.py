"""step_exchange_ms: see step_exchange_ms.json beside this file."""

import os
from statistics import median

from harness import spec, timeline, xplane

PHASE = "step.exchange"
_phases = spec.load_module(
    os.path.join(os.path.dirname(__file__), "step_update_ms.py")).phases


def step_ms_by_chip(run):
    """{chip: median ms of the step's program on that chip}, in the
    traced window."""
    tl = timeline.of_run(run)
    step = (run.get("trace") or {}).get("step_program")
    if tl is None or not step:
        return {}
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(tl.path).planes:
        m = xplane._DEVICE_PLANE.match(plane.name)
        for line in plane.lines if m else ():
            if line.name != "XLA Modules":
                continue
            runs = [e.duration_ns / 1e6 for e in line.events
                    if xplane._program(e.name) == step
                    and e.start_ns >= tl.lo
                    and e.start_ns + e.duration_ns <= tl.hi]
            if runs:
                out[int(m.group(1))] = median(runs)
    return out


def note(run):
    return {"step_ms_by_chip": step_ms_by_chip(run)}


def read(run):
    return _phases(run).get(PHASE)

"""step_dense_ms: see step_dense_ms.json beside this file."""

import os

from harness import spec

PHASE = "step.dense"
_phases = spec.load_module(
    os.path.join(os.path.dirname(__file__), "step_update_ms.py")).phases


def read(run):
    return _phases(run).get(PHASE)

"""The benchmark's trace reduction on recorded v5e traces (CPU only, a few
seconds): benchmarks/testdata/check.py for harness/xplane.py,
check_timeline.py for harness/timeline.py and every reader built on it,
and check_pass_boundary.py for the readers of the pass boundary (a third
trace, from a program that has their spans).
Also: each per-layer metric BENCHMARK.json declares has its two files."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.mark.parametrize(
    "script", ["check.py", "check_timeline.py", "check_pass_boundary.py"])
def test_recorded_trace_reduces_to_expected(script):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "testdata", script)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, cwd=ROOT, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.startswith("ok:"), proc.stdout


def test_declared_metrics_have_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["end_to_end"] + bench["per_layer"]:
        stem = os.path.join(BENCH, "metrics", entry["name"])
        assert os.path.exists(stem + ".py"), entry["name"]
        with open(stem + ".json") as f:
            said = json.load(f)
        for key in ("name", "unit", "better", "source"):
            assert said[key] == entry[key], (entry["name"], key)
        if "layer" in entry:
            assert (said["layer"], said["moves"]) == (
                entry["layer"], entry["moves"]), entry["name"]

#!/usr/bin/env python3
"""Check the trace reduction (harness/xplane.py) on the CPU:

- interval arithmetic on hand-made cases;
- the recorded v5e trace beside this file (a few steps of a one-chip cell,
  cut from a traced run on the chip; see expected.json for which) reduces
  to the numbers in expected.json, and its busy time agrees with a second,
  independent computation (a sweep over the sorted end points).

    JAX_PLATFORMS=cpu python3 benchmarks/testdata/check.py
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import xplane  # noqa: E402


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def intervals():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert u == [[0, 3], [5, 8], [10, 11]], u
    assert xplane.total(u) == 7
    assert xplane.subtract([[0, 12]], u) == [[3, 5], [8, 10], [11, 12]]
    assert xplane.subtract(u, [[2, 6], [10, 20]]) == [[0, 2], [6, 8]]
    assert xplane.clip(u, 2, 10.5) == [[2, 3], [5, 8], [10, 10.5]]
    assert xplane.op_label(
        "%fusion.7 = f32[8,16]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop"
    ) == "fusion.7 f32[8,16]"
    assert xplane.op_label(
        "%all-reduce-start = (f32[4]{0}, f32[4]{0}) all-reduce-start(%x)"
    ) == "all-reduce-start f32[4]"


def sweep_busy_ns(path, window):
    """Busy time by counting open operations over the sorted end points:
    shares no code with xplane.union."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo = hi = None
    points = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/host:CPU" and e.name == window:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
                if plane.name.startswith("/device:TPU:") and \
                        line.name == "XLA Ops":
                    points.append((e.start_ns, 1))
                    points.append((e.start_ns + e.duration_ns, -1))
    if lo is None:  # no window span: first to last device event
        lo, hi = min(p[0] for p in points), max(p[0] for p in points)
    busy, open_ops, last = 0.0, 0, None
    for at, step in sorted(points):
        at = min(max(at, lo), hi)
        if open_ops > 0:
            busy += at - last
        open_ops += step
        last = at
    return busy


def recorded():
    with open(os.path.join(HERE, "expected.json")) as f:
        want = json.load(f)
    path = os.path.join(HERE, want["trace"])
    got = xplane.reduce(path, span_names=want["span_names"],
                        window=want["window"])
    assert got["devices"] == want["devices"], got["devices"]
    assert got["step_program"] == want["step_program"], got["step_program"]
    assert got["programs"][want["step_program"]]["runs"] == want["step_runs"]
    for key in ("window_s", "busy_s", "step_device_ms", "program_s",
                "collective_exposed_s"):
        assert close(got[key], want[key], 1e-6), (key, got[key], want[key])
    assert got["device_ops"][0][0] == want["top_op"], got["device_ops"][0]
    assert [k for k, _ in got["idle_gaps"]] == want["idle_gap_order"], \
        got["idle_gaps"]
    assert close(got["busy_s"] * 1e9, sweep_busy_ns(path, want["window"]),
                 1e-9)
    assert 0.0 < got["busy_s"] <= got["window_s"]
    return got


if __name__ == "__main__":
    intervals()
    got = recorded()
    print("ok: %d steps of %s, median %.3f ms, busy %.6f of %.6f s"
          % (got["programs"][got["step_program"]]["runs"],
             got["step_program"], got["step_device_ms"], got["busy_s"],
             got["window_s"]))

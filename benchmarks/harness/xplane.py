"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle
time, time per program and per operation, and the idle gaps by what the
host was doing. Read with nothing but jax (``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand on a v5e trace, PR 23/24): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per run of a compiled program), ``XLA Ops`` (one event per HLO
operation, back to back inside a program) and ``Async XLA Ops`` (copies
and collectives in flight, overlapping the former); and ``/host:CPU``
with one line per thread, where ``jax.profiler.TraceAnnotation`` spans
(the program's own, bridged by ``DMLC_TPU_TRACE_JAX=1``) sit beside the
runtime's events. Device and host events share one clock.

Checked against a small recorded trace by ``benchmarks/testdata/check.py``.
"""

from __future__ import annotations

import glob
import os
import re
from statistics import median

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?\b")


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def total(intervals) -> float:
    return float(sum(end - start for start, end in intervals))


def subtract(a, b):
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_label(hlo: str) -> str:
    """'%fusion.7 = f32[8,16]{1,0:T(8,128)} fusion(...)' ->
    'fusion.7 f32[8,16]': the operation and the shape it makes."""
    head, _, rest = hlo.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    name = head.lstrip("%")
    return "%s %s" % (name, shape.group(1)) if shape else name


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(path: str, span_names=(), window=None) -> dict:
    """Reduce one trace. ``span_names``: the host spans (TraceAnnotation
    names) that idle gaps are attributed to; ``window``: the name of the
    span that bounds the traced window (default: first to last device
    event). Times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    host_spans = []  # (name, start, end) of the spans in span_names
    bounds = None
    wanted = set(span_names)
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: _events(line) for line in plane.lines}
            devices[int(m.group(1))] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for name, start, end in _events(line):
                    if name == window:
                        bounds = (start, end)
                    elif name in wanted:
                        host_spans.append((name, start, end))
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in %s" % path)

    if bounds is None:
        every = [ev for lines in devices.values()
                 for evs in lines.values() for ev in evs]
        bounds = (min(e[1] for e in every), max(e[2] for e in every))
    lo, hi = bounds
    window_ns = hi - lo

    busy_ns, per_op, programs = [], {}, {}
    exposed_ns, step_ns, gaps = [], [], {}
    for _, lines in sorted(devices.items()):
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = clip(union((s, e) for _, s, e in ops), lo, hi)
        busy_ns.append(total(busy))
        for name, start, end in ops:
            if end > lo and start < hi:
                label = op_label(name)
                per_op[label] = per_op.get(label, 0.0) + (
                    min(end, hi) - max(start, lo))
        for name, start, end in lines.get("XLA Modules", []):
            if start >= lo and end <= hi:
                programs.setdefault(_program(name), []).append(end - start)
        # collectives: in flight on the async line or running on the op
        # line; exposed where no other operation runs beside them
        coll = [(s, e) for line in ("XLA Ops", "Async XLA Ops")
                for name, s, e in lines.get(line, [])
                if _COLLECTIVE.search(name.partition(" = ")[2])]
        other = [(s, e) for name, s, e in lines.get("XLA Ops", [])
                 if not _COLLECTIVE.search(name.partition(" = ")[2])]
        exposed = subtract(clip(union(coll), lo, hi), union(other))
        exposed_ns.append(total(exposed))
        step_ns.append(total(clip(union(
            (s, e) for _, s, e in lines.get("XLA Modules", [])), lo, hi)))
        # idle gaps, each given to the innermost wanted host span open at
        # its start
        for start, end in subtract([[lo, hi]], busy):
            inner = None
            for name, s, e in host_spans:
                if s <= start < e and (inner is None or e - s < inner[1]):
                    inner = (name, e - s)
            key = inner[0] if inner else "no_span"
            gaps[key] = gaps.get(key, 0.0) + (end - start)

    n = len(devices)
    for key in gaps:
        gaps[key] /= n
    for key in per_op:
        per_op[key] /= n
    step_name = max(programs, key=lambda k: sum(programs[k]), default=None)
    return {
        "devices": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "programs": {
            k: {"runs": len(v), "median_ms": median(v) / 1e6,
                "total_s": sum(v) / n / 1e9}
            for k, v in programs.items()},
        "step_program": step_name,
        "step_device_ms": (
            median(programs[step_name]) / 1e6 if step_name else None),
        "collective_exposed_s": sum(exposed_ns) / n / 1e9,
        "program_s": sum(step_ns) / n / 1e9,
        "device_ops": sorted(
            ((k, v / 1e9) for k, v in per_op.items()),
            key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(
            ((k, v / 1e9) for k, v in gaps.items()),
            key=lambda kv: -kv[1])[:10],
    }

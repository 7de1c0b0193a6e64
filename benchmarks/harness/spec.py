"""What a cell is made of, found by name from BENCHMARK.json.

A workload names a configuration and a traffic mix; a configuration is
``<file>.json`` with ``<file>.py`` beside it; a traffic mix is
``benchmarks/traffic/<traffic>.json``; a metric is
``benchmarks/metrics/<name>.py`` with a ``read(run)`` function. Nothing in
this package names any of them.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a python file by path (file names carry '-' and '.')."""
    name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One workload of BENCHMARK.json with everything it points at."""

    def __init__(self, workload: str, rehearse: bool = False):
        bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        names = [w["name"] for w in bench["workloads"]]
        if workload not in names:
            # written and tried, not declared: the same entries, kept apart
            more = _load_json(os.path.join(BENCH_DIR, "candidates.json"))
            for key in ("configs", "workloads", "per_layer"):
                bench[key] = bench[key] + more[key]
        entry = next(
            (w for w in bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise SystemExit("no workload %r in BENCHMARK.json" % workload)
        self.name = workload
        self.chips = int(entry["chips"])
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        path = os.path.join(ROOT, conf["file"])
        self.cfg = _load_json(path)
        if rehearse:
            self.cfg.update(self.cfg.get("rehearse", {}))
        self.config = load_module(os.path.splitext(path)[0] + ".py")
        self.traffic = _load_json(os.path.join(
            BENCH_DIR, "traffic", entry["traffic"] + ".json"))
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    def _metrics(self, entries):
        """[(entry, reader module)] of the metrics this cell reports."""
        out = []
        for m in entries:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append((m, load_module(
                os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"))))
        return out


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            "device kind %r is not in benchmarks/harness/peaks.json"
            % device_kind)
    return table[device_kind]

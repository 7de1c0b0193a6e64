#!/usr/bin/env python
"""Lint obs metric names against the naming rule and the docs.

Walks dmlc_tpu/ + bench.py for ``registry().counter("...")``-style
registrations (the obs API takes the metric name as the first literal
argument — a non-literal name is invisible to this lint and to readers,
so keep names literal at call sites) and fails when a name

- does not follow ``dmlc_<area>_<name>_<unit>`` with the unit suffix in
  UNITS (counters must end ``_total``), or
- is not documented in docs/observability.md (backticked), or
- is documented but no longer registered anywhere (stale docs).

Run directly (exit code 0/1) or via tests/test_metric_lint.py.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOC = ROOT / "docs" / "observability.md"

UNITS = {"total", "ns", "bytes", "rows", "value", "count", "rank", "version",
         "mbps",
         # compiled-step cost attribution (obs/xla_cost.py + goodput MFU):
         # per-call FLOPs, "bytes accessed" (XLA cost_analysis's own key,
         # kept verbatim), a 0..1 utilization ratio, sampled milliseconds
         "flops", "accessed", "ratio", "ms",
         # the host's lead over the chip (models/fitloop.py): a count of
         # launched steps, observed a step at a time into a histogram
         "steps"}

# ".counter(" / ".gauge(" / ".histogram(" followed by a string literal —
# matches across the line break of a wrapped call
CALL_RE = re.compile(
    r"\.(counter|gauge|histogram)\(\s*[\"']([^\"']+)[\"']", re.S
)
# require a unit suffix so prose mentions of e.g. `dmlc_tpu.obs` don't
# read as metric names
DOC_NAME_RE = re.compile(
    r"`(dmlc_[a-z0-9_]+_"
    r"(?:total|ns|bytes|rows|value|count|rank|version|mbps"
    r"|flops|accessed|ratio|ms|steps))"
)


def registered_names() -> dict:
    """name -> list of (relative path, kind) registration sites."""
    out: dict = {}
    files = sorted(ROOT.glob("dmlc_tpu/**/*.py")) + [ROOT / "bench.py"]
    for path in files:
        if "tests" in path.parts:
            continue
        for kind, name in CALL_RE.findall(path.read_text()):
            out.setdefault(name, []).append(
                (str(path.relative_to(ROOT)), kind)
            )
    return out


def documented_names() -> set:
    if not DOC.exists():
        return set()
    return set(DOC_NAME_RE.findall(DOC.read_text()))


def lint() -> list:
    errors = []
    names = registered_names()
    documented = documented_names()
    if not names:
        errors.append(
            "no metric registrations found under dmlc_tpu/ — the lint's "
            "call-site regex is probably out of sync with the obs API"
        )
    if not DOC.exists():
        errors.append(f"missing {DOC.relative_to(ROOT)}")
    for name, sites in sorted(names.items()):
        where = ", ".join(f"{p} ({k})" for p, k in sites[:3])
        segs = name.split("_")
        if not name.startswith("dmlc_"):
            errors.append(f"{name}: must start with dmlc_  [{where}]")
            continue
        if len(segs) < 3:
            errors.append(
                f"{name}: want dmlc_<area>_<name>_<unit>  [{where}]"
            )
            continue
        if segs[-1] not in UNITS:
            errors.append(
                f"{name}: unit suffix {segs[-1]!r} not in "
                f"{sorted(UNITS)}  [{where}]"
            )
        if any(kind == "counter" for _, kind in sites) and segs[-1] != "total":
            errors.append(
                f"{name}: counters must end _total  [{where}]"
            )
        if documented and name not in documented:
            errors.append(
                f"{name}: not documented in docs/observability.md  [{where}]"
            )
    for name in sorted(documented - set(names)):
        errors.append(
            f"{name}: documented in docs/observability.md but never "
            "registered in source"
        )
    return errors


def main() -> int:
    errors = lint()
    for err in errors:
        print(f"check_metric_names: {err}")
    if errors:
        print(f"check_metric_names: {len(errors)} error(s)")
        return 1
    print(
        f"check_metric_names: {len(registered_names())} metric name(s) OK"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

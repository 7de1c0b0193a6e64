#!/usr/bin/env python3
"""Check the readers of the pass boundary on a recorded v5e trace, on the
CPU:

- a third recorded trace beside this file (the traced window of
  kdd12-fm.libsvm with ONE restart in it, from a program that has the
  ``drain_wait`` / ``loss_fetch`` / ``take`` / ``put`` / ``deliver`` /
  ``produce`` spans; expected_pass_boundary.json says which run) gives
  ``drain_wake_ms``, ``loss_fetch_ms``, ``restart_idle_ms`` and the
  counters' metrics the values that run printed on the chip;
- what must hold of the spans on the device's clock: ``drain_wait`` and
  ``loss_fetch`` lie inside ``loss_readback`` in that order, the device's
  last run of the pass ends inside ``drain_wait``, and the wake-up is
  shorter than the restart's idle;
- the second recorded trace (check_timeline.py's, from a program without
  those spans) leaves the two trace readers out and raises nothing.

    JAX_PLATFORMS=cpu python3 benchmarks/testdata/check_pass_boundary.py
"""

import json
import lzma
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from check_timeline import same  # noqa: E402  (the script beside this one)
from harness import spec, timeline, xplane  # noqa: E402

TRACE_READERS = ("drain_wake_ms", "loss_fetch_ms")


def _reader(name):
    return spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics", name + ".py"))


def _laid_out(root, want, spans):
    """A run as the readers get it, its trace where harness/main.py leaves
    a traced run's."""
    where = os.path.join(root, want["cell"], "trace", "plugins", "profile",
                         "recorded")
    os.makedirs(where)
    src = os.path.join(HERE, want["trace"])
    if src.endswith(".xz"):  # the whole window's operations, packed
        with lzma.open(src) as f, open(os.path.join(
                where, os.path.basename(src)[:-3]), "wb") as out:
            shutil.copyfileobj(f, out)
    else:
        shutil.copy(src, where)
    timeline.RUN_DIR = root
    timeline._cache.clear()
    run = dict(want["run"], cell=want["cell"], spans=spans)
    run["trace"] = xplane.reduce(
        xplane.find_trace(os.path.join(root, want["cell"], "trace")),
        span_names=sorted({s["name"] for s in spans}),
        window=timeline.WINDOW)
    return run


def _load(name):
    with open(os.path.join(HERE, name)) as f:
        want = json.load(f)
    with open(os.path.join(HERE, want["spans"])) as f:
        return want, json.load(f)


def recorded():
    want, spans = _load("expected_pass_boundary.json")
    root = tempfile.mkdtemp(prefix="bench_boundary_")
    try:
        run = _laid_out(root, want, spans)
        tl = timeline.of_run(run)
        got, notes = {}, {}
        for name in want["metrics"]:
            reader = _reader(name)
            got[name] = reader.read(run)
            if name in want["notes"]:
                notes[name] = reader.note(run)
        same(got, want["metrics"], "metrics")
        same(notes, want["notes"], "notes")
        # the device goes idle under drain_wait, so the restart's idle gap
        # is given to it (the innermost listened span open at its start)
        assert [k for k, _ in run["trace"]["idle_gaps"]][:1] == \
            ["drain_wait"], run["trace"]["idle_gaps"]
        # one clock: the spans against the device's runs
        outer = tl.spans("loss_readback")
        drains, fetches = tl.spans("drain_wait"), tl.spans("loss_fetch")
        assert len(outer) == len(drains) == len(fetches) == want["restarts"]
        for o, d, f in zip(outer, drains, fetches):
            assert d.parent is o and f.parent is o
            assert o.start <= d.start <= d.end <= f.start <= f.end <= o.end
            # its pass: the runs its own pass's train_steps launched
            last = max(r[1] for r in tl.runs if r[1] <= f.start)
            assert d.start < last < d.end, (d.start, last, d.end)
            assert (d.end - last) / 1e6 < got["restart_idle_ms"]
            assert d.args["steps"] >= 1 and f.args["scalars"] >= 1
        # one identifier from produce to train_step, on two threads
        ident = lambda s: (s.args["pass_"], s.args["batch"])  # noqa: E731
        steps = {ident(s): s for s in tl.spans("train_step")}
        made = {ident(s): s for s in tl.spans("produce")}
        for name in ("take", "put", "deliver", "consume"):
            found = {ident(s): s for s in tl.spans(name)}
            assert len(set(found) & set(steps)) >= len(steps) - 3, name
        both = set(made) & set(steps)
        assert both and all(made[i].thread != steps[i].thread
                            and made[i].end <= steps[i].start for i in both)
        assert all("inflight" in s.args for s in steps.values())
        return got
    finally:
        shutil.rmtree(root, ignore_errors=True)


def absent_before():
    """The older recorded trace: a program with none of the new spans."""
    want, spans = _load("expected_restart.json")
    root = tempfile.mkdtemp(prefix="bench_boundary_old_")
    try:
        run = _laid_out(root, want, spans)
        for name in TRACE_READERS:
            assert _reader(name).read(run) is None, name
        for name in ("step_lead_steps", "feed_put_ms_per_batch",
                     "feed_stage_ms_per_batch", "put_unlanded_share"):
            assert _reader(name).read(dict(run, counters={})) is None, name
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    got = recorded()
    absent_before()
    print("ok: drain wake %.3f ms, loss fetch %.3f ms, restart idle %.3f ms"
          % (got["drain_wake_ms"], got["loss_fetch_ms"],
             got["restart_idle_ms"]))

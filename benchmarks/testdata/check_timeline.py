#!/usr/bin/env python3
"""Check harness/timeline.py and the readers built on it, on the CPU:

- the protobuf wire reader on a hand-made message, and span nesting and
  self time on hand-made spans;
- a second recorded v5e trace beside this file (the whole traced window of
  a one-chip cell with a restart between two passes in it; see
  expected_restart.json for which run) gives the timeline facts and the
  value of every reader that expected_restart.json holds, the same values
  that run printed on the chip.

    JAX_PLATFORMS=cpu python3 benchmarks/testdata/check_timeline.py
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from check import close  # noqa: E402  (the script beside this one)
from harness import spec, timeline, xplane  # noqa: E402


def same(got, want, where):
    """Numbers within 1e-6 relative, containers alike, all else equal."""
    if isinstance(want, dict):
        assert sorted(got) == sorted(want), (where, sorted(got))
        for key in want:
            same(got[key], want[key], "%s.%s" % (where, key))
    elif isinstance(want, float):
        assert close(got, want, 1e-6), (where, got, want)
    else:
        assert got == want, (where, got, want)


def wire():
    def varint(n):
        out = bytearray()
        while n > 0x7F:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        out.append(n)
        return bytes(out)

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def entry(key, message):  # one entry of a protobuf map<int64, message>
        return field(1, key) + field(2, message)

    stat_meta = field(5, entry(7, field(1, 7) + field(2, b"tf_op"))) + \
        field(5, entry(9, field(1, 9) + field(2, b"jit(f)/step.update/sub:")))
    by_value = field(1, 300) + field(2, b"%fusion.1 = f32[8]") + \
        field(5, field(1, 7) + field(5, b"jit(f)/step.scatter/add:"))
    by_ref = field(1, 301) + field(2, b"%fusion.2 = f32[8]") + \
        field(5, field(1, 7) + field(7, 9))
    bare = field(1, 302) + field(2, b"%copy.3 = f32[8]") + \
        field(5, field(1, 8) + varint(2 << 3 | 1) + b"\0" * 8)
    device = field(2, b"/device:TPU:0") + stat_meta + b"".join(
        field(4, entry(i, m)) for i, m in ((300, by_value), (301, by_ref),
                                           (302, bare)))
    host = field(2, b"/host:CPU") + stat_meta + field(4, entry(300, by_value))
    blob = field(1, host) + field(1, device) + field(4, b"a-host-name")
    assert timeline.op_scopes(blob) == {
        "%fusion.1 = f32[8]": "jit(f)/step.scatter/add:",
        "%fusion.2 = f32[8]": "jit(f)/step.update/sub:"}


def nesting():
    spans = timeline._nest([
        timeline.Span("b", 2, 5, {}, 0), timeline.Span("a", 0, 10, {}, 0),
        timeline.Span("c", 6, 9, {}, 0), timeline.Span("d", 3, 4, {}, 0),
        timeline.Span("e", 12, 13, {}, 0)])
    by_name = {s.name: s for s in spans}
    assert [s.name for s in by_name["a"].children] == ["b", "c"]
    assert by_name["d"].parent is by_name["b"]
    assert by_name["e"].parent is None
    assert by_name["a"].self_intervals() == [[0, 2], [5, 6], [9, 10]]
    assert by_name["a"].self_ns == 4 and by_name["b"].self_ns == 2


def recorded():
    with open(os.path.join(HERE, "expected_restart.json")) as f:
        want = json.load(f)
    with open(os.path.join(HERE, want["spans"])) as f:
        spans = json.load(f)
    root = tempfile.mkdtemp(prefix="bench_timeline_")
    try:
        # laid out as harness/main.py leaves a traced run
        where = os.path.join(root, want["cell"], "trace", "plugins",
                             "profile", "recorded")
        os.makedirs(where)
        shutil.copy(os.path.join(HERE, want["trace"]), where)
        timeline.RUN_DIR = root
        run = dict(want["run"], cell=want["cell"], spans=spans)
        run["trace"] = xplane.reduce(
            xplane.find_trace(os.path.join(root, want["cell"], "trace")),
            span_names=sorted({s["name"] for s in spans}),
            window=timeline.WINDOW)
        assert [k for k, _ in run["trace"]["idle_gaps"]][:1] == \
            want["top_idle_gap"], run["trace"]["idle_gaps"]
        tl = timeline.of_run(run)
        assert timeline.of_run(run) is tl  # read once
        facts = {
            "window_s": (tl.hi - tl.lo) / 1e9,
            "program_runs": len(tl.runs),
            "operations": len(tl.ops),
            "spans": {name: len(tl.spans(name))
                      for name in want["timeline"]["spans"]},
            "scoped_operations": len(tl.op_scopes()),
            "first_launch": list(tl.launches("train_step")[0][1][2:]),
        }
        same(facts, want["timeline"], "timeline")
        # with no launch id to follow, order from the drain point joins
        # the steps of the new pass to the same runs
        by_id = dict((s.args["batch"], r[2])
                     for s, r in tl.launches("train_step")
                     if s.args["pass_"] == want["new_pass"])
        tl.launch_of = lambda span: None
        by_order = dict((s.args["batch"], r[2]) for s, r in tl.launches(
            "train_step", drain="loss_readback"))
        assert by_order and all(by_id[b] == i for b, i in by_order.items()
                                if b in by_id), (by_id, by_order)
        assert set(by_id) <= set(by_order)
        del tl.launch_of
        got, notes = {}, {}
        for name in want["metrics"]:
            reader = spec.load_module(os.path.join(
                spec.BENCH_DIR, "metrics", name + ".py"))
            got[name] = reader.read(run)
            if name in want["notes"]:
                notes[name] = reader.note(run)
        same(got, want["metrics"], "metrics")
        same(notes, want["notes"], "notes")
        # a program without the new spans, args and counters (the parent
        # of the PR that added them): nothing raises, the metric is left out
        old = dict(run, pipeline={}, counters={}, spans=[
            {k: v for k, v in s.items() if k != "args"} for s in spans
            if s["name"] not in ("loss_readback", "epoch_close",
                                 "feed_restart")])
        timeline._cache.clear()
        for name in want["absent_without_new_spans"]:
            reader = spec.load_module(os.path.join(
                spec.BENCH_DIR, "metrics", name + ".py"))
            assert reader.read(old) is None, name
        return got
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    wire()
    nesting()
    got = recorded()
    print("ok: restart idle %.3f ms, step launch %.3f ms, queue lead %.1f "
          "ms, scatter + update %.3f ms a step"
          % (got["restart_idle_ms"], got["step_dispatch_ms_per_batch"],
             got["step_queue_lead_ms"], got["step_update_ms"]))

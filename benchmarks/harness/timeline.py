"""The traced run's profile as one timeline: the program's spans with their
args, thread by thread, beside the device's program runs and operations,
all on the profile's one clock and bounded by the ``bench.trace`` span.

``harness/xplane.py`` reduces the same file to totals; the readers that
need to follow one batch or one restart read it through this module. The
file is parsed once a process (``of_run`` caches by path).

What a v5e profile holds beyond what xplane.py describes (looked at by
hand on PR 25's traces of both cells):

- a ``TraceAnnotation(name, **args)`` is a host event whose name stays
  bare and whose args are the event's stats, so the program's spans carry
  ``pass_`` / ``batch`` / ``step`` there; the python tracer's own events
  (``$file.py:1 fn``) and the runtime's share the thread's line and are
  told apart by name;
- an ``XLA Modules`` event carries the runtime's launch id, ``run_id``,
  and so does the host's enqueue of that launch. Between a span of the
  program and the enqueue lie hand-overs between threads, each marked by a
  producer stat (``_pt``, ``_p``) on one side and the same pair as consumer
  (``_ct``, ``_c``) on the other: ``launch_of`` follows them;
- an ``XLA Ops`` event has no stat that names its ``jax.named_scope``; the
  scope is a stat (``tf_op``) of the event's *metadata*, which
  ``jax.profiler.ProfileData`` does not hand out. ``op_scopes`` reads it
  from the file's protobuf wire format (XSpace > XPlane > XEventMetadata >
  XStat), some forty lines, with nothing but the standard library.
"""

from __future__ import annotations

import os
from bisect import bisect_left
from dataclasses import dataclass, field

from harness import spec, xplane

RUN_DIR = os.path.join(spec.ROOT, ".bench_run")  # where main.py traces to
WINDOW = "bench.trace"
_LINK_STATS = ("_pt", "_p", "_ct", "_c", "run_id")
_MAX_HOPS = 6  # hand-overs followed from a span to its launch

_cache = {}


@dataclass
class Span:
    """One span of the program on one host thread, times in ns."""

    name: str
    start: float
    end: float
    args: dict
    thread: int
    parent: "Span" = None
    children: list = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start

    def self_intervals(self):
        """The span's interval less what its child spans cover."""
        return xplane.subtract(
            [[self.start, self.end]],
            xplane.union((c.start, c.end) for c in self.children))

    @property
    def self_ns(self) -> float:
        return xplane.total(self.self_intervals())


@dataclass
class _Link:
    """A host event that hands work on (produces) or takes it up
    (consumes), or names a launch."""

    start: float
    end: float
    thread: int
    produces: tuple
    consumes: tuple
    run_id: int


def _nest(spans):
    """Parent and children by containment in time, on one thread."""
    spans.sort(key=lambda s: (s.start, -s.end))
    open_spans = []
    for s in spans:
        while open_spans and open_spans[-1].end <= s.start:
            open_spans.pop()
        if open_spans and s.end <= open_spans[-1].end:
            s.parent = open_spans[-1]
            open_spans[-1].children.append(s)
        open_spans.append(s)
    return spans


class Timeline:
    """See the module's docstring. ``lo``/``hi``: the window in ns;
    ``threads``: {line number: [Span]} of the program's spans that lie in
    the window; ``runs``: [(start, end, run_id, program)] and ``ops``:
    [(name, start, end)] of the first device, in time order."""

    def __init__(self, path, span_names, window=WINDOW):
        from jax.profiler import ProfileData

        self.path = path
        wanted = set(span_names)
        self.threads, self._links, bounds = {}, [], None
        device = None
        for plane in ProfileData.from_file(path).planes:
            if xplane._DEVICE_PLANE.match(plane.name):
                if device is None or plane.name < device.name:
                    device = plane
            elif plane.name == "/host:CPU":
                for number, line in enumerate(plane.lines):
                    bounds = self._host_line(
                        number, line, wanted, window) or bounds
        if device is None:
            raise ValueError("no /device:TPU:<n> plane in %s" % path)
        lines = {line.name: line for line in device.lines}
        runs = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                 dict(e.stats).get("run_id"), xplane._program(e.name))
                for e in lines["XLA Modules"].events]
        ops = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
               for e in lines["XLA Ops"].events]
        if bounds is None:
            bounds = (min(o[1] for o in ops), max(o[2] for o in ops))
        self.lo, self.hi = bounds
        self.runs = sorted(r for r in runs if r[1] > self.lo
                           and r[0] < self.hi)
        self.ops = sorted((o for o in ops if o[2] > self.lo
                           and o[1] < self.hi), key=lambda o: o[1])
        for number in list(self.threads):
            inside = [s for s in self.threads[number]
                      if s.start >= self.lo and s.end <= self.hi]
            if inside:
                self.threads[number] = _nest(inside)
            else:
                del self.threads[number]
        self._links.sort(key=lambda k: k.start)
        self._starts = [k.start for k in self._links]
        self._consumers = {}
        for k in self._links:
            if k.consumes:
                self._consumers.setdefault(k.consumes, []).append(k)
        self._scopes = None

    def _host_line(self, number, line, wanted, window):
        bounds = None
        for e in line.events:
            name = e.name
            if name == window:
                bounds = (float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
            elif name in wanted:
                self.threads.setdefault(number, []).append(Span(
                    name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns), dict(e.stats),
                    number))
            elif not name.startswith("$"):  # not the python tracer's
                stats = {k: v for k, v in e.stats if k in _LINK_STATS}
                if stats:
                    self._links.append(_Link(
                        float(e.start_ns),
                        float(e.start_ns + e.duration_ns), number,
                        (stats["_pt"], stats["_p"]) if "_p" in stats else (),
                        (stats["_ct"], stats["_c"]) if "_c" in stats else (),
                        stats.get("run_id")))
        return bounds

    # ---- the program's spans --------------------------------------------
    def spans(self, name):
        """Every span of that name in the window, in time order."""
        return sorted((s for spans in self.threads.values() for s in spans
                       if s.name == name), key=lambda s: s.start)

    # ---- from a span to the device run it launched -----------------------
    def _inside(self, thread, start, end):
        i = bisect_left(self._starts, start)
        while i < len(self._links) and self._links[i].start <= end:
            k = self._links[i]
            if k.thread == thread and k.end <= end:
                yield k
            i += 1

    def launch_of(self, span):
        """The runtime's launch id of the first program the span enqueued:
        from the events inside the span on its thread, along the
        producer/consumer hand-overs to the event that names a
        ``run_id``. None when the trace carries no such chain."""
        frontier = [(span.thread, span.start, span.end)]
        for _ in range(_MAX_HOPS):
            handed = []
            for thread, start, end in frontier:
                for k in self._inside(thread, start, end):
                    if k.run_id is not None:
                        return k.run_id
                    for c in self._consumers.get(k.produces, ()):
                        handed.append((c.thread, c.start, c.end))
            if not handed:
                return None
            frontier = handed
        return None

    def launches(self, name, drain=None):
        """[(span, device run)] for the spans called ``name`` that launched
        a program run of the window. By the runtime's launch id where the
        trace carries one on both sides; else by order from a drain point:
        the first run that starts after a span called ``drain`` has ended
        is that of the first ``name`` span after it."""
        by_id = {r[2]: r for r in self.runs if r[2] is not None}
        spans = self.spans(name)
        joined = [(s, by_id[i]) for s in spans
                  for i in [self.launch_of(s)] if i in by_id]
        if joined or drain is None:
            return joined
        drains = self.spans(drain)
        for n, d in enumerate(drains):
            until = drains[n + 1].start if n + 1 < len(drains) else self.hi
            after = [s for s in spans if d.end <= s.start < until]
            started = [r for r in self.runs if r[0] >= d.end]
            joined.extend(zip(after, started))
        return joined

    # ---- the device ------------------------------------------------------
    def busy(self):
        """Disjoint intervals in which an operation runs, in the window."""
        return xplane.clip(
            xplane.union((s, e) for _, s, e in self.ops), self.lo, self.hi)

    def op_scopes(self):
        """{operation's event name: its ``tf_op`` metadata stat} of the
        device planes ('jit(step)/step.update/sub:' for an operation made
        under ``jax.named_scope('step.update')``)."""
        if self._scopes is None:
            with open(self.path, "rb") as f:
                self._scopes = op_scopes(f.read())
        return self._scopes


def of_run(run):
    """The timeline of a traced run (``run`` as the readers get it), or
    None when the run was not traced or left no profile behind."""
    if not run.get("trace"):
        return None
    try:
        path = xplane.find_trace(os.path.join(RUN_DIR, run["cell"], "trace"))
    except FileNotFoundError:
        return None
    if path not in _cache:
        _cache.clear()  # one run a process
        _cache[path] = Timeline(path, {s["name"] for s in run["spans"]})
    return _cache[path]


def listened(run, timeline, anchor):
    """The spans the program's own listener kept (``run["spans"]``) of the
    thread that holds the ``anchor`` spans, on the profile's clock,
    clipped to the window and nested. The profile lacks the spans that
    were open when it started or stopped (an annotation entered before the
    profiler is not recorded: the ``epoch`` around the window's first
    batches, for one); the listener has them all, on the program's clock.
    The two are laid over each other by the ``anchor`` spans both hold,
    matched by their ``(pass_, batch)``. None when nothing matches."""
    def ident(args):
        return args.get("pass_"), args.get("batch")

    profiled = {ident(s.args): s.start for s in timeline.spans(anchor)
                if "pass_" in s.args}
    shifts, tid = [], None
    for e in run["spans"]:
        at = profiled.get(ident(e.get("args") or {}))
        if e["name"] == anchor and at is not None:
            shifts.append(at - e["ts"] * 1e3)
            tid = e["tid"]
    if not shifts:
        return None
    shift = sorted(shifts)[len(shifts) // 2]
    lo, hi = timeline.lo, timeline.hi
    spans = []
    for e in run["spans"]:
        start = e["ts"] * 1e3 + shift
        end = start + e.get("dur", 0.0) * 1e3
        if e.get("ph") == "X" and e["tid"] == tid and end > lo \
                and start < hi:
            spans.append(Span(e["name"], max(start, lo), min(end, hi),
                              e.get("args") or {}, tid))
    return _nest(spans)


# ---- the metadata the profile API does not hand out ----------------------
def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as memoryviews, fixed-width ones as None."""
    at, n = 0, len(buf)
    while at < n:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            at += size
        elif kind in (1, 5):
            value = None
            at += 8 if kind == 1 else 4
        else:
            raise ValueError("wire type %d in an .xplane.pb" % kind)
        yield key >> 3, value


def _map_value(entry):
    """The value message of one protobuf map entry."""
    return next((v for n, v in _fields(entry) if n == 2), b"")


def op_scopes(blob, stat="tf_op"):
    """See ``Timeline.op_scopes``. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.id = 1, .name = 2."""
    out = {}
    for number, plane in _fields(memoryview(blob)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(_map_value(v))
            elif n == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not xplane._DEVICE_PLANE.match(name):
            continue
        wanted = {i for i, s in stat_names.items() if s == stat}
        for event in events:
            op, scope = None, None
            for n, v in _fields(event):
                if n == 2:
                    op = bytes(v).decode()
                elif n == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        scope = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if op and scope:
                out[op] = scope
    return out

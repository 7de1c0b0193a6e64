"""Span tracer: Chrome trace-event JSON (Perfetto-loadable) pipeline spans.

``span("parse", chunk=i)`` is a context manager that records one complete
("ph": "X") trace event — name, start, duration, thread — into an
in-process buffer; ``flush()`` (and an atexit hook) writes the buffer to
the path named by ``DMLC_TPU_TRACE`` as ``{"traceEvents": [...]}``, the
format both chrome://tracing and https://ui.perfetto.dev open directly.

Tracing is OFF unless ``DMLC_TPU_TRACE`` is set: ``span()`` then returns a
shared no-op context manager (two empty method calls per span). The env
var is re-read per ``span()`` call — one dict lookup — so tests and
long-lived processes can turn tracing on/off without re-imports.

Timestamps are anchored to ``time.monotonic_ns()`` with a one-shot
wall-clock anchor (``anchor_unix_ns``) captured at module import and
recorded in the trace ``metadata`` block: span ``ts`` values are µs since
the monotonic epoch, so an NTP step mid-job cannot fold or reorder the
timeline, and consumers that need absolute time (the tracker's merged
job trace, obs/plane.py) recover it as ``anchor_unix_ns + ts·1000``.
The emitted JSON stays Perfetto-compatible — extra top-level keys next
to ``traceEvents`` are part of the Chrome trace object format.

A span reads the clock twice: ``time.monotonic_ns()`` at entry and at
exit give its ``ts``, its ``dur`` and — where the caller passes a registry
histogram, ``span(name, hist=h, ...)`` — the one value the histogram
observes, so a span and the counter of the same name time the same
interval. ``hist`` fills with tracing on or off: with tracing off the span
is a two-read timer that records no event (and the shared no-op when the
histogram is the registry's no-op child, ``DMLC_TPU_METRICS=0``).

Listeners: :func:`add_listener` registers a callback invoked with each
completed span event. While any listener is registered, spans are
recorded even without ``DMLC_TPU_TRACE`` (the listener IS the consumer —
the flight recorder and the heartbeat span publisher both attach this
way), but the in-process buffer only grows when a trace *file* is
configured, so a listener alone cannot leak memory.

Optional jax bridging: with ``DMLC_TPU_TRACE_JAX=1`` each span also enters
a ``jax.profiler.TraceAnnotation`` when the running jax exposes it, so the
same span names show up inside an XLA profiler capture next to the device
timeline, on the device trace's clock. The span's args go with it
(``TraceAnnotation(name, **args)``) and land as the event's stats in the
``.xplane.pb``; the event's name stays bare. The class is looked up once,
by the first bridged span. Absent jax or the API, the bridge silently
stays off.

Batch identity: ``DeviceFeed`` numbers a batch ``(pass_, batch)`` — its
pass over the source and the batch's place in that pass — on ``produce``
(the producer's thread), ``feed_batch``, ``take``, ``dispatch``, ``stage``,
``put``, ``deliver`` and ``consume``, and leaves the pair in a
thread-local around the consume yield (:func:`set_current_batch`), which
the fit loop's ``train_step`` span reads back (:func:`current_batch`).

Flow events: ``new_flow()`` allocates a job-unique flow id and
``flow_start/flow_step/flow_end`` emit Chrome-trace flow events
(``"ph": "s"/"t"/"f"``) that Perfetto renders as arrows connecting the
enclosing duration slices — across threads, and (because the id embeds
the rank) across ranks once obs/plane.py merges per-worker traces. A
flow point binds to the ``"ph": "X"`` slice open on the same pid/tid at
its timestamp, so always emit flow points *inside* the span for the
stage they mark. When tracing is off, ``new_flow()`` returns 0 and every
flow call is an early-returning no-op — zero allocations on the hot
path (the disabled contract tests/test_obs.py pins).
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from dmlc_tpu.obs.metrics import NOOP as _NOOP_METRIC

_lock = threading.Lock()
_events: List[Dict] = []
_listeners: List[Callable[[Dict], None]] = []
_atexit_registered = False
# one-shot anchor pair: span ts are µs since _EPOCH_MONO_NS (NTP-immune);
# _ANCHOR_UNIX_NS is the wall clock at that same instant, published in the
# trace metadata so merged/absolute timelines can be reconstructed
_EPOCH_MONO_NS = time.monotonic_ns()
_ANCHOR_UNIX_NS = time.time_ns()

_PID = os.getpid()


def _now_us() -> float:
    return (time.monotonic_ns() - _EPOCH_MONO_NS) / 1e3


def anchor_unix_ns() -> int:
    """Wall-clock ns at the trace epoch (span ``ts`` zero point)."""
    return _ANCHOR_UNIX_NS


# (TraceAnnotation,) of the running jax, looked up by the first bridged
# span; (None,) when jax or the API is absent
_bridge: Optional[Tuple] = None


def _jax_annotation_cls():
    if os.environ.get("DMLC_TPU_TRACE_JAX") != "1":
        return None
    global _bridge
    if _bridge is None:
        try:
            import jax.profiler as _jp
        except Exception:
            _bridge = (None,)
        else:
            _bridge = (getattr(_jp, "TraceAnnotation", None),)
    return _bridge[0]


class _NoopSpan:
    """Shared disabled span: stateless, safe to reuse concurrently."""

    __slots__ = ()
    #: whether the span records an event (what ``set_current_batch`` and
    #: other per-event work is worth doing for)
    live = False
    dur_ns = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _TimedSpan:
    """``span(name, hist=h)`` with tracing off: the span's two clock reads
    into its histogram, no event, no args kept."""

    __slots__ = ("_hist", "_t0", "dur_ns")
    live = False

    def __init__(self, hist):
        self._hist = hist
        self._t0 = 0
        self.dur_ns = 0

    def __enter__(self):
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        self.dur_ns = time.monotonic_ns() - self._t0
        self._hist.observe(self.dur_ns)
        return False


class _Span:
    __slots__ = ("name", "args", "_hist", "_t0", "_annot", "dur_ns")
    live = True

    def __init__(self, name: str, args: Dict, annot=None, hist=None):
        self.name = name
        self.args = args
        self._hist = hist
        self._t0 = 0
        self._annot = annot
        #: the span's duration, from exit on (what ``hist`` observed)
        self.dur_ns = 0

    def __enter__(self):
        if self._annot is not None:
            self._annot.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        # the one pair of reads: ts, dur and the histogram's value
        dur_ns = self.dur_ns = time.monotonic_ns() - self._t0
        if self._annot is not None:
            self._annot.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(dur_ns)
        event = {
            "name": self.name,
            "ph": "X",
            "ts": (self._t0 - _EPOCH_MONO_NS) / 1e3,
            "dur": dur_ns / 1e3,
            "pid": _PID,
            "tid": threading.get_ident(),
        }
        if self.args:
            event["args"] = self.args
        # the buffer backs the DMLC_TPU_TRACE file; listeners keep their
        # own (bounded) state, so listener-only tracing cannot leak
        if _active_path() is not None:
            with _lock:
                _events.append(event)
        for fn in list(_listeners):
            try:
                fn(event)
            except Exception:
                pass  # telemetry consumers must never break the traced code
        return False


def _active_path() -> Optional[str]:
    # raw os.environ read: this sits on the per-batch path and must not
    # pay the typed-parse layer for the common "unset" case
    return os.environ.get("DMLC_TPU_TRACE") or None


def _ensure_atexit() -> None:
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(flush)


def add_listener(fn: Callable[[Dict], None]) -> None:
    """Register ``fn(event)`` to be called with every completed span.

    Registering a listener also arms span recording (``span()`` returns a
    live span while any listener exists, trace file or not)."""
    with _lock:
        if fn not in _listeners:
            _listeners.append(fn)


def remove_listener(fn: Callable[[Dict], None]) -> None:
    with _lock:
        try:
            _listeners.remove(fn)
        except ValueError:
            pass


def span(name: str, hist=None, **args):
    """Context manager timing one pipeline stage as a named trace span.

    Keyword args become the event's ``args`` payload — keep them small
    and JSON-serializable (chunk/batch indices). ``hist``: a registry
    histogram that observes the span's duration in ns at exit, from the
    same two clock reads that give the event's ``ts`` and ``dur``, with
    tracing on or off.

    Tracing is off unless ``DMLC_TPU_TRACE`` names an output file or a
    listener is registered. The span is then the shared inert
    ``NOOP_SPAN`` where there is nothing to observe into (no ``hist``, or
    the registry's no-op child under ``DMLC_TPU_METRICS=0``), else a
    two-read timer that records no event."""
    if _active_path() is None and not _listeners:
        if hist is None or hist is _NOOP_METRIC:
            return NOOP_SPAN
        return _TimedSpan(hist)
    _ensure_atexit()
    cls = _jax_annotation_cls()
    annot = cls(name, **args) if cls is not None else None
    return _Span(name, args, annot, hist)


# ---- Flow events (causal dataflow arrows) -------------------------------
# Chrome trace flow events match globally on (cat, id): a chunk's id must
# be unique across every process whose trace lands in the merged /trace.
# Layout: (rank+1) in the high bits | pid salt | per-process counter. The
# pid salt keeps colocated rank-0 processes (tests, local launcher) from
# colliding; the counter wraps at 2^24 flows, far past any one job.
_FLOW_CAT = "dataflow"
_FLOW_IDS = itertools.count(1)
_FLOW_BASE: Optional[int] = None
_FLOW_TLS = threading.local()


def _flow_base() -> int:
    global _FLOW_BASE
    if _FLOW_BASE is None:
        try:
            rank = int(os.environ.get("DMLC_TASK_ID") or 0)
        except ValueError:
            rank = 0
        _FLOW_BASE = (((rank & 0x3FFFFF) + 1) << 40) | (
            (_PID & 0xFFFF) << 24
        )
    return _FLOW_BASE


def new_flow() -> int:
    """Allocate a flow id, or 0 when tracing is disarmed.

    0 is the "no flow" sentinel every flow call early-returns on, so the
    disabled path allocates nothing — callers can thread the result
    unconditionally."""
    if _active_path() is None and not _listeners:
        return 0
    return _flow_base() | (next(_FLOW_IDS) & 0xFFFFFF)


def _flow_event(fid: int, ph: str, name: str) -> None:
    event = {
        "name": name,
        "cat": _FLOW_CAT,
        "ph": ph,
        "id": fid,
        "ts": _now_us(),
        "pid": _PID,
        "tid": threading.get_ident(),
    }
    if ph == "f":
        # bind the arrow head to the enclosing slice rather than the
        # next slice on the thread ("binding point: enclosing")
        event["bp"] = "e"
    if _active_path() is not None:
        _ensure_atexit()
        with _lock:
            _events.append(event)
    for fn in list(_listeners):
        try:
            fn(event)
        except Exception:
            pass  # telemetry consumers must never break the traced code


def flow_start(fid: int, name: str = "flow") -> None:
    """Emit the ``"s"`` (start) point of flow ``fid``. No-op when ``fid``
    is 0 or tracing is disarmed. Call inside the span of the producing
    stage so the arrow tail attaches to that slice."""
    if not fid or (_active_path() is None and not _listeners):
        return
    _flow_event(fid, "s", name)


def flow_step(fid: int, name: str = "flow") -> None:
    """Emit a ``"t"`` (step) point: the flow passed through the enclosing
    stage. No-op when ``fid`` is 0 or tracing is disarmed."""
    if not fid or (_active_path() is None and not _listeners):
        return
    _flow_event(fid, "t", name)


def flow_end(fid: int, name: str = "flow") -> None:
    """Emit the ``"f"`` (finish) point terminating flow ``fid`` (with
    ``"bp": "e"`` so the head binds to the enclosing slice)."""
    if not fid or (_active_path() is None and not _listeners):
        return
    _flow_event(fid, "f", name)


def set_current_flow(fid: int) -> None:
    """Stash ``fid`` as this thread's ambient flow. DeviceFeed sets it
    around the consume yield so fit-loop code (collective op spans,
    train_step) can mark the in-flight chunk without plumbing ids."""
    _FLOW_TLS.fid = fid


def current_flow() -> int:
    """This thread's ambient flow id (0 when none is set)."""
    return getattr(_FLOW_TLS, "fid", 0)


_NO_BATCH: Dict = {}


def set_current_batch(pass_: Optional[int], batch: int = 0) -> None:
    """Stash the identity of the batch this thread holds (``None``
    clears it). DeviceFeed sets it beside the current flow, around the
    consume yield, and only while the consume span is live."""
    _FLOW_TLS.batch = (
        _NO_BATCH if pass_ is None else {"pass_": pass_, "batch": batch})


def current_batch() -> Dict:
    """``{"pass_": p, "batch": b}`` of the batch this thread holds, for a
    span's args (``span("train_step", **current_batch())``); a shared
    empty dict when none is set."""
    return getattr(_FLOW_TLS, "batch", _NO_BATCH)


def events() -> List[Dict]:
    """Copy of the buffered trace events (ordered by span *completion*)."""
    with _lock:
        return list(_events)


def events_after(cursor: int) -> Tuple[List[Dict], int]:
    """Buffered events past ``cursor`` plus the new cursor — the
    incremental read the heartbeat span publisher batches from."""
    with _lock:
        return list(_events[cursor:]), len(_events)


def clear() -> None:
    with _lock:
        _events.clear()


def metadata() -> Dict:
    """The trace-file metadata block (clock anchor + process identity)."""
    return {
        "clock": "monotonic_ns",
        "anchor_unix_ns": _ANCHOR_UNIX_NS,
        "pid": _PID,
    }


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write all buffered events to ``path`` (default: ``DMLC_TPU_TRACE``)
    as a Chrome trace JSON object. Returns the path written, or None when
    there is no destination. The buffer is kept: repeated flushes rewrite
    the file with the complete history (the file is always loadable)."""
    path = path or _active_path()
    if path is None:
        return None
    with _lock:
        payload = {
            "traceEvents": list(_events),
            "displayTimeUnit": "ms",
            "metadata": metadata(),
        }
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh)
    os.replace(tmp, path)
    return path

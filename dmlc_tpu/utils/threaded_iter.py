"""Bounded producer/consumer prefetch iterator.

Capability parity with ``dmlc::ThreadedIter`` (include/dmlc/threadediter.h):
a background producer thread fills a bounded queue (default capacity 8,
threadediter.h:80) ahead of the consumer; ``before_first`` restarts the
producer for a new epoch (the kBeforeFirst signal, threadediter.h:211-215);
exceptions thrown in the producer are captured and re-raised in the consumer
(threadediter.h:374-404,456-466). Beyond the reference, a producer given a
``rewind`` begins the next epoch by itself and stages it ahead
(``ThreadedIter.advance``). The reference's free-cell ``Recycle`` buffer
pool (threadediter.h:442-454) exists to reach zero steady-state allocation in
C++; the Python twin relies on refcounting (the native C++ core in cpp/ keeps
the recycling design).
"""

from __future__ import annotations

import queue
import sys
import threading
from typing import Callable, Generic, Iterable, Iterator, Optional, TypeVar

T = TypeVar("T")

_END = object()


class _Exc:
    def __init__(self, err: BaseException):
        self.err = err


class _Passes:
    """Passes the producer has wound itself into and the consumer has not
    begun. The lock orders the producer's self-rewind against the
    consumer's decision to stop it: a pass is either claimed whole
    (:meth:`ThreadedIter.advance`) or never begun."""

    def __init__(self):
        self.lock = threading.Lock()
        self.ahead = 0


def _produce(make_iter, rewind, discard, q: "queue.Queue",
             stop: threading.Event, passes: _Passes) -> None:
    """The producer thread's body. A function of its arguments and no
    method: the thread holds no reference to its ThreadedIter, so an
    iterator whose owner is dropped is collected and stops its thread."""

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    try:
        while True:
            for item in make_iter():
                if not put(item):
                    discard(item)
                    return
            again = False
            if rewind is not None:
                with passes.lock:
                    again = not stop.is_set() and bool(rewind())
                    if again:
                        passes.ahead += 1
            # the end mark follows the rewind: a consumer that has seen it
            # finds the decision made and waits for nothing
            if not put(_END) or not again:
                return
    except BaseException as err:  # noqa: BLE001 — propagate to consumer
        put(_Exc(err))


class ThreadedIter(Generic[T]):
    """Prefetch items of ``make_iter()`` in a background thread.

    ``make_iter`` is called once per epoch (at construction and at each
    ``before_first``) and must return a fresh iterator — the analog of the
    reference's ``next``/``beforefirst`` producer closures
    (threadediter.h:300-408).

    With ``rewind`` the producer does not stop at the end of a pass: it
    calls ``rewind()`` on its own thread and, where that returns true (the
    source is at its start again), marks the end of the pass in the queue
    and goes on producing the NEXT pass into the same bounded queue.
    Iteration still ends at the mark; :meth:`advance` then begins the
    pass that is already staged. A ``rewind`` that returns false ends the
    producer as if none were given.
    ``discard`` gets each item made and never taken: where the producer
    is stopped, and the rest of a pass :meth:`advance` leaves.
    """

    def __init__(
        self,
        make_iter: Callable[[], Iterable[T]],
        max_capacity: int = 8,
        name: str = "threaded-iter",
        rewind: Optional[Callable[[], bool]] = None,
        discard: Callable[[T], None] = lambda item: None,
    ):
        self._make_iter = make_iter
        self._rewind = rewind
        self._discard = discard
        self._cap = max_capacity
        self._name = name
        self._thread: Optional[threading.Thread] = None
        self._queue: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._passes = _Passes()
        self._finished = False
        self.before_first()

    def _shutdown_producer(self) -> None:
        if self._thread is not None:
            self._stop.set()
            # Drain so a blocked put() notices the stop flag promptly, and
            # again for what a put already under way left
            self._drain()
            self._thread.join()
            self._drain()
            self._thread = None

    def _drain(self) -> None:
        while not self._queue.empty():  # the producer only ever adds
            item = self._queue.get_nowait()
            if item is not _END and not isinstance(item, _Exc):
                self._discard(item)

    # ---- consumer API --------------------------------------------------
    def before_first(self) -> None:
        """Restart the producer for a fresh epoch."""
        self._shutdown_producer()
        self._queue = queue.Queue(self._cap)
        self._stop = threading.Event()
        self._passes = _Passes()
        self._finished = False
        self._thread = threading.Thread(
            target=_produce,
            args=(self._make_iter, self._rewind, self._discard, self._queue,
                  self._stop, self._passes),
            name=self._name,
            daemon=True,
        )
        self._thread.start()

    def advance(self) -> bool:
        """Begin the next pass where the producer has wound itself to it:
        what is left of this pass is dropped, through its end mark, and
        True returned. Else the producer is stopped as by :meth:`close`
        and False returned; the caller rewinds the source and calls
        :meth:`before_first`."""
        with self._passes.lock:
            wound = self._passes.ahead > 0
            if wound:
                self._passes.ahead -= 1
            else:
                self._stop.set()  # under the lock: no rewind after this
        if not wound:
            self._shutdown_producer()
            return False
        while (item := self.next()) is not None:
            self._discard(item)
        self._finished = False
        return True

    def next(self) -> Optional[T]:
        """Next item, or None at end of epoch. Re-raises producer errors."""
        if self._finished:
            return None
        item = self._queue.get()
        if item is _END:
            self._finished = True
            return None
        if isinstance(item, _Exc):
            self._finished = True
            raise item.err
        return item

    def __iter__(self) -> Iterator[T]:
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    def close(self) -> None:
        self._shutdown_producer()

    def __del__(self) -> None:  # pragma: no cover
        try:
            if sys.is_finalizing():
                # a daemon thread no longer runs, so it cannot be joined
                self._stop.set()
            else:
                self.close()
        except Exception:
            pass

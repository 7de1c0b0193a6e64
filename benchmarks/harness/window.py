"""The measured window: the learner's own ``fit_feed`` loop, epoch after
epoch over the cell's file, until the time is up.

The feed is wrapped only to count the rows handed to the step, to end the
window at a step boundary, and to start and stop the profiler part-way.
The window opens after warm-up with a fresh pass over the file and closes
on ``block_until_ready`` of the parameters after the last counted step.
"""

from __future__ import annotations

import resource
import time


def _cpu_s() -> float:
    """User + system CPU seconds of this process, all threads."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def family_sum(counters, prefix, suffix=""):
    """Sum of the window's deltas of every registry entry of one metric
    family (all label sets), or None when the registry has none."""
    found = [v for k, v in counters.items()
             if k.startswith(prefix) and k.endswith(suffix)]
    return sum(found) if found else None


class FeedProxy:
    """A DeviceFeed with its iteration replaced: ``fit_feed`` reads
    everything else (spec, mesh, stats, restart) from the real feed."""

    def __init__(self, feed):
        self._feed = feed

    def __getattr__(self, name):
        return getattr(self._feed, name)


class WindowFeed(FeedProxy):
    """A DeviceFeed seen through a counter and a deadline."""

    def __init__(self, feed, on_batch=None):
        super().__init__(feed)
        self._on_batch = on_batch
        self.deadline = float("inf")
        self.rows = 0
        self.batches = 0
        self.expired = False
        self.pass_rows = []  # rows of each full pass over the file
        self.pipeline = {}  # the parser's own counters, summed over epochs

    def __iter__(self):
        source = iter(self._feed)
        first = self.rows
        try:
            for batch in source:
                now = time.perf_counter()
                if now >= self.deadline:
                    self.expired = True
                    return
                self.rows += int(batch["num_rows"])
                self.batches += 1
                if self._on_batch is not None:
                    self._on_batch(now)
                yield batch
            self.pass_rows.append(self.rows - first)
        finally:
            source.close()

    def note_pipeline(self):
        """Add the parser's per-pass counters (they restart with every
        pass) to the running sums; call before each restart."""
        stats = self._feed.stats().get("pipeline") or {}
        for key, value in stats.items():
            if isinstance(value, (int, float)):
                self.pipeline[key] = self.pipeline.get(key, 0) + value


def measure(model, feed, seconds, obs, registry, on_batch=None):
    """Run the window; returns the raw facts the metric readers use."""
    import jax

    wfeed = WindowFeed(feed, on_batch)
    jax.block_until_ready(model.params)
    flat0 = registry.flat_values()
    cpu0 = _cpu_s()
    t0 = time.perf_counter()
    wfeed.deadline = t0 + seconds
    epoch = 0
    while True:
        with obs.span("bench.epoch", epoch=epoch):
            model.fit_feed(wfeed, epochs=1)
        wfeed.note_pipeline()
        if wfeed.expired:
            break
        with obs.span("bench.restart", epoch=epoch):
            wfeed.before_first()
        epoch += 1
    jax.block_until_ready(model.params)
    t1 = time.perf_counter()
    cpu1 = _cpu_s()
    flat1 = registry.flat_values()
    return {
        "window_s": t1 - t0,
        "t0": t0,
        "t1": t1,
        "rows": wfeed.rows,
        "batches": wfeed.batches,
        "pass_rows": wfeed.pass_rows,
        "cpu_s": cpu1 - cpu0,
        "pipeline": wfeed.pipeline,
        "counters": {k: flat1[k] - flat0.get(k, 0.0) for k in flat1},
    }

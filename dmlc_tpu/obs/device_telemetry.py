"""Device/XLA telemetry: recompile sentinel, HBM accounting, profiler capture.

The obs stack up to PR 7 watches the host side (stages, flows, ranks); this
module lights up the device side on the same registry:

- ``instrumented_jit(fn, name=...)`` — a drop-in ``jax.jit`` wrapper that
  counts compiles per function (``dmlc_xla_compiles_total{fn=}``), histograms
  the wall time of each compiling call (``dmlc_xla_compile_ns{fn=}``), and
  after a warmup window treats any further compile as an anomaly: log
  warning + ``xla.recompile`` flight event + ``dmlc_xla_recompiles_total``.
  The trick is that jit traces the wrapped Python body exactly once per
  cache miss, so a counter bump inside the body IS a compile counter — no
  private jax APIs. This turns FixedShapePool's one-trace-per-bucket design
  claim into a live invariant. Each compiling call also hands its arguments
  to obs/xla_cost.py (``note_compile``) which caches the compiled program's
  cost/memory analytics per (fn, bucket shape) — compile-time only, never
  per step; ``jitted.lower(...).compile()`` there returns the executable
  the call just built (no second trace, no second XLA compile), so the
  recompile sentinel itself is not perturbed.
- ``sample()`` — per-device HBM gauges from ``device.memory_stats()``
  (``dmlc_device_hbm_bytes{device=}``; graceful no-op on CPU backends where
  the runtime reports nothing) plus a live-buffer census over
  ``jax.live_arrays()`` (``dmlc_device_live_bytes{device=}``) which works on
  every backend. Sampled at payload-publish time, by bench, and optionally
  by a background poller (``maybe_start_hbm_poller``).
- ``h2d_meter()`` — byte/bandwidth accounting for the feed's ``device_put``
  dispatch path (``dmlc_feed_h2d_bytes_total``, ``dmlc_feed_h2d_mbps``).
- ``capture_profile(seconds)`` — run ``jax.profiler`` for a window in a
  background thread and drop the artifact beside the flight-recorder dump;
  triggered job-wide by the tracker's ``/profile?seconds=N`` endpoint via
  the heartbeat-ack side channel (see obs/plane.py).

Knobs: ``DMLC_TPU_DEVICE_TELEMETRY`` (default 1; 0 makes ``instrumented_jit``
return the plain ``jax.jit`` callable — the disabled dispatch path is
byte-for-byte the uninstrumented one) and ``DMLC_TPU_HBM_POLL_S`` (default 0;
>0 starts a daemon thread sampling HBM every that many seconds).
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Optional

from dmlc_tpu.obs import flight, xla_cost
from dmlc_tpu.obs.metrics import Registry, registry
from dmlc_tpu.params.knobs import device_telemetry_enabled, hbm_poll_s

logger = logging.getLogger("dmlc_tpu.obs.device")

__all__ = [
    "DEFAULT_WARMUP_CALLS",
    "InstrumentedJit",
    "instrumented_jit",
    "compile_counts",
    "H2DMeter",
    "h2d_meter",
    "sample",
    "peak_hbm_bytes",
    "maybe_start_hbm_poller",
    "capture_profile",
    "detail_section",
    "reset",
]

#: Calls before a fresh trace stops being "expected warmup" and becomes an
#: anomaly. Shape buckets all show up in the first few batches of a fit; a
#: compile after this many dispatches means an unbucketed shape leaked in.
DEFAULT_WARMUP_CALLS = 32


class InstrumentedJit:
    """``jax.jit`` with a compile counter and a post-warmup recompile alarm.

    The jitted callable wraps a shim whose Python body runs once per trace
    (jit cache miss): the shim bumps ``self.compiles``. Dispatch-side we
    compare the count before/after the call — a change means this call
    compiled, so its wall time (trace+compile+first run, documented caveat)
    goes to the compile-time histogram, and past ``warmup_calls`` dispatches
    it also fires the anomaly path.
    """

    __slots__ = (
        "fn_name",
        "warmup_calls",
        "compiles",
        "calls",
        "_jitted",
        "_reg",
        "_m_compiles",
        "_m_recompiles",
        "_h_compile_ns",
    )

    def __init__(
        self,
        fn: Callable,
        name: str,
        warmup_calls: int = DEFAULT_WARMUP_CALLS,
        reg: Optional[Registry] = None,
        **jit_kwargs: Any,
    ):
        import jax

        reg = reg if reg is not None else registry()
        self._reg = reg
        self.fn_name = name
        self.warmup_calls = int(warmup_calls)
        self.compiles = 0
        self.calls = 0
        self._m_compiles = reg.counter(
            "dmlc_xla_compiles_total",
            "XLA traces (jit cache misses) per instrumented function",
            fn=name,
        )
        self._m_recompiles = reg.counter(
            "dmlc_xla_recompiles_total",
            "post-warmup recompile anomalies per instrumented function",
            fn=name,
        )
        self._h_compile_ns = reg.histogram(
            "dmlc_xla_compile_ns",
            "wall time of calls that compiled (trace+compile+first run)",
            fn=name,
        )

        def _counting(*args, **kwargs):
            # Body executes once per jit cache miss — this IS the compile
            # counter. Runs under tracing, so only host-side effects here.
            self.compiles += 1
            self._m_compiles.inc()
            return fn(*args, **kwargs)

        try:
            _counting.__name__ = getattr(fn, "__name__", name)
        except (AttributeError, TypeError):
            pass
        self._jitted = jax.jit(_counting, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        before = self.compiles
        t0 = time.monotonic_ns()
        out = self._jitted(*args, **kwargs)
        self.calls += 1
        if self.compiles != before:
            self._h_compile_ns.observe(time.monotonic_ns() - t0)
            # never raises: a failed extraction warns once for this site
            xla_cost.note_compile(
                self.fn_name, self._jitted, args, kwargs, reg=self._reg)
            if self.calls > self.warmup_calls:
                self._m_recompiles.inc()
                flight.record_event(
                    "xla.recompile",
                    fn=self.fn_name,
                    compiles=self.compiles,
                    calls=self.calls,
                )
                logger.warning(
                    "xla recompile anomaly: %s traced signature #%d at call "
                    "%d (warmup window %d) — an unbucketed shape or dtype "
                    "reached the jitted step",
                    self.fn_name,
                    self.compiles,
                    self.calls,
                    self.warmup_calls,
                )
        return out

    # Pass through the bits of the jit surface used in-tree.
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __repr__(self) -> str:
        return "InstrumentedJit(%s, compiles=%d, calls=%d)" % (
            self.fn_name,
            self.compiles,
            self.calls,
        )


def instrumented_jit(
    fn: Callable,
    name: str,
    warmup_calls: int = DEFAULT_WARMUP_CALLS,
    **jit_kwargs: Any,
):
    """``jax.jit`` with the recompile sentinel attached.

    With ``DMLC_TPU_DEVICE_TELEMETRY=0`` this returns the plain
    ``jax.jit(fn, **jit_kwargs)`` callable — no wrapper object, no counter,
    no per-dispatch branch: the disabled hot path is exactly the
    uninstrumented one (allocation-free, pinned by test like the PR 7
    flow-id discipline). The knob is read once, here, at build time.

    Every learner step and device collective is built here, so this is
    also where the persistent compile cache is placed
    (utils/jax_compat.py) before the first compile.
    """
    import jax

    from dmlc_tpu.utils.jax_compat import place_compile_cache

    place_compile_cache()
    if not device_telemetry_enabled():
        return jax.jit(fn, **jit_kwargs)
    return InstrumentedJit(fn, name, warmup_calls=warmup_calls, **jit_kwargs)


_FLAT_FN_RE = re.compile(r'^dmlc_xla_compiles_total\{.*?fn="((?:[^"\\]|\\.)*)"')


def compile_counts(reg: Optional[Registry] = None) -> Dict[str, int]:
    """Per-function compile totals read back from the registry.

    Keys are the ``fn=`` label values; feeds the bench detail section and
    the one-trace-per-bucket test.
    """
    reg = reg if reg is not None else registry()
    out: Dict[str, int] = {}
    for flat, value in reg.flat_values().items():
        m = _FLAT_FN_RE.match(flat)
        if m:
            out[m.group(1).replace('\\"', '"').replace("\\\\", "\\")] = int(value)
    return out


class H2DMeter:
    """Byte/bandwidth accounting for one feed's host→device dispatch path."""

    __slots__ = ("_m_bytes", "_h_mbps")

    def __init__(self, reg: Optional[Registry] = None, **labels: str):
        reg = reg if reg is not None else registry()
        self._m_bytes = reg.counter(
            "dmlc_feed_h2d_bytes_total",
            "host->device payload bytes submitted through device_put",
            **labels,
        )
        self._h_mbps = reg.histogram(
            "dmlc_feed_h2d_mbps",
            "per-put H2D submission bandwidth, MB/s (bytes over the wall "
            "time of the dispatch call; async backends overstate sustained "
            "bandwidth — read it as submission rate)",
            **labels,
        )

    def note(self, nbytes: int, elapsed_ns: int) -> None:
        if nbytes <= 0:
            return
        self._m_bytes.inc(nbytes)
        if elapsed_ns > 0:
            # bytes/ns → MB/s: x * 1e9 / 1e6 = x * 1e3
            self._h_mbps.observe(nbytes * 1e3 / elapsed_ns)


def h2d_meter(reg: Optional[Registry] = None, **labels: str) -> Optional[H2DMeter]:
    """An :class:`H2DMeter`, or ``None`` when device telemetry is off.

    Callers keep the ``None`` and skip metering entirely — the disabled
    dispatch path has no timing calls and no byte walk.
    """
    if not device_telemetry_enabled():
        return None
    return H2DMeter(reg, **labels)


_state_lock = threading.Lock()
_peak_hbm = 0
_poller_started = False


def sample(reg: Optional[Registry] = None) -> Dict[str, Dict[str, int]]:
    """Refresh per-device memory gauges; returns ``{"hbm": {...}, "live": {...}}``.

    ``hbm`` comes from ``device.memory_stats()`` (``bytes_in_use`` →
    ``dmlc_device_hbm_bytes{device=}``, ``bytes_limit`` →
    ``dmlc_device_hbm_limit_bytes{device=}``); CPU backends report no stats
    and contribute nothing — graceful no-op, never an error. ``live`` is a
    census over ``jax.live_arrays()`` nbytes attributed evenly across each
    array's device set (``dmlc_device_live_bytes{device=}``), which works on
    every backend including CPU.
    """
    if not device_telemetry_enabled():
        return {"hbm": {}, "live": {}}
    try:
        import jax
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        return {"hbm": {}, "live": {}}
    reg = reg if reg is not None else registry()

    hbm: Dict[str, int] = {}
    try:
        devices = jax.local_devices()
    except Exception:
        devices = []
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        label = "%s:%d" % (getattr(dev, "platform", "dev"), getattr(dev, "id", 0))
        used = stats.get("bytes_in_use")
        if used is not None:
            reg.gauge(
                "dmlc_device_hbm_bytes",
                "device memory in use per device (memory_stats bytes_in_use)",
                device=label,
            ).set(int(used))
            hbm[label] = int(used)
        limit = stats.get("bytes_limit")
        if limit:
            reg.gauge(
                "dmlc_device_hbm_limit_bytes",
                "device memory capacity per device (memory_stats bytes_limit)",
                device=label,
            ).set(int(limit))

    live: Dict[str, float] = {}
    try:
        arrays = jax.live_arrays()
    except Exception:
        arrays = []
    for arr in arrays:
        try:
            devs = list(arr.devices())
            nbytes = int(arr.nbytes)
        except Exception:
            continue
        if not devs:
            continue
        share = nbytes / len(devs)
        for dev in devs:
            label = "%s:%d" % (getattr(dev, "platform", "dev"), getattr(dev, "id", 0))
            live[label] = live.get(label, 0.0) + share
    live_int = {k: int(v) for k, v in live.items()}
    for label, nbytes in live_int.items():
        reg.gauge(
            "dmlc_device_live_bytes",
            "live jax array bytes per device (live_arrays census; the "
            "backend-independent HBM proxy)",
            device=label,
        ).set(nbytes)

    global _peak_hbm
    peak_now = max(hbm.values(), default=0)
    if not peak_now:
        peak_now = max(live_int.values(), default=0)
    with _state_lock:
        if peak_now > _peak_hbm:
            _peak_hbm = peak_now
    return {"hbm": hbm, "live": live_int}


def peak_hbm_bytes() -> int:
    """High-water mark across every ``sample()`` so far (this process).

    Prefers ``memory_stats`` bytes; falls back to the live-buffer census on
    backends without stats so bench can still gate a peak on CPU.
    """
    with _state_lock:
        return _peak_hbm


def maybe_start_hbm_poller() -> bool:
    """Start the background HBM sampler once, if ``DMLC_TPU_HBM_POLL_S`` > 0.

    Returns True when a poller is (already) running. Default 0 means no
    thread at all — the periodic path costs nothing unless asked for.
    """
    period = hbm_poll_s()
    if period <= 0 or not device_telemetry_enabled():
        return False
    global _poller_started
    with _state_lock:
        if _poller_started:
            return True
        _poller_started = True

    def _loop():
        while True:
            time.sleep(period)
            try:
                sample()
            except Exception:  # noqa: BLE001 - telemetry must never kill the job
                logger.debug("hbm poll failed", exc_info=True)

    threading.Thread(target=_loop, daemon=True, name="dmlc-hbm-poll").start()
    logger.info("hbm poller started (every %.1fs)", period)
    return True


_capture_lock = threading.Lock()
_capturing = False


def _artifact_dir() -> str:
    """Where capture artifacts land: beside the flight-recorder dump when
    the recorder is armed, else the working directory."""
    rec = flight.recorder()
    path = rec.path() if hasattr(rec, "path") else None
    if path:
        return os.path.dirname(path) or "."
    return "."


def capture_profile(
    seconds: float,
    out_dir: Optional[str] = None,
    req_id: int = 0,
    block: bool = False,
) -> Optional[threading.Thread]:
    """Run ``jax.profiler`` for ``seconds`` in a background thread.

    The artifact directory is ``profile-rank<k>-req<n>/`` beside the
    flight-recorder dump. One capture at a time: overlapping requests are
    dropped (returns None) rather than corrupting the active trace. Always
    records a ``profile.capture`` flight event on completion. ``block=True``
    joins the thread (tests).
    """
    global _capturing
    with _capture_lock:
        if _capturing:
            logger.warning("profile capture already running; dropping req %d", req_id)
            return None
        _capturing = True

    rank = 0
    try:
        rank = int(os.environ.get("DMLC_TASK_ID", "0") or 0)
    except ValueError:
        pass
    base = out_dir if out_dir is not None else _artifact_dir()
    target = os.path.join(base, "profile-rank%d-req%d" % (rank, req_id))
    seconds = max(0.0, float(seconds))

    def _run():
        global _capturing
        ok = False
        try:
            import jax

            os.makedirs(target, exist_ok=True)
            jax.profiler.start_trace(target)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            ok = True
        except Exception as err:  # noqa: BLE001 - capture is best-effort
            logger.warning("profile capture failed: %s", err)
        finally:
            with _capture_lock:
                _capturing = False
            flight.record_event(
                "profile.capture",
                seconds=seconds,
                dir=target,
                req=req_id,
                ok=ok,
            )
            registry().counter(
                "dmlc_device_profile_captures_total",
                "on-demand profiler capture attempts (see ok field of the "
                "profile.capture flight event for failures)",
            ).inc()
            if ok:
                logger.info(
                    "profile capture done: %.1fs -> %s (req %d)",
                    seconds,
                    target,
                    req_id,
                )

    th = threading.Thread(target=_run, daemon=True, name="dmlc-profile-capture")
    th.start()
    if block:
        th.join()
    return th


def detail_section(reg: Optional[Registry] = None) -> Dict[str, Any]:
    """The ``device_telemetry`` block for bench's detail artifact.

    Compile counts per fn, the process-lifetime peak HBM, and the mean H2D
    submission bandwidth — the keys obs/sentry.py knows how to gate
    (``compiles.<fn>`` and ``hbm.peak_bytes`` lower-better, ``h2d_mbps``
    higher-better).
    """
    reg = reg if reg is not None else registry()
    sample(reg)
    out: Dict[str, Any] = {"compiles": compile_counts(reg)}
    peak = peak_hbm_bytes()
    if peak > 0:
        out["peak_hbm_bytes"] = peak
    h2d_sum = 0.0
    h2d_count = 0.0
    for flat, value in reg.flat_values().items():
        if flat.startswith("dmlc_feed_h2d_mbps"):
            if flat.endswith(":sum"):
                h2d_sum += value
            elif flat.endswith(":count"):
                h2d_count += value
    if h2d_count > 0:
        out["h2d_mbps"] = round(h2d_sum / h2d_count, 1)
    return out


def reset() -> None:
    """Forget process-level state (tests): peak HBM, poller/capture flags,
    and the xla cost-record cache (stale records would otherwise pin their
    gauges to a previous test's registry)."""
    global _peak_hbm, _poller_started, _capturing
    with _state_lock:
        _peak_hbm = 0
        _poller_started = False
    with _capture_lock:
        _capturing = False
    xla_cost.reset()

"""Compiled-program cost analytics: per-jit-site FLOP/byte/memory records.

PR 13 moved the training hot path inside the compiled graph (in-graph
``psum`` SPMD steps) and PR 16 made it device-resident, which left the
obs plane blind past the jit boundary: FLOPs executed, HBM bytes moved,
and ICI collective traffic all happen inside one opaque dispatch. This
module restores that visibility *at compile time, never per step*: when
an :class:`~dmlc_tpu.obs.device_telemetry.InstrumentedJit` site compiles
a new (fn, bucket-shape) signature, :func:`note_compile` asks jit for
the executable the call has just built: ``jitted.lower(*args)`` on the
same argument objects reads only their avals and shardings (which a
donated, deleted array keeps), hits jit's trace and lowering caches, and
``.compile()`` on that cached lowering returns the executable already
attached to it — no second trace (the recompile sentinel is untouched)
and no second XLA compile (``extract_ms`` on each record is the proof:
milliseconds, not the site's compile time). It then reads the
executable's analytics:

- ``compiled.cost_analysis()`` → per-call ``flops`` and ``bytes
  accessed`` (``dmlc_xla_flops{fn=}``,
  ``dmlc_xla_bytes_accessed{fn=}``);
- ``compiled.memory_analysis()`` → peak program bytes: argument +
  output + temp + generated code, minus donation aliasing
  (``dmlc_xla_peak_bytes{fn=}``);
- the optimized HLO text → bytes moved by in-graph collectives
  (all-reduce / all-gather / reduce-scatter / collective-permute /
  all-to-all result shapes summed; ``dmlc_xla_collective_bytes{fn=}``)
  — the allreduce traffic ``dmlc_collective_*`` stopped seeing when the
  psum moved in-graph.

Records are cached per (fn, bucket signature): a bucket that has been
analyzed once is never re-extracted (pinned by test), so steady-state
training pays nothing. Analytics never kill a step: a probe that fails
leaves its gauge absent and logs ONE warning naming the jit site — never
a silent absence. Under ``DMLC_TPU_METRICS=0`` the hook returns
immediately.

The same records feed the model-based roofline: obs/goodput.py turns
steps × per-step flops into an MFU verdict against the device's
published peaks (:data:`DEVICE_PEAKS`, keyed by ``device_kind``;
``DMLC_TPU_PEAK_FLOPS`` / ``DMLC_TPU_PEAK_HBM_GBPS`` /
``DMLC_TPU_ICI_PEAK_GBPS`` override), the ``/xla`` status endpoint and
``obs-report --xla`` render the per-site tables, and bench's detail
artifact carries the ``xla`` section plus ``sgd_mfu`` (sentry-gated
higher-is-better).
"""

from __future__ import annotations

import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from dmlc_tpu.obs.metrics import Registry, metrics_enabled, registry
from dmlc_tpu.params import knobs

logger = logging.getLogger("dmlc_tpu.obs.xla_cost")

__all__ = [
    "bucket_signature",
    "collective_bytes_from_hlo",
    "note_compile",
    "extraction_count",
    "records",
    "per_fn",
    "sites_from_flat",
    "step_costs",
    "detail_section",
    "DEVICE_PEAKS",
    "device_peaks",
    "reset",
]

_lock = threading.Lock()
# (fn, bucket signature) -> record; insertion-ordered, so per_fn() keeps
# the LATEST bucket per site while counting all of them
_records: Dict[Tuple[str, str], Dict[str, Any]] = {}
_extractions = 0
# (fn, probe) pairs whose failure has been logged — once each
_warned: set = set()

#: the gauge fields every record carries (and the flat-metric parser reads)
FIELDS = ("flops", "bytes_accessed", "peak_bytes", "collective_bytes")


def bucket_signature(args: tuple, kwargs: Optional[dict] = None) -> str:
    """Shape/dtype/placement signature of one call's argument tree — the
    cache key half that distinguishes what jit compiles separately:
    FixedShapePool buckets, and the same shapes placed over a mesh (a
    4-chip SPMD step is a different program from the one-chip step with
    equal shapes, and its record carries the collective bytes).
    Non-array leaves contribute their type name only (their values do
    not retrace)."""
    import jax

    parts: List[str] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs or {})):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            parts.append(type(leaf).__name__)
            continue
        sig = "%s[%s]" % (dtype, ",".join(str(d) for d in shape))
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is not None:  # mesh-placed: NamedSharding
            sig += "@%s%s" % (dict(leaf.sharding.mesh.shape), tuple(spec))
        parts.append(sig)
    return ";".join(parts)


# one collective *call site* per match: the op name must be applied
# (trailing "(" ), so parameter/operand shape mentions don't count, and
# async pairs count once — "-start" matches, "-done" cannot (the hyphen
# is outside [\w.]).
_COLL_CALL_RE = re.compile(
    r"=\s*([^=]*?)\s*"
    r"(?:all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?[\w.]*\(")
_SHAPE_RE = re.compile(r"\b(pred|[a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")


def _dtype_bytes(token: str) -> int:
    if token == "pred":
        return 1
    m = re.search(r"(\d+)", token)
    bits = int(m.group(1)) if m else 8
    return max(1, bits // 8)


def collective_bytes_from_hlo(hlo_text: str) -> float:
    """Bytes produced by in-graph collective ops, summed over the result
    shapes in one optimized-HLO module text. XLA's CPU ``cost_analysis``
    carries no collective byte keys, so this is derived from the program
    itself — the per-call ICI payload of an SPMD psum step."""
    total = 0.0
    for m in _COLL_CALL_RE.finditer(hlo_text):
        for token, dims in _SHAPE_RE.findall(m.group(1)):
            count = 1
            for dim in dims.split(","):
                if dim:
                    count *= int(dim)
            total += count * _dtype_bytes(token)
    return total


def _warn_once(fn_name: str, probe: str, err: BaseException) -> None:
    """One warning per (jit site, probe): the gauge that probe feeds is
    absent for this site and the log says why."""
    with _lock:
        if (fn_name, probe) in _warned:
            return
        _warned.add((fn_name, probe))
    logger.warning(
        "xla cost %s unavailable for jit site %s: %s: %s",
        probe, fn_name, type(err).__name__, err)


def _extract(fn_name: str, jitted, args: tuple,
             kwargs: dict) -> Dict[str, float]:
    """One executable's analytics; a probe that fails leaves its field
    out (and warns once for the site) rather than reporting a zero."""
    compiled = jitted.lower(*args, **kwargs).compile()
    out: Dict[str, float] = {}
    try:
        analysis = compiled.cost_analysis()
        out["flops"] = max(0.0, float(analysis.get("flops", 0.0) or 0.0))
        out["bytes_accessed"] = max(
            0.0, float(analysis.get("bytes accessed", 0.0) or 0.0))
    except Exception as err:  # noqa: BLE001 - analytics never kill a step
        _warn_once(fn_name, "cost_analysis", err)
    try:
        mem = compiled.memory_analysis()
        peak = 0.0
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
            peak += float(getattr(mem, field, 0) or 0)
        # donated buffers alias an argument onto an output: counted once
        peak -= float(getattr(mem, "alias_size_in_bytes", 0) or 0)
        out["peak_bytes"] = max(0.0, peak)
    except Exception as err:  # noqa: BLE001
        _warn_once(fn_name, "memory_analysis", err)
    try:
        out["collective_bytes"] = collective_bytes_from_hlo(
            compiled.as_text())
    except Exception as err:  # noqa: BLE001
        _warn_once(fn_name, "hlo text", err)
    return out


def _set_gauges(fn_name: str, rec: Dict[str, Any],
                reg: Optional[Registry] = None) -> None:
    """One gauge per field the extraction produced — a probe that failed
    leaves its gauge absent for the site, never a zero."""
    reg = reg if reg is not None else registry()
    if "flops" in rec:
        reg.gauge(
            "dmlc_xla_flops",
            "per-call FLOPs of the latest compiled bucket per jit site "
            "(XLA cost_analysis)", fn=fn_name,
        ).set(float(rec["flops"]))
    if "bytes_accessed" in rec:
        reg.gauge(
            "dmlc_xla_bytes_accessed",
            "per-call memory traffic of the latest compiled bucket per jit "
            "site (XLA cost_analysis 'bytes accessed')", fn=fn_name,
        ).set(float(rec["bytes_accessed"]))
    if "peak_bytes" in rec:
        reg.gauge(
            "dmlc_xla_peak_bytes",
            "compiled-program peak bytes per jit site (memory_analysis: "
            "argument+output+temp+code, donation aliases counted once)",
            fn=fn_name,
        ).set(float(rec["peak_bytes"]))
    if "collective_bytes" in rec:
        reg.gauge(
            "dmlc_xla_collective_bytes",
            "per-call bytes produced by in-graph collectives per jit site "
            "(summed from the optimized HLO's all-reduce/all-gather/"
            "reduce-scatter/collective-permute/all-to-all result shapes)",
            fn=fn_name,
        ).set(float(rec["collective_bytes"]))


def note_compile(fn_name: str, jitted, args: tuple,
                 kwargs: Optional[dict] = None,
                 reg: Optional[Registry] = None) -> Optional[Dict[str, Any]]:
    """Record one jit site's compiled-program analytics; the
    InstrumentedJit compile-branch hook.

    Runs only when a call actually compiled, and extracts at most once
    per (fn, bucket signature) — a signature already analyzed returns
    its cached record with no lowering, no compile, no gauge write.
    ``args`` are the objects the call just consumed (donated arrays
    included: only their avals and shardings are read). Never raises:
    a failure returns None after one warning naming the site."""
    if not metrics_enabled():
        return None
    kwargs = kwargs or {}
    t0 = time.monotonic_ns()
    try:
        key = (fn_name, bucket_signature(args, kwargs))
        with _lock:
            rec = _records.get(key)
        if rec is not None:
            return rec
        costs = _extract(fn_name, jitted, args, kwargs)
    except Exception as err:  # noqa: BLE001 - analytics never kill a step
        _warn_once(fn_name, "extraction", err)
        return None
    rec = dict(costs, fn=fn_name, bucket=key[1],
               extract_ms=round((time.monotonic_ns() - t0) / 1e6, 3))
    global _extractions
    with _lock:
        if key in _records:  # lost a race: first extraction already won
            return _records[key]
        _records[key] = rec
        _extractions += 1
    _set_gauges(fn_name, rec, reg)
    return rec


def extraction_count() -> int:
    """Extractions actually performed this process (cache misses only) —
    what the no-re-extract pin asserts against."""
    with _lock:
        return _extractions


def records() -> List[Dict[str, Any]]:
    """Every cached record, extraction order (one per fn × bucket)."""
    with _lock:
        return [dict(rec) for rec in _records.values()]


def per_fn() -> Dict[str, Dict[str, Any]]:
    """Latest record per jit site plus its bucket count — the ``/xla``
    local view and bench's ``xla`` detail section rows."""
    out: Dict[str, Dict[str, Any]] = {}
    with _lock:
        items = list(_records.items())
    for (fn, _bucket), rec in items:
        row = dict(rec)
        row["buckets"] = out[fn]["buckets"] + 1 if fn in out else 1
        out[fn] = row
    return out


_FLAT_XLA_RE = re.compile(
    r'^(dmlc_xla_(?:flops|bytes_accessed|peak_bytes|collective_bytes))'
    r'\{[^}]*?fn="((?:[^"\\]|\\.)*)"')


def sites_from_flat(flat: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Per-site cost rows parsed back out of a flat registry snapshot —
    how the tracker reads a *worker's* records off its heartbeat payload
    (the gauges ride ``flat_values()`` like every other metric)."""
    out: Dict[str, Dict[str, float]] = {}
    for key, value in flat.items():
        m = _FLAT_XLA_RE.match(key)
        if not m:
            continue
        name, fn = m.groups()
        fn = fn.replace('\\"', '"').replace("\\\\", "\\")
        out.setdefault(fn, {})[name[len("dmlc_xla_"):]] = float(value)
    return out


def step_costs(flat: Dict[str, float]) -> Dict[str, float]:
    """The model train step's per-call flops/bytes from a flat snapshot:
    the max across ``*.step`` / ``*.step_mp`` sites (the dominant bucket
    of the hot step). Feeds goodput's window flop estimate
    (steps × per-step flops) and the MFU verdict."""
    out = {"flops": 0.0, "bytes": 0.0}
    for fn, rec in sites_from_flat(flat).items():
        if fn.rsplit(".", 1)[-1] not in ("step", "step_mp"):
            continue
        out["flops"] = max(out["flops"], rec.get("flops", 0.0))
        out["bytes"] = max(out["bytes"], rec.get("bytes_accessed", 0.0))
    return out


def detail_section() -> Dict[str, Any]:
    """The ``xla`` block for bench's detail artifact and the ``/xla``
    endpoint's local half: per-site latest records + extraction count."""
    return {"sites": per_fn(), "extractions": extraction_count()}


# ---------------------------------------------------------------------------
# published peaks: the one table every roofline denominator comes from
# ---------------------------------------------------------------------------

#: Per-chip peaks keyed by ``jax.Device.device_kind``, in the units of the
#: goodput ceilings they fill (``peak_flops`` FLOP/s, ``hbm_gbps`` and
#: ``ici_gbps`` GB/s). v5e — Google Cloud documentation, "TPU v5e": 197
#: TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops": 197e12,
        "hbm_gbps": 819.0,
        "ici_gbps": 200.0,
    },
}


def device_peaks(device_kind: Optional[str] = None) -> Dict[str, float]:
    """Roofline peaks for this process's device: the :data:`DEVICE_PEAKS`
    row for ``device_kind`` (default: ``jax.devices()[0].device_kind``),
    with ``DMLC_TPU_PEAK_FLOPS`` / ``DMLC_TPU_PEAK_HBM_GBPS`` /
    ``DMLC_TPU_ICI_PEAK_GBPS`` winning where set. A kind that is not in
    the table and not overridden yields NO key — callers then report no
    MFU / HBM fraction / ICI utilization at all, never a made-up one.
    Initializes the jax backend unless ``device_kind`` is passed, so only
    processes that own a device call it bare."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peaks = dict(DEVICE_PEAKS.get(device_kind, {}))
    for key, override in (("peak_flops", knobs.peak_flops()),
                          ("hbm_gbps", knobs.peak_hbm_gbps()),
                          ("ici_gbps", knobs.ici_peak_gbps())):
        if override > 0.0:
            peaks[key] = override
    return peaks


def reset() -> None:
    """Forget process-level state (tests): records, the extraction
    counter, and which failures were already logged."""
    global _extractions
    with _lock:
        _records.clear()
        _warned.clear()
        _extractions = 0

"""dmlc_tpu.obs — unified metrics + tracing.

One observability surface for the whole stack (the tf.data lesson,
arXiv:2101.12127: uniform per-stage metrics are the precondition for
bottleneck diagnosis and auto-tuning):

- :func:`registry` — the process-wide label-aware Counter/Gauge/Histogram
  store every stage counter lives in (``DMLC_TPU_METRICS=0`` disables;
  see obs/metrics.py)
- :func:`span` — the Chrome-trace span context manager gated by
  ``DMLC_TPU_TRACE=<path>``; with ``hist=`` it also observes its duration
  into a registry histogram, tracing on or off (see obs/trace.py)
- :func:`new_flow` / :func:`flow_start` / :func:`flow_step` /
  :func:`flow_end` — causal dataflow arrows (Chrome-trace flow events)
  connecting a chunk's io→parse→stage→dispatch→consume journey across
  threads and ranks; :func:`current_flow` / :func:`set_current_flow`
  carry the in-flight chunk's id through the fit loop, and
  :func:`current_batch` / :func:`set_current_batch` the batch's
  ``(pass_, batch)`` identity
- exporters — JSONL / Prometheus textfile / log-sink summary, driven at
  epoch boundaries by :func:`export_epoch` via ``DMLC_TPU_METRICS_EXPORT``
- :func:`cross_host_snapshot` / :func:`report_skew` — per-host
  min/median/max over a ``collective.DeviceEngine`` allreduce
- ``obs.plane`` — the job-wide observability plane: workers piggyback
  metric/span payloads on tracker heartbeats; the tracker serves
  ``/healthz /workers /metrics /trace`` over HTTP when
  ``DMLC_TPU_STATUS_PORT`` is set (see obs/plane.py)
- ``obs.flight`` — crash flight recorder: a bounded ring of recent
  spans/metric deltas/resilience events dumped to
  ``flightrec-rank<k>.json`` on fatal error when ``DMLC_TPU_FLIGHTREC``
  names a directory (see obs/flight.py)
- ``obs.device_telemetry`` — the device side: :func:`instrumented_jit`
  recompile sentinel, HBM/live-buffer gauges, H2D bandwidth metering,
  and on-demand ``jax.profiler`` capture through the status plane
  (``DMLC_TPU_DEVICE_TELEMETRY``; see obs/device_telemetry.py)
- ``obs.goodput`` — the runtime goodput ledger: per-window stage
  budgets, roofline attribution, and the live binding-constraint
  verdict served by ``/goodput``, obs-top, obs-report, and bench
  (see obs/goodput.py)
- ``obs.watchdog`` — the in-run SLO watchdog over ledger windows:
  throughput collapse, recompile storms, pipeline stalls, straggler
  ranks, non-finite numerics; fires ``watchdog.alert`` flight events
  (see obs/watchdog.py)
- ``obs.audit`` — the cross-rank determinism audit plane: streaming
  per-stage content-digest chains (io_read/parse/batch/model), epoch
  self-checks, tracker-side cross-rank comparison behind ``/audit``,
  and ``audit-rank<k>.json`` replay bundles on the first fork
  (``DMLC_TPU_AUDIT``; see obs/audit.py)

Metric names follow ``dmlc_<area>_<name>_<unit>`` and every registered
name is documented in docs/observability.md (enforced by
``scripts/check_metric_names.py`` / tests/test_metric_lint.py).
"""

from dmlc_tpu.obs.aggregate import cross_host_snapshot, report_skew
from dmlc_tpu.obs.device_telemetry import instrumented_jit
from dmlc_tpu.obs.goodput import GoodputLedger, attribute, ledger
from dmlc_tpu.obs.watchdog import Watchdog, make_watchdog
from dmlc_tpu.obs.exporters import (
    export_epoch,
    export_jsonl,
    export_prometheus,
    prometheus_lines,
    summary_line,
)
from dmlc_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    registry,
)
from dmlc_tpu.obs.trace import (
    NOOP_SPAN,
    clear as clear_trace,
    current_batch,
    current_flow,
    events as trace_events,
    flow_end,
    flow_start,
    flow_step,
    flush as flush_trace,
    new_flow,
    set_current_batch,
    set_current_flow,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "registry",
    "span",
    "NOOP_SPAN",
    "new_flow",
    "flow_start",
    "flow_step",
    "flow_end",
    "current_flow",
    "set_current_flow",
    "current_batch",
    "set_current_batch",
    "trace_events",
    "clear_trace",
    "flush_trace",
    "export_epoch",
    "export_jsonl",
    "export_prometheus",
    "prometheus_lines",
    "summary_line",
    "cross_host_snapshot",
    "report_skew",
    "instrumented_jit",
    "GoodputLedger",
    "attribute",
    "ledger",
    "Watchdog",
    "make_watchdog",
]

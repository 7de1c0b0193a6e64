"""Ingest-pipeline tuning knobs, resolved through the typed env layer.

The ingest→HBM pipeline (data/pipeline.py + device/feed.py) has three
load-bearing degrees of freedom, each exposed the reference way
(parameter.h:1035-1063 typed GetEnv) so deployments tune them without
code changes:

- ``DMLC_TPU_NTHREAD``   — parse workers per parser (chunk fan-out width)
- ``DMLC_TPU_PREFETCH``  — device transfers kept in flight ahead of the
  consumer (``BatchSpec.prefetch``; 1 = classic double-buffer)
- ``DMLC_TPU_HOST_PREFETCH`` — parsed-but-undispatched host batches the
  feed's producer thread may buffer (-1 = auto: 0 on a 1-core host,
  else 2 — ``DeviceFeed.host_prefetch``'s own default)

Every call site that previously hard-coded a width resolves through
these helpers, so one env var retunes the whole stack (create_parser,
DeviceFeed, the learners' fit loops, bench.py).

The observability layer (dmlc_tpu/obs) adds three more:

- ``DMLC_TPU_METRICS`` — 0 disables the metrics registry (registrations
  hand out a shared no-op child; default 1, whose hot path is one
  lock-and-add)
- ``DMLC_TPU_TRACE`` — path for the Chrome trace-event JSON written by
  ``obs.span`` (empty = tracing off, the default)
- ``DMLC_TPU_METRICS_EXPORT`` — path the registry is exported to at
  epoch boundaries: ``*.prom`` → Prometheus textfile, else JSONL
  (empty = no file export, the default)
- ``DMLC_TPU_HEARTBEAT_GAP`` — seconds without a worker heartbeat
  before the tracker logs it as a straggler (default 60)

The job observability plane (obs/plane.py + obs/flight.py) adds:

- ``DMLC_TPU_STATUS_PORT`` — port for the tracker's HTTP status server
  (0 = ephemeral; unset = no server, no thread, no socket — the default)
- ``DMLC_TPU_STATUS_URI`` — ``host:port`` of the running status server,
  exported *by* the tracker to workers (informational; never set it
  yourself)
- ``DMLC_TPU_OBS_PUBLISH`` — workers piggyback obs payloads on tracker
  heartbeats when 1; exported by the tracker when its status plane is
  armed (default off — a worker never surprises a reference tracker)
- ``DMLC_TPU_OBS_PAYLOAD_MAX`` — byte cap for one heartbeat obs payload
  (default 65536; oldest spans shed first, counted in
  ``dmlc_obs_spans_dropped_total``)
- ``DMLC_TPU_FLIGHTREC`` — directory the crash flight recorder dumps
  ``flightrec-rank<k>.json`` into (empty = recorder off, the default)
- ``DMLC_TPU_FLIGHTREC_CAP`` — flight-recorder ring capacity in records
  (default 256)

The resilience layer (dmlc_tpu/resilience) adds five more:

- ``DMLC_TPU_RETRY_BUDGET`` — process-wide retry token bucket capacity
  (0 = unlimited, the default; see resilience/retry.py)
- ``DMLC_TPU_RETRY_DEADLINE_S`` — default wall-clock deadline per
  retried logical call, seconds (0 = none, the default)
- ``DMLC_TPU_FAULTS`` — deterministic fault-injection spec, e.g.
  ``io.read:p=0.02:seed=7;collective.send:nth=3`` (empty = every
  faultpoint is a shared no-op, the default)
- ``DMLC_TPU_HEDGE_S`` — latency threshold in seconds after which the
  readahead fetch path issues one hedged backup request (0 = hedging
  off, the default)
- ``DMLC_TPU_CKPT_FALLBACK_URI`` — secondary checkpoint directory that
  ``CheckpointManager`` commits to when the primary URI exhausts its
  retry budget (empty = no fallback, the default)

Preemption-proof snapshots (collective/snapshot.py +
resilience/preempt.py, see docs/robustness.md "Preemption & resume")
add two more:

- ``DMLC_TPU_SNAP_EVERY_S`` — wall-clock job-snapshot cadence in
  seconds: with a ``Snapshotter`` armed, an epoch boundary also commits
  when this much time passed since the last committed snapshot, on top
  of the epoch cadence (0 = epoch cadence only, the default)
- ``DMLC_TPU_PREEMPT_DEADLINE_S`` — seconds the preemption handler
  budgets between a preemption notice (SIGTERM or injected
  ``preempt.notice``) and process exit; the just-in-time snapshot
  commit must land inside it (default 30)

Elastic membership (tracker/rendezvous.py + collective, see
docs/robustness.md "Elastic membership") adds four more:

- ``DMLC_TPU_ELASTIC`` — workers opt into generation re-rendezvous: a
  collective failure (or a bumped heartbeat ack) re-enters the tracker
  with ``cmd='elastic'`` into a rebuilt world instead of ``recover``
  into the old one (default off — fixed-world rabit semantics)
- ``DMLC_TPU_ELASTIC_WINDOW_S`` — tracker-side quiescence window for a
  membership transition: the generation commits this many seconds after
  the last entrant arrived (default 3)
- ``DMLC_TPU_EVICT_AFTER_S`` — tracker-side eviction policy: a rank
  whose last heartbeat is older than this is marked evicted and the
  survivors drain into a smaller world via ``run_with_recovery``
  (0 = eviction off, the default; requires workers that heartbeat)
- ``DMLC_TPU_SPARE`` — set by the launcher (``--spares N``) on warm
  spare tasks: ``collective.init`` registers via the tracker ``join``
  handshake and blocks until a transition activates the spare (or
  exits 0 if the job finishes without needing it)

The fault-tolerant data service (data/dispatcher.py + data/service.py,
see docs/distributed.md "Disaggregated ingest") adds five more:

- ``DMLC_TPU_DATA_CHUNKS`` — chunks the dispatcher splits one dataset
  into (the lease/requeue granularity; default 16)
- ``DMLC_TPU_DATA_LEASE_S`` — seconds a leased/delivered chunk may stay
  unacked before the dispatcher requeues it (default 30)
- ``DMLC_TPU_DATA_DEAD_S`` — seconds of heartbeat silence before a data
  worker is declared dead and its leases requeued (default 10)
- ``DMLC_TPU_DATA_PENDING_CAP`` — cap on one service's undelivered-block
  requeue stash; a full stash backpressures then drops (default 64;
  0 or negative = unbounded, the pre-cap behavior)
- ``DMLC_TPU_DATA_HEDGE_S`` — seconds of fetch silence before a
  dispatcher-mode client hedges the fetch against a second live worker
  (0 = hedging off, the default)

The multi-tenant fleet layer (data/dispatcher.py jobs + the shared
source cache + the autoscaler, see docs/distributed.md "Multi-tenant
fleet") adds four more:

- ``DMLC_TPU_DATA_MAX_JOBS`` — tenant jobs one dispatcher admits before
  refusing registration with typed backpressure (DataBusyError;
  default 8)
- ``DMLC_TPU_DATA_JOB_INFLIGHT`` — default per-job cap on
  leased+delivered chunks in flight; the fair-share scheduler answers
  ``busy`` above it (0 = uncapped, the default)
- ``DMLC_TPU_DATA_CACHE_MB`` — byte budget (in MiB) of the per-worker
  job-shared source cache: N jobs reading one dataset parse it once
  (default 256; 0 disables the tier, every parse goes direct)
- ``DMLC_TPU_DATA_SCALE_INTERVAL_S`` — seconds between worker-autoscaler
  control-loop ticks (default 1.0)

Device telemetry (obs/device_telemetry.py, see docs/observability.md
"Device telemetry") adds two more:

- ``DMLC_TPU_DEVICE_TELEMETRY`` — the recompile sentinel, H2D meter, and
  HBM gauges (default on; 0 makes ``instrumented_jit`` return the plain
  ``jax.jit`` callable and ``h2d_meter`` return None — the disabled hot
  path is byte-for-byte the uninstrumented one)
- ``DMLC_TPU_HBM_POLL_S`` — period in seconds for the background HBM
  sampler thread (0 = no thread, the default; sampling still happens at
  payload-publish and bench boundaries)

The collective engine layer (collective/__init__.py, see
docs/distributed.md "Device collectives") adds:

- ``DMLC_TPU_COLLECTIVE`` — engine selection for ``collective.init``:
  ``auto`` (default), ``device`` (in-mesh XLA collectives — the SPMD
  training path), ``socket`` (reference rabit tree/ring, the
  CPU/cross-host fallback), ``local``. An explicit ``engine=`` argument
  to ``init`` always beats the env.

The vectorized text-parse path (data/vparse.py + cpp/parse_simd.cc, see
docs/pipeline.md "Vectorized parse") adds three more:

- ``DMLC_TPU_PARSE_BACKEND`` — chunk-parse implementation selector:
  ``auto`` (default: native core when loadable, else the vectorized
  numpy path), ``native`` (native-or-vector, never scalar), ``vector``
  (numpy columnar path even when the native core is available — the
  parity suite's workhorse), ``scalar`` (pure-Python reference oracle)
- ``DMLC_TPU_PARSE_PROCS`` — when > 0, PipelinedParser routes chunk
  parses through a pool of that many worker *processes* instead of
  parsing on its worker threads (GIL-free scaling for the Python parse
  backends; ordering, backpressure and error poisoning are unchanged
  because the OrderedWindow threads block on the process futures)
- ``DMLC_TPU_SIMD`` — native engine dispatch: unset/empty = adaptive
  (a first-line probe routes long-feature-id corpora to the AVX2 tile
  engine, short-id corpora to the scalar SWAR core), ``1`` = always use
  the engine when the CPU supports it (parity tests force this),
  anything else = engine off

The goodput ledger and runtime watchdog (obs/goodput.py +
obs/watchdog.py, see docs/observability.md "Goodput & attribution")
add five more:

- ``DMLC_TPU_WATCHDOG_STALL_S`` — cumulative seconds of zero ledger
  progress before the watchdog fires a ``stall`` alert (default 60;
  0 disables stall detection)
- ``DMLC_TPU_WATCHDOG_PROFILE`` — when 1, a firing watchdog triggers
  the on-demand device profiler capture for the regression window
  (default off)
- ``DMLC_TPU_PARSE_PEAK_MBPS`` — roofline ceiling for the parse stage
  in MB/s (default 1000 — the vectorized parse_only tier)
- ``DMLC_TPU_STEP_PEAK_MBPS`` — roofline ceiling for the device step's
  byte rate in MB/s (default 0 = unknown; set from the model's measured
  FLOP rate)
- ``DMLC_TPU_ICI_PEAK_GBPS`` — override for the per-chip interconnect
  peak in GB/s (default 0 = the device kind's published figure in
  ``obs.xla_cost.DEVICE_PEAKS``; the same figure bench_collective.py
  scores utilization against)

The compiled-step cost attribution layer (obs/xla_cost.py, see
docs/observability.md "Compiled-step cost attribution") adds two
more:

- ``DMLC_TPU_PEAK_FLOPS`` — override for the roofline peak in FLOP/s
  behind the MFU verdict (default 0 = the device kind's published peak,
  ``obs.xla_cost.DEVICE_PEAKS``; an unknown kind gives no MFU)
- ``DMLC_TPU_PEAK_HBM_GBPS`` — override for the memory-bandwidth peak
  in GB/s behind the achieved-HBM-fraction verdict (default 0 = the
  device kind's published peak, as above)

Baked columnar shards (io/shard.py + tools/bake.py, see
docs/pipeline.md "Baked shards & global shuffle") add three more:

- ``DMLC_TPU_SHUFFLE`` — windowed global-shuffle seed for shard reads
  (≥ 0 arms a seeded permutation of the global window table, a pure
  function of (seed, epoch); -1 — the default — reads windows in baked
  order). The ``shuffle_chunks`` URI arg beats the env per dataset.
- ``DMLC_TPU_SHUFFLE_WINDOW`` — shuffle unit in consecutive baked
  windows (default 1, floor 1): larger units trade shuffle quality for
  longer sequential runs on disk
- ``DMLC_TPU_SHARD_MMAP`` — zero-copy shard reads: windows decode as
  ``np.frombuffer`` views over one file mapping (default on; 0 falls
  back to seek+read per window — NFS or map-exhausted hosts)

The determinism audit plane (obs/audit.py, see docs/observability.md
"Audit plane") adds two more:

- ``DMLC_TPU_AUDIT`` — streaming stage-digest ledger: ``1``/``full``
  digests every chunk/batch/step, ``sample`` digests every
  ``DMLC_TPU_AUDIT_SAMPLE_N``-th chunk, anything else (the default)
  hands every call site the shared no-op auditor — the hot path stays
  allocation-free
- ``DMLC_TPU_AUDIT_SAMPLE_N`` — sampling stride for ``sample`` mode
  (default 16, floor 1)

``KNOWN_KNOBS`` below is the authoritative list of every
``DMLC_TPU_*`` variable the tree reads; ``scripts/check_faultpoints.py``
fails CI when a knob is referenced anywhere without being registered
here.
"""

from __future__ import annotations

from typing import Optional

from dmlc_tpu.params.env import get_env


def default_nthread(explicit: Optional[int] = None) -> int:
    """Parse-worker count: the explicit argument when given, else the
    ``DMLC_TPU_NTHREAD`` env knob, else 2 (the reference's default)."""
    if explicit is not None:
        return max(1, int(explicit))
    return max(1, get_env("DMLC_TPU_NTHREAD", 2))


def default_prefetch(explicit: Optional[int] = None) -> int:
    """Device-transfer window: explicit argument, else ``DMLC_TPU_PREFETCH``,
    else 1 (double-buffer)."""
    if explicit is not None:
        return max(1, int(explicit))
    return max(1, get_env("DMLC_TPU_PREFETCH", 1))


def default_host_prefetch(explicit: Optional[int] = None) -> Optional[int]:
    """Host-batch queue depth: explicit argument, else
    ``DMLC_TPU_HOST_PREFETCH`` (-1 → None → DeviceFeed's cpu-count auto),
    else None."""
    if explicit is not None:
        return explicit
    val = get_env("DMLC_TPU_HOST_PREFETCH", -1)
    return None if val < 0 else val


def metrics_enabled() -> bool:
    """Whether the obs metrics registry hands out live children
    (``DMLC_TPU_METRICS``, default on). Read at metric *registration*
    time, never on the per-increment path."""
    return get_env("DMLC_TPU_METRICS", True)


def trace_path() -> str:
    """Chrome-trace output path for ``obs.span`` (``DMLC_TPU_TRACE``;
    empty = tracing off)."""
    return get_env("DMLC_TPU_TRACE", "")


def metrics_export_path() -> str:
    """Epoch-boundary registry export target (``DMLC_TPU_METRICS_EXPORT``;
    ``*.prom`` → Prometheus textfile, anything else → JSONL appends,
    empty = no file export)."""
    return get_env("DMLC_TPU_METRICS_EXPORT", "")


def heartbeat_gap() -> float:
    """Straggler threshold in seconds for tracker heartbeats
    (``DMLC_TPU_HEARTBEAT_GAP``, default 60)."""
    return float(get_env("DMLC_TPU_HEARTBEAT_GAP", 60.0))


def status_port() -> Optional[int]:
    """Tracker status-server port (``DMLC_TPU_STATUS_PORT``; 0 =
    ephemeral). None — the default — means no server at all: the tracker
    keeps the shared no-op plane, binds nothing, starts no thread."""
    val = get_env("DMLC_TPU_STATUS_PORT", -1)
    return None if val < 0 else val


def obs_publish_enabled() -> bool:
    """Whether this worker piggybacks obs payloads onto tracker
    heartbeats (``DMLC_TPU_OBS_PUBLISH``; exported by the tracker when
    its status plane is armed, default off)."""
    return get_env("DMLC_TPU_OBS_PUBLISH", False)


def obs_payload_max() -> int:
    """Byte cap for one heartbeat obs payload
    (``DMLC_TPU_OBS_PAYLOAD_MAX``, default 64 KiB, floor 1 KiB so the
    liveness + clock-probe core always fits)."""
    return max(1024, get_env("DMLC_TPU_OBS_PAYLOAD_MAX", 65536))


def flightrec_dir() -> str:
    """Crash flight-recorder dump directory (``DMLC_TPU_FLIGHTREC``;
    empty = recorder off, the default)."""
    return get_env("DMLC_TPU_FLIGHTREC", "")


def flightrec_capacity() -> int:
    """Flight-recorder ring capacity in records
    (``DMLC_TPU_FLIGHTREC_CAP``, default 256, floor 16)."""
    return max(16, get_env("DMLC_TPU_FLIGHTREC_CAP", 256))


def retry_budget_tokens() -> int:
    """Process-wide retry token-bucket capacity
    (``DMLC_TPU_RETRY_BUDGET``; 0 = unlimited, the default)."""
    return max(0, get_env("DMLC_TPU_RETRY_BUDGET", 0))


def retry_deadline_s() -> float:
    """Default wall-clock deadline per retried logical call in seconds
    (``DMLC_TPU_RETRY_DEADLINE_S``; 0 = no deadline, the default)."""
    return max(0.0, float(get_env("DMLC_TPU_RETRY_DEADLINE_S", 0.0)))


def faults_spec() -> str:
    """The deterministic fault-injection spec (``DMLC_TPU_FAULTS``;
    empty = faultpoints are a shared no-op, the default). Grammar in
    resilience/faults.py and docs/robustness.md."""
    return get_env("DMLC_TPU_FAULTS", "")


def hedge_threshold_s() -> float:
    """Latency threshold after which the readahead fetch path issues a
    single hedged backup request (``DMLC_TPU_HEDGE_S``; 0 = hedging
    off, the default)."""
    return max(0.0, float(get_env("DMLC_TPU_HEDGE_S", 0.0)))


def ckpt_fallback_uri() -> str:
    """Secondary checkpoint directory used when commits to the primary
    URI exhaust their retry budget (``DMLC_TPU_CKPT_FALLBACK_URI``;
    empty = no fallback, the default)."""
    return get_env("DMLC_TPU_CKPT_FALLBACK_URI", "")


def snap_every_s() -> float:
    """Wall-clock job-snapshot cadence (``DMLC_TPU_SNAP_EVERY_S``,
    default 0 = epoch cadence only): with a ``Snapshotter`` armed, an
    epoch boundary also commits when this many seconds passed since the
    last committed snapshot, whatever the epoch cadence says."""
    return max(0.0, float(get_env("DMLC_TPU_SNAP_EVERY_S", 0.0)))


def preempt_deadline_s() -> float:
    """Seconds budgeted between a preemption notice (SIGTERM or an
    injected ``preempt.notice`` fault) and process exit
    (``DMLC_TPU_PREEMPT_DEADLINE_S``, default 30): the just-in-time
    coordinated snapshot commit must land inside this window."""
    return max(0.0, float(get_env("DMLC_TPU_PREEMPT_DEADLINE_S", 30.0)))


def elastic_enabled() -> bool:
    """Whether this worker participates in elastic membership
    (``DMLC_TPU_ELASTIC``, default off): collective failures and bumped
    heartbeat acks re-rendezvous into the tracker's next generation
    (``cmd='elastic'``) instead of recovering into the fixed world."""
    return get_env("DMLC_TPU_ELASTIC", False)


def elastic_window_s() -> float:
    """Tracker-side quiescence window in seconds for one membership
    transition (``DMLC_TPU_ELASTIC_WINDOW_S``, default 3): the new
    generation commits once no new entrant has arrived for this long,
    floor 0.1 so the accept loop always gets a tick to batch entrants."""
    return max(0.1, float(get_env("DMLC_TPU_ELASTIC_WINDOW_S", 3.0)))


def evict_after_s() -> float:
    """Tracker-side straggler eviction threshold in seconds
    (``DMLC_TPU_EVICT_AFTER_S``; 0 = eviction off, the default). A rank
    whose last heartbeat is older than this is marked evicted: its next
    elastic re-entry is refused and the survivors rebuild without it."""
    return max(0.0, float(get_env("DMLC_TPU_EVICT_AFTER_S", 0.0)))


def data_chunks(explicit: Optional[int] = None) -> int:
    """Chunk count for lease-based dispatch: the explicit argument when
    given, else ``DMLC_TPU_DATA_CHUNKS``, else 16. More chunks = finer
    reassignment granularity (less lost work per worker death) at more
    lease RPCs per epoch; floor 1."""
    if explicit is not None:
        return max(1, int(explicit))
    return max(1, get_env("DMLC_TPU_DATA_CHUNKS", 16))


def data_lease_s(explicit: Optional[float] = None) -> float:
    """Chunk lease duration in seconds: explicit argument, else
    ``DMLC_TPU_DATA_LEASE_S``, else 30. Size it well above one chunk's
    parse+serve+consume time — a too-short lease requeues chunks that
    merely ran slow (their late deliveries are then rejected: correct,
    but wasted work). Floor 0.1."""
    if explicit is not None:
        return max(0.1, float(explicit))
    return max(0.1, float(get_env("DMLC_TPU_DATA_LEASE_S", 30.0)))


def data_dead_after_s(explicit: Optional[float] = None) -> float:
    """Data-worker death threshold in seconds of heartbeat silence:
    explicit argument, else ``DMLC_TPU_DATA_DEAD_S``, else 10. Workers
    heartbeat at a third of this, so one lost beat never reads as a
    crash. Floor 0.1."""
    if explicit is not None:
        return max(0.1, float(explicit))
    return max(0.1, float(get_env("DMLC_TPU_DATA_DEAD_S", 10.0)))


def data_pending_cap() -> int:
    """Cap on a block service's undelivered-block requeue stash
    (``DMLC_TPU_DATA_PENDING_CAP``, default 64; 0 or negative =
    unbounded). A full stash backpressures the stashing thread briefly,
    then drops the block — metered as a drop, never silently."""
    return get_env("DMLC_TPU_DATA_PENDING_CAP", 64)


def data_max_jobs(explicit: Optional[int] = None) -> int:
    """Tenant jobs one dispatcher admits: explicit argument, else
    ``DMLC_TPU_DATA_MAX_JOBS``, else 8. Registration past the cap is
    refused with ``DataBusyError`` — typed backpressure the client's
    RetryPolicy already classifies transient. Floor 1."""
    if explicit is not None:
        return max(1, int(explicit))
    return max(1, get_env("DMLC_TPU_DATA_MAX_JOBS", 8))


def data_job_inflight() -> int:
    """Default per-job in-flight chunk cap (leased + delivered) for the
    fair-share lease scheduler (``DMLC_TPU_DATA_JOB_INFLIGHT``; 0 =
    uncapped, the default). ``add_job(max_inflight=...)`` overrides it
    per job."""
    return max(0, get_env("DMLC_TPU_DATA_JOB_INFLIGHT", 0))


def data_cache_mb() -> int:
    """Byte budget in MiB for the job-shared source cache
    (``DMLC_TPU_DATA_CACHE_MB``, default 256; 0 disables the tier —
    every chunk parse goes direct). Read once, at first cache use."""
    return max(0, get_env("DMLC_TPU_DATA_CACHE_MB", 256))


def data_scale_interval_s(explicit: Optional[float] = None) -> float:
    """Worker-autoscaler control-loop period in seconds: explicit
    argument, else ``DMLC_TPU_DATA_SCALE_INTERVAL_S``, else 1.0. Floor
    0.05 — the loop samples a snapshot per tick and must not busy-spin
    the dispatcher lock."""
    if explicit is not None:
        return max(0.05, float(explicit))
    return max(0.05, float(get_env("DMLC_TPU_DATA_SCALE_INTERVAL_S", 1.0)))


def data_hedge_s() -> float:
    """Fetch-hedging threshold for dispatcher-mode clients in seconds
    (``DMLC_TPU_DATA_HEDGE_S``; 0 = hedging off, the default). Distinct
    from ``DMLC_TPU_HEDGE_S`` (the readahead I/O hedge): this one races
    a whole chunk fetch against a second data worker."""
    return max(0.0, float(get_env("DMLC_TPU_DATA_HEDGE_S", 0.0)))


def device_telemetry_enabled() -> bool:
    """Whether the device telemetry layer is live
    (``DMLC_TPU_DEVICE_TELEMETRY``, default on). Read once where each
    surface is built (jit wrap time, feed construction), never on the
    per-dispatch path."""
    return get_env("DMLC_TPU_DEVICE_TELEMETRY", True)


def hbm_poll_s() -> float:
    """Background HBM sampler period in seconds (``DMLC_TPU_HBM_POLL_S``;
    0 = no poller thread, the default)."""
    return max(0.0, float(get_env("DMLC_TPU_HBM_POLL_S", 0.0)))


def watchdog_stall_s() -> float:
    """Cumulative seconds without goodput-ledger progress before the
    runtime watchdog fires a ``stall`` alert
    (``DMLC_TPU_WATCHDOG_STALL_S``, default 60; 0 = stall detection
    off)."""
    return max(0.0, float(get_env("DMLC_TPU_WATCHDOG_STALL_S", 60.0)))


def watchdog_profile() -> bool:
    """Whether a firing watchdog auto-triggers the on-demand device
    profiler capture for the regression window
    (``DMLC_TPU_WATCHDOG_PROFILE``, default off)."""
    return get_env("DMLC_TPU_WATCHDOG_PROFILE", False)


def parse_peak_mbps() -> float:
    """Roofline ceiling for the parse stage in MB/s
    (``DMLC_TPU_PARSE_PEAK_MBPS``, default 1000 — the vectorized
    parse_only bench tier; 0 = unknown)."""
    return max(0.0, float(get_env("DMLC_TPU_PARSE_PEAK_MBPS", 1000.0)))


def step_peak_mbps() -> float:
    """Roofline ceiling for the device step's consumed byte rate in
    MB/s (``DMLC_TPU_STEP_PEAK_MBPS``, default 0 = unknown — set it
    from the model's measured FLOP rate to score step utilization)."""
    return max(0.0, float(get_env("DMLC_TPU_STEP_PEAK_MBPS", 0.0)))


def ici_peak_gbps() -> float:
    """Override for the per-chip interconnect peak in GB/s
    (``DMLC_TPU_ICI_PEAK_GBPS``, default 0 = the device kind's published
    figure, ``obs.xla_cost.DEVICE_PEAKS``) — what bench_collective.py
    and the goodput collective roofline score utilization against."""
    return max(0.0, float(get_env("DMLC_TPU_ICI_PEAK_GBPS", 0.0)))


def peak_flops() -> float:
    """Override for the roofline peak in FLOP/s (``DMLC_TPU_PEAK_FLOPS``,
    default 0 = the device kind's published peak,
    ``obs.xla_cost.device_peaks``). The MFU verdict is window FLOPs
    (steps × per-step XLA flops) over this ceiling."""
    return max(0.0, float(get_env("DMLC_TPU_PEAK_FLOPS", 0.0)))


def peak_hbm_gbps() -> float:
    """Override for the device-memory-bandwidth peak in GB/s
    (``DMLC_TPU_PEAK_HBM_GBPS``, default 0 = the device kind's published
    peak, ``obs.xla_cost.device_peaks``). The achieved-HBM-fraction
    verdict is window bytes accessed over this ceiling."""
    return max(0.0, float(get_env("DMLC_TPU_PEAK_HBM_GBPS", 0.0)))


def audit_mode() -> str:
    """Determinism-audit ledger mode (``DMLC_TPU_AUDIT``): ``full``
    (aliases ``1``/``on``) digests every chunk, parsed block, emitted
    batch, and model step; ``sample`` digests every
    :func:`audit_sample_n`-th sequence number for bounded overhead;
    ``off`` — the default — makes :func:`dmlc_tpu.obs.audit.auditor`
    return the shared no-op child (zero-alloc hot path)."""
    val = str(get_env("DMLC_TPU_AUDIT", "")).strip().lower()
    if val in ("1", "on", "full", "true"):
        return "full"
    if val == "sample":
        return "sample"
    return "off"


def audit_sample_n() -> int:
    """Digest stride for ``DMLC_TPU_AUDIT=sample``
    (``DMLC_TPU_AUDIT_SAMPLE_N``, default 16, floor 1): only sequence
    numbers divisible by N are digested, trading localization
    granularity for overhead."""
    return max(1, get_env("DMLC_TPU_AUDIT_SAMPLE_N", 16))


def parse_backend() -> str:
    """Chunk-parse implementation (``DMLC_TPU_PARSE_BACKEND``): one of
    ``auto`` (native when loadable, else vector — the default),
    ``native``, ``vector``, ``scalar``. Unknown values read as auto."""
    val = str(get_env("DMLC_TPU_PARSE_BACKEND", "auto")).strip().lower()
    return val if val in ("auto", "native", "vector", "scalar") else "auto"


def parse_procs() -> int:
    """Process-pool parse workers (``DMLC_TPU_PARSE_PROCS``, default 0 =
    parse on the PipelinedParser's own threads). When > 0 each worker
    thread submits its chunk to a shared pool of this many processes and
    blocks on the future, so window ordering, backpressure and error
    poisoning behave exactly as in the threaded path."""
    return max(0, get_env("DMLC_TPU_PARSE_PROCS", 0))


def shuffle_seed() -> int:
    """Windowed global-shuffle seed for baked shard reads
    (``DMLC_TPU_SHUFFLE``, default -1 = shuffle off). A seed ≥ 0 arms a
    seeded permutation of the shard window table — a pure function of
    (seed, epoch), independent of the world size, so re-sharding and
    resume replay the same global order (io/shard.py). A
    ``shuffle_chunks=`` URI arg overrides the env per dataset."""
    return int(get_env("DMLC_TPU_SHUFFLE", -1))


def shuffle_window() -> int:
    """Shuffle unit in consecutive baked windows
    (``DMLC_TPU_SHUFFLE_WINDOW``, default 1, floor 1): the permutation
    moves runs of this many windows together, trading shuffle quality
    for longer sequential reads."""
    return max(1, get_env("DMLC_TPU_SHUFFLE_WINDOW", 1))


def shard_mmap() -> bool:
    """Zero-copy shard reads (``DMLC_TPU_SHARD_MMAP``, default on):
    window decodes are ``np.frombuffer`` views over one shared file
    mapping. 0 falls back to seek+read per window."""
    return bool(get_env("DMLC_TPU_SHARD_MMAP", True))


def collective_engine() -> str:
    """Collective engine selection (``DMLC_TPU_COLLECTIVE``): one of
    ``auto`` (the default — device when a multi-process mesh is up,
    socket when a tracker URI is set, else local), ``device`` (in-mesh
    XLA collectives — the SPMD training path), ``socket`` (the
    reference rabit tree/ring over TCP — CPU/cross-host fallback),
    ``local`` (single-process no-op world). Unknown values read as
    auto. Consulted by ``collective.init(engine="auto")`` only — an
    explicit ``engine=`` argument always wins over the env."""
    val = str(get_env("DMLC_TPU_COLLECTIVE", "auto")).strip().lower()
    return val if val in ("auto", "device", "socket", "local") else "auto"


def is_spare() -> bool:
    """Whether this process was launched as a warm spare
    (``DMLC_TPU_SPARE``, set by the launcher's ``--spares`` tasks).
    ``collective.init`` then registers through the tracker ``join``
    handshake and blocks until a membership transition activates it."""
    return get_env("DMLC_TPU_SPARE", False)


# Every DMLC_TPU_* env var the tree reads, in one place. The faultpoint
# lint (scripts/check_faultpoints.py) greps the source for DMLC_TPU_*
# literals and fails when one is missing from this registry, so a new
# knob cannot ship undocumented.
KNOWN_KNOBS = (
    # ingest pipeline
    "DMLC_TPU_NTHREAD",
    "DMLC_TPU_PREFETCH",
    "DMLC_TPU_HOST_PREFETCH",
    "DMLC_TPU_READAHEAD_MB",
    "DMLC_TPU_READAHEAD_CONNS",
    "DMLC_TPU_FEED_PUT",
    # vectorized parse path
    "DMLC_TPU_PARSE_BACKEND",
    "DMLC_TPU_PARSE_PROCS",
    "DMLC_TPU_SIMD",
    # native bridge
    "DMLC_TPU_NATIVE",
    "DMLC_TPU_NATIVE_LIB",
    "DMLC_TPU_ABI_VERSION",
    "DMLC_TPU_PALLAS",
    # observability
    "DMLC_TPU_METRICS",
    "DMLC_TPU_TRACE",
    "DMLC_TPU_TRACE_JAX",
    "DMLC_TPU_METRICS_EXPORT",
    "DMLC_TPU_HEARTBEAT_GAP",
    # job observability plane
    "DMLC_TPU_STATUS_PORT",
    "DMLC_TPU_STATUS_URI",
    "DMLC_TPU_OBS_PUBLISH",
    "DMLC_TPU_OBS_PAYLOAD_MAX",
    "DMLC_TPU_FLIGHTREC",
    "DMLC_TPU_FLIGHTREC_CAP",
    # fault-tolerant data service
    "DMLC_TPU_DATA_CHUNKS",
    "DMLC_TPU_DATA_LEASE_S",
    "DMLC_TPU_DATA_DEAD_S",
    "DMLC_TPU_DATA_PENDING_CAP",
    "DMLC_TPU_DATA_HEDGE_S",
    # multi-tenant fleet: jobs, shared source cache, autoscaler
    "DMLC_TPU_DATA_MAX_JOBS",
    "DMLC_TPU_DATA_JOB_INFLIGHT",
    "DMLC_TPU_DATA_CACHE_MB",
    "DMLC_TPU_DATA_SCALE_INTERVAL_S",
    # device telemetry
    "DMLC_TPU_DEVICE_TELEMETRY",
    "DMLC_TPU_HBM_POLL_S",
    # goodput ledger + runtime watchdog
    "DMLC_TPU_WATCHDOG_STALL_S",
    "DMLC_TPU_WATCHDOG_PROFILE",
    # baked columnar shards
    "DMLC_TPU_SHUFFLE",
    "DMLC_TPU_SHUFFLE_WINDOW",
    "DMLC_TPU_SHARD_MMAP",
    # determinism audit plane
    "DMLC_TPU_AUDIT",
    "DMLC_TPU_AUDIT_SAMPLE_N",
    "DMLC_TPU_PARSE_PEAK_MBPS",
    "DMLC_TPU_STEP_PEAK_MBPS",
    "DMLC_TPU_ICI_PEAK_GBPS",
    "DMLC_TPU_PEAK_FLOPS",
    "DMLC_TPU_PEAK_HBM_GBPS",
    # collective / distributed bootstrap
    "DMLC_TPU_COLLECTIVE",
    "DMLC_TPU_RECOVER_TIMEOUT",
    "DMLC_TPU_RING_THRESHOLD_BYTES",
    "DMLC_TPU_COORDINATOR",
    "DMLC_TPU_NUM_PROC",
    "DMLC_TPU_PROC_ID",
    # resilience
    "DMLC_TPU_RETRY_BUDGET",
    "DMLC_TPU_RETRY_DEADLINE_S",
    "DMLC_TPU_FAULTS",
    "DMLC_TPU_HEDGE_S",
    "DMLC_TPU_CKPT_FALLBACK_URI",
    # preemption-proof snapshots
    "DMLC_TPU_SNAP_EVERY_S",
    "DMLC_TPU_PREEMPT_DEADLINE_S",
    # elastic membership
    "DMLC_TPU_ELASTIC",
    "DMLC_TPU_ELASTIC_WINDOW_S",
    "DMLC_TPU_EVICT_AFTER_S",
    "DMLC_TPU_SPARE",
    # bench harness
    "DMLC_TPU_BENCH_DETAIL",
    "DMLC_TPU_BENCH_DIR",
    "DMLC_TPU_BENCH_SOCKET_WORLD",
)

"""DeviceFeed: the host-parse → H2D → mesh-sharded batch pipeline.

The reference's ThreadedIter pipeline ends with host RowBlocks
(threadediter.h + parser.h); DeviceFeed is its TPU continuation (SURVEY §3.1
"TPU build" note): a background thread re-batches parser output into
fixed-shape batches, transfers them with async ``jax.device_put`` (or
``jax.make_array_from_process_local_data`` when a multi-host mesh is given),
and keeps ``spec.prefetch`` batches in flight (default 1 — the classic
double-buffer; deeper windows pin more HBM but hide per-batch dispatch/DMA
latency) so H2D DMA overlaps both host parsing and the previous step's
compute. ``host_prefetch`` separately bounds the host-side ThreadedIter
queue of parsed-but-undispatched blocks. At the end of a pass the producer
thread rewinds the parser itself and stages the next pass's first batches
while the device drains this one's last steps, so ``before_first`` after a
whole pass has only its bookkeeping left (docs/pipeline.md, "Restarting a
pass").

Batch layouts:
- "dense": [batch, num_features] f32 + labels/weights — the MXU-friendly
  layout for small dense feature spaces (HIGGS, Criteo-dense)
- "csr": DeviceCSRBatch arrays (CSR offsets shipped; row ids expanded on
  device for segment-sum SpMV) with nnz
  bucketing — for genuinely sparse data (see dmlc_tpu.ops.spmv)
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu import obs
from dmlc_tpu.obs import audit, device_telemetry, flight
from dmlc_tpu.data.parsers import Parser, ThreadedParser, create_parser
from dmlc_tpu.data.row_block import RowBlockContainer
from dmlc_tpu.device.csr import (
    DeviceCSRBatch,
    ShardedCSRBatch,
    block_to_dense,
    pad_to_bucket,
    pad_to_bucket_sharded,
)
from dmlc_tpu.params.knobs import default_host_prefetch, default_prefetch
from dmlc_tpu.utils.logging import check
from dmlc_tpu.utils.threaded_iter import ThreadedIter

# obs label values: each feed/pool instance gets its own metric children
# ("feed=f3"), so concurrent feeds never clobber each other's windows and
# SPMD hosts (same construction order) produce host-comparable vectors
_FEED_IDS = itertools.count()
_POOL_IDS = itertools.count()


def _available_cpus() -> int:
    """CPUs actually usable by this process: cgroup/affinity-aware
    (os.cpu_count() reports the machine and would spawn a useless
    producer thread in a 1-CPU container on a big host)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):  # non-linux
        return os.cpu_count() or 1


class _SyncIter:
    """ThreadedIter-shaped adapter running the producer inline (no
    thread): `host_prefetch=0`. Same consumer surface — iteration,
    close(), before_first() restart."""

    def __init__(self, factory):
        self._factory = factory
        self._gen = factory()

    def __iter__(self):
        return self._gen

    def next(self):
        return next(self._gen, None)

    def advance(self) -> bool:
        # no thread to have wound ahead: always the caller's rewind
        self.close()
        return False

    def before_first(self) -> None:
        # close the old generator first (ThreadedIter.before_first fully
        # shuts down its producer): a suspended generator would keep a
        # staged native batch and parser state pinned alongside the new one
        self.close()
        self._gen = self._factory()

    def close(self) -> None:
        gen, self._gen = self._gen, iter(())
        if hasattr(gen, "close"):
            gen.close()


@dataclass
class BatchSpec:
    """Static-shape contract for one feed."""

    batch_size: int
    layout: str = "dense"  # "dense" | "csr"
    num_features: int = 0  # required for dense
    nnz_bucket: Optional[int] = None  # fixed bucket for csr (else auto)
    drop_remainder: bool = False
    # device transfers in flight ahead of the consumer. jax dispatch is
    # async, so a deeper window hides per-batch dispatch/DMA latency at
    # the cost of pinning that many extra batches in HBM. 1 = the
    # classic double-buffer; None resolves
    # through the DMLC_TPU_PREFETCH knob (params/knobs.py).
    prefetch: Optional[int] = None


class FixedShapePool:
    """Host staging buffers keyed by (shape, dtype) bucket, reused across
    batches.

    Two jobs, per the static-shape discipline (device/csr.py header):

    1. **Shape accounting.** Every ``acquire`` records its (shape, dtype)
       key; ``shape_keys``/``stats()["shapes"]`` expose exactly the set of
       distinct buffer shapes a feed produced — the contract a jitted
       consumer compiles against (one trace per shape bucket, no
       per-batch recompilation; proven by test).

    2. **Buffer reuse.** With ``recycle=True`` the allocation per batch is
       retired: ``retire(bufs, guards)`` offers a batch's host arrays
       back, guarded by the device arrays their transfer produced. On a
       TPU ``device_put`` returns before the runtime has finished reading
       the host buffer (measured on the v5e: mutating the source right
       after the call changes what lands), so a buffer may be rewritten
       only once its guards report the copy complete (``is_ready``,
       never blocking). That question is asked ONCE, at ``retire``,
       while the caller still owns the guards: a donating train step
       deletes its batch arrays, and ``is_ready`` on a deleted array
       raises forever — a guard kept for later could never come true.
       Landed → straight onto the free list; still in flight → the
       buffers are dropped (the runtime's own reference keeps them alive
       until the copy ends, then they are garbage) and the next
       ``acquire`` allocates. ``recycle`` must be False when the
       transfer may alias the host buffer instead of copying it (the cpu
       backend's zero-copy jit ingest, ``DeviceFeed._put_tree``): there
       the consumer owns the buffer and reuse would rewrite batches
       already delivered — bit-parity over reuse.
    """

    # leak sentinel: every this many acquires, compare the outstanding
    # buffer count (handed out, not yet returned) against its previous
    # high-water mark; this many CONSECUTIVE new highs means a consumer
    # is acquiring without ever retiring — a staging leak, not churn
    LEAK_CHECK_EVERY = 64
    LEAK_STRIKES = 4

    def __init__(self, recycle: bool = True):
        self.recycle = recycle
        self._free: dict = {}  # key -> [np.ndarray]
        pid = "p%d" % next(_POOL_IDS)
        reg = obs.registry()
        self._m_allocated = reg.counter(
            "dmlc_pool_allocated_total",
            "staging buffers newly allocated", pool=pid)
        self._m_reused = reg.counter(
            "dmlc_pool_reused_total",
            "staging buffers recycled from the free list", pool=pid)
        # plain ints next to the registry mirrors: the hit-rate surface
        # (stats(), tests, bench) stays truthful under DMLC_TPU_METRICS=0
        self.allocated = 0
        self.reused = 0
        self.retired = 0  # buffers accepted back through retire()
        self.dropped = 0  # offered back with the transfer still in flight
        self.double_retired = 0  # duplicate retire() offers rejected
        self._shapes: set = set()
        # id()s of buffers currently owned by the pool (_free):
        # a second retire() of one of these would hand the same memory to
        # two future acquirers — the guard drops the duplicate instead
        self._pooled_ids: set = set()
        self._acquires = 0
        self._leak_high = 0
        self._leak_strikes = 0
        self._leak_reported = False

    @staticmethod
    def _key(shape, dtype):
        if isinstance(shape, int):
            shape = (shape,)
        return (tuple(shape), np.dtype(dtype).str)

    @property
    def shape_keys(self) -> frozenset:
        return frozenset(self._shapes)

    def acquire(self, shape, dtype) -> np.ndarray:
        key = self._key(shape, dtype)
        self._shapes.add(key)
        if self.recycle:
            self._acquires += 1
            if self._acquires % self.LEAK_CHECK_EVERY == 0:
                self._leak_check()
            free = self._free.get(key)
            if free:
                buf = free.pop()
                self._pooled_ids.discard(id(buf))
                self.reused += 1
                self._m_reused.inc()
                return buf
        self.allocated += 1
        self._m_allocated.inc()
        return np.empty(key[0], dtype=dtype)

    @property
    def outstanding(self) -> int:
        """Buffers handed out (allocated + reused) and not yet offered
        back through :meth:`retire` — the quantity the leak sentinel
        watches."""
        return (self.allocated + self.reused) - self.retired - self.dropped

    def retire(self, bufs, guards) -> None:
        """Offer a batch's staging buffers back, guarded by the device
        arrays their transfer produced. Call it while the guards are
        still alive — before a donating consumer sees the batch. Buffers
        whose copy has landed join the free list; otherwise they are
        dropped (see the class docstring). A buffer the pool already
        holds (double-retire — two delivery paths returning one batch) is
        dropped rather than queued twice: queuing it again would hand the
        same memory to two future acquirers and silently corrupt an
        in-flight batch."""
        if not self.recycle:
            return
        if not all(g.is_ready() for g in guards):
            self.dropped += len(bufs)
            return
        for buf in bufs:
            bid = id(buf)
            if bid in self._pooled_ids:
                self.double_retired += 1
                continue
            self._pooled_ids.add(bid)
            self.retired += 1
            self._free.setdefault(
                self._key(buf.shape, buf.dtype), []
            ).append(buf)

    def _leak_check(self) -> None:
        """Fire one ``pool.leak`` flight event when the outstanding buffer
        count keeps making new highs — acquires without matching retires
        grow host memory linearly with the fit and this is the earliest
        observable signature."""
        if self._leak_reported:
            return
        out = self.outstanding
        if out > self._leak_high:
            self._leak_high = out
            self._leak_strikes += 1
            if self._leak_strikes >= self.LEAK_STRIKES:
                self._leak_reported = True
                flight.record_event(
                    "pool.leak",
                    outstanding=out,
                    allocated=self.allocated,
                    reused=self.reused,
                    retired=self.retired,
                )
        else:
            self._leak_strikes = 0

    def stats(self) -> dict:
        return {
            "shapes": len(self._shapes),
            "allocated": self.allocated,
            "reused": self.reused,
            "retired": self.retired,
            "dropped": self.dropped,
            "double_retired": self.double_retired,
            "outstanding": self.outstanding,
        }


def stall_breakdown(stats: dict) -> str:
    """One-line human summary of :meth:`DeviceFeed.stats` — where the
    epoch's wall time sat (ms per stage) plus pool reuse, for fit-loop
    logging and bench extra fields. ``host_wait`` ≈ 0 means the feed kept
    up with the consumer; ``host_wait`` ≈ ``host_batch`` means the
    consumer was ingest-bound.

    Purely a formatter: the numbers come from the obs registry
    (``dmlc_feed_*`` / ``dmlc_pool_*`` / ``dmlc_pipeline_*`` metrics,
    epoch-windowed by ``stats()`` — docs/observability.md has the name
    table)."""
    ms = 1e6
    parts = [
        "feed[%d batches]" % stats.get("batches", 0),
        "host_batch %.1fms" % (stats.get("host_batch_ns", 0) / ms),
        "dispatch %.1fms" % (stats.get("dispatch_ns", 0) / ms),
        "host_wait %.1fms" % (stats.get("host_wait_ns", 0) / ms),
        "consume %.1fms" % (stats.get("consume_ns", 0) / ms),
    ]
    pool = stats.get("pool") or {}
    if pool.get("allocated"):
        parts.append(
            "pool %d shapes %d alloc %d reuse"
            % (pool.get("shapes", 0), pool["allocated"],
               pool.get("reused", 0))
        )
    pipe = stats.get("pipeline") or {}
    if pipe.get("chunks"):
        parts.append(
            "parse[%d chunks x%d] %.1fms (+%.1fms wait)"
            % (pipe["chunks"], pipe.get("nthread", 1),
               pipe.get("parse_ns", 0) / ms,
               pipe.get("consumer_wait_ns", 0) / ms)
        )
    return " | ".join(parts)


class _HostStage:
    """The feed's host side: the parser's blocks re-batched into fixed-size
    host batches, on the producer thread (inline with ``host_prefetch=0``),
    and the parser's rewind at the end of a pass.

    Held by the feed and by the producer thread, and holds no reference to
    the feed: a feed that is dropped is collected, and its ThreadedIter
    stops the thread."""

    # what a pass ended with, kept when the producer winds past it
    _FACTS = ("pipeline", "bytes_read", "plan", "host_batch_ns")

    def __init__(self, parser, spec: BatchSpec, shards: int, host_batch_ns,
                 cpu):
        self.parser = parser
        self.spec = spec
        self.shards = shards
        self.host_batch_ns = host_batch_ns
        # the producer thread's CPU histogram; None in sync mode, where
        # this runs on the consumer's thread and its count covers it
        self.cpu = cpu
        # determinism audit: batch-stage digests at pool emit, keyed by
        # per-epoch batch index (obs/audit.py). The shared no-op child
        # when DMLC_TPU_AUDIT is off.
        self.audit = audit.auditor()
        # exactly-once ack emission (dispatcher-mode RemoteBlockParser):
        # switch the parser to explicit acks BEFORE the producer thread
        # can issue its first fetch, so prefetched chunks are acked only
        # when their rows are consumed (or dropped) by this feed
        self.ack = getattr(parser, "ack", None)
        set_explicit = getattr(parser, "set_explicit_ack", None)
        if callable(self.ack) and callable(set_explicit):
            set_explicit()
        else:
            self.ack = None
        # orders the producer's rewind of the parser against the
        # consumer's reads of it (fact)
        self.lock = threading.Lock()
        # _FACTS of each pass the producer has wound past and the
        # consumer has not left (DeviceFeed.before_first), oldest first
        self.ended: deque = deque()
        # the pass the batches being produced belong to, for the
        # ``produce`` span: the producer's own rewind moves it on, and the
        # feed sets it where it restarts the producer itself
        self.pass_ = 0

    @property
    def rewinds_itself(self) -> bool:
        """Whether the end of a pass is this stage's to rewind at. Not
        with explicit acks (a rewind asks the service for the next epoch's
        chunks and moves the exactly-once frontier) and not under an
        armed audit (its batch chain is keyed per epoch and closes at the
        boundary the consumer draws)."""
        return self.ack is None and not self.audit.enabled

    def use_native(self) -> bool:
        """Native C++ re-batch + densify/COO-pad (pipeline.cc StageBatch):
        no RowBlockContainer copies, no numpy scatter — the feed-side answer
        to the parse-vs-feed throughput cliff (BASELINE.md)."""
        return (
            getattr(self.parser, "supports_batch_fetch", False)
            and self.spec.layout in ("dense", "csr")
        )

    # ---- the end of a pass ----------------------------------------------
    def _live(self, fact):
        if fact == "host_batch_ns":
            return self.host_batch_ns.sum
        if fact == "bytes_read":
            return self.parser.bytes_read
        read = getattr(
            self.parser,
            {"pipeline": "stats", "plan": "snapshot_state"}[fact], None)
        return read() if callable(read) else None

    def fact(self, name: str):
        """One of ``_FACTS`` for the pass the consumer is in: as that pass
        ended where the producer has wound past it (the parser's counters
        and read plan are the next pass's by then), else live."""
        with self.lock:
            return self.ended[0][name] if self.ended else self._live(name)

    def rewind(self) -> bool:
        """On the producer thread, the pass's last batch staged: keep
        what the pass ended with and rewind the parser, so that the next
        pass's first batches are staged while the consumer launches the
        last steps of this one and the device drains them. False where the
        parser does not rewind (a one-pass stream): the producer stops,
        and ``before_first`` raises it where it is asked."""
        with self.lock:
            facts = {name: self._live(name) for name in self._FACTS}
            try:
                self.parser.before_first()
            except Exception:  # noqa: BLE001 — see docstring
                return False
            self.ended.append(facts)
            self.pass_ += 1
        return True

    # ---- re-batch parser blocks into fixed-size slices ------------------
    def _host_batches(self) -> Iterator:
        from dmlc_tpu.resilience import faultpoint

        if self.use_native():
            producer = self._host_batches_native()
        else:
            producer = self._host_batches_python()
        cpu = self.cpu
        cpu_at = time.thread_time_ns()
        npass = self.pass_
        for nbatch in itertools.count():
            faultpoint("device.feed")
            # the batch's life begins here, under the identifier every
            # later span of it carries; the pass is the one the batch
            # BELONGS to (the producer stages pass n+1 while the consumer
            # is still in pass n)
            with obs.span("produce", hist=self.host_batch_ns, pass_=npass,
                          batch=nbatch):
                try:
                    item = next(producer)
                except StopIteration:
                    return
                finally:
                    if cpu is not None:
                        now = time.thread_time_ns()
                        cpu.observe(now - cpu_at)
                        cpu_at = now
            yield item

    def _host_batches_python(self) -> Iterator:
        bs = self.spec.batch_size
        bidx = 0  # per-epoch batch index (audit batch-chain key)
        pending = RowBlockContainer()
        # flow ids (and dispatcher chunk seq ids) of parser chunks not yet
        # represented in an emitted batch; rebatching is N:M, so each
        # chunk's ids ride the first slice it contributes rows to
        flows = []
        seqs = []
        for block in self.parser:
            fid = getattr(block, "flow_id", 0)
            if fid:
                flows.append(fid)
            sid = getattr(block, "seq_id", None)
            if sid is not None:
                seqs.append(sid)
            pending.push_block(block)
            if len(pending) < bs:
                continue
            # Finalize once, emit every full slice, keep only the tail.
            whole = pending.to_block()
            nfull = len(whole) // bs
            for k in range(nfull):
                piece = whole.slice(k * bs, (k + 1) * bs)
                if flows:
                    piece.flow_ids = tuple(flows)
                    flows = []
                if seqs:
                    piece.seq_ids = tuple(seqs)
                    seqs = []
                self.audit.note_batch(bidx, piece)
                bidx += 1
                yield piece
            pending = RowBlockContainer()
            if len(whole) > nfull * bs:
                pending.push_block(whole.slice(nfull * bs, len(whole)))
        if len(pending) and not self.spec.drop_remainder:
            tail = pending.to_block()
            if flows:
                tail.flow_ids = tuple(flows)
            if seqs:
                tail.seq_ids = tuple(seqs)
                seqs = []
            self.audit.note_batch(bidx, tail)
            yield tail
        if seqs and self.ack is not None:
            # chunks whose rows only ever reached a dropped remainder (or
            # an empty chunk) still count as visited — ack them here or
            # the dispatcher would requeue them forever
            for sid in seqs:
                self.ack_seq(sid)

    def _host_batches_native(self) -> Iterator:
        spec = self.spec
        bs = spec.batch_size
        shards = self.shards
        while True:
            if spec.layout == "dense":
                check(spec.num_features > 0,
                      "dense layout requires num_features")
                out = self.parser.read_batch_dense(bs, spec.num_features)
            elif shards > 1:
                # mesh csr: entries partitioned per shard on the host so
                # each device receives only its own nnz
                out = self.parser.read_batch_coo_sharded(
                    bs, shards, nnz_bucket=spec.nnz_bucket
                )
            else:
                out = self.parser.read_batch_coo(
                    bs, nnz_bucket=spec.nnz_bucket
                )
            if out is None:
                return
            rows = out[3] if spec.layout == "dense" else out.num_rows
            if rows < bs and spec.drop_remainder:
                return
            yield out

    def ack_seq(self, sid) -> None:
        """Report one dispatcher chunk consumed; best-effort — a dead
        dispatcher must not kill the training loop (the lease deadline
        covers a lost ack; the duplicate-ack path makes a retried one
        harmless)."""
        try:
            self.ack(sid)
        except Exception:  # noqa: BLE001 — see docstring
            pass


class DeviceFeed:
    """Iterate device batches from a parser or URI.

    With a ``mesh``, batches are sharded over its ``axis`` (default "dp") on
    the leading dimension; each process feeds its local shard (multi-host:
    pass the per-host InputSplit part via the parser's uri part/num_parts).
    """

    def __init__(
        self,
        source: Parser | ThreadedParser | str,
        spec: BatchSpec,
        mesh: Optional[Mesh] = None,
        axis: str = "dp",
        part_index: int = 0,
        num_parts: int = 1,
        host_prefetch: Optional[int] = None,  # ThreadedIter queue depth
        # (host blocks); 0 = synchronous (no producer thread); None =
        # the DMLC_TPU_HOST_PREFETCH knob, else auto: 0 on a 1-core
        # host, else 2
    ):
        if host_prefetch is None:
            host_prefetch = default_host_prefetch()
        if host_prefetch is None:
            host_prefetch = 0 if _available_cpus() <= 1 else 2
        if isinstance(source, str):
            source = create_parser(source, part_index, num_parts)
        self._parser = source
        self.spec = spec
        self._mesh = mesh
        self._axis = axis
        # computed once: mesh/axis are immutable, and the multi-process
        # branch scans the mesh's device array
        self._shards = self._axis_shards()
        if mesh is not None:
            # the per-PROCESS batch divides over this process's shards
            # along the axis (== the full axis extent single-process)
            check(
                spec.batch_size % self._shards == 0,
                "batch_size %d must divide over this process's %d shards "
                "of mesh axis %s",
                spec.batch_size,
                self._shards,
                axis,
            )
            if jax.process_count() > 1 and spec.layout == "csr":
                # auto bucketing sizes from LOCAL data; different hosts
                # would pick different buckets and the global assembly
                # needs identical local shapes — make the bucket explicit
                check(
                    spec.nnz_bucket is not None,
                    "multi-process csr feeds require an explicit "
                    "spec.nnz_bucket (auto bucketing is per-host)",
                )
        # the transfer window: spec value or the DMLC_TPU_PREFETCH knob
        self._prefetch = default_prefetch(spec.prefetch)
        # host staging buffers recycle only where the device transfer
        # provably COPIES (accelerator H2D lands in device memory); the
        # cpu backend may alias numpy buffers zero-copy through the jit
        # boundary (_put_tree), where reuse would rewrite delivered
        # batches — there the pool only does shape accounting
        self.pool = FixedShapePool(recycle=jax.default_backend() != "cpu")
        # per-stage wall time (SURVEY §5.1: "where does feed time go?")
        # lives in the obs registry as per-batch histograms; the host stage
        # observes on the ThreadedIter thread, the rest on the consuming
        # thread — registered BEFORE the producer thread starts. stats()
        # windows the monotonic registry totals with _epoch_base so it
        # still describes the current epoch.
        fid = "f%d" % next(_FEED_IDS)
        reg = obs.registry()
        self._stage = {
            "host_batch_ns": reg.histogram(
                "dmlc_feed_host_batch_ns",
                "per-batch host production (parse + densify/pad)", feed=fid),
            "dispatch_ns": reg.histogram(
                "dmlc_feed_dispatch_ns",
                "per-batch async device transfer submission", feed=fid),
            "host_wait_ns": reg.histogram(
                "dmlc_feed_host_wait_ns",
                "per-batch consumer wait on the host producer", feed=fid),
            "consume_ns": reg.histogram(
                "dmlc_feed_consume_ns",
                "per-batch time the consumer held the batch", feed=fid),
        }
        # the parts of a batch on the consumer thread that stats() does
        # not window: each is the ``hist=`` of the span of its name
        self._h_stage = reg.histogram(
            "dmlc_feed_stage_ns",
            "per-batch padding / densifying on the consumer thread (the "
            "Python re-batch producer's batches only)", feed=fid)
        self._h_put = reg.histogram(
            "dmlc_feed_put_ns",
            "per-batch device_put submission alone", feed=fid)
        self._m_unlanded = reg.counter(
            "dmlc_feed_unlanded_deliveries_total",
            "batches handed to the consumer before their host-to-device "
            "copy had landed", feed=fid)
        self._m_batches = reg.counter(
            "dmlc_feed_batches_total", "device batches delivered", feed=fid)
        # rows delivered — the goodput ledger's examples/s numerator
        # (obs/goodput.py windows it against wall time)
        self._m_rows = reg.counter(
            "dmlc_feed_rows_total", "examples delivered to device",
            feed=fid)
        # device_put calls per feed: the sentry gates this against the
        # batch count — per-array dispatch regressions (N calls where one
        # pytree put would do) surface as dispatches/batch > 1
        self._m_dispatches = reg.counter(
            "dmlc_feed_h2d_dispatches_total",
            "device_put dispatch calls (one per batched pytree put; "
            "per-array regressions show up as dispatches/batch > 1)",
            feed=fid)
        # CPU time the feed's two threads burn, counted by the threads
        # themselves (time.thread_time_ns, one read a batch each): the
        # producer's covers re-batch/staging, the consumer's everything
        # between two deliveries — dispatch, the fit loop, the step's
        # launch. The native reader and parse workers count theirs in
        # parser.stats() (reader_cpu_ns / parse_cpu_ns).
        self._m_cpu = {
            stage: reg.histogram(
                "dmlc_stage_cpu_ns",
                "per-batch thread CPU time by pipeline stage", stage=stage)
            for stage in ("feed_producer", "consumer")
        }
        # passes over the source this feed has begun: with a batch's
        # place in its pass, the batch id every feed span carries
        self._pass = 0
        # H2D accounting around _put_tree: None when device telemetry is
        # off, and then the dispatch path has no byte walk and no timer.
        self._h2d = device_telemetry.h2d_meter(feed=fid)
        device_telemetry.maybe_start_hbm_poller()
        self._epoch_base: dict = {}
        # restarts, and those of them that found the next pass staged:
        # the producer had reached the end of its pass and rewound
        self._m_restarts = reg.counter(
            "dmlc_feed_restarts_total",
            "before_first() calls: passes begun after the first", feed=fid)
        self._m_prewound = reg.counter(
            "dmlc_feed_prewound_restarts_total",
            "restarts that found the producer rewound and the next pass's "
            "first batches staged", feed=fid)
        self._sync_host = host_prefetch <= 0
        self._host = host = _HostStage(
            self._parser, spec, self._shards, self._stage["host_batch_ns"],
            None if self._sync_host else self._m_cpu["feed_producer"])
        if self._sync_host:
            # synchronous host stage: on a 1-core host the prefetch
            # thread cannot overlap anything and only adds context
            # switches (~5% of the recordio->SGD epoch); a real TPU host
            # (many cores) keeps the thread and the overlap
            self._host_iter = _SyncIter(host._host_batches)
        else:
            self._host_iter = ThreadedIter(
                host._host_batches, max_capacity=host_prefetch,
                name="device-feed",
                rewind=host.rewind if host.rewinds_itself else None,
            )

    def _use_native_batches(self) -> bool:
        return self._host.use_native()

    def _axis_shards(self) -> int:
        """How many shard sections THIS process builds along the batch
        axis (mesh-geometry logic shared with the GBDT learner —
        ``parallel.local_axis_shards`` carries the multi-process
        rationale; getting it wrong interleaves hosts' shards and feeds
        every device garbage row offsets)."""
        if self._mesh is None:
            return 1
        from dmlc_tpu.parallel import local_axis_shards

        return local_axis_shards(self._mesh, self._axis)

    # ---- device side ---------------------------------------------------
    def _sharding(self, spec: P) -> Optional[NamedSharding]:
        if self._mesh is None:
            return None
        return NamedSharding(self._mesh, spec)

    def _put_tree(self, arrays: dict, specs: dict, nbatch: int = 0) -> dict:
        """One batched transfer for all of a batch's arrays: per-array
        device_put pays the dispatch overhead N times; a pytree
        device_put batches them. The ``put`` span covers the submission
        alone (``dmlc_feed_put_ns``).
        With device telemetry on, the put is metered from the span's own
        clock reads: payload bytes → ``dmlc_feed_h2d_bytes_total``, bytes
        over the submission's time → ``dmlc_feed_h2d_mbps``."""
        with obs.span("put", hist=self._h_put, pass_=self._pass,
                      batch=nbatch) as span:
            out = self._put_tree_raw(arrays, specs)
        meter = self._h2d
        if meter is not None:
            nbytes = 0
            for v in arrays.values():
                nbytes += getattr(v, "nbytes", 0)
            meter.note(nbytes, span.dur_ns)
        return out

    def _put_tree_raw(self, arrays: dict, specs: dict) -> dict:
        if self._mesh is None:
            if jax.default_backend() == "cpu" and \
                    os.environ.get("DMLC_TPU_FEED_PUT") != "1":
                # CPU single-device: the jit boundary performs the
                # (aligned, possibly zero-copy) ingest itself — an eager
                # device_put is one extra full copy on the same core the
                # parse/densify pipeline runs on (measured ~15% of the
                # recordio->SGD epoch). On an accelerator the eager put
                # IS the async H2D overlap, so only cpu skips.
                # DMLC_TPU_FEED_PUT=1 restores the put for A/B.
                return arrays
            self._m_dispatches.inc()
            return jax.device_put(arrays)
        if jax.process_count() > 1:
            return self._put_tree_multihost(arrays, specs)
        shardings = {k: self._sharding(specs[k]) for k in arrays}
        self._m_dispatches.inc()
        return jax.device_put(arrays, shardings)

    def _global_shape(self, arr, spec: P) -> tuple:
        """Global shape of ``arr`` under ``spec``: the leading dim sharded
        over the mesh axis multiplies by total/local shard sections
        (each process contributes ``self._shards`` contiguous sections);
        replicated arrays keep their local shape."""
        if len(spec) and spec[0] == self._axis:
            total = self._mesh.shape[self._axis]
            return (arr.shape[0] * (total // self._shards),) + arr.shape[1:]
        return arr.shape

    def _put_tree_multihost(self, arrays: dict, specs: dict) -> dict:
        """Multi-host assembly through ONE batched ``device_put``.

        ``jax.make_array_from_process_local_data`` is per-array by API
        shape — N dispatch round trips per batch (the overhead the
        ``dmlc_feed_h2d_dispatches_total``/batch ratio gates). Instead:
        compute each array's global shape, slice this process's
        addressable per-device shards as host views
        (``addressable_devices_indices_map`` rebased by the local block's
        global offset), ship every shard of every array through one
        batched ``device_put``, and assemble the global arrays with
        ``make_array_from_single_device_arrays`` — metadata only, no
        further transfer."""
        shardings = {k: self._sharding(specs[k]) for k in arrays}
        try:
            views, devs, plans = [], [], []
            for k, v in arrays.items():
                sh = shardings[k]
                gshape = self._global_shape(v, specs[k])
                ndim = len(gshape)
                idx_map = sh.addressable_devices_indices_map(gshape)
                devices = list(idx_map)
                norm = {
                    d: tuple(idx_map[d]) + (slice(None),) * (
                        ndim - len(idx_map[d]))
                    for d in devices
                }
                # this process's local block is contiguous in global
                # coords: its offset per dim is the min start over the
                # process's own shards
                offs = [
                    min((norm[d][dim].start or 0) for d in devices)
                    for dim in range(ndim)
                ]
                for d in devices:
                    local = tuple(
                        slice(
                            (s.start or 0) - off,
                            (s.stop if s.stop is not None else size) - off,
                        )
                        for s, off, size in zip(norm[d], offs, gshape)
                    )
                    views.append(v[local])
                    devs.append(d)
                plans.append((k, gshape, sh, len(devices)))
            self._m_dispatches.inc()
            shards = jax.device_put(views, devs)
            out, pos = {}, 0
            for k, gshape, sh, n in plans:
                out[k] = jax.make_array_from_single_device_arrays(
                    gshape, sh, list(shards[pos: pos + n])
                )
                pos += n
            return out
        except Exception:  # noqa: BLE001 — exotic sharding/runtime: keep
            # feeding through the per-array path rather than kill the fit
            # (the dispatch counter records the N-call cost honestly)
            self._m_dispatches.inc(len(arrays))
            return {
                k: jax.make_array_from_process_local_data(shardings[k], v)
                for k, v in arrays.items()
            }

    def _to_device(self, block, flows=(), nbatch=0):
        """→ (device batch, staging buffers to retire — () when the host
        arrays came from the native pipeline or no pooled path).
        ``flows``: flow ids of the chunks in ``block`` — stepped inside
        the ``stage`` span so the pool staging slice joins the arrow
        chain (python paths only; native batches carry no flows).
        ``nbatch``: the batch's place in its pass, for that span."""
        spec = self.spec
        if isinstance(block, tuple):  # native dense batch, pre-densified
            x, labels, weights, rows = block
            out = self._put_tree(
                {"x": x, "label": labels, "weight": weights},
                {"x": P(self._axis), "label": P(self._axis),
                 "weight": P(self._axis)},
                nbatch,
            )
            out["num_rows"] = rows
            return out, ()
        if isinstance(block, (DeviceCSRBatch, ShardedCSRBatch)):
            # native COO batch: staged in C++, no pooled staging to retire
            return self._put_csr(block, nbatch), ()
        if spec.layout == "dense":
            check(spec.num_features > 0, "dense layout requires num_features")
            with obs.span("stage", hist=self._h_stage, rows=len(block),
                          pass_=self._pass, batch=nbatch):
                for fid in flows:
                    obs.flow_step(fid, "chunk")
                x, labels, weights = block_to_dense(
                    block, spec.batch_size, spec.num_features, pool=self.pool
                )
            out = self._put_tree(
                {"x": x, "label": labels, "weight": weights},
                {"x": P(self._axis), "label": P(self._axis),
                 "weight": P(self._axis)},
                nbatch,
            )
            out["num_rows"] = len(block)
            return out, (x, labels, weights)
        if spec.layout == "csr":
            shards = self._shards
            with obs.span("stage", hist=self._h_stage, rows=len(block),
                          pass_=self._pass, batch=nbatch):
                for fid in flows:
                    obs.flow_step(fid, "chunk")
                if shards > 1:
                    batch = pad_to_bucket_sharded(
                        block, spec.batch_size, shards,
                        nnz_bucket=spec.nnz_bucket,
                    )
                    bufs = ()
                else:
                    batch = pad_to_bucket(
                        block, spec.batch_size, nnz_bucket=spec.nnz_bucket,
                        pool=self.pool,
                    )
                    bufs = (batch.labels, batch.weights, batch.indices,
                            batch.values, batch.row_ids, batch.offsets)
            return self._put_csr(batch, nbatch), bufs
        raise ValueError(f"unknown layout {spec.layout!r}")

    def _put_csr(self, batch, nbatch: int = 0):
        # ShardedCSRBatch: per-shard entry sections — P(axis) on the flat
        # entry arrays ships each device only its own nnz (H2D ∝
        # global_nnz / world). DeviceCSRBatch (no mesh / single shard):
        # entries replicated. Either way the row mapping crosses H2D as
        # the small CSR ``offsets`` array (∝ rows), NOT the per-entry
        # ``row_ids`` (∝ nnz); the train step expands row ids on device
        # (ops.spmv.expand_row_ids) where the cumsum is effectively free.
        sharded = isinstance(batch, ShardedCSRBatch)
        entry_spec = P(self._axis) if sharded else P()
        out = self._put_tree(
            {
                "label": batch.labels,
                "weight": batch.weights,
                "indices": batch.indices,
                "values": batch.values,
                "offsets": batch.offsets,
            },
            {
                "label": P(self._axis),
                "weight": P(self._axis),
                "indices": entry_spec,
                "values": entry_spec,
                "offsets": entry_spec,
            },
            nbatch,
        )
        out["num_rows"] = batch.num_rows
        out["num_nonzero"] = batch.num_nonzero
        return out

    def _deliver(self, entry):
        """Ask whether the batch's async H2D copy has landed — of its own
        device arrays, NOW, before a donating consumer deletes them —
        count the batch where it has not
        (``dmlc_feed_unlanded_deliveries_total``: its step's launch will
        wait for its input), retire its staging buffers, which are reused
        only if it has, and hand the batch to the consumer."""
        batch, bufs = entry[0], entry[1]
        guards = [v for v in batch.values() if hasattr(v, "is_ready")]
        landed = all(g.is_ready() for g in guards)
        if not landed:
            self._m_unlanded.inc()
        if bufs:
            # asked once where the copy has landed; a pool handed guards
            # asks them itself
            self.pool.retire(bufs, () if landed else guards)
        return batch

    def __iter__(self):
        """Yield device batches with ``spec.prefetch`` transfers in flight
        ahead of the consumer (async dispatch pipelining). A parser/host
        error propagates at its in-order position after the batches before
        it; the feed stays closeable afterwards (close() joins the
        producer and parser threads)."""
        window = self._prefetch
        pending = deque()
        it = iter(self._host_iter)
        npass = self._pass
        nbatch = 0
        cpu = self._m_cpu["consumer"]
        cpu_at = time.thread_time_ns()

        stage = self._stage
        # sync mode has no producer thread to wait on: the time inside
        # next() IS host production and already accrues to the host_batch
        # stage — also counting it as a wait would double-book the stage
        # breakdown
        wait_hist = None if self._sync_host else stage["host_wait_ns"]

        def _consume(entry):
            nonlocal cpu_at
            with obs.span("deliver", pass_=npass, batch=entry[4]):
                batch = self._deliver(entry)
            flows = entry[2]
            # the consume span covers the yield: its duration IS the time
            # the consumer held the batch (generator suspended). The
            # thread-local current flow and batch id are set for that same
            # window so fit-loop spans (train_step, collective ops) can
            # mark the in-flight chunk and batch; flow_end fires inside
            # the span, closing the arrow chain on the consume slice.
            with obs.span("consume", hist=stage["consume_ns"], pass_=npass,
                          batch=entry[4]) as span:
                live = span.live
                if flows:
                    obs.set_current_flow(flows[0])
                if live:
                    obs.set_current_batch(npass, entry[4])
                try:
                    yield batch
                finally:
                    if flows:
                        obs.set_current_flow(0)
                    if live:
                        obs.set_current_batch(None)
                    for fid in flows:
                        obs.flow_end(fid, "chunk")
            if self._host.ack is not None:
                # the consumer released the batch: every chunk whose rows
                # first appeared in it is now consumed — advance the
                # exactly-once ack frontier
                for sid in entry[3]:
                    self._host.ack_seq(sid)
            # this thread's CPU time since the last delivery: dispatches,
            # the consumer's loop body, the step's launch
            now = time.thread_time_ns()
            cpu.observe(now - cpu_at)
            cpu_at = now

        while True:
            with obs.span("feed_batch", pass_=npass, batch=nbatch):
                try:
                    # the wait for the producer's queue
                    with obs.span("take", hist=wait_hist, pass_=npass,
                                  batch=nbatch):
                        block = next(it)
                except StopIteration:
                    break
                flows = getattr(block, "flow_ids", ())
                seqs = getattr(block, "seq_ids", ())
                with obs.span("dispatch", hist=stage["dispatch_ns"],
                              pass_=npass, batch=nbatch):
                    for fid in flows:
                        obs.flow_step(fid, "chunk")
                    batch_bufs = self._to_device(block, flows, nbatch)
                    # async dispatch; the entry keeps the chunk ids so
                    # _consume can close flows and ack seqs on delivery,
                    # and the batch's number for its consume span
                    pending.append(batch_bufs + (flows, seqs, nbatch))
                self._m_batches.inc()
                # row accounting across block shapes: native dense tuple
                # carries its count at [3], padded batches as num_rows,
                # python RowBlocks via len()
                if isinstance(block, tuple):
                    self._m_rows.inc(int(block[3]))
                else:
                    self._m_rows.inc(
                        int(getattr(block, "num_rows", 0) or len(block)))
                nbatch += 1
            if len(pending) > window:
                yield from _consume(pending.popleft())
        while pending:
            yield from _consume(pending.popleft())

    def stats(self) -> dict:
        """Per-stage wall time (ns): host batch production (parse+densify),
        device dispatch, time this consumer spent waiting on the host
        thread, and time the consumer held each batch (its step work) —
        plus the staging-pool counters and the parser pipeline's own stage
        counters when it exposes them (SURVEY §5.1). Together these
        decompose an epoch: overlap-bound means host_wait ≈ 0 and
        consume dominates; sum-of-stages-bound shows up as host_wait ≈
        host_batch."""
        base = self._epoch_base
        out = {
            "batches": int(self._m_batches.value - base.get("batches", 0)),
            "pool": self.pool.stats(),
        }
        for key, hist in self._stage.items():
            # the producer's side of a pass that has ended reads as it
            # ended until before_first(), though it is staging the next
            total = (self._host.fact(key) if key == "host_batch_ns"
                     else hist.sum)
            out[key] = int(total - base.get(key, 0))
        pipeline = self._host.fact("pipeline")
        if pipeline:
            out["pipeline"] = pipeline
        return out

    def before_first(self) -> None:
        with obs.span("feed_restart", pass_=self._pass + 1):
            self._m_restarts.inc()
            host = self._host
            prewound = self._host_iter.advance()
            if prewound:
                # the producer reached the end of the pass, rewound the
                # parser on its own thread and has this pass's first
                # batches staged: only the bookkeeping is left
                self._m_prewound.inc()
                with host.lock:
                    host_batch_base = host.ended.popleft()["host_batch_ns"]
            else:
                # mid-pass, or a rewind that is not the producer's to
                # make: the producer is stopped; rewind and start it again
                self._parser.before_first()
                host_batch_base = host.host_batch_ns.sum
            # registry metrics are monotonic (Prometheus semantics);
            # stats() windows them against this baseline so it always
            # describes the current epoch, aligned with the native
            # pipeline's per-reopen counters
            self._epoch_base = {
                key: hist.sum for key, hist in self._stage.items()
            }
            self._epoch_base["host_batch_ns"] = host_batch_base
            self._epoch_base["batches"] = self._m_batches.value
            # the consumer's spans read the new number from here on; a
            # producer that staged ahead emits none that carry it
            self._pass += 1
            if not prewound:
                host.pass_ = self._pass
                self._host_iter.before_first()

    @property
    def bytes_read(self) -> int:
        return self._host.fact("bytes_read")

    # ---- job-snapshot state ---------------------------------------------
    def snapshot_state(self) -> Optional[dict]:
        """The parser's resumable read plan (None where it has none) at
        the boundary of the pass the consumer is in: where the producer
        has wound the parser past it, the plan it kept at that pass's
        end."""
        return self._host.fact("plan")

    def restore_state(self, plan: dict) -> None:
        """Put the parser at the boundary a job snapshot captured. The
        producer has been staging the plan the feed was built with: it is
        stopped first and started again over the restored one."""
        restore = getattr(self._parser, "restore_state", None)
        if not callable(restore):
            return
        self._host_iter.close()
        self._host.ended.clear()
        self._host.pass_ = self._pass
        restore(plan)
        self._host_iter.before_first()

    def close(self) -> None:
        self._host_iter.close()
        self._parser.close()

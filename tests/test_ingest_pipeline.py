"""Async ingest→HBM pipeline: ordered parity, backpressure, shutdown,
fixed-shape pool trace discipline (data/pipeline.py + device/feed.py).

All tests drive the pure-Python parser stack (LibSVMParser constructed
directly) so the contracts hold even where the native C++ pipeline would
normally win the create_parser routing.
"""

import gc
import queue
import sys
import threading
import time

import jax
import numpy as np
import pytest

from dmlc_tpu.data.parsers import LibSVMParser
from dmlc_tpu.data.pipeline import PipelinedParser
from dmlc_tpu.device.csr import BlockBatcher
from dmlc_tpu.device.feed import (
    BatchSpec,
    DeviceFeed,
    FixedShapePool,
    stall_breakdown,
)
from dmlc_tpu.io.input_split import create_input_split
from dmlc_tpu.io.readahead import OrderedWindow
from dmlc_tpu.obs import audit
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.params.knobs import (
    default_host_prefetch,
    default_nthread,
    default_prefetch,
)
from dmlc_tpu.utils.logging import DMLCError

ROWS = 3000
CHUNK = 8192  # small chunks so every test exercises multi-chunk pipelining


def _write_svm(path, rows=ROWS, seed=0):
    rng = np.random.RandomState(seed)
    lines = []
    for i in range(rows):
        ids = np.sort(rng.choice(40, size=1 + i % 7, replace=False))
        feats = " ".join("%d:%.6f" % (j, rng.rand()) for j in ids)
        lines.append("%d %s" % (i % 2, feats))
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def _base_parser(path, chunk=CHUNK):
    # threaded=False: the threaded split wrapper's producer starts pulling
    # at the default (8 MB) chunk size before a hint can land, which would
    # collapse these small files into one chunk and test nothing
    split = create_input_split(path, 0, 1, "text", threaded=False)
    split.hint_chunk_size(chunk)
    return LibSVMParser(split, nthread=1)


def _rows_of(parser):
    """Every row as a (label, indices, values) tuple, exact dtype+bits."""
    rows = []
    for block in parser:
        for k in range(len(block)):
            s, e = block.offset[k], block.offset[k + 1]
            rows.append((
                block.label[k].tobytes(),
                np.asarray(block.index[s:e]).tobytes(),
                np.asarray(block.value[s:e]).tobytes()
                if block.value is not None else b"",
            ))
    return rows


@pytest.fixture()
def svm_path(tmp_path):
    return _write_svm(tmp_path / "pipe.svm")


class TestOrderedParity:
    def test_bit_identical_to_serial(self, svm_path):
        serial = _base_parser(svm_path)
        want = _rows_of(serial)
        serial.close()
        assert len(want) == ROWS

        piped = PipelinedParser(_base_parser(svm_path), nthread=4)
        got = _rows_of(piped)
        assert got == want  # ordered window ⇒ byte-exact record order
        stats = piped.stats()
        assert stats["chunks"] > 1  # multi-chunk, or the test proves nothing
        assert stats["nthread"] == 4
        piped.close()

    def test_before_first_restarts_identically(self, svm_path):
        piped = PipelinedParser(_base_parser(svm_path), nthread=3)
        first = _rows_of(piped)
        piped.before_first()
        second = _rows_of(piped)
        assert first == second
        assert piped.bytes_read > 0
        piped.close()

    def test_backpressure_bounds_chunks_in_flight(self, svm_path):
        pulled = []

        class CountingParser(LibSVMParser):
            def next_chunk(self):
                chunk = super().next_chunk()
                if chunk is not None:
                    pulled.append(1)
                return chunk

        split = create_input_split(svm_path, 0, 1, "text", threaded=False)
        split.hint_chunk_size(2048)
        piped = PipelinedParser(
            CountingParser(split, nthread=1), nthread=1, window=2
        )
        consumed = 0
        while piped.next_block() is not None:
            consumed += 1
            # the consumer-driven fill never reads ahead past the window
            assert len(pulled) <= consumed + 2
        assert len(pulled) > 2
        piped.close()


class TestShutdown:
    def _exploding(self, svm_path, marker_chunk):
        seen = []

        class ExplodingParser(LibSVMParser):
            def parse_chunk(self, chunk):
                seen.append(1)
                if len(seen) == marker_chunk:
                    raise ValueError("parse exploded")
                return super().parse_chunk(chunk)

        split = create_input_split(svm_path, 0, 1, "text", threaded=False)
        split.hint_chunk_size(2048)
        return ExplodingParser(split, nthread=1)

    def test_midstream_error_propagates_in_order(self, svm_path):
        piped = PipelinedParser(self._exploding(svm_path, 3), nthread=2)
        blocks = 0
        with pytest.raises(ValueError, match="parse exploded"):
            while piped.next_block() is not None:
                blocks += 1
        assert blocks == 2  # every block before the failed chunk delivered
        # the queue is poisoned: further pulls refuse rather than hang
        with pytest.raises(DMLCError):
            piped.next_block()
        piped.close()  # clean, idempotent
        piped.close()

    def test_feed_error_propagates_and_feed_stays_closeable(self, svm_path):
        spec = BatchSpec(batch_size=256, layout="dense", num_features=40,
                         prefetch=2)
        feed = DeviceFeed(
            PipelinedParser(self._exploding(svm_path, 2), nthread=2),
            spec, host_prefetch=2,
        )
        with pytest.raises(Exception, match="parse exploded"):
            for _ in feed:
                pass
        feed.close()
        # no stray non-daemon threads wedging interpreter shutdown
        assert all(
            t.daemon or t is threading.main_thread() or not t.is_alive()
            for t in threading.enumerate()
        )

    def test_exhaustion_closes_clean(self, svm_path):
        piped = PipelinedParser(_base_parser(svm_path), nthread=2)
        assert sum(len(b) for b in piped) == ROWS
        assert piped.next_block() is None  # exhausted, not an error
        piped.close()


def _collect(feed):
    """Every array of every batch, as bytes."""
    return [{k: np.asarray(v).tobytes() for k, v in batch.items()
             if not np.isscalar(v)} for batch in feed]


class TestDeviceFeedParity:
    @pytest.mark.parametrize("layout", ["dense", "csr"])
    def test_pipelined_feed_bit_identical_to_serial(self, svm_path, layout):
        spec_serial = BatchSpec(batch_size=512, layout=layout,
                                num_features=40, prefetch=1)
        serial = DeviceFeed(_base_parser(svm_path), spec_serial,
                            host_prefetch=0)
        want = _collect(serial)
        serial.close()

        spec_pipe = BatchSpec(batch_size=512, layout=layout,
                              num_features=40, prefetch=2)
        piped = DeviceFeed(
            PipelinedParser(_base_parser(svm_path), nthread=4),
            spec_pipe, host_prefetch=2,
        )
        got = _collect(piped)
        assert got == want
        stats = piped.stats()
        assert stats["pipeline"]["chunks"] > 1
        assert "consume_ns" in stats
        assert stall_breakdown(stats)  # formats without blowing up
        piped.close()


class TestPythonProducerPuts:
    """The Python re-batch producer (the parsers with no native batch
    fetch): batches do not depend on where the parser cut its chunks, and
    a batch crosses to the device in one put."""

    @pytest.mark.parametrize("layout", ["dense", "csr"])
    def test_python_rebatch_spans_chunk_boundaries(self, svm_path, layout):
        """Tiny parser chunks force every batch to span several blocks —
        the accumulate/slice logic, not the happy one-block path — and
        must give the batches of the same file read as one chunk."""
        spec = BatchSpec(batch_size=256, layout=layout, num_features=40)
        whole = DeviceFeed(_base_parser(svm_path, chunk=1 << 24), spec,
                           host_prefetch=0)
        want = _collect(whole)
        assert whole._parser.bytes_read > 0
        whole.close()
        chunked = DeviceFeed(_base_parser(svm_path, chunk=1024), spec,
                             host_prefetch=0)
        got = _collect(chunked)
        chunked.close()
        assert len(want) == -(-ROWS // 256)
        assert got == want  # every array of every batch, byte-exact

    def test_dispatch_counter_one_per_batch(self, svm_path, monkeypatch):
        """The whole pytree crosses in ONE device_put per batch —
        dispatches/batch > 1 is the per-array regression the sentry
        gates (dmlc_feed_h2d_dispatches_total)."""
        spec = BatchSpec(batch_size=512, layout="csr", num_features=40)
        feed = DeviceFeed(_base_parser(svm_path), spec, host_prefetch=0)
        # on the cpu backend the eager put is skipped: the feed puts as
        # on an accelerator once it is built (its pool does not recycle)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        batches = sum(1 for _ in feed)
        assert batches > 0
        assert feed._m_dispatches.value == batches
        feed.close()

    def test_batched_multihost_put_matches_per_array(self, svm_path):
        """_put_tree_multihost (one batched device_put + metadata-only
        assembly) must equal the per-array
        make_array_from_process_local_data result. Single-process mesh:
        both APIs are exercisable and must agree exactly."""
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
        spec = BatchSpec(batch_size=4, layout="dense", num_features=8)
        feed = DeviceFeed(_base_parser(svm_path), spec, mesh=mesh,
                          host_prefetch=0)
        from jax.sharding import PartitionSpec as P

        arrays = {
            "x": np.arange(32, dtype=np.float32).reshape(4, 8),
            "label": np.arange(4, dtype=np.float32),
            "vec": np.arange(8, dtype=np.float32),  # replicated
        }
        specs = {"x": P("dp"), "label": P("dp"), "vec": P()}
        before = feed._m_dispatches.value
        got = feed._put_tree_multihost(arrays, specs)
        assert feed._m_dispatches.value == before + 1  # ONE batched put
        for k, v in arrays.items():
            ref = jax.make_array_from_process_local_data(
                feed._sharding(specs[k]), v)
            assert got[k].shape == ref.shape
            assert got[k].sharding == ref.sharding
            assert np.array_equal(np.asarray(got[k]), np.asarray(ref))
        feed.close()


def _blocks(rows=(7, 5, 9, 4, 6, 8), seed=3):
    """RowBlocks of the given row counts; the third has no values, the
    fourth no weights."""
    from dmlc_tpu.data.row_block import RowBlock

    rng = np.random.RandomState(seed)
    out = []
    for k, n in enumerate(rows):
        counts = rng.randint(0, 6, size=n)
        nnz = int(counts.sum())
        out.append(RowBlock(
            offset=np.concatenate([[0], np.cumsum(counts)]),
            label=rng.rand(n).astype(np.float32),
            index=rng.randint(0, 40, size=nnz).astype(np.uint32),
            value=None if k == 2 else rng.rand(nnz).astype(np.float32),
            weight=None if k == 3 else rng.rand(n).astype(np.float32)))
    return out


def _staged_bytes(out):
    arrays = out if isinstance(out, tuple) else [
        v for v in vars(out).values() if isinstance(v, np.ndarray)]
    meta = () if isinstance(out, tuple) else (out.num_rows, out.num_nonzero)
    return [(a.dtype.str, a.shape, a.tobytes()) for a in arrays], meta


class TestStagingFromPieces:
    """A batch staged from its pieces — rows of a run of blocks, the first
    and the last cut — is the batch staged from one block of the same
    rows, byte for byte."""

    @pytest.mark.parametrize("npieces", [1, 2, 5])
    @pytest.mark.parametrize("layout", ["dense", "csr", "csr-sharded"])
    def test_pieces_stage_as_their_concatenation(self, layout, npieces):
        from dmlc_tpu.data.row_block import RowBlockContainer
        from dmlc_tpu.device.csr import (
            block_to_dense, pad_to_bucket, pad_to_bucket_sharded)

        blocks = _blocks()
        # two batches in a row: the first ends inside block npieces - 1,
        # which the second begins with
        cut = 2
        first = [(blocks[0], 2, cut + 3 if npieces == 1 else len(blocks[0]))]
        first += [(b, 0, len(b)) for b in blocks[1:npieces - 1]]
        if npieces > 1:
            first.append((blocks[npieces - 1], 0, cut))
        last_block, split = first[-1][0], first[-1][2]
        second = [(last_block, split, len(last_block)),
                  (blocks[npieces], 0, len(blocks[npieces]))]
        pool = FixedShapePool(recycle=True)
        for pieces in (first, second):
            assert len(pieces) in (npieces, 2)
            whole = RowBlockContainer()
            for b, lo, hi in pieces:
                whole.push_block(b.slice(lo, hi))
            whole = whole.to_block()
            if layout == "dense":
                got = block_to_dense(pieces, 32, 40, pool=pool)
                want = block_to_dense(whole, 32, 40)
            elif layout == "csr":
                got = pad_to_bucket(pieces, 32, pool=pool)
                want = pad_to_bucket(whole, 32)
            else:
                got = pad_to_bucket_sharded(pieces, 32, 2, pool=pool)
                want = pad_to_bucket_sharded(whole, 32, 2)
            assert _staged_bytes(got) == _staged_bytes(want)
            # the next batch is staged into this one's buffers, dirty
            staged = got if layout == "dense" else [
                v for v in vars(got).values() if isinstance(v, np.ndarray)]
            for buf in staged:
                buf.fill(7)
            pool.retire(staged, ())
        assert pool.reused > 0

    def test_batches_carry_their_chunks_and_ack_a_dropped_tail(self):
        blocks = _blocks(rows=(10, 20, 3, 0, 5))
        for k, b in enumerate(blocks):
            b.flow_id, b.seq_id = 100 + k, k
        acked = []
        batcher = BlockBatcher(blocks, None, None, True,
                               audit.auditor(), acked.append)
        got = []
        while (batch := batcher.read_batch_coo(16)) is not None:
            got.append((batch.num_rows, batch.flow_ids, batch.seq_ids))
        # each chunk's ids ride the first batch its rows reach; the last
        # 6 rows are dropped, and the chunks only they reached are acked
        assert got == [(16, (100, 101), (0, 1)), (16, (102,), (2,))]
        assert acked == [3, 4]
        batcher.restart(1)
        assert batcher.read_batch_coo(16).seq_ids == (0, 1)


class TestFixedShapePool:
    def test_one_trace_per_shape_bucket(self, svm_path):
        spec = BatchSpec(batch_size=512, layout="csr", num_features=40)
        feed = DeviceFeed(
            PipelinedParser(_base_parser(svm_path), nthread=2),
            spec, host_prefetch=2,
        )
        step = jax.jit(
            lambda b: (b["values"].sum(), b["indices"].max(),
                       b["label"].sum())
        )
        shapes_seen = set()
        for batch in feed:
            step(batch)
            shapes_seen.add(tuple(
                (k, np.shape(v)) for k, v in sorted(batch.items())
                if not np.isscalar(v)
            ))
        # static-shape contract: the jit traced exactly once per distinct
        # batch-shape bucket, never per batch
        assert step._cache_size() == len(shapes_seen)
        assert len(shapes_seen) < feed.stats()["batches"]
        # the pool's shape accounting saw every staged buffer shape
        assert feed.pool.stats()["shapes"] > 0
        feed.close()

    def _guard(self, ready):
        class G:
            def is_ready(self):
                return ready()
        return G()

    def test_recycles_only_after_transfer_done(self):
        """The guard is asked once, at retire: landed → reused; still in
        flight → dropped for good (a guard kept for later could be a
        donated, deleted array whose is_ready raises forever)."""
        pool = FixedShapePool(recycle=True)
        a = pool.acquire(64, np.float32)
        ready = [False]
        pool.retire([a], [self._guard(lambda: ready[0])])
        ready[0] = True  # landing later does not resurrect the buffer
        b = pool.acquire(64, np.float32)
        assert b is not a
        pool.retire([b], [self._guard(lambda: True)])
        c = pool.acquire(64, np.float32)  # landed at retire → reused
        assert c is b
        stats = pool.stats()
        assert stats == {"shapes": 1, "allocated": 2, "reused": 1,
                         "retired": 1, "dropped": 1, "double_retired": 0,
                         "outstanding": 1}

    def test_donated_guard_cannot_wedge_the_pool(self):
        """A real donated jax array raises from is_ready — which is why
        retire must run BEFORE the consumer's donating step (the feed's
        _deliver order), and why nothing is queued behind a guard."""
        x = jax.device_put(np.ones(8, np.float32))
        jax.jit(lambda v: v + 1, donate_argnums=0)(x)
        assert x.is_deleted()
        with pytest.raises(Exception, match="deleted"):
            x.is_ready()
        pool = FixedShapePool(recycle=True)
        live = jax.device_put(np.ones(8, np.float32))
        jax.block_until_ready(live)
        for _ in range(4):  # retire-before-donate keeps recycling
            buf = pool.acquire(8, np.float32)
            pool.retire([buf], [live])
        assert pool.stats()["reused"] == 3

    def test_no_recycle_mode_only_accounts_shapes(self):
        pool = FixedShapePool(recycle=False)
        a = pool.acquire((8, 4), np.float32)
        pool.retire([a], [self._guard(lambda: True)])
        b = pool.acquire((8, 4), np.float32)
        assert b is not a  # bit-parity over reuse where puts may alias
        assert pool.stats()["reused"] == 0
        assert pool.shape_keys == {((8, 4), np.dtype(np.float32).str)}

    def test_in_flight_retires_are_dropped_not_queued(self):
        pool = FixedShapePool(recycle=True)
        for _ in range(40):
            buf = pool.acquire(16, np.int32)
            pool.retire([buf], [self._guard(lambda: False)])
        stats = pool.stats()
        assert stats["dropped"] == 40 and stats["reused"] == 0
        assert stats["outstanding"] == 0  # dropped buffers are not a leak

    def test_double_retire_is_rejected(self):
        """A buffer offered back twice must not be queued twice — two
        future acquires sharing one backing array would corrupt an
        in-flight batch."""
        pool = FixedShapePool(recycle=True)
        a = pool.acquire(32, np.float32)
        pool.retire([a], [self._guard(lambda: True)])
        pool.retire([a], [self._guard(lambda: True)])  # duplicate offer
        assert pool.stats()["double_retired"] == 1
        assert pool.stats()["retired"] == 1
        b = pool.acquire(32, np.float32)
        c = pool.acquire(32, np.float32)
        assert b is a and c is not a  # handed out exactly once
        # once re-acquired, retiring again is legitimate, not a double
        pool.retire([b], [self._guard(lambda: True)])
        assert pool.stats()["double_retired"] == 1

    def test_leak_sentinel_fires_flight_event(self, tmp_path):
        """Acquires without matching retires make monotonic outstanding
        highs — after LEAK_STRIKES consecutive check windows, exactly one
        ``pool.leak`` flight event."""
        from dmlc_tpu.obs import flight

        rec = flight.configure(str(tmp_path), capacity=64, rank=0,
                               install=False)
        try:
            pool = FixedShapePool(recycle=True)
            n = pool.LEAK_CHECK_EVERY * (pool.LEAK_STRIKES + 2)
            for _ in range(n):
                pool.acquire(8, np.float32)  # never retired: a leak
            events = [r for r in rec.records()
                      if r["kind"] == "pool.leak"]
            assert len(events) == 1  # fires once, not per window
            assert events[0]["outstanding"] > 0
            assert events[0]["retired"] == 0
        finally:
            flight.reset()

    def test_acquired_on_one_thread_retired_on_another(self):
        """The feed's producer acquires and its consumer retires, through
        a queue of depth 4: no buffer is handed out twice, none is
        rewritten while it is held, and every one comes back."""
        pool = FixedShapePool(recycle=True)
        handoff = queue.Queue(4)
        held, lock, twice = set(), threading.Lock(), []

        def produce():
            for i in range(3000):
                bufs = [pool.acquire(16 + i % 3, np.int64) for _ in range(2)]
                with lock:
                    twice.extend(i for b in bufs if id(b) in held)
                    held.update(id(b) for b in bufs)
                for b in bufs:
                    b.fill(i)
                handoff.put((i, bufs))
            handoff.put(None)

        landed = [self._guard(lambda: True)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)  # switch threads as often as can be
        try:
            producer = threading.Thread(target=produce)
            producer.start()
            while (item := handoff.get(timeout=60)) is not None:
                i, bufs = item
                assert all((b == i).all() for b in bufs), i
                with lock:
                    held.difference_update(id(b) for b in bufs)
                pool.retire(bufs, landed)
            producer.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not producer.is_alive()
        assert twice == []
        assert pool.outstanding == 0 and pool.reused > 0
        stats = pool.stats()
        assert stats["allocated"] + stats["reused"] == stats["retired"] == 6000

    def test_healthy_churn_never_trips_leak_sentinel(self, tmp_path):
        from dmlc_tpu.obs import flight

        rec = flight.configure(str(tmp_path), capacity=64, rank=0,
                               install=False)
        try:
            pool = FixedShapePool(recycle=True)
            for _ in range(pool.LEAK_CHECK_EVERY * (pool.LEAK_STRIKES + 2)):
                buf = pool.acquire(8, np.float32)
                pool.retire([buf], [self._guard(lambda: True)])
            assert not [r for r in rec.records()
                        if r["kind"] == "pool.leak"]
        finally:
            flight.reset()


def _ids_of(seen, names=("feed_batch", "dispatch", "consume")):
    """{span name: [(pass_, batch), ...]} in the order the spans closed."""
    out = {}
    for e in seen:
        if e.get("ph") == "X" and e["name"] in names:
            args = e["args"]
            out.setdefault(e["name"], []).append(
                (args["pass_"], args["batch"]))
    return out


def _restart_counts(feed):
    """(restarts, pre-wound restarts) this feed has counted."""
    return int(feed._m_restarts.value), int(feed._m_prewound.value)


def _feed_threads():
    return [t for t in threading.enumerate() if t.name == "device-feed"]


class _Wrapped:
    """A parser seen through a wrapper a test can hang facts on."""

    def __init__(self, base):
        self._base = base
        self.rewound_on = []  # thread idents, one a before_first()

    def __iter__(self):
        return iter(self._base)

    def before_first(self):
        self.rewound_on.append(threading.get_ident())
        self._base.before_first()

    @property
    def bytes_read(self):
        return self._base.bytes_read

    def close(self):
        self._base.close()


class _Acked(_Wrapped):
    """The dispatcher parser's surface: explicit acks."""

    def set_explicit_ack(self):
        pass

    def ack(self, seq):
        pass


class _OnePass(_Wrapped):
    """A stream that cannot rewind (RemoteBlockParser's answer)."""

    def before_first(self):
        self.rewound_on.append(threading.get_ident())
        raise DMLCError("a one-pass stream")


@pytest.fixture(params=["text", "dtsh", "mesh"])
def source(request, tmp_path):
    """make_feed(host_prefetch) over the same rows from native text (the
    native stager), a baked ``.dtsh`` shard (the Python re-batch path) or
    text staged per chip for a mesh (``read_batch_coo_sharded``)."""
    from dmlc_tpu import native
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.parallel import data_parallel_mesh
    from dmlc_tpu.tools.bake import bake_dataset

    kind = request.param
    if kind != "dtsh" and not native.available():
        pytest.skip("native library not built")
    uri = _write_svm(tmp_path / "rewind.svm")
    mesh = None
    if kind == "dtsh":
        uri = str(tmp_path / "rewind.dtsh")
        bake_dataset(str(tmp_path / "rewind.svm"), uri, data_format="libsvm",
                     rows_per_window=100)
    elif kind == "mesh":
        mesh = data_parallel_mesh(jax.devices()[:4])
    spec = BatchSpec(batch_size=64, layout="csr", num_features=40,
                     nnz_bucket=512)

    def make_feed(host_prefetch=2, parser=None):
        return DeviceFeed(parser or create_parser(uri, 0, 1), spec,
                          mesh=mesh, host_prefetch=host_prefetch)

    make_feed.uri = uri
    return make_feed


class TestProducerRewindsItself:
    """A feed whose producer has reached the end of a pass rewinds the
    parser on its own thread and stages the next pass; ``before_first``
    then has only its bookkeeping to do. ``host_prefetch=0`` has no thread
    to work ahead, so it is the feed restarted the old way."""

    BATCHES = -(-ROWS // 64)

    def _passes(self, feed, n):
        seen, passes = [], []
        obs_trace.add_listener(seen.append)
        try:
            for k in range(n):
                if k:
                    feed.before_first()
                passes.append(_collect(feed))
        finally:
            obs_trace.remove_listener(seen.append)
        return passes, _ids_of(seen)

    @pytest.mark.parametrize("passes", [2, 3])
    def test_passes_equal_those_of_a_feed_restarted_the_old_way(
            self, source, passes):
        old = source(host_prefetch=0)
        want, want_ids = self._passes(old, passes)
        assert _restart_counts(old) == (passes - 1, 0)
        old.close()
        feed = source()
        got, got_ids = self._passes(feed, passes)
        # the end mark follows the rewind, so every restart after a whole
        # pass found the next one staged
        assert _restart_counts(feed) == (passes - 1, passes - 1)
        feed.close()
        assert len(got[0]) == self.BATCHES and got == want
        assert got_ids == want_ids
        assert got_ids["consume"] == [
            (p, b) for p in range(passes) for b in range(self.BATCHES)]

    def test_restart_in_mid_pass_takes_the_old_path(self, source):
        old = source(host_prefetch=0)
        want = _collect(old)
        old.close()
        feed = source()
        it = iter(feed)
        head = [next(it) for _ in range(3)]
        it.close()
        assert len(head) == 3
        feed.before_first()
        assert _restart_counts(feed) == (1, 0)
        assert _collect(feed) == want
        feed.before_first()  # this one after a whole pass
        assert _restart_counts(feed) == (2, 1)
        assert _collect(feed) == want
        feed.close()

    def test_stats_are_the_ended_passes_until_before_first(self, source):
        old = source(host_prefetch=0)
        _collect(old)
        want_bytes = old.bytes_read
        want_pipe = old.stats().get("pipeline") or {}
        old.close()
        feed = source()
        _collect(feed)
        deadline = time.monotonic() + 30
        while not feed._host_iter._queue.full() and \
                time.monotonic() < deadline:
            time.sleep(0.001)
        # the producer has read on into the next pass; the feed says what
        # the pass that ended read
        assert feed._host_iter._queue.full()
        assert feed._parser.bytes_read > want_bytes
        assert feed.bytes_read == want_bytes
        pipe = feed.stats().get("pipeline") or {}
        counts = [k for k, v in want_pipe.items()
                  if isinstance(v, int) and not k.endswith("_ns")]
        assert counts and {k: pipe[k] for k in counts} == {
            k: want_pipe[k] for k in counts}
        assert feed.stats()["batches"] == self.BATCHES
        ended_host_ns = feed.stats()["host_batch_ns"]
        time.sleep(0.01)
        assert feed.stats()["host_batch_ns"] == ended_host_ns
        feed.before_first()
        assert feed.stats()["batches"] == 0
        assert feed.bytes_read > want_bytes
        _collect(feed)
        assert feed.bytes_read == 2 * want_bytes
        feed.close()

    def test_close_after_a_staged_pass_leaves_nothing(self, source):
        feed = source()
        # on the cpu backend the pool only counts shapes; recycle as an
        # accelerator's does (each batch is copied while it is held)
        feed.pool.recycle = True
        assert len(_collect(feed)) == self.BATCHES
        thread = feed._host_iter._thread
        assert thread.is_alive()  # parked on the pass it staged
        feed.close()
        assert not thread.is_alive() and feed._host_iter._thread is None
        assert feed.pool.outstanding == 0

    def test_a_dropped_feed_is_collected_and_stops_its_thread(self, source):
        before = set(_feed_threads())
        feed = source()
        _collect(feed)
        mine = [t for t in _feed_threads() if t not in before]
        assert len(mine) == 1 and mine[0].is_alive()
        del feed
        gc.collect()
        mine[0].join(timeout=30)
        assert not mine[0].is_alive()

    @pytest.mark.parametrize("why", ["ack", "audit", "sync"])
    def test_never_rewinds_where_it_is_not_the_feeds_to_decide(
            self, source, why, monkeypatch):
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.obs import audit

        if why == "audit":
            monkeypatch.setenv("DMLC_TPU_AUDIT", "1")
            audit.reset_auditor()
        try:
            parser = None
            if why == "ack":
                parser = _Acked(create_parser(source.uri, 0, 1))
            feed = source(host_prefetch=0 if why == "sync" else 2,
                          parser=parser)
            first = _collect(feed)
            feed.before_first()
            assert _collect(feed) == first
            assert _restart_counts(feed) == (1, 0)
            if parser is not None:  # rewound once, by the consumer
                assert parser.rewound_on == [threading.get_ident()]
            feed.close()
        finally:
            if why == "audit":
                monkeypatch.delenv("DMLC_TPU_AUDIT")
                audit.reset_auditor()

    def test_a_one_pass_stream_raises_where_it_is_asked(self, source):
        from dmlc_tpu.data import create_parser

        parser = _OnePass(create_parser(source.uri, 0, 1))
        feed = source(parser=parser)
        assert len(_collect(feed)) == self.BATCHES
        with pytest.raises(DMLCError, match="one-pass"):
            feed.before_first()
        # asked once by the producer, which stopped, and once here
        assert len(parser.rewound_on) == 2
        assert parser.rewound_on[1] == threading.get_ident()
        assert _restart_counts(feed) == (1, 0)
        feed.close()


# the consumer thread's spans of one batch, and where each lies
_BATCH_TREE = {
    "take": "feed_batch", "dispatch": "feed_batch", "put": "dispatch",
    "deliver": None, "consume": None,
}


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


class TestBatchSpans:
    """One batch under one ``(pass_, batch)`` from the producer's thread to
    the consumer's hand, every part of it under a leaf span (device/feed.py:
    with the counter of its name where something reads one), on both
    producers' paths."""

    BATCHES = -(-ROWS // 64)

    def _traced(self, make_feed, passes=1, **kw):
        """(feed, spans): the listener is there before the feed is, whose
        producer starts to stage as soon as it is built."""
        seen = []
        obs_trace.add_listener(seen.append)
        try:
            feed = make_feed(**kw)
            for k in range(passes):
                if k:
                    feed.before_first()
                for _ in feed:
                    pass
        finally:
            obs_trace.remove_listener(seen.append)
        return feed, [e for e in seen if e.get("ph") == "X"]

    @pytest.mark.parametrize("host_prefetch", [2, 0])
    def test_the_span_tree_of_a_batch(self, source, host_prefetch):
        feed, spans = self._traced(source, host_prefetch=host_prefetch)
        batched = isinstance(feed._host.reader, BlockBatcher)
        feed.close()
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e)
        want = set(_BATCH_TREE) | {"produce", "feed_batch"}
        assert want <= set(by_name)
        # the native stager pads in C++, a BlockBatcher under ``stage``
        assert ("stage" in by_name) == batched
        consumer = {e["tid"] for e in by_name["consume"]}
        assert len(consumer) == 1
        producer = {e["tid"] for e in by_name["produce"]}
        # the producer has its own thread, or none (inline under take)
        assert (producer == consumer) == (host_prefetch == 0)
        ids = lambda e: (e["args"]["pass_"], e["args"]["batch"])  # noqa: E731
        batches = [(0, b) for b in range(self.BATCHES)]
        for name, parent in _BATCH_TREE.items():
            if name not in by_name:
                continue
            found = {ids(e): e for e in by_name[name]}
            # take also has the wait that found the pass's end
            assert sorted(found)[:len(batches)] == batches, name
            assert {e["tid"] for e in by_name[name]} == consumer, name
            for ident in batches:
                e = found[ident]
                outer = [o for o in spans if o is not e and _inside(e, o)
                         and o["tid"] == e["tid"]
                         and o["name"] in _BATCH_TREE.values()]
                innermost = min(outer, key=lambda o: o["dur"], default=None)
                assert (innermost and innermost["name"]) == parent, (name, e)
                if parent:
                    assert ids(innermost) == ident, (name, e)
        # (a pass's last produce span found its end and made no batch)
        produced = {ids(e): e for e in by_name["produce"]
                    if e["args"]["batch"] < self.BATCHES}
        # a producer with a thread has staged the head of the next pass
        ahead = [i for i in produced if i[0] == 1]
        # (its queue, and the one it holds while the queue is full)
        assert len(ahead) <= (host_prefetch + 1 if host_prefetch else 0), ahead
        for ident in ahead:
            del produced[ident]
        assert sorted(produced) == batches
        if batched:  # staged inside its own produce span, on its thread
            staged = {ids(e): e for e in by_name["stage"]}
            assert set(staged) == set(produced) | set(ahead)
            for ident, e in produced.items():
                assert staged[ident]["tid"] == e["tid"]
                assert _inside(staged[ident], e), ident
                assert staged[ident]["args"]["rows"] == min(
                    64, ROWS - 64 * ident[1])
        if host_prefetch == 0:
            takes = {ids(e): e for e in by_name["take"]}
            for ident, e in produced.items():
                assert _inside(e, takes[ident])
        for ident in batches:  # made before it is taken, put, handed over
            order = [produced[ident]] + [
                next(e for e in by_name[n] if ids(e) == ident)
                for n in ("take", "put", "deliver", "consume")]
            ends = [e["ts"] + e["dur"] for e in order]
            assert ends == sorted(ends), ident

    def test_produce_carries_the_pass_its_batch_belongs_to(self, source):
        """Across pre-wound restarts: the producer stages pass n+1 while
        the consumer is in pass n, and numbers it n+1."""
        feed, spans = self._traced(source, passes=3)
        assert _restart_counts(feed) == (2, 2)
        feed.close()
        made = [(e["args"]["pass_"], e["args"]["batch"]) for e in spans
                if e["name"] == "produce"
                and e["args"]["batch"] < self.BATCHES]
        # the producer may have staged the head of a fourth pass
        want = [(p, b) for p in range(3) for b in range(self.BATCHES)]
        assert made[:len(want)] == want
        assert all(p == 3 for p, _ in made[len(want):])
        held = [(e["args"]["pass_"], e["args"]["batch"]) for e in spans
                if e["name"] == "consume"]
        assert held == want

    def test_produce_follows_a_restart_made_in_mid_pass(self, source):
        seen = []
        obs_trace.add_listener(seen.append)
        try:
            feed = source()
            it = iter(feed)
            for _ in range(3):
                next(it)
            it.close()
            feed.before_first()  # stops the producer and starts it again
            for _ in feed:
                pass
        finally:
            obs_trace.remove_listener(seen.append)
        feed.close()
        made = [(e["args"]["pass_"], e["args"]["batch"]) for e in seen
                if e.get("ph") == "X" and e["name"] == "produce"
                and e["args"]["batch"] < self.BATCHES]
        second = [m for m in made if m[0] == 1]
        assert second[:self.BATCHES] == [
            (1, b) for b in range(self.BATCHES)]
        assert {p for p, _ in made} <= {0, 1, 2}

    def test_counters_fill_with_tracing_off(self, source, monkeypatch):
        """No listener, no trace file: every span with a counter is a
        two-read timer, a span without one (``deliver``) the shared inert
        object, and nothing is recorded."""
        from dmlc_tpu import obs

        assert not obs_trace._listeners
        obs.clear_trace()
        inert, real = {}, obs.span

        def span(name, hist=None, **args):
            made = real(name, hist=hist, **args)
            inert.setdefault(name, set()).add(made is obs.NOOP_SPAN)
            return made

        monkeypatch.setattr(obs, "span", span)
        feed = source()
        batched = isinstance(feed._host.reader, BlockBatcher)
        n = sum(1 for _ in feed)
        assert n == self.BATCHES
        timed = {"produce", "take", "dispatch", "put", "consume"}
        if batched:
            timed.add("stage")
        assert {k for k, v in inert.items() if v == {False}} == timed
        # (the Python parser's own spans have no counter either)
        assert {"feed_batch", "deliver"} <= {
            k for k, v in inert.items() if v == {True}}
        counts = {
            "host_wait": feed._stage["host_wait_ns"].count,
            "dispatch": feed._stage["dispatch_ns"].count,
            "consume": feed._stage["consume_ns"].count,
            "put": feed._h_put.count,
        }
        # one wait more than batches: the one that found the pass's end
        assert counts == {
            "host_wait": n + 1, "dispatch": n, "consume": n, "put": n}
        assert feed._stage["host_batch_ns"].count >= n
        # staged on the producer, which may have staged the next pass's
        # head: once a batch it produced, never on the consumer
        staged = feed._h_stage.count
        assert (n <= staged < feed._stage["host_batch_ns"].count
                if batched else staged == 0)
        assert feed._h_put.sum <= feed._stage["dispatch_ns"].sum
        assert feed._m_unlanded.value == 0
        feed.close()
        assert obs.trace_events() == []

    def test_sync_host_books_production_once(self, source):
        """``host_prefetch=0``: the time inside next() is production
        (``produce``, host_batch_ns) and no wait (``take`` has no
        counter)."""
        feed = source(host_prefetch=0)
        n = sum(1 for _ in feed)
        assert feed._stage["host_wait_ns"].count == 0
        assert feed._stage["host_batch_ns"].count == n + 1
        feed.close()

    def test_a_delivery_before_the_copy_landed_is_counted(self, source):
        class Guard:
            def __init__(self, ready):
                self.ready = ready

            def is_ready(self):
                return self.ready

        feed = source()
        feed.pool.recycle = True  # as on an accelerator
        landed = {"label": Guard(True), "values": Guard(True), "num_rows": 4}
        flying = {"label": Guard(True), "values": Guard(False), "num_rows": 4}
        a = feed.pool.acquire(8, np.float32)
        b = feed.pool.acquire(8, np.float32)
        assert feed._deliver((landed, (a,), (), (), 0)) is landed
        assert feed._m_unlanded.value == 0
        assert feed._deliver((flying, (b,), (), (), 1)) is flying
        assert feed._m_unlanded.value == 1
        # no staging buffers to retire (the native stager's batches): the
        # question is asked all the same
        assert feed._deliver((flying, (), (), (), 2)) is flying
        assert feed._m_unlanded.value == 2
        # and the pool reuses only the buffer whose copy had landed
        stats = feed.pool.stats()
        assert (stats["retired"], stats["dropped"]) == (1, 1)
        feed.close()


class TestKnobs:
    def test_nthread_knob(self, monkeypatch, svm_path):
        monkeypatch.setenv("DMLC_TPU_NTHREAD", "3")
        assert default_nthread() == 3
        assert default_nthread(5) == 5  # explicit wins
        piped = PipelinedParser(_base_parser(svm_path))
        assert piped.stats()["nthread"] == 3
        piped.close()

    def test_prefetch_knobs(self, monkeypatch, svm_path):
        monkeypatch.setenv("DMLC_TPU_PREFETCH", "4")
        monkeypatch.setenv("DMLC_TPU_HOST_PREFETCH", "0")
        assert default_prefetch() == 4
        assert default_prefetch(2) == 2
        assert default_host_prefetch() == 0
        spec = BatchSpec(batch_size=512, layout="dense", num_features=40)
        feed = DeviceFeed(_base_parser(svm_path), spec)
        assert feed._prefetch == 4
        assert feed._sync_host  # host prefetch 0 → inline producer
        assert sum(1 for _ in feed) > 0
        feed.close()

    def test_host_prefetch_auto(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_HOST_PREFETCH", raising=False)
        assert default_host_prefetch() is None
        monkeypatch.setenv("DMLC_TPU_HOST_PREFETCH", "-1")
        assert default_host_prefetch() is None
        assert default_host_prefetch(3) == 3


class TestOrderedWindow:
    def test_preserves_order_and_closes(self):
        win = OrderedWindow(lambda x: x * x, workers=4, window=6)
        results = []
        for i in range(20):
            if win.free_slots <= 0:
                results.append(win.pop())
            win.submit(i)
        while len(win):
            results.append(win.pop())
        assert results == [i * i for i in range(20)]
        win.close()
        with pytest.raises(DMLCError):
            win.submit(1)

    def test_error_poisons_window(self):
        def boom(x):
            if x == 2:
                raise RuntimeError("task failed")
            return x

        win = OrderedWindow(boom, workers=2, window=4)
        for i in range(4):
            win.submit(i)
        assert win.pop() == 0
        assert win.pop() == 1
        with pytest.raises(RuntimeError, match="task failed"):
            win.pop()
        with pytest.raises(DMLCError):
            win.submit(9)


@pytest.mark.slow
def test_stress_pipeline_four_workers(tmp_path):
    """4 parse workers × prefetch 2 × host prefetch 2, three epochs over a
    file large enough for dozens of chunks — parity and clean shutdown
    under sustained concurrency."""
    path = _write_svm(tmp_path / "stress.svm", rows=20000, seed=7)

    serial = DeviceFeed(
        _base_parser(path, chunk=4096),
        BatchSpec(batch_size=256, layout="csr", num_features=40, prefetch=1),
        host_prefetch=0,
    )
    want = [{k: np.asarray(v).tobytes() for k, v in b.items()
             if not np.isscalar(v)} for b in serial]
    serial.close()

    feed = DeviceFeed(
        PipelinedParser(_base_parser(path, chunk=4096), nthread=4),
        BatchSpec(batch_size=256, layout="csr", num_features=40, prefetch=2),
        host_prefetch=2,
    )
    for _ in range(3):
        got = [{k: np.asarray(v).tobytes() for k, v in b.items()
                if not np.isscalar(v)} for b in feed]
        assert got == want
        feed.before_first()
    stats = feed.stats()
    assert stats["pipeline"]["nthread"] == 4
    feed.close()

"""Native ingest pipeline tests (cpp/pipeline.cc).

Covers the exactly-once partition contract (input_split_base.cc:30-64
semantics), agreement with the Python parser stack, epoch restart, csv
label/weight column splitting, and error propagation out of the worker
threads — the TPU-build analog of split_read_test.cc +
libsvm_parser_test.cc run as unit tests instead of manual CLI harnesses.
"""

import os

import numpy as np
import pytest

from dmlc_tpu import native
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.parsers import NativePipelineParser

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library not built"
)


def _collect(parser):
    labels, indices, values = [], [], []
    rows = 0
    for block in parser:
        rows += len(block)
        labels.append(block.label)
        indices.append(block.index)
        values.append(
            block.value
            if block.value is not None
            else np.ones(block.num_nonzero, dtype=np.float32)
        )
    return (
        rows,
        np.concatenate(labels) if labels else np.empty(0),
        np.concatenate(indices) if indices else np.empty(0),
        np.concatenate(values) if values else np.empty(0),
    )


@pytest.fixture
def svm_file(tmp_path):
    rng = np.random.RandomState(7)
    path = tmp_path / "data.svm"
    lines = []
    for i in range(997):  # prime count, ragged widths
        nfeat = 1 + (i * 7) % 5
        feats = " ".join(
            f"{j + 1}:{rng.rand():.4f}" for j in range(nfeat)
        )
        lines.append(f"{i % 2} {feats}")
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def test_routes_to_native_pipeline(svm_file):
    parser = create_parser(svm_file, 0, 1)
    assert isinstance(parser, NativePipelineParser)
    parser.close()


def test_matches_python_stack(svm_file):
    rows_n, lab_n, idx_n, val_n = _collect(create_parser(svm_file, 0, 1))
    os.environ["DMLC_TPU_NATIVE"] = "0"
    try:
        py = create_parser(svm_file, 0, 1)
        assert not isinstance(py, NativePipelineParser)
        rows_p, lab_p, idx_p, val_p = _collect(py)
    finally:
        del os.environ["DMLC_TPU_NATIVE"]
    assert rows_n == rows_p == 997
    np.testing.assert_array_equal(lab_n, lab_p)
    np.testing.assert_array_equal(idx_n, idx_p)
    np.testing.assert_allclose(val_n, val_p, rtol=1e-6)


@pytest.mark.parametrize("nparts", [1, 2, 3, 7, 64])
def test_exactly_once_partitions(svm_file, nparts):
    """Every record lands in exactly one part, for adversarial part counts."""
    whole_rows, whole_lab, _, _ = _collect(create_parser(svm_file, 0, 1))
    rows = 0
    labs = []
    for part in range(nparts):
        r, lab, _, _ = _collect(create_parser(svm_file, part, nparts))
        rows += r
        labs.append(lab)
    assert rows == whole_rows
    np.testing.assert_array_equal(np.concatenate(labs), whole_lab)


def test_partitions_with_tiny_chunks(svm_file):
    """Chunk boundaries inside records: grow-and-cut logic (Chunk::Load)."""
    parser = NativePipelineParser(
        [svm_file], [os.path.getsize(svm_file)], "libsvm", 0, 1, nthread=2
    )
    pipe_args = parser._open_args
    parser.close()
    from dmlc_tpu.native import IngestPipeline

    pipe = IngestPipeline(
        pipe_args[0], pipe_args[1], native.INGEST_LIBSVM, 0, 1,
        nthread=2, chunk_bytes=1 << 16,
    )
    rows = 0
    while True:
        blk = pipe.next_block()
        if blk is None:
            break
        rows += len(blk["labels"])
    pipe.close()
    assert rows == 997


def test_multi_file(tmp_path):
    a = tmp_path / "a.svm"
    b = tmp_path / "b.svm"
    a.write_text("1 1:1.0\n0 2:2.0\n")
    b.write_text("1 3:3.0\n")
    uri = f"{a};{b}"
    rows, lab, idx, val = _collect(create_parser(uri, 0, 1))
    assert rows == 3
    np.testing.assert_array_equal(lab, [1, 0, 1])
    np.testing.assert_array_equal(idx, [1, 2, 3])


def test_before_first_rereads(svm_file):
    parser = create_parser(svm_file, 0, 1)
    assert isinstance(parser, NativePipelineParser)
    r1, lab1, _, _ = _collect(parser)
    parser.before_first()
    r2, lab2, _, _ = _collect(parser)
    parser.close()
    assert r1 == r2 == 997
    np.testing.assert_array_equal(lab1, lab2)
    assert parser.bytes_read > 0


def test_weights_and_qid(tmp_path):
    path = tmp_path / "w.svm"
    path.write_text("1:0.5 qid:3 1:1.0 2:2.0\n0:2.0 qid:4 3:4.0\n")
    block = create_parser(str(path), 0, 1).next_block()
    np.testing.assert_array_equal(block.label, [1, 0])
    np.testing.assert_allclose(block.weight, [0.5, 2.0])
    np.testing.assert_array_equal(block.qid, [3, 4])


def test_libfm(tmp_path):
    path = tmp_path / "d.libfm"
    path.write_text("1 0:1:0.5 2:7:1.5\n0 1:3:2.5\n")
    parser = create_parser(str(path), 0, 1, data_format="libfm")
    assert isinstance(parser, NativePipelineParser)
    block = parser.next_block()
    parser.close()
    np.testing.assert_array_equal(block.label, [1, 0])
    np.testing.assert_array_equal(block.field, [0, 2, 1])
    np.testing.assert_array_equal(block.index, [1, 7, 3])
    np.testing.assert_allclose(block.value, [0.5, 1.5, 2.5])


def test_csv_label_column(tmp_path):
    path = tmp_path / "d.csv"
    path.write_text("1.0,2.0,3.0\n4.0,5.0,6.0\n")
    parser = create_parser(
        str(path) + "?format=csv&label_column=0", 0, 1
    )
    assert isinstance(parser, NativePipelineParser)
    block = parser.next_block()
    parser.close()
    np.testing.assert_array_equal(block.label, [1.0, 4.0])
    np.testing.assert_allclose(
        block.to_dense(), [[2.0, 3.0], [5.0, 6.0]]
    )


def test_parse_error_propagates(tmp_path):
    path = tmp_path / "bad.svm"
    path.write_text("1 1:1.0\nnot-a-row at:all\n")
    parser = create_parser(str(path), 0, 1)
    assert isinstance(parser, NativePipelineParser)
    from dmlc_tpu.utils.logging import DMLCError

    with pytest.raises(DMLCError):
        _collect(parser)
    parser.close()


def test_empty_parts_beyond_data(tmp_path):
    path = tmp_path / "tiny.svm"
    path.write_text("1 1:1.0\n")
    total = 0
    for part in range(8):
        r, _, _, _ = _collect(create_parser(str(path), part, 8))
        total += r
    assert total == 1


# ---------------------------------------------------------------------------
# Native batch staging (pipeline.cc StageBatch/FetchBatch*): the fixed-shape
# TPU feed path — re-batch + densify/COO-pad in C++
# ---------------------------------------------------------------------------


def _dense_from_blocks(blocks, rows, num_features):
    x = np.zeros((rows, num_features), dtype=np.float32)
    labels = np.zeros(rows, dtype=np.float32)
    off = 0
    for b in blocks:
        for r in range(len(b)):
            labels[off + r] = b.label[r]
            for k in range(b.offset[r], b.offset[r + 1]):
                if b.index[k] < num_features:
                    val = 1.0 if b.value is None else b.value[k]
                    x[off + r, b.index[k]] = val
        off += len(b)
    return x, labels


def test_batch_dense_matches_block_path(svm_file):
    blocks = list(create_parser(svm_file, 0, 1))
    want_x, want_labels = _dense_from_blocks(blocks, 997, 6)

    parser = create_parser(svm_file, 0, 1)
    assert parser.supports_batch_fetch
    got_x, got_labels, got_w = [], [], []
    total = 0
    while True:
        out = parser.read_batch_dense(128, 6)
        if out is None:
            break
        x, labels, weights, n = out
        assert x.shape == (128, 6)
        # padding contract: rows past n are zero with weight 0
        assert (weights[n:] == 0).all() and (weights[:n] == 1).all()
        assert (x[n:] == 0).all() and (labels[n:] == 0).all()
        got_x.append(x[:n])
        got_labels.append(labels[:n])
        total += n
    parser.close()
    assert total == 997
    np.testing.assert_allclose(np.concatenate(got_x), want_x, rtol=1e-6)
    np.testing.assert_array_equal(np.concatenate(got_labels), want_labels)


def test_batch_coo_matches_block_path(svm_file):
    blocks = list(create_parser(svm_file, 0, 1))
    want_nnz = sum(b.num_nonzero for b in blocks)

    parser = create_parser(svm_file, 0, 1)
    rows = 0
    nnz = 0
    vals = []
    while True:
        batch = parser.read_batch_coo(100, nnz_floor=4)
        if batch is None:
            break
        rows += batch.num_rows
        nnz += batch.num_nonzero
        # padded entries are arithmetic no-ops
        assert (batch.values[batch.num_nonzero:] == 0).all()
        assert (batch.indices[batch.num_nonzero:] == 0).all()
        assert batch.nnz_bucket >= batch.num_nonzero
        # row_ids address rows within this batch
        if batch.num_nonzero:
            assert batch.row_ids[: batch.num_nonzero].max() < batch.num_rows
        vals.append(batch.values[: batch.num_nonzero])
    parser.close()
    assert rows == 997
    assert nnz == want_nnz
    want_vals = np.concatenate(
        [b.value if b.value is not None
         else np.ones(b.num_nonzero, np.float32) for b in blocks]
    )
    np.testing.assert_allclose(np.concatenate(vals), want_vals, rtol=1e-6)


def test_batch_dense_partition_union(svm_file):
    """Batched fetch over k-of-n partitions covers every row exactly once."""
    whole = list(create_parser(svm_file, 0, 1))
    _, want_labels = _dense_from_blocks(whole, 997, 6)
    got = []
    for part in range(3):
        parser = create_parser(svm_file, part, 3)
        while True:
            out = parser.read_batch_dense(64, 6)
            if out is None:
                break
            _x, labels, _w, n = out
            got.append(labels[:n])
        parser.close()
    got = np.concatenate(got)
    assert len(got) == 997
    np.testing.assert_array_equal(got, want_labels)


def test_pipeline_stats(svm_file):
    parser = create_parser(svm_file, 0, 1)
    list(parser)
    stats = parser.stats()
    assert stats["bytes_read"] > 0
    assert stats["chunks"] >= 1
    assert stats["parse_ns"] > 0
    parser.close()


def test_pipeline_stats_count_thread_cpu(svm_file):
    """The reader thread and the parse workers count their own CPU time
    into the two slots appended to ingest_stats."""
    parser = create_parser(svm_file, 0, 1)
    list(parser)
    stats = parser.stats()
    parser.close()
    assert stats["reader_cpu_ns"] > 0
    assert stats["parse_cpu_ns"] > 0
    # CPU time of the workers cannot pass their wall time by more than the
    # clocks' granularity (a worker is one thread per parse)
    assert stats["parse_cpu_ns"] <= stats["parse_ns"] * 1.5 + 5e6


def test_ingest_stats_old_length_buffer(svm_file):
    """A caller that still passes the seven-slot buffer is served as
    before and nothing is written past its end."""
    from dmlc_tpu import native

    parser = create_parser(svm_file, 0, 1)
    assert isinstance(parser, NativePipelineParser)
    list(parser)
    pipe = parser._pipe
    lib, handle = pipe._lib, pipe._handle
    old = np.full(8, -1.0)
    lib.ingest_stats(handle, native._ptr(old), 7)
    new = np.full(10, -1.0)
    lib.ingest_stats(handle, native._ptr(new), 10)
    parser.close()
    assert old[7] == -1.0 and (old[:7] >= 0).all()
    np.testing.assert_array_equal(new[:7], old[:7])
    assert new[7] > 0 and new[8] > 0 and new[9] == -1.0


def test_parser_teardown_after_its_pipeline_was_finalized(svm_file):
    """The collector may finalize a parser's native pipeline before the
    parser itself (both sit in one garbage cycle); the parser's teardown
    then asks a closed pipeline for its byte count."""
    parser = create_parser(svm_file, 0, 1)
    assert isinstance(parser, NativePipelineParser)
    list(parser)
    parser._pipe.close()
    assert parser._pipe.bytes_read == 0
    parser.close()  # no NULL handle reaches the library


def test_device_feed_counts_stage_cpu(svm_file):
    """dmlc_stage_cpu_ns grows for the feed's producer thread and for
    the consumer side, one observation a batch."""
    from dmlc_tpu import obs
    from dmlc_tpu.device import BatchSpec, DeviceFeed

    def read():
        flat = obs.registry().flat_values()
        key = 'dmlc_stage_cpu_ns{stage="%s"}:%s'
        return {stage: (flat.get(key % (stage, "sum"), 0.0),
                        flat.get(key % (stage, "count"), 0.0))
                for stage in ("feed_producer", "consumer")}

    before = read()
    feed = DeviceFeed(
        create_parser(svm_file, 0, 1),
        BatchSpec(batch_size=128, layout="dense", num_features=6),
        host_prefetch=2,
    )
    for _ in feed:
        np.sum(np.arange(20000, dtype=np.float64))  # the consumer's work
    feed.close()
    after = read()
    for stage in ("feed_producer", "consumer"):
        assert after[stage][0] > before[stage][0], stage
        assert after[stage][1] - before[stage][1] >= 8, stage


def test_batch_csv_rejected(tmp_path):
    path = tmp_path / "d.csv"
    path.write_text("1,2,3\n4,5,6\n")
    parser = create_parser(str(path), 0, 1, data_format="csv")
    assert isinstance(parser, NativePipelineParser)
    assert not parser.supports_batch_fetch
    parser.close()


def test_device_feed_native_path_matches_legacy(svm_file):
    """DeviceFeed over the native batch path == the RowBlock re-batch path."""
    import jax

    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.device.csr import BlockBatcher

    spec = BatchSpec(batch_size=128, layout="dense", num_features=6)
    feed_native = DeviceFeed(create_parser(svm_file, 0, 1), spec)
    assert feed_native._host.reader is feed_native._parser
    native_batches = [jax.device_get(b["x"]) for b in feed_native]
    feed_native.close()

    os.environ["DMLC_TPU_NATIVE"] = "0"
    try:
        py_parser = create_parser(svm_file, 0, 1)
        assert not isinstance(py_parser, NativePipelineParser)
        feed_py = DeviceFeed(py_parser, spec)
        assert isinstance(feed_py._host.reader, BlockBatcher)
        py_batches = [jax.device_get(b["x"]) for b in feed_py]
        feed_py.close()
    finally:
        del os.environ["DMLC_TPU_NATIVE"]

    assert len(native_batches) == len(py_batches)
    for a, b in zip(native_batches, py_batches):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_device_feed_stats(svm_file):
    """Feed-level stage timers (SURVEY §5.1): host batch, dispatch, wait,
    plus the native pipeline's counters."""
    import jax  # noqa: F401 — feed touches the device layer

    from dmlc_tpu.device import BatchSpec, DeviceFeed

    feed = DeviceFeed(
        create_parser(svm_file, 0, 1),
        BatchSpec(batch_size=128, layout="dense", num_features=6),
    )
    n = sum(b["num_rows"] for b in feed)
    stats = feed.stats()
    feed.close()
    assert n == 997
    assert stats["batches"] == 8
    assert stats["host_batch_ns"] > 0
    assert stats["dispatch_ns"] > 0
    assert stats["pipeline"]["bytes_read"] > 0



def test_mmap_reader_matches_fread(svm_file, monkeypatch):
    """The zero-copy mmap reader (pipeline.cc TryMmapReader) must produce
    byte-identical blocks to the fread loop for every partitioning — same
    cut discipline, same exactly-once boundary semantics."""
    baselines = {}
    for nparts in (1, 2, 5):
        monkeypatch.setenv("DMLC_TPU_MMAP", "0")
        for part in range(nparts):
            baselines[(nparts, part)] = _collect(
                create_parser(svm_file, part, nparts, nthread=1)
            )
        monkeypatch.setenv("DMLC_TPU_MMAP", "1")
        for part in range(nparts):
            rows, labels, indices, values = _collect(
                create_parser(svm_file, part, nparts, nthread=1)
            )
            brows, blabels, bindices, bvalues = baselines[(nparts, part)]
            assert rows == brows
            np.testing.assert_array_equal(labels, blabels)
            np.testing.assert_array_equal(indices, bindices)
            np.testing.assert_array_equal(values, bvalues)



def test_block_pool_recycles_buffers(tmp_path):
    """Blocks released by the consumer (the numpy-view finalizer, via
    ingest_block_free) return to the pipeline's BlockPool: a prompt
    consumer sees the same physical buffers again instead of fresh
    mallocs. The file must span MANY chunks (the chunk floor is 64 KB)
    and the assertion is unconditional — a silently disengaged pool is
    exactly the regression this exists to catch."""
    from dmlc_tpu.native import IngestPipeline

    path = tmp_path / "big.svm"
    with open(path, "w") as fh:
        for i in range(40_000):  # ~1.2 MB -> ~10 blocks at 128 KB chunks
            fh.write(f"{i % 2} {i % 97 + 1}:0.5 {i % 89 + 101}:1.5\n")
    pipe = IngestPipeline(
        [str(path)], [os.path.getsize(path)], native.INGEST_LIBSVM, 0, 1,
        nthread=1, chunk_bytes=1 << 17,
    )
    addrs = []
    rows = 0
    while True:
        blk = pipe.next_block()
        if blk is None:
            break
        rows += len(blk["labels"])
        addrs.append(blk["labels"].__array_interface__["data"][0])
        del blk  # view GC -> ingest_block_free -> pool return
    pipe.close()
    assert rows == 40_000
    assert len(addrs) >= 4, f"expected many chunks, got {len(addrs)}"
    assert len(set(addrs)) < len(addrs), (
        "no buffer reuse across blocks — BlockPool disengaged: %r" % addrs
    )



def test_block_pool_survives_consumer_holding_blocks(svm_file):
    """A consumer that HOLDS every block (defeating the pool) must still
    get correct, independent data — pooling is an optimization, never an
    aliasing hazard: a held block's arrays must not be re-filled."""
    parser = create_parser(svm_file, 0, 1, nthread=1)
    held = [b for b in parser]
    parser.close()
    total = sum(len(b) for b in held)
    assert total == 997
    # concatenation must reproduce the whole file exactly (no aliasing)
    labels = np.concatenate([b.label for b in held])
    assert labels.shape[0] == 997
    expected = np.array([i % 2 for i in range(997)], dtype=np.float32)
    np.testing.assert_array_equal(labels, expected)


def test_cachefile_routes_native_rowgroup(tmp_path):
    """#cachefile on a local libsvm uri = DiskRowIter's build-then-stream
    contract (disk_row_iter.h:95-141) with a binary row-group cache served
    by the native recordio path: first instance builds, later instances
    stream the cache, content identical to the plain text parse; a changed
    source invalidates the cache via the meta signature."""
    import time as _time

    path = tmp_path / "d.svm"
    with open(path, "w") as fh:
        for i in range(5000):
            fh.write(f"{i % 2} {i % 7 + 1}:0.25 {i % 11 + 30}:1.5\n")
    cache = tmp_path / "d.cache"
    uri = f"{path}#{cache}"

    def collect(u):
        return _collect(create_parser(u, 0, 1, nthread=1))

    first = collect(uri)          # builds the cache
    # the native cache gets its own .rowrec suffix so the Python stack's
    # CachedInputSplit (different format, same #cachefile name) can never
    # pick it up by accident
    assert (tmp_path / "d.cache.rowrec").exists()
    assert (tmp_path / "d.cache.rowrec.meta").exists()
    assert not cache.exists()
    cached = collect(uri)         # streams it
    plain = collect(str(path))
    for got in (first, cached):
        assert got[0] == plain[0] == 5000
        np.testing.assert_array_equal(got[1], plain[1])
        np.testing.assert_array_equal(got[2], plain[2])
        np.testing.assert_array_equal(got[3], plain[3])
    # the cached instance must be the native recordio pipeline
    p = create_parser(uri, 0, 1, nthread=1)
    assert isinstance(p, NativePipelineParser)
    p.close()
    # parts get their own caches; union is exactly-once
    total = 0
    for part in range(3):
        pp = create_parser(uri, part, 3, nthread=1)
        total += sum(len(b) for b in pp)
        pp.close()
    assert total == 5000
    assert (tmp_path / "d.cache.split3.part2.rowrec").exists()
    # source change -> stale cache rebuilt, not served
    with open(path, "a") as fh:
        fh.write("1 3:9.0\n")
    now = _time.time() + 10
    os.utime(path, (now, now))
    rebuilt = collect(uri)
    assert rebuilt[0] == 5001


def test_cachefile_concurrent_builders(tmp_path):
    """Two builders racing on the same uri must both produce correct rows
    and leave a valid cache (pid+uuid tmp names; last atomic replace
    wins) — interleaved writes into a shared tmp would corrupt silently."""
    import threading

    path = tmp_path / "c.svm"
    with open(path, "w") as fh:
        for i in range(20000):
            fh.write(f"{i % 2} {i % 13 + 1}:0.5\n")
    uri = f"{path}#{tmp_path / 'race.cache'}"
    results = []
    errors = []

    def build():
        try:
            p = create_parser(uri, 0, 1, nthread=1)
            results.append(sum(len(b) for b in p))
            p.close()
        except Exception as err:  # surfaced below
            errors.append(err)

    threads = [threading.Thread(target=build) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert results == [20000, 20000], results
    # the surviving cache replays correctly
    p = create_parser(uri, 0, 1, nthread=1)
    assert sum(len(b) for b in p) == 20000
    p.close()


def test_shuffle_chunks_native(tmp_path):
    """?shuffle_chunks=SEED: the mmap reader visits the part's chunks in
    seeded random order (input_split_shuffle.h semantics at chunk
    granularity) — deterministic per seed, different across seeds,
    exactly-once, and still the native pipeline."""
    path = tmp_path / "s.svm"
    with open(path, "w") as fh:
        for i in range(400000):
            fh.write(f"{i % 2} 1:{i}.0\n")  # value = row id: order visible

    def order(uri, part=0, nparts=1):
        p = create_parser(uri, part, nparts, nthread=1)
        vals = np.concatenate([np.asarray(b.value) for b in p])
        native_route = isinstance(p, NativePipelineParser)
        p.close()
        return vals, native_route

    base, nat = order(str(path))
    assert nat
    np.testing.assert_array_equal(base, np.arange(400000, dtype=np.float32))
    s7a, nat7 = order(str(path) + "?shuffle_chunks=7")
    s7b, _ = order(str(path) + "?shuffle_chunks=7")
    s9, _ = order(str(path) + "?shuffle_chunks=9")
    assert nat7
    assert not np.array_equal(s7a, base)
    np.testing.assert_array_equal(s7a, s7b)
    assert not np.array_equal(s7a, s9)
    np.testing.assert_array_equal(np.sort(s7a), base)
    # multi-part: shuffled parts stay exactly-once
    parts = []
    for part in range(3):
        v, _ = order(str(path) + "?shuffle_chunks=5", part, 3)
        parts.append(v)
    np.testing.assert_array_equal(np.sort(np.concatenate(parts)), base)
    # cachefile combines: cached epochs shuffle natively too
    uri3 = f"{path}?shuffle_chunks=11#{tmp_path / 'cc'}"
    v3, nat3 = order(uri3)
    v3b, _ = order(uri3)
    assert nat3 and not np.array_equal(v3, base)
    np.testing.assert_array_equal(v3, v3b)
    np.testing.assert_array_equal(np.sort(v3), base)


def test_shuffle_chunks_multifile_falls_back(tmp_path):
    """A multi-file uri cannot mmap one mapping, so the request routes to
    the Python stack's InputSplitShuffle — never silently sequential."""
    a, b = tmp_path / "a.svm", tmp_path / "b.svm"
    with open(a, "w") as fh:
        for i in range(50000):
            fh.write(f"1 1:{i}.0\n")
    with open(b, "w") as fh:
        for i in range(50000):
            fh.write(f"0 1:{50000 + i}.0\n")
    p = create_parser(f"{a};{b}?shuffle_chunks=3", 0, 1, nthread=1)
    assert not isinstance(p, NativePipelineParser)
    vals = np.concatenate([np.asarray(blk.value) for blk in p])
    p.close()
    np.testing.assert_array_equal(
        np.sort(vals), np.arange(100000, dtype=np.float32)
    )
    assert not np.array_equal(vals, np.sort(vals))  # actually shuffled


def test_shuffle_chunks_empty_parts(tmp_path):
    """Parts whose byte window holds no record begin are legitimately
    empty — with shuffle requested they must yield zero rows exactly like
    the sequential path, never an error (reproduced rc=-3 regression)."""
    path = tmp_path / "tiny.svm"
    path.write_text("1 1:1.0\n0 2:2.0\n1 3:3.0\n")
    total = 0
    for part in range(8):
        p = create_parser(str(path) + "?shuffle_chunks=1", part, 8,
                          nthread=1)
        total += sum(len(b) for b in p)
        p.close()
    assert total == 3


def test_shuffle_chunks_reshuffles_per_epoch(tmp_path):
    """before_first() visits a FRESH permutation (seed+epoch) — the
    reference regenerates its shuffle every epoch
    (indexed_recordio_split.cc BeforeFirst); a replayed order would
    defeat shuffled SGD across epochs. A fresh parser with the same seed
    still reproduces epoch 0 exactly."""
    path = tmp_path / "e.svm"
    with open(path, "w") as fh:
        for i in range(400000):
            fh.write(f"{i % 2} 1:{i}.0\n")
    uri = str(path) + "?shuffle_chunks=7"
    p = create_parser(uri, 0, 1, nthread=1)
    e0 = np.concatenate([np.asarray(b.value) for b in p])
    p.before_first()
    e1 = np.concatenate([np.asarray(b.value) for b in p])
    p.close()
    base = np.arange(400000, dtype=np.float32)
    assert not np.array_equal(e0, e1)
    np.testing.assert_array_equal(np.sort(e0), base)
    np.testing.assert_array_equal(np.sort(e1), base)
    p2 = create_parser(uri, 0, 1, nthread=1)
    r0 = np.concatenate([np.asarray(b.value) for b in p2])
    p2.close()
    np.testing.assert_array_equal(r0, e0)


def test_shuffle_chunks_fuzz_cut_discipline(tmp_path):
    """Property fuzz (fixed rng): ragged rows × adversarial chunk sizes ×
    seeds — the shuffled emission must preserve the exact multiset of
    rows the sequential parse yields (a cut-discipline bug would split or
    duplicate boundary records)."""
    from dmlc_tpu.native import IngestPipeline

    rng = np.random.RandomState(13)
    path = tmp_path / "fz.svm"
    with open(path, "w") as fh:
        for i in range(30000):
            nfeat = 1 + int(rng.randint(0, 6))
            fh.write(f"{i % 2} " + " ".join(
                f"{int(rng.randint(1, 500))}:{i}.0" for _ in range(nfeat)
            ) + "\n")
    size = os.path.getsize(path)

    def collect(seed, chunk_bytes):
        pipe = IngestPipeline(
            [str(path)], [size], native.INGEST_LIBSVM, 0, 1,
            nthread=2, chunk_bytes=chunk_bytes, shuffle_seed=seed,
        )
        labels, values = [], []
        while True:
            blk = pipe.next_block()
            if blk is None:
                break
            labels.append(np.array(blk["labels"]))
            values.append(np.array(blk["values"]))
        pipe.close()
        return np.concatenate(labels), np.sort(np.concatenate(values))

    base_labels, base_values = collect(-1, 1 << 16)
    assert len(base_labels) == 30000
    for seed, chunk in ((3, 1 << 14), (11, 1 << 15), (29, 100_000)):
        labels, values = collect(seed, chunk)
        assert len(labels) == 30000, (seed, chunk)
        np.testing.assert_array_equal(values, base_values)
        np.testing.assert_array_equal(np.sort(labels), np.sort(base_labels))


def test_device_feed_over_shuffled_uri(tmp_path):
    """DeviceFeed composes with ?shuffle_chunks: the fixed-shape batch
    staging consumes shuffled blocks and the epoch still covers every
    row exactly once (sum of labels is order-invariant)."""
    import jax

    from dmlc_tpu.device import BatchSpec, DeviceFeed

    path = tmp_path / "f.svm"
    with open(path, "w") as fh:
        for i in range(300000):
            fh.write(f"{i % 2} 1:0.5 2:{i % 7}.0\n")
    spec = BatchSpec(batch_size=4096, layout="dense", num_features=3)
    rows = 0
    label_sum = 0.0
    feed = DeviceFeed(
        create_parser(str(path) + "?shuffle_chunks=5", 0, 1, nthread=1),
        spec,
    )
    for batch in feed:
        rows += batch["num_rows"]
        label_sum += float(jax.numpy.sum(batch["label"]))
    feed.close()
    assert rows == 300000
    assert label_sum == 150000.0  # every i%2 label seen exactly once

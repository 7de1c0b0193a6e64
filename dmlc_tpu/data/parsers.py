"""Text parsers: libsvm / libfm / csv chunks → CSR RowBlocks.

Capability parity with src/data/ (parser.h, text_parser.h, libsvm_parser.h,
libfm_parser.h, csv_parser.h, strtonum.h):

- ``Parser``: streaming one-pass DataIter over RowBlocks pulled from an
  InputSplit chunk source (parser.h:24-66); tracks ``bytes_read`` for MB/s
  telemetry (text_parser.h:43)
- chunk parsing is parallelized across worker threads by splitting the chunk
  at line boundaries (text_parser.h:94-134 uses OpenMP; here a thread pool +
  numpy-vectorized token conversion, which is both the Python idiom and what
  the native C++ core in cpp/ does with std::thread)
- ``ThreadedParser``: background-thread prefetch of parsed blocks, queue
  depth 8 (parser.h:70-126), applied by default by the factory
- formats: libsvm ``label[:weight] [qid:n] idx[:val]...`` (libsvm_parser.h:
  36-99 — omitted values mean 1, per-row weights, qid supported), libfm
  ``label field:idx:val`` (libfm_parser.h:35-90), dense csv with
  ``label_column`` (csv_parser.h:63-104, CSVParserParam :22-32)
- parser registry + ``create_parser(uri, part, nparts, format)`` resolving
  "auto" through the ``format=`` URI arg, default libsvm (src/data.cc:62-85)
"""

from __future__ import annotations

import concurrent.futures
import os
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from dmlc_tpu.data import vparse
from dmlc_tpu.data.row_block import (
    INDEX_DTYPE,
    REAL_DTYPE,
    RowBlock,
    RowBlockContainer,
)
from dmlc_tpu.io.input_split import InputSplit, create_input_split
from dmlc_tpu.io.uri_spec import URISpec
from dmlc_tpu.params.knobs import parse_backend, parse_procs
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.params.registry import Registry
from dmlc_tpu.utils.logging import DMLCError, check
from dmlc_tpu.utils.threaded_iter import ThreadedIter


class Parser:
    """Streaming parser base: DataIter over RowBlocks (data.h:298-316)."""

    def __init__(self, source: InputSplit, nthread: int = 2):
        self._source = source
        self._nthread = max(1, nthread)
        self._pool = (
            concurrent.futures.ThreadPoolExecutor(max_workers=self._nthread)
            if self._nthread > 1
            else None
        )
        self.bytes_read = 0

    # ---- subclass hook -------------------------------------------------
    def parse_chunk(self, chunk: bytes) -> RowBlockContainer:
        raise NotImplementedError

    # ---- iteration -----------------------------------------------------
    def next_chunk(self) -> Optional[bytes]:
        """Next raw chunk from the source (None at end), accounted in
        ``bytes_read`` — the producer half of ``next_block``, split out so
        the cross-chunk pipeline (data/pipeline.py) can pull chunks
        without parsing them inline."""
        chunk = self._source.next_chunk()
        if chunk is not None:
            self.bytes_read += len(chunk)
        return chunk

    def _split_lines(self, chunk: bytes, nparts: int) -> List[bytes]:
        """Split a chunk at line boundaries into ~equal parts
        (text_parser.h:104-118 / BackFindEndLine :71-77)."""
        if nparts <= 1 or len(chunk) < 4096:
            return [chunk]
        step = len(chunk) // nparts
        bounds = [0]
        for i in range(1, nparts):
            pos = chunk.rfind(b"\n", bounds[-1], i * step)
            pos2 = chunk.rfind(b"\r", bounds[-1], i * step)
            pos = max(pos, pos2)
            bounds.append(pos + 1 if pos > 0 else bounds[-1])
        bounds.append(len(chunk))
        return [chunk[bounds[i] : bounds[i + 1]] for i in range(nparts)]

    def next_block(self) -> Optional[RowBlock]:
        """Parse the next chunk into one RowBlock; None at end of data."""
        while True:
            chunk = self.next_chunk()
            if chunk is None:
                return None
            parts = self._split_lines(chunk, self._nthread)
            if self._pool is not None and len(parts) > 1:
                containers = list(self._pool.map(self.parse_chunk, parts))
            else:
                containers = [self.parse_chunk(p) for p in parts]
            merged = containers[0]
            for extra in containers[1:]:
                if len(extra):
                    merged.push_block(extra.to_block())
            if len(merged):
                return merged.to_block()
            # empty chunk (e.g. all blank lines): keep pulling

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            block = self.next_block()
            if block is None:
                return
            yield block

    def before_first(self) -> None:
        self._source.before_first()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        self._source.close()


def _tokens_to_floats(tokens: List[bytes]) -> np.ndarray:
    """Vectorized bytes→float64 conversion (the strtonum.h hot loop,
    done as one C-level astype instead of per-token strtof)."""
    if not tokens:
        return np.empty(0, dtype=np.float64)
    return np.asarray(tokens, dtype="S").astype(np.float64)


def _native_libsvm(chunk: bytes) -> Optional[RowBlockContainer]:
    """Native-core libsvm chunk parse (cpp/parse.cc); None → python path."""
    from dmlc_tpu import native

    parsed = native.parse_libsvm_chunk(chunk)
    if parsed is None:
        return None
    out = RowBlockContainer()
    if len(parsed["labels"]) == 0:
        return out
    flags = parsed["flags"]
    out.push_arrays(
        parsed["labels"],
        parsed["counts"],
        parsed["indices"],
        value=parsed["values"] if flags & native.HAS_VALUE else None,
        weight=parsed["weights"] if flags & native.HAS_WEIGHT else None,
        qid=parsed["qids"] if flags & native.HAS_QID else None,
    )
    return out


def _native_libfm(chunk: bytes) -> Optional[RowBlockContainer]:
    from dmlc_tpu import native

    parsed = native.parse_libfm_chunk(chunk)
    if parsed is None:
        return None
    out = RowBlockContainer()
    if len(parsed["labels"]) == 0:
        return out
    out.push_arrays(
        parsed["labels"],
        parsed["counts"],
        parsed["indices"],
        value=parsed["values"],
        field=parsed["fields"],
    )
    return out


class LibSVMParser(Parser):
    """``label[:weight] [qid:n] index[:value]...`` (libsvm_parser.h).

    Chunk parsing routes through ``DMLC_TPU_PARSE_BACKEND``
    (params/knobs.py): native C++ core first under auto/native, then the
    columnar vectorized tokenizer (data/vparse.py), with the scalar line
    loop as the semantic oracle (``backend=scalar`` or vparse's own
    grammar fallback)."""

    def parse_chunk(self, chunk: bytes) -> RowBlockContainer:
        backend = parse_backend()
        if backend in ("auto", "native"):
            native_out = _native_libsvm(chunk)
            if native_out is not None:
                return native_out
        out = RowBlockContainer()
        if backend == "scalar":
            vparse.parse_libsvm_scalar(chunk, out)
        else:
            vparse.parse_libsvm_vector(chunk, out)
        return out

    def _parse_general(self, chunk: bytes, out: RowBlockContainer) -> None:
        """Scalar oracle path (qid, bare indices, mixed weights — the full
        grammar). Kept as a hook for subclasses; delegates to vparse."""
        vparse.parse_libsvm_scalar(chunk, out)


class LibFMParser(Parser):
    """``label field:index:value`` triples (libfm_parser.h:35-90)."""

    def parse_chunk(self, chunk: bytes) -> RowBlockContainer:
        native_out = _native_libfm(chunk)
        if native_out is not None:
            return native_out
        out = RowBlockContainer()
        lines = [ln for ln in chunk.splitlines() if ln.strip()]
        if not lines:
            return out
        flat: List[bytes] = []
        counts = np.empty(len(lines), dtype=np.int64)
        for i, line in enumerate(lines):
            toks = line.replace(b":", b" ").split()
            check(
                (len(toks) - 1) % 3 == 0,
                "invalid libfm line: %s",
                line[:80].decode(errors="replace"),
            )
            counts[i] = (len(toks) - 1) // 3
            flat.extend(toks)
        values = _tokens_to_floats(flat)
        pos = 0
        labels = np.empty(len(lines), dtype=np.float64)
        fld_parts, idx_parts, val_parts = [], [], []
        for i in range(len(lines)):
            nfeat = int(counts[i])
            labels[i] = values[pos]
            triples = values[pos + 1 : pos + 1 + 3 * nfeat].reshape(nfeat, 3)
            fld_parts.append(triples[:, 0])
            idx_parts.append(triples[:, 1])
            val_parts.append(triples[:, 2])
            pos += 1 + 3 * nfeat
        out.push_arrays(
            labels.astype(REAL_DTYPE),
            counts,
            np.concatenate(idx_parts).astype(INDEX_DTYPE)
            if idx_parts
            else np.empty(0, dtype=INDEX_DTYPE),
            value=np.concatenate(val_parts).astype(REAL_DTYPE)
            if val_parts
            else np.empty(0, dtype=REAL_DTYPE),
            field=np.concatenate(fld_parts).astype(INDEX_DTYPE)
            if fld_parts
            else None,
        )
        return out


class CSVParserParam(Parameter):
    """URI args for the csv parser (csv_parser.h:22-32)."""

    format = field(str, "csv", description="File format.")
    label_column = field(
        int, -1, description="Column index that will be put into label."
    )
    weight_column = field(
        int, -1, description="Column index for per-row weights (TPU-new)."
    )


class CSVParser(Parser):
    """Dense CSV → CSR with running column indices (csv_parser.h:63-104)."""

    def __init__(self, source: InputSplit, args: Dict[str, str] = None, nthread: int = 2):
        super().__init__(source, nthread)
        self.param = CSVParserParam()
        self.param.init(args or {}, allow_unknown=True)
        check(self.param.format == "csv", "CSVParser requires format=csv")

    def parse_chunk(self, chunk: bytes) -> RowBlockContainer:
        out = RowBlockContainer()
        backend = parse_backend()
        if backend in ("auto", "native"):
            from dmlc_tpu import native

            table = native.parse_csv_chunk(chunk)
            if table is not None:
                if len(table) == 0:
                    return out
                return self._table_to_block(table, out)
        # vparse cell spans come straight from comma/newline offset arrays
        # (no b",".join re-join); the scalar table is the semantic oracle
        if backend == "scalar":
            table = vparse.parse_csv_scalar_table(chunk)
        else:
            table = vparse.parse_csv_vector_table(chunk)
        if table.shape[0] == 0:
            return out
        return self._table_to_block(table, out)

    def _table_to_block(
        self, table: np.ndarray, out: RowBlockContainer
    ) -> RowBlockContainer:
        return _csv_table_to_block(
            table, self.param.label_column, self.param.weight_column, out
        )


def _csv_table_to_block(
    table: np.ndarray,
    label_col: int,
    weight_col: int,
    out: RowBlockContainer,
) -> RowBlockContainer:
    """Split label/weight columns out of a dense table → CSR block."""
    nrows, ncols = table.shape
    keep = np.ones(ncols, dtype=bool)
    labels = np.zeros(nrows, dtype=REAL_DTYPE)
    weight = None
    if 0 <= label_col < ncols:
        labels = table[:, label_col].astype(REAL_DTYPE)
        keep[label_col] = False
    if 0 <= weight_col < ncols:
        weight = table[:, weight_col].astype(REAL_DTYPE)
        keep[weight_col] = False
    data = table[:, keep]
    nfeat = data.shape[1]
    counts = np.full(nrows, nfeat, dtype=np.int64)
    index = np.tile(np.arange(nfeat, dtype=INDEX_DTYPE), nrows)
    out.push_arrays(
        labels,
        counts,
        index,
        value=np.ascontiguousarray(data).reshape(-1).astype(REAL_DTYPE),
        weight=weight,
    )
    return out


def _feed_pipeline(pipe, reader, error_holder: list) -> None:
    """Remote-ingest feeder thread: in-order readahead buffers → native
    push ABI. ``push`` blocks for backpressure; a *fetch* failure is
    recorded in ``error_holder`` and aborts the pipeline so a consumer
    blocked in next_block wakes with an error instead of hanging. A push
    failure means the pipeline itself already failed (parse error, close)
    — nothing is recorded, the consumer sees the pipeline's own error.

    Module-level on purpose: the thread must hold no reference to the
    parser object so an abandoned parser can still be collected.
    """
    from dmlc_tpu.utils.logging import DMLCError as _DMLCError

    try:
        if getattr(reader, "prefers_direct_feed", False) and hasattr(
            pipe, "push_reserve"
        ):
            from dmlc_tpu.io.readahead import PushRejected

            # single connection: stream each range straight into native
            # push memory (readinto), no per-range Python buffers. Fetch
            # errors fall through to the abort path below; a rejected
            # push means the pipeline already failed — record nothing so
            # its own error wins at the consumer (same contract as the
            # pipe.push loop).
            try:
                reader.feed_into(pipe)
            except PushRejected:
                return
        else:
            for buf in reader:
                try:
                    pipe.push(buf)
                except _DMLCError:
                    return
        try:
            pipe.push_eof()
        except _DMLCError:
            return
    except BaseException as err:  # noqa: BLE001 — must reach the consumer
        error_holder.append(err)
        try:
            pipe.push_abort()
        except Exception:
            pass


class NativePipelineParser:
    """All-native ingest: cpp/pipeline.cc reader + parse workers.

    Drop-in for ``ThreadedParser(LibSVM/LibFM/CSVParser(...))`` when the
    native library is loaded: record-boundary chunking, threaded parse, and
    the ordered prefetch queue all run in C++ with no Python in the parse
    loop — Python only wraps the finished CSR arrays. Same exactly-once
    partition semantics as ``create_input_split``
    (input_split_base.cc:30-64).

    Two byte sources feed the same native machinery:

    - local files: the C++ reader thread (``ingest_open``);
    - any registered remote filesystem (``gs://``, ``s3://``, ``hdfs://``,
      ...): parallel range-GET readahead (io/readahead.py) on Python
      threads pushing the partition stream through ``ingest_push`` — the
      multi-connection generalization of the reference's native S3 reader
      (s3_filesys.cc:219-445).
    """

    def __init__(
        self,
        paths: List[str],
        sizes: List[int],
        data_format: str,
        part_index: int,
        num_parts: int,
        nthread: int = 2,
        args: Optional[Dict[str, str]] = None,
        remote_fs=None,
        remote_uris=None,
        shuffle_seed: int = -1,
    ):
        from dmlc_tpu import native

        self._fmt_name = data_format
        self._fmt = {
            "libsvm": native.INGEST_LIBSVM,
            "libfm": native.INGEST_LIBFM,
            "csv": native.INGEST_CSV,
            "recordio": native.INGEST_RECORDIO,
        }[data_format]
        self._open_args = (paths, sizes, part_index, num_parts, nthread)
        self._shuffle_seed = shuffle_seed
        self._epoch = 0  # advances the shuffle permutation per epoch
        self._remote_fs = remote_fs
        self._remote_uris = remote_uris
        self._csv_param = None
        if data_format == "csv":
            self._csv_param = CSVParserParam()
            self._csv_param.init(args or {}, allow_unknown=True)
        self._pipe = None
        self._feeder = None
        self._reader = None
        self._feed_error_holder: list = []
        self._bytes_read_done = 0
        self._open()

    def _open(self) -> None:
        import os
        import threading

        from dmlc_tpu import native

        paths, sizes, part, nparts, nthread = self._open_args
        if self._remote_fs is None:
            if self._shuffle_seed >= 0:
                # shuffle granularity is the chunk: 1 MB chunks give a
                # ~100MB file >=100 visit-order permutation slots (the
                # reference's InputSplitShuffle uses 16 sub-splits per
                # part) at a small throughput cost vs 8 MB chunks.
                # seed+epoch: each before_first() visits a FRESH
                # permutation, regenerated like the reference's per-epoch
                # reshuffle (indexed_recordio_split.cc BeforeFirst) yet
                # replayable from the base seed
                self._pipe = native.IngestPipeline(
                    paths, sizes, self._fmt, part, nparts,
                    nthread=nthread, chunk_bytes=1 << 20,
                    shuffle_seed=_mix_epoch_seed(
                        self._shuffle_seed, self._epoch),
                )
            else:
                self._pipe = native.IngestPipeline(
                    paths, sizes, self._fmt, part, nparts, nthread=nthread
                )
            return
        from dmlc_tpu.io.readahead import (
            DEFAULT_CONNECTIONS,
            DEFAULT_RANGE_BYTES,
            RemotePartitionReader,
        )

        reader = RemotePartitionReader(
            self._remote_fs,
            list(zip(self._remote_uris, sizes)),
            part,
            nparts,
            range_bytes=int(
                os.environ.get(
                    "DMLC_TPU_READAHEAD_MB", DEFAULT_RANGE_BYTES >> 20
                )
            ) << 20,
            connections=int(
                os.environ.get("DMLC_TPU_READAHEAD_CONNS", DEFAULT_CONNECTIONS)
            ),
            record_format=(
                "recordio" if self._fmt_name == "recordio" else "text"
            ),
        )
        self._pipe = native.IngestPipeline(
            None, None, self._fmt, 0, 1, nthread=nthread, push=True
        )
        # the feeder must hold no reference to this parser (or __del__
        # could never run and an abandoned parser would leak the thread
        # and the native pipeline); errors travel through a shared holder
        self._feed_error_holder: list = []
        self._reader = reader
        self._feeder = threading.Thread(
            target=_feed_pipeline,
            args=(self._pipe, reader, self._feed_error_holder),
            name="remote-ingest-feeder", daemon=True,
        )
        self._feeder.start()

    @property
    def _feed_error(self) -> Optional[BaseException]:
        return self._feed_error_holder[0] if self._feed_error_holder else None

    @property
    def bytes_read(self) -> int:
        return self._bytes_read_done + (
            self._pipe.bytes_read if self._pipe is not None else 0
        )

    def next_block(self) -> Optional[RowBlock]:
        from dmlc_tpu import native

        while True:
            try:
                parsed = self._pipe.next_block()
            except DMLCError:
                if self._feed_error is not None:
                    raise DMLCError(
                        f"remote ingest feeder failed: {self._feed_error}"
                    ) from self._feed_error
                raise
            if parsed is None:
                return None
            if self._fmt == native.INGEST_CSV:
                table = parsed["table"]
                if table.shape[0] == 0:
                    continue
                out = RowBlockContainer()
                _csv_table_to_block(
                    table,
                    self._csv_param.label_column,
                    self._csv_param.weight_column,
                    out,
                )
                return out.to_block()
            if len(parsed["labels"]) == 0:
                continue
            flags = parsed.get("flags", 0)
            has_value = self._fmt == native.INGEST_LIBFM or (
                flags & native.HAS_VALUE
            )
            return RowBlock(
                offset=parsed["offsets"],
                label=parsed["labels"],
                index=parsed["indices"],
                value=parsed["values"] if has_value else None,
                weight=parsed.get("weights"),
                qid=parsed.get("qids"),
                field=parsed.get("fields"),
            )

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            block = self.next_block()
            if block is None:
                return
            yield block

    # ---- native fixed-shape batch path (the TPU feed fast path) -------
    # Re-batching to [batch_size] rows and densify/COO-pad run in C++
    # (pipeline.cc StageBatch/FetchBatch*), so the per-batch Python work is
    # one ctypes call + device_put. libsvm/libfm only (csv densifies via
    # its table layout already).

    @property
    def supports_batch_fetch(self) -> bool:
        from dmlc_tpu import native

        return self._fmt in (
            native.INGEST_LIBSVM, native.INGEST_LIBFM,
            native.INGEST_RECORDIO,
        )

    def _stage(self, batch_size: int):
        try:
            return self._pipe.stage_batch(batch_size)
        except DMLCError:
            if self._feed_error is not None:
                raise DMLCError(
                    f"remote ingest feeder failed: {self._feed_error}"
                ) from self._feed_error
            raise

    def read_batch_dense(self, batch_size: int, num_features: int):
        """→ DenseBatch (x [batch,F] f32, labels, weights, num_rows) or None
        at end of stream. Short final batch is zero-padded (weight 0)."""
        from dmlc_tpu.device.csr import DenseBatch

        if self._stage(batch_size) is None:
            return None
        return DenseBatch(self._pipe.fetch_batch_dense(batch_size,
                                                       num_features))

    def read_batch_coo(
        self, batch_size: int, nnz_bucket=None, nnz_floor: int = 256
    ):
        """→ DeviceCSRBatch or None at end of stream. The nnz bucket is
        fixed when given, else device/csr.round_up_bucket's
        sixteenth-octave policy."""
        from dmlc_tpu.device.csr import DeviceCSRBatch, round_up_bucket

        staged = self._stage(batch_size)
        if staged is None:
            return None
        _rows, nnz = staged
        bucket = (
            nnz_bucket if nnz_bucket is not None
            else round_up_bucket(nnz, nnz_floor)
        )
        labels, weights, indices, values, row_ids, offsets, rows = (
            self._pipe.fetch_batch_coo(batch_size, bucket)
        )
        return DeviceCSRBatch(
            labels=labels, weights=weights, indices=indices, values=values,
            row_ids=row_ids, offsets=offsets, num_rows=rows, num_nonzero=nnz,
        )

    def read_batch_coo_sharded(
        self,
        batch_size: int,
        num_shards: int,
        nnz_bucket=None,
        nnz_floor: int = 256,
    ):
        """→ ShardedCSRBatch (per-shard entry sections, local row ids) or
        None at end of stream. Bucket = round_up_bucket (sixteenth-octave
        steps) over the max shard nnz unless fixed."""
        from dmlc_tpu.device.csr import ShardedCSRBatch, round_up_bucket

        staged = self._stage(batch_size)
        if staged is None:
            return None
        _rows, nnz = staged
        bucket = (
            nnz_bucket if nnz_bucket is not None
            else round_up_bucket(
                self._pipe.staged_max_shard_nnz(batch_size, num_shards),
                nnz_floor,
            )
        )
        labels, weights, indices, values, row_ids, offsets, rows = (
            self._pipe.fetch_batch_coo_sharded(batch_size, num_shards, bucket)
        )
        return ShardedCSRBatch(
            labels=labels, weights=weights, indices=indices, values=values,
            row_ids=row_ids, offsets=offsets, num_rows=rows, num_nonzero=nnz,
            num_shards=num_shards, nnz_bucket=bucket,
        )

    def stats(self) -> Optional[dict]:
        """Per-stage pipeline counters (ns), or None when closed."""
        return self._pipe.stats() if self._pipe is not None else None

    def _teardown(self) -> None:
        if self._pipe is None:
            return
        if self._feeder is not None:
            # abort first: a feeder blocked in push() wakes with an error,
            # and cancelled fetch retries stop at their next checkpoint —
            # both before the native handle is freed
            self._reader.cancel()
            self._pipe.push_abort()
            self._feeder.join()
            self._feeder = None
            self._reader = None
        self._bytes_read_done += self._pipe.bytes_read
        self._pipe.close()
        self._pipe = None

    def before_first(self) -> None:
        self._teardown()
        self._epoch += 1
        self._open()

    def close(self) -> None:
        self._teardown()

    def __del__(self):
        # ordering matters: the feeder must be joined before the native
        # handle is freed (a feeder blocked in push() touches it)
        try:
            self._teardown()
        except Exception:
            pass


def _try_native_cached(
    spec: URISpec,
    data_format: str,
    part_index: int,
    num_parts: int,
    nthread: int,
) -> Optional["NativePipelineParser"]:
    """``#cachefile`` on a local libsvm uri, the TPU-native way.

    DiskRowIter's build-then-stream contract
    (/root/reference/src/data/disk_row_iter.h:95-141: BuildCache spills
    parsed pages, TryLoadCache streams them back per epoch) with the
    cache in the binary row-group format (data/rowrec.py): the first
    parser instance parses its text part through the native pipeline and
    spills row groups; every later epoch — and every later parser
    instance over the same uri — ingests the cache with the scan-free
    recordio path (~5-9x the text parse on this host class). The cache
    carries a sidecar meta with the source signature so a changed source
    rebuilds instead of silently serving stale rows (the reference
    reuses blindly; cheap to do better). Scope: libsvm only — libfm
    carries fields the row-group layout omits, csv has a table layout,
    recordio is already binary.
    """
    if data_format != "libsvm":
        return None
    files = _native_local_files(spec)
    if files is None:
        return None
    import json as _json

    # a DISTINCT path from the user's #cachefile name: the Python stack's
    # CachedInputSplit/DiskRowIter use that exact path in incompatible
    # formats and reuse whatever exists — a later fallback run (native
    # lib unavailable) must find ITS cache absent, not misparse
    # row-group binary as framed text chunks
    cache = spec.cache_file + ".rowrec"
    meta_path = cache + ".meta"
    import uuid

    # unique per BUILDER (pid alone shares a name across threads of one
    # process): concurrent builders must not interleave writes into one
    # shared tmp; last atomic replace wins
    tmp_tag = ".tmp.%d.%s" % (os.getpid(), uuid.uuid4().hex[:8])
    try:
        sig = {
            "format": "rowrec-v1",
            "src_bytes": int(sum(info.size for info in files)),
            # ns-resolution mtime: a same-length in-place rewrite within
            # the same second must still invalidate
            "src_mtime_ns": max(
                os.stat(info.path.name).st_mtime_ns for info in files
            ),
            "part": part_index,
            "num_parts": num_parts,
        }
        valid = False
        if os.path.exists(cache) and os.path.exists(meta_path):
            try:
                with open(meta_path) as fh:
                    valid = _json.load(fh) == sig
            except (OSError, ValueError):
                valid = False
        if not valid:
            from dmlc_tpu.data.rowrec import RowGroupWriter
            from dmlc_tpu.io.filesystem import create_stream

            base = NativePipelineParser(
                [info.path.name for info in files],
                [info.size for info in files],
                "libsvm", part_index, num_parts,
                nthread=nthread, args=spec.args,
            )
            try:
                with create_stream(cache + tmp_tag, "w") as out:
                    writer = RowGroupWriter(out, rows_per_group=4096)
                    for block in base:
                        writer.write_block(block)
            finally:
                base.close()
            os.replace(cache + tmp_tag, cache)
            with open(meta_path + tmp_tag, "w") as fh:
                _json.dump(sig, fh)
            os.replace(meta_path + tmp_tag, meta_path)
        # the cache holds exactly THIS part's rows: serve it whole
        # (shuffle_chunks applies to the cached epochs as well — the
        # cache is one local file, the mmap reader's best case)
        return NativePipelineParser(
            [cache], [os.path.getsize(cache)], "recordio", 0, 1,
            nthread=nthread, args=spec.args,
            shuffle_seed=_shuffle_seed_arg(spec),
        )
    except Exception:
        for tmp in (cache + tmp_tag, meta_path + tmp_tag):
            try:
                os.remove(tmp)
            except OSError:
                pass
        return None


def _native_local_files(spec: URISpec):
    """Listable, all-local split files when the native lib is usable, else
    None — the shared precondition of every native routing decision."""
    from dmlc_tpu import native

    if not native.available():
        return None
    from dmlc_tpu.io.filesystem import list_split_files

    try:
        files = list_split_files(spec.uri)
    except Exception:
        return None
    if not files or not all(
        info.path.protocol in ("file://", "") for info in files
    ):
        return None
    return files


def _mix_epoch_seed(seed: int, epoch: int) -> int:
    """(base seed, epoch) → decorrelated per-epoch seed (splitmix64
    finalizer, masked non-negative int64). Plain ``seed + epoch`` would
    make adjacent base seeds share permutation sequences offset by one
    epoch — correlated "independent" runs."""
    mask = (1 << 64) - 1
    x = (seed * 0x9E3779B97F4A7C15 + epoch + 1) & mask
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & mask
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & mask
    x ^= x >> 31
    return x & ((1 << 62) - 1)


def _shuffle_seed_arg(spec: URISpec) -> int:
    """``?shuffle_chunks=SEED`` URI arg → seed int, or -1 when absent.
    The native mmap reader visits the part's chunks in seeded random
    order (input_split_shuffle.h semantics at chunk granularity); the
    Python stack maps the same request onto InputSplitShuffle. Both
    backends regenerate the permutation each epoch (``before_first``
    advances it, like the reference's per-epoch reshuffle), and the whole
    epoch sequence is replayable from the one base seed: a fresh parser
    over the same uri repeats epoch 0, its first ``before_first`` repeats
    epoch 1, and so on."""
    raw = spec.args.get("shuffle_chunks")
    if raw is None:
        return -1
    try:
        seed = int(raw)
    except ValueError:
        raise DMLCError(
            f"shuffle_chunks must be an integer seed, got {raw!r}"
        ) from None
    check(seed >= 0, "shuffle_chunks seed must be >= 0, got %d", seed)
    return seed


def _try_native_pipeline(
    spec: URISpec,
    data_format: str,
    part_index: int,
    num_parts: int,
    nthread: int,
) -> Optional[NativePipelineParser]:
    """Route to the all-native pipeline when the dataset allows it.

    Local files take the C++ reader; any single remote filesystem takes
    the parallel-readahead push path. Mixed/unlistable datasets fall back
    to the Python InputSplit stack.
    """
    if data_format not in ("libsvm", "libfm", "csv", "recordio"):
        return None
    if spec.cache_file:
        return _try_native_cached(
            spec, data_format, part_index, num_parts, nthread
        )
    from dmlc_tpu import native

    if not native.available():
        return None
    from dmlc_tpu.io.filesystem import get_filesystem, list_split_files

    try:
        files = list_split_files(spec.uri)
    except Exception:
        return None
    if not files:
        return None
    local = all(info.path.protocol in ("file://", "") for info in files)
    sizes = [info.size for info in files]
    shuffle_seed = _shuffle_seed_arg(spec)
    try:
        if local:
            return NativePipelineParser(
                [info.path.name for info in files], sizes,
                data_format, part_index, num_parts,
                nthread=nthread, args=spec.args,
                shuffle_seed=shuffle_seed,
            )
        if shuffle_seed >= 0:
            return None  # remote push path streams sequentially; the
            # Python stack's InputSplitShuffle takes the request
        # one remote filesystem for the whole dataset
        keys = {(info.path.protocol, info.path.host) for info in files}
        if len(keys) != 1 or any(s <= 0 for s in sizes):
            return None
        fs = get_filesystem(files[0].path)
        return NativePipelineParser(
            [], sizes, data_format, part_index, num_parts,
            nthread=nthread, args=spec.args,
            remote_fs=fs, remote_uris=[info.path for info in files],
        )
    except Exception:
        return None


class ThreadedParser:
    """Background-thread parse prefetch, depth 8 (parser.h:70-126)."""

    def __init__(self, base: Parser, max_capacity: int = 8):
        self._base = base
        self._wait_ns = 0  # this pass's waits of next_block on the queue
        self._iter = ThreadedIter(
            self._produce, max_capacity=max_capacity, name="threaded-parser"
        )

    def _produce(self) -> Iterator[RowBlock]:
        while True:
            block = self._base.next_block()
            if block is None:
                return
            yield block

    @property
    def bytes_read(self) -> int:
        return self._base.bytes_read

    def next_block(self) -> Optional[RowBlock]:
        t0 = time.monotonic_ns()
        block = self._iter.next()
        self._wait_ns += time.monotonic_ns() - t0
        return block

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            block = self.next_block()
            if block is None:
                return
            yield block

    def stats(self) -> dict:
        """The base parser's counters of this pass, where it keeps any,
        and ``consumer_wait_ns``: how long ``next_block``'s caller waited
        for the prefetch thread (the native pipeline's name for the same
        wait). They restart with every pass."""
        base_stats = getattr(self._base, "stats", None)
        out = dict(base_stats() or {}) if callable(base_stats) else {}
        out["consumer_wait_ns"] = int(self._wait_ns)
        return out

    def before_first(self) -> None:
        self._iter.close()
        self._base.before_first()
        self._wait_ns = 0
        self._iter.before_first()

    # ---- job-snapshot state: the base's read plan, where it has one ----
    def snapshot_state(self) -> Optional[dict]:
        snap = getattr(self._base, "snapshot_state", None)
        return snap() if callable(snap) else None

    def restore_state(self, st: dict) -> None:
        # the prefetch thread is reading the plan the base was built with
        self._iter.close()
        self._base.restore_state(st)
        self._wait_ns = 0
        self._iter.before_first()

    def close(self) -> None:
        self._iter.close()
        self._base.close()


# ---------------------------------------------------------------------------
# Registry + factory (src/data.cc:62-85,150-158; data.h:317-350)
# ---------------------------------------------------------------------------

PARSER_REGISTRY: Registry = Registry.get("parser")


def register_parser(name: str, factory=None):
    """DMLC_REGISTER_DATA_PARSER equivalent; factory(source, args, nthread)."""
    return PARSER_REGISTRY.register(name, factory) if factory else PARSER_REGISTRY.register(name)


def _make_recordio_parser(source, args, nthread):
    from dmlc_tpu.data.rowrec import RecordIORowParser

    return RecordIORowParser(source, args, nthread)


register_parser("libsvm", lambda source, args, nthread: LibSVMParser(source, nthread))
register_parser("libfm", lambda source, args, nthread: LibFMParser(source, nthread))
register_parser("csv", lambda source, args, nthread: CSVParser(source, args, nthread))
register_parser("recordio", _make_recordio_parser)

# InputSplit record type per format ("text" unless registered here): the
# recordio parser consumes whole framed records, not lines
_SPLIT_TYPE = {"recordio": "recordio"}


def create_parser(
    uri: str,
    part_index: int = 0,
    num_parts: int = 1,
    data_format: str = "auto",
    nthread: Optional[int] = None,
    threaded: bool = True,
) -> Parser:
    """Parser<I>::Create (src/data.cc:62-85,132-138).

    "auto" resolves through the ``format=`` URI arg, defaulting to libsvm.
    ``nthread=None`` resolves through the ``DMLC_TPU_NTHREAD`` knob
    (params/knobs.py; default 2). Threaded text parsers take the
    cross-chunk pipeline (data/pipeline.PipelinedParser: N parse workers
    + bounded ordered queue) when the native C++ pipeline declines;
    non-chunk parsers (registry plugins) keep the ThreadedParser block
    prefetch.
    """
    from dmlc_tpu.params.knobs import default_nthread

    nthread = default_nthread(nthread)
    spec = URISpec(uri, part_index, num_parts)
    if data_format == "auto":
        data_format = spec.args.get("format")
        if data_format is None:
            from dmlc_tpu.io.shard import is_shard_uri

            data_format = "shard" if is_shard_uri(spec.uri) else "libsvm"
    if data_format == "shard":
        # baked columnar shards (io/shard.py): pre-tokenized, so there is
        # no parse stage to fan out — the ShardParser decodes windows as
        # frombuffer slices and owns its audit/flow wiring (including the
        # shard signature, which it salts per epoch when shuffle is
        # armed), so DMLC_TPU_AUDIT gets native digest points here and
        # never forces a text re-parse of baked input
        from dmlc_tpu.io.shard import ShardParser

        base = ShardParser(
            spec.uri, part_index, num_parts, args=spec.args, nthread=nthread
        )
        return ThreadedParser(base) if threaded else base
    entry = PARSER_REGISTRY.find(data_format)
    if entry is None:
        raise DMLCError(
            f"unknown data format {data_format!r}; known: "
            f"{PARSER_REGISTRY.list_all_names()}"
        )
    # stamp the determinism auditor's shard signature so digest chains
    # only compare across runs/ranks reading the same (uri, part) slice
    # (obs/audit.py; no-op child when DMLC_TPU_AUDIT is off)
    from dmlc_tpu.obs import audit

    audit.auditor().set_shard(uri, part_index, num_parts)
    if (threaded and parse_backend() in ("auto", "native")
            and parse_procs() == 0 and not audit.auditor().enabled):
        # Built-in formats over local files take the all-native pipeline
        # (reader + parse + prefetch in C++); everything else composes the
        # Python InputSplit stack with native chunk parses inside. A
        # vector/scalar backend override or a process-pool request
        # (DMLC_TPU_PARSE_PROCS>0) keeps the Python PipelinedParser so the
        # selected engine actually runs. An enabled determinism auditor
        # does too: the all-native pipeline has no io_read/parse digest
        # points, and an armed audit plane that silently observes nothing
        # is worse than the Python pipeline's (native-chunk-parse) cost.
        native_parser = _try_native_pipeline(
            spec, data_format, part_index, num_parts, nthread
        )
        if native_parser is not None:
            return native_parser
    shuffle_seed = _shuffle_seed_arg(spec)
    source = create_input_split(
        uri, part_index, num_parts, _SPLIT_TYPE.get(data_format, "text"),
        # the Python stack answers shuffle_chunks with InputSplitShuffle
        # (sub-split visit order — the same reference semantic the native
        # mmap reader implements at chunk granularity)
        num_shuffle_parts=16 if shuffle_seed >= 0 else 0,
        seed=max(shuffle_seed, 0),
    )
    base = entry(source, spec.args, nthread)
    if not threaded:
        return base
    if isinstance(base, Parser):
        # chunk-level fan-out + ordered prefetch in one stage; the base's
        # intra-chunk pool stays idle (ThreadPoolExecutor spawns lazily)
        from dmlc_tpu.data.pipeline import PipelinedParser

        return PipelinedParser(base, nthread=nthread)
    return ThreadedParser(base)

"""Pre-tokenized columnar shards: bake once, ingest at RecordIO speed.

Text ingest pays the tokenize/strtonum tax every epoch: parse_only peaks
near ~1 GB/s while the RecordIO framed path ingests at ~2.4 GB/s
(BENCH_r05). A *shard* is the dataset with that tax paid once, offline:
the parser's :class:`~dmlc_tpu.data.row_block.RowBlockContainer` columnar
arrays written to disk as typed segments, so epoch-1+ reads are
``np.frombuffer`` slices (zero-copy off an mmap) instead of text parses.
This evolves the reference's ``indexed_recordio_split`` idea (random
access via a record index) from framed-bytes to columnar-typed storage.

File layout (all little-endian)::

    MAGIC "DTSHARD1"                                      8 bytes
    header <HHI>: version, reserved, rows_per_window      8 bytes
    window 0                                              |
      <BBHIQ>: tag 'W', flags, reserved, nrows, nnz       | data
      label    f32[nrows]                                 |
      weight   f32[nrows]      (flags & HAS_WEIGHT)       |
      qid      i64[nrows]      (flags & HAS_QID)          |
      row_nnz  u32[nrows]                                 |
      index    u32[nnz]                                   |
      value    f32[nnz]        (flags & HAS_VALUE)        |
      field    u32[nnz]        (flags & HAS_FIELD)        |
    window 1 ... window N-1                               |
    footer                                                |
      index    <QQQI>[N]: offset, nbytes, nnz, nrows      | 28 B each
      meta     <QQIHH>: rows, nnz, nwindows, ver, flags   | 24 B
    tail <IQ>: crc32(footer), footer_len                  12 bytes
    MAGIC "DTSHARD1"                                      8 bytes

The footer is the random-access index: window ``i`` lives at
``offset[i]`` and is decodable in isolation, which is what the windowed
global shuffle permutes and what the determinism auditor digests
(io_read = raw window bytes, parse = decoded block — the same two
chain stages the text pipeline records). The crc32 + trailing magic
guard torn writes: a truncated or overwritten file fails closed with a
:class:`DMLCError` before any row is emitted, and the ``shard.read``
faultpoint injects exactly that class of fault for the chaos suite.

Shuffle (``DMLC_TPU_SHUFFLE`` seed, ``DMLC_TPU_SHUFFLE_WINDOW`` unit)
permutes the *global* window table — all windows of all files, before
partitioning — with a splitmix64-mixed per-epoch seed, then hands rank
``k`` of ``n`` its contiguous slice of the permuted order. The order is
a pure function of (seed, epoch), never of the world size, so
``reset_partition`` re-sharding and dispatcher redelivery replay
bit-identically: the union of every rank's slice is the one global
permutation. See docs/pipeline.md "Baked shards & global shuffle".
"""

from __future__ import annotations

import mmap
import os
import struct
import time
import zlib
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from dmlc_tpu import obs
from dmlc_tpu.data.row_block import (
    INDEX_DTYPE,
    REAL_DTYPE,
    RowBlock,
    RowBlockContainer,
)
from dmlc_tpu.utils.logging import DMLCError, check

MAGIC = b"DTSHARD1"
SHARD_FORMAT_VERSION = 1
SHARD_SUFFIX = ".dtsh"
DEFAULT_ROWS_PER_WINDOW = 4096

_HEADER = struct.Struct("<HHI")  # version, reserved, rows_per_window
_WIN = struct.Struct("<BBHIQ")  # tag, flags, reserved, nrows, nnz
_IDX = struct.Struct("<QQQI")  # offset, nbytes, nnz, nrows
_META = struct.Struct("<QQIHH")  # rows, nnz, nwindows, version, flags
_TAIL = struct.Struct("<IQ")  # crc32(footer), footer_len

_WIN_TAG = 0x57  # 'W'
HAS_WEIGHT = 1
HAS_QID = 2
HAS_VALUE = 4
HAS_FIELD = 8

# numpy view of the footer index: one structured record per window
_IDX_DTYPE = np.dtype(
    [("offset", "<u8"), ("nbytes", "<u8"), ("nnz", "<u8"), ("nrows", "<u4")]
)


def _local_path(uri: str) -> str:
    """Strip the ``file://`` scheme; shards are a local-filesystem format
    (the bake CLI writes them next to the corpus; remote serving goes
    through the data service, whose workers read locally)."""
    if uri.startswith("file://"):
        return uri[len("file://"):]
    return uri


def is_shard_uri(uri: str) -> bool:
    """Whether ``uri`` names baked shard data by suffix convention."""
    return _local_path(str(uri)).split("?", 1)[0].endswith(SHARD_SUFFIX)


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------


class ShardWriter:
    """Stream RowBlocks into one ``.dtsh`` shard file.

    Rows are re-windowed to ``rows_per_window`` regardless of how the
    incoming blocks were chunked (the window is the shuffle/audit/index
    granule, so its size must be a bake parameter, not an accident of
    parser chunking). ``close`` seals the footer; an unclosed or
    interrupted write leaves a file with no valid tail, which readers
    reject — torn bakes fail closed.
    """

    def __init__(self, path: str, rows_per_window: int = DEFAULT_ROWS_PER_WINDOW):
        self.path = _local_path(path)
        self.rows_per_window = max(1, int(rows_per_window))
        self._file = open(self.path, "wb")
        self._file.write(MAGIC)
        self._file.write(_HEADER.pack(SHARD_FORMAT_VERSION, 0, self.rows_per_window))
        self._index: List[Tuple[int, int, int, int]] = []
        self._pending = RowBlockContainer()
        self._union_flags = 0
        self.rows_written = 0
        self.nnz_written = 0
        self._closed = False

    def write_block(self, block) -> None:
        """Append a RowBlock (or anything with ``to_block``)."""
        if hasattr(block, "to_block") and not isinstance(block, RowBlock):
            block = block.to_block()
        self._pending.push_block(block)
        while self._pending.size >= self.rows_per_window:
            whole = self._pending.to_block()
            n = len(whole)
            w = self.rows_per_window
            full = (n // w) * w
            for lo in range(0, full, w):
                self._emit_window(whole.slice(lo, lo + w))
            self._pending = RowBlockContainer()
            if full < n:
                self._pending.push_block(whole.slice(full, n))

    def _emit_window(self, block: RowBlock) -> None:
        nrows = len(block)
        nnz = block.num_nonzero
        flags = 0
        segs: List[np.ndarray] = [np.ascontiguousarray(block.label, dtype=REAL_DTYPE)]
        if block.weight is not None:
            flags |= HAS_WEIGHT
            segs.append(np.ascontiguousarray(block.weight, dtype=REAL_DTYPE))
        if block.qid is not None:
            flags |= HAS_QID
            segs.append(np.ascontiguousarray(block.qid, dtype=np.int64))
        segs.append(np.ascontiguousarray(np.diff(block.offset), dtype=np.uint32))
        segs.append(np.ascontiguousarray(block.index, dtype=np.uint32))
        if block.value is not None:
            flags |= HAS_VALUE
            segs.append(np.ascontiguousarray(block.value, dtype=REAL_DTYPE))
        if block.field is not None:
            flags |= HAS_FIELD
            segs.append(np.ascontiguousarray(block.field, dtype=np.uint32))
        offset = self._file.tell()
        self._file.write(_WIN.pack(_WIN_TAG, flags, 0, nrows, nnz))
        for seg in segs:
            self._file.write(seg.tobytes())
        nbytes = self._file.tell() - offset
        self._index.append((offset, nbytes, nnz, nrows))
        self._union_flags |= flags
        self.rows_written += nrows
        self.nnz_written += nnz

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._pending.size:
            self._emit_window(self._pending.to_block())
            self._pending = RowBlockContainer()
        footer = b"".join(_IDX.pack(*entry) for entry in self._index)
        footer += _META.pack(
            self.rows_written,
            self.nnz_written,
            len(self._index),
            SHARD_FORMAT_VERSION,
            self._union_flags,
        )
        self._file.write(footer)
        self._file.write(_TAIL.pack(zlib.crc32(footer) & 0xFFFFFFFF, len(footer)))
        self._file.write(MAGIC)
        self._file.close()

    def __enter__(self) -> "ShardWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Reader
# ---------------------------------------------------------------------------


class ShardReader:
    """Random-access window reads over one sealed shard file.

    ``use_mmap`` (default: the ``DMLC_TPU_SHARD_MMAP`` knob) maps the
    file once and decodes windows as zero-copy ``np.frombuffer`` views;
    the fallback path seeks and reads per window. Both verify the
    leading magic and the crc32-guarded footer before the first row is
    served, and both cross-check every window header against the footer
    index — a torn footer, truncated segment, or stale index raises
    :class:`DMLCError` rather than yielding silently wrong rows.
    """

    def __init__(self, path: str, use_mmap: Optional[bool] = None):
        from dmlc_tpu.params.knobs import shard_mmap

        self.path = _local_path(path)
        self._mmap_wanted = shard_mmap() if use_mmap is None else bool(use_mmap)
        self._file = open(self.path, "rb")
        self._mm: Optional[mmap.mmap] = None
        self._load_footer()
        if self._mmap_wanted:
            try:
                self._mm = mmap.mmap(self._file.fileno(), 0, access=mmap.ACCESS_READ)
            except (ValueError, OSError):  # empty or unmappable: seek path
                self._mm = None

    # ---- footer ---------------------------------------------------------
    def _fail(self, why: str) -> None:
        raise DMLCError("bad shard %s: %s" % (self.path, why))

    def _load_footer(self) -> None:
        from dmlc_tpu.resilience import faultpoint

        # chaos-suite hook: an injected fault here behaves like a transient
        # read error (OSError → retried per RetryPolicy); real corruption
        # below raises DMLCError, which is fatal by classification
        faultpoint("shard.read")
        size = os.fstat(self._file.fileno()).st_size
        head_len = len(MAGIC) + _HEADER.size
        tail_len = _TAIL.size + len(MAGIC)
        if size < head_len + tail_len:
            self._fail("file too short (%d bytes)" % size)
        self._file.seek(0)
        if self._file.read(len(MAGIC)) != MAGIC:
            self._fail("leading magic mismatch")
        version, _, self.rows_per_window = _HEADER.unpack(
            self._file.read(_HEADER.size))
        if version != SHARD_FORMAT_VERSION:
            self._fail("unsupported version %d" % version)
        self._file.seek(size - tail_len)
        crc, footer_len = _TAIL.unpack(self._file.read(_TAIL.size))
        if self._file.read(len(MAGIC)) != MAGIC:
            self._fail("trailing magic mismatch (torn or unsealed write)")
        if footer_len > size - head_len - tail_len:
            self._fail("footer length %d exceeds file" % footer_len)
        self._file.seek(size - tail_len - footer_len)
        footer = self._file.read(footer_len)
        if (zlib.crc32(footer) & 0xFFFFFFFF) != crc:
            self._fail("footer crc mismatch (torn write)")
        if (footer_len - _META.size) % _IDX.size:
            self._fail("footer size %d not index-aligned" % footer_len)
        (self.num_rows, self.num_nonzero, nwin, meta_ver, self.union_flags
         ) = _META.unpack(footer[footer_len - _META.size:])
        if meta_ver != version:
            self._fail("meta/header version mismatch")
        if nwin != (footer_len - _META.size) // _IDX.size:
            self._fail("window count disagrees with index size")
        self._index = np.frombuffer(footer, dtype=_IDX_DTYPE, count=nwin)
        self.footer_crc = int(crc)
        data_end = size - tail_len - footer_len
        if nwin:
            last = self._index[nwin - 1]
            if int(last["offset"]) + int(last["nbytes"]) != data_end:
                self._fail("index does not cover the data section")

    @property
    def num_windows(self) -> int:
        return len(self._index)

    def window_rows(self, i: int) -> int:
        return int(self._index[i]["nrows"])

    def window_nbytes(self, i: int) -> int:
        return int(self._index[i]["nbytes"])

    # ---- window reads ---------------------------------------------------
    def window_bytes(self, i: int):
        """Raw encoded bytes of window ``i`` — a zero-copy memoryview in
        mmap mode. This is what the audit plane's io_read digest covers."""
        from dmlc_tpu.resilience import faultpoint

        faultpoint("shard.read")
        ent = self._index[i]
        off, n = int(ent["offset"]), int(ent["nbytes"])
        if self._mm is not None:
            return memoryview(self._mm)[off:off + n]
        self._file.seek(off)
        buf = self._file.read(n)
        if len(buf) != n:
            self._fail("truncated window %d (%d of %d bytes)" % (i, len(buf), n))
        return buf

    def read_window(self, i: int, raw=None) -> RowBlock:
        """Decode window ``i`` into a RowBlock. Pass ``raw`` (from
        :meth:`window_bytes`) to decode an already-fetched buffer."""
        ent = self._index[i]
        if raw is None:
            raw = self.window_bytes(i)
        tag, flags, _, nrows, nnz = _WIN.unpack_from(raw, 0)
        if tag != _WIN_TAG:
            self._fail("window %d tag %#x (index/data skew)" % (i, tag))
        if nrows != int(ent["nrows"]) or nnz != int(ent["nnz"]):
            self._fail("window %d header disagrees with footer index" % i)
        pos = _WIN.size
        need = _WIN.size + 8 * nrows + 4 * nnz  # label + row_nnz + index
        if flags & HAS_WEIGHT:
            need += 4 * nrows
        if flags & HAS_QID:
            need += 8 * nrows
        if flags & HAS_VALUE:
            need += 4 * nnz
        if flags & HAS_FIELD:
            need += 4 * nnz
        if len(raw) != need:
            self._fail("window %d is %d bytes, segments need %d (truncated)"
                       % (i, len(raw), need))

        def seg(dtype, count):
            nonlocal pos
            a = np.frombuffer(raw, dtype=dtype, count=count, offset=pos)
            pos += a.nbytes
            return a

        label = seg(REAL_DTYPE, nrows)
        weight = seg(REAL_DTYPE, nrows) if flags & HAS_WEIGHT else None
        qid = seg(np.int64, nrows) if flags & HAS_QID else None
        row_nnz = seg(np.uint32, nrows)
        index = seg(INDEX_DTYPE, nnz)
        value = seg(REAL_DTYPE, nnz) if flags & HAS_VALUE else None
        field = seg(np.uint32, nnz) if flags & HAS_FIELD else None
        offset = np.zeros(nrows + 1, dtype=np.int64)
        np.cumsum(row_nnz, out=offset[1:])
        if int(offset[-1]) != nnz:
            self._fail("window %d row_nnz sums to %d, header says %d"
                       % (i, int(offset[-1]), nnz))
        return RowBlock(offset=offset, label=label, index=index,
                        value=value, weight=weight, qid=qid, field=field)

    def close(self) -> None:
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                pass  # exported zero-copy views keep the map alive until GC
            self._mm = None
        try:
            self._file.close()
        except Exception:
            pass

    def __enter__(self) -> "ShardReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Parser: shard files → RowBlocks with windowed global shuffle
# ---------------------------------------------------------------------------


def _epoch_mixed_seed(seed: int, epoch: int) -> int:
    # splitmix64 decorrelation, shared with the text path's per-epoch
    # chunk shuffle so both stacks draw epochs the same way
    from dmlc_tpu.data.parsers import _mix_epoch_seed

    return _mix_epoch_seed(seed, epoch)


class ShardParser:
    """Parser-shaped reader over baked shards (one file, a directory, or
    a ``part-*`` family — whatever :func:`list_split_files` resolves).

    The unit of delivery is the baked window: ``next_block`` returns one
    window per call, decoded zero-copy in mmap mode, with the same
    io_read/parse span + flow-id + audit-digest wiring the text
    pipeline's :class:`~dmlc_tpu.data.pipeline.PipelinedParser` gives
    chunks, so everything downstream (DeviceFeed, BlockService, the
    audit plane) is format-blind.

    Shuffle: a seed ≥ 0 (``shuffle_chunks`` URI arg, else the
    ``DMLC_TPU_SHUFFLE`` knob) arms a seeded permutation of the global
    window table in units of ``DMLC_TPU_SHUFFLE_WINDOW`` consecutive
    windows. The permutation is a pure function of (seed, epoch):
    construction is epoch 0, each ``before_first`` advances one epoch,
    and ``reset_partition`` re-slices the *current* epoch's order — so
    any (rank, world) decomposition of the same seed reads the same
    global sequence, which is what makes dispatcher redelivery and
    mid-epoch resume bit-reproducible with shuffle armed.

    Audit: with shuffle armed the auditor's shard signature is salted
    with the epoch-mixed seed. Delivery order then legitimately differs
    across epochs, and the signature change scopes chain comparison to
    one epoch (cross-rank and restart-replay compares still line up —
    same seed + epoch ⇒ same salt) instead of tripping the epoch-roll
    self-check.
    """

    def __init__(
        self,
        uri: str,
        part_index: int = 0,
        num_parts: int = 1,
        args: Optional[Dict] = None,
        nthread: Optional[int] = None,
        seed: Optional[int] = None,
        shuffle_window: Optional[int] = None,
        use_mmap: Optional[bool] = None,
    ):
        from dmlc_tpu.io.filesystem import list_split_files
        from dmlc_tpu.params import knobs

        del nthread  # decode is frombuffer slices; prefetch happens above us
        self.uri = str(uri)
        args = dict(args or {})
        if seed is None:
            raw = args.get("shuffle_chunks")
            seed = int(raw) if raw is not None else knobs.shuffle_seed()
        self._seed = int(seed)
        self._unit = max(
            1,
            int(shuffle_window) if shuffle_window is not None
            else knobs.shuffle_window(),
        )
        infos = list_split_files(self.uri)
        check(bool(infos), "shard uri %s matches no files", self.uri)
        for info in infos:
            check(info.path.protocol in ("file://", ""),
                  "shard reader requires local files, got %s",
                  info.path.protocol)
        paths = sorted(info.path.name for info in infos)
        self._readers = [ShardReader(p, use_mmap=use_mmap) for p in paths]
        # global window table, in (file, window) order: the domain the
        # shuffle permutes and the partitioner slices
        self._table: List[Tuple[int, int]] = [
            (f, w)
            for f, rd in enumerate(self._readers)
            for w in range(rd.num_windows)
        ]
        self.num_rows = sum(rd.num_rows for rd in self._readers)
        self._part = int(part_index)
        self._nparts = max(1, int(num_parts))
        self._epoch = 0
        self._seq = 0
        self._epoch_base = 0
        from dmlc_tpu.obs import audit

        self._audit = audit.auditor()
        self.bytes_read = 0
        # this pass's thread CPU time in window reads and in decodes, under
        # the native pipeline's names (stats(); restart with every pass)
        self._reader_cpu_ns = 0
        self._parse_cpu_ns = 0
        self._order: np.ndarray = np.empty(0, dtype=np.int64)
        self._pos = 0
        self._closed = False
        self._reorder()

    # ---- order ----------------------------------------------------------
    def _global_order(self) -> np.ndarray:
        nwin = len(self._table)
        if self._seed < 0 or nwin == 0:
            return np.arange(nwin, dtype=np.int64)
        mixed = _epoch_mixed_seed(self._seed, self._epoch)
        rng = np.random.Generator(np.random.PCG64(mixed))
        nunits = -(-nwin // self._unit)
        perm = rng.permutation(nunits)
        starts = perm * self._unit
        order = np.concatenate([
            np.arange(s, min(s + self._unit, nwin), dtype=np.int64)
            for s in starts
        ]) if nunits else np.empty(0, dtype=np.int64)
        return order

    def _reorder(self) -> None:
        order = self._global_order()
        lo = self._part * len(order) // self._nparts
        hi = (self._part + 1) * len(order) // self._nparts
        self._order = order[lo:hi]
        self._pos = 0
        self._stamp_audit()

    def _stamp_audit(self) -> None:
        if not self._audit.enabled:
            return
        sig_uri = self.uri
        if self._seed >= 0:
            # per-epoch salt: a reshuffled epoch is a different read plan,
            # so it gets its own chain domain (see class docstring)
            sig_uri = "%s#shuffle-%x" % (
                self.uri, _epoch_mixed_seed(self._seed, self._epoch))
        self._audit.set_shard(sig_uri, self._part, self._nparts)

    # ---- Parser surface -------------------------------------------------
    def next_block(self) -> Optional[RowBlock]:
        from dmlc_tpu.resilience import faultpoint

        check(not self._closed, "shard parser is closed")
        if self._pos >= len(self._order):
            return None
        fidx, widx = self._table[int(self._order[self._pos])]
        reader = self._readers[fidx]
        seq = self._seq
        fid = obs.new_flow()
        cpu0 = time.thread_time_ns()
        with obs.span("io_read", chunk=seq, flow=fid):
            raw = reader.window_bytes(widx)
            obs.flow_start(fid, "chunk")
        cpu1 = time.thread_time_ns()
        self._reader_cpu_ns += cpu1 - cpu0
        if self._audit.enabled:
            self._audit.note_chunk(seq - self._epoch_base, raw)
            cpu1 = time.thread_time_ns()
        with obs.span("parse", chunk=seq, flow=fid):
            obs.flow_step(fid, "chunk")
            faultpoint("shard.read")
            block = reader.read_window(widx, raw)
        self._parse_cpu_ns += time.thread_time_ns() - cpu1
        if self._audit.enabled:
            self._audit.note_parse(seq - self._epoch_base, block)
        block.flow_id = fid
        self.bytes_read += len(raw)
        self._seq += 1
        self._pos += 1
        return block

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            block = self.next_block()
            if block is None:
                return
            yield block

    def before_first(self) -> None:
        """Rewind for the next epoch: with shuffle armed this draws the
        next epoch's permutation (construction was epoch 0)."""
        self._epoch += 1
        self._epoch_base = self._seq
        self._reader_cpu_ns = self._parse_cpu_ns = 0
        self._reorder()

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        """Re-shard within the *current* epoch's global order (elastic
        re-sharding composes with shuffle: the permutation is fixed by
        (seed, epoch), only the slice moves)."""
        self._part = int(part_index)
        self._nparts = max(1, int(num_parts))
        self._reorder()

    def stats(self) -> dict:
        return {
            "windows": len(self._order),
            "windows_total": len(self._table),
            "files": len(self._readers),
            "rows": int(self.num_rows),
            "epoch": int(self._epoch),
            "shuffle_seed": int(self._seed),
            "shuffle_window": int(self._unit),
            "reader_cpu_ns": int(self._reader_cpu_ns),
            "parse_cpu_ns": int(self._parse_cpu_ns),
        }

    # ---- job-snapshot state ---------------------------------------------
    def snapshot_state(self) -> dict:
        """Resumable read-plan state for a job snapshot: everything the
        permutation is a pure function of. The order itself is *not*
        serialized — resume re-derives it from (seed, epoch) and
        re-slices for the current partition, so the snapshot stays tiny
        and a restore is provably the same plan, not a copied one."""
        return {
            "uri": self.uri,
            "seed": int(self._seed),
            "window": int(self._unit),
            "epoch": int(self._epoch),
            "part": int(self._part),
            "nparts": int(self._nparts),
        }

    def restore_state(self, st: dict) -> None:
        """Jump to the epoch boundary *after* ``st["epoch"]`` (snapshots
        are taken at epoch boundaries: the snapshotted epoch finished, so
        the resumed run starts the next one). Re-derives the epoch
        permutation from the restored (seed, epoch) and re-slices it for
        this parser's *current* partition — resuming with a different
        part/nparts split composes the same way elastic re-sharding
        does."""
        check(st.get("uri", self.uri) == self.uri,
              "snapshot read-plan is for %s, not %s",
              st.get("uri"), self.uri)
        check(int(st.get("window", self._unit)) == self._unit,
              "snapshot shuffle window %s != configured %d (the epoch "
              "permutation would differ)", st.get("window"), self._unit)
        self._seed = int(st["seed"])
        self._epoch = int(st["epoch"]) + 1
        self._epoch_base = self._seq
        self._reorder()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for rd in self._readers:
            rd.close()

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Source-cache keying
# ---------------------------------------------------------------------------


def cache_token(uri: str, data_format: str) -> Optional[List]:
    """Shard-content token folded into SourceCache.chunk_key.

    Text sources are keyed by (uri, part, nparts, format) alone; baked
    shards add [format version, per-file (footer crc32, size), shuffle
    seed, shuffle window] so a re-baked file (same path, new bytes) or a
    re-seeded job never hits another job's cached parse. Returns None
    for non-shard inputs (key unchanged), and degrades to (size, mtime)
    when a footer is unreadable — an unreadable shard must still never
    collide with its replacement."""
    if data_format != "shard" and not is_shard_uri(uri):
        return None
    from dmlc_tpu.params import knobs

    token: List = [SHARD_FORMAT_VERSION, knobs.shuffle_seed(),
                   knobs.shuffle_window()]
    files: List = []
    try:
        from dmlc_tpu.io.filesystem import list_split_files

        for info in sorted(list_split_files(uri), key=lambda i: i.path.name):
            path = info.path.name
            try:
                size = os.path.getsize(path)
                with open(path, "rb") as f:
                    f.seek(max(0, size - _TAIL.size - len(MAGIC)))
                    crc = _TAIL.unpack(f.read(_TAIL.size))[0]
                files.append([path, int(size), int(crc)])
            except (OSError, struct.error):
                try:
                    st = os.stat(path)
                    files.append([path, int(st.st_size), int(st.st_mtime_ns)])
                except OSError:
                    files.append([path, -1, -1])
    except Exception:
        files.append(["unlistable", str(uri)])
    token.append(files)
    return token

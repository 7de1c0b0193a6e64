"""Device CSR batches with static-shape padding/bucketing.

The reference's ``RowBlock`` (data.h:170) is variable-length CSR on the host.
XLA wants static shapes: a new shape means a new compilation, and a stream of
ragged batches would cause a recompilation storm (SURVEY §7 "hard parts").

Policy here:
- row count is fixed per feed (``batch_size``; the final short batch is
  padded with zero-weight rows so loss/grad contributions vanish),
- nnz is rounded up to a bucket (default: round_up_bucket's
  sixteenth-octave steps above a floor),
  padded entries point at index 0 with value 0 so they are arithmetic no-ops,
- the row-mapping is carried as a per-entry ``row_ids`` array (COO-style),
  which is what TPU-friendly ``segment_sum`` SpMV consumes — instead of the
  host CSR ``offset`` array, whose per-row dynamic slicing XLA can't tile.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional

import numpy as np

from dmlc_tpu import obs
from dmlc_tpu.data.row_block import RowBlock, RowBlockContainer
from dmlc_tpu.utils.logging import check


def round_up_bucket(n: int, floor: int = 256) -> int:
    """Static-shape nnz bucket ≥ n: the next multiple of a sixteenth of
    the enclosing power of two (with a floor).

    Pure powers of two waste up to ~50% of the segment-sum/SpMV work on
    padding (measured: the Criteo-shape csr SGD ran 22% faster with a
    tight bucket vs the pow2 one). Sixteenth-of-octave steps bound the
    waste at ~12.5% of n (the worst case sits just above a power of two,
    where the step is n/8) while keeping the number of distinct shapes
    XLA compiles small (a steady-state feed with
    stable per-batch nnz sees one, plus one for the short final
    batch). An octave spans pow2/2, so its step of pow2/16 yields at
    most 8 distinct buckets inside it."""
    n = max(n, floor, 1)
    pow2 = 1 << (n - 1).bit_length()
    step = max(floor, pow2 >> 4)
    return ((n + step - 1) // step) * step


@dataclass
class DeviceCSRBatch:
    """A static-shape, device-ready sparse batch (host numpy twin).

    Shapes: labels/weights/row_valid are [batch]; indices/values/row_ids are
    [nnz_bucket]. Padded nnz entries have value 0 at feature 0 and row_id
    pointing at the first padded row (or row 0 with value 0 — a no-op either
    way for segment-sum SpMV).
    """

    labels: np.ndarray  # [batch] f32
    weights: np.ndarray  # [batch] f32 (0.0 for padded rows)
    indices: np.ndarray  # [nnz_bucket] i32 feature ids
    values: np.ndarray  # [nnz_bucket] f32 (0.0 for padded entries)
    row_ids: np.ndarray  # [nnz_bucket] i32 row of each entry
    offsets: np.ndarray  # [batch + 1] i32 CSR twin of row_ids (shipped to
    # device instead of row_ids: H2D ∝ rows, not nnz; padded rows repeat
    # the valid nnz)
    num_rows: int  # valid rows
    num_nonzero: int  # valid entries

    @property
    def batch_size(self) -> int:
        return len(self.labels)

    @property
    def nnz_bucket(self) -> int:
        return len(self.indices)


class DenseBatch(tuple):
    """A static-shape dense batch ``(x [batch, F] f32, labels, weights,
    num_rows)``: rows past ``num_rows`` are zero with weight 0."""

    num_rows = property(lambda self: self[3])


def _pieces(rows) -> list:
    """``[(block, lo, hi)]``, rows lo:hi of each block in turn: a RowBlock
    whole, or the pieces a :class:`BlockBatcher` cut a batch from."""
    return [(rows, 0, len(rows))] if isinstance(rows, RowBlock) else rows


def _joined(pieces) -> RowBlockContainer:
    """The pieces' rows in one container (``to_block`` copies them)."""
    out = RowBlockContainer()
    for block, lo, hi in _pieces(pieces):
        out.push_block(block.slice(lo, hi))
    return out


def _staging(pool, shape, dtype, fill=0):
    """A staging array filled with ``fill``: from the feed's
    FixedShapePool when given (host-buffer reuse — the allocation
    retired, the fill kept), else a fresh np.full."""
    if pool is None:
        return np.full(shape, fill, dtype=dtype)
    buf = pool.acquire(shape, dtype)
    buf.fill(fill)
    return buf


def pad_to_bucket(
    block,
    batch_size: int,
    nnz_bucket: Optional[int] = None,
    nnz_floor: int = 256,
    pool=None,
) -> DeviceCSRBatch:
    """Pad a host RowBlock slice, or a batch's pieces, into a static-shape
    DeviceCSRBatch. ``pool`` (device/feed.FixedShapePool) recycles the
    staging arrays."""
    pieces = _pieces(block)
    n = sum(hi - lo for _, lo, hi in pieces)
    check(n <= batch_size, "block larger than batch_size")
    nnz = sum(int(b.offset[hi] - b.offset[lo]) for b, lo, hi in pieces)
    bucket = nnz_bucket if nnz_bucket is not None else round_up_bucket(nnz, nnz_floor)
    check(nnz <= bucket, "nnz exceeds bucket")

    labels = _staging(pool, batch_size, np.float32)
    weights = _staging(pool, batch_size, np.float32)
    indices = _staging(pool, bucket, np.int32)
    values = _staging(pool, bucket, np.float32)
    row_ids = _staging(pool, bucket, np.int32)
    offsets = _staging(pool, batch_size + 1, np.int32, fill=nnz)
    row = pos = 0
    for b, lo, hi in pieces:
        start, end = int(b.offset[lo]), int(b.offset[hi])
        rows, ents = slice(row, row + hi - lo), slice(pos, pos + end - start)
        labels[rows] = b.label[lo:hi]
        weights[rows] = 1.0 if b.weight is None else b.weight[lo:hi]
        indices[ents] = b.index[start:end]
        values[ents] = 1.0 if b.value is None else b.value[start:end]
        row_ids[ents] = np.repeat(
            np.arange(row, rows.stop, dtype=np.int32),
            np.diff(b.offset[lo:hi + 1]))
        offsets[rows] = b.offset[lo:hi] - start + pos
        row, pos = rows.stop, ents.stop
    return DeviceCSRBatch(
        labels=labels,
        weights=weights,
        indices=indices,
        values=values,
        row_ids=row_ids,
        offsets=offsets,
        num_rows=n,
        num_nonzero=nnz,
    )


@dataclass
class ShardedCSRBatch:
    """A static-shape COO batch partitioned by destination mesh shard.

    indices/values/row_ids are flat [num_shards * nnz_bucket] with
    contiguous per-shard sections and LOCAL row ids (shard s owns rows
    [s*rows_per_shard, (s+1)*rows_per_shard)); sharding the leading dim
    with P(axis) ships each device only its own entries, so per-device
    H2D is ∝ global_nnz / world — the Criteo-scale requirement the
    replicated layout breaks (every device paying global nnz).
    """

    labels: np.ndarray  # [batch] f32
    weights: np.ndarray  # [batch] f32 (0.0 for padded rows)
    indices: np.ndarray  # [num_shards * nnz_bucket] i32
    values: np.ndarray  # [num_shards * nnz_bucket] f32
    row_ids: np.ndarray  # [num_shards * nnz_bucket] i32, LOCAL per shard
    offsets: np.ndarray  # [num_shards * (rows_per_shard + 1)] i32 per-shard
    # LOCAL CSR offsets into the shard's entry section (shipped instead of
    # row_ids)
    num_rows: int
    num_nonzero: int
    num_shards: int
    nnz_bucket: int  # per shard

    @property
    def batch_size(self) -> int:
        return len(self.labels)


def pad_to_bucket_sharded(
    block,
    batch_size: int,
    num_shards: int,
    nnz_bucket: Optional[int] = None,
    nnz_floor: int = 256,
    pool=None,
) -> ShardedCSRBatch:
    """Partition a RowBlock's entries, or a batch's pieces' joined, by
    destination shard (row-range split) into per-shard padded sections —
    the pure-Python twin of pipeline.cc FetchBatchCooSharded. ``pool``
    (device/feed.FixedShapePool) recycles the staging arrays."""
    if not isinstance(block, RowBlock):
        block = _joined(block).to_block()
    n = len(block)
    check(n <= batch_size, "block larger than batch_size")
    check(batch_size % num_shards == 0,
          "batch_size %d must divide over %d shards", batch_size, num_shards)
    rows_per_shard = batch_size // num_shards

    labels = _staging(pool, batch_size, np.float32)
    labels[:n] = block.label
    weights = _staging(pool, batch_size, np.float32)
    weights[:n] = 1.0 if block.weight is None else block.weight

    rows = np.repeat(
        np.arange(n, dtype=np.int64), np.diff(block.offset).astype(np.int64)
    )
    vals = (
        np.ones(block.num_nonzero, dtype=np.float32)
        if block.value is None
        else np.asarray(block.value, np.float32)
    )
    shard_of = rows // rows_per_shard
    counts = np.bincount(shard_of, minlength=num_shards) if len(rows) else (
        np.zeros(num_shards, dtype=np.int64)
    )
    bucket = (
        nnz_bucket if nnz_bucket is not None
        else round_up_bucket(int(counts.max()) if len(rows) else 0, nnz_floor)
    )
    check(int(counts.max() if len(rows) else 0) <= bucket,
          "a shard's nnz exceeds the bucket")

    indices = _staging(pool, num_shards * bucket, np.int32)
    values = _staging(pool, num_shards * bucket, np.float32)
    row_ids = _staging(pool, num_shards * bucket, np.int32)
    offsets = _staging(pool, num_shards * (rows_per_shard + 1), np.int32)
    # entries arrive row-major, so each shard's entries are contiguous
    start = 0
    for s in range(num_shards):
        c = int(counts[s])
        seg = slice(start, start + c)
        out = slice(s * bucket, s * bucket + c)
        indices[out] = block.index[seg]
        values[out] = vals[seg]
        local = rows[seg] - s * rows_per_shard
        row_ids[out] = local
        # local CSR offsets for this shard's section (padded rows repeat c)
        obase = s * (rows_per_shard + 1)
        offsets[obase: obase + rows_per_shard + 1] = np.searchsorted(
            local, np.arange(rows_per_shard + 1), side="left"
        ).astype(np.int32)
        start += c
    return ShardedCSRBatch(
        labels=labels,
        weights=weights,
        indices=indices,
        values=values,
        row_ids=row_ids,
        offsets=offsets,
        num_rows=n,
        num_nonzero=block.num_nonzero,
        num_shards=num_shards,
        nnz_bucket=bucket,
    )


def block_to_dense(
    block, batch_size: int, num_features: int, pool=None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Densify a RowBlock, or a batch's pieces, into fixed [batch,
    num_features] — the right layout when the feature dim is small/dense
    (e.g. HIGGS's 28), letting the MXU do a plain matmul instead of
    gather+segment-sum. ``pool`` (device/feed.FixedShapePool) recycles
    the staging arrays."""
    pieces = _pieces(block)
    n = sum(hi - lo for _, lo, hi in pieces)
    check(n <= batch_size, "block larger than batch_size")
    x = _staging(pool, (batch_size, num_features), np.float32)
    labels = _staging(pool, batch_size, np.float32)
    weights = _staging(pool, batch_size, np.float32)
    row = 0
    for b, lo, hi in pieces:
        start, end = int(b.offset[lo]), int(b.offset[hi])
        rows = slice(row, row + hi - lo)
        at = np.repeat(np.arange(row, rows.stop), np.diff(b.offset[lo:hi + 1]))
        index = b.index[start:end]
        keep = index < num_features
        x[at[keep], index[keep]] = (
            1.0 if b.value is None else b.value[start:end][keep])
        labels[rows] = b.label[lo:hi]
        weights[rows] = 1.0 if b.weight is None else b.weight[lo:hi]
        row = rows.stop
    return x, labels, weights


class BlockBatcher:
    """The batch fetch of a parser that has no native one: its RowBlocks
    cut into batches, each copied once from the blocks into the pool's
    buffers on the calling thread (the feed's producer), under the
    ``stage`` span. The reads are NativePipelineParser's, with its
    signatures and batches. Each batch also carries ``flow_ids`` and
    ``seq_ids`` (the chunks whose rows first appear in it, closed and
    acked on delivery) and ``staging`` (the pooled buffers the consumer
    retires). Under ``drop_remainder`` a short last batch is dropped and
    the chunks only it reached are acked here."""

    def __init__(self, parser, pool, stage_hist, drop_remainder: bool,
                 audit, ack=None):
        self.parser = parser
        self._pool = pool
        self._hist = stage_hist
        self._drop_remainder = drop_remainder
        self._audit = audit
        self._ack = ack
        self.restart(0)

    def restart(self, npass: int) -> None:
        """Begin pass ``npass`` of the parser, which is at its start."""
        self._blocks = iter(self.parser)
        self._head = None  # (block, its first row not yet batched)
        self._flows, self._seqs = [], []
        self._pass, self._batch = npass, 0

    def _take(self, batch_size: int):
        """(pieces ``[(block, lo, hi)]``, rows) of the next batch, short
        only at the end of the pass; None where no batch is left."""
        pieces, rows = [], 0
        while rows < batch_size:
            if self._head is None:
                block = next(self._blocks, None)
                if block is None:
                    break
                if getattr(block, "flow_id", 0):
                    self._flows.append(block.flow_id)
                if getattr(block, "seq_id", None) is not None:
                    self._seqs.append(block.seq_id)
                self._head = (block, 0)
            block, lo = self._head
            hi = min(len(block), lo + batch_size - rows)
            pieces.append((block, lo, hi))
            rows += hi - lo
            self._head = None if hi == len(block) else (block, hi)
        if rows == batch_size or (rows and not self._drop_remainder):
            return pieces, rows
        if self._ack is not None:
            # chunks only a dropped remainder reached (or empty ones) were
            # visited: unacked, the dispatcher would requeue them forever
            for sid in self._seqs:
                self._ack(sid)
        self._seqs = []
        return None

    def _stage(self, batch_size: int, make):
        """``make(pieces, rows)`` → the next batch, staged into the pool;
        None at the end of the pass."""
        taken = self._take(batch_size)
        if taken is None:
            return None
        pieces, rows = taken
        if self._audit.enabled:
            self._audit.note_batch(self._batch, _joined(pieces))
        with obs.span("stage", hist=self._hist, rows=rows, pass_=self._pass,
                      batch=self._batch):
            for fid in self._flows:
                obs.flow_step(fid, "chunk")
            batch = make(pieces, rows)
        arrays = batch if isinstance(batch, DenseBatch) else [
            getattr(batch, f.name) for f in fields(batch)]
        batch.staging = tuple(a for a in arrays if isinstance(a, np.ndarray))
        batch.flow_ids, batch.seq_ids = tuple(self._flows), tuple(self._seqs)
        self._flows, self._seqs = [], []
        self._batch += 1
        return batch

    def read_batch_dense(self, batch_size: int, num_features: int):
        return self._stage(batch_size, lambda pieces, rows: DenseBatch(
            block_to_dense(pieces, batch_size, num_features, pool=self._pool)
            + (rows,)))

    def read_batch_coo(self, batch_size: int, nnz_bucket=None,
                       nnz_floor: int = 256):
        return self._stage(batch_size, lambda pieces, rows: pad_to_bucket(
            pieces, batch_size, nnz_bucket, nnz_floor, pool=self._pool))

    def read_batch_coo_sharded(self, batch_size: int, num_shards: int,
                               nnz_bucket=None, nnz_floor: int = 256):
        return self._stage(batch_size, lambda pieces, rows: (
            pad_to_bucket_sharded(pieces, batch_size, num_shards,
                                  nnz_bucket, nnz_floor, pool=self._pool)))

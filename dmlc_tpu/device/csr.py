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

from dataclasses import dataclass
from typing import Optional

import numpy as np

from dmlc_tpu.data.row_block import RowBlock
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


def _staging(pool, shape, dtype):
    """A zeroed staging array: from the feed's FixedShapePool when given
    (host-buffer reuse — the allocation retired, the zero-fill kept),
    else a fresh np.zeros."""
    if pool is None:
        return np.zeros(shape, dtype=dtype)
    buf = pool.acquire(shape, dtype)
    buf.fill(0)
    return buf


def pad_to_bucket(
    block: RowBlock,
    batch_size: int,
    nnz_bucket: Optional[int] = None,
    nnz_floor: int = 256,
    pool=None,
) -> DeviceCSRBatch:
    """Pad a host RowBlock slice into a static-shape DeviceCSRBatch.
    ``pool`` (device/feed.FixedShapePool) recycles the staging arrays."""
    n = len(block)
    check(n <= batch_size, "block larger than batch_size")
    nnz = block.num_nonzero
    bucket = nnz_bucket if nnz_bucket is not None else round_up_bucket(nnz, nnz_floor)
    check(nnz <= bucket, "nnz exceeds bucket")

    labels = _staging(pool, batch_size, np.float32)
    labels[:n] = block.label
    weights = _staging(pool, batch_size, np.float32)
    weights[:n] = 1.0 if block.weight is None else block.weight

    indices = _staging(pool, bucket, np.int32)
    values = _staging(pool, bucket, np.float32)
    row_ids = _staging(pool, bucket, np.int32)
    indices[:nnz] = block.index
    values[:nnz] = (
        np.ones(nnz, dtype=np.float32) if block.value is None else block.value
    )
    row_ids[:nnz] = np.repeat(
        np.arange(n, dtype=np.int32), np.diff(block.offset).astype(np.int64)
    )
    if pool is None:
        offsets = np.full(batch_size + 1, nnz, dtype=np.int32)
    else:
        offsets = pool.acquire(batch_size + 1, np.int32)
        offsets.fill(nnz)
    offsets[: n + 1] = np.asarray(block.offset[: n + 1], dtype=np.int32)
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
    block: RowBlock,
    batch_size: int,
    num_shards: int,
    nnz_bucket: Optional[int] = None,
    nnz_floor: int = 256,
) -> ShardedCSRBatch:
    """Partition a RowBlock's entries by destination shard (row-range
    split) into per-shard padded sections — the pure-Python twin of
    pipeline.cc FetchBatchCooSharded."""
    n = len(block)
    check(n <= batch_size, "block larger than batch_size")
    check(batch_size % num_shards == 0,
          "batch_size %d must divide over %d shards", batch_size, num_shards)
    rows_per_shard = batch_size // num_shards

    labels = np.zeros(batch_size, dtype=np.float32)
    labels[:n] = block.label
    weights = np.zeros(batch_size, dtype=np.float32)
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

    indices = np.zeros(num_shards * bucket, dtype=np.int32)
    values = np.zeros(num_shards * bucket, dtype=np.float32)
    row_ids = np.zeros(num_shards * bucket, dtype=np.int32)
    offsets = np.zeros(num_shards * (rows_per_shard + 1), dtype=np.int32)
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
    block: RowBlock, batch_size: int, num_features: int, pool=None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Densify a RowBlock into fixed [batch, num_features] — the right layout
    when the feature dim is small/dense (e.g. HIGGS's 28), letting the MXU do
    a plain matmul instead of gather+segment-sum. ``pool``
    (device/feed.FixedShapePool) recycles the staging arrays."""
    n = len(block)
    check(n <= batch_size, "block larger than batch_size")
    x = _staging(pool, (batch_size, num_features), np.float32)
    rows = np.repeat(np.arange(n), np.diff(block.offset).astype(np.int64))
    vals = (
        np.ones(block.num_nonzero, dtype=np.float32)
        if block.value is None
        else block.value
    )
    keep = block.index < num_features
    x[rows[keep], block.index[keep]] = vals[keep]
    labels = _staging(pool, batch_size, np.float32)
    labels[:n] = block.label
    weights = _staging(pool, batch_size, np.float32)
    weights[:n] = 1.0 if block.weight is None else block.weight
    return x, labels, weights

"""Sparse matrix-vector products for device CSR batches.

Design note (why COO + segment_sum, not CSR offsets): per-row dynamic slicing
of a CSR ``offset`` array is serial, ragged control flow XLA cannot tile onto
the TPU's vector/matrix units. With a per-entry ``row_ids`` array the forward
SpMV is a gather + ``segment_sum`` — both static-shape, fully vectorized, and
fusable — and the gradient is the same primitive with feature ids as the
segment keys. Padded entries (value 0 at feature 0, row 0) are arithmetic
no-ops, so the static nnz bucket needs no masking.

Reference parity: this replaces `Row::SDot` (data.h:152-158), the only
compute kernel the reference ships.

On TPU the row-direction segment-sum can additionally route through the
fused Pallas kernel (:func:`spmv_pallas`, DMLC_TPU_PALLAS=1 with a csr
layout): same contract, the reduce tiled as a one-hot masked add instead
of XLA's scatter chain. The transpose (feature-direction) reduce stays
on XLA in every configuration — see the design note in
ops/pallas_kernels.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map


def expand_row_ids(offsets, nnz: int):
    """[rows + 1] CSR offsets → [nnz] COO row ids, on device.

    The feed ships the small offsets array across H2D (∝ rows) instead of
    per-entry row_ids (∝ nnz); this expansion — scatter-add a mark at every
    row boundary, then an inclusive cumsum — is O(nnz) vectorized work that
    XLA fuses into the consuming segment-sum's input. Entry e's row is
    #{r ≥ 1 : offsets[r] ≤ e}. Padding semantics: when the batch fills the
    bucket exactly (offsets[rows] == nnz) the tail boundary marks land past
    the end and ``mode="drop"`` discards them; when valid nnz < bucket, the
    padded rows' marks land in-bounds at the valid-nnz index, so the padded
    entries' cumsum overshoots and the clamp to the LAST row absorbs them
    (also saving ``jnp.take``'s out-of-bounds NaN fill) — harmless either
    way because padded values are 0 (arithmetic no-op in both segment-sum
    directions).

    ``nnz`` must be the static bucket size (values.shape[0] under jit).
    """
    marks = jnp.zeros(nnz, jnp.int32).at[offsets[1:]].add(1, mode="drop")
    return jnp.minimum(jnp.cumsum(marks), offsets.shape[0] - 2)


@partial(jax.jit, static_argnames=("num_rows",))
def spmv(values, indices, row_ids, weight_vec, num_rows: int):
    """y[r] = sum_{e: row_ids[e]==r} values[e] * weight_vec[indices[e]].

    values/indices/row_ids: [nnz] static-shape (padded) COO entries.
    weight_vec: [num_features]. Returns [num_rows].
    """
    contrib = values * jnp.take(weight_vec, indices, axis=0)
    return jax.ops.segment_sum(contrib, row_ids, num_segments=num_rows)


@partial(jax.jit, static_argnames=("num_rows", "interpret"))
def spmv_pallas(values, indices, row_ids, weight_vec, num_rows: int,
                interpret: bool = False):
    """:func:`spmv` with the row-direction reduce on the fused Pallas
    kernel (ops/pallas_kernels.coo_segment_sum) instead of XLA's
    scatter-based ``segment_sum`` lowering. The feature gather stays on
    XLA, where it fuses into the kernel's ``contrib`` input — per-entry
    dynamic gather is the part a TPU kernel cannot tile (module design
    note), the batch-row reduce is the part it can. Bit-parity with
    :func:`spmv` is pinned by the CI parity digest on integer-valued
    data (exact f32 sums ⇒ reduction order is unobservable)."""
    from dmlc_tpu.ops.pallas_kernels import coo_segment_sum

    contrib = values * jnp.take(weight_vec, indices, axis=0)
    return coo_segment_sum(contrib, row_ids, num_rows, interpret=interpret)


@partial(jax.jit, static_argnames=("num_features",))
def spmv_transpose(values, indices, row_ids, row_grads, num_features: int):
    """g[f] = sum_{e: indices[e]==f} values[e] * row_grads[row_ids[e]].

    The gradient of ``spmv`` w.r.t. ``weight_vec``: scatter-add of per-row
    grads back onto features. Returns [num_features].
    """
    contrib = values * jnp.take(row_grads, row_ids, axis=0)
    return jax.ops.segment_sum(contrib, indices, num_segments=num_features)


def make_sharded_spmv(mesh, num_rows: int, axis: str = "dp"):
    """SpMV with entries AND output rows sharded over ``axis``.

    Consumes the ShardedCSRBatch layout (device/csr.py): entry arrays are
    flat [num_shards * nnz_bucket] with per-shard sections and LOCAL row
    ids, so each device receives only its own entries (per-device H2D ∝
    global_nnz / world) and the segment-sum is purely local — no global
    mask, no replication. Returns f(values, indices, row_ids, weight_vec)
    -> [num_rows] sharded on the leading axis; weight_vec is replicated.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    assert num_rows % n_shards == 0, "num_rows must divide over the mesh axis"
    rows_local = num_rows // n_shards

    def _local(values, indices, row_ids, weight_vec):
        contrib = values * jnp.take(weight_vec, indices, axis=0)
        return jax.ops.segment_sum(
            contrib, row_ids, num_segments=rows_local
        )

    return jax.jit(
        shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P()),
            out_specs=P(axis),
        )
    )

"""TPU compute ops over device-resident CSR batches and long sequences.

The reference stops at host CSR (`RowBlock`, data.h:170) and leaves compute to
downstream learners; here the framework supplies the TPU-shaped kernels those
learners need: COO/segment-sum SpMV (forward) and its transpose (gradient
scatter) plus mesh-sharded variants, and the sequence-parallel attention
schedules (ring / all-to-all) for long-context training — SURVEY §5.7's
extension point, realized.
"""

from dmlc_tpu.ops.spmv import (
    spmv,
    spmv_transpose,
    make_sharded_spmv,
)
from dmlc_tpu.ops.moe import (
    init_moe_params,
    make_moe_layer,
    moe_dense_oracle,
    shard_moe_params,
)
from dmlc_tpu.ops.pipeline_parallel import (
    make_pipeline,
    pipeline_oracle,
    shard_pipeline_params,
)
from dmlc_tpu.ops.sequence_parallel import (
    full_attention,
    make_pallas_flash_local,
    make_ring_attention,
    make_ulysses_attention,
    zigzag_shard,
    zigzag_unshard,
)
from dmlc_tpu.utils.jax_compat import place_compile_cache

# the kernels in this package are bare ``jax.jit`` sites (they compile at
# first call, not at import): place the persistent compile cache now
place_compile_cache()

__all__ = [
    "spmv",
    "spmv_transpose",
    "make_sharded_spmv",
    "full_attention",
    "make_pallas_flash_local",
    "make_ring_attention",
    "make_ulysses_attention",
    "zigzag_shard",
    "zigzag_unshard",
    "init_moe_params",
    "make_moe_layer",
    "moe_dense_oracle",
    "shard_moe_params",
    "make_pipeline",
    "pipeline_oracle",
    "shard_pipeline_params",
]

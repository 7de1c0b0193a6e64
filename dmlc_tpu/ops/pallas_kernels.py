"""Pallas TPU kernels for the hot compute ops.

The flagship loop's compute core is ``margin = X @ w`` followed by an
elementwise loss/grad and ``gw = X^T (weight * dmargin)`` (models/linear.py).
XLA already fuses the elementwise work into the matmuls; the Pallas kernel
here goes one step further and keeps the whole step — both matmuls, the
loss, and the scalar reductions — resident in VMEM per batch tile, with the
gradient accumulated across grid steps. One HBM read of X per step, no
intermediate [B] arrays ever round-tripping through HBM.

The sparse (COO) path gets a kernel too, with a narrower scope. Per-entry
dynamic gather/scatter is exactly what the TPU's vector unit can't tile
(SURVEY §7 hard parts; ops/spmv.py design note), so the feature-id gather
(``vec[indices]`` — the segment keys span millions of features) stays on
XLA, where it fuses into the kernel's input. What Pallas CAN tile is the
row-direction reduce: ``coo_segment_sum`` turns the multi-op
scatter-segment-sum chain into a one-hot broadcast-compare + masked
VPU reduce per (row tile, entry tile) — the segment ids are batch row
ids, bounded by batch_size, so the one-hot tile is small and static. The
transpose direction (segment by FEATURE id, ops/spmv.spmv_transpose)
stays on XLA's scatter: a one-hot over millions of features would sweep
every entry tile per feature tile and serialize. Exact f32 by the same
argument as the dense kernel (VPU masked add, no MXU truncation), so
bit-parity with XLA holds on integer-valued data where sums are exactly
representable.

Tiling: batch rows are processed TILE_B at a time; the feature dim is padded
to a lane multiple (128) by the wrapper, and the row tile to a sublane
multiple. Padded rows carry weight 0, padded features carry x == w == 0, so
both are arithmetic no-ops (the same invariant as device/csr.py padding).

Opt-in: models/linear.py uses it when DMLC_TPU_PALLAS=1 (or use_pallas=True);
it exists as the template for wider fused steps (FM interactions,
multi-tower).

Every kernel here compiles through Mosaic for the TPU it runs on
(``interpret=False``, the default) and fails loudly on a backend Mosaic
cannot target. Interpreter mode is something a caller passes — the CPU
tests do — never something this module infers from the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.objectives import margin_loss_grad
from dmlc_tpu.utils.jax_compat import import_pallas

pl, pltpu = import_pallas()

_LANE = 128
_TILE_B = 512


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _fused_step_kernel(objective: str, x_ref, y_ref, wgt_ref, w_ref, b_ref,
                       gw_ref, gb_ref, loss_ref, wsum_ref):
    """One batch tile: margin → dloss → partial gw/gb/loss/wsum, accumulated
    across the (sequential) grid."""
    i = pl.program_id(0)

    x = x_ref[...]                       # [TILE_B, F]
    y = y_ref[...]                       # [TILE_B, 1]
    wgt = wgt_ref[...]                   # [TILE_B, 1]
    w = w_ref[...]                       # [1, F] — lane-major: an [F, 1]
    # layout would pad the unit lane dimension to 128 and cost 128x VMEM

    # A matvec is bandwidth-bound (2 flops/element): broadcast-multiply +
    # reduce on the VPU is both the natural lowering (Mosaic rejects the
    # [T,F]x[1,F] dot_general contraction) and exact f32 — the MXU's
    # single-pass bf16 truncation would cost ~1e-2 relative error here
    margin = jnp.sum(x * w, axis=1, keepdims=True) + b_ref[0, 0]  # [TILE_B, 1]
    loss, dmargin = margin_loss_grad(objective, margin, y)

    wg = wgt * dmargin                   # [TILE_B, 1]
    gw_part = jnp.sum(x * wg, axis=0, keepdims=True)  # [1, F]
    # (1,1)-shaped partials: Mosaic cannot store scalars to VMEM
    gb_part = jnp.sum(wg).reshape(1, 1)
    loss_part = jnp.sum(wgt * loss).reshape(1, 1)
    wsum_part = jnp.sum(wgt).reshape(1, 1)

    @pl.when(i == 0)
    def _():
        gw_ref[...] = gw_part
        gb_ref[...] = gb_part
        loss_ref[...] = loss_part
        wsum_ref[...] = wsum_part

    @pl.when(i > 0)
    def _():
        gw_ref[...] += gw_part
        gb_ref[...] += gb_part
        loss_ref[...] += loss_part
        wsum_ref[...] += wsum_part


@functools.partial(
    jax.jit, static_argnames=("objective", "tile_b", "interpret")
)
def fused_linear_grads(
    x, label, weight, w, b,
    objective: str = "logistic",
    tile_b: int = _TILE_B,
    interpret: bool = False,
):
    """(gw [F], gb, loss_sum, weight_sum) for a dense batch, one kernel.

    Same contract as the _local_grads dense path in models/linear.py.
    Shapes: x [B, F], label/weight [B], w [F], b scalar. B and F need not be
    tile-aligned — the wrapper zero-pads (padded rows get weight 0).
    """
    bsz, nfeat = x.shape
    fpad = _round_up(max(nfeat, _LANE), _LANE)
    # keep the x tile within a VMEM budget (~2 MiB leaves room for Mosaic's
    # double buffering inside the 16 MiB scoped limit); floor is the f32
    # sublane minimum so very wide feature dims shrink the row tile instead
    # of blowing VMEM
    vmem_rows = max(8, ((2 << 20) // (fpad * 4)) // 8 * 8)
    tile = min(tile_b, vmem_rows, _round_up(max(bsz, 8), 8))
    bpad = _round_up(max(bsz, tile), tile)
    if fpad != nfeat or bpad != bsz:
        x = jnp.pad(x, ((0, bpad - bsz), (0, fpad - nfeat)))
        label = jnp.pad(label, (0, bpad - bsz))
        weight = jnp.pad(weight, (0, bpad - bsz))
        w = jnp.pad(w, (0, fpad - nfeat))

    grid = bpad // tile
    kernel = functools.partial(_fused_step_kernel, objective)
    gw, gb, loss_sum, wsum = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((tile, fpad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, fpad), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, fpad), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, fpad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * bpad * fpad,  # two matmuls over the batch
            bytes_accessed=bpad * fpad * 4 + fpad * 4 * 2 + bpad * 8,
            transcendentals=bpad,
        ),
        interpret=interpret,
    )(
        x.astype(jnp.float32),
        label.astype(jnp.float32).reshape(-1, 1),
        weight.astype(jnp.float32).reshape(-1, 1),
        w.astype(jnp.float32).reshape(1, -1),
        jnp.asarray(b, jnp.float32).reshape(1, 1),
    )
    return gw[0, :nfeat], gb[0, 0], loss_sum[0, 0], wsum[0, 0]


# ---------------------------------------------------------------------------
# COO row-direction segment-sum for the sparse SpMV path (ops/spmv.py)
# ---------------------------------------------------------------------------
#
# y[r] = sum_{e: row_ids[e]==r} contrib[e]. The grid walks (row tile,
# entry tile); each step compares the entry tile's row ids against the
# row tile's id range (2D broadcasted_iota — a 1D iota does not lower on
# TPU) and masked-adds the matching contributions on the VPU,
# accumulating across the sequential entry-tile sweep. Padded entries
# carry contrib 0 (the csr bucket invariant) and the wrapper's alignment
# pad carries row id -1, which matches no tile row.

_SEG_TILE_E = 512  # entries per grid step
_SEG_TILE_R = 256  # output rows per grid step (lane multiple)


def _seg_sum_kernel(rid_ref, contrib_ref, out_ref):
    j = pl.program_id(0)  # row tile (output block)
    k = pl.program_id(1)  # entry tile (sequential sweep, accumulates)
    rid = rid_ref[...]  # [TILE_E, 1] i32
    contrib = contrib_ref[...]  # [TILE_E, 1] f32
    rows = j * _SEG_TILE_R + jax.lax.broadcasted_iota(
        jnp.int32, (1, _SEG_TILE_R), 1
    )
    # one-hot membership of each entry in this row tile; masked add on
    # the VPU keeps f32 exact (MXU one-hot matmul would truncate to bf16
    # — the same exactness argument as the dense kernel's matvec)
    part = jnp.sum(
        jnp.where(rid == rows, contrib, 0.0), axis=0, keepdims=True
    )  # [1, TILE_R]

    @pl.when(k == 0)
    def _():
        out_ref[...] = part

    @pl.when(k > 0)
    def _():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("num_rows", "interpret"))
def coo_segment_sum(contrib, row_ids, num_rows: int, interpret: bool = False):
    """``jax.ops.segment_sum(contrib, row_ids, num_rows)`` as a Pallas
    reduce — the row-direction half of the SpMV chain (ops/spmv.spmv),
    with the feature gather left to XLA where it fuses into ``contrib``.
    contrib [E] f32, row_ids [E] i32 (entries beyond the valid nnz must
    carry contrib 0); returns [num_rows] f32."""
    e = contrib.shape[0]
    epad = _round_up(max(e, _SEG_TILE_E), _SEG_TILE_E)
    rpad = _round_up(max(num_rows, _SEG_TILE_R), _SEG_TILE_R)
    if epad != e:
        contrib = jnp.pad(contrib, (0, epad - e))
        row_ids = jnp.pad(row_ids, (0, epad - e), constant_values=-1)
    out = pl.pallas_call(
        _seg_sum_kernel,
        grid=(rpad // _SEG_TILE_R, epad // _SEG_TILE_E),
        in_specs=[
            pl.BlockSpec((_SEG_TILE_E, 1), lambda j, k: (k, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_SEG_TILE_E, 1), lambda j, k: (k, 0),
                         memory_space=pltpu.VMEM),
        ],
        # one lane-major [1, rpad] row, tiled along the lanes: Mosaic
        # wants a block's second-to-last dim divisible by 8 or equal to
        # the array's — a (1, TILE_R) block of a [tiles, TILE_R] array is
        # neither (refused on the v5e), of a [1, rpad] array it is
        out_specs=pl.BlockSpec((1, _SEG_TILE_R), lambda j, k: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, rpad), jnp.float32),
        cost_estimate=pl.CostEstimate(
            # each (row tile, entry tile) pair compares + masked-adds
            flops=2 * (rpad // _SEG_TILE_R) * epad,
            bytes_accessed=(rpad // _SEG_TILE_R) * epad * 8 + rpad * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(
        row_ids.astype(jnp.int32).reshape(-1, 1),
        contrib.astype(jnp.float32).reshape(-1, 1),
    )
    return out.reshape(-1)[:num_rows]


# ---------------------------------------------------------------------------
# Byte tokenizer for the vectorized text-parse path (data/vparse.py)
# ---------------------------------------------------------------------------
#
# Token boundaries are a pure elementwise problem once the one-byte
# neighbor shifts are materialized: start = nonsep(cur) & sep(prev),
# end = nonsep(cur) & sep(next). The wrapper builds the three shifted
# views on the host (overlapping slices of one padded buffer — no extra
# copies) so the kernel is shift-free and tiles cleanly on the VPU; the
# 0x20 padding byte is a separator, so padded lanes produce no
# boundaries. Semantics are pinned to vparse.token_boundary_masks by the
# parity suite. Offset extraction (flatnonzero) stays on the host — it
# has no fixed-shape device analog.

_TOK_SEP = (0x20, 0x09, 0x3A, 0x0A, 0x0D)  # space tab colon \n \r
_TOK_ROWS = 256  # uint8 sublane tile is 32; 256x128 rows/step = 32 KiB


def _tokenize_kernel(cur_ref, prv_ref, nxt_ref, starts_ref, ends_ref):
    def sep(v):
        m = v == _TOK_SEP[0]
        for code in _TOK_SEP[1:]:
            m = m | (v == code)
        return m

    cur = cur_ref[...].astype(jnp.int32)
    nonsep = ~sep(cur)
    starts_ref[...] = (
        nonsep & sep(prv_ref[...].astype(jnp.int32))
    ).astype(jnp.uint8)
    ends_ref[...] = (
        nonsep & sep(nxt_ref[...].astype(jnp.int32))
    ).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tokenize_call(cur, prv, nxt, interpret: bool = False):
    rows = cur.shape[0]
    spec = pl.BlockSpec((_TOK_ROWS, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _tokenize_kernel,
        grid=(rows // _TOK_ROWS,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANE), jnp.uint8),
            jax.ShapeDtypeStruct((rows, _LANE), jnp.uint8),
        ],
        interpret=interpret,
    )(cur, prv, nxt)


def tokenize_boundaries(a, interpret: bool = False):
    """(starts_mask, ends_mask) bool arrays for libsvm tokens over the
    uint8 chunk ``a`` — the Pallas variant of
    ``vparse.token_boundary_masks``, used when ``DMLC_TPU_PALLAS`` is
    ``1``/``parse``. Compiles for the TPU by default; ``interpret=True``
    is the caller's explicit request for the Pallas interpreter."""
    import numpy as np

    n = int(a.size)
    if n == 0:
        empty = np.zeros(0, dtype=bool)
        return empty, empty.copy()
    quantum = _TOK_ROWS * _LANE
    pad = -(-n // quantum) * quantum
    buf = np.full(pad + 2, 0x20, dtype=np.uint8)
    buf[1 : 1 + n] = a
    cur = buf[1 : 1 + pad].reshape(-1, _LANE)
    prv = buf[0:pad].reshape(-1, _LANE)
    nxt = buf[2 : 2 + pad].reshape(-1, _LANE)
    starts, ends = _tokenize_call(cur, prv, nxt, interpret=interpret)
    starts = np.asarray(starts).reshape(-1)[:n].astype(bool)
    ends = np.asarray(ends).reshape(-1)[:n].astype(bool)
    return starts, ends

"""Sequence/context parallelism: ring attention and all-to-all attention.

The reference predates long-context training and ships nothing here
(SURVEY §5.7: absent; the closest analog is record-boundary-preserving
chunked streaming). This module realizes the documented extension point
the TPU-first way — the sequence dimension is a mesh axis, and the two
standard schedules are provided:

- ``ring_attention``: K/V shards rotate around the mesh axis with
  ``ppermute`` while each device accumulates its queries' attention in
  the flash/online-softmax form (running max + denominator), so peak
  memory is O(T_local²) and the full T×T score matrix never exists.
  Communication rides the ICI ring; compute overlaps the rotation inside
  one jitted loop.
- ``ulysses_attention`` (all-to-all): ``all_to_all`` re-shards sequence →
  heads, every device runs FULL attention for its head group (exact
  softmax, any local kernel), and a second ``all_to_all`` restores the
  sequence sharding. Needs heads % axis_size == 0; two collectives total.

Shapes are [batch, seq, heads, head_dim] with ``seq`` sharded over the
axis. Both match full attention exactly (tests/test_sequence_parallel.py
asserts parity on an 8-device mesh), including causal masking via global
position indices.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size, pcast
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.utils.logging import check

_NEG_INF = -1e30  # mask value: large-negative beats -inf (0*inf=nan in bwd)


def _group_ratio(q, k, v):
    """Q-heads per KV-head (grouped-query attention). 1 = classic MHA;
    H % H_kv must divide (llama-class GQA, MQA at H_kv = 1). K and V must
    agree — the grouped einsums would otherwise silently mis-pair heads
    (the classic MHA einsum made a mismatch a shape error; keep that)."""
    h, hk = q.shape[2], k.shape[2]
    check(k.shape[2] == v.shape[2],
          "k has %d heads but v has %d", k.shape[2], v.shape[2])
    check(h % hk == 0, "num_heads %d must divide by num_kv_heads %d", h, hk)
    return h // hk


def _grouped_scores(q, k, scale):
    """QKᵀ with KV-head grouping: q [B,Tq,H,D] x k [B,Tk,Hk,D] →
    [B,H,Tq,Tk] (the G = H/Hk query heads of a group share one KV head —
    no materialized KV repeat)."""
    b, t_q, h, d = q.shape
    hk = k.shape[2]
    qg = q.reshape(b, t_q, hk, h // hk, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    return s.reshape(b, h, t_q, k.shape[1])


def _grouped_pv(p, v):
    """probs [B,H,Tq,Tk] x v [B,Tk,Hk,D] → [B,Tq,H,D] under grouping."""
    b, h, t_q, t_k = p.shape
    hk = v.shape[2]
    pg = p.reshape(b, hk, h // hk, t_q, t_k)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pg, v)
    return out.reshape(b, t_q, h, v.shape[-1])


def full_attention(q, k, v, causal: bool = False, window: int = 0):
    """Reference single-device attention: softmax(QKᵀ/√d)V.

    q [B, T, H, D]; k/v [B, T, H_kv, D] with H_kv | H (GQA/MQA — H_kv = H
    is classic MHA); out [B, T, H, D]. ``window > 0`` adds mistral-style
    sliding-window masking (query p attends keys in (p-window, p]; implies
    causal). The parity oracle for the sharded schedules."""
    check(window >= 0, "window must be >= 0, got %d", window)
    _group_ratio(q, k, v)
    causal = causal or window > 0
    d = q.shape[-1]
    scores = _grouped_scores(q, k, 1.0 / jnp.sqrt(float(d)))
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        qp = jnp.arange(t_q)[:, None]
        kp = jnp.arange(t_k)[None, :]
        mask = qp >= kp
        if window > 0:
            mask &= (qp - kp) < window
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _grouped_pv(probs, v)


def _block_accumulate(q, k_blk, v_blk, m, l, o, q_pos, k_pos, causal, scale,
                      window: int = 0):
    """One online-softmax block update (the flash-attention recurrence).

    q [B,Tq,H,D]; k_blk/v_blk [B,Tk,Hk,D] with Hk | H (GQA); m,l [B,H,Tq];
    o [B,Tq,H,D]. q_pos [Tq] / k_pos [Tk] are GLOBAL positions for causal
    and sliding-window masking. The accumulator stays per Q head — only the score/PV einsums
    group, so GQA costs nothing extra here (and the ring ships the SMALLER
    KV shards around the ICI ring: bandwidth ∝ Hk, not H).
    """
    s = _grouped_scores(q, k_blk, scale)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
        if window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) must not produce nan
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * correction + p.sum(axis=-1)
    pv = _grouped_pv(p, v_blk)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new




def zigzag_indices(t: int, num_devices: int):
    """Permutation mapping natural order → zigzag device layout.

    The sequence splits into 2N equal chunks; device i holds chunks
    (i, 2N-1-i) — one early + one late — so under CAUSAL masking every
    device does the same total score work per ring hop. With the
    contiguous layout device 0's queries see almost nothing and device
    N-1's see everything: the ring runs in lockstep, so the most-loaded
    device sets every hop's wall time and half the fleet idles. Zigzag is
    the standard fix (llama-class context-parallel training).
    """
    check(t % (2 * num_devices) == 0,
          "seq len %d must divide by 2*num_devices (%d)", t, 2 * num_devices)
    c = t // (2 * num_devices)
    order = []
    for i in range(num_devices):
        order.extend(range(i * c, (i + 1) * c))
        j = 2 * num_devices - 1 - i
        order.extend(range(j * c, (j + 1) * c))
    return np.asarray(order, dtype=np.int32)


def zigzag_shard(x, num_devices: int):
    """Reorder [B, T, ...] from natural to zigzag layout (device i's
    contiguous shard then holds chunks i and 2N-1-i). Apply BEFORE
    sequence-sharding the array over the mesh axis; activations can stay
    in this layout across layers so the cost is paid once."""
    return jnp.take(x, jnp.asarray(zigzag_indices(x.shape[1], num_devices)),
                    axis=1)


def zigzag_unshard(x, num_devices: int):
    """Inverse of :func:`zigzag_shard`."""
    perm = zigzag_indices(x.shape[1], num_devices)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm), dtype=perm.dtype)
    return jnp.take(x, jnp.asarray(inv), axis=1)


def make_ring_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = False, window: int = 0,
    layout: str = "contiguous", batch_axis=None, remat: bool = False,
):
    """Jitted f(q, k, v) -> out with the sequence dim sharded over ``axis``.

    Inside each step the local K/V shard is consumed and then rotated one
    hop around the ring (``ppermute``); after axis_size steps every query
    has seen every key. The accumulator is the online-softmax triple
    (m, l, o), so the result equals exact softmax attention — verified
    against ``full_attention`` — not an approximation.

    ``window > 0`` = mistral-style sliding window (implies causal). Blocks
    entirely outside every local query's window skip their compute exactly
    like fully-future causal blocks — at long T with a small window most
    hops are skips, so wall time approaches O(T·window) while the exact
    result is preserved.

    ``batch_axis`` (a second mesh axis) composes data parallelism: place
    q/k/v with P(batch_axis, axis) and each dp shard runs an independent
    ring over its own batch rows.

    ``remat=True`` wraps each ring hop in ``jax.checkpoint``: the backward
    pass recomputes the hop's scores instead of keeping every hop's
    intermediates alive — activation memory stops scaling with axis_size
    (the standard trade for long-context training; FLOPs roughly +1x fwd).
    """
    check(window >= 0, "window must be >= 0, got %d", window)
    check(layout in ("contiguous", "zigzag"),
          "layout must be 'contiguous' or 'zigzag', got %r", layout)
    causal = causal or window > 0
    zigzag = layout == "zigzag"

    def _local(q, k, v):
        size = axis_size(axis)
        idx = jax.lax.axis_index(axis)
        b, t_local, h, d = q.shape
        scale = 1.0 / jnp.sqrt(float(d))

        if zigzag:
            # device dev holds chunks (dev, 2N-1-dev) of 2N chunks: one
            # early + one late, so causal score work is equal on every
            # device (inputs must be pre-permuted with zigzag_shard)
            c = t_local // 2

            def dev_pos(dev):
                return jnp.concatenate([
                    dev * c + jnp.arange(c),
                    (2 * size - 1 - dev) * c + jnp.arange(c),
                ])
        else:

            def dev_pos(dev):
                return dev * t_local + jnp.arange(t_local)

        q_pos = dev_pos(idx)

        # pcast-to-varying: fresh constants enter the scan carry as
        # device-varying values (the step output varies over the axis)

        m = pcast(
            jnp.full((b, h, t_local), _NEG_INF, dtype=q.dtype),
            axis, to="varying",
        )
        l = pcast(
            jnp.zeros((b, h, t_local), dtype=q.dtype), axis, to="varying"
        )
        o = jnp.zeros_like(q)
        perm = [(i, (i + 1) % size) for i in range(size)]

        # block 0 (the local K/V shard) is consumed before any rotation,
        # and each scan step rotates THEN consumes — size-1 rotations
        # total, none discarded
        m, l, o = _block_accumulate(
            q, k, v, m, l, o, q_pos, dev_pos(idx), causal, scale, window,
        )

        def step(carry, step_idx):
            k_cur, v_cur, m, l, o = carry
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            # after `step_idx` rotations this device holds the shard that
            # started at ring position (idx - step_idx) mod size
            src = (idx - step_idx) % size
            k_pos = dev_pos(src)
            if causal and not zigzag:
                # a block entirely in this device's future is fully masked,
                # and with a sliding window so is a block entirely OLDER
                # than every local query's window: skip the einsum/exp work
                # (the rotation still runs — the ring schedule needs every
                # hop). Divergent across devices by design; no collectives
                # inside the branches. Window overlap test: the youngest
                # key of block src is (src+1)*t_local - 1; the oldest local
                # query is idx*t_local; attendable iff their distance is
                # inside the window.
                needed = src <= idx
                if window > 0:
                    needed &= (
                        idx * t_local - ((src + 1) * t_local - 1)
                    ) < window
                m, l, o = jax.lax.cond(
                    needed,
                    lambda ops: _block_accumulate(
                        q, ops[0], ops[1], ops[2], ops[3], ops[4],
                        q_pos, k_pos, causal, scale, window,
                    ),
                    lambda ops: (ops[2], ops[3], ops[4]),
                    (k_cur, v_cur, m, l, o),
                )
            elif causal and window > 0:
                # zigzag + window: a hop IS fully masked when both of the
                # block's chunks fall outside every local query's window.
                # Per (q chunk, k chunk) pair the banded mask has a hit
                # iff q_hi >= k_lo (causal reach) and q_lo - k_hi < W
                # (window reach); the hop is needed if any of the 4 pairs
                # hits — keeps the documented O(T·W) walltime under zigzag
                def chunk_ranges(dev):
                    early = (dev * c, (dev + 1) * c - 1)
                    late = ((2 * size - 1 - dev) * c,
                            (2 * size - dev) * c - 1)
                    return (early, late)

                needed = False
                for qlo, qhi in chunk_ranges(idx):
                    for klo, khi in chunk_ranges(src):
                        needed |= (qhi >= klo) & ((qlo - khi) < window)
                m, l, o = jax.lax.cond(
                    needed,
                    lambda ops: _block_accumulate(
                        q, ops[0], ops[1], ops[2], ops[3], ops[4],
                        q_pos, k_pos, causal, scale, window,
                    ),
                    lambda ops: (ops[2], ops[3], ops[4]),
                    (k_cur, v_cur, m, l, o),
                )
            else:
                # zigzag pure-causal: no hop is ever fully masked (every
                # device holds an early chunk every other device's late
                # queries can see) — the BALANCE is the optimization;
                # positions make the masking exact
                m, l, o = _block_accumulate(
                    q, k_cur, v_cur, m, l, o, q_pos, k_pos, causal, scale,
                    window,
                )
            return (k_cur, v_cur, m, l, o), None

        # prevent_cse=False: inside lax.scan the problematic CSE cannot
        # happen (per the jax.checkpoint docs), so skip the optimization
        # barriers it would otherwise insert around every hop
        step_fn = (
            jax.checkpoint(step, prevent_cse=False) if remat else step
        )
        (k, v, m, l, o), _ = jax.lax.scan(
            step_fn, (k, v, m, l, o), jnp.arange(1, size)
        )
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return o / denom

    # batch_axis composes data parallelism on a multi-axis mesh: the
    # batch dim shards over it while seq shards over ``axis`` (each
    # dp-shard runs its own independent ring — no cross-talk)
    spec = P(batch_axis, axis)
    _sharded = jax.jit(
        shard_map(
            _local,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
    )

    def _wrapped(q, k, v):
        _group_ratio(q, k, v)  # validate heads before tracing
        if zigzag:
            n = mesh.shape[axis]
            check(q.shape[1] % (2 * n) == 0,
                  "zigzag needs seq len %% 2*axis_size == 0 (T=%d, n=%d)",
                  q.shape[1], n)
        return _sharded(q, k, v)

    return _wrapped


def make_ulysses_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = False, window: int = 0,
    local_attention=None, batch_axis=None,
):
    """Jitted f(q, k, v) -> out: all-to-all sequence↔head re-sharding.

    Each device trades its sequence shard of every head for the FULL
    sequence of heads/axis_size heads, runs exact local attention (or a
    supplied ``local_attention(q, k, v)`` kernel — e.g. a Pallas flash
    kernel), and the second all-to-all restores [seq-sharded, all heads].

    A custom kernel owns its own masking, so combining ``causal=True``
    with ``local_attention`` is rejected rather than silently dropped.
    ``batch_axis`` composes data parallelism exactly as in
    :func:`make_ring_attention`.
    """
    check(window >= 0, "window must be >= 0, got %d", window)
    check(
        not ((causal or window > 0) and local_attention is not None),
        "pass causality/windowing inside your local_attention kernel; the "
        "flags only configure the built-in full_attention",
    )
    n_shards = mesh.shape[axis]

    def _local(q, k, v):
        # [B, T_local, H, D] -> [B, T, H/size, D]: gather seq, scatter heads
        def seq_to_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        def heads_to_seq(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        fn = local_attention if local_attention is not None else partial(
            full_attention, causal=causal, window=window
        )
        out = fn(qh, kh, vh)
        return heads_to_seq(out)

    def _wrapped(q, k, v):
        check(
            q.shape[2] % n_shards == 0,
            "ulysses needs heads %% axis_size == 0 (got %d heads over %d)",
            q.shape[2], n_shards,
        )
        # GQA: KV heads re-shard over the same axis, so they must divide
        # too (each device then holds H/size query heads against Hk/size
        # KV heads — the group ratio is preserved locally)
        check(
            k.shape[2] % n_shards == 0,
            "ulysses needs kv_heads %% axis_size == 0 (got %d over %d)",
            k.shape[2], n_shards,
        )
        _group_ratio(q, k, v)
        return _sharded(q, k, v)

    u_spec = P(batch_axis, axis)
    _sharded = jax.jit(
        shard_map(
            _local,
            mesh=mesh,
            in_specs=(u_spec, u_spec, u_spec),
            out_specs=u_spec,
            # pallas_call out_shapes carry no varying-mesh-axes metadata,
            # so custom kernels cannot pass the vma check
            check_vma=local_attention is None,
        )
    )
    return _wrapped


def make_pallas_flash_local(causal: bool = False, block_sizes=None):
    """A ``local_attention`` kernel for ``make_ulysses_attention`` backed by
    the Pallas TPU flash-attention kernel (VMEM-resident blockwise softmax
    on the MXU — the hot-op kernel the all-to-all schedule is built to
    host). TPU-only (Mosaic lowering); adapts this module's [B, T, H, D]
    layout to the kernel's [B, H, T, D]. The XLA path materializes T×T
    scores in HBM, flash never does; its timing against XLA on the chip
    is not measured (chip_smoke.py establishes that it compiles and
    matches ``full_attention`` at T=2048 causal).
    """
    import math

    from dmlc_tpu.utils.jax_compat import import_pallas

    import_pallas()  # before jax's own kernel imports it whole
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    def _block(t: int, cap: int) -> int:
        """Largest divisor of t that is <= cap and a multiple of 128 (the
        Pallas kernel requires seq_len % block == 0; the MXU wants lane
        multiples). Falls back to t itself for short sequences."""
        for d in range(min(cap, t) // 128 * 128, 0, -128):
            if t % d == 0:
                return d
        return t

    def kernel(q, k, v):
        # the Pallas kernel wants matched head counts; GQA KV heads are
        # materialized to H here (local cost ∝ T·H·D — what MHA would pay)
        if k.shape[2] != q.shape[2]:
            rep = _group_ratio(q, k, v)
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / math.sqrt(q.shape[-1])
        bs = block_sizes
        if bs is None:
            # big q/k blocks keep the MXU fed and the grid small (the
            # kernel's own defaults are far smaller)
            t = q.shape[1]
            bq = _block(t, 1024)
            bk = _block(t, 2048)
            bs = BlockSizes(
                block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
                block_q_major_dkv=bq, block_k_major_dkv=bk,
                block_q_dkv=bq, block_k_dkv=bk,
                block_q_dq=bq, block_k_dq=bk, block_k_major_dq=bk,
            )
        out = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            sm_scale=scale,
            block_sizes=bs,
        )
        return out.transpose(0, 2, 1, 3)

    return kernel

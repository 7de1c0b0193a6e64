"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference predates pipelined model training (SURVEY §2.9 lists no PP);
this realizes the extension point the TPU-first way, completing the
parallelism matrix next to dp (allreduce), mp (feature-sharded), sp
(ring/ulysses attention) and ep (MoE dispatch):

- the model is N identical-structure STAGES whose parameters carry a
  leading stage dim sharded over the ``pp`` axis (each device materializes
  one stage — model memory scales out with depth);
- a batch is split into M microbatches; the schedule runs M + N - 1 ticks
  inside ONE ``lax.scan``: at tick t, device i computes its stage on
  microbatch t - i and hands the activation to device i+1 with a single
  ``ppermute`` hop (neighbor traffic only — ICI-friendly, no host);
- the classic GPipe bubble applies: N - 1 of the ticks are fill/drain, so
  efficiency is M / (M + N - 1) — raise M to amortize.

The schedule is exact: outputs equal folding the stages sequentially
(``pipeline_oracle``), including gradients through the scan + ppermute
(tests/test_pipeline_parallel.py, 8-stage virtual mesh).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size, pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.utils.logging import check


def pipeline_oracle(stage_fn: Callable, params, x):
    """Sequential reference: fold every stage over x (stage s uses
    ``tree_map(lambda a: a[s], params)``)."""
    n_stages = jax.tree_util.tree_leaves(params)[0].shape[0]
    y = x
    for s in range(n_stages):
        p_s = jax.tree_util.tree_map(lambda a: a[s], params)
        y = stage_fn(p_s, y)
    return y


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable,
    num_microbatches: int,
    axis: str = "pp",
    batch_axis=None,
):
    """Jitted f(params, x[batch, ...]) -> y with GPipe microbatch schedule.

    ``stage_fn(stage_params, act) -> act`` is one stage (shapes preserved);
    ``params`` leaves have leading dim = axis size (one stage per device,
    sharded P(axis) by :func:`shard_pipeline_params`). ``x``'s batch dim
    must divide into ``num_microbatches``. With ``batch_axis=None`` x/y
    are replicated across the axis; with ``batch_axis="dp"`` (a second
    mesh axis) the batch dim shards over it — pass x placed P(batch_axis)
    — and each dp shard streams its own microbatches, so the PER-SHARD
    batch must divide ``num_microbatches``.
    """
    n_stages = mesh.shape[axis]
    m = num_microbatches

    def _local(params, x):
        idx = jax.lax.axis_index(axis)
        size = axis_size(axis)
        batch = x.shape[0]
        mb = batch // m
        micro = x.reshape(m, mb, *x.shape[1:])
        # pcast-to-varying: the scan outputs vary over the axis, so the
        # initial carries must too (same trick as the ring-attention scan)
        state = pcast(
            jnp.zeros_like(micro[0]), axis, to="varying"
        )  # activation arriving from my left
        outputs = pcast(jnp.zeros_like(micro), axis, to="varying")
        perm = [(i, i + 1) for i in range(size - 1)]  # forward handoff

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped during drain ticks —
            # those results are never collected); others consume the
            # activation handed over last tick
            inp = jnp.where(
                idx == 0, micro[jnp.clip(t, 0, m - 1)], state
            )
            # device i participates only while t - i lands on a real
            # microbatch; on fill/drain ticks substitute a REAL microbatch
            # for the zero-initialized carry. The discarded results never
            # reach outputs or any valid tick downstream, but computing on
            # zeros would let stage fns with zero-singularities (norms,
            # divisions) produce NaN primals whose VJPs poison gradients
            # through 0*NaN even though the forward is masked.
            valid = (t >= idx) & (t - idx < m)
            inp = jnp.where(valid, inp, micro[0])
            out = stage_fn(jax.tree_util.tree_map(lambda a: a[0], params),
                           inp)
            # the LAST stage's output for microbatch t - (size - 1)
            done = t - (size - 1)
            collect = (idx == size - 1) & (done >= 0)
            outputs = outputs.at[jnp.clip(done, 0, m - 1)].set(
                jnp.where(collect, out, outputs[jnp.clip(done, 0, m - 1)])
            )
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(m + size - 1)
        )
        # outputs live on the last stage only; psum replicates them (all
        # other shards contribute zeros)
        outputs = jax.lax.psum(
            jnp.where(idx == size - 1, outputs, jnp.zeros_like(outputs)),
            axis_name=axis,
        )
        return outputs.reshape(batch, *x.shape[1:])

    # batch_axis composes dp: each dp-shard streams its own microbatches
    # through the same per-device stages
    sharded = jax.jit(
        shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(axis), P(batch_axis)),
            out_specs=P(batch_axis),
        )
    )

    def _wrapped(params, x):
        leading = jax.tree_util.tree_leaves(params)[0].shape[0]
        check(leading == n_stages,
              "params lead dim %d != pipeline stages %d", leading, n_stages)
        # the constraint is per batch shard: each dp shard streams its own
        # microbatches
        dp = mesh.shape[batch_axis] if batch_axis is not None else 1
        check(x.shape[0] % dp == 0,
              "batch %d must divide over %s size %d", x.shape[0],
              batch_axis, dp)
        local_batch = x.shape[0] // dp
        check(local_batch % m == 0 and local_batch >= m,
              "per-shard batch %d must divide into %d microbatches",
              local_batch, m)
        return sharded(params, x)

    return _wrapped


def shard_pipeline_params(params, mesh: Mesh, axis: str = "pp"):
    """Place stage-stacked params (leading dim = n_stages) one stage per
    device over ``axis``."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))), params
    )

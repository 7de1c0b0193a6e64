"""Factorization machines over COO device batches.

The libfm format the reference parses (libfm_parser.h) exists to feed this
model family; the reference ships the parser and leaves the model downstream.
TPU-first formulation: all per-entry work is gathers + segment_sums (static
shapes), and the O(nnz·K) factor math is batched so XLA can keep it on the
vector units. On one device the step scatter-adds each entry's update
into the rows it names and never passes over the table; on a mesh the
entries are reduced to a dense gradient for the psum.

score(x) = b + Σ_i w_i x_i + ½ Σ_k [(Σ_i v_ik x_i)² − Σ_i v_ik² x_i²]
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.collective.device import bucketed_psum
from dmlc_tpu.models.linear import (
    _margin_grad,
    _suppress_donation_warnings,
    step_batch,
)
from dmlc_tpu.obs.device_telemetry import instrumented_jit
from dmlc_tpu.ops.spmv import expand_row_ids, spmv
from dmlc_tpu.parallel.partition import match_partition_rules, shard_params
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import check


class FMParam(Parameter):
    objective = field(str, "logistic")
    learning_rate = field(float, 0.05, lower_bound=0.0)
    l2 = field(float, 0.0, lower_bound=0.0)
    num_factors = field(int, 8, lower_bound=1)
    num_features = field(int, 0)
    init_scale = field(float, 0.01, lower_bound=0.0)


def init_fm_params(
    num_features: int, num_factors: int, init_scale: float = 0.01, seed: int = 0
) -> Dict:
    key = jax.random.PRNGKey(seed)
    return {
        "w": jnp.zeros((num_features,), dtype=jnp.float32),
        "b": jnp.zeros((), dtype=jnp.float32),
        "v": init_scale
        * jax.random.normal(key, (num_features, num_factors), dtype=jnp.float32),
    }


#: Data-parallel placement for {"w": [F], "b": scalar, "v": [F, K]}:
#: everything replicated, the batch shards, grads psum in-graph. Linted
#: by scripts/check_partition_rules.py like LINEAR_PARTITION_RULES.
FM_PARTITION_RULES = ((r"^(w|b|v)$", P()),)


def _fm_entry_grads(params, batch, objective: str):
    """Loss sums and the per-entry gradient contributions of one COO
    batch shard: entry e of row r at feature i adds ``dw[e]`` to w_i's
    gradient and ``dv[e]`` to v_i's. How they reach the parameters is the
    caller's: scatter-added into the table (single device) or reduced to
    dense grads for the psum (mesh).

    The ``step.*`` scopes name the step's phases in the compiled
    program's metadata (shared with models/linear.py), so a device
    profile can be read by phase; they change no operation."""
    label = batch["label"]
    weight = batch["weight"]
    values = batch["values"]
    indices = batch["indices"]
    num_rows = label.shape[0]

    with jax.named_scope("step.gather"):
        # offsets → row ids on device (local per shard under shard_map)
        row_ids = expand_row_ids(batch["offsets"], values.shape[0])
        v_e = jnp.take(params["v"], indices, axis=0)  # [nnz, K]
    with jax.named_scope("step.forward"):
        xv = values[:, None] * v_e  # [nnz, K]
        s = jax.ops.segment_sum(xv, row_ids, num_segments=num_rows)  # [B, K]
        q = jax.ops.segment_sum(xv * xv, row_ids, num_segments=num_rows)
        linear = spmv(values, indices, row_ids, params["w"], num_rows)
        margin = params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
        loss, gmargin = _margin_grad(objective, margin, label)
        loss_sum = jnp.sum(weight * loss)
    with jax.named_scope("step.backward"):
        wg = weight * gmargin  # [B]
        gb = jnp.sum(wg)
        dw = wg[row_ids] * values  # [nnz]
        # dv[e,k] = x_e * (s[r,k] − x_e v[i,k]), scaled by wg[r]
        s_e = jnp.take(s, row_ids, axis=0)  # [nnz, K]
        dv = dw[:, None] * (s_e - xv)
    return dw, gb, dv, loss_sum, jnp.sum(weight)


#: factor-table rows one scatter-add of the update loop covers. Timed on
#: the v5e in the kdd12-fm cell (PERF.md, PR 26): 512 to 2048 read the
#: same step, 8192 costs 0.6 ms of a 14.2 ms step in slots past the last
#: distinct id.
_UPDATE_CHUNK = 2048


def _scatter_add_rows(w, v, indices, dw, dv):
    """``w[i] += Σ dw[e]`` and ``v[i] += Σ dv[e]`` over the entries e that
    name feature i, into ``w`` and ``v`` themselves (in place when the
    caller donated them). A row no entry names is not written; a padded
    entry adds its 0 to feature 0.

    The entries of one id are summed first and reach its row in one
    add. Ids repeat within a batch (thousands of times for the popular
    ones under a power law), and entry-by-entry adds into a parameter
    much larger than the update round at the parameter's magnitude each
    time: against a float64 step that read 20 times the error of a dense
    gradient's one subtraction. Summing first keeps that one rounding.

    On the chip a row scatter-add is serial, ~0.1 µs a slot whether the
    slot's id is in range or dropped, so the distinct ids are sorted to
    the front and ``v`` takes them ``_UPDATE_CHUNK`` slots at a time until
    the last slot that holds one. ``w``'s 1-D scatter costs a pass over
    ``w`` whatever the number of slots, so it is made once."""
    n = indices.shape[0]
    order = jnp.argsort(indices)
    sorted_ids = indices[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    slot_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    # every entry's slot at the entry's own place: dv is not permuted
    slot = jnp.zeros((n,), jnp.int32).at[order].set(
        slot_sorted, unique_indices=True)
    sum_w = jax.ops.segment_sum(dw, slot, num_segments=n)
    sum_v = jax.ops.segment_sum(dv, slot, num_segments=n)
    # slot j holds the j-th distinct id; the slots after the last hold ids
    # past the table, which the scatter drops (distinct, as promised)
    pad = (-n) % _UPDATE_CHUNK
    ids = (w.shape[0] + jnp.arange(n + pad, dtype=jnp.int32)).at[
        slot_sorted].set(sorted_ids)
    sum_v = jnp.pad(sum_v, ((0, pad), (0, 0)))
    flags = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
    w = w.at[ids[:n]].add(sum_w, **flags)

    def add_chunk(i, v):
        at = i * _UPDATE_CHUNK
        return v.at[lax.dynamic_slice_in_dim(ids, at, _UPDATE_CHUNK)].add(
            lax.dynamic_slice_in_dim(sum_v, at, _UPDATE_CHUNK), **flags)

    distinct = slot_sorted[-1] + 1
    chunks = (distinct + _UPDATE_CHUNK - 1) // _UPDATE_CHUNK
    return w, lax.fori_loop(0, chunks, add_chunk, v)


def make_fm_train_step(
    mesh: Optional[Mesh],
    num_features: int,
    objective: str = "logistic",
    learning_rate: float = 0.05,
    l2: float = 0.0,
    axis: str = "dp",
    param_specs=None,
    donate_batch: bool = False,
):
    """Jitted FM SGD step over COO batches.

    Single device (``mesh is None``): the update touches only the rows
    the batch names. The entries' contributions, scaled by
    ``-learning_rate / weight_sum``, are scatter-ADDED into ``w`` and
    ``v`` (:func:`_scatter_add_rows`; ids repeat within a batch); no
    gradient of the table's shape exists. ``l2 > 0`` adds one scaling
    pass over the table before the scatter-add:
    ``v - lr*(g + l2*v) = v*(1 - lr*l2) - lr*g``.

    Mesh: a psum needs one buffer of a fixed shape, so the entries are
    reduced to dense grads and ONE fused (dtype-bucketed) in-graph psum
    carries the [F,K] factor grads, [F] linear grads and loss scalars
    across ICI as a single contiguous f32 buffer, then a dense update.

    ``donate_batch=True`` (single-device path) donates params AND the
    batch arrays, the same contract as
    :func:`~dmlc_tpu.models.linear.make_linear_train_step`: XLA reuses
    the H2D landing buffers and scatters into the factor table in place
    (without it the step copies the table first) — only for streaming
    callers that rebind params each step and never touch a batch after
    its step (DeviceFeed loops, FMLearner)."""
    check(num_features > 0, "num_features required")

    if mesh is None:

        def step(params, batch):
            dw, gb, dv, loss_sum, wsum = _fm_entry_grads(
                params, batch, objective)
            with jax.named_scope("step.update"):
                denom = jnp.maximum(wsum, 1e-12)
                scale = -learning_rate / denom
                w, v = params["w"], params["v"]
                if l2:
                    w = w * (1.0 - learning_rate * l2)
                    v = v * (1.0 - learning_rate * l2)
                w, v = _scatter_add_rows(
                    w, v, batch["indices"], scale * dw, scale * dv)
                params = {
                    "w": w,
                    "b": params["b"] - learning_rate * (gb / denom),
                    "v": v,
                }
            return params, {"loss_sum": loss_sum, "weight_sum": wsum}

        fn = instrumented_jit(
            step, "fm.step",
            donate_argnums=(0, 1) if donate_batch else (),
        )
        return _suppress_donation_warnings(fn) if donate_batch else fn

    # Entries arrive SHARDED (ShardedCSRBatch: per-shard sections, local
    # row ids) — each device holds only its own nnz; no global mask.
    batch_specs = {
        "label": P(axis),
        "weight": P(axis),
        "indices": P(axis),
        "values": P(axis),
        "offsets": P(axis),
    }

    if param_specs is None:
        param_specs = match_partition_rules(
            FM_PARTITION_RULES,
            jax.eval_shape(lambda: init_fm_params(max(num_features, 1), 2)),
        )

    def _sharded(params, batch):
        dw, gb, dv, loss_sum, wsum = _fm_entry_grads(params, batch, objective)
        with jax.named_scope("step.scatter"):
            indices = batch["indices"]
            gw = jax.ops.segment_sum(dw, indices, num_segments=num_features)
            gv = jax.ops.segment_sum(dv, indices, num_segments=num_features)
        # gradients never round-trip through host numpy: one bucketed
        # in-graph psum carries the whole gradient pytree across ICI
        gw, gb, gv, loss_sum, wsum = bucketed_psum(
            (gw, gb, gv, loss_sum, wsum), axis=axis
        )
        with jax.named_scope("step.update"):
            denom = jnp.maximum(wsum, 1e-12)
            params = {
                "w": params["w"] - learning_rate * (gw / denom + l2 * params["w"]),
                "b": params["b"] - learning_rate * (gb / denom),
                "v": params["v"] - learning_rate * (gv / denom + l2 * params["v"]),
            }
        return params, {"loss_sum": loss_sum, "weight_sum": wsum}

    step = shard_map(
        _sharded, mesh=mesh,
        in_specs=(param_specs, batch_specs),
        out_specs=(param_specs, P()),
    )
    return instrumented_jit(step, "fm.step", donate_argnums=(0,))


class FMLearner:
    """uri → fitted FM params over a DeviceFeed (csr layout)."""

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        self.param = FMParam()
        self.param.init(hyper)
        self.mesh = mesh
        self.params = None
        self._step = None
        self._nf = None
        self._unlisten = None
        if mesh is not None:
            import weakref

            from dmlc_tpu import collective

            ref = weakref.ref(self)

            def _membership_cb():
                learner = ref()
                if learner is not None and learner.params is not None:
                    learner.reshard()

            self._unlisten = collective.on_membership_change(_membership_cb)

    def _ensure(self, num_features: int):
        if self.params is None:
            nf = self.param.num_features or num_features
            self.params = init_fm_params(
                nf, self.param.num_factors, self.param.init_scale
            )
            self._nf = nf
            if self.mesh is not None:
                self.params = shard_params(
                    self.params, self.mesh, rules=FM_PARTITION_RULES
                )
        if self._step is None:
            self._step = make_fm_train_step(
                self.mesh,
                self._nf or self.param.num_features or num_features,
                objective=self.param.objective,
                learning_rate=self.param.learning_rate,
                l2=self.param.l2,
                # the fit loop rebinds params every step and never touches
                # a batch after its step — the donation contract holds
                donate_batch=self.mesh is None,
            )

    def reshard(self, mesh: Optional[Mesh] = None) -> None:
        """Elastic re-entry hook (see LinearLearner.reshard): re-place the
        factor table + linear weights on a mesh rebuilt over the current
        device set and drop the traced step."""
        if self.mesh is None or self.params is None:
            return
        if mesh is None:
            check(
                len(self.mesh.axis_names) == 1,
                "pass mesh= to reshard a multi-axis mesh",
            )
            mesh = Mesh(np.asarray(jax.devices()), self.mesh.axis_names)
        self.mesh = mesh
        self.params = shard_params(
            jax.device_get(self.params), mesh, rules=FM_PARTITION_RULES
        )
        self._step = None

    def fit_feed(self, feed, epochs: int = 1, log_every: int = 0,
                 snapshotter=None, start_epoch: int = 0, history=None):
        """Train over a csr DeviceFeed; ``log_every`` (epochs) also logs
        the feed's per-stage stall breakdown (device.feed.stall_breakdown).

        ``snapshotter``/``start_epoch``/``history`` follow the same
        preemption-proof contract as LinearLearner.fit_feed: epoch
        boundaries hand a state tree to the async snapshot writer, a
        preemption notice finalizes a just-in-time commit and raises
        ``Preempted`` (see docs/robustness.md "Preemption & resume")."""
        from dmlc_tpu.models.linear import EpochMetrics

        check(feed.spec.layout == "csr", "FM consumes csr batches")
        # see LinearLearner.fit_feed: mesh steps need the sharded layout
        check(
            getattr(feed, "_mesh", None) is self.mesh,
            "feed mesh and learner mesh must match (csr entry layouts "
            "differ between mesh and single-device runs)",
        )
        from dmlc_tpu import obs
        from dmlc_tpu.models.fitloop import FitLoopObs
        from dmlc_tpu.resilience import Preempted, preempt

        fl = FitLoopObs("fm")
        history = list(history) if history else []
        for epoch in range(start_epoch, epochs):
            acc = EpochMetrics()
            nstep = 0
            preempted = False
            t0 = time.monotonic_ns()
            with obs.span("epoch", model="fm", epoch=epoch):
                for batch in feed:
                    self._ensure(self.param.num_features)
                    with obs.span("train_step", model="fm", step=nstep,
                                  **obs.current_batch()):
                        obs.flow_step(obs.current_flow(), "chunk")
                        self.params, metrics = self._step(
                            self.params, step_batch(batch, "csr")
                        )
                    acc.add(metrics)
                    fl.note_step()
                    nstep += 1
                    if snapshotter is not None and preempt.poll():
                        preempted = True
                        break
            if preempted:
                snapshotter.finalize()
                raise Preempted(
                    "preempted in epoch %d after %d steps" % (epoch, nstep))
            fl.finish_epoch(
                epoch, nstep, t0, acc, history, feed=feed,
                log_every=log_every, params=self.params,
                snapshotter=snapshotter,
                snap_state=(None if snapshotter is None else
                            lambda e=epoch: self._snapshot_state(
                                feed, e, history)),
                # the step was built for self.mesh (_ensure): without one
                # every step scatter-adds, on a mesh none does
                sparse_update_steps=nstep if self.mesh is None else 0,
            )
            if epoch + 1 < epochs:
                feed.before_first()
        return history

    def _snapshot_state(self, feed, epoch: int, history) -> Dict:
        """Job-snapshot state tree at one epoch boundary (see
        LinearLearner._snapshot_state — FM has no velocity term)."""
        from dmlc_tpu.obs import audit

        state = {
            "model": {"params": dict(self.params)},
            "epoch": int(epoch),
            "history": [float(x) for x in history],
            "rng": None,
            "audit": audit.auditor().export_state(),
        }
        parser = getattr(feed, "_parser", None)
        if hasattr(parser, "snapshot_state"):
            state["data"] = {"parser": parser.snapshot_state()}
        return state

    def restore_snapshot_model(self, model: Dict) -> None:
        """Re-place a snapshot's host FM params on device (mesh-placed
        when this learner runs on a mesh)."""
        self.params = {k: jnp.asarray(v) for k, v in model["params"].items()}
        if self.mesh is not None:
            self.params = shard_params(
                self.params, self.mesh, rules=FM_PARTITION_RULES)

    def predict_batch(self, batch) -> np.ndarray:
        num_rows = int(batch["label"].shape[0])
        row_ids = expand_row_ids(batch["offsets"], batch["values"].shape[0])
        v_e = jnp.take(self.params["v"], batch["indices"], axis=0)
        xv = batch["values"][:, None] * v_e
        s = jax.ops.segment_sum(xv, row_ids, num_segments=num_rows)
        q = jax.ops.segment_sum(xv * xv, row_ids, num_segments=num_rows)
        linear = spmv(
            batch["values"], batch["indices"], row_ids,
            self.params["w"], num_rows,
        )
        return np.asarray(
            self.params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
        )

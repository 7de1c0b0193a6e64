"""Linear learners with data-parallel psum gradient sync.

This is the BASELINE north-star model: ``libsvm file → InputSplit(part=host)
→ parser → device batch → psum(grad) → SGD`` (SURVEY §7 minimum end-to-end
slice). The reference has no learners; this is the allreduce-SGD loop its
downstream (rabit-based) consumers run, built TPU-first:

- the train step is one jitted shard_map over the mesh: local forward +
  gradient, one fused psum per step (large fused buckets are what push ICI
  utilization up — SURVEY §7 hard parts), parameters replicated and donated
- deterministic f32 accumulation: per-shard sums then a single psum, so the
  reduction order is fixed and CPU-vs-TPU runs are comparable bit-for-bit at
  the f32 level
- dense layout for small feature spaces (HIGGS: one [B,F]·[F] matvec on the
  MXU) and COO/segment-sum for sparse (dmlc_tpu.ops.spmv)
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.collective.device import bucketed_psum
from dmlc_tpu.models import fitloop
from dmlc_tpu.models.fitloop import FeedLearner, suppress_donation_warnings
from dmlc_tpu.obs.device_telemetry import instrumented_jit
from dmlc_tpu.ops.objectives import margin_loss_grad
from dmlc_tpu.ops.spmv import expand_row_ids, spmv, spmv_transpose
from dmlc_tpu.parallel.partition import match_partition_rules, shard_params
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import DMLCError, check


class LinearModelParam(Parameter):
    """Hyper-parameters (a dmlc Parameter struct, parameter.h style)."""

    objective = field(
        str,
        "logistic",
        description="Loss: logistic (labels 0/1), squared, or hinge (0/1).",
    )
    learning_rate = field(float, 0.1, lower_bound=0.0)
    l2 = field(float, 0.0, lower_bound=0.0, description="L2 penalty on w.")
    momentum = field(float, 0.0, lower_bound=0.0, upper_bound=1.0)
    num_features = field(int, 0, description="Feature dim (0 = infer).")


def init_linear_params(num_features: int, dtype=jnp.float32) -> Dict:
    """{"w": [F], "b": scalar} — replicated across the mesh."""
    return {
        "w": jnp.zeros((num_features,), dtype=dtype),
        "b": jnp.zeros((), dtype=dtype),
    }


#: Data-parallel placement for {"w": [F], "b": scalar}: everything
#: replicated — only the BATCH shards over the mesh, and the in-graph
#: psum lands identical grads on every device. Declared as a regex
#: partition-rule table (parallel/partition.py) so the placement is
#: data, linted by scripts/check_partition_rules.py, instead of being
#: hard-coded into the step builder.
LINEAR_PARTITION_RULES = ((r"^(w|b)$", P()),)

#: Feature-sharded (dp×mp) placement: the weight vector splits over the
#: model axis (make_feature_sharded_train_step's layout).
LINEAR_MP_PARTITION_RULES = ((r"^w$", P("mp")), (r"^b$", P()))


def linear_predict_dense(params: Dict, x):
    return x @ params["w"] + params["b"]


def margin_grad(objective: str, margin, label):
    """Per-row (loss, dloss/dmargin) — shared with the Pallas fused kernel
    (ops/objectives.py holds the single definition)."""
    try:
        return margin_loss_grad(objective, margin, label)
    except ValueError as err:
        raise DMLCError(str(err)) from err


def _resolve_pallas(use_pallas: Optional[bool], layout: str,
                    objective: str):
    """Validate + default the Pallas kernel switch (env
    DMLC_TPU_PALLAS=1); shared by the mesh and hostsync step builders.

    Returns the kernel MODE, not a bare bool: False (off), "dense" (the
    fused whole-step kernel, dense layout), or "spmv" (the COO
    segment-sum kernel on the csr margin path; the feature-direction
    scatter stays on XLA). Truthiness is preserved, so boolean callers
    keep working."""
    if use_pallas is None:
        import os

        use_pallas = os.environ.get("DMLC_TPU_PALLAS", "0") == "1"
    if not use_pallas:
        return False
    if layout == "dense":
        from dmlc_tpu.ops.objectives import OBJECTIVES

        check(objective in OBJECTIVES,
              "pallas path unavailable for this objective")
        return "dense"
    return "spmv"


def _build_local_grads(objective: str, layout: str, num_features: int,
                       use_pallas: bool, pallas_interpret: bool = False):
    """The per-shard gradient core: f(params, batch) -> (gw, gb, loss_sum,
    weight_sum), no cross-device communication. ONE definition feeds every
    sync flavor — the in-graph SPMD step, the single-device step, and the
    legacy host-allreduce twin — so their local math is identical by
    construction (the parity suites lean on this).

    The Pallas kernels compile for the backend they are on (Mosaic
    targets the TPU and fails loudly elsewhere); ``pallas_interpret`` is
    the caller's explicit request for interpreter mode (CPU tests).

    The ``step.*`` scopes name the step's phases in the compiled
    program's metadata (shared with models/fm.py), so a device profile
    can be read by phase; they change no operation."""

    def _forward(params, batch):
        if layout == "dense":
            return batch["x"] @ params["w"] + params["b"], None
        # the batch carries CSR offsets (small H2D payload); expand to
        # per-entry row ids here, on device. Under the mesh shard_map
        # the shapes are per-shard local, so the same expansion yields
        # local row ids from the shard's local offsets.
        row_ids = expand_row_ids(
            batch["offsets"], batch["values"].shape[0]
        )
        if use_pallas == "spmv":
            from dmlc_tpu.ops.spmv import spmv_pallas

            linear = spmv_pallas(
                batch["values"], batch["indices"], row_ids,
                params["w"], batch["label"].shape[0],
                interpret=pallas_interpret,
            )
        else:
            linear = spmv(
                batch["values"], batch["indices"], row_ids,
                params["w"], batch["label"].shape[0],
            )
        return linear + params["b"], row_ids

    def _local_grads(params, batch):
        label = batch["label"]
        weight = batch["weight"]
        if layout == "dense" and use_pallas:
            from dmlc_tpu.ops.pallas_kernels import fused_linear_grads

            gw, gb, loss_sum, wsum = fused_linear_grads(
                batch["x"], label, weight, params["w"], params["b"],
                objective=objective, interpret=pallas_interpret,
            )
            # the kernel computes in f32; keep the params dtype contract of
            # the XLA path (no silent upcast of bf16 params mid-training)
            return (gw.astype(params["w"].dtype),
                    gb.astype(params["b"].dtype), loss_sum, wsum)
        with jax.named_scope("step.forward"):
            margin, row_ids = _forward(params, batch)
            loss, gmargin = margin_grad(objective, margin, label)
            loss_sum = jnp.sum(weight * loss)
        with jax.named_scope("step.backward"):
            wg = weight * gmargin
            gb = jnp.sum(wg)
        with jax.named_scope("step.scatter"):
            if layout == "dense":
                gw = batch["x"].T @ wg
            else:
                gw = spmv_transpose(
                    batch["values"], batch["indices"], row_ids, wg,
                    num_features,
                )
        weight_sum = jnp.sum(weight)
        return gw, gb, loss_sum, weight_sum

    return _local_grads


def _on_mesh(tree, mesh: Mesh) -> bool:
    """Whether ``tree``'s (first) leaf is already placed on ``mesh`` —
    every leaf of a params tree is placed together (shard_params, or the
    step's own outputs), so one leaf speaks for the tree."""
    leaf = jax.tree_util.tree_leaves(tree)[0]
    sharding = getattr(leaf, "sharding", None)
    return isinstance(sharding, NamedSharding) and sharding.mesh == mesh


def _build_apply(learning_rate: float, l2: float, momentum: float):
    """The SGD update: f(params, velocity, gw, gb, wsum) with the grads
    already reduced. Shared across sync flavors like _build_local_grads."""

    @jax.named_scope("step.update")
    def _apply(params, velocity, gw, gb, wsum):
        denom = jnp.maximum(wsum, 1e-12)
        gw = gw / denom + l2 * params["w"]
        gb = gb / denom
        if momentum > 0.0:
            velocity = {
                "w": momentum * velocity["w"] + gw,
                "b": momentum * velocity["b"] + gb,
            }
            gw, gb = velocity["w"], velocity["b"]
        params = {
            "w": params["w"] - learning_rate * gw,
            "b": params["b"] - learning_rate * gb,
        }
        return params, velocity

    return _apply


def make_linear_train_step(
    mesh: Optional[Mesh],
    objective: str = "logistic",
    learning_rate: float = 0.1,
    l2: float = 0.0,
    momentum: float = 0.0,
    layout: str = "dense",
    num_features: int = 0,
    axis: str = "dp",
    use_pallas: Optional[bool] = None,
    donate_batch: bool = False,
    param_specs=None,
    pallas_interpret: bool = False,
):
    """Build the jitted allreduce-SGD step.

    Returns step(params, velocity, batch) -> (params, velocity, metrics)
    where metrics = {"loss_sum": Σ w·loss, "weight_sum": Σ w} (host divides).
    With ``mesh`` the batch is consumed sharded over ``axis`` and gradients
    cross ICI in one fused psum; without, it is a single-device step.

    ``axis`` may be a tuple of mesh axis names for hybrid data
    parallelism — e.g. ``("dcn", "dp")`` on a
    :func:`~dmlc_tpu.parallel.make_multislice_mesh` shards batch rows over
    slices × chips and the psum lowers to a per-slice ICI reduction plus
    one small cross-slice DCN exchange (outer axis = slices).

    ``use_pallas`` (default: env DMLC_TPU_PALLAS=1) routes the dense
    gradient core through the fused Pallas kernel
    (ops/pallas_kernels.fused_linear_grads); on the csr layout it routes
    the margin SpMV's row reduce through the COO segment-sum kernel
    (ops/spmv.spmv_pallas) while the feature-direction scatter stays on
    XLA. XLA stays the default. The kernels compile through Mosaic for
    the TPU; ``pallas_interpret=True`` runs them in the Pallas
    interpreter instead (how the CPU tests drive this path — never
    inferred from the backend).

    ``donate_batch=True`` donates ALL step inputs — params, velocity, and
    the batch arrays: the H2D landing buffers are released to XLA the
    moment the step consumes them (HBM headroom for the next in-flight
    transfer — SURVEY §7 hard parts: donation) and the parameter update
    is in-place. Only for streaming callers that rebind params/velocity
    each step and never touch a batch after its step (DeviceFeed loops,
    the bench tiers, LinearLearner); reusing a donated input afterward is
    an error by design. Default False keeps every input alive (the mesh
    path has always donated params/velocity — that is unchanged).
    """
    check(layout in ("dense", "csr"), "layout must be dense or csr")
    if layout == "csr":
        check(num_features > 0, "csr layout requires num_features")
    use_pallas = _resolve_pallas(use_pallas, layout, objective)
    _local_grads = _build_local_grads(objective, layout, num_features,
                                      use_pallas, pallas_interpret)
    _apply = _build_apply(learning_rate, l2, momentum)

    if mesh is None:

        def step(params, velocity, batch):
            gw, gb, loss_sum, wsum = _local_grads(params, batch)
            params, velocity = _apply(params, velocity, gw, gb, wsum)
            return params, velocity, {"loss_sum": loss_sum, "weight_sum": wsum}

        # this path historically donated nothing — donation here is purely
        # opt-in (tests and notebooks legitimately reuse inputs)
        fn = instrumented_jit(
            step, "linear.step",
            donate_argnums=(0, 1, 2) if donate_batch else (),
        )
        return suppress_donation_warnings(fn) if donate_batch else fn

    # Mesh path: one shard_map; batch rows sharded, params replicated. The
    # csr layout ships SHARDED entries (ShardedCSRBatch: per-shard entry
    # sections with local row ids, device/csr.py), so each device receives
    # only its own nnz and the segment-sum is purely local — per-device
    # H2D ∝ global_nnz / world, the Criteo-scale contract.
    if layout == "dense":
        batch_specs = {
            "x": P(axis),
            "label": P(axis),
            "weight": P(axis),
        }
    else:
        batch_specs = {
            "label": P(axis),
            "weight": P(axis),
            "indices": P(axis),
            "values": P(axis),
            "offsets": P(axis),
        }

    # parameter placement as DATA: the rule table (or a caller-supplied
    # spec tree) drives both sides of the shard_map signature, so the
    # step's layout contract and shard_params' placement cannot drift
    if param_specs is None:
        template = jax.eval_shape(
            lambda: init_linear_params(max(num_features, 1))
        )
        param_specs = match_partition_rules(LINEAR_PARTITION_RULES, template)

    def _sharded(params, velocity, batch):
        gw, gb, loss_sum, wsum = _local_grads(params, batch)
        # ONE fused allreduce for everything that crosses ICI: grads and
        # the loss/weight scalars ride a single dtype-bucketed in-graph
        # psum (collective.bucketed_psum) — gradients never round-trip
        # through host numpy or collective.allreduce.
        gw, gb, loss_sum, wsum = bucketed_psum(
            (gw, gb, loss_sum, wsum), axis=axis
        )
        params, velocity = _apply(params, velocity, gw, gb, wsum)
        return params, velocity, {"loss_sum": loss_sum, "weight_sum": wsum}

    step = shard_map(
        _sharded,
        mesh=mesh,
        in_specs=(param_specs, param_specs, batch_specs),
        out_specs=(param_specs, param_specs, P()),
    )
    fn = instrumented_jit(
        step, "linear.step",
        donate_argnums=(0, 1, 2) if donate_batch else (0, 1),
    )
    if donate_batch:
        fn = suppress_donation_warnings(fn)

    def placed_step(params, velocity, batch):
        # The step returns mesh-placed params; an unplaced tree coming in
        # (fresh jnp.zeros, a load()) types differently — its avals carry
        # no mesh — and would trace and compile the same bucket a second
        # time. Place it first, as LinearLearner does from step zero; a
        # tree already on the mesh costs one leaf check.
        if not _on_mesh(params, mesh):
            params = shard_params(params, mesh, specs=param_specs)
        if not _on_mesh(velocity, mesh):
            velocity = shard_params(velocity, mesh, specs=param_specs)
        return fn(params, velocity, batch)

    return placed_step


def make_hostsync_train_step(
    objective: str = "logistic",
    learning_rate: float = 0.1,
    l2: float = 0.0,
    momentum: float = 0.0,
    layout: str = "dense",
    num_features: int = 0,
    use_pallas: Optional[bool] = None,
    pallas_interpret: bool = False,
):
    """The legacy host-round-trip twin of the mesh SPMD step: local grads
    on device, ONE fused ``collective.allreduce`` over the active host
    engine (socket tree/ring on CPU clusters), apply on device.

    This is the rabit loop (examples/distributed_sgd.py) behind the
    step(params, velocity, batch) signature, and the ONLY sync flavor
    that works across socket-engine processes (no single ``Mesh`` spans
    them). It shares ``_build_local_grads``/``_build_apply`` with the
    SPMD step, and its reduction — one contiguous same-dtype buffer
    through the engine — mirrors ``bucketed_psum``'s bucket layout, so
    at world 2 (one addition per element on either path) the two sync
    flavors are bit-identical; the ci_checks.sh SPMD smoke pins that.
    In-mesh training should use :func:`make_linear_train_step` — see
    docs/distributed.md "Device collectives" for the migration note.
    """
    check(layout in ("dense", "csr"), "layout must be dense or csr")
    if layout == "csr":
        check(num_features > 0, "csr layout requires num_features")
    use_pallas = _resolve_pallas(use_pallas, layout, objective)
    local = instrumented_jit(
        _build_local_grads(objective, layout, num_features, use_pallas,
                           pallas_interpret),
        "linear.hostsync_grads",
    )
    apply_fn = instrumented_jit(
        _build_apply(learning_rate, l2, momentum), "linear.hostsync_apply"
    )

    def step(params, velocity, batch):
        from dmlc_tpu import collective

        gw, gb, loss_sum, wsum = local(params, batch)
        gw_h = np.asarray(gw)
        scalars = np.asarray(
            [gb, loss_sum, wsum], dtype=gw_h.dtype
        )
        # one fused buffer = one allreduce per step, the same bucket
        # layout bucketed_psum traces in-graph
        reduced = collective.allreduce(
            np.concatenate([gw_h.ravel(), scalars])
        )
        gw_r = jnp.asarray(reduced[: gw_h.size].reshape(gw_h.shape))
        gb_r = jnp.asarray(reduced[gw_h.size])
        wsum_r = jnp.asarray(reduced[gw_h.size + 2])
        params, velocity = apply_fn(params, velocity, gw_r, gb_r, wsum_r)
        return params, velocity, {
            "loss_sum": reduced[gw_h.size + 1],
            "weight_sum": reduced[gw_h.size + 2],
        }

    return step


def make_feature_sharded_train_step(
    mesh: Mesh,
    objective: str = "logistic",
    learning_rate: float = 0.1,
    batch_axis: str = "dp",
    feature_axis: str = "mp",
):
    """dp×mp train step: batch rows sharded over ``batch_axis``, the weight
    vector (and the feature dim of x) sharded over ``feature_axis``.

    This is the TPU-native analog of the reference's parameter-server mode
    (PARITY §2.9): parameter state lives sharded across devices instead of
    on server processes, and the "push/pull" is XLA collectives — a psum of
    partial margins over ``feature_axis`` (the pull of the full model
    response) and a psum of gradients over ``batch_axis`` (the push of data
    shards' updates). Only mp-invariant scalars and [B/dp] vectors cross
    ICI; the [F/mp] gradient never leaves its shard.

    Layouts (global shapes): x [B, F] sharded (dp, mp); label/weight [B]
    sharded (dp); params {"w": [F] sharded (mp), "b": replicated}.
    Returns (step, in_shardings) where in_shardings maps example arrays to
    ``NamedSharding``s for ``jax.device_put``.
    """
    dp = batch_axis
    mp = feature_axis
    # the canonical axis name resolves through the linted rule table; a
    # custom feature_axis keeps the same shape with the name swapped in
    if mp == "mp":
        param_specs = match_partition_rules(
            LINEAR_MP_PARTITION_RULES,
            jax.eval_shape(lambda: init_linear_params(2)),
        )
    else:
        param_specs = {"w": P(mp), "b": P()}

    def _step(params, batch_x, batch_y, batch_w):
        # local shapes: x [B/dp, F/mp], w [F/mp]
        partial_margin = batch_x @ params["w"]
        margin = jax.lax.psum(partial_margin, mp) + params["b"]
        loss, dmargin = margin_grad(objective, margin, batch_y)
        wg = batch_w * dmargin
        # margin is mp-invariant, so wg is too: gw needs only the dp-psum
        gw = jax.lax.psum(batch_x.T @ wg, dp)
        gb = jax.lax.psum(jnp.sum(wg), dp)
        wsum = jax.lax.psum(jnp.sum(batch_w), dp)
        loss_sum = jax.lax.psum(jnp.sum(batch_w * loss), dp)
        denom = jnp.maximum(wsum, 1e-12)
        new_params = {
            "w": params["w"] - learning_rate * gw / denom,
            "b": params["b"] - learning_rate * gb / denom,
        }
        return new_params, {"loss_sum": loss_sum, "weight_sum": wsum}

    step = instrumented_jit(
        shard_map(
            _step,
            mesh=mesh,
            in_specs=(param_specs, P(dp, mp), P(dp), P(dp)),
            out_specs=(param_specs, P()),
        ),
        "linear.step_mp",
        donate_argnums=(0,),
    )
    in_shardings = {
        "x": NamedSharding(mesh, P(dp, mp)),
        "label": NamedSharding(mesh, P(dp)),
        "weight": NamedSharding(mesh, P(dp)),
        "w": NamedSharding(mesh, P(mp)),
        "b": NamedSharding(mesh, P()),
    }
    return step, in_shardings


class LinearLearner(FeedLearner):
    """Convenience trainer: uri → fitted params (the rabit-SGD loop).

    ``sync`` picks the gradient-reduction flavor:

    - ``"spmd"`` (default): the in-graph path — params live mesh-placed
      (``shard_params`` over ``LINEAR_PARTITION_RULES``), the batch
      shards over the mesh, and the allreduce is a bucketed psum traced
      INSIDE the jitted step. Gradients never touch host numpy.
    - ``"host"``: the legacy rabit loop (``make_hostsync_train_step``) —
      the cross-host fallback when the socket engine spans processes no
      single Mesh can.

    The fit loop is :func:`dmlc_tpu.models.fitloop.fit_feed`; a mesh
    learner re-places params and velocity when the mesh's membership
    changes (:meth:`~dmlc_tpu.models.fitloop.FeedLearner.reshard`).
    """

    name = "linear"
    state_trees = ("params", "velocity")

    def __init__(self, mesh: Optional[Mesh] = None, sync: str = "spmd",
                 **hyper):
        check(sync in ("spmd", "host"), "sync must be spmd or host")
        self.param = LinearModelParam()
        self.param.init(hyper)
        self.sync = sync
        self.velocity = None
        self._layout = None
        self._nf = None
        super().__init__(mesh)

    def partition_rules(self):
        return LINEAR_PARTITION_RULES

    def _ensure(self, num_features: int, layout: str):
        if self.params is None:
            nf = self.param.num_features or num_features
            self.params = init_linear_params(nf)
            self.velocity = {
                "w": jnp.zeros_like(self.params["w"]),
                "b": jnp.zeros_like(self.params["b"]),
            }
            self._layout = layout
            self._nf = nf
            if self.mesh is not None and self.sync == "spmd":
                # params live mesh-placed from step zero: the traced step
                # consumes committed arrays, no per-call resharding
                self.params = shard_params(
                    self.params, self.mesh, rules=LINEAR_PARTITION_RULES
                )
                self.velocity = shard_params(
                    self.velocity, self.mesh, rules=LINEAR_PARTITION_RULES
                )
        if self._step is None:
            if self._layout is None:
                # params came from load(): derive what init skipped
                self._layout = layout
                self._nf = (self.param.num_features or num_features
                            or int(self.params["w"].shape[0]))
            if self.sync == "host":
                self._step = make_hostsync_train_step(
                    objective=self.param.objective,
                    learning_rate=self.param.learning_rate,
                    l2=self.param.l2,
                    momentum=self.param.momentum,
                    layout=self._layout,
                    num_features=self._nf,
                )
            else:
                self._step = make_linear_train_step(
                    self.mesh,
                    objective=self.param.objective,
                    learning_rate=self.param.learning_rate,
                    l2=self.param.l2,
                    momentum=self.param.momentum,
                    layout=self._layout,
                    num_features=self._nf,
                    donate_batch=True,  # fit_feed consumes batches once
                )

    def ensure_step(self, spec) -> None:
        self._ensure(spec.num_features, spec.layout)

    def train_step(self, arrays: Dict) -> Dict:
        self.params, self.velocity, metrics = self._step(
            self.params, self.velocity, arrays)
        return metrics

    def fit_uri(self, uri: str, **kw):
        """:func:`dmlc_tpu.models.fitloop.fit_uri` for this learner (its
        arguments and the snapshot / resume contract are listed there)."""
        return fitloop.fit_uri(self, uri, **kw)

    def fit_feed(self, feed, *args, **kw):
        """Train over a DeviceFeed for N epochs; returns per-epoch losses:
        :func:`dmlc_tpu.models.fitloop.fit_feed` for this learner."""
        return fitloop.fit_feed(self, feed, *args, **kw)

    def snapshot_model(self) -> Dict:
        return {"params": dict(self.params),
                "velocity": dict(self.velocity or {})}

    def restore_snapshot_model(self, model: Dict) -> None:
        """Re-place a snapshot's host model/optimizer state on device
        (mesh-placed when this learner runs spmd on a mesh)."""
        self.params = {k: jnp.asarray(v) for k, v in model["params"].items()}
        velocity = model.get("velocity")
        if velocity is not None:
            self.velocity = {k: jnp.asarray(v) for k, v in velocity.items()}
        if self.mesh is not None and self.sync == "spmd":
            self.params = shard_params(
                self.params, self.mesh, rules=LINEAR_PARTITION_RULES)
            if self.velocity is not None:
                self.velocity = shard_params(
                    self.velocity, self.mesh, rules=LINEAR_PARTITION_RULES)

    def predict(self, x: np.ndarray) -> np.ndarray:
        check(self.params is not None, "model not fitted")
        return np.asarray(linear_predict_dense(self.params, jnp.asarray(x)))

    # ---- checkpointing via the Stream surface (SURVEY §5.4) -------------
    def save(self, uri: str) -> None:
        from dmlc_tpu.io.filesystem import create_stream
        from dmlc_tpu.io.serializer import save_obj

        with create_stream(uri, "w") as out:
            save_obj(
                out,
                {
                    "param": self.param.to_dict(),
                    "w": np.asarray(self.params["w"]),
                    "b": np.asarray(self.params["b"]),
                },
            )

    def load(self, uri: str) -> None:
        from dmlc_tpu.io.filesystem import create_stream
        from dmlc_tpu.io.serializer import load_obj

        with create_stream(uri, "r") as stream:
            payload = load_obj(stream)
        self.param.init(payload["param"], allow_unknown=True)
        self.params = {
            "w": jnp.asarray(payload["w"]),
            "b": jnp.asarray(payload["b"]),
        }

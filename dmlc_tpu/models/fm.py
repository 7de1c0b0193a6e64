"""Factorization machines over COO device batches.

The libfm format the reference parses (libfm_parser.h) exists to feed this
model family; the reference ships the parser and leaves the model downstream.
TPU-first formulation: all per-entry work is gathers + segment_sums (static
shapes), and the O(nnz·K) factor math is batched so XLA can keep it on the
vector units. Passes over the entries that share an index vector are one
pass over concatenated columns (on the chip such a pass costs per index,
not per column): the row sums of the forward pass, a row's terms on their
way back to its entries, and the sums of an id's entries, for which the
update sorts the batch's ids once. On one device the step scatter-adds
each id's summed update into the row it names and never passes over the
table; on a mesh with
the table replicated the entries are reduced to a dense gradient for the
psum; on a mesh with the table's factors sharded
(``table_sharding="factors"``) every chip scatter-adds into its own
columns and only the batch and one ``f32[rows]`` psum cross ICI.

score(x) = b + Σ_i w_i x_i + ½ Σ_k [(Σ_i v_ik x_i)² − Σ_i v_ik² x_i²]
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.collective.device import all_gather, bucketed_psum, psum
from dmlc_tpu.models import fitloop
from dmlc_tpu.models.fitloop import FeedLearner, suppress_donation_warnings
from dmlc_tpu.models.linear import margin_grad
from dmlc_tpu.obs.device_telemetry import instrumented_jit
from dmlc_tpu.ops.spmv import expand_row_ids
from dmlc_tpu.parallel.partition import (
    match_partition_rules,
    shard_params,
    sharding_tree,
)
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import check


class FMParam(Parameter):
    objective = field(str, "logistic")
    learning_rate = field(float, 0.05, lower_bound=0.0)
    l2 = field(float, 0.0, lower_bound=0.0)
    num_factors = field(int, 8, lower_bound=1)
    num_features = field(int, 0)
    init_scale = field(float, 0.01, lower_bound=0.0)
    # how a mesh holds the factor table: a whole copy on every chip, or
    # each chip num_factors / chips of its columns (a table wider than
    # one chip's memory); without a mesh there is nothing to choose
    table_sharding = field(
        str, "replicated",
        enum={"replicated": "replicated", "factors": "factors"})


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

#: ``table_sharding="factors"``: chip c of the ``dp`` axis holds columns
#: [c*K/n, (c+1)*K/n) of ``v``; ``w`` and ``b`` stay replicated. The
#: factors of an FM do not interact, so a chip's columns give its share
#: of the interaction term and take their update with nothing of the
#: table's shape crossing ICI.
FM_FACTOR_PARTITION_RULES = ((r"^(w|b)$", P()), (r"^v$", P(None, "dp")))


def fm_partition_rules(table_sharding: str = "replicated"):
    """The rule table of an FM placed as ``table_sharding`` says (over
    the ``dp`` axis, the only one a learner divides anything over)."""
    if table_sharding == "replicated":
        return FM_PARTITION_RULES
    check(table_sharding == "factors",
          "table_sharding must be 'replicated' or 'factors', got %r",
          table_sharding)
    return FM_FACTOR_PARTITION_RULES


def _check_factor_shards(num_factors: int, mesh: Mesh, axis: str) -> None:
    shards = mesh.shape[axis]
    check(num_factors % shards == 0,
          "table_sharding='factors' needs num_factors divisible by the %d "
          "chips of mesh axis %r, got num_factors=%d",
          shards, axis, num_factors)


def _row_sums(params, indices, row_ids, values, num_rows: int):
    """Per row: ``s`` = Σ x_e v_e, ``q`` = Σ (x_e v_e)² (both ``[B, K]``)
    and the linear term Σ x_e w_e, in ONE ``segment_sum`` over the
    concatenated columns; ``xv [nnz, K]`` is handed back for the backward
    pass. On the chip a pass over the entries costs per index, not per
    column, while a row of its target fits one 128-lane tile (PERF.md,
    PR 29), so the three sums share their pass."""
    k = params["v"].shape[1]
    with jax.named_scope("step.gather"):
        v_e = jnp.take(params["v"], indices, axis=0)  # [nnz, K]
        w_e = jnp.take(params["w"], indices, axis=0)  # [nnz]
    with jax.named_scope("step.forward"):
        xv = values[:, None] * v_e  # [nnz, K]
        sums = jax.ops.segment_sum(
            jnp.concatenate([xv, xv * xv, (values * w_e)[:, None]], axis=1),
            row_ids, num_segments=num_rows)  # [B, 2K + 1]
    return xv, sums[:, :k], sums[:, k:2 * k], sums[:, 2 * k]


def _fm_entry_grads(params, batch, objective: str,
                    factor_axis: Optional[str] = None):
    """Loss sums and the per-entry gradient contributions of one COO
    batch shard: entry e of row r at feature i adds ``dw[e]`` to w_i's
    gradient and ``dv[e]`` to v_i's. How they reach the parameters is the
    caller's: scatter-added into the table (single device, factor-sharded
    mesh) or reduced to dense grads for the psum (replicated mesh).

    Passes that share an index vector are one pass over concatenated
    columns: the three row sums of the forward pass (:func:`_row_sums`),
    and a row's ``s`` and ``wg`` on their way back to its entries.

    ``factor_axis``: ``params["v"]`` holds this chip's columns only and
    the batch is the whole step's (``row_ids`` given, global); the
    columns' share of the interaction term is psummed over that axis
    between forward and backward, under ``step.exchange``.

    The ``step.*`` scopes name the step's phases in the compiled
    program's metadata (shared with models/linear.py), so a device
    profile can be read by phase; they change no operation."""
    label = batch["label"]
    weight = batch["weight"]
    values = batch["values"]
    num_rows = label.shape[0]

    with jax.named_scope("step.gather"):
        # offsets → row ids on device (local per shard under shard_map)
        row_ids = batch["row_ids"] if "row_ids" in batch else \
            expand_row_ids(batch["offsets"], values.shape[0])
    xv, s, q, linear = _row_sums(
        params, batch["indices"], row_ids, values, num_rows)
    with jax.named_scope("step.forward"):
        interaction = 0.5 * jnp.sum(s * s - q, axis=-1)
    if factor_axis is not None:
        with jax.named_scope("step.exchange"):
            interaction = psum(interaction, factor_axis)
    with jax.named_scope("step.forward"):
        margin = params["b"] + linear + interaction
        loss, gmargin = margin_grad(objective, margin, label)
        loss_sum = jnp.sum(weight * loss)
    with jax.named_scope("step.backward"):
        wg = weight * gmargin  # [B]
        gb = jnp.sum(wg)
        # a row's s and wg reach its entries in one gather
        back = jnp.take(
            jnp.concatenate([s, wg[:, None]], axis=1), row_ids, axis=0)
        dw = back[:, -1] * values  # [nnz]
        # dv[e,k] = x_e * (s[r,k] − x_e v[i,k]), scaled by wg[r]
        dv = dw[:, None] * (back[:, :-1] - xv)
    return dw, gb, dv, loss_sum, jnp.sum(weight)


#: factor-table rows one scatter-add of the update loop covers. Timed on
#: the v5e in the kdd12-fm cell (PERF.md, PR 26): 512 to 2048 read the
#: same step, 8192 costs 0.6 ms of a 14.2 ms step in slots past the last
#: distinct id.
_UPDATE_CHUNK = 2048


def _in_id_order(indices, dw, dv):
    """The batch's feature ids sorted, once, and the entries' updates
    ``[dv | dw]`` (``[nnz, K + 1]``) in that order. The sort carries each
    entry's place as its payload and the updates follow in one gather
    (its source is the batch, a few MB: a tenth of a scatter's cost on
    the chip). Stable: every chip of a factor-sharded mesh sorts the same
    gathered batch and sums an id's entries in the same order. A padded
    entry (value 0, feature 0) sorts to the front and still adds 0.

    The gathers from the parameters stay in the feed's order, ahead of
    this: in id order the entries of a popular id lie side by side and
    read one row of ``v`` hundreds of times in a row, which the chip
    serves slower (PERF.md, PR 29: 4.6 ms for 3.4)."""
    place = jnp.arange(indices.shape[0], dtype=jnp.int32)
    indices, place = lax.sort((indices, place), num_keys=1)
    upd = jnp.concatenate([dv, dw[:, None]], axis=1)
    return indices, jnp.take(upd, place, axis=0)


def _scatter_add_rows(w, v, indices, upd):
    """``v[i] += Σ upd[e, :-1]`` and ``w[i] += Σ upd[e, -1]`` over the
    entries e that name feature i, into ``w`` and ``v`` themselves (in
    place when the caller donated them). ``indices`` is SORTED and ``upd``
    in its order (:func:`_in_id_order`). A row no entry names is not
    written; a padded entry adds its 0 to feature 0.

    The entries of one id are summed first and reach its row in one
    add. Ids repeat within a batch (thousands of times for the popular
    ones under a power law), and entry-by-entry adds into a parameter
    much larger than the update round at the parameter's magnitude each
    time: against a float64 step that read 20 times the error of a dense
    gradient's one subtraction. Summing first keeps that one rounding.
    In id order an id's entries lie side by side, so an entry's slot is
    the count of distinct ids before it (sorted by construction), and
    ``v``'s and ``w``'s updates are summed by slot in ONE pass (as
    :func:`_row_sums` sums by row, and for its reason).

    On the chip a row scatter-add is serial, ~0.1 µs a slot whether the
    slot's id is in range or dropped, so the distinct ids are compacted to
    the front and ``v`` takes them ``_UPDATE_CHUNK`` slots at a time until
    the last slot that holds one. ``w``'s 1-D scatter costs a pass over
    ``w`` whatever the number of slots, so it is made once."""
    n = indices.shape[0]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), indices[1:] != indices[:-1]])
    slot = jnp.cumsum(first.astype(jnp.int32)) - 1
    sums = jax.ops.segment_sum(
        upd, slot, num_segments=n, indices_are_sorted=True)  # [n, K + 1]
    # slot j holds the j-th distinct id; the slots after the last hold ids
    # past the table, which the scatter drops (distinct, as promised)
    pad = (-n) % _UPDATE_CHUNK
    ids = (w.shape[0] + jnp.arange(n + pad, dtype=jnp.int32)).at[
        slot].set(indices)
    sum_v = jnp.pad(sums[:, :-1], ((0, pad), (0, 0)))
    flags = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
    w = w.at[ids[:n]].add(sums[:, -1], **flags)

    def add_chunk(i, v):
        at = i * _UPDATE_CHUNK
        return v.at[lax.dynamic_slice_in_dim(ids, at, _UPDATE_CHUNK)].add(
            lax.dynamic_slice_in_dim(sum_v, at, _UPDATE_CHUNK), **flags)

    distinct = slot[-1] + 1
    chunks = (distinct + _UPDATE_CHUNK - 1) // _UPDATE_CHUNK
    return w, lax.fori_loop(0, chunks, add_chunk, v)


def _gather_sections(batch, axis: str):
    """Under ``shard_map``: the feed's row-split sections of one step's
    batch (``ShardedCSRBatch``: every chip its own rows' entries, with
    LOCAL offsets) gathered over ``axis`` into the whole COO batch on
    every chip, row ids made global. What crosses ICI is what crossed
    H2D: the entries, the offsets (not per-entry row ids), labels and
    weights."""
    whole = {k: all_gather(batch[k], axis, tiled=True)
             for k in ("label", "weight", "indices", "values")}
    bucket = batch["indices"].shape[0]  # one section's entries
    rows = batch["label"].shape[0]  # one section's rows
    offsets = all_gather(batch["offsets"], axis)  # [sections, rows + 1]
    local = jax.vmap(lambda o: expand_row_ids(o, bucket))(offsets)
    first_row = rows * jnp.arange(offsets.shape[0], dtype=local.dtype)
    whole["row_ids"] = (local + first_row[:, None]).reshape(-1)
    return whole


def exchange_bytes(batch, shards: int) -> int:
    """Bytes one chip contributes to the collectives of one
    factor-sharded step, from the shapes: its section of the batch to
    the gather, and its ``f32[rows]`` share of the interaction term to
    the psum. ``batch``: the arrays the step takes (global shapes)."""
    gathered = sum(int(a.nbytes) for a in batch.values())
    return gathered // shards + int(batch["label"].nbytes)


def _sparse_update(params, indices, grads, learning_rate: float, l2: float):
    """The step's update from per-entry contributions ``grads`` =
    (dw, gb, dv, weight_sum): put in feature-id order under ``step.order``
    (:func:`_in_id_order`, the step's one sort), then under
    ``step.update`` scaled by ``-learning_rate / weight_sum`` and
    scatter-ADDED into ``w`` and ``v`` (:func:`_scatter_add_rows`; ids
    repeat within a batch), so only the rows the batch names are written
    and no gradient of the table's shape exists. ``l2 > 0`` adds one
    scaling pass over the table before the scatter-add:
    ``v - lr*(g + l2*v) = v*(1 - lr*l2) - lr*g``."""
    dw, gb, dv, wsum = grads
    with jax.named_scope("step.order"):
        indices, upd = _in_id_order(indices, dw, dv)
    with jax.named_scope("step.update"):
        denom = jnp.maximum(wsum, 1e-12)
        scale = -learning_rate / denom
        w, v = params["w"], params["v"]
        if l2:
            w = w * (1.0 - learning_rate * l2)
            v = v * (1.0 - learning_rate * l2)
        w, v = _scatter_add_rows(w, v, indices, scale * upd)
        return {
            "w": w,
            "b": params["b"] - learning_rate * (gb / denom),
            "v": v,
        }


def make_fm_train_step(
    mesh: Optional[Mesh],
    num_features: int,
    objective: str = "logistic",
    learning_rate: float = 0.05,
    l2: float = 0.0,
    axis: str = "dp",
    param_specs=None,
    donate_batch: bool = False,
    table_sharding: str = "replicated",
):
    """Jitted FM SGD step over COO batches.

    Single device (``mesh is None``): the update touches only the rows
    the batch names (:func:`_sparse_update`).

    Mesh, table replicated: a psum needs one buffer of a fixed shape, so
    the entries are reduced to dense grads and ONE fused (dtype-bucketed)
    in-graph psum carries the [F,K] factor grads, [F] linear grads and
    loss scalars across ICI as a single contiguous f32 buffer, then a
    dense update.

    Mesh, ``table_sharding="factors"`` (params placed by
    :data:`FM_FACTOR_PARTITION_RULES`): every chip gathers the step's
    whole batch (:func:`_gather_sections`), computes the interaction
    term of its own columns, psums that one ``f32[rows]`` vector, and
    applies the single-device sparse update to its columns and to its
    replica of ``w`` and ``b`` (the same arithmetic on the same data on
    every chip, so the replicas stay bit-equal). Both collectives sit
    under ``step.exchange``; nothing of the table's shape crosses ICI
    or exists besides the table.

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
            params = _sparse_update(
                params, batch["indices"], (dw, gb, dv, wsum),
                learning_rate, l2)
            return params, {"loss_sum": loss_sum, "weight_sum": wsum}

        fn = instrumented_jit(
            step, "fm.step",
            donate_argnums=(0, 1) if donate_batch else (),
        )
        return suppress_donation_warnings(fn) if donate_batch else fn

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
            fm_partition_rules(table_sharding),
            jax.eval_shape(lambda: init_fm_params(max(num_features, 1), 2)),
        )

    if table_sharding == "factors":

        def _factor_sharded(params, batch):
            with jax.named_scope("step.exchange"):
                whole = _gather_sections(batch, axis)
            dw, gb, dv, loss_sum, wsum = _fm_entry_grads(
                params, whole, objective, factor_axis=axis)
            params = _sparse_update(
                params, whole["indices"], (dw, gb, dv, wsum),
                learning_rate, l2)
            return params, {"loss_sum": loss_sum, "weight_sum": wsum}

        # every chip computes w, b and the loss sums from the gathered
        # batch, which shard_map types as varying: the replicas are equal
        # by construction, not by a collective it could check
        step = shard_map(
            _factor_sharded, mesh=mesh,
            in_specs=(param_specs, batch_specs),
            out_specs=(param_specs, P()),
            check_vma=False,
        )
        return instrumented_jit(step, "fm.step", donate_argnums=(0,))

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


class FMLearner(FeedLearner):
    """uri → fitted FM params over a DeviceFeed (csr layout); the fit loop
    is :func:`dmlc_tpu.models.fitloop.fit_feed`.

    On a mesh ``table_sharding`` (an :class:`FMParam` field) says how the
    factor table is held: ``"replicated"`` (default; a whole copy on every
    chip, the dense gradient psummed) or ``"factors"`` (each chip
    ``num_factors / chips`` columns of ``v``, for a table wider than one
    chip's memory; see :func:`make_fm_train_step`). ``reshard`` passes
    the params through one host copy (a factor-sharded table whole: 28 GB
    at 54.7 M ids x 128), and every chip that held a column slice must
    still answer: no other chip has those columns, so after losing one
    the way back is the last snapshot."""

    name = "fm"
    #: the mesh axis the batch (and a sharded table) divides over, the
    #: DeviceFeed's default
    axis = "dp"

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        self.param = FMParam()
        self.param.init(hyper)
        self._nf = None
        # a sharded table's steps since the last epoch boundary, and the
        # bytes one of them exchanges, both by nnz bucket (the shapes the
        # step was compiled for fix the bytes)
        self._steps_of: Dict[int, int] = {}
        self._bytes_of: Dict[int, int] = {}
        super().__init__(mesh)

    @property
    def table_shards(self) -> int:
        """Chips one logical factor table is divided over (1: every chip,
        or the one device, holds all of it)."""
        if self.mesh is None or self.param.table_sharding != "factors":
            return 1
        return int(self.mesh.shape[self.axis])

    def partition_rules(self):
        return fm_partition_rules(self.param.table_sharding)

    def check_mesh(self, mesh: Mesh) -> None:
        if self.param.table_sharding == "factors":
            _check_factor_shards(self.param.num_factors, mesh, self.axis)

    def param_shardings(self):
        """NamedSharding tree of the params on this learner's mesh (None
        without one): what an initialiser's ``out_shardings`` takes so
        that each chip generates only the part it holds."""
        if self.mesh is None:
            return None
        # the rules go by a leaf's name and rank, not by its size
        template = jax.eval_shape(
            lambda: init_fm_params(2, self.param.num_factors))
        return sharding_tree(
            self.mesh,
            match_partition_rules(self.partition_rules(), template))

    def _ensure(self, num_features: int):
        if self.params is None:
            nf = self.param.num_features or num_features
            init = partial(init_fm_params, nf, self.param.num_factors,
                           self.param.init_scale)
            # on a mesh the initialiser runs as one program placed by the
            # rules: a chip writes its own part and no whole table exists
            # on any one of them first
            self.params = init() if self.mesh is None else jax.jit(
                init, out_shardings=self.param_shardings())()
            self._nf = nf
        if self._step is None:
            self._step = make_fm_train_step(
                self.mesh,
                self._nf or self.param.num_features or num_features,
                objective=self.param.objective,
                learning_rate=self.param.learning_rate,
                l2=self.param.l2,
                axis=self.axis,
                # the fit loop rebinds params every step and never touches
                # a batch after its step — the donation contract holds
                donate_batch=self.mesh is None,
                table_sharding=self.param.table_sharding,
            )

    def ensure_step(self, spec) -> None:
        check(spec.layout == "csr", "FM consumes csr batches")
        self._ensure(self.param.num_features)

    def train_step(self, arrays: Dict) -> Dict:
        shards = self.table_shards
        if shards > 1:
            bucket = arrays["indices"].shape[0]
            self._steps_of[bucket] = self._steps_of.get(bucket, 0) + 1
            if bucket not in self._bytes_of:
                self._bytes_of[bucket] = exchange_bytes(arrays, shards)
        self.params, metrics = self._step(self.params, arrays)
        return metrics

    def epoch_span_args(self) -> Dict:
        return {"table_shards": self.table_shards}

    def epoch_closed(self, reg, nstep: int) -> None:
        """FM's own counters. The step was built for ``self.mesh`` and the
        table's sharding (``_ensure``): one device and a factor-sharded
        mesh scatter-add every step, a mesh of replicas none.
        ``dmlc_fit_sparse_update_steps_total`` over
        ``dmlc_fit_steps_total`` is the share of steps that took that
        path; the exchanged bytes are from the shapes (the gradient psum
        of a replicated model is not among them,
        ``dmlc_xla_collective_bytes`` has it)."""
        shards = self.table_shards
        sparse = self.mesh is None or shards > 1
        reg.counter(
            "dmlc_fit_sparse_update_steps_total",
            "optimizer steps that scatter-added into the touched "
            "rows instead of applying a dense gradient",
            model=self.name).inc(nstep if sparse else 0)
        reg.counter(
            "dmlc_fit_sharded_table_steps_total",
            "optimizer steps over a parameter table sharded over "
            "the mesh's chips (no chip holds the whole table)",
            model=self.name).inc(nstep if shards > 1 else 0)
        reg.counter(
            "dmlc_fit_exchange_bytes_total",
            "bytes one chip contributed to the collectives of "
            "sharded-table steps (batch gather + interaction psum)",
            model=self.name).inc(
                sum(n * self._bytes_of[b]
                    for b, n in self._steps_of.items()))
        self._steps_of.clear()

    def fit_uri(self, uri: str, **kw):
        """:func:`dmlc_tpu.models.fitloop.fit_uri` for this learner
        (layout csr; the arguments and the snapshot / resume contract are
        listed there)."""
        return fitloop.fit_uri(self, uri, layout="csr", **kw)

    def fit_feed(self, feed, *args, **kw):
        """Train over a csr DeviceFeed; returns per-epoch losses:
        :func:`dmlc_tpu.models.fitloop.fit_feed` for this learner."""
        # bookkeeping of a pass a preemption cut short, or of other shapes
        self._steps_of.clear()
        self._bytes_of.clear()
        return fitloop.fit_feed(self, feed, *args, **kw)

    def snapshot_model(self) -> Dict:
        """The params as the device arrays they are; a table sharded over
        chips reaches the host as the ONE logical ``[F, K]`` array
        (``collective.checkpoint._to_host`` assembles it shard by shard),
        so a snapshot restores under any placement."""
        return {"params": dict(self.params)}

    def restore_snapshot_model(self, model: Dict) -> None:
        """Re-place a snapshot's host FM params on device: straight from
        the host arrays to this learner's placement (each chip receives
        only the part its rules give it; the snapshot's own placement
        does not matter, the table is one logical array)."""
        params = model["params"]
        want = (self.param.num_features or params["v"].shape[0],
                self.param.num_factors)
        check(tuple(params["v"].shape) == want,
              "snapshot holds a factor table of shape %s, this learner "
              "trains %s", tuple(params["v"].shape), want)
        self._nf = want[0]
        if self.mesh is None:
            self.params = {k: jnp.asarray(v) for k, v in params.items()}
        else:
            self.params = shard_params(
                params, self.mesh, rules=self.partition_rules())

    def predict_batch(self, batch) -> np.ndarray:
        num_rows = int(batch["label"].shape[0])
        row_ids = expand_row_ids(batch["offsets"], batch["values"].shape[0])
        _, s, q, linear = _row_sums(
            self.params, batch["indices"], row_ids, batch["values"], num_rows)
        return np.asarray(
            self.params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
        )

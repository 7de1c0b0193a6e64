"""Factorization machines over COO device batches.

The reference ships a libfm parser (libfm_parser.h, ``label
field:idx:val``) and leaves the models downstream. This model reads no
field: it trains from any CSR batch (LIBSVM, libfm with the field column
ignored, shards). The field-aware model that format exists for is
models/ffm.py, which shares this module's step head, chunk loops and
stateful-update skeleton and takes an entry's field from its id's range
(``FFMParam.field_sizes``): the feed carries no field column to the
device. TPU-first formulation: all per-entry work is gathers +
segment_sums (static shapes), and the O(nnz·K) factor math is batched so
XLA can keep it on the vector units. The step opens with its one sort,
of the batch's entries by feature id, and everything after it runs in
that order: ``v`` and ``w``
are read at the batch's DISTINCT ids only (ids repeat within a batch,
about 26 k distinct of 90,112 entries under kdd2012's power law) and the
entries take their rows from that few-MB buffer; passes over the entries
that share an index vector are one pass over concatenated columns (on
the chip such a pass costs per index, not per column): the row sums of
the forward pass, a row's terms on their way back to its entries, and the
sums of an id's entries. On one device the step scatter-adds each id's
summed update into the row it names and never passes over the table; on
a mesh with the table replicated the entries are reduced to a dense
gradient for the psum; on a mesh with the table's factors sharded
(``table_sharding="factors"``) every chip scatter-adds into its own
columns and only the batch and one ``f32[rows]`` psum cross ICI.

The update rule is ``FMParam.optimizer``'s: ``"sgd"`` adds each id's
scaled gradient into its row; ``"ftrl_adagrad"`` (difacto's: FTRL-proximal
on ``w``, per-element AdaGrad on ``v``) keeps state for every parameter
row, tables ``z``, ``n`` (as ``w``) and ``a`` (as ``v``) beside the
weights in ``params``, reads a touched row's state once and SETS weights
and state from the rule (:func:`_stateful_update`).

score(x) = b + Σ_i w_i x_i + ½ Σ_k [(Σ_i v_ik x_i)² − Σ_i v_ik² x_i²]
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional

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
    # the update rule. "sgd": w, v and b by plain SGD at learning_rate
    # (l2 as weight decay on the whole table). "ftrl_adagrad": w by
    # FTRL-proximal (alpha = learning_rate, beta = lr_beta, l1, l2), v by
    # per-element AdaGrad (v_learning_rate, v_lr_beta, v_l2), b by SGD;
    # the rule and its sources are on _stateful_update
    optimizer = field(
        str, "sgd", enum={"sgd": "sgd", "ftrl_adagrad": "ftrl_adagrad"})
    l1 = field(float, 0.0, lower_bound=0.0)
    lr_beta = field(float, 1.0, lower_bound=0.0)
    v_learning_rate = field(float, 0.05, lower_bound=0.0)
    v_lr_beta = field(float, 1.0, lower_bound=0.0)
    v_l2 = field(float, 0.0, lower_bound=0.0)


class FtrlAdagrad(NamedTuple):
    """What ``optimizer="ftrl_adagrad"`` needs beside ``learning_rate``
    (FTRL's alpha) and ``l2`` (FTRL's), named as :class:`FMParam` names
    them."""

    l1: float
    lr_beta: float
    v_learning_rate: float
    v_lr_beta: float
    v_l2: float


def init_fm_params(
    num_features: int, num_factors: int, init_scale: float = 0.01, seed: int = 0,
    optimizer: str = "sgd",
) -> Dict:
    """``w``, ``b``, ``v`` and, for a rule that keeps state
    (``optimizer="ftrl_adagrad"``), its tables at zero: ``z`` and ``n``
    of ``w``'s shape (FTRL's), ``a`` of ``v``'s (AdaGrad's sum of
    squared gradients)."""
    key = jax.random.PRNGKey(seed)
    params = {
        "w": jnp.zeros((num_features,), dtype=jnp.float32),
        "b": jnp.zeros((), dtype=jnp.float32),
        "v": init_scale
        * jax.random.normal(key, (num_features, num_factors), dtype=jnp.float32),
    }
    if optimizer != "sgd":
        params.update(
            z=jnp.zeros_like(params["w"]), n=jnp.zeros_like(params["w"]),
            a=jnp.zeros_like(params["v"]))
    return params


#: the leaves of ``params`` a stateful rule adds
STATE_TABLES = ("a", "n", "z")


#: Data-parallel placement for {"w": [F], "b": scalar, "v": [F, K]}:
#: everything replicated, the batch shards, grads psum in-graph. Linted
#: by scripts/check_partition_rules.py like LINEAR_PARTITION_RULES. A
#: stateful rule's tables are placed as their weights are, here and
#: below: ``z``, ``n`` as ``w``, ``a`` as ``v``.
FM_PARTITION_RULES = ((r"^(w|b|v|z|n|a)$", P()),)

#: ``table_sharding="factors"``: chip c of the ``dp`` axis holds columns
#: [c*K/n, (c+1)*K/n) of ``v``; ``w`` and ``b`` stay replicated. The
#: factors of an FM do not interact, so a chip's columns give its share
#: of the interaction term and take their update with nothing of the
#: table's shape crossing ICI.
FM_FACTOR_PARTITION_RULES = (
    (r"^(w|b|z|n)$", P()), (r"^(v|a)$", P(None, "dp")))


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


#: parameter rows one pass of the step's two chunk loops covers (the
#: gather at the distinct ids and ``v``'s scatter-add). Timed on the v5e
#: in the kdd12-fm cell (PERF.md, PR 26): 512 to 2048 read the same step,
#: 8192 costs 0.6 ms of a 14.2 ms step in slots past the last distinct
#: id.
_UPDATE_CHUNK = 2048


class _IdOrder(NamedTuple):
    """The batch's entries in feature-id order (:func:`_in_id_order`)."""

    #: s32[n] every entry's feature id, ascending
    entry_ids: jax.Array
    #: s32[n] every entry's slot: the count of distinct ids before it
    slot: jax.Array
    #: s32[n + pad] slot j holds the j-th distinct id; the slots after the
    #: last hold distinct ids past the table (a gather fills them, a
    #: scatter drops them); padded to whole chunks of ``_UPDATE_CHUNK``
    ids: jax.Array
    #: s32[] how many distinct ids the batch names
    distinct: jax.Array

    @property
    def chunks(self):
        """s32[] passes of ``_UPDATE_CHUNK`` slots up to the last slot that
        holds a distinct id: what both chunk loops run."""
        return (self.distinct + _UPDATE_CHUNK - 1) // _UPDATE_CHUNK


def _in_id_order(indices, row_ids, values, num_features: int):
    """The step's one sort: the batch's entries by feature id, ``row_ids``
    and ``values`` riding as payloads (a 1-D gather of ``s32[nnz]`` by
    place costs 0.64 ms on the chip, the two payloads 0.05). Returns the
    :class:`_IdOrder` and the two payloads in that order.

    Stable, so an id's entries keep the feed's order among themselves,
    and every chip of a factor-sharded mesh, sorting the same gathered
    batch, sums them in the same order. In id order an id's entries lie
    side by side, so an entry's slot is the count of distinct ids before
    it, sorted by construction. A padded entry (value 0, feature 0) sorts
    to the front, reads row 0 and adds 0 to it."""
    n = indices.shape[0]
    indices, row_ids, values = lax.sort(
        (indices, row_ids, values), num_keys=1)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), indices[1:] != indices[:-1]])
    slot = jnp.cumsum(first.astype(jnp.int32)) - 1
    # the distinct ids to the front, in order: every other entry becomes
    # an id past the table, distinct and above them all, and a sort of
    # that one array does what a scatter by slot would (0.08 ms against
    # 0.44 on the chip: PERF.md, PR 31)
    pad = (-n) % _UPDATE_CHUNK
    past = num_features + jnp.arange(n + pad, dtype=jnp.int32)
    ids = jnp.concatenate(
        [lax.sort(jnp.where(first, indices, past[:n])), past[n:]])
    return _IdOrder(indices, slot, ids, slot[-1] + 1), row_ids, values


def _take_distinct(tables, order: _IdOrder):
    """``[t[ids] | ...]`` for the 1-D and 2-D ``tables`` of one height,
    side by side (``[n + pad, columns]``), at the batch's distinct ids,
    ``_UPDATE_CHUNK`` slots a pass until the last slot that holds one:
    the loop adapts to what the batch holds. A slot past the distinct ids
    reads 0."""
    flags = dict(indices_are_sorted=True, unique_indices=True)

    def take_chunk(i, rows):
        at = i * _UPDATE_CHUNK
        ids = lax.dynamic_slice_in_dim(order.ids, at, _UPDATE_CHUNK)
        got = jnp.concatenate(
            [jnp.take(t, ids, axis=0, **flags) if t.ndim == 2
             else jnp.take(t, ids, axis=0, **flags)[:, None]
             for t in tables], axis=1)
        return lax.dynamic_update_slice_in_dim(rows, got, at, axis=0)

    columns = sum(t.shape[1] if t.ndim == 2 else 1 for t in tables)
    rows = jnp.zeros((order.ids.shape[0], columns), tables[0].dtype)
    # under a shard_map that checks it, the loop's carry must vary over
    # the axes the batch varies over from the start
    varying = tuple(jax.typeof(order.ids).vma)
    if varying:
        rows = lax.pcast(rows, varying, to="varying")
    return lax.fori_loop(0, order.chunks, take_chunk, rows)


def _gather_rows(tables, order: _IdOrder):
    """The entries' rows of ``tables`` side by side (the FM's ``[v_e |
    w_e]``, ``[nnz, K + 1]``), in id order, with each touched row of the
    parameters read ONCE: ``rows = [v[ids] | w[ids]]`` at the distinct
    ids (:func:`_take_distinct`), then one batch-sized gather
    ``rows[slot]`` whose source is a few MB (a tenth of a gather from the
    table's cost on the chip). Returns (rows, the entries' rows).

    A gather from the table costs per index (22 ns a row of 16 columns,
    37 ns of 32, 16 ns an element of ``w``: PERF.md, PR 31) and nothing
    for being repeated, so the popular ids of a power law are most of a
    per-entry gather's cost; a batch with no repeated id gathers what a
    per-entry gather would."""
    rows = _take_distinct(tables, order)
    return rows, jnp.take(rows, order.slot, axis=0)


def _row_sums(vw, row_ids, values, num_rows: int):
    """Per row: ``s`` = Σ x_e v_e, ``q`` = Σ (x_e v_e)² (both ``[B, K]``)
    and the linear term Σ x_e w_e, in ONE ``segment_sum`` over the
    concatenated columns; ``xv [nnz, K]`` is handed back for the backward
    pass. ``vw`` = ``[v_e | w_e]`` per entry (:func:`_gather_rows`), the
    entries in any order. On the chip a pass over the entries costs per
    index, not per column, while a row of its target fits one 128-lane
    tile (PERF.md, PR 29), so the three sums share their pass."""
    k = vw.shape[1] - 1
    with jax.named_scope("step.forward"):
        xv = values[:, None] * vw[:, :k]  # [nnz, K]
        sums = jax.ops.segment_sum(
            jnp.concatenate(
                [xv, xv * xv, (values * vw[:, k])[:, None]], axis=1),
            row_ids, num_segments=num_rows)  # [B, 2K + 1]
    return xv, sums[:, :k], sums[:, k:2 * k], sums[:, 2 * k]


def _entries_in_id_order(tables, batch):
    """``step.order`` and ``step.gather``, the head of every FM and FFM
    program: the batch's entries sorted by feature id
    (:func:`_in_id_order`) and the rows of ``tables`` (the FM's ``(v,
    w)``) side by side for each (:func:`_gather_rows`). Returns (order,
    rows, vw, row_ids, values): ``rows`` the distinct ids' ``[v | w]``,
    the last three per entry in id order."""
    values = batch["values"]
    with jax.named_scope("step.gather"):
        # offsets → row ids on device (local per shard under shard_map)
        row_ids = batch["row_ids"] if "row_ids" in batch else \
            expand_row_ids(batch["offsets"], values.shape[0])
    with jax.named_scope("step.order"):
        order, row_ids, values = _in_id_order(
            batch["indices"], row_ids, values, tables[0].shape[0])
    with jax.named_scope("step.gather"):
        rows, vw = _gather_rows(tables, order)
    return order, rows, vw, row_ids, values


def _fm_entry_grads(params, batch, objective: str,
                    factor_axis: Optional[str] = None):
    """Loss sums and the per-entry gradient contributions of one COO
    batch shard, the entries in feature-id order (``order``, an
    :class:`_IdOrder`): entry e of row r at feature i adds ``dw[e]`` to
    w_i's gradient and ``dv[e]`` to v_i's. How they reach the parameters
    is the caller's: scatter-added into the table (single device,
    factor-sharded mesh) or reduced to dense grads for the psum
    (replicated mesh). Returns (dw, gb, dv, loss_sum, weight_sum, order,
    seen); ``seen`` = (the distinct ids' ``[v | w]``, the entries'
    values) is what a stateful rule reads besides (:func:`_stateful_update`).

    Passes that share an index vector are one pass over concatenated
    columns: the three row sums of the forward pass (:func:`_row_sums`),
    and a row's ``s`` and ``wg`` on their way back to its entries. The
    row sums add a row's entries in id order, not the feed's: the same
    float32 terms in another order.

    ``factor_axis``: ``params["v"]`` holds this chip's columns only and
    the batch is the whole step's (``row_ids`` given, global); the
    columns' share of the interaction term is psummed over that axis
    between forward and backward, under ``step.exchange``.

    The ``step.*`` scopes name the step's phases in the compiled
    program's metadata (shared with models/linear.py), so a device
    profile can be read by phase; they change no operation."""
    label = batch["label"]
    weight = batch["weight"]
    order, rows, vw, row_ids, values = _entries_in_id_order(
        (params["v"], params["w"]), batch)
    xv, s, q, linear = _row_sums(vw, row_ids, values, label.shape[0])
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
    return dw, gb, dv, loss_sum, jnp.sum(weight), order, (rows, values)


def _scatter_add_rows(w, v, order: _IdOrder, upd):
    """``v[i] += Σ upd[e, :-1]`` and ``w[i] += Σ upd[e, -1]`` over the
    entries e that name feature i, into ``w`` and ``v`` themselves (in
    place when the caller donated them). ``upd`` is in ``order``'s order.
    A row no entry names is not written; a padded entry adds its 0 to
    feature 0.

    The entries of one id are summed first and reach its row in one
    add. Ids repeat within a batch (thousands of times for the popular
    ones under a power law), and entry-by-entry adds into a parameter
    much larger than the update round at the parameter's magnitude each
    time: against a float64 step that read 20 times the error of a dense
    gradient's one subtraction. Summing first keeps that one rounding.
    ``v``'s and ``w``'s updates are summed by slot in ONE pass (as
    :func:`_row_sums` sums by row, and for its reason).

    On the chip a row scatter-add is serial, ~0.1 µs a slot whether the
    slot's id is in range or dropped, so ``v`` takes the distinct ids
    ``_UPDATE_CHUNK`` slots at a time until the last slot that holds one
    (the loop :func:`_gather_rows` reads them by). ``w``'s 1-D scatter
    costs a pass over ``w`` whatever the number of slots, so it is made
    once."""
    n = order.slot.shape[0]
    sums = jax.ops.segment_sum(
        upd, order.slot, num_segments=n, indices_are_sorted=True)  # [n, K + 1]
    ids = order.ids
    sum_v = jnp.pad(sums[:, :-1], ((0, ids.shape[0] - n), (0, 0)))
    flags = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
    w = w.at[ids[:n]].add(sums[:, -1], **flags)

    def add_chunk(i, v):
        at = i * _UPDATE_CHUNK
        return v.at[lax.dynamic_slice_in_dim(ids, at, _UPDATE_CHUNK)].add(
            lax.dynamic_slice_in_dim(sum_v, at, _UPDATE_CHUNK), **flags)

    return w, lax.fori_loop(0, order.chunks, add_chunk, v)


def _check_rule_placement(optimizer: str, mesh: Optional[Mesh],
                          table_sharding: str) -> None:
    """Every rule but ``"sgd"`` keeps state for every parameter row."""
    check(optimizer == "sgd" or mesh is None or table_sharding == "factors",
          "optimizer=%r keeps state for every parameter row "
          "and updates the rows a batch names; the replicated mesh step "
          "applies a dense psummed gradient and has no such path: train "
          "on one device or with table_sharding='factors'", optimizer)


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


def _sparse_update(params, order: _IdOrder, grads, learning_rate: float,
                   l2: float, seen=None, rule: Optional[FtrlAdagrad] = None):
    """The step's update from per-entry contributions ``grads`` =
    (dw, gb, dv, weight_sum), the entries in ``order``'s order: under
    ``step.update`` scaled by ``-learning_rate / weight_sum`` and
    scatter-ADDED into ``w`` and ``v`` (:func:`_scatter_add_rows`; ids
    repeat within a batch), so only the rows the batch names are written
    and no gradient of the table's shape exists. The sort, the slots and
    the distinct ids it needs are the step's head's (``step.order``); it
    computes none. ``l2 > 0`` adds one scaling pass over the table before
    the scatter-add: ``v - lr*(g + l2*v) = v*(1 - lr*l2) - lr*g``.

    With a ``rule`` (``optimizer="ftrl_adagrad"``) the update is
    :func:`_stateful_update`'s, which sets rows where this one adds."""
    if rule is not None:
        return _stateful_update(
            params, order, grads, seen, learning_rate, l2, rule)
    dw, gb, dv, wsum = grads
    with jax.named_scope("step.update"):
        denom = jnp.maximum(wsum, 1e-12)
        upd = (-learning_rate / denom) * jnp.concatenate(
            [dv, dw[:, None]], axis=1)
        w, v = params["w"], params["v"]
        if l2:
            w = w * (1.0 - learning_rate * l2)
            v = v * (1.0 - learning_rate * l2)
        w, v = _scatter_add_rows(w, v, order, upd)
        return {
            "w": w,
            "b": params["b"] - learning_rate * (gb / denom),
            "v": v,
        }


def _set_rows(table, order: _IdOrder, new):
    """``table[ids[j]] = new[j]`` for every slot j that holds a distinct
    id (``new``: ``[n + pad]`` or ``[n + pad, K]``, by slot), into
    ``table`` itself when the caller donated it; a row no slot names is
    not written. Shaped as :func:`_scatter_add_rows` is, and for its
    reasons: a 2-D table takes the slots ``_UPDATE_CHUNK`` at a time up
    to the last chunk that holds a distinct id, a 1-D one in one
    scatter."""
    ids = order.ids
    flags = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
    if table.ndim == 1:
        n = order.slot.shape[0]
        return table.at[ids[:n]].set(new[:n], **flags)

    def set_chunk(i, table):
        at = i * _UPDATE_CHUNK
        return table.at[lax.dynamic_slice_in_dim(ids, at, _UPDATE_CHUNK)].set(
            lax.dynamic_slice_in_dim(new, at, _UPDATE_CHUNK), **flags)

    return lax.fori_loop(0, order.chunks, set_chunk, table)


def _split_columns(buffer, like):
    """{name: its columns of ``buffer``} for the tables of ``like`` (name
    -> an array of the table's rank) side by side in that order: a 1-D
    table is one column and comes back 1-D."""
    out, at = {}, 0
    for name, table in like.items():
        if table.ndim == 1:
            out[name] = buffer[:, at]
            at += 1
        else:
            out[name] = buffer[:, at:at + table.shape[1]]
            at += table.shape[1]
    return out


def _update_at_distinct(params, order: _IdOrder, grads, seen, wsum, state,
                        rule):
    """The skeleton of an update by a rule that keeps state for every
    parameter row, written once for the rules of this module and of
    models/ffm.py. ``grads``: {weight table: the entries' contributions
    to its gradient, ``[n]`` or ``[n, C]`` in ``order``'s order}, the
    tables in the column order of the head's read; ``seen`` = (the
    distinct ids' weights as the head read them, the entries' values);
    ``state``: the names of the tables of ``params`` the rule keeps beside
    the weights; ``rule(old, grad) -> new``: dicts by table name over the
    distinct ids' buffers (``grad`` the mean gradients of the weight
    tables, ``new`` every table's rows, weights and state). Returns (the
    new tables, ``max(wsum, 1e-12)``).

    The rule is not a scaled sum of the entries: an id's gradient is
    summed first (one ``segment_sum`` by sorted slot, as the SGD step's),
    whole BEFORE the rule runs; the id's state is read ONCE
    (:func:`_take_distinct`); weights and state are SET at the distinct
    ids (:func:`_set_rows`), so no array of a table's shape exists
    besides the tables and a row no entry names is neither read nor
    written. A slot whose entries all have value 0 (padding names feature
    0) keeps its weights and its state to the bit, whatever the rule.

    ``step.state`` holds what the rule adds to the SGD step: the state
    rows' read, the rule, the state rows' write. The weights' writes and
    the id sums stay under ``step.update``."""
    rows, values = seen
    n = values.shape[0]
    with jax.named_scope("step.update"):
        denom = jnp.maximum(wsum, 1e-12)
        # the last column counts an id's entries that carry a value
        sums = jax.ops.segment_sum(
            jnp.concatenate(
                [g if g.ndim == 2 else g[:, None] for g in grads.values()]
                + [(values != 0).astype(rows.dtype)[:, None]], axis=1),
            order.slot, num_segments=n, indices_are_sorted=True)
        sums = jnp.pad(sums, ((0, order.ids.shape[0] - n), (0, 0)))
        grad = {name: g / denom
                for name, g in _split_columns(sums, grads).items()}
        live = sums[:, -1] > 0
        old = _split_columns(rows, grads)
    with jax.named_scope("step.state"):
        tables = {name: params[name] for name in state}
        old.update(_split_columns(
            _take_distinct(tuple(tables.values()), order), tables))
        new = {name: jnp.where(live if rows_.ndim == 1 else live[:, None],
                               rows_, old[name])
               for name, rows_ in rule(old, grad).items()}
        out = {name: _set_rows(params[name], order, new[name])
               for name in state}
    with jax.named_scope("step.update"):
        out.update({name: _set_rows(params[name], order, new[name])
                    for name in grads})
    return out, denom


def _ftrl_adagrad(old, grad, alpha: float, l2: float, rule: FtrlAdagrad):
    """The rule of :func:`_stateful_update` alone, elementwise over the
    distinct ids' buffers: ``old`` = {w, z, n ``[slots]``, v, a ``[slots,
    K]``} and an id's mean gradients ``grad`` = {w, v} give the five new
    values."""
    new_n = old["n"] + grad["w"] * grad["w"]
    root = jnp.sqrt(new_n)
    new_z = old["z"] + grad["w"] - (
        root - jnp.sqrt(old["n"])) / alpha * old["w"]
    new_w = jnp.where(
        jnp.abs(new_z) <= rule.l1, 0.0,
        -(new_z - jnp.sign(new_z) * rule.l1)
        / ((rule.lr_beta + root) / alpha + l2))
    grad_v = grad["v"] + rule.v_l2 * old["v"]
    new_a = old["a"] + grad_v * grad_v
    new_v = old["v"] - rule.v_learning_rate * grad_v / (
        rule.v_lr_beta + jnp.sqrt(new_a))
    return {"w": new_w, "z": new_z, "n": new_n, "v": new_v, "a": new_a}


def _stateful_update(params, order: _IdOrder, grads, seen,
                     learning_rate: float, l2: float, rule: FtrlAdagrad):
    """The step's update under ``optimizer="ftrl_adagrad"``, difacto's
    rule (github.com/dmlc/difacto ``src/sgd/sgd_updater.cc``; Li et al.,
    WSDM 2016), from what :func:`_sparse_update` takes and ``seen`` = (the
    distinct ids' ``[v | w]`` the head read, the entries' values). Per
    distinct id i of the batch, with g the batch's mean gradient of w_i
    and G that of v_i (the sums over the entries that name i, over
    ``weight_sum``):

    w, FTRL-proximal (McMahan et al., KDD 2013, algorithm 1; state z, n;
    alpha = ``learning_rate``, beta = ``lr_beta``)::

        n' = n + g^2;  z' = z + g - (sqrt(n') - sqrt(n)) / alpha * w
        w' = 0 if |z'| <= l1 else
             -(z' - sign(z') l1) / ((beta + sqrt(n')) / alpha + l2)

    v, AdaGrad per element (Duchi et al., 2011; state a)::

        G = G + v_l2 * v;  a' = a + G^2
        v' = v - v_learning_rate * G / (v_lr_beta + sqrt(a'))

    b by SGD at ``learning_rate``. How the sums, the state's read and the
    SETs are laid out is :func:`_update_at_distinct`'s: an id under the
    L1 threshold holds an exact 0, and a slot whose entries all have
    value 0 keeps weights and state to the bit, whatever ``v_l2``."""
    dw, gb, dv, wsum = grads
    new, denom = _update_at_distinct(
        params, order, {"v": dv, "w": dw}, seen, wsum, ("a", "z", "n"),
        partial(_ftrl_adagrad, alpha=learning_rate, l2=l2, rule=rule))
    with jax.named_scope("step.update"):
        return dict(new, b=params["b"] - learning_rate * (gb / denom))


def _batch_specs(axis: str):
    """How a mesh step takes the feed's batch: entries arrive SHARDED
    (ShardedCSRBatch: per-shard sections, local row ids), each device
    holds only its own nnz; no global mask."""
    return {k: P(axis)
            for k in ("label", "weight", "indices", "values", "offsets")}


def _make_sparse_step(local, name: str, mesh: Optional[Mesh], axis: str,
                      param_specs, donate_batch: bool):
    """The two programs whose update touches only the rows a batch names,
    for a step ``local(params, batch, factor_axis) -> (params, metrics)``
    (the FM's, models/ffm.py's), jitted as ``name``.

    ``mesh is None``: ``local`` on the one device; ``donate_batch``
    donates params AND the batch arrays. On a mesh (the table's factors
    sharded by ``param_specs``): every chip gathers the step's whole
    batch (:func:`_gather_sections`, under ``step.exchange``) and runs
    ``local`` on its own columns with ``factor_axis=axis``."""
    if mesh is None:

        def step(params, batch):
            return local(params, batch, None)

        fn = instrumented_jit(
            step, name,
            donate_argnums=(0, 1) if donate_batch else (),
        )
        return suppress_donation_warnings(fn) if donate_batch else fn

    def _factor_sharded(params, batch):
        with jax.named_scope("step.exchange"):
            whole = _gather_sections(batch, axis)
        return local(params, whole, axis)

    # every chip computes its replicas (the FM's w and b) and the loss
    # sums from the gathered batch, which shard_map types as varying: the
    # replicas are equal by construction, not by a collective it could
    # check
    step = shard_map(
        _factor_sharded, mesh=mesh,
        in_specs=(param_specs, _batch_specs(axis)),
        out_specs=(param_specs, P()),
        check_vma=False,
    )
    return instrumented_jit(step, name, donate_argnums=(0,))


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
    rule: Optional[FtrlAdagrad] = None,
):
    """Jitted FM step over COO batches: ``(params, batch) -> (params,
    metrics)``, metrics = ``loss_sum``, ``weight_sum`` and
    ``touched_rows``, the count of parameter rows the step read (the
    distinct ids of what a chip sorted, summed over the chips where each
    sorts its own section): device scalars the fit loop reads once a
    pass. Every program opens with :func:`_entries_in_id_order`.

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

    ``rule`` (``FMParam.optimizer="ftrl_adagrad"``; None is plain SGD):
    ``params`` holds the rule's state tables too (``init_fm_params(...,
    optimizer=)``) and both sparse paths update through
    :func:`_stateful_update`, on a factor-sharded mesh every chip its
    columns of ``a`` as of ``v`` and its replica of ``z`` and ``n``. The
    replicated mesh step reduces the entries to a dense gradient, which
    has no per-row state to meet: it refuses a rule.

    ``donate_batch=True`` (single-device path) donates params AND the
    batch arrays, the same contract as
    :func:`~dmlc_tpu.models.linear.make_linear_train_step`: XLA reuses
    the H2D landing buffers and scatters into the factor table in place
    (without it the step copies the table first) — only for streaming
    callers that rebind params each step and never touch a batch after
    its step (DeviceFeed loops, FMLearner)."""
    check(num_features > 0, "num_features required")
    optimizer = "sgd" if rule is None else "ftrl_adagrad"
    _check_rule_placement(optimizer, mesh, table_sharding)

    def local(params, batch, factor_axis):
        dw, gb, dv, loss_sum, wsum, order, seen = _fm_entry_grads(
            params, batch, objective, factor_axis=factor_axis)
        params = _sparse_update(
            params, order, (dw, gb, dv, wsum), learning_rate, l2,
            seen, rule)
        return params, {"loss_sum": loss_sum, "weight_sum": wsum,
                        "touched_rows": order.distinct}

    if mesh is not None and param_specs is None:
        param_specs = match_partition_rules(
            fm_partition_rules(table_sharding),
            jax.eval_shape(lambda: init_fm_params(
                max(num_features, 1), 2, optimizer=optimizer)),
        )
    if mesh is None or table_sharding == "factors":
        return _make_sparse_step(
            local, "fm.step", mesh, axis, param_specs, donate_batch)

    def _sharded(params, batch):
        dw, gb, dv, loss_sum, wsum, order, _ = _fm_entry_grads(
            params, batch, objective)
        with jax.named_scope("step.scatter"):
            gw = jax.ops.segment_sum(
                dw, order.entry_ids, num_segments=num_features,
                indices_are_sorted=True)
            gv = jax.ops.segment_sum(
                dv, order.entry_ids, num_segments=num_features,
                indices_are_sorted=True)
        # gradients never round-trip through host numpy: one bucketed
        # in-graph psum carries the whole gradient pytree across ICI (the
        # chips' counts of distinct ids ride it as a float, exact below
        # 2**24)
        gw, gb, gv, loss_sum, wsum, touched = bucketed_psum(
            (gw, gb, gv, loss_sum, wsum,
             order.distinct.astype(jnp.float32)), axis=axis
        )
        with jax.named_scope("step.update"):
            denom = jnp.maximum(wsum, 1e-12)
            params = {
                "w": params["w"] - learning_rate * (gw / denom + l2 * params["w"]),
                "b": params["b"] - learning_rate * (gb / denom),
                "v": params["v"] - learning_rate * (gv / denom + l2 * params["v"]),
            }
        return params, {"loss_sum": loss_sum, "weight_sum": wsum,
                        "touched_rows": touched.astype(jnp.int32)}

    step = shard_map(
        _sharded, mesh=mesh,
        in_specs=(param_specs, _batch_specs(axis)),
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
    the way back is the last snapshot.

    ``optimizer="ftrl_adagrad"`` (and ``l1``, ``lr_beta``,
    ``v_learning_rate``, ``v_lr_beta``, ``v_l2``; :func:`_stateful_update`
    has the rule): ``params`` holds the rule's state tables ``z``, ``n``
    and ``a`` beside ``w``, ``b``, ``v``, placed as their weights are, so
    a snapshot, a restore under another placement and ``reshard`` carry
    them with no word of their own. ``predict_batch`` ignores them. One
    device and a factor-sharded mesh take the rule; a mesh of replicas
    refuses it."""

    name = "fm"
    #: the learner's hyper-parameters' class
    param_class = FMParam
    #: the mesh axis the batch (and a sharded table) divides over, the
    #: DeviceFeed's default
    axis = "dp"

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        self.param = self.param_class()
        self.param.init(hyper)
        self._nf = None
        # the steps since the last epoch boundary by nnz bucket, and the
        # bytes a sharded table's step exchanges at that bucket (the
        # shapes the step was compiled for fix the bytes)
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

    @property
    def optimizer(self) -> str:
        """The update rule's name, the ``optimizer`` label of the
        learner's span and counters."""
        return self.param.optimizer

    @property
    def state_tables(self):
        """The tables of ``params`` the rule keeps beside the weights
        (none under plain SGD)."""
        return () if self.param.optimizer == "sgd" else STATE_TABLES

    @property
    def columns(self) -> int:
        """The width of ``v`` (and ``a``), what a factor-sharded mesh
        divides."""
        return self.param.num_factors

    def partition_rules(self):
        return fm_partition_rules(self.param.table_sharding)

    def check_mesh(self, mesh: Mesh) -> None:
        _check_rule_placement(
            self.optimizer, mesh, self.param.table_sharding)
        if self.param.table_sharding == "factors":
            _check_factor_shards(self.param.num_factors, mesh, self.axis)

    @property
    def rule(self) -> Optional[FtrlAdagrad]:
        """The stateful rule's hyperparameters, None under plain SGD."""
        if self.param.optimizer == "sgd":
            return None
        return FtrlAdagrad(**{f: getattr(self.param, f)
                              for f in FtrlAdagrad._fields})

    def _initialiser(self, num_features: int):
        """``seed -> params`` of this learner's shapes over
        ``num_features`` ids."""
        return partial(init_fm_params, num_features, self.param.num_factors,
                       self.param.init_scale, optimizer=self.param.optimizer)

    def _make_step(self, num_features: int):
        return make_fm_train_step(
            self.mesh, num_features,
            objective=self.param.objective,
            learning_rate=self.param.learning_rate,
            l2=self.param.l2,
            axis=self.axis,
            # the fit loop rebinds params every step and never touches
            # a batch after its step — the donation contract holds
            donate_batch=self.mesh is None,
            table_sharding=self.param.table_sharding,
            rule=self.rule,
        )

    def param_shardings(self):
        """NamedSharding tree of the params on this learner's mesh (None
        without one): what an initialiser's ``out_shardings`` takes so
        that each chip generates only the part it holds."""
        if self.mesh is None:
            return None
        # the rules go by a leaf's name and rank, not by its size
        template = jax.eval_shape(self._initialiser(2))
        return sharding_tree(
            self.mesh,
            match_partition_rules(self.partition_rules(), template))

    def _ensure(self, num_features: int):
        if self.params is None:
            nf = self.param.num_features or num_features
            init = self._initialiser(nf)
            # on a mesh the initialiser runs as one program placed by the
            # rules: a chip writes its own part and no whole table exists
            # on any one of them first
            self.params = init() if self.mesh is None else jax.jit(
                init, out_shardings=self.param_shardings())()
            self._nf = nf
        if self._step is None:
            self._step = self._make_step(
                self._nf or self.param.num_features or num_features)

    def ensure_step(self, spec) -> None:
        check(spec.layout == "csr", "FM consumes csr batches")
        self._ensure(self.param.num_features)

    def train_step(self, arrays: Dict) -> Dict:
        bucket = arrays["indices"].shape[0]
        self._steps_of[bucket] = self._steps_of.get(bucket, 0) + 1
        shards = self.table_shards
        if shards > 1 and bucket not in self._bytes_of:
            self._bytes_of[bucket] = exchange_bytes(arrays, shards)
        self.params, metrics = self._step(self.params, arrays)
        return metrics

    def epoch_span_args(self) -> Dict:
        return {"table_shards": self.table_shards,
                "optimizer": self.optimizer}

    def state_bytes(self) -> int:
        """Bytes of optimizer state one chip holds: its part of every
        table the rule keeps beside the weights (0 under plain SGD)."""
        if self.params is None:
            return 0
        # from the shapes: ``a`` is divided as ``v`` is, ``z`` and ``n``
        # are whole on every chip
        return sum(
            int(self.params[k].nbytes)
            // (self.table_shards if self.params[k].ndim == 2 else 1)
            for k in self.state_tables)

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """FM's own counters. The step was built for ``self.mesh`` and the
        table's sharding (``_ensure``): one device and a factor-sharded
        mesh scatter-add every step, a mesh of replicas none.
        ``dmlc_fit_sparse_update_steps_total`` over
        ``dmlc_fit_steps_total`` is the share of steps that took that
        path; the exchanged bytes are from the shapes (the gradient psum
        of a replicated model is not among them,
        ``dmlc_xla_collective_bytes`` has it).

        ``dmlc_fit_touched_rows_total`` over ``dmlc_fit_entries_total`` is
        the share of parameter reads the steps still made: the distinct
        ids of each batch (``touched_rows``, counted on the device and
        read with the pass's losses) over its entries (from the shapes,
        padding included). 1.0 on data with no repeated id.

        ``dmlc_fit_stateful_update_steps_total`` counts the steps that
        took a rule with per-row state (``optimizer`` names it; none
        under ``"sgd"``), ``dmlc_fit_optimizer_state_bytes`` what that
        state holds of one chip's memory."""
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
                sum(n * self._bytes_of.get(b, 0)
                    for b, n in self._steps_of.items()))
        reg.counter(
            "dmlc_fit_touched_rows_total",
            "parameter rows the steps read: the distinct feature ids "
            "of each batch",
            model=self.name).inc(sums.get("touched_rows", 0))
        reg.counter(
            "dmlc_fit_entries_total",
            "entries of the batches the steps took, padding included",
            model=self.name).inc(
                sum(n * b for b, n in self._steps_of.items()))
        reg.counter(
            "dmlc_fit_stateful_update_steps_total",
            "optimizer steps that read and wrote per-row optimizer state "
            "at the rows the batch named",
            model=self.name, optimizer=self.optimizer).inc(
                nstep if self.state_tables else 0)
        reg.gauge(
            "dmlc_fit_optimizer_state_bytes",
            "bytes of optimizer state on one chip, beside the weights",
            model=self.name).set(self.state_bytes())
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
                self.columns)
        check(tuple(params["v"].shape) == want,
              "snapshot holds a factor table of shape %s, this learner "
              "trains %s", tuple(params["v"].shape), want)
        held = sorted(k for k in STATE_TABLES if k in params)
        need = sorted(self.state_tables)
        check(held == need,
              "snapshot holds the optimizer state %s, optimizer=%r keeps %s",
              held, self.optimizer, need)
        self._nf = want[0]
        if self.mesh is None:
            self.params = {k: jnp.asarray(v) for k, v in params.items()}
        else:
            self.params = shard_params(
                params, self.mesh, rules=self.partition_rules())

    def predict_batch(self, batch) -> np.ndarray:
        _, _, vw, row_ids, values = _entries_in_id_order(
            (self.params["v"], self.params["w"]), batch)
        _, s, q, linear = _row_sums(
            vw, row_ids, values, int(batch["label"].shape[0]))
        return np.asarray(
            self.params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
        )

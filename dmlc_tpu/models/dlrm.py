"""DLRM over COO device batches: a dense net over the table's rows
(Naumov et al., "Deep Learning Recommendation Model for Personalization
and Recommendation Systems", arXiv:1906.00091; github.com/facebookresearch/dlrm
``dlrm_s_pytorch.py``, interaction ``dot`` without the diagonal, plain
SGD, sparse gradients for the tables).

One row: label ``y``, ``x`` the D dense features, ids ``i_1..i_F``, one
of each of F tables, every table's rows K wide::

    h = x;  h = relu(W_l h + b_l)  over the bottom MLP's layers;  z = h   [K]
    e_f = E[i_f]                  (one id of a table a row: the bag is the row)
    T = [z; e_1; ...; e_F] [F + 1, K];  P = T T^t
    p = the (F + 1) F / 2 entries of P strictly under the diagonal, row-major
    r = [z; p];  r = relu(V_l r + c_l)  over the top MLP's layers but the last,
    s = V_last r + c_last   [1]
    loss = mean over the batch of -(y log sigmoid(s) + (1 - y) log(1 - sigmoid(s)))
    step : theta <- theta - learning_rate * dloss/dtheta  for every W, b, V, c
           and for the rows of E the batch names (a row named twice gets the sum)

It is the first learner here whose step holds matrix products and whose
parameters are not all per-id rows: the state is ONE per-id table ``emb``
(``f32[ids, K]``; on one device a :class:`~dmlc_tpu.models.fm.PackedTables`
of lane rows, 8 ids of 16 columns to 128 lanes) **and a tree of dense
arrays** (``bot.0.w`` ... ``top.2.b``, ``w`` as ``[out, in]``), kept where
the FM keeps its scalar ``b``: the tree's leaves that are no per-id table.

A batch's entries are of two kinds, told apart by the id's range as
models/ffm.py tells fields apart. Ids ``1..D`` (``dense_features``) index
no table: an entry ``d:x`` is the row's dense feature ``d``, a real
number. The ids after them are the tables' rows, ``field_sizes`` as
contiguous ranges in order (table f from ``1 + D + sum(sizes[:f])``); an
entry there with a value other than 0 names the row's id of that table,
and the value is not read further. Id 0 is the feed's padding. Rows
``0..D`` of ``emb`` are never read into the model and never change.

The step, by scope. ``step.order``: the batch laid out as ``x [D, rows]``
and ``ids [rows, F]`` (0 where a row names no id of a table: such an entry
adds a zero vector), then the FM's sort by id over those ``rows x F``
table entries ONLY, with each entry's place ``row * F + table`` as the
payload, and the sort that brings every entry's slot back to its place.
``step.gather``: the one read at the distinct ids (models/fm.py
``_read_distinct``) and each entry's K words, in place order.
``step.dense``: both MLPs, the interaction, the loss, their backward
pass (``jax.vjp`` of the plain forward) and the dense parameters' SGD;
activations are ``[features, rows]``, the rows along the chip's lanes, so
that the interaction is a product and a sum over whole vectors and the
triangle is cut along major axes; every matrix product at
``precision=HIGHEST`` (float32 as the configuration states it: six
bfloat16 passes on the MXU). ``step.update``: each entry's gradient
brought to id order, an id's entries summed (on a TPU by a segmented
scan along each id's run), ``old + sum``, and the distinct lane rows
written back (models/fm.py ``_add_lane_rows``: the writer every rule
shares, on a TPU one DMA a row).

How the batch is laid out. A batch whose every row lists its D dense ids
in order and then one id of every table in order (what a LIBSVM file of
such rows gives: 39 entries a row here) IS that layout, and is cut by a
reshape. Any other batch (a row that lacks an entry, a short last batch)
is laid out by two scatters over its entries, which on the v5e cost 5.6
ms a batch of 8192 rows where the reshape costs 0.03 (a step of 9.3 ms
becomes one of 14.9: PERF.md, PR 42); the program holds both and a
``cond`` picks. A row that names two ids of one
table (a multi-hot bag) keeps the larger and the step counts the other
as left out; :meth:`DLRMLearner.epoch_closed` refuses a pass that left
any out.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from dmlc_tpu.models.ffm import _field_sizes
from dmlc_tpu.models.fm import (
    FMLearner,
    PackedTables,
    _IdOrder,
    _Read,
    _add_lane_rows,
    _groups,
    _head_tables,
    _make_sparse_step,
    _read_distinct,
    _regroup,
    _scatter_add_rows,
    _slots_in_id_order,
    _summer,
    _write_rows,
    init_packed,
)
from dmlc_tpu.models.linear import margin_grad
from dmlc_tpu.ops.spmv import expand_row_ids
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import check

#: the one per-id table, by the name the equations use
DLRM_TABLES = ("emb",)

_HIGHEST = lax.Precision.HIGHEST


class DLRMParam(Parameter):
    # the source's --learning-rate; plain SGD on every parameter
    learning_rate = field(float, 0.1, lower_bound=0.0)
    # --arch-sparse-feature-size: the columns of every table's rows
    num_factors = field(int, 16, lower_bound=1)
    num_features = field(int, 0)
    # the ids 1..dense_features index no table: their values are the
    # row's dense features
    dense_features = field(int, 13, lower_bound=1)
    # the tables' rows as contiguous id ranges in order, after the dense ids
    field_sizes = field(_field_sizes, ())
    # --arch-mlp-bot, input to output: dense_features first, num_factors last
    mlp_bot = field(_field_sizes, (13, 512, 256, 64, 16))
    # --arch-mlp-top WITH its input (the source derives it): num_factors +
    # the pairs of the tables and the bottom MLP's output; 1 last
    mlp_top = field(_field_sizes, ())


def table_lows(dense_features: int, field_sizes) -> np.ndarray:
    """The first id of every table: the tables partition the ids after
    ``dense_features`` in order."""
    sizes = np.asarray(field_sizes, dtype=np.int64)
    return 1 + dense_features + np.concatenate([[0], np.cumsum(sizes)[:-1]])


def dense_shapes(mlp_bot, mlp_top) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of the dense parameters, in the order of the
    equations: ``bot.<l>.w [out, in]``, ``bot.<l>.b [out]``, then the top
    MLP's."""
    shapes = {}
    for net, widths in (("bot", mlp_bot), ("top", mlp_top)):
        for layer, (n, m) in enumerate(zip(widths[:-1], widths[1:])):
            shapes["%s.%d.w" % (net, layer)] = (m, n)
            shapes["%s.%d.b" % (net, layer)] = (m,)
    return shapes


def init_dense(mlp_bot, mlp_top, key) -> Dict:
    """The dense parameters as the source starts them: ``w [m, n]`` normal
    with deviation ``sqrt(2 / (m + n))``, ``b [m]`` with ``sqrt(1 / m)``."""
    out = {}
    for at, (name, shape) in enumerate(dense_shapes(mlp_bot, mlp_top).items()):
        m = shape[0]
        spread = np.sqrt(2.0 / (m + shape[1])) if len(shape) == 2 \
            else np.sqrt(1.0 / m)
        out[name] = np.float32(spread) * jax.random.normal(
            jax.random.fold_in(key, at), shape, dtype=jnp.float32)
    return out


def _signed_draw(key, shape):
    """A table's start before its scale: uniform in [-1, 1)."""
    return jax.random.uniform(
        key, shape, dtype=jnp.float32, minval=-1.0, maxval=1.0)


def _id_scale(ids, lows, field_sizes):
    """``sqrt(1 / rows of its table)`` for every id of ``ids``, the
    source's spread of a table's start; 0 for the ids before the tables."""
    scale = jnp.zeros(ids.shape, jnp.float32)
    for low, size in zip(lows, field_sizes):
        scale = jnp.where(ids >= int(low), np.float32(np.sqrt(1.0 / size)),
                          scale)
    return scale


def init_dlrm_params(num_features: int, num_factors: int, dense_features: int,
                     field_sizes, mlp_bot, mlp_top, seed=0) -> Dict:
    """The logical tree, one array a leaf: ``emb f32[num_features,
    num_factors]`` (a table's rows uniform in ``+-sqrt(1 / its rows)``,
    the rows before the tables 0) and the dense parameters of
    :func:`init_dense`."""
    key = jax.random.PRNGKey(seed)
    ids = jnp.arange(num_features, dtype=jnp.int32)[:, None]
    emb = _signed_draw(key, (num_features, num_factors)) * _id_scale(
        ids, table_lows(dense_features, field_sizes), field_sizes)
    return dict(init_dense(mlp_bot, mlp_top, key), emb=emb)


def _lay_out(batch, dense: int, lows, sizes):
    """The batch as the model reads it: ``x f32[dense, rows]`` (a row's
    dense features down a column; 0 where the row has no such entry),
    ``ids s32[rows * fields]`` (place ``row * fields + f`` holds the row's
    id of table f, 0 where it names none) and the count of valued table
    entries that found no place (a second id of one table in one row).

    Two ways to it, chosen on the device by what the batch holds (the
    module's docstring): a batch whose every row lists ``1..dense`` and
    then one id of each table, in order, is cut by a reshape; any other
    by a scatter-add of the dense values and a scatter-max of the ids."""
    indices, values = batch["indices"], batch["values"]
    rows = batch["label"].shape[0]
    fields = len(sizes)
    width = dense + fields
    last = np.asarray(lows) + np.asarray(sizes) - 1  # a table's last id

    def scattered():
        n = indices.shape[0]
        row_ids = batch["row_ids"] if "row_ids" in batch else \
            expand_row_ids(batch["offsets"], n)
        is_dense = (indices >= 1) & (indices <= dense)
        named = (indices > dense) & (values != 0)
        table = sum((indices > int(hi)).astype(jnp.int32) for hi in last[:-1])
        x = jnp.zeros((rows * dense + 1,), values.dtype).at[
            jnp.where(is_dense, row_ids * dense + indices - 1, rows * dense)
        ].add(values)[:-1].reshape(rows, dense).T
        ids = jnp.zeros((rows * fields + 1,), indices.dtype).at[
            jnp.where(named, row_ids * fields + table, rows * fields)
        ].max(indices)[:-1]
        return x, ids, jnp.sum(named) - jnp.sum(ids != 0)

    if rows * width > indices.shape[0]:
        return scattered()
    v = values[:rows * width].reshape(rows, width)
    i = indices[:rows * width].reshape(rows, width)

    def cut():
        return (v[:, :dense].T,
                jnp.where(v[:, dense:] != 0, i[:, dense:], 0).reshape(-1),
                jnp.zeros((), jnp.int32))

    in_order = (
        jnp.all(batch["offsets"] == width * jnp.arange(
            rows + 1, dtype=batch["offsets"].dtype))
        & jnp.all(i[:, :dense] == jnp.arange(1, dense + 1, dtype=i.dtype))
        & jnp.all((i[:, dense:] >= jnp.asarray(lows, i.dtype))
                  & (i[:, dense:] <= jnp.asarray(last, i.dtype))))
    return lax.cond(in_order, cut, scattered)


def _mlp(dense: Dict, net: str, h, last_relu: bool):
    """``h [in, rows]`` through the layers ``<net>.<l>``: ``relu(w h +
    b)``, the last layer without the ``relu`` unless ``last_relu``."""
    layers = sum(name.startswith(net + ".") for name in dense) // 2
    for layer in range(layers):
        h = jnp.matmul(dense["%s.%d.w" % (net, layer)], h,
                       precision=_HIGHEST) \
            + dense["%s.%d.b" % (net, layer)][:, None]
        if last_relu or layer + 1 < layers:
            h = jax.nn.relu(h)
    return h


def dlrm_forward(dense: Dict, emb, x):
    """The model's margin ``s f32[rows]`` from the dense parameters, the
    rows' table vectors ``emb [fields, K, rows]`` and their dense
    features ``x [D, rows]``: the equations of the module's docstring,
    activations as ``[features, rows]``."""
    z = _mlp(dense, "bot", x, last_relu=True)  # [K, rows]
    t = jnp.concatenate([z[None], emb], axis=0)  # [fields + 1, K, rows]
    # row i of P under its diagonal: t_i against the vectors before it,
    # a product and a sum over whole [K, rows] blocks
    under = jnp.concatenate(
        [jnp.sum(t[i][None] * t[:i], axis=1) for i in range(1, t.shape[0])],
        axis=0)  # [(F + 1) F / 2, rows]
    return _mlp(dense, "top", jnp.concatenate([z, under], axis=0),
                last_relu=False)[0]


def _by_table(rows, fields: int):
    """``rows [places, K]``, a row a place (place ``r * fields + f``), as
    ``[fields, K, batch rows]``: the batch's rows along the lanes."""
    return rows.reshape(-1, fields, rows.shape[1]).transpose(1, 2, 0)


def _by_place(vectors):
    """:func:`_by_table` back: ``[places, K]``."""
    fields, k, rows = vectors.shape
    return vectors.transpose(2, 0, 1).reshape(rows * fields, k)


def _named(ids, fields: int):
    """``bool[fields, 1, batch rows]``: whether a row names an id of a
    table, laid as :func:`_by_table` lays the vectors."""
    return (ids != 0).reshape(-1, fields).T[:, None, :]


def _order_places(ids, num_ids: int):
    """The FM's step head over the table entries alone: ``ids
    s32[places]`` sorted, each entry's place its payload. Returns (the
    :class:`~dmlc_tpu.models.fm._IdOrder`, every entry's place in id
    order, every place's slot): the second sort brings the slots, which
    are by id order, back to place order (a sort of two ``s32`` arrays
    where a scatter by place costs several times as much on the chip)."""
    places = jnp.arange(ids.shape[0], dtype=jnp.int32)
    sorted_ids, place = lax.sort((ids, places), num_keys=1)
    order = _slots_in_id_order(sorted_ids, num_ids)
    _, slot = lax.sort((place, order.slot), num_keys=1)
    return order, place, slot


class _Head(NamedTuple):
    """What the step's head hands the model (:func:`_head`)."""

    #: f32[dense features, rows] the rows' dense features
    x: jax.Array
    #: s32[places] place ``row * tables + table`` holds the row's id of
    #: that table, 0 where it names none
    ids: jax.Array
    #: s32[] valued table entries that found no place
    left_out: jax.Array
    #: the table entries in id order
    order: _IdOrder
    #: s32[places] every entry's place, in id order
    place: jax.Array
    #: what the one read at the distinct ids brought
    read: _Read
    #: f32[places, K] every place's row of the table
    rows: jax.Array


def _head(params, batch, dense_features: int, lows, sizes) -> _Head:
    """``step.order`` and ``step.gather``, the head of the step and of
    ``predict_batch``: the batch laid out (:func:`_lay_out`), its table
    entries in id order (:func:`_order_places`), the one read at the
    distinct ids and every place's row from that buffer."""
    tables, _ = _head_tables(params, DLRM_TABLES)
    num_ids = tables.num_ids if isinstance(
        tables, PackedTables) else tables[0].shape[0]
    with jax.named_scope("step.order"):
        x, ids, left_out = _lay_out(batch, dense_features, lows, sizes)
        order, place, slot = _order_places(ids, num_ids)
    with jax.named_scope("step.gather"):
        read = _read_distinct(tables, order)
        # a slot and a place lie inside their arrays by construction:
        # said, so that no gather carries the select that fills what
        # lies outside (a pass over ``[places, 16]`` pays for 128 lanes)
        rows = read.words.at[slot].get(mode="promise_in_bounds")
    return _Head(x, ids, left_out, order, place, read, rows)


def _model(head: _Head, fields: int):
    """``(dense, vectors [fields, K, rows]) -> margins`` over the head's
    batch. A place that holds no id read row 0: masked here, where the
    rows lie along the lanes (a pass over ``[places, 16]`` pays for 128
    lanes a row)."""
    named = _named(head.ids, fields)
    return lambda dense, vectors: dlrm_forward(
        dense, jnp.where(named, vectors, 0.0), head.x)


def make_dlrm_train_step(
    num_features: int,
    dense_features: int,
    field_sizes,
    learning_rate: float = 0.1,
    donate_batch: bool = False,
    platform: Optional[str] = None,
    interpret: bool = False,
    id_sums: Optional[Dict[int, str]] = None,
):
    """Jitted DLRM step over COO batches on ONE device, ``(params, batch)
    -> (params, metrics)``. ``params``: the dict of
    :func:`init_dlrm_params` or a :class:`~dmlc_tpu.models.fm.PackedTables`
    whose one table is ``emb`` and whose other leaves are the dense
    parameters; it comes back as it came (the step takes the grouping of
    its reads and writes from the tree it is given, as the FM's does).
    Metrics: the FM's ``loss_sum``, ``weight_sum``, ``touched_rows``, and
    ``left_out``, the valued table entries that took no part
    (:func:`_lay_out`). ``donate_batch``, ``platform``, ``interpret`` and
    ``id_sums`` as :func:`~dmlc_tpu.models.fm.make_fm_train_step` takes
    them."""
    check(num_features > 0, "num_features required")
    sizes = tuple(int(n) for n in field_sizes)
    lows = table_lows(dense_features, sizes)
    check(int(lows[-1]) + sizes[-1] <= num_features,
          "the tables end at id %d, num_features is %d",
          int(lows[-1]) + sizes[-1] - 1, num_features)
    fields = len(sizes)
    write = partial(_write_rows, platform=platform, interpret=interpret)

    def local(params, batch, _):
        label, weight = batch["label"], batch["weight"]
        head = _head(params, batch, dense_features, lows, sizes)
        dense = {k: params[k] for k in params if k not in DLRM_TABLES}
        with jax.named_scope("step.dense"):
            # the backward pass is made under the scope too: a reader of
            # the trace gives an operation to the first part of its path
            # that starts with ``step.``
            margin, back = jax.vjp(
                _model(head, fields), dense, _by_table(head.rows, fields))
            loss, slope = margin_grad("logistic", margin, label)
            wsum = jnp.sum(weight)
            denom = jnp.maximum(wsum, 1e-12)
            loss_sum = jnp.sum(weight * loss)
            grads, by_table = back(weight * slope / denom)
            dense = {k: v - learning_rate * grads[k]
                     for k, v in dense.items()}
            upd = _by_place(-learning_rate * by_table)
        with jax.named_scope("step.update"):
            # every entry's update in id order (a place that holds no id
            # brings 0 to row 0), then the FM's update of plain SGD
            upd = upd.at[head.place].get(mode="promise_in_bounds")
            if isinstance(params, PackedTables):
                arrays = [_add_lane_rows(
                    params, head.order, upd, head.read, None, write,
                    _summer(platform, interpret, id_sums, batch))]
            else:
                arrays = _scatter_add_rows(
                    _groups(params, DLRM_TABLES), head.order, upd)
            params = _regroup(params, DLRM_TABLES, arrays, dense)
        return params, {"loss_sum": loss_sum, "weight_sum": wsum,
                        "touched_rows": head.order.distinct,
                        "left_out": head.left_out}

    return _make_sparse_step(local, "dlrm.step", None, "dp", None,
                             donate_batch)


class DLRMLearner(FMLearner):
    """uri → fitted DLRM params over a DeviceFeed (csr layout), through
    the fit loop, the counters, the placement code, the row writer and
    the check's five calls of :class:`~dmlc_tpu.models.fm.FMLearner`
    (the module's docstring has the model, how a batch's entries are told
    apart, and the step). ONE device: a mesh is refused by name.

    ``params`` is a :class:`~dmlc_tpu.models.fm.PackedTables` whose one
    table is ``emb`` (lane rows: 8 ids of 16 columns) and whose other
    leaves are the dense parameters (:func:`dense_shapes`).
    :meth:`scalars` hands each of them out WHOLE under its name, an array
    and no float: everything of the model that is no per-id table. A
    snapshot holds ``emb`` as the logical ``[ids, K]`` table and the dense
    parameters by name, and restores into lane rows."""

    name = "dlrm"
    param_class = DLRMParam
    optimizer = "sgd"
    state_tables = ()

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        super().__init__(mesh, **hyper)
        p = self.param
        check(len(p.field_sizes) > 0 and min(p.field_sizes) > 0,
              "a DLRM takes an entry's table from its id's range: give "
              "field_sizes, the tables' rows as contiguous id ranges in "
              "order, got %r", p.field_sizes)
        ids = 1 + p.dense_features + sum(p.field_sizes)
        check(p.num_features in (0, ids),
              "id 0, %d dense ids and the tables' %d rows are %d ids, "
              "num_features is %d", p.dense_features, sum(p.field_sizes),
              ids, p.num_features)
        p.num_features = ids
        top_in = p.num_factors + self.fields * (self.fields + 1) // 2
        check(p.mlp_bot[0] == p.dense_features
              and p.mlp_bot[-1] == p.num_factors,
              "mlp_bot runs from dense_features %d to num_factors %d, got %r",
              p.dense_features, p.num_factors, p.mlp_bot)
        check(len(p.mlp_top) >= 2 and p.mlp_top[0] == top_in
              and p.mlp_top[-1] == 1,
              "mlp_top runs from %d (num_factors + the %d pairs) to 1, "
              "got %r", top_in, top_in - p.num_factors, p.mlp_top)

    def check_mesh(self, mesh: Mesh) -> None:
        check(mesh is None,
              "a DLRM's dense net lives whole on ONE device beside its "
              "table; a mesh has no path for it (no psum of a dense "
              "gradient, no placement of a dense tree): train on one device")

    @property
    def fields(self) -> int:
        return len(self.param.field_sizes)

    @property
    def dense_params(self) -> int:
        """The count of the dense net's values."""
        return sum(int(np.prod(shape)) for shape in dense_shapes(
            self.param.mlp_bot, self.param.mlp_top).values())

    def table_layout(self):
        return ((DLRM_TABLES[0], self.param.num_factors),)

    def _initialiser(self, num_features: int):
        p = self.param
        lows = table_lows(p.dense_features, p.field_sizes)

        def init(seed):
            packed = init_packed(
                num_features, self.table_layout(), _signed_draw, {}, {},
                seed, scale=partial(
                    _id_scale, lows=lows, field_sizes=p.field_sizes))
            return PackedTables(
                packed.rows,
                init_dense(p.mlp_bot, p.mlp_top, jax.random.PRNGKey(seed)),
                packed.layout, packed.num_ids)

        return init

    def _make_step(self, num_features: int):
        return make_dlrm_train_step(
            num_features, self.param.dense_features, self.param.field_sizes,
            learning_rate=self.param.learning_rate, donate_batch=True,
            platform=self._step_platform, id_sums=self._id_sums)

    def epoch_span_args(self) -> Dict:
        return dict(super().epoch_span_args(), fields=self.fields,
                    dense_features=self.param.dense_features,
                    dense_params=self.dense_params)

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """The FM's counters under ``model="dlrm"``, and
        ``dmlc_fit_dense_net_steps_total``: over ``dmlc_fit_steps_total``
        the share of steps that ran a dense net over the table's rows and
        updated its parameters (every step of this learner);
        ``dmlc_fit_dense_param_bytes``: what that net holds of one chip's
        memory. A pass whose batches named two ids of one table in one
        row is refused here (the step keeps one: :func:`_lay_out`)."""
        super().epoch_closed(reg, nstep, sums)
        reg.counter(
            "dmlc_fit_dense_net_steps_total",
            "optimizer steps that ran a dense net (matrix products) over "
            "the table's rows and updated its dense parameters",
            model=self.name).inc(nstep)
        reg.gauge(
            "dmlc_fit_dense_param_bytes",
            "bytes of dense (not per-id) parameters on one chip",
            model=self.name).set(4 * self.dense_params)
        check(not sums.get("left_out", 0),
              "%d table entries took no part in the pass: a row named more "
              "than one id of one table (a multi-hot bag), which this "
              "learner does not sum", int(sums.get("left_out", 0)))

    def restore_snapshot_model(self, model: Dict) -> None:
        """A snapshot's logical ``emb`` and dense parameters into this
        learner's lane rows, from the host arrays."""
        params = {k: np.asarray(v) for k, v in model["params"].items()}
        want = dict(dense_shapes(self.param.mlp_bot, self.param.mlp_top),
                    emb=(self.param.num_features, self.param.num_factors))
        held = {k: v.shape for k, v in params.items()}
        check(held == want,
              "snapshot holds %s, this learner trains %s", held, want)
        self._nf = self.param.num_features
        self.params = jax.tree_util.tree_map(
            jnp.asarray, PackedTables.pack(params, self.table_layout()))

    def predict_batch(self, batch) -> np.ndarray:
        """The margin ``s`` of every row of one device batch."""
        p = self.param
        head = _head(self.params, batch, p.dense_features, table_lows(
            p.dense_features, p.field_sizes), p.field_sizes)
        return np.asarray(_model(head, self.fields)(
            self.params.scalars, _by_table(head.rows, self.fields)))

    def scalars(self) -> Dict:
        """Every dense parameter WHOLE under its name (``bot.0.w`` ...
        ``top.2.b``): arrays, where the FM's one entry is a float."""
        return dict(self.params.scalars)

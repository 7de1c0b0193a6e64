"""DLRM over COO device batches: a dense net over the table's rows
(Naumov et al., "Deep Learning Recommendation Model for Personalization
and Recommendation Systems", arXiv:1906.00091; github.com/facebookresearch/dlrm
``dlrm_s_pytorch.py``, interaction ``dot`` without the diagonal, plain
SGD, sparse gradients for the tables), and MLPerf Training's DLRM-DCNv2
beside it (github.com/mlcommons/training ``recommendation_v2/torchrec_dlrm``,
TorchRec's ``DLRM_DCN``: multi-hot bags, a low-rank cross net, Adagrad).

One row: label ``y``, ``x`` the D dense features, a bag ``B_f`` of
``k_f`` ids of each of F tables (``bag_sizes``; one id a table by
default), every table's rows K wide::

    h = x;  h = relu(W_l h + b_l)  over the bottom MLP's layers;  z = h   [K]
    e_f = sum_{i in B_f} E[i]     (sum pooling; an id named twice counts twice)
    dot:  T = [z; e_1; ...; e_F] [F + 1, K];  P = T T^t
          p = the (F + 1) F / 2 entries of P strictly under the diagonal,
          row-major;  r = [z; p]
    dcn:  x0 = [z; e_1; ...; e_F]  [(F + 1) K]
          x_{l+1} = x0 * (U_l (V_l x_l) + c_l) + x_l  over the cross net's
          layers (``dcn_num_layers``, V_l [rank, (F + 1) K]);  r = x_L
    r = relu(V_l r + c_l)  over the top MLP's layers but the last,
    s = V_last r + c_last   [1]
    loss = mean over the batch of -(y log sigmoid(s) + (1 - y) log(1 - sigmoid(s)))
    sgd     : theta <- theta - learning_rate * g  for every dense parameter
              and for the rows of E the batch names (g of a row: the sum
              over its entries)
    adagrad : G <- G + g^2;  theta <- theta - learning_rate * g / (sqrt(G) + eps)
              for every dense parameter (G a tree of the dense shapes);
              for the rows of E the batch names, row-wise (fbgemm's
              EXACT_ROWWISE_ADAGRAD, one accumulator ``m`` a row):
              m_i <- m_i + mean over the K columns of g_i^2;
              E[i] <- E[i] - learning_rate * g_i / (sqrt(m_i) + eps)

It is the first learner here whose step holds matrix products and whose
parameters are not all per-id rows: the state is ONE per-id table ``emb``
(``f32[ids, K]``; on one device a :class:`~dmlc_tpu.models.fm.PackedTables`
of lane rows, 8 ids of 16 columns to 128 lanes, or one id of 128) **and a
tree of dense arrays** (``bot.0.w`` ... ``top.2.b``, ``w`` as ``[out,
in]``; the cross net's ``cross.<l>.v``, ``cross.<l>.u``, ``cross.<l>.c``),
kept where the FM keeps its scalar ``b``: the tree's leaves that are no
per-id table. Under Adagrad the tree holds besides an accumulator of each
dense parameter's shape, ``G.<name>``, and the rows' accumulators ``m``
(``f32[ids]``), which lie apart from ``emb``'s lane rows: one more word an
id would take 129 columns to 640 lanes a lane row of 4 ids (8.2 GB where
``emb`` alone is 6.6 GB in ``mlperf-dlrm-dcnv2``), out of the DMA
writer's reach.

A batch's entries are of two kinds, told apart by the id's range as
models/ffm.py tells fields apart. Ids ``1..D`` (``dense_features``) index
no table: an entry ``d:x`` is the row's dense feature ``d``, a real
number. The ids after them are the tables' rows, ``field_sizes`` as
contiguous ranges in order (table f from ``1 + D + sum(sizes[:f])``); an
entry there with a value other than 0 names an id of that table for the
row's bag, and the value is not read further. Id 0 is the feed's padding.
Rows ``0..D`` of ``emb`` are never read into the model and never change.

The step, by scope; no scope is opened inside another, and an operation
of the trace goes to the first part of its scope path that starts with
``step.`` (benchmarks/metrics/step_update_ms.py ``phases``): ``jax.vjp``'s
backward operations carry the scope their ``back`` is called under
FIRST, before ``transpose(jvp(.))``, so each part's backward pass is
called under that part's own scope. ``step.order``: the batch laid out as
``x [D, rows]`` and ``ids [rows * P]`` (P = the bags' sizes summed; place
``row * P + f`` with one id a table, and with bags member-major, place
``(start of f's bag + member) * rows + row``, so that each member of a bag
is a whole ``[rows, K]`` slab of the rows ``step.gather`` brings; the
place holds the row's id, 0 where it names none: such a place adds a zero
vector), then the FM's sort by id over those ``rows x P`` places ONLY,
taken row by row, with each entry's place as the payload, and the sort
that brings every entry's slot back to its place.
``step.gather``: the one read at the distinct ids (models/fm.py
``_read_distinct``) and each place's K words, in place order.
``step.pool`` (``dcn`` only): the bags' sums, each a sum of its members'
aligned slabs along the major axis, and, backward, each bag's gradient
broadcast over its members' slabs: no pad and no relayout.
``step.cross`` (``dcn`` only): the cross net, forward and backward.
``step.dense``: both MLPs (under ``dot`` the interaction too), the loss,
their backward pass and the dense parameters' rule; activations are
``[features, rows]``, the rows along the chip's lanes, so that the
interaction is a product and a sum over whole vectors and the triangle is
cut along major axes; every matrix product at ``precision=HIGHEST``
(float32 as the configurations state it: six bfloat16 passes on the MXU).
``step.update``: each entry's gradient brought to id order, an id's
entries summed (under SGD on a TPU by a segmented scan along each id's
run while its output fits the kernel's VMEM, else by ``segment_sum``),
and the distinct lane rows written back (models/fm.py ``_put_lane_rows``:
the writer every rule shares, on a TPU one DMA a row at 128 lanes):
``old + sum`` under SGD; under Adagrad the rule itself, the read of ``m``
at the distinct ids and its write lie in ``step.state`` between the sums
and the write (models/fm.py ``_rowwise_adagrad``).

How the batch is laid out. A batch whose every row lists its D dense ids
in order and then the ids of every table's bag in order (what a LIBSVM
file of such rows gives: 39 entries a row in ``criteo-dlrm``, 227 in
``mlperf-dlrm-dcnv2``) IS that layout, and is cut by a reshape. Any other
batch (a row that lacks an entry, a short last batch) is laid out by
scatters over its entries, which on the v5e cost 5.6 ms a batch of 8192
rows of ``criteo-dlrm`` where the reshape costs 0.03 (a step of 9.3 ms
becomes one of 14.9: PERF.md, PR 42); the program holds both and a
``cond`` picks. A bag's members take their places in the order the row
lists them. A row that names more ids of one table than its bag holds
(with bags of one: a second id) keeps the bag's worth (of one: the larger
id) and the step counts the others as left out;
:meth:`DLRMLearner.epoch_closed` refuses a pass that left any out.
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
    _rowwise_adagrad,
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

#: Adagrad's accumulator of the table's rows, one word an id, apart from
#: ``emb``; and the prefix of a dense parameter's accumulator
ROW_STATE = "m"
ACC = "G."

_HIGHEST = lax.Precision.HIGHEST


class DLRMParam(Parameter):
    # the source's --learning-rate, of every parameter's rule
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
    # --arch-mlp-top WITH its input (the source derives it): under dot
    # num_factors + the pairs of the tables and the bottom MLP's output,
    # under dcn num_factors x (tables + 1); 1 last
    mlp_top = field(_field_sizes, ())
    # the ids a row names of each table, in order (MLPerf's
    # --multi_hot_sizes): a bag whose rows are summed; empty, one of each
    bag_sizes = field(_field_sizes, ())
    # how the bottom MLP's output meets the pooled vectors: the dot
    # products of the pairs, or a low-rank cross net over all of them
    # (DCN-v2, --interaction_type dcn) of dcn_num_layers layers of rank
    # dcn_low_rank_dim
    interaction = field(str, "dot", enum={"dot": "dot", "dcn": "dcn"})
    dcn_num_layers = field(int, 3, lower_bound=1)
    dcn_low_rank_dim = field(int, 512, lower_bound=1)
    # the rule of every parameter: plain SGD, or Adagrad (the dense tree
    # elementwise, the table's rows row-wise), eps beside the root
    optimizer = field(str, "sgd", enum={"sgd": "sgd", "adagrad": "adagrad"})
    eps = field(float, 1e-8, lower_bound=0.0)


def table_lows(dense_features: int, field_sizes) -> np.ndarray:
    """The first id of every table: the tables partition the ids after
    ``dense_features`` in order."""
    sizes = np.asarray(field_sizes, dtype=np.int64)
    return 1 + dense_features + np.concatenate([[0], np.cumsum(sizes)[:-1]])


def dense_shapes(mlp_bot, mlp_top, cross=(0, 0)) -> Dict[str, Tuple[int, ...]]:
    """{name: shape} of the dense parameters, in the order of the
    equations: ``bot.<l>.w [out, in]``, ``bot.<l>.b [out]``; ``cross`` =
    (layers, rank) of a cross net over the top MLP's input, width W:
    ``cross.<l>.v [rank, W]``, ``cross.<l>.u [W, rank]``, ``cross.<l>.c
    [W]`` (none at 0 layers); then the top MLP's."""
    shapes = {}
    for net, widths in (("bot", mlp_bot), ("top", mlp_top)):
        if net == "top":
            layers, rank = cross
            for layer in range(layers):
                shapes["cross.%d.v" % layer] = (rank, widths[0])
                shapes["cross.%d.u" % layer] = (widths[0], rank)
                shapes["cross.%d.c" % layer] = (widths[0],)
        for layer, (n, m) in enumerate(zip(widths[:-1], widths[1:])):
            shapes["%s.%d.w" % (net, layer)] = (m, n)
            shapes["%s.%d.b" % (net, layer)] = (m,)
    return shapes


def init_dense(mlp_bot, mlp_top, key, cross=(0, 0)) -> Dict:
    """The dense parameters as the source starts them: ``w [m, n]`` normal
    with deviation ``sqrt(2 / (m + n))``, ``b [m]`` with ``sqrt(1 /
    m)``; the cross net's ``v`` and ``u`` as a ``w`` (TorchRec's
    ``xavier_normal_``), its ``c`` 0."""
    out = {}
    shapes = dense_shapes(mlp_bot, mlp_top, cross)
    for at, (name, shape) in enumerate(shapes.items()):
        m = shape[0]
        if name.startswith("cross.") and len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
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


def adagrad_state(dense: Dict, num_ids: int) -> Dict:
    """Adagrad's accumulators at their start, 0 as the MLPerf rules fix
    it: ``G.<name>`` of each dense parameter's shape and the rows' ``m
    f32[num_ids]``."""
    out = {ACC + k: jnp.zeros(v.shape, jnp.float32) for k, v in dense.items()}
    out[ROW_STATE] = jnp.zeros((num_ids,), jnp.float32)
    return out


def init_dlrm_params(num_features: int, num_factors: int, dense_features: int,
                     field_sizes, mlp_bot, mlp_top, seed=0, cross=(0, 0),
                     optimizer: str = "sgd") -> Dict:
    """The logical tree, one array a leaf: ``emb f32[num_features,
    num_factors]`` (a table's rows uniform in ``+-sqrt(1 / its rows)``,
    the rows before the tables 0), the dense parameters of
    :func:`init_dense` and, under ``optimizer="adagrad"``, their
    accumulators (:func:`adagrad_state`)."""
    key = jax.random.PRNGKey(seed)
    ids = jnp.arange(num_features, dtype=jnp.int32)[:, None]
    emb = _signed_draw(key, (num_features, num_factors)) * _id_scale(
        ids, table_lows(dense_features, field_sizes), field_sizes)
    dense = init_dense(mlp_bot, mlp_top, key, cross)
    if optimizer == "adagrad":
        dense.update(adagrad_state(dense, num_features))
    return dict(dense, emb=emb)


def _bag_starts(bags) -> np.ndarray:
    """The first place of each bag among a row's ``sum(bags)``."""
    return np.concatenate([[0], np.cumsum(bags)[:-1]]).astype(np.int32)


def _lay_out(batch, dense: int, lows, sizes, bags=None):
    """The batch as the model reads it: ``x f32[dense, rows]`` (a row's
    dense features down a column; 0 where the row has no such entry),
    ``ids s32[rows * P]`` (P = the bags' sizes summed) and the count of
    valued table entries that found no place (an id past its bag's size:
    with bags of one, a second id of one table in one row). With ``bags``
    None (one id a table) the places are row-major: place ``row * P + f``
    holds the row's id of table f. With ``bags`` given they are
    member-major: place ``(the start of f's bag + member) * rows + row``
    holds the row's member-th id of table f, so that a member's places
    are one run of ``rows`` (:func:`_pool`). 0 where the row names none.

    Two ways to it, chosen on the device by what the batch holds (the
    module's docstring): a batch whose every row lists ``1..dense`` and
    then each table's bag, in order, is cut by a reshape (member-major:
    the ``[rows, P]`` block of ids transposed); any other by a scatter-add
    of the dense values and a scatter of the ids: with bags of one a
    scatter-max, else each entry's rank among the row's entries of its
    table (a stable sort by row and table), the entries past the bag left
    out."""
    indices, values = batch["indices"], batch["values"]
    rows = batch["label"].shape[0]
    fields = len(sizes)
    member = bags is not None
    bags = (1,) * fields if bags is None else tuple(bags)
    places = sum(bags)
    width = dense + places
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
        if places == fields:
            ids = jnp.zeros((rows * fields + 1,), indices.dtype).at[
                jnp.where(named, table * rows + row_ids if member else
                          row_ids * fields + table, rows * fields)
            ].max(indices)[:-1]
            return x, ids, jnp.sum(named) - jnp.sum(ids != 0)
        # a bag's members in the order the row lists them: sorted by (row,
        # table) and then by their order in the batch, an entry's rank is
        # its distance from the first of its run
        key = jnp.where(named, row_ids * fields + table, rows * fields)
        key, _, held = lax.sort(
            (key, jnp.arange(n, dtype=jnp.int32), indices), num_keys=2)
        at = jnp.arange(n, dtype=jnp.int32)
        first = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
        rank = at - lax.cummax(jnp.where(first, at, 0), axis=0)
        table = key % fields
        kept = (key < rows * fields) & (rank < jnp.asarray(bags)[table])
        place = (jnp.asarray(_bag_starts(bags))[table] + rank) * rows + \
            key // fields
        ids = jnp.zeros((rows * places + 1,), indices.dtype).at[
            jnp.where(kept, place, rows * places)].set(held)[:-1]
        return x, ids, jnp.sum(named) - jnp.sum(kept)

    if rows * width > indices.shape[0]:
        return scattered()
    v = values[:rows * width].reshape(rows, width)
    i = indices[:rows * width].reshape(rows, width)

    def cut():
        x = v[:, :dense].T
        ids = jnp.where(v[:, dense:] != 0, i[:, dense:], 0)
        return (x, (ids.T if member else ids).reshape(-1),
                jnp.zeros((), jnp.int32))

    # every place's first and last id: its table's
    low, high = np.repeat(lows, bags), np.repeat(last, bags)
    in_order = (
        jnp.all(batch["offsets"] == width * jnp.arange(
            rows + 1, dtype=batch["offsets"].dtype))
        & jnp.all(i[:, :dense] == jnp.arange(1, dense + 1, dtype=i.dtype))
        & jnp.all((i[:, dense:] >= jnp.asarray(low, i.dtype))
                  & (i[:, dense:] <= jnp.asarray(high, i.dtype))))
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


def _cross(dense: Dict, x0):
    """The low-rank cross net over ``x0 [W, rows]``: ``x_{l+1} = x0 *
    (u_l (v_l x_l) + c_l) + x_l`` over the layers ``cross.<l>``."""
    layers = sum(name.startswith("cross.") for name in dense) // 3
    x = x0
    for layer in range(layers):
        low = jnp.matmul(dense["cross.%d.v" % layer], x, precision=_HIGHEST)
        x = x0 * (jnp.matmul(dense["cross.%d.u" % layer], low,
                             precision=_HIGHEST)
                  + dense["cross.%d.c" % layer][:, None]) + x
    return x


def dcn_forward(dense: Dict, emb, x):
    """:func:`dlrm_forward` under ``interaction="dcn"``: ``x0 = [z; e_1;
    ...; e_F]`` (``emb [fields, K, rows]`` down the features), the cross
    net (:func:`_cross`), the top MLP."""
    z = _mlp(dense, "bot", x, last_relu=True)  # [K, rows]
    x0 = jnp.concatenate([z, emb.reshape(-1, emb.shape[-1])], axis=0)
    return _mlp(dense, "top", _cross(dense, x0), last_relu=False)[0]


def _pool(ids, bags, rows):
    """``[fields, K, batch rows]``: each bag's sum of its places' rows
    (``rows [places, K]`` member-major, place ``(start of f's bag +
    member) * batch rows + r``: :func:`_lay_out`); a place that holds no
    id adds 0, whatever row 0 holds.

    As ``[P, batch rows, K]`` (a free reshape while the batch's rows are
    a multiple of the chip's 8 sublanes) a member is one whole slab and a
    bag a run of slabs along the major axis: its sum adds aligned tiles,
    and the backward pass jax makes of the split, the sums and the stack
    (a broadcast of each bag's gradient over its slabs, concatenated)
    neither pads nor lays the rows out anew. Each bag masks its own slabs:
    the chip's compiler then makes the masks, the sums and the stack one
    pass over the rows, where one masked copy of them all, read by every
    bag's sum, was laid out anew for the sums."""
    p, k = sum(bags), rows.shape[1]
    cuts = _bag_starts(bags)[1:]
    slabs = jnp.split(rows.reshape(p, -1, k), cuts, axis=0)
    named = jnp.split((ids != 0).reshape(p, -1, 1), cuts, axis=0)
    pooled = jnp.stack(
        [jnp.where(m, s, 0.0).reshape(s.shape[1:]) if n == 1 else
         jnp.sum(jnp.where(m, s, 0.0), axis=0)
         for s, m, n in zip(slabs, named, bags)])  # [fields, rows, K]
    return pooled.transpose(0, 2, 1)


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


def _order_places(ids, num_ids: int, batch_rows: Optional[int] = None):
    """The FM's step head over the table entries alone: ``ids
    s32[places]`` sorted, each entry's place its payload. Returns (the
    :class:`~dmlc_tpu.models.fm._IdOrder`, every entry's place in id
    order, every place's slot): the second sort brings the slots, which
    are by id order, back to place order (a sort of two ``s32`` arrays
    where a scatter by place costs several times as much on the chip).

    ``batch_rows``: the batch's rows where the places are member-major
    (:func:`_lay_out`). The stable sort then takes them row by row, so
    that an id's entries lie, and their sums add, in (row, member) order
    as under row-major places: a member's place would move with the
    ranks of the bag's other members, which a slice of a table that
    holds some of them does not see."""
    if batch_rows is not None:
        ids = ids.reshape(-1, batch_rows).T.reshape(-1)
    places = jnp.arange(ids.shape[0], dtype=jnp.int32)
    sorted_ids, place = lax.sort((ids, places), num_keys=1)
    if batch_rows is not None:
        # the row-major place the sort carried, as the member-major one
        per_row = ids.shape[0] // batch_rows
        place = place % per_row * batch_rows + place // per_row
    order = _slots_in_id_order(sorted_ids, num_ids)
    _, slot = lax.sort((place, order.slot), num_keys=1)
    return order, place, slot


class _Head(NamedTuple):
    """What the step's head hands the model (:func:`_head`)."""

    #: f32[dense features, rows] the rows' dense features
    x: jax.Array
    #: s32[places] every place's id, 0 where it names none; places
    #: row-major, or member-major under bags (:func:`_lay_out`)
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


def _head(params, batch, dense_features: int, lows, sizes,
          bags=None) -> _Head:
    """``step.order`` and ``step.gather``, the head of the step and of
    ``predict_batch``: the batch laid out (:func:`_lay_out`), its table
    entries in id order (:func:`_order_places`), the one read at the
    distinct ids and every place's row from that buffer."""
    tables, _ = _head_tables(params, DLRM_TABLES)
    num_ids = tables.num_ids if isinstance(
        tables, PackedTables) else tables[0].shape[0]
    with jax.named_scope("step.order"):
        x, ids, left_out = _lay_out(batch, dense_features, lows, sizes, bags)
        order, place, slot = _order_places(
            ids, num_ids, None if bags is None else x.shape[1])
    with jax.named_scope("step.gather"):
        read = _read_distinct(tables, order)
        # a slot and a place lie inside their arrays by construction:
        # said, so that no gather carries the select that fills what
        # lies outside (a pass over ``[places, 16]`` pays for 128 lanes)
        rows = read.words.at[slot].get(mode="promise_in_bounds")
    return _Head(x, ids, left_out, order, place, read, rows)


def _model(head: _Head, fields: int):
    """``(dense, vectors [fields, K, rows]) -> margins`` over the head's
    batch, one id a table and ``dot``. A place that holds no id read row
    0: masked here, where the rows lie along the lanes (a pass over
    ``[places, 16]`` pays for 128 lanes a row)."""
    named = _named(head.ids, fields)
    return lambda dense, vectors: dlrm_forward(
        dense, jnp.where(named, vectors, 0.0), head.x)


def _dense_of(params) -> Dict:
    """The dense parameters of a tree: every leaf but the tables and
    Adagrad's accumulators."""
    return {k: params[k] for k in params if k not in DLRM_TABLES
            and k != ROW_STATE and not k.startswith(ACC)}


def _loss(margin, label, weight):
    """(the batch's weighted loss sum, its weight sum, the loss's slope
    of each margin): the mean BCE's cotangent."""
    loss, slope = margin_grad("logistic", margin, label)
    wsum = jnp.sum(weight)
    denom = jnp.maximum(wsum, 1e-12)
    loss_sum = jnp.sum(weight * loss)
    return loss_sum, wsum, weight * slope / denom


def _dcn_grads(dense: Dict, vectors, x, label, weight):
    """:func:`dcn_forward` and its backward pass, each part under its own
    scope, forward and back: the bottom MLP (``step.dense``), the cross
    net over ``x0`` (``step.cross``: ``x0``'s concatenation too), the top
    MLP and the loss (``step.dense``). Returns (loss sum, weight sum, the
    dense parameters' gradients, the vectors' ``[fields, K, rows]``)."""
    def part(net):
        return {k: v for k, v in dense.items() if k.startswith(net + ".")}

    with jax.named_scope("step.dense"):
        z, bot_back = jax.vjp(
            lambda d: _mlp(d, "bot", x, last_relu=True), part("bot"))
    with jax.named_scope("step.cross"):
        x0 = jnp.concatenate(
            [z, vectors.reshape(-1, vectors.shape[-1])], axis=0)
        top_in, cross_back = jax.vjp(_cross, part("cross"), x0)
    with jax.named_scope("step.dense"):
        margin, top_back = jax.vjp(
            lambda d, h: _mlp(d, "top", h, last_relu=False)[0], part("top"),
            top_in)
        loss_sum, wsum, slope = _loss(margin, label, weight)
        grads, d_top_in = top_back(slope)
    with jax.named_scope("step.cross"):
        cross_grads, d_x0 = cross_back(d_top_in)
        k = z.shape[0]
        d_vectors = d_x0[k:].reshape(vectors.shape)
    with jax.named_scope("step.dense"):
        (bot_grads,) = bot_back(d_x0[:k])
    return loss_sum, wsum, dict(grads, **cross_grads, **bot_grads), d_vectors


def _dense_rule(params, dense: Dict, grads: Dict, learning_rate: float,
                eps: Optional[float]) -> Dict:
    """The dense parameters after the step: plain SGD (``eps`` None), or
    Adagrad with the accumulators of ``params`` (``G <- G + g^2; theta <-
    theta - learning_rate * g / (sqrt(G) + eps)``), the new accumulators
    beside them under their names."""
    if eps is None:
        return {k: v - learning_rate * grads[k] for k, v in dense.items()}
    out = {}
    for k, v in dense.items():
        acc = params[ACC + k] + grads[k] * grads[k]
        out[k] = v - learning_rate * grads[k] / (jnp.sqrt(acc) + eps)
        out[ACC + k] = acc
    return out


def make_dlrm_train_step(
    num_features: int,
    dense_features: int,
    field_sizes,
    learning_rate: float = 0.1,
    donate_batch: bool = False,
    platform: Optional[str] = None,
    interpret: bool = False,
    id_sums: Optional[Dict[int, str]] = None,
    bag_sizes=None,
    interaction: str = "dot",
    optimizer: str = "sgd",
    eps: float = 1e-8,
):
    """Jitted DLRM step over COO batches on ONE device, ``(params, batch)
    -> (params, metrics)``. ``params``: the dict of
    :func:`init_dlrm_params` or a :class:`~dmlc_tpu.models.fm.PackedTables`
    whose one table is ``emb`` and whose other leaves are the dense
    parameters (under Adagrad their accumulators and ``m`` too); it comes
    back as it came (the step takes the grouping of its reads and writes
    from the tree it is given, as the FM's does; the cross net's layers
    from the tree's names). Metrics: the FM's ``loss_sum``,
    ``weight_sum``, ``touched_rows``, and ``left_out``, the valued table
    entries that took no part (:func:`_lay_out`); where a bag holds more
    than one id, ``bag_entries``, the ids those bags hold. ``bag_sizes``
    (None: one id a table), ``interaction`` and ``optimizer`` as
    :class:`DLRMParam` has them; ``donate_batch``, ``platform``,
    ``interpret`` and ``id_sums`` as
    :func:`~dmlc_tpu.models.fm.make_fm_train_step` takes them.

    One id a table under ``dot`` and SGD is the step of ``criteo-dlrm``
    as it was before bags, the cross net and Adagrad came: the same
    program (tests/test_fm_step_programs.py holds its digest)."""
    check(num_features > 0, "num_features required")
    sizes = tuple(int(n) for n in field_sizes)
    lows = table_lows(dense_features, sizes)
    check(int(lows[-1]) + sizes[-1] <= num_features,
          "the tables end at id %d, num_features is %d",
          int(lows[-1]) + sizes[-1] - 1, num_features)
    fields = len(sizes)
    bags = tuple(int(n) for n in (
        () if bag_sizes is None else bag_sizes)) or (1,) * fields
    check(len(bags) == fields and min(bags) >= 1,
          "bag_sizes gives a bag of one or more ids to each of the %d "
          "tables, got %r", fields, bag_sizes)
    # the cross net's vectors are the bags' sums, in a scope of their own
    dcn = interaction == "dcn"
    check(dcn or max(bags) == 1,
          "a bag of more than one id is pooled for the cross net only: "
          "under dot every bag holds one id, got %r", bag_sizes)
    multi = np.repeat(np.asarray(bags) > 1, bags)  # a place in a bag of 2+
    adagrad = optimizer == "adagrad"
    # the entries' gradients reach the rule scaled (SGD) or as they are
    scale = 1.0 if adagrad else -learning_rate
    write = partial(_write_rows, platform=platform, interpret=interpret)

    def local(params, batch, _):
        label, weight = batch["label"], batch["weight"]
        head = _head(params, batch, dense_features, lows, sizes,
                     bags if dcn else None)
        dense = _dense_of(params)
        metrics = {}
        if dcn:
            with jax.named_scope("step.pool"):
                vectors, pool_back = jax.vjp(
                    partial(_pool, head.ids, bags), head.rows)
                if multi.any():
                    metrics["bag_entries"] = jnp.sum(
                        (head.ids.reshape(multi.size, -1) != 0)
                        & multi[:, None])
            loss_sum, wsum, grads, by_table = _dcn_grads(
                dense, vectors, head.x, label, weight)
        else:
            with jax.named_scope("step.dense"):
                # the backward pass is made under the scope too: a reader
                # of the trace gives an operation to the first part of its
                # path that starts with ``step.``
                margin, back = jax.vjp(
                    _model(head, fields), dense, _by_table(head.rows, fields))
                loss_sum, wsum, slope = _loss(margin, label, weight)
                grads, by_table = back(slope)
        with jax.named_scope("step.dense"):
            dense = _dense_rule(params, dense, grads, learning_rate,
                                eps if adagrad else None)
            if not dcn:
                upd = _by_place(scale * by_table)
        if dcn:
            with jax.named_scope("step.pool"):
                (upd,) = pool_back(scale * by_table)
        summer = _summer(platform, interpret, id_sums, batch)
        if adagrad:
            # the rule's own scopes: the sums and the row write under
            # ``step.update``, ``m``'s read, the rule and ``m``'s write
            # under ``step.state``
            with jax.named_scope("step.update"):
                upd = upd.at[head.place].get(mode="promise_in_bounds")
            table = params if isinstance(params, PackedTables) else \
                params[DLRM_TABLES[0]]
            rows, dense[ROW_STATE] = _rowwise_adagrad(
                table, params[ROW_STATE], head.order, upd, head.read,
                learning_rate, eps, write, summer)
            params = _regroup(params, DLRM_TABLES, [rows], dense)
        else:
            with jax.named_scope("step.update"):
                # every entry's update in id order (a place that holds no
                # id brings 0 to row 0), then the FM's update of plain SGD
                upd = upd.at[head.place].get(mode="promise_in_bounds")
                if isinstance(params, PackedTables):
                    arrays = [_add_lane_rows(
                        params, head.order, upd, head.read, None, write,
                        summer)]
                else:
                    arrays = _scatter_add_rows(
                        _groups(params, DLRM_TABLES), head.order, upd)
                params = _regroup(params, DLRM_TABLES, arrays, dense)
        return params, dict(
            metrics, loss_sum=loss_sum, weight_sum=wsum,
            touched_rows=head.order.distinct, left_out=head.left_out)

    return _make_sparse_step(local, "dlrm.step", None, "dp", None,
                             donate_batch)


class DLRMLearner(FMLearner):
    """uri → fitted DLRM params over a DeviceFeed (csr layout), through
    the fit loop, the counters, the placement code, the row writer and
    the check's five calls of :class:`~dmlc_tpu.models.fm.FMLearner`
    (the module's docstring has the model, how a batch's entries are told
    apart, and the step). ONE device: a mesh is refused by name.

    ``params`` is a :class:`~dmlc_tpu.models.fm.PackedTables` whose one
    table is ``emb`` (lane rows: 8 ids of 16 columns, one of 128) and
    whose other leaves are the dense parameters (:func:`dense_shapes`)
    and, under Adagrad, their accumulators ``G.<name>`` and the rows'
    ``m`` (:func:`adagrad_state`). :meth:`scalars` hands each dense
    parameter and accumulator out WHOLE under its name, an array and no
    float; :meth:`table_names` gives ``emb`` and ``m``. A snapshot holds
    ``emb`` as the logical ``[ids, K]`` table and every other leaf by
    name, and restores into lane rows."""

    name = "dlrm"
    param_class = DLRMParam

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
        check(not p.bag_sizes or (len(p.bag_sizes) == self.fields
                                  and min(p.bag_sizes) >= 1),
              "bag_sizes gives a bag of one or more ids to each of the %d "
              "tables, got %r", self.fields, p.bag_sizes)
        check(p.interaction == "dcn" or max(self.bags) == 1,
              "a bag of more than one id is pooled for the cross net only: "
              "under dot every bag holds one id, got %r", p.bag_sizes)
        check(p.optimizer == "sgd" or p.eps > 0,
              "Adagrad divides by sqrt(G) + eps: eps must be above 0")
        check(p.mlp_bot[0] == p.dense_features
              and p.mlp_bot[-1] == p.num_factors,
              "mlp_bot runs from dense_features %d to num_factors %d, got %r",
              p.dense_features, p.num_factors, p.mlp_bot)
        if p.interaction == "dot":
            top_in = p.num_factors + self.fields * (self.fields + 1) // 2
            check(len(p.mlp_top) >= 2 and p.mlp_top[0] == top_in
                  and p.mlp_top[-1] == 1,
                  "mlp_top runs from %d (num_factors + the %d pairs) to 1, "
                  "got %r", top_in, top_in - p.num_factors, p.mlp_top)
        else:
            top_in = p.num_factors * (self.fields + 1)
            check(len(p.mlp_top) >= 2 and p.mlp_top[0] == top_in
                  and p.mlp_top[-1] == 1,
                  "mlp_top runs from %d (num_factors x the %d tables and the "
                  "bottom MLP, the cross net's width) to 1, got %r", top_in,
                  self.fields, p.mlp_top)

    def check_mesh(self, mesh: Mesh) -> None:
        check(mesh is None,
              "a DLRM's dense net lives whole on ONE device beside its "
              "table; a mesh has no path for it (no psum of a dense "
              "gradient, no placement of a dense tree): train on one device")

    @property
    def optimizer(self) -> str:
        return self.param.optimizer

    @property
    def state_tables(self):
        """The per-id table the rule keeps beside ``emb``: ``m`` under
        Adagrad."""
        return (ROW_STATE,) if self.param.optimizer == "adagrad" else ()

    @property
    def fields(self) -> int:
        return len(self.param.field_sizes)

    @property
    def bags(self) -> Tuple[int, ...]:
        """The ids a row names of each table (``bag_sizes``; one each)."""
        return tuple(self.param.bag_sizes) or (1,) * self.fields

    @property
    def cross(self) -> Tuple[int, int]:
        """(layers, rank) of the cross net; (0, 0) under ``dot``."""
        p = self.param
        if p.interaction != "dcn":
            return 0, 0
        return p.dcn_num_layers, p.dcn_low_rank_dim

    @property
    def dense_params(self) -> int:
        """The count of the dense net's values."""
        return sum(int(np.prod(shape)) for shape in dense_shapes(
            self.param.mlp_bot, self.param.mlp_top, self.cross).values())

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
            dense = init_dense(p.mlp_bot, p.mlp_top, jax.random.PRNGKey(seed),
                               self.cross)
            if p.optimizer == "adagrad":
                dense.update(adagrad_state(dense, num_features))
            return PackedTables(
                packed.rows, dense, packed.layout, packed.num_ids)

        return init

    def _make_step(self, num_features: int):
        p = self.param
        return make_dlrm_train_step(
            num_features, p.dense_features, p.field_sizes,
            learning_rate=p.learning_rate, donate_batch=True,
            platform=self._step_platform, id_sums=self._id_sums,
            bag_sizes=self.bags, interaction=p.interaction,
            optimizer=p.optimizer, eps=p.eps)

    def state_bytes(self) -> int:
        """Bytes of the rows' optimizer state: ``m``, one word an id."""
        return 4 * self.param.num_features if self.state_tables else 0

    def epoch_span_args(self) -> Dict:
        return dict(super().epoch_span_args(), fields=self.fields,
                    dense_features=self.param.dense_features,
                    dense_params=self.dense_params,
                    interaction=self.param.interaction,
                    bag_ids=sum(self.bags), place_order=(
                        "member" if self.param.interaction == "dcn"
                        else "row"))

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """The FM's counters under ``model="dlrm"``, and
        ``dmlc_fit_dense_net_steps_total``: over ``dmlc_fit_steps_total``
        the share of steps that ran a dense net over the table's rows and
        updated its parameters (every step of this learner);
        ``dmlc_fit_dense_param_bytes``: what that net holds of one chip's
        memory; ``dmlc_fit_dense_state_bytes``: what Adagrad keeps beside
        it (0 under SGD); ``dmlc_fit_cross_net_steps_total``: the steps
        that ran the cross net (every step under ``dcn``);
        ``dmlc_fit_bag_entries_total``: the table entries the steps pooled
        into a bag of more than one id (``bag_entries``, counted on the
        device). A pass whose batches named more ids of a table in a row
        than its bag holds is refused here (the step keeps the bag's
        worth: :func:`_lay_out`)."""
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
        reg.gauge(
            "dmlc_fit_dense_state_bytes",
            "bytes of the dense parameters' optimizer state on one chip",
            model=self.name).set(
                4 * self.dense_params if self.state_tables else 0)
        reg.counter(
            "dmlc_fit_cross_net_steps_total",
            "optimizer steps that ran a low-rank cross net over the "
            "bottom MLP's output and the pooled vectors",
            model=self.name).inc(nstep if self.cross[0] else 0)
        reg.counter(
            "dmlc_fit_bag_entries_total",
            "table entries the steps pooled into a bag of more than one id",
            model=self.name).inc(int(sums.get("bag_entries", 0)))
        check(not sums.get("left_out", 0),
              "%d table entries took no part in the pass: a row named more "
              "than one id of one table (a multi-hot bag) where bag_sizes "
              "gives it one, or more ids than its bag holds",
              int(sums.get("left_out", 0)))

    def restore_snapshot_model(self, model: Dict) -> None:
        """A snapshot's logical ``emb`` and every other leaf into this
        learner's lane rows, from the host arrays."""
        p = self.param
        params = {k: np.asarray(v) for k, v in model["params"].items()}
        want = dense_shapes(p.mlp_bot, p.mlp_top, self.cross)
        if self.state_tables:
            want.update({ACC + k: v for k, v in want.items()},
                        **{ROW_STATE: (p.num_features,)})
        want[DLRM_TABLES[0]] = (p.num_features, p.num_factors)
        held = {k: v.shape for k, v in params.items()}
        check(held == want,
              "snapshot holds %s, this learner trains %s", held, want)
        self._nf = p.num_features
        self.params = jax.tree_util.tree_map(
            jnp.asarray, PackedTables.pack(params, self.table_layout()))

    def predict_batch(self, batch) -> np.ndarray:
        """The margin ``s`` of every row of one device batch."""
        p = self.param
        dcn = p.interaction == "dcn"
        head = _head(self.params, batch, p.dense_features, table_lows(
            p.dense_features, p.field_sizes), p.field_sizes,
            self.bags if dcn else None)
        dense = _dense_of(self.params)
        if not dcn:
            return np.asarray(_model(head, self.fields)(
                dense, _by_table(head.rows, self.fields)))
        return np.asarray(dcn_forward(
            dense, _pool(head.ids, self.bags, head.rows), head.x))

    def scalars(self) -> Dict:
        """Every dense parameter WHOLE under its name (``bot.0.w`` ...
        ``top.2.b``), and under Adagrad every accumulator ``G.<name>``:
        arrays, where the FM's one entry is a float."""
        return {k: v for k, v in self.params.scalars.items()
                if k != ROW_STATE}

    def table_names(self) -> Tuple[str, ...]:
        """``emb`` and, under Adagrad, the rows' accumulators ``m``."""
        return DLRM_TABLES + self.state_tables

    def _held(self, name: str):
        if name == ROW_STATE:
            return self.params[ROW_STATE], None
        return super()._held(name)

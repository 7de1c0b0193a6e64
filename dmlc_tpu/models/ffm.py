"""Field-aware factorization machines over COO device batches: libffm's
model and its AdaGrad (Juan, Zhuang, Chin, Lin, "Field-aware
Factorization Machines for CTR Prediction", RecSys 2016;
github.com/ycjuan/libffm ``ffm.cpp``).

Feature i keeps one k-vector FOR EVERY FIELD: ``v[i, b]`` is what it
shows a partner of field b. For a row with entries e = (id i_e, value
x_e), f(e) the field of entry e::

    r        = 1 / sum_e x_e^2          (libffm's instance-wise normalisation)
    phi      = r * sum_{e < e'} < v[i_e, f(e')], v[i_e', f(e)] > x_e x_e'
    S[a, b]  = sum_{e : f(e) = a} x_e v[i_e, b]
    phi      = r/2 * ( sum_{a,b} < S[a,b], S[b,a] >
                       - sum_e x_e^2 |v[i_e, f(e)]|^2 )
    dphi/dv[i_e, b] = r x_e ( S[b, f(e)]  -  [b = f(e)] x_e v[i_e, f(e)] )
    loss     = log(1 + exp(-y phi)), y = +-1

The second form of ``phi`` is the first rearranged (all ordered pairs,
less the diagonal, halved) and holds for any CSR row: a field twice, a
field absent, real values. There is no linear term and no bias. The FM's
sum-then-square identity (models/fm.py) does not hold here: the step
needs each entry's field, a sum per (row, field), the pair term over a
``[rows, fields, fields, k]`` block and, on the way back, that block
read transposed. All of it sits under ``step.fields``; the head of the
step (``step.order``, ``step.gather``), the chunk loops and the
stateful-update skeleton are models/fm.py's.

The tables are 2-D, ``v``, ``a`` ``f32[F, k * fields]``, the columns
FACTOR-MAJOR: column ``c * fields + b`` holds factor c for partners of
field b, so that a contiguous split of the columns over a mesh
(``table_sharding="factors"``) gives each chip whole factors of every
field. One device keeps them as ONE packed array ``[v | a]`` (models/fm.py
``PackedTables``): an id's factors and their accumulators are one row,
read once and set once a step, since PR 38 as lanes of a row-major
lane row (5 ids of 44 columns to 256 lanes; 7.04 ms a step where the
two tables apart cost 11.69 in the ``kdd12-ffm.libsvm`` cell: PERF.md,
PRs 36 and 38); a mesh keeps the two arrays, each chip its columns of
both. The pair term is a sum over the factor index, so a chip's columns
give its share of ``phi``, one psum of ``f32[rows]`` completes it, and
every update is local (field-major columns would split by partner
field, over which the pair term does not decompose).

An entry's field is the id range its id falls in
(``FFMParam.field_sizes``: contiguous ranges in order, the last ending
at ``num_features``, as the LIBSVM collection's ``kdd2012`` has them).
The feed carries no libfm ``field`` column to the device (device/ has no
such array), so a learner given no ``field_sizes`` refuses.

The update is libffm's AdaGrad per element, for every id the batch
names with a value, the id's mean gradient whole before the rule runs::

    G  = (sum over the batch's entries naming i of kappa_row dphi/dv[i])
         / rows + l2 * v[i]
    a' = a + G^2;   v' = v - learning_rate * G / sqrt(a')

UNITS. libffm updates per instance, with ``a`` starting at 1 and lambda
2e-5. This step's gradients are MEANS over the batch's B rows, and
AdaGrad is covariant in the gradient's scale, so libffm's rule in these
units starts ``a`` at ``1 / B^2`` and takes ``l2 = lambda / B``. That is
not cosmetic: a mean gradient squared is near 4e-11 at B = 8192, and
``1 + 4e-11`` is 1 in float32, so with ``a_init=1`` the accumulator never
moves and the step is plain SGD at ``learning_rate``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.collective.device import psum
from dmlc_tpu.models.fm import (
    FMLearner,
    _check_rule_placement,
    _entries_in_id_order,
    _head_tables,
    _make_sparse_step,
    _regroup,
    init_packed,
    _update_at_distinct,
    _write_rows,
)
from dmlc_tpu.models.linear import margin_grad
from dmlc_tpu.parallel.partition import match_partition_rules
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import check

#: the one rule this model trains by, as its span and counters name it
OPTIMIZER = "adagrad"

#: the per-id tables in the order the step's head reads them (the order of
#: a packed row's columns): the factors, then AdaGrad's accumulator
FFM_TABLES = ("v", "a")


def _field_sizes(value) -> Tuple[int, ...]:
    """``FFMParam.field_sizes`` from a sequence of ints or their text
    (``"3,3,18"``, brackets allowed: what ``str`` of the tuple gives)."""
    if isinstance(value, str):
        value = [t for t in value.strip("()[] ").split(",") if t.strip()]
    return tuple(int(n) for n in value)


class FFMParam(Parameter):
    objective = field(str, "logistic")
    # libffm's eta
    learning_rate = field(float, 0.2, lower_bound=0.0)
    # libffm's lambda, in mean-gradient units (lambda / batch rows),
    # added once to the gradient of every id the batch names
    l2 = field(float, 0.0, lower_bound=0.0)
    # the k factors an id keeps FOR EVERY FIELD (libffm's k, default 4)
    num_factors = field(int, 4, lower_bound=1)
    num_features = field(int, 0)
    # the fields as contiguous id ranges, in order; the last ends at
    # num_features (so the first starts at id 1 where ids are 1-based and
    # num_features = the largest id + 1); ids below the first range are
    # of field 0
    field_sizes = field(_field_sizes, ())
    # v starts uniform in [0, init_scale): libffm's 1 / sqrt(k), 0.5 at
    # its default k = 4
    init_scale = field(float, 0.5, lower_bound=0.0)
    # AdaGrad's accumulator starts here. libffm's 1 is in per-instance
    # units: with mean gradients over B rows that is 1 / B^2 (the
    # module's docstring, UNITS)
    a_init = field(float, 1.0, lower_bound=0.0)
    # how a mesh holds v and a: "factors" gives each chip num_factors /
    # chips factors of every field; a mesh of replicas has no path for a
    # rule that keeps state (models/fm.py _check_rule_placement)
    table_sharding = field(
        str, "replicated",
        enum={"replicated": "replicated", "factors": "factors"})


def _ffm_draw(key, shape, init_scale: float):
    """``v``'s start: uniform draws in [0, ``init_scale``)."""
    return init_scale * jax.random.uniform(key, shape, dtype=jnp.float32)


def init_ffm_params(num_features: int, num_factors: int, fields: int,
                    init_scale: float = 0.5, a_init: float = 1.0,
                    seed: int = 0) -> Dict:
    """``v`` uniform in [0, ``init_scale``) and AdaGrad's accumulator
    ``a`` at ``a_init``, both ``f32[num_features, num_factors *
    fields]``, columns factor-major."""
    shape = (num_features, num_factors * fields)
    return {
        "v": _ffm_draw(jax.random.PRNGKey(seed), shape, init_scale),
        "a": jnp.full(shape, a_init, dtype=jnp.float32),
    }


#: ``table_sharding="factors"``, the one placement a mesh has for this
#: model: chip c of the ``dp`` axis holds columns [c*C/n, (c+1)*C/n) of
#: ``v`` and ``a``, whole factors of every field (the columns are
#: factor-major). Linted by scripts/check_partition_rules.py.
FFM_FACTOR_PARTITION_RULES = ((r"^(v|a)$", P(None, "dp")),)


def field_lows(field_sizes, num_features: int) -> Tuple[int, ...]:
    """The first id of every field but the first, for ranges that end at
    ``num_features``: an id's field is the count of these it reaches."""
    sizes = np.asarray(field_sizes, dtype=np.int64)
    check(len(sizes) > 0 and (sizes > 0).all(),
          "an FFM takes an entry's field from its id's range and the feed "
          "carries no libfm field column to the device: give field_sizes, "
          "the fields' sizes as contiguous id ranges in order, got %r",
          tuple(field_sizes))
    check(int(sizes.sum()) <= num_features,
          "field_sizes cover %d ids, num_features is %d",
          int(sizes.sum()), num_features)
    ends = num_features - np.cumsum(sizes[::-1])  # each field's first id
    return tuple(int(n) for n in ends[-2::-1])


def _field_sums(ve, order, row_ids, values, lows, num_rows: int):
    """What the field-aware forward pass adds to the FM's, under
    ``step.fields``. ``ve`` = ``v[i_e]`` per entry (``[nnz, C]``, C = k *
    fields, factor-major), the entries in id order. Returns (share, norm,
    back, mine, seg): this chip's columns' share of the rows' ``phi``
    before the normalisation (``[B]``), ``r`` per row, the block ``S``
    TRANSPOSED as the backward pass reads it (``[B * fields, C]``: row
    ``r * fields + a`` holds ``S[b, a]`` in column ``c * fields + b``),
    ``x_e v[i_e, f(e)]`` in its own columns and 0 elsewhere (``[nnz,
    C]``), and every entry's ``row * fields + field``.

    The (row, field) sums, the diagonal and ``sum x^2`` share one
    ``segment_sum`` (a pass over the entries costs per index, not per
    column: PERF.md, PR 29)."""
    fields = len(lows) + 1
    c = ve.shape[1]
    k = c // fields
    with jax.named_scope("step.fields"):
        # an id's field: the ranges it has reached (10 compares at 11)
        fld = sum((order.entry_ids >= lo).astype(jnp.int32) for lo in lows)
        seg = row_ids * fields + fld
        xv = values[:, None] * ve
        partner = jnp.arange(c, dtype=jnp.int32) % fields  # a column's b
        mine = jnp.where(partner[None, :] == fld[:, None], xv, 0.0)
        sums = jax.ops.segment_sum(
            jnp.concatenate(
                [xv, jnp.sum(mine * mine, axis=1, keepdims=True),
                 (values * values)[:, None]], axis=1),
            seg, num_segments=num_rows * fields)  # [B * fields, C + 2]
        by_row = sums[:, c:].reshape(num_rows, fields, 2).sum(axis=1)
        x2 = by_row[:, 1]
        norm = jnp.where(x2 > 0, 1.0 / x2, 0.0)  # a row of no entries
        # a row's block S as fields * C values in (a, c, b) order, and the
        # same values with the two field axes exchanged: a fixed
        # permutation of the row (a transpose of the 4-D block takes the
        # chip's compiler 90 s and 15 MB of code, this a second)
        block = sums[:, :c].reshape(num_rows, fields * c)
        swap = np.arange(fields * c).reshape(fields, k, fields).transpose(
            2, 1, 0).reshape(-1)
        back = jnp.take(block, swap, axis=1)
        share = 0.5 * (jnp.sum(block * back, axis=1) - by_row[:, 0])
    return share, norm, back.reshape(num_rows * fields, c), mine, seg


def _ffm_entry_grads(params, batch, lows, objective: str,
                     factor_axis: Optional[str] = None):
    """Loss sums and the per-entry gradient contributions ``dv`` (``[nnz,
    C]``, the entries in feature-id order) of one COO batch, as
    models/fm.py ``_fm_entry_grads`` gives them for the FM. Returns (dv,
    loss_sum, weight_sum, order, seen), ``seen`` = (the distinct ids'
    ``v``, the entries' values).

    ``factor_axis``: ``params["v"]`` holds this chip's factors only; the
    columns' share of ``phi`` is psummed over that axis under
    ``step.exchange``. ``r`` and the loss's slope are per row and the same
    on every chip."""
    label, weight = batch["label"], batch["weight"]
    order, rows, ve, row_ids, values = _entries_in_id_order(
        *_head_tables(params, ("v",)), batch)
    share, norm, back, mine, seg = _field_sums(
        ve, order, row_ids, values, lows, label.shape[0])
    if factor_axis is not None:
        with jax.named_scope("step.exchange"):
            share = psum(share, factor_axis)
    with jax.named_scope("step.forward"):
        loss, gmargin = margin_grad(objective, norm * share, label)
        loss_sum = jnp.sum(weight * loss)
    with jax.named_scope("step.backward"):
        scale = weight * gmargin * norm  # [B]
    with jax.named_scope("step.fields"):
        # entry e of row r and field a reads S[b, a] for every b, and its
        # row's scale, in one gather from a source of a few MB
        fields = len(lows) + 1
        got = jnp.take(
            jnp.concatenate(
                [back, jnp.repeat(scale, fields)[:, None]], axis=1),
            seg, axis=0)
    with jax.named_scope("step.backward"):
        dv = (got[:, -1] * values)[:, None] * (got[:, :-1] - mine)
    return dv, loss_sum, jnp.sum(weight), order, (rows, values)


def _adagrad(old, grad, learning_rate: float, l2: float):
    """libffm's rule alone, elementwise over the distinct ids' buffers."""
    g = grad["v"] + l2 * old["v"]
    a = old["a"] + g * g
    return {"v": old["v"] - learning_rate * g / jnp.sqrt(a), "a": a}


def make_ffm_train_step(
    mesh: Optional[Mesh],
    num_features: int,
    field_sizes,
    objective: str = "logistic",
    learning_rate: float = 0.2,
    l2: float = 0.0,
    axis: str = "dp",
    param_specs=None,
    donate_batch: bool = False,
    table_sharding: str = "replicated",
    platform: Optional[str] = None,
    interpret: bool = False,
):
    """Jitted FFM step over COO batches, ``(params, batch) -> (params,
    metrics)`` with ``params`` = {``v``, ``a``} or the packed ``[v | a]``
    (a :class:`~dmlc_tpu.models.fm.PackedTables`; the step takes the
    grouping from the tree it is given) and the metrics of
    :func:`~dmlc_tpu.models.fm.make_fm_train_step`. One device, or a mesh
    with ``table_sharding="factors"`` (params placed by
    :data:`FFM_FACTOR_PARTITION_RULES`): the two programs of
    ``_make_sparse_step``. The update sets rows from their state
    (``_update_at_distinct``: over tables apart ``a``'s read, the rule and
    ``a``'s write under ``step.state``, ``v``'s write and the id sums
    under ``step.update``; over a packed row the rule alone under
    ``step.state``, the one row write under ``step.update``), so a mesh of
    replicas, whose step applies a dense psummed gradient, is refused.
    ``platform`` and ``interpret`` as ``make_fm_train_step`` takes them:
    a packed tree's lane rows go back through the same ``_write_rows``."""
    check(num_features > 0, "num_features required")
    _check_rule_placement(OPTIMIZER, mesh, table_sharding)
    lows = field_lows(field_sizes, num_features)
    rule = partial(_adagrad, learning_rate=learning_rate, l2=l2)
    write = partial(_write_rows, platform=platform, interpret=interpret)

    def local(params, batch, factor_axis):
        dv, loss_sum, wsum, order, seen = _ffm_entry_grads(
            params, batch, lows, objective, factor_axis)
        arrays, _ = _update_at_distinct(
            params, order, {"v": dv}, seen, wsum, ("a",), rule, write)
        params = _regroup(params, FFM_TABLES, arrays, {})
        return params, {"loss_sum": loss_sum, "weight_sum": wsum,
                        "touched_rows": order.distinct}

    if mesh is not None and param_specs is None:
        param_specs = match_partition_rules(
            FFM_FACTOR_PARTITION_RULES,
            jax.eval_shape(lambda: init_ffm_params(2, 1, len(lows) + 1)))
    return _make_sparse_step(
        local, "ffm.step", mesh, axis, param_specs, donate_batch)


class FFMLearner(FMLearner):
    """uri → fitted FFM params over a DeviceFeed (csr layout), through the
    fit loop, the counters, the snapshots and the placement code of
    :class:`~dmlc_tpu.models.fm.FMLearner`, whose factors this model
    makes field-aware. The logical tables are ``v`` and ``a``, both
    ``f32[F, num_factors * fields]`` (the module's docstring has the
    layout, the equations and the UNITS of ``a_init`` and ``l2``): one
    packed array ``[v | a]`` on one device, two arrays on a mesh, where
    AdaGrad's accumulator is placed as ``v`` is; a snapshot holds the two
    logical tables either way, so a restore under another placement and
    ``reshard`` carry ``a`` with no word of their own.
    One device and a factor-sharded mesh (``num_factors`` divisible by
    its chips) train it; a mesh of replicas is refused, and so is a
    learner given no ``field_sizes``."""

    name = "ffm"
    param_class = FFMParam
    optimizer = OPTIMIZER
    state_tables = ("a",)

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        super().__init__(mesh, **hyper)
        sizes = self.param.field_sizes
        field_lows(sizes, self.param.num_features or sum(sizes))
        check(self.param.a_init > 0,
              "a_init must be positive: the rule divides by sqrt(a)")

    @property
    def fields(self) -> int:
        return len(self.param.field_sizes)

    @property
    def columns(self) -> int:
        return self.param.num_factors * self.fields

    def partition_rules(self):
        return FFM_FACTOR_PARTITION_RULES

    def table_layout(self):
        return tuple((name, self.columns) for name in FFM_TABLES)

    def _initialiser(self, num_features: int):
        if not self.packs:
            return partial(
                init_ffm_params, num_features, self.param.num_factors,
                self.fields, self.param.init_scale, self.param.a_init)
        return partial(
            init_packed, num_features, self.table_layout(),
            partial(_ffm_draw, init_scale=self.param.init_scale),
            {"a": self.param.a_init}, {})

    def _make_step(self, num_features: int):
        return make_ffm_train_step(
            self.mesh, num_features, self.param.field_sizes,
            objective=self.param.objective,
            learning_rate=self.param.learning_rate, l2=self.param.l2,
            axis=self.axis, donate_batch=self.mesh is None,
            table_sharding=self.param.table_sharding,
            platform=self._step_platform)

    def epoch_span_args(self) -> Dict:
        return dict(super().epoch_span_args(), fields=self.fields)

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """The FM's counters under ``model="ffm"``, and
        ``dmlc_fit_field_aware_steps_total``: over
        ``dmlc_fit_steps_total`` the share of steps whose interactions
        went by the entries' fields (every step of this learner)."""
        super().epoch_closed(reg, nstep, sums)
        reg.counter(
            "dmlc_fit_field_aware_steps_total",
            "optimizer steps whose pair term used, for each entry, the "
            "factors kept for its partner's field",
            model=self.name).inc(nstep)

    def predict_batch(self, batch) -> np.ndarray:
        """``phi`` of every row of one device batch (one device)."""
        lows = field_lows(self.param.field_sizes, self._nf)
        order, _, ve, row_ids, values = _entries_in_id_order(
            *_head_tables(self.params, ("v",)), batch)
        share, norm, _, _, _ = _field_sums(
            ve, order, row_ids, values, lows, int(batch["label"].shape[0]))
        return np.asarray(norm * share)

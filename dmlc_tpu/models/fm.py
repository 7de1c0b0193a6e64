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

How the logical per-id tables (``v``, ``w``; a stateful rule's ``a``,
``z``, ``n``) are grouped into physical arrays is the one parameter of
the step's reads and writes (:func:`_groups`). Where one device holds
whole rows of every table they are ONE array, :class:`PackedTables`: an
id's weights and state side by side in one row, ``[v | w]`` or ``[v | w
| a | z | n]``, so the step makes one chunk loop of reads at the batch's
distinct ids and one of row writes, where five tables apart cost a read
and a write each (on the chip an indexed pass over a table costs per
index and hardly per column while a row fits one 128-lane tile: PERF.md,
PRs 29, 32 and 36). That array is laid ROW-MAJOR, several ids to a lane
row (:func:`lane_geometry`): the chip lays a narrow ``f32[F, C]`` out
column-major in tiles of 8 columns x 128 ids, an id's words 512 bytes
apart over C / 8 tiles it shares with 127 other ids, while a row of
``f32[R, 128]`` is 512 contiguous bytes of one tile, so a touched id is
read and written in one piece (PERF.md, PR 38). Tables divided or
replicated over a mesh stay one array a table, each placed by its own
rule. The grouping follows from the tree a step is given and, for a
learner, from its placement (``FMLearner.packs``); no hyper-parameter
names it.

The update rule is ``FMParam.optimizer``'s: ``"sgd"`` adds each id's
scaled gradient into its row; ``"ftrl_adagrad"`` (difacto's: FTRL-proximal
on ``w``, per-element AdaGrad on ``v``) keeps state for every parameter
row, tables ``z``, ``n`` (as ``w``) and ``a`` (as ``v``) beside the
weights in ``params`` (in the same packed row on one device), reads a
touched row's state once and SETS weights and state from the rule
(:func:`_stateful_update`).

A FOURTH storage keeps factors only for the ids that earn them
(:class:`AdaptiveTables`, :class:`AdaptiveFMLearner`: difacto's
memory-adaptive constraints ``V_threshold`` and ``l1_shrk``, one device,
the stateful rule): a base array with five words for every id (``w``,
``z``, ``n``, an exact count, the number of its factor row) and a table of
SLOTS, one ``[v | a]`` row each, far smaller than the id space. Its step
(:func:`_adaptive_step`) reads through that indirection (the distinct
ids' base rows, then the factor rows at the slots they name), leaves the
ids without factors out of the forward pass and the update, counts, and
hands free slots to the ids that cross the threshold, on the device, by a
prefix sum over the sorted distinct ids (``step.activate``). It shares
the sort, the lane-row reads and writes, the row sums, the forward and
backward pass, the id sums and the rule with the dense steps; which step
runs follows, again, from the tree it is given.

score(x) = b + Σ_i w_i x_i + ½ Σ_k [(Σ_i v_ik x_i)² − Σ_i v_ik² x_i²]
"""

from __future__ import annotations

from collections.abc import Mapping
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.extend import random as jex_random
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
from dmlc_tpu.utils.jax_compat import import_pallas
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


def _fm_draw(key, shape, init_scale: float):
    """``v``'s start: normal draws at ``init_scale``."""
    return init_scale * jax.random.normal(key, shape, dtype=jnp.float32)


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
        "v": _fm_draw(key, (num_features, num_factors), init_scale),
    }
    if optimizer != "sgd":
        params.update(
            z=jnp.zeros_like(params["w"]), n=jnp.zeros_like(params["w"]),
            a=jnp.zeros_like(params["v"]))
    return params


#: the leaves of ``params`` a stateful rule adds
STATE_TABLES = ("a", "n", "z")

#: the FM's per-id tables in the order the step's head reads them (the
#: order of a packed row's columns): under plain SGD, and under
#: ``optimizer="ftrl_adagrad"``, whose state follows the weights
SGD_TABLES = ("v", "w")
FTRL_TABLES = ("v", "w", "a", "z", "n")


def _columns(widths) -> int:
    """The columns tables of these ``widths`` take side by side (a 1-D
    table, width 0, takes one)."""
    return sum(max(width, 1) for width in widths)


def _span(buffer, span: Tuple[int, int]):
    """The columns ``span`` = (first, width) of ``buffer [n, C]``; width
    0 names a 1-D table, one column, and comes back 1-D."""
    first, width = span
    return buffer[:, first:first + width] if width else buffer[:, first]


#: lanes of one row of the chip's tiles; a lane row is a multiple of them
_LANES = 128


def lane_geometry(columns: int) -> Tuple[int, int]:
    """(``L``, ``p``): the lanes of one lane row of a packed array whose
    ids keep ``columns`` words each, and the ids that lie side by side in
    it. ``L`` is the smallest multiple of 128 whose lanes no id uses (``L
    - p * columns``) are under a fifth of the row: 17 columns 128 and 7,
    35 columns 128 and 3, 44 columns 256 and 5 (two ids of 44 in 128
    lanes would leave 40 unused, a third more memory)."""
    lanes = _LANES
    while True:
        per_row = lanes // columns
        if 5 * (lanes - per_row * columns) < lanes:
            return lanes, per_row
        lanes += _LANES


def _to_lane_rows(flat):
    """``[F, C]`` (numpy or jax; id i's words in row i) as lane rows
    ``[R, L]``: id i in row ``i // p`` at lanes ``[(i % p) * C, (i % p +
    1) * C)``; the lanes past ``p * C`` and the places past the last id
    hold 0."""
    lib = np if isinstance(flat, np.ndarray) else jnp
    ids, columns = flat.shape
    lanes, per_row = lane_geometry(columns)
    rows = -(-ids // per_row)
    flat = lib.pad(flat, ((0, rows * per_row - ids), (0, 0)))
    return lib.pad(flat.reshape(rows, per_row * columns),
                   ((0, 0), (0, lanes - per_row * columns)))


def _from_lane_rows(rows, columns: int, num_ids: int):
    """:func:`_to_lane_rows` back: ``[num_ids, columns]``."""
    per_row = rows.shape[1] // columns
    return rows[:, :per_row * columns].reshape(-1, columns)[:num_ids]


def _cut_lanes(lanes, place, columns: int):
    """``[n, columns]``: of each lane row of ``lanes [n, L]`` the words
    of the id at ``place [n]`` (a select over the row's static slices)."""
    out = lanes[..., :columns]
    for at in range(1, lanes.shape[-1] // columns):
        out = jnp.where((place == at)[..., None],
                        lanes[..., at * columns:(at + 1) * columns], out)
    return out


@jax.tree_util.register_pytree_node_class
class PackedTables(Mapping):
    """The per-id tables of one learner side by side in ONE array. An id
    keeps ``C`` words, its row of each logical table in the order of
    ``layout`` = ((name, width), ...), width 0 a 1-D table (one word);
    ``rows f32[R, L]`` holds ``p`` ids to a lane row (:func:`lane_geometry`
    of ``C``), id i in row ``i // p`` at lanes ``[(i % p) * C, (i % p + 1)
    * C)``, ``R = ceil(num_ids / p)``::

        lane   0        C        2C            p*C      L
        row r  | id r*p | id r*p+1 | ... | id r*p+p-1 | 0 |

    ``scalars`` = {name: array}: every leaf that is no per-id table (the
    FM's ``b``, ``f32[]``; a DLRM's dense net, a matrix or a vector a
    name: models/dlrm.py). What a device that
    holds whole rows of every table keeps, so that a step reads each
    touched id's words once and writes them once, in one piece of one
    tile. A pytree: ``rows`` and the scalars are its leaves, the layout
    and ``num_ids`` its static part, so a step jitted over either tree
    takes the grouping from the tree it is given.

    As a mapping it reads like the tree of tables it stands for:
    ``params["v"]`` is the logical table, a COPY of its columns (for a
    look at a fitted model or a test; the step, the check's five calls
    and the snapshot never make one), and ``dict(params)`` is the tree
    with one array a table."""

    def __init__(self, rows, scalars: Dict, layout, num_ids: int):
        self.rows = rows
        self.scalars = scalars
        self.layout = tuple(layout)
        self.num_ids = int(num_ids)

    def tree_flatten(self):
        return (self.rows, self.scalars), (self.layout, self.num_ids)

    @classmethod
    def tree_unflatten(cls, static, children):
        return cls(*children, *static)

    @classmethod
    def pack(cls, parts, layout):
        """The logical tree ``parts`` (one array a table, numpy or jax)
        as one packed tree; every leaf ``layout`` does not name is a
        scalar."""
        lib = np if isinstance(parts[layout[0][0]], np.ndarray) else jnp
        names = [name for name, _ in layout]
        flat = lib.concatenate(
            [parts[name] if width else parts[name][:, None]
             for name, width in layout], axis=1)
        return cls(_to_lane_rows(flat),
                   {k: v for k, v in parts.items() if k not in names},
                   layout, flat.shape[0])

    @property
    def columns(self) -> int:
        """``C``: the words an id keeps."""
        return _columns(width for _, width in self.layout)

    @property
    def per_row(self) -> int:
        """``p``: the ids of one lane row."""
        return self.rows.shape[1] // self.columns

    def span(self, name: str) -> Tuple[int, int]:
        """(first column, width) of logical table ``name`` among an id's
        words."""
        first = 0
        for table, width in self.layout:
            if table == name:
                return first, width
            first += max(width, 1)
        raise KeyError(name)

    def lane_rows_of(self, ids):
        """(lane row, place in it) of every id of ``ids``; an id past the
        table names a lane row past the array (a gather fills it, a
        scatter drops it), ascending as the id does."""
        past = ids >= self.num_ids
        return (jnp.where(past, self.rows.shape[0] + (ids - self.num_ids),
                          ids // self.per_row),
                ids % self.per_row)

    def __getitem__(self, name):
        if name in self.scalars:
            return self.scalars[name]
        return _span(_from_lane_rows(self.rows, self.columns, self.num_ids),
                     self.span(name))

    def __iter__(self):
        yield from (name for name, _ in self.layout)
        yield from self.scalars

    def __len__(self):
        return len(self.layout) + len(self.scalars)


def _no_such_key_op(*_):
    raise NotImplementedError(
        "a key of draws at an offset only draws (init_packed)")


def _bits_at_offset(key, bit_width: int, shape):
    """``jax.random``'s threefry bits (``jax_threefry_partitionable``, the
    default: element i of a shape is drawn from the counter i) for the
    counters ``offset + i``: ``key`` = uint32[3] (k1, k2, offset)."""
    check(bit_width == 32, "draws at an offset are 32 bits wide")
    counts = key[2] + lax.iota(jnp.uint32, int(np.prod(shape))).reshape(shape)
    bits1, bits2 = jex_random.threefry2x32_p.bind(
        key[0], key[1], jnp.zeros_like(counts), counts)
    return bits1 ^ bits2


#: threefry keys that draw a BLOCK of a larger array's draws: what
#: ``jax.random.normal(key, (F, K))[at:at + R]`` holds, from a key that
#: carries the offset ``at * K``, without the larger array
_OFFSET_DRAWS = jex_random.define_prng_impl(
    key_shape=(3,), seed=_no_such_key_op, split=_no_such_key_op,
    random_bits=_bits_at_offset, fold_in=_no_such_key_op,
    name="threefry2x32_at_offset", tag="fryo")

#: ids one pass of a packed row's initialiser draws (16 MB of 16 columns:
#: what a pass holds beside the array is a few times that)
_INIT_BLOCK = 1 << 18


def init_packed(num_features: int, layout, draw, fill: Dict, scalars: Dict,
                seed, scale=None) -> PackedTables:
    """A learner's tables at their start as ONE packed array of lane rows
    (:class:`PackedTables`), written in place a block of whole lane rows
    a pass: every id's words at their tables' ``fill`` values (0 where a
    table has none) but the leading table's (``v``: the one table that
    starts random), which are drawn about ``_INIT_BLOCK`` ids a pass, so
    that no table-sized array exists beside the one being written (the
    compiler does not fuse a table of draws into the array that pads it:
    drawn whole it lies beside the packed array, 3.9 GB of 16 columns).

    ``draw(key, shape)``: the draws of the logical initialiser
    (``init_fm_params``: ``init_scale * normal``). A block's key carries
    its offset (:data:`_OFFSET_DRAWS`), so id i holds row i of
    ``draw(PRNGKey(seed), (num_features, width))`` to the bit; the last
    lane row's places past the last id hold 0, as the unused lanes do.
    ``scale(ids) -> f32`` of ``ids``' shape: what an id's draws are
    multiplied by, for a start whose spread differs from id to id
    (models/dlrm.py: by the table an id belongs to)."""
    name, width = layout[0]
    columns = _columns(w for _, w in layout)
    lanes, per_row = lane_geometry(columns)
    rows = -(-num_features // per_row)
    check(rows * per_row * width < 1 << 32,
          "a packed row's initialiser counts draws in 32 bits: %d x %d",
          num_features, width)
    start = np.concatenate(
        [np.full(max(w, 1), fill.get(table, 0.0), np.float32)
         for table, w in layout])  # an id's words, but for the draws
    # lane by lane: the place it belongs to and its column there, whether
    # it holds a draw (then which of a lane row's draws), else its value
    place, column = np.divmod(np.arange(lanes), columns)
    drawn = (place < per_row) & (column < width)
    source = np.where(drawn, place * width + column, 0)
    filled = np.where(place < per_row, start[column], np.float32(0))
    block = min(rows, max(_INIT_BLOCK // per_row, 1))  # lane rows a pass
    k1, k2 = jax.random.key_data(jax.random.PRNGKey(seed))

    def draw_block(i, array):
        # the last block starts early and draws some rows again
        at = jnp.minimum(i * block, rows - block)
        key = jax.random.wrap_key_data(
            jnp.stack([k1, k2, (at * (per_row * width)).astype(jnp.uint32)]),
            impl=_OFFSET_DRAWS)
        # a lane row's draws side by side. The chip keeps such a narrow
        # array with its LONG side along the lanes, so the lanes of the
        # lane rows are put together as ROWS of that array (each a row of
        # draws or a constant) and turned once, whole tiles at a time
        # (cut into an id's 16 columns first, each piece is padded to 128
        # lanes and turned on its own: 140 ms of 200 at 35 columns)
        draws = draw(key, (block, per_row * width)).T

        def ids_of():
            return (at + jnp.arange(block)) * per_row + place[:, None]

        picked = jnp.take(draws, source, axis=0)
        if scale is not None:
            by = scale(ids_of())
            picked = jnp.where(by != 0, picked * by, 0.0)  # no -0.0
        lanes_first = jnp.where(drawn[:, None], picked, filled[:, None])
        ids = ids_of()
        return lax.dynamic_update_slice(
            array, jnp.where(ids < num_features, lanes_first, 0.0).T,
            (at, 0))

    array = lax.fori_loop(
        0, -(-rows // block), draw_block,
        jnp.zeros((rows, lanes), jnp.float32))
    return PackedTables(array, scalars, layout, num_features)


class _Group(NamedTuple):
    """A physical array of the parameters and the logical tables whose
    columns it holds: how the step's reads and writes are divided."""

    array: jax.Array
    #: {logical table: its columns, 0 for a 1-D table}, in column order
    widths: Dict[str, int]


def _groups(params, names) -> List[_Group]:
    """The physical arrays that hold the logical tables ``names``: one
    group a table for a tree with one array a table, ONE group for a
    :class:`PackedTables` (whose layout must be ``names`` in that order:
    the order the step's head reads them in)."""
    if isinstance(params, PackedTables):
        held = tuple(name for name, _ in params.layout)
        check(held == tuple(names),
              "the packed row holds %s, the step reads %s", held, names)
        return [_Group(params.rows, dict(params.layout))]
    return [_Group(params[name], {
        name: params[name].shape[1] if params[name].ndim == 2 else 0})
        for name in names]


def _regroup(params, names, arrays, scalars: Dict):
    """The tree ``params`` came as, over the groups' new ``arrays`` (as
    :func:`_groups` listed them) and the new ``scalars``."""
    if isinstance(params, PackedTables):
        (rows,) = arrays
        return PackedTables(rows, scalars, params.layout, params.num_ids)
    return dict(zip(names, arrays), **scalars)


def _head_tables(params, names):
    """What the step's head reads for the logical tables ``names``: (the
    physical arrays, or the packed tree itself; how many leading words of
    their rows side by side are ``names``' own, None when all are)."""
    if isinstance(params, PackedTables):
        head = params.layout[:len(names)]
        check(tuple(name for name, _ in head) == tuple(names),
              "the packed row starts with %s, the step's head reads %s",
              [name for name, _ in head], names)
        return params, _columns(width for _, width in head)
    return tuple(params[name] for name in names), None


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
    indices, row_ids, values = lax.sort(
        (indices, row_ids, values), num_keys=1)
    return _slots_in_id_order(indices, num_features), row_ids, values


def _slots_in_id_order(indices, num_features: int) -> _IdOrder:
    """The :class:`_IdOrder` of entries whose feature ids ``indices``
    ARE ascending: what :func:`_in_id_order` does after its sort (a step
    whose entries carry other payloads sorts them itself:
    models/dlrm.py)."""
    n = indices.shape[0]
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
    return _IdOrder(indices, slot, ids, slot[-1] + 1)


def _take_distinct(tables, order: _IdOrder, sorted_ids: bool = True):
    """``[t[ids] | ...]`` for the 1-D and 2-D ``tables`` of one height,
    side by side (``[n + pad, columns]``), at the batch's distinct ids,
    ``_UPDATE_CHUNK`` slots a pass until the last slot that holds one:
    the loop adapts to what the batch holds. A slot past the distinct ids
    reads 0. ``sorted_ids=False``: ``order.ids`` are distinct rows in no
    order (the factor rows a slot map names: :class:`AdaptiveTables`)."""
    flags = dict(indices_are_sorted=sorted_ids, unique_indices=True)

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


class _Read(NamedTuple):
    """What the step's head read at the batch's distinct ids."""

    #: [n + pad, C] slot j holds the j-th distinct id's row of every table
    #: the head read, side by side
    words: jax.Array
    #: [n + pad, L] the lane rows those words were cut from, as read (a
    #: packed tree; None when the tables lie apart): what the write puts
    #: back around them
    lanes: Optional[jax.Array] = None


def _take_lane_rows(packed: PackedTables, order: _IdOrder) -> _Read:
    """:func:`_take_distinct` over a packed tree: each distinct id's lane
    row (one indexed read of 512 or 1024 contiguous bytes; a lane row
    that holds several of the batch's ids is read once for each) and the
    id's own words cut out of it, ``_UPDATE_CHUNK`` slots a pass until
    the last slot that holds a distinct id."""
    lane_rows, places = packed.lane_rows_of(order.ids)
    columns = packed.columns

    def take_chunk(i, read):
        at = i * _UPDATE_CHUNK
        got = jnp.take(
            packed.rows, lax.dynamic_slice_in_dim(lane_rows, at, _UPDATE_CHUNK),
            axis=0, indices_are_sorted=True)
        words = _cut_lanes(
            got, lax.dynamic_slice_in_dim(places, at, _UPDATE_CHUNK), columns)
        return _Read(
            lax.dynamic_update_slice_in_dim(read.words, words, at, axis=0),
            lax.dynamic_update_slice_in_dim(read.lanes, got, at, axis=0))

    slots = order.ids.shape[0]
    dtype = packed.rows.dtype
    return lax.fori_loop(0, order.chunks, take_chunk, _Read(
        jnp.zeros((slots, columns), dtype),
        jnp.zeros((slots, packed.rows.shape[1]), dtype)))


def row_writer(platform: Optional[str], lanes: int) -> str:
    """How :func:`_write_rows` writes whole rows of ``lanes`` lanes into an
    array on the devices of ``platform``: ``"dma"`` (one async copy a row,
    a Pallas TPU kernel) on a TPU at 128 lanes, ``"scatter"`` (XLA's)
    everywhere else: the CPU, and 256 lanes, where Mosaic refuses a slice
    of one row. From those two facts alone: no option chooses."""
    return "dma" if platform == "tpu" and lanes == _LANES else "scatter"


def _write_rows(array, target, new, platform: Optional[str] = None,
                interpret: bool = False):
    """``array[target[j]] = new[j]``, whole lane rows, the targets
    distinct; a target past the array writes nothing. ``platform``: that
    of the devices ``array`` lies on, as whoever built the step saw it
    (None: not said, and so the scatter); :func:`row_writer` chooses by
    it and the row's lanes.

    The portable form, and what the kernel's tests compare with, is XLA's
    scatter, its targets NOT flagged sorted: on the chip a row-major
    array's scatter flagged sorted passes over the whole array (12 ms a
    chunk over 4 GB), unflagged it writes in place at 75 ns a slot
    whether the slot writes or not (PERF.md, PR 38). The DMA writer pays
    for the rows it writes, 29-31 ns each (PERF.md, PR 41).

    ``interpret``: the kernel, where it is chosen, runs in Pallas'
    interpreter (the CPU tests pass it; nothing infers it)."""
    if row_writer(platform, array.shape[1]) == "dma":
        return _dma_write_rows(array, target, new, interpret)
    return array.at[target].set(new, unique_indices=True, mode="drop")


#: row copies the DMA writer keeps in flight, a semaphore each (on the
#: v5e 8 write a step's 24,500 rows in 0.81 ms, 16 and 32 in 0.755, 64 in
#: 0.764; 16 with the drain a loop, as it is now, 0.775: PERF.md, PR 41)
_DMA_IN_FLIGHT = 16


def _dma_write_rows(array, target, new, interpret: bool = False):
    """:func:`_write_rows` as pure writes: ``new [slots, L]`` in VMEM,
    ``target s32[slots]`` scalar-prefetched, ``array`` left where it lies
    (aliased to the output, so a donated array is written in place and
    nothing passes over it) and, for each slot whose target lies inside
    it, ONE async copy of the slot's row to its target row,
    :data:`_DMA_IN_FLIGHT` at a time: a copy waits for the copy that
    last used its semaphore, and all are waited for before the kernel
    ends. The targets are distinct, so copies in flight never meet."""
    pl, pltpu = import_pallas()
    height = array.shape[0]

    def kernel(target_ref, new_ref, _, out_ref, sems):
        def copy(slot, row, number):
            return pltpu.make_async_copy(
                new_ref.at[pl.ds(slot, 1)], out_ref.at[pl.ds(row, 1)],
                sems.at[number % _DMA_IN_FLIGHT])

        def wait_for(number, if_):
            # every copy moves one row: any of them stands for the one
            # whose semaphore this is
            @pl.when(if_)
            def _():
                copy(0, 0, number).wait()

        def put(slot, started):
            row = target_ref[slot]
            live = row < height

            @pl.when(live)
            def _():
                wait_for(started, started >= _DMA_IN_FLIGHT)
                copy(slot, row, started).start()

            return started + live.astype(jnp.int32)

        def drain(number, started):
            wait_for(number, number < started)
            return started

        # the drain is a loop too: every ``when`` and every copy the
        # kernel's text holds is traced and lowered in each process (8 ms
        # apiece on the chip's host: PERF.md, PR 41)
        started = lax.fori_loop(0, target_ref.shape[0], put, jnp.int32(0))
        lax.fori_loop(0, _DMA_IN_FLIGHT, drain, started)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(new.shape, lambda i, target: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((_DMA_IN_FLIGHT,))]),
        out_shape=jax.ShapeDtypeStruct(array.shape, array.dtype),
        input_output_aliases={2: 0},  # counted with the prefetched targets
        interpret=interpret, name="write_rows",
    )(target, new, array)


def _put_lane_rows(packed: PackedTables, order: _IdOrder, lanes, new,
                   write=_write_rows):
    """``new [n + pad, C]``, the distinct ids' new words by slot, put
    back into ``packed.rows`` (in place when the caller donated it): each
    distinct LANE row written once, whole, with no read of anybody else's
    data. ``lanes``: the lane rows as :func:`_take_lane_rows` read them.

    Ids that share a lane row are neighbours in id order, at most ``p``
    slots in a run. The run's first slot takes the lane row as read and
    lays over it the new words of every slot of the run, each at its
    place, so that the lanes of ids the batch does not name go back as
    they were read; the run's other slots write nothing (they name a row
    past the array, as do the slots past the distinct ids and the slots
    of ids past the table). ``write``: :func:`_write_rows` as the step's
    builder bound it."""
    lane_rows, places = packed.lane_rows_of(order.ids)
    columns, per_row = packed.columns, packed.per_row
    height, width = packed.rows.shape
    slots = order.ids.shape[0]
    # the place each lane belongs to (the unused lanes to none)
    place_of = np.minimum(np.arange(width) // columns, per_row)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), lane_rows[1:] != lane_rows[:-1]])
    # a slot that writes nothing names the row ``height`` + the slot's
    # own number, whatever row past the array its id named: the targets
    # stay distinct, as the scatter is promised
    target = jnp.where(
        first & (lane_rows < height), lane_rows,
        height + jnp.arange(slots, dtype=lane_rows.dtype))
    # a chunk's last slots look at the slots after it
    reach = per_row - 1
    lane_rows = jnp.pad(lane_rows, (0, reach), constant_values=-1)
    places = jnp.pad(places, (0, reach))
    new = jnp.pad(new, ((0, reach), (0, 0)))

    def put_chunk(i, array):
        at = i * _UPDATE_CHUNK

        def chunk_of(x, ahead=0):
            return lax.dynamic_slice_in_dim(x, at + ahead, _UPDATE_CHUNK)

        merged = chunk_of(lanes)
        here = chunk_of(lane_rows)
        for ahead in range(per_row):
            words = jnp.pad(
                jnp.tile(chunk_of(new, ahead), (1, per_row)),
                ((0, 0), (0, width - per_row * columns)))
            mine = (chunk_of(lane_rows, ahead) == here)[:, None] & (
                chunk_of(places, ahead)[:, None] == place_of[None, :])
            merged = jnp.where(mine, words, merged)
        return write(array, chunk_of(target), merged)

    return lax.fori_loop(0, order.chunks, put_chunk, packed.rows)


def _read_distinct(tables, order: _IdOrder) -> _Read:
    """The distinct ids' rows of ``tables`` (the arrays side by side, or
    the one packed tree: :func:`_head_tables` gives either), each touched
    row of the parameters read ONCE."""
    if isinstance(tables, PackedTables):
        return _take_lane_rows(tables, order)
    return _Read(_take_distinct(tables, order))


def _gather_rows(tables, order: _IdOrder, head: Optional[int] = None):
    """The entries' rows of ``tables`` side by side (the FM's ``[v_e |
    w_e]``, ``[nnz, K + 1]``), in id order, with each touched row of the
    parameters read ONCE: ``rows = [v[ids] | w[ids]]`` at the distinct
    ids (:func:`_take_distinct`; :func:`_take_lane_rows` over a packed
    tree), then one batch-sized gather ``rows[slot]`` whose source is a
    few MB (a tenth of a gather from the table's cost on the chip).
    Returns (the :class:`_Read`, the entries' rows).

    ``head``: the entries take the first ``head`` columns only (a packed
    row holds the optimizer's state after the weights: the distinct ids'
    buffer has it, the per-entry buffer does not grow by it).

    A gather from the table costs per index (22 ns a row of 16 columns,
    37 ns of 32, 16 ns an element of ``w``: PERF.md, PR 31) and nothing
    for being repeated, so the popular ids of a power law are most of a
    per-entry gather's cost; a batch with no repeated id gathers what a
    per-entry gather would."""
    read = _read_distinct(tables, order)
    rows = read.words
    weights = rows if head in (None, rows.shape[1]) else rows[:, :head]
    return read, jnp.take(weights, order.slot, axis=0)


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


def _entries_in_id_order(tables, head: Optional[int], batch):
    """``step.order`` and ``step.gather``, the head of every FM and FFM
    program: the batch's entries sorted by feature id
    (:func:`_in_id_order`) and the rows of ``tables`` (the FM's ``(v,
    w)``, or the one packed array; :func:`_head_tables` gives both
    arguments) side by side for each (:func:`_gather_rows`). Returns
    (order, rows, vw, row_ids, values): ``rows`` the :class:`_Read` of
    the distinct ids' rows of ``tables``, every column; the last three
    per entry in id order, ``vw`` the first ``head`` columns."""
    num_ids = tables.num_ids if isinstance(
        tables, PackedTables) else tables[0].shape[0]
    order, row_ids, values = _batch_in_id_order(batch, num_ids)
    with jax.named_scope("step.gather"):
        rows, vw = _gather_rows(tables, order, head)
    return order, rows, vw, row_ids, values


def _batch_in_id_order(batch, num_ids: int):
    """The batch's entries sorted by feature id: every entry's row
    (``step.gather``: the offsets' expansion) and the step's one sort
    (``step.order``, :func:`_in_id_order`). Returns (order, row_ids,
    values), the last two per entry in id order."""
    values = batch["values"]
    with jax.named_scope("step.gather"):
        # offsets → row ids on device (local per shard under shard_map)
        row_ids = batch["row_ids"] if "row_ids" in batch else \
            expand_row_ids(batch["offsets"], values.shape[0])
    with jax.named_scope("step.order"):
        return _in_id_order(batch["indices"], row_ids, values, num_ids)


def _fm_entry_grads(params, batch, objective: str,
                    factor_axis: Optional[str] = None):
    """Loss sums and the per-entry gradient contributions of one COO
    batch shard, the entries in feature-id order (``order``, an
    :class:`_IdOrder`): entry e of row r at feature i adds ``dw[e]`` to
    w_i's gradient and ``dv[e]`` to v_i's. How they reach the parameters
    is the caller's: scatter-added into the table (single device,
    factor-sharded mesh) or reduced to dense grads for the psum
    (replicated mesh). Returns (dw, gb, dv, loss_sum, weight_sum, order,
    seen); ``seen`` = (the distinct ids' rows as the head read them:
    ``[v | w]``, or the whole packed row, state and all; the entries'
    values) is what a stateful rule reads besides (:func:`_stateful_update`).
    The head is :func:`_entries_in_id_order`, the rest
    :func:`_fm_row_grads`."""
    order, rows, vw, row_ids, values = _entries_in_id_order(
        *_head_tables(params, ("v", "w")), batch)
    grads = _fm_row_grads(
        params["b"], vw, row_ids, values, batch, objective, factor_axis)
    return (*grads, order, (rows, values))


def _fm_row_grads(bias, vw, row_ids, values, batch, objective: str,
                  factor_axis: Optional[str] = None):
    """Forward and backward of the FM over entries whose rows ``vw`` =
    ``[v_e | w_e]`` the step's head has gathered (in any order; the
    heads hand them over in id order): (dw, gb, dv, loss_sum,
    weight_sum), the per-entry contributions of :func:`_fm_entry_grads`.
    An entry whose ``v_e`` is 0 adds nothing to its row's interaction
    term (how :class:`AdaptiveTables`' head leaves out the ids that hold
    no factors).

    Passes that share an index vector are one pass over concatenated
    columns: the three row sums of the forward pass (:func:`_row_sums`),
    and a row's ``s`` and ``wg`` on their way back to its entries. The
    row sums add a row's entries in id order, not the feed's: the same
    float32 terms in another order.

    ``factor_axis``: ``vw`` holds this chip's columns of ``v`` only and
    the batch is the whole step's (``row_ids`` global); the columns'
    share of the interaction term is psummed over that axis between
    forward and backward, under ``step.exchange``.

    The ``step.*`` scopes name the step's phases in the compiled
    program's metadata (shared with models/linear.py), so a device
    profile can be read by phase; they change no operation."""
    label = batch["label"]
    weight = batch["weight"]
    xv, s, q, linear = _row_sums(vw, row_ids, values, label.shape[0])
    with jax.named_scope("step.forward"):
        interaction = 0.5 * jnp.sum(s * s - q, axis=-1)
    if factor_axis is not None:
        with jax.named_scope("step.exchange"):
            interaction = psum(interaction, factor_axis)
    with jax.named_scope("step.forward"):
        margin = bias + linear + interaction
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


def _scatter_add_rows(groups, order: _IdOrder, upd):
    """``t[i] += Σ upd[e, t's columns]`` over the entries e that name
    feature i, for every logical table t of ``groups``
    (:func:`_groups`; the FM's ``v`` and ``w``, or the one packed ``[v |
    w]``), into the groups' arrays themselves (in place when the caller
    donated them). ``upd`` ``[n, columns]`` is in ``order``'s order, its
    columns in the groups'. Returns the new arrays, one a group. A row no
    entry names is not written; a padded entry adds its 0 to feature 0.

    The entries of one id are summed first and reach its row in one
    add. Ids repeat within a batch (thousands of times for the popular
    ones under a power law), and entry-by-entry adds into a parameter
    much larger than the update round at the parameter's magnitude each
    time: against a float64 step that read 20 times the error of a dense
    gradient's one subtraction. Summing first keeps that one rounding.
    Every table's updates are summed by slot in ONE pass (as
    :func:`_row_sums` sums by row, and for its reason).

    On the chip a row scatter-add is serial, ~0.1 µs a slot whether the
    slot's id is in range or dropped, so a 2-D array takes the distinct
    ids ``_UPDATE_CHUNK`` slots at a time until the last slot that holds
    one (the loop :func:`_gather_rows` reads them by), whatever the
    number of tables in its row. A 1-D array's scatter costs a pass over
    it whatever the number of slots, so it is made once (1.39 ms for
    ``w`` beside a ``v`` of 16 columns; as the 17th column of a packed
    row it is nearly free: PERF.md, PR 36)."""
    n = order.slot.shape[0]
    sums = jax.ops.segment_sum(
        upd, order.slot, num_segments=n, indices_are_sorted=True)  # [n, K + 1]
    ids = order.ids
    flags = dict(indices_are_sorted=True, unique_indices=True, mode="drop")

    def add_chunks(array, cols):
        cols = jnp.pad(cols, ((0, ids.shape[0] - n), (0, 0)))

        def add_chunk(i, array):
            at = i * _UPDATE_CHUNK
            return array.at[
                lax.dynamic_slice_in_dim(ids, at, _UPDATE_CHUNK)].add(
                    lax.dynamic_slice_in_dim(cols, at, _UPDATE_CHUNK),
                    **flags)

        return lax.fori_loop(0, order.chunks, add_chunk, array)

    out, first = [], 0
    for array, widths in groups:
        columns = _columns(widths.values())
        cols = sums[:, first:first + columns]
        out.append(array.at[ids[:n]].add(cols[:, 0], **flags)
                   if array.ndim == 1 else add_chunks(array, cols))
        first += columns
    return out


def _check_rule_placement(optimizer: str, mesh: Optional[Mesh],
                          table_sharding: str) -> None:
    """Every rule but ``"sgd"`` keeps state for every parameter row."""
    check(optimizer == "sgd" or mesh is None or table_sharding == "factors",
          "optimizer=%r keeps state for every parameter row "
          "and updates the rows a batch names; the replicated mesh step "
          "applies a dense psummed gradient and has no such path: train "
          "on one device or with table_sharding='factors'", optimizer)


def _check_adaptive_placement(optimizer: str, mesh: Optional[Mesh]) -> None:
    """A memory-adaptive FM (:class:`AdaptiveTables`) lives on one device
    and trains by difacto's rule."""
    check(mesh is None,
          "a memory-adaptive FM (factor_capacity, v_threshold, l1_shrk) "
          "keeps a slot map and hands out factor rows inside the step of "
          "ONE device; a mesh has no such path: train on one device")
    check(optimizer == "ftrl_adagrad",
          "a memory-adaptive FM trains by optimizer='ftrl_adagrad' "
          "(l1_shrk reads FTRL's w), got optimizer=%r", optimizer)


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
                   l2: float, seen=None, rule: Optional[FtrlAdagrad] = None,
                   write=_write_rows):
    """The step's update from per-entry contributions ``grads`` =
    (dw, gb, dv, weight_sum), the entries in ``order``'s order: under
    ``step.update`` scaled by ``-learning_rate / weight_sum`` and
    scatter-ADDED into ``v`` and ``w``, or into the one packed ``[v |
    w]``, as ``params`` holds them (:func:`_scatter_add_rows`; ids repeat
    within a batch), so only the rows the batch names are written and no
    gradient of the table's shape exists. The sort, the slots and the
    distinct ids it needs are the step's head's (``step.order``); it
    computes none. ``l2 > 0`` adds one scaling pass over each array
    before the scatter-add: ``v - lr*(g + l2*v) = v*(1 - lr*l2) - lr*g``.

    With a ``rule`` (``optimizer="ftrl_adagrad"``) the update is
    :func:`_stateful_update`'s, which sets rows where this one adds.
    ``write``: what writes a packed tree's lane rows
    (:func:`_put_lane_rows`)."""
    if rule is not None:
        return _stateful_update(
            params, order, grads, seen, learning_rate, l2, rule, write)
    dw, gb, dv, wsum = grads
    with jax.named_scope("step.update"):
        denom = jnp.maximum(wsum, 1e-12)
        upd = (-learning_rate / denom) * jnp.concatenate(
            [dv, dw[:, None]], axis=1)
        decay = 1.0 - learning_rate * l2
        if isinstance(params, PackedTables):
            arrays = [_add_lane_rows(
                params, order, upd, seen[0], decay if l2 else None, write)]
        else:
            groups = _groups(params, SGD_TABLES)
            if l2:
                groups = [group._replace(array=group.array * decay)
                          for group in groups]
            arrays = _scatter_add_rows(groups, order, upd)
        return _regroup(
            params, SGD_TABLES, arrays,
            {"b": params["b"] - learning_rate * (gb / denom)})


def _add_lane_rows(packed: PackedTables, order: _IdOrder, upd, read: _Read,
                   decay: Optional[float], write=_write_rows):
    """:func:`_scatter_add_rows` over a packed tree: an id's entries
    summed first (the same one ``segment_sum``), then ``old + sum``, the
    float32 add the scatter-add makes, on the words the head read, and
    the new words put back by the writer every rule shares
    (:func:`_put_lane_rows`). ``decay``: the weight decay's factor, one
    scaling pass over the array before the add, as :func:`_sparse_update`
    makes over tables apart. Returns the new array."""
    n = order.slot.shape[0]
    sums = jax.ops.segment_sum(
        upd, order.slot, num_segments=n, indices_are_sorted=True)
    # summed FIRST, as the docstring of :func:`_scatter_add_rows` says
    # why: left to itself the compiler folds ``old + segment_sum(...)``
    # into a scatter-add of the entries into ``old``, one rounding at the
    # parameter's magnitude for every entry (17 times the error of one
    # add against the float64 reference, on the chip: PERF.md, PR 38)
    sums = lax.optimization_barrier(
        jnp.pad(sums, ((0, order.ids.shape[0] - n), (0, 0))))
    if decay is not None:
        # the touched ids read again from the scaled array: words scaled
        # here would round with the add (a fused multiply-add), not as
        # the array's own pass rounds them
        packed = _regroup(
            packed, None, [packed.rows * decay], packed.scalars)
        read = _take_lane_rows(packed, order)
    words, lanes = read
    return _put_lane_rows(packed, order, lanes, words + sums, write)


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


def _split_columns(buffer, widths):
    """{name: its columns of ``buffer``} for the tables of ``widths``
    (name -> its columns, 0 for a 1-D table) side by side in that order:
    a 1-D table is one column and comes back 1-D."""
    out, first = {}, 0
    for name, width in widths.items():
        out[name] = _span(buffer, (first, width))
        first += max(width, 1)
    return out


def _join_columns(tables, widths):
    """:func:`_split_columns` back: the tables of ``widths`` side by
    side; one table alone is handed back as it is."""
    if len(widths) == 1:
        (name,) = widths
        return tables[name]
    return jnp.concatenate(
        [tables[name] if width else tables[name][:, None]
         for name, width in widths.items()], axis=1)


def _id_sums(grads, values, order: _IdOrder, wsum, dtype):
    """An id's whole gradient before a rule runs: ``grads`` = {weight
    table: the entries' contributions, ``[n]`` or ``[n, C]`` in
    ``order``'s order} summed by sorted slot in ONE ``segment_sum`` with
    one more column, the count of an id's entries that carry a value.
    Returns ({table: the distinct ids' MEAN gradients by slot, ``[n +
    pad]`` or ``[n + pad, C]``}, that count by slot ``[n + pad]`` (an
    exact whole number of ``dtype``), ``max(wsum, 1e-12)``)."""
    n = values.shape[0]
    denom = jnp.maximum(wsum, 1e-12)
    # the last column counts an id's entries that carry a value
    sums = jax.ops.segment_sum(
        jnp.concatenate(
            [g if g.ndim == 2 else g[:, None] for g in grads.values()]
            + [(values != 0).astype(dtype)[:, None]],
            axis=1),
        order.slot, num_segments=n, indices_are_sorted=True)
    sums = jnp.pad(sums, ((0, order.ids.shape[0] - n), (0, 0)))
    widths = {name: 0 if g.ndim == 1 else g.shape[1]
              for name, g in grads.items()}
    grad = {name: g / denom
            for name, g in _split_columns(sums, widths).items()}
    return grad, sums[:, -1], denom


def _update_at_distinct(params, order: _IdOrder, grads, seen, wsum, state,
                        rule, write=_write_rows):
    """The skeleton of an update by a rule that keeps state for every
    parameter row, written once for the rules of this module and of
    models/ffm.py. ``grads``: {weight table: the entries' contributions
    to its gradient, ``[n]`` or ``[n, C]`` in ``order``'s order}, the
    tables in the column order of the head's read; ``seen`` = (the
    distinct ids' rows as the head read them, the entries' values);
    ``state``: the names of the tables of ``params`` the rule keeps beside
    the weights; ``rule(old, grad) -> new``: dicts by table name over the
    distinct ids' buffers (``grad`` the mean gradients of the weight
    tables, ``new`` every table's rows, weights and state). Returns (the
    new arrays, one for each of ``_groups(params, weights + state)``,
    ``max(wsum, 1e-12)``).

    The rule is not a scaled sum of the entries: an id's gradient is
    summed first (one ``segment_sum`` by sorted slot, as the SGD step's),
    whole BEFORE the rule runs; the id's state is read ONCE; weights and
    state are SET at the distinct ids (:func:`_set_rows`), so no array of
    a table's shape exists besides the tables and a row no entry names is
    neither read nor written. A slot whose entries all have value 0
    (padding names feature 0) keeps its weights and its state to the bit,
    whatever the rule.

    The reads and the writes go by physical array (:func:`_groups`). A
    packed row came whole in the head's read, state and all, and goes
    back in ONE set of all its columns (:func:`_put_lane_rows`, through
    ``write``). Tables that lie apart: the head
    read the weights, the state's arrays are read here
    (:func:`_take_distinct`), and every array is set on its own.

    ``step.state`` holds what the rule adds to the SGD step: the read of
    the arrays that hold state only, the rule and the keep-or-take
    selects, those arrays' write. The write of an array that holds
    weights and the id sums stay under ``step.update``."""
    rows, values = seen
    groups = _groups(params, tuple(grads) + tuple(state))
    # the head read every array that holds a weight table
    def in_head(group):
        return any(name in grads for name in group.widths)

    head = [g for g in groups if in_head(g)]
    rest = [g for g in groups if not in_head(g)]

    def widths_of(some):
        return {k: v for g in some for k, v in g.widths.items()}

    def set_rows_of(some, new):
        if isinstance(params, PackedTables):  # the one array, or none
            return [_put_lane_rows(params, order, rows.lanes,
                                   _join_columns(new, g.widths), write)
                    for g in some]
        return [_set_rows(g.array, order, _join_columns(new, g.widths))
                for g in some]

    with jax.named_scope("step.update"):
        grad, valued, denom = _id_sums(
            grads, values, order, wsum, rows.words.dtype)
        live = valued > 0
        old = _split_columns(rows.words, widths_of(head))
    with jax.named_scope("step.state"):
        if rest:
            old.update(_split_columns(
                _take_distinct(tuple(g.array for g in rest), order),
                widths_of(rest)))
        new = {name: jnp.where(live if rows_.ndim == 1 else live[:, None],
                               rows_, old[name])
               for name, rows_ in rule(old, grad).items()}
        state_arrays = set_rows_of(rest, new)
    with jax.named_scope("step.update"):
        # the arrays in ``groups``' order: the weights' lead it
        return set_rows_of(head, new) + state_arrays, denom


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
                     learning_rate: float, l2: float, rule: FtrlAdagrad,
                     write=_write_rows):
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
    arrays, denom = _update_at_distinct(
        params, order, {"v": dv, "w": dw}, seen, wsum, ("a", "z", "n"),
        partial(_ftrl_adagrad, alpha=learning_rate, l2=l2, rule=rule), write)
    with jax.named_scope("step.update"):
        return _regroup(
            params, FTRL_TABLES, arrays,
            {"b": params["b"] - learning_rate * (gb / denom)})


#: the words an id keeps in the base array of :class:`AdaptiveTables`, in
#: lane order: FTRL's weight and state (float32 bits), the count of the
#: entries that named the id, and the factor row it holds (int32; -1: none)
BASE_WORDS = ("w", "z", "n", "cnt", "slot")
_BASE_LAYOUT = tuple((name, 0) for name in BASE_WORDS)

#: the logical per-id tables of a memory-adaptive FM, by the names its
#: equations use: ``cnt`` and ``has_v`` whole numbers, ``v`` the factors an
#: id holds or would start from, ``a`` their accumulator (0 without a row)
ADAPTIVE_TABLES = ("w", "z", "n", "cnt", "has_v", "v", "a")

#: whole numbers among :class:`AdaptiveTables`' scalars, beside ``b``
_ADAPTIVE_COUNTS = ("active_ids", "refused", "counted_rows", "active_entries")


class Adaptive(NamedTuple):
    """difacto's memory-adaptive constraints, named as
    :class:`AdaptiveFMParam` names them: what the step over
    :class:`AdaptiveTables` needs beside the rule's :class:`FtrlAdagrad`.
    The tree carries them (static, as its ``init_scale``)."""

    v_threshold: int
    l1_shrk: bool
    #: counts are taken over the first ``count_rows`` rows the tree sees
    count_rows: int


def _split_base(words):
    """{name: ``[n]``} of :data:`BASE_WORDS` from base rows ``words s32[n,
    5]``: ``w``, ``z``, ``n`` as the float32 their bits are."""
    floats = lax.bitcast_convert_type(words[..., :3], jnp.float32)
    held = {name: floats[..., j] for j, name in enumerate(BASE_WORDS[:3])}
    held.update(cnt=words[..., 3], slot=words[..., 4])
    return held


def _join_base(held):
    """:func:`_split_base` back: ``s32[n, 5]``."""
    floats = jnp.stack([held[name] for name in BASE_WORDS[:3]], axis=1)
    return jnp.concatenate(
        [lax.bitcast_convert_type(floats, jnp.int32),
         held["cnt"][:, None], held["slot"][:, None]], axis=1)


def _factor_start(key, ids, num_factors: int, init_scale: float):
    """``v0 [n, K]``: the factors id ``ids[j]`` starts from when it is
    given a row, ``init_scale`` times a standard normal draw that is a
    pure function of ``key`` (``uint32[2]``, the seed's threefry key),
    the id and the column: the threefry block of the counter (id,
    column), its bits made a normal as ``jax.random.normal`` makes one.
    Nothing is stored for an id without a row, and an id draws the same
    factors whenever and in whatever batch it is activated."""
    shape = (ids.shape[0], num_factors)
    bits1, bits2 = jex_random.threefry2x32_p.bind(
        key[0], key[1],
        jnp.broadcast_to(ids.astype(jnp.uint32)[:, None], shape),
        jnp.broadcast_to(lax.iota(jnp.uint32, num_factors)[None, :], shape))
    # 23 random mantissa bits: [1, 2), then (-1, 1)
    unit = lax.bitcast_convert_type(
        ((bits1 ^ bits2) >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    uniform = jnp.maximum(low, unit * (np.float32(1.0) - low) + low)
    return init_scale * (np.float32(np.sqrt(2.0)) * lax.erf_inv(uniform))


@jax.tree_util.register_pytree_node_class
class AdaptiveTables(Mapping):
    """The storage of a memory-adaptive FM (difacto's ``V_threshold`` and
    ``l1_shrk``: Li et al., WSDM 2016): every id has a row of FIVE words,
    an id has factors only once it has earned them, and the factors live
    in a table of SLOTS far smaller than the id space.

    ``base s32[R, 128]``: lane rows (:func:`lane_geometry` of 5: 25 ids a
    row) of :data:`BASE_WORDS`, ``w``, ``z``, ``n`` as float32 bits beside
    the exact ``cnt`` and the id's ``slot`` (-1: none); integers, so that
    no float operation ever meets a count's bits. ``factors f32[capacity,
    2K]``: slot s holds ``[v | a]`` of the id whose ``slot`` is s, one id
    a lane row. ``scalars``: ``b`` and the whole numbers ``active_ids``
    (slots handed out), ``refused``, ``counted_rows``, ``active_entries``
    (:data:`_ADAPTIVE_COUNTS`, int32; the last wraps). ``key uint32[2]``:
    the seed's key, from which an id's first factors are drawn when it is
    activated (:func:`_factor_start`); ``init_scale`` their scale;
    ``adaptive`` the threshold, ``l1_shrk`` and the rows counts are taken
    over (:class:`Adaptive`).

    A pytree, as :class:`PackedTables` is: the step takes the storage from
    the tree it is given. As a mapping it reads as the LOGICAL tables of
    :data:`ADAPTIVE_TABLES` (copies, through the slot map: for a look at a
    fitted model or a test) and the scalars."""

    def __init__(self, base, factors, scalars: Dict, key, num_ids: int,
                 init_scale: float, adaptive: Adaptive):
        self.base = base
        self.factors = factors
        self.scalars = scalars
        self.key = key
        self.num_ids = int(num_ids)
        self.init_scale = float(init_scale)
        self.adaptive = Adaptive(*adaptive)

    def tree_flatten(self):
        return ((self.base, self.factors, self.scalars, self.key),
                (self.num_ids, self.init_scale, self.adaptive))

    @classmethod
    def tree_unflatten(cls, static, children):
        return cls(*children, *static)

    def holding(self, base, factors, scalars: Dict) -> "AdaptiveTables":
        """This tree over other arrays: what a step hands back."""
        return AdaptiveTables(base, factors, scalars, self.key, self.num_ids,
                              self.init_scale, self.adaptive)

    @property
    def base_rows(self) -> PackedTables:
        """The base array as the packed tree it is, for the lane-row
        reads and writes every packed learner shares."""
        return PackedTables(self.base, {}, _BASE_LAYOUT, self.num_ids)

    @property
    def capacity(self) -> int:
        return self.factors.shape[0]

    @property
    def num_factors(self) -> int:
        return self.factors.shape[1] // 2

    def start(self, ids):
        """``v0`` of ``ids``: :func:`_factor_start` under this tree's key."""
        return _factor_start(
            self.key, ids, self.num_factors, self.init_scale)

    def __getitem__(self, name):
        if name in self.scalars:
            return self.scalars[name]
        if name not in ADAPTIVE_TABLES:
            raise KeyError(name)
        return _adaptive_rows_at(
            self, jnp.arange(self.num_ids, dtype=jnp.int32), name=name)

    def __iter__(self):
        yield from ADAPTIVE_TABLES
        yield from self.scalars

    def __len__(self):
        return len(ADAPTIVE_TABLES) + len(self.scalars)


def init_adaptive(num_features: int, num_factors: int, capacity: int,
                  init_scale: float, adaptive: Adaptive,
                  seed) -> AdaptiveTables:
    """A memory-adaptive FM at its start: every id's words 0 and no slot,
    every factor row 0, nothing counted, the seed's key kept for the
    draws of :func:`_factor_start`."""
    lanes, per_row = lane_geometry(len(BASE_WORDS))
    rows = -(-num_features // per_row)
    place, column = np.divmod(np.arange(lanes), len(BASE_WORDS))
    empty = np.where(
        (place < per_row) & (column == BASE_WORDS.index("slot")), -1, 0
    ).astype(np.int32)
    ids = jnp.arange(rows, dtype=jnp.int32)[:, None] * per_row + place
    scalars = {"b": jnp.zeros((), jnp.float32)}
    scalars.update({name: jnp.zeros((), jnp.int32)
                    for name in _ADAPTIVE_COUNTS})
    return AdaptiveTables(
        jnp.where(ids < num_features, empty, 0),
        jnp.zeros((capacity, 2 * num_factors), jnp.float32), scalars,
        jax.random.key_data(jax.random.PRNGKey(seed)), num_features,
        init_scale, adaptive)


@partial(jax.jit, donate_argnums=(0,))
def _grant_counted(params: AdaptiveTables, cnt, ids, held_rows,
                   counted_rows) -> AdaptiveTables:
    """``params`` with every id's count set to ``cnt s32[F]`` and the
    first ``held_rows`` of ``ids`` (ascending, ``s32[capacity]``) holding
    factor rows 0, 1, ... in that order, each ``[v0(id) | 0]``; every
    other id without a row, ``w``, ``z``, ``n`` as they are. The words
    are set in the lanes where they lie: no array of an id a row."""
    capacity, num_ids = params.capacity, params.num_ids
    number = jnp.arange(capacity, dtype=jnp.int32)
    real = number < held_rows
    slot = jnp.full((num_ids,), -1, jnp.int32).at[
        jnp.where(real, ids, num_ids + number)].set(
            number, mode="drop", unique_indices=True,
            indices_are_sorted=True)
    rows, lanes = params.base.shape
    per_row = lanes // len(BASE_WORDS)
    place, column = np.divmod(np.arange(lanes), len(BASE_WORDS))
    words = {name: jnp.pad(of_ids, (0, rows * per_row - num_ids))
             for name, of_ids in (("cnt", cnt), ("slot", slot))}
    # both arrays in place, a block of rows a pass; the last block may
    # overlap the one before
    block = min(rows, 1 << 15)

    def put_words(i, base):
        at = jnp.minimum(i * block, rows - block)
        lane_rows = lax.dynamic_slice_in_dim(base, at, block)
        for name, padded in words.items():
            by_row = lax.dynamic_slice_in_dim(
                padded, at * per_row, block * per_row).reshape(block, per_row)
            lane_rows = jnp.where(
                (place < per_row) & (column == BASE_WORDS.index(name)),
                jnp.take(by_row, np.minimum(place, per_row - 1), axis=1),
                lane_rows)
        return lax.dynamic_update_slice_in_dim(base, lane_rows, at, 0)

    base = lax.fori_loop(0, -(-rows // block), put_words, params.base)
    k = params.num_factors
    chunk = min(capacity, 4 * _UPDATE_CHUNK)

    def put_factors(i, factors):
        at = jnp.minimum(i * chunk, capacity - chunk)
        fresh = jnp.pad(
            params.start(lax.dynamic_slice_in_dim(ids, at, chunk)),
            ((0, 0), (0, k)))
        held = (at + jnp.arange(chunk, dtype=jnp.int32)) < held_rows
        return lax.dynamic_update_slice_in_dim(
            factors, jnp.where(held[:, None], fresh, 0.0), at, 0)

    factors = lax.fori_loop(
        0, -(-capacity // chunk), put_factors, params.factors)
    return params.holding(base, factors, dict(
        params.scalars, active_ids=held_rows, counted_rows=counted_rows))


class _AdaptiveRead(NamedTuple):
    """What the head of the step over :class:`AdaptiveTables` read at the
    batch's distinct ids, by slot of the :class:`_IdOrder`."""

    #: the base lane rows and the ids' five words (:func:`_take_lane_rows`)
    base: _Read
    #: {name: ``[n + pad]``} of :data:`BASE_WORDS`
    held: Dict
    #: bool[n + pad]: the slot holds a distinct id that has a factor row
    has: jax.Array
    #: bool[n + pad] ``u``: the id's factors take part in this step
    uses: jax.Array
    #: f32[n + pad, 2K] ``[v | a]`` of the ids that have a row
    factors: jax.Array


def _adaptive_head(params: AdaptiveTables, batch):
    """:func:`_entries_in_id_order` through a slot map: the sort, the
    distinct ids' base rows, then the factor rows at the slots those rows
    name (an id without one names a row past the array, each its own, as
    the write's idle slots do), and ``[u v | w]`` for every entry, ``u``
    from the values BEFORE the step. Returns (order, the
    :class:`_AdaptiveRead`, vw, row_ids, values)."""
    order, row_ids, values = _batch_in_id_order(batch, params.num_ids)
    with jax.named_scope("step.gather"):
        base = _take_lane_rows(params.base_rows, order)
        held = _split_base(base.words)
        number = jnp.arange(order.ids.shape[0], dtype=jnp.int32)
        has = (number < order.distinct) & (held["slot"] >= 0)
        at = jnp.where(has, held["slot"], params.capacity + number)
        factors = _take_distinct(
            (params.factors,), order._replace(ids=at), sorted_ids=False)
        uses = has & (held["w"] != 0) if params.adaptive.l1_shrk else has
        k = params.num_factors
        vw = jnp.concatenate(
            [jnp.where(uses[:, None], factors[:, :k], 0.0),
             held["w"][:, None]], axis=1)
        vw = jnp.take(vw, order.slot, axis=0)
    return order, _AdaptiveRead(base, held, has, uses, factors), vw, \
        row_ids, values


def _put_factor_rows(params: AdaptiveTables, order: _IdOrder, target, new,
                     granted, write=_write_rows):
    """``factors[target[j]] = new[j]`` for every slot j of the order, whole
    rows through the one writer (:func:`_write_rows`), ``_UPDATE_CHUNK``
    slots a pass up to the last slot that holds a distinct id; a
    ``granted`` slot's row is ``[v0(id) | 0]`` instead. The draws of
    ``v0`` are made for the chunks that hold an activated id only (none
    once the counts stand still): ``step.activate``."""
    k = params.num_factors

    def put_chunk(i, array):
        at = i * _UPDATE_CHUNK

        def chunk_of(x):
            return lax.dynamic_slice_in_dim(x, at, _UPDATE_CHUNK)

        taken = chunk_of(granted)
        with jax.named_scope("step.activate"):
            fresh = lax.cond(
                jnp.any(taken),
                lambda ids: jnp.pad(params.start(ids), ((0, 0), (0, k))),
                lambda ids: jnp.zeros((_UPDATE_CHUNK, 2 * k), jnp.float32),
                chunk_of(order.ids))
        with jax.named_scope("step.update"):
            rows = jnp.where(taken[:, None], fresh, chunk_of(new))
            return write(array, chunk_of(target), rows)

    return lax.fori_loop(0, order.chunks, put_chunk, params.factors)


def _adaptive_step(params: AdaptiveTables, batch, objective: str,
                   learning_rate: float, l2: float, rule: FtrlAdagrad,
                   write=_write_rows):
    """One step of difacto's memory-adaptive FM over
    :class:`AdaptiveTables` (``benchmarks/configs/kdd12-fm-k128-adaptive.
    json`` states the equations): count, forward and backward with the
    factors of the ids whose ``u`` is 1, :func:`_ftrl_adagrad` at the
    distinct ids (``w``, ``z``, ``n`` of every id named with a value,
    ``v``, ``a`` of those with ``u`` = 1), then the activation: an id
    named with a value that has no row, whose count passed
    ``v_threshold`` and (under ``l1_shrk``) whose NEW ``w`` is not 0,
    takes the next free slot, in id order (a prefix sum over the sorted
    distinct ids), while there are slots; past that it is refused and
    may be taken by a later step. Nothing leaves the device.

    The reads are :func:`_adaptive_head`'s; the writes are two: the base
    lane rows through :func:`_put_lane_rows` and the factor rows whole
    (:func:`_put_factor_rows`), each through ``write`` (:func:`_write_rows`
    as the builder bound it: the base rows have 128 lanes, the factor rows
    ``2K``).
    ``step.activate`` holds the count, the test, the prefix sum and the
    draws of ``v0``; the other phases are scoped as in the dense step.
    Returns (the new tree, the step's metrics)."""
    order, read, vw, row_ids, values = _adaptive_head(params, batch)
    adaptive, scalars = params.adaptive, params.scalars
    dw, gb, dv, loss_sum, wsum = _fm_row_grads(
        scalars["b"], vw, row_ids, values, batch, objective)
    k = params.num_factors
    held, has, uses = read.held, read.has, read.uses
    with jax.named_scope("step.update"):
        grad, valued, denom = _id_sums(
            {"v": dv, "w": dw}, values, order, wsum, jnp.float32)
        live = valued > 0
    with jax.named_scope("step.state"):
        old = {name: held[name] for name in ("w", "z", "n")}
        old.update(v=read.factors[:, :k], a=read.factors[:, k:])
        takes = dict(w=live, z=live, n=live,
                     v=(live & uses)[:, None], a=(live & uses)[:, None])
        new = {name: jnp.where(takes[name], rows, old[name])
               for name, rows in _ftrl_adagrad(
                   old, grad, alpha=learning_rate, l2=l2, rule=rule).items()}
    with jax.named_scope("step.activate"):
        named = valued.astype(jnp.int32)
        counting = scalars["counted_rows"] < adaptive.count_rows
        rows_seen = jnp.where(
            counting, jnp.sum(batch["weight"] != 0).astype(jnp.int32), 0)
        cnt = held["cnt"] + jnp.where(counting, named, 0)
        wants = live & ~has & (cnt > adaptive.v_threshold)
        if adaptive.l1_shrk:
            wants = wants & (new["w"] != 0)
        place = scalars["active_ids"] + jnp.cumsum(
            wants.astype(jnp.int32)) - 1
        granted = wants & (place < params.capacity)
        slot = jnp.where(granted, place, held["slot"])
        number = jnp.arange(order.ids.shape[0], dtype=jnp.int32)
        target = jnp.where(has | granted, slot, params.capacity + number)
        after = {
            "active_ids": scalars["active_ids"] + jnp.sum(granted),
            "refused": scalars["refused"] + jnp.sum(wants & ~granted),
            "counted_rows": scalars["counted_rows"] + rows_seen,
            "active_entries": scalars["active_entries"] + jnp.sum(
                jnp.where(uses, named, 0)),
        }
    with jax.named_scope("step.update"):
        base = _put_lane_rows(
            params.base_rows, order, read.base.lanes,
            _join_base(dict(new, cnt=cnt, slot=slot)), write)
    factors = _put_factor_rows(
        params, order, target,
        jnp.concatenate([new["v"], new["a"]], axis=1), granted, write)
    with jax.named_scope("step.update"):
        after["b"] = scalars["b"] - learning_rate * (gb / denom)
    return (params.holding(base, factors, after),
            {"loss_sum": loss_sum, "weight_sum": wsum,
             "touched_rows": order.distinct})


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
    platform: Optional[str] = None,
    interpret: bool = False,
):
    """Jitted FM step over COO batches: ``(params, batch) -> (params,
    metrics)``, metrics = ``loss_sum``, ``weight_sum`` and
    ``touched_rows``, the count of parameter rows the step read (the
    distinct ids of what a chip sorted, summed over the chips where each
    sorts its own section): device scalars the fit loop reads once a
    pass. Every program opens with :func:`_entries_in_id_order`.

    Single device (``mesh is None``): the update touches only the rows
    the batch names (:func:`_sparse_update`). ``params`` is the dict with
    one array a table (``init_fm_params``) or a :class:`PackedTables`
    with the tables of :data:`SGD_TABLES` / :data:`FTRL_TABLES` as its
    row, and comes back as it came: the step takes the grouping of its
    reads and writes from the tree it is given (one read and one write
    of the packed row; one of each for every table that lies apart), and
    the two agree to the bit.

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

    One device, with a ``rule``: ``params`` may be an
    :class:`AdaptiveTables`, whose step is :func:`_adaptive_step`: the
    branch is on the tree the step is given, and the tree carries the
    threshold, ``l1_shrk`` and the rows counts are taken over.

    ``donate_batch=True`` (single-device path) donates params AND the
    batch arrays, the same contract as
    :func:`~dmlc_tpu.models.linear.make_linear_train_step`: XLA reuses
    the H2D landing buffers and scatters into the factor table in place
    (without it the step copies the table first) — only for streaming
    callers that rebind params each step and never touch a batch after
    its step (DeviceFeed loops, FMLearner).

    ``platform``: that of the devices ``params`` lie on, as the caller
    saw it (a learner reads it off its params). A packed tree's lane
    rows, and an :class:`AdaptiveTables`' rows, go back through
    :func:`_write_rows`, which chooses its writer by the platform and the
    row's lanes (:func:`row_writer`). **None (the default): not said,
    which means XLA's scatter, the portable form, on whatever device**: a
    direct caller on a TPU who wants the DMA writer says ``"tpu"``.
    ``interpret`` is passed on to the writer (the CPU tests' way to the
    kernel)."""
    check(num_features > 0, "num_features required")
    optimizer = "sgd" if rule is None else "ftrl_adagrad"
    _check_rule_placement(optimizer, mesh, table_sharding)
    write = partial(_write_rows, platform=platform, interpret=interpret)

    def local(params, batch, factor_axis):
        if isinstance(params, AdaptiveTables):
            _check_adaptive_placement(optimizer, mesh)
            return _adaptive_step(params, batch, objective, learning_rate,
                                  l2, rule, write)
        dw, gb, dv, loss_sum, wsum, order, seen = _fm_entry_grads(
            params, batch, objective, factor_axis=factor_axis)
        params = _sparse_update(
            params, order, (dw, gb, dv, wsum), learning_rate, l2,
            seen, rule, write)
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


@partial(jax.jit, static_argnames=("span",))
def _rows_at(held, ids, span=None):
    """A logical table's rows at ``ids``: gathered from the array that
    holds them, the table's columns (``span``; None: every column) cut
    from the RESULT. ``held``: the array, or the packed tree, whose lane
    rows are gathered and the ids' own words cut from them (every word,
    ``[n, C]``: one program for all the tables of a learner, which cuts
    a table's columns from what comes back)."""
    if not isinstance(held, PackedTables):
        rows = jnp.take(held, ids, axis=0)
        return rows if span is None else _span(rows, span)
    return _by_chunks(partial(_lane_words, held), ids)


def _lane_words(held: PackedTables, ids):
    """``[n, C]``: every word of ``ids`` in a packed tree, their lane rows
    gathered and each id's own words cut from its row."""
    lane_rows, places = held.lane_rows_of(ids)
    return _cut_lanes(jnp.take(held.rows, lane_rows, axis=0), places,
                      held.columns)


def _by_chunks(rows_of, ids):
    """``rows_of(ids)`` (``[n]`` or ``[n, C]``), a chunk of ids a pass:
    the lane rows of all of them at once lie beside the table (1.3 GB for
    the check's 360,000 ids of 17 columns)."""
    n = ids.shape[0]
    chunk = min(n, 4 * _UPDATE_CHUNK)
    rows = lax.map(
        rows_of, jnp.pad(ids, (0, -n % chunk)).reshape(-1, chunk))
    return rows.reshape((-1,) + rows.shape[2:])[:n]


@partial(jax.jit, static_argnames=("name",))
def _adaptive_rows_at(params: AdaptiveTables, ids, name: str):
    """Logical table ``name`` of :data:`ADAPTIVE_TABLES` at ``ids``,
    through the slot map: ``v`` of an id without a factor row is the
    ``v0`` it would start from, its ``a`` 0."""
    k = params.num_factors

    def rows_of(some):
        held = _split_base(_lane_words(params.base_rows, some))
        if name in held:
            return held[name]
        has = held["slot"] >= 0
        if name == "has_v":
            return has.astype(jnp.int32)
        rows = jnp.take(
            params.factors, jnp.where(has, held["slot"], 0), axis=0)
        if name == "v":
            return jnp.where(has[:, None], rows[:, :k], params.start(some))
        return jnp.where(has[:, None], rows[:, k:], 0.0)

    return _by_chunks(rows_of, ids)


@jax.jit
def _prints_by_slot(factors, slot_bits):
    """{``has_v``, ``v``, ``a``: ``uint32[F]``}: the fingerprints of the
    logical tables of an :class:`AdaptiveTables` that lie behind the slot
    map, from the ids' ``slot`` words (``slot_bits uint32[F]``, in id
    order), in ONE program: a factor row's two sums are taken a SLOT and
    carried to the id that holds it (``capacity`` sums, not ``F x K``
    draws of ``v0``) through one index; an id without a row reads 0."""
    has = slot_bits < jnp.uint32(1 << 31)
    k = factors.shape[1] // 2
    at = jnp.where(has, slot_bits, 0).astype(jnp.int32)
    out = {"has_v": has.astype(jnp.uint32)}
    for name, columns in (("v", factors[:, :k]), ("a", factors[:, k:])):
        prints = jnp.sum(lax.bitcast_convert_type(columns, jnp.uint32),
                         axis=1, dtype=jnp.uint32)
        out[name] = jnp.where(has, jnp.take(prints, at), jnp.uint32(0))
    return out


@partial(jax.jit, static_argnames=("span",))
def _row_fingerprints(held, span=None):
    """The wrapping sum of the bit patterns of each row of a logical
    table, read where its columns lie (``held`` and ``span`` as
    :func:`_rows_at` takes them): slices inside a reduction, no copy of a
    table. ``uint32[ids]``, or, of a packed tree's lane rows, one
    ``uint32[R]`` for each place of a lane row (:func:`_places_in_id_order`
    puts them in id order)."""
    if isinstance(held, PackedTables):
        # every place's sum in ONE reduction with a result for each, so
        # that the array is read once (a reduction a place reads it p
        # times, 28 GB whatever the geometry: 39 ms). A table of one
        # column is summed over two lanes, the other one masked: a
        # reduction over one lane is no reduction to the compiler, and p
        # passes again. (The slice inside the bitcast: converted whole,
        # the array's bits lie beside it.)
        first, width = span
        lanes = held.rows.shape[1]
        width = max(width, 2)
        windows = []
        for at in range(0, held.per_row * held.columns, held.columns):
            low = min(at + first, lanes - width)
            bits = lax.bitcast_convert_type(
                held.rows[:, low:low + width], jnp.uint32)
            if span[1] < 2:
                bits = jnp.where(
                    np.arange(low, low + width) == at + first, bits,
                    jnp.uint32(0))
            windows.append(bits)
        return lax.reduce(
            tuple(windows), (jnp.uint32(0),) * len(windows),
            lambda a, b: tuple(x + y for x, y in zip(a, b)), (1,))
    bits = lax.bitcast_convert_type(held, jnp.uint32)
    if span is not None:
        bits = _span(bits, span)
    return bits if bits.ndim == 1 else jnp.sum(bits, axis=1, dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("num_ids",))
def _places_in_id_order(by_place, num_ids: int):
    """``by_place``: for each place q of a lane row a vector ``[R]`` whose
    element r belongs to id ``r * p + q``; returns ``[num_ids]`` in id
    order. 128 lane rows at a time, a fixed permutation of ``p * 128``
    lanes: an array ``[R, p]``, its short side minor, is padded to 128
    lanes on the chip (43 times its size at p = 3). One program for
    every table of a learner (its code is most of a fingerprint's)."""
    per_row = len(by_place)
    if per_row == 1:
        return by_place[0][:num_ids]
    blocks = -(-by_place[0].shape[0] // _LANES)
    side_by_side = jnp.concatenate(
        [jnp.pad(x, (0, blocks * _LANES - x.shape[0])).reshape(
            blocks, _LANES) for x in by_place], axis=1)
    lane_row, place = np.divmod(np.arange(per_row * _LANES), per_row)
    return jnp.take(side_by_side, place * _LANES + lane_row, axis=1).reshape(
        -1)[:num_ids]


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
    refuses it.

    ``params`` on ONE device is a :class:`PackedTables` (:attr:`packs`):
    every per-id table of :meth:`table_layout` in one array, which reads
    as the mapping of logical tables it stands for (``params["v"]`` is a
    copy of ``v``'s columns). On a mesh it is the dict with one array a
    table, placed by :meth:`partition_rules`. A snapshot holds the logical
    tables under either, and restores under either. The benchmark's check
    reads the tables through :meth:`init_tables`, :meth:`table_names`,
    :meth:`scalars`, :meth:`table_rows` and :meth:`table_fingerprints`
    (``benchmarks/harness/tables.py``), which read them wherever they
    lie and never make an array of a table's shape.

    :class:`AdaptiveFMLearner` is this learner over a fourth storage, an
    :class:`AdaptiveTables` (a base row for every id, factor rows only
    for the ids that earned them, reached through a slot map): what it
    keeps, what its snapshot holds and what such an indirection owes the
    five calls are on its docstring."""

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
        # of those steps, the ones that took a packed tree
        self._packed_steps = 0
        # the platform the builder of ``_step`` was handed (``_ensure``)
        self._step_platform: Optional[str] = None
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

    @property
    def packs(self) -> bool:
        """Whether this learner keeps its per-id tables as one packed
        array (:class:`PackedTables`). It follows from the placement:
        where one device holds whole rows of every table, an id's
        weights and state are one row; tables divided or replicated over
        a mesh lie apart, each placed by its own rule."""
        return self.mesh is None

    def table_layout(self) -> Tuple[Tuple[str, int], ...]:
        """((logical table, its columns; 0 for a 1-D table), ...) in the
        order the step's head reads them, a packed row's column order."""
        k = self.param.num_factors
        width = {"v": k, "a": k}
        names = SGD_TABLES if self.param.optimizer == "sgd" else FTRL_TABLES
        return tuple((name, width.get(name, 0)) for name in names)

    def _initialiser(self, num_features: int):
        """``seed -> params`` of this learner's shapes over
        ``num_features`` ids, in the grouping it keeps them in: the
        tables of :func:`init_fm_params`, or the same values as one
        packed array (:func:`init_packed`)."""
        if not self.packs:
            return partial(
                init_fm_params, num_features, self.param.num_factors,
                self.param.init_scale, optimizer=self.param.optimizer)
        return partial(
            init_packed, num_features, self.table_layout(),
            partial(_fm_draw, init_scale=self.param.init_scale), {},
            {"b": jnp.zeros((), jnp.float32)})

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
            platform=self._step_platform,
        )

    def param_shardings(self):
        """NamedSharding tree of the params on this learner's mesh (None
        without one): what an initialiser's ``out_shardings`` takes so
        that each chip generates only the part it holds."""
        if self.mesh is None:
            return None
        # the rules go by a leaf's name and rank, not by its size
        template = jax.eval_shape(self._initialiser(2), 0)
        return sharding_tree(
            self.mesh,
            match_partition_rules(self.partition_rules(), template))

    def init_tables(self, seed, num_features: int = 0) -> None:
        """The learner's own storage from ``seed``: ONE jitted program
        with the seed as an argument (one program for every seed), its
        output placed by the learner's own shardings, so that on a mesh a
        chip writes its own part and no whole table exists on any one of
        them first, and a packed row is written as one array (the draws
        into ``v``'s columns, the rest beside them) with the draws of
        :func:`init_fm_params`."""
        nf = self.param.num_features or num_features
        self.params = jax.jit(
            self._initialiser(nf), out_shardings=self.param_shardings())(
                jnp.uint32(int(seed) % (1 << 32)))
        self._nf = nf
        if row_writer(self._params_platform(), self._written_lanes) == "dma":
            # the step will trace the kernel: its import here, while the
            # device writes the tables (the call above has only started
            # that), and not where the step's first trace would wait for it
            import_pallas()

    def _ensure(self, num_features: int):
        if self.params is None:
            self.init_tables(0, num_features)
        if self._step is None:
            # kept: what is counted and reported (:attr:`row_writer`) is
            # what the step's builder was handed
            self._step_platform = self._params_platform()
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
        self._packed_steps += isinstance(self.params, PackedTables)
        self.params, metrics = self._step(self.params, arrays)
        return metrics

    @property
    def row_columns(self) -> int:
        """The columns of the one row that holds an id's weights and
        state; 0 when the tables lie apart."""
        packed = self.packs if self.params is None else isinstance(
            self.params, PackedTables)
        return _columns(w for _, w in self.table_layout()) if packed else 0

    @property
    def lane_geometry(self) -> Tuple[int, int]:
        """(``L``, ``p``): the lanes of one lane row of the packed array
        and the ids side by side in it (:func:`lane_geometry` of
        :attr:`row_columns`); (0, 0) when the tables lie apart."""
        columns = self.row_columns
        return lane_geometry(columns) if columns else (0, 0)

    def _params_platform(self) -> Optional[str]:
        """The platform of the devices the params lie on (None before
        there are any): what the step's builder is told."""
        if self.params is None:
            return None
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        return next(iter(leaf.devices())).platform

    @property
    def _written_lanes(self) -> int:
        """The lanes of the lane rows the step writes whole through
        :func:`_write_rows`; 0 when the tables lie apart."""
        return self.lane_geometry[0]

    @property
    def row_writer(self) -> str:
        """What the step writes those lane rows back by: :func:`row_writer`
        of the platform its builder was handed and their lanes (``"dma"``
        / ``"scatter"``); before a step is built, of what a builder would
        be handed now (no params yet: nothing, so ``"scatter"``).
        ``"none"`` when the tables lie apart, whose writes are no whole
        rows."""
        lanes = self._written_lanes
        told = (self._params_platform() if self._step is None
                else self._step_platform)
        return row_writer(told, lanes) if lanes else "none"

    def epoch_span_args(self) -> Dict:
        lanes, per_row = self.lane_geometry
        return {"table_shards": self.table_shards,
                "optimizer": self.optimizer,
                "row_columns": self.row_columns,
                "row_lanes": lanes, "ids_per_lane_row": per_row,
                "row_writer": self.row_writer}

    def audit_params(self):
        """The arrays as they lie (a packed row as ``rows``): a sample
        of a logical table would copy its columns."""
        if isinstance(self.params, PackedTables):
            return dict(self.params.scalars, rows=self.params.rows)
        return self.params

    def state_bytes(self) -> int:
        """Bytes of optimizer state one chip holds: its part of every
        table the rule keeps beside the weights (0 under plain SGD),
        their logical columns wherever they lie."""
        if self.params is None:
            return 0
        # from the shapes: ``a`` is divided as ``v`` is, ``z`` and ``n``
        # are whole on every chip
        return sum(
            4 * self._nf * max(width, 1)
            // (self.table_shards if width else 1)
            for name, width in self.table_layout()
            if name in self.state_tables)

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
        state holds of one chip's memory.

        ``dmlc_fit_packed_row_steps_total`` counts the steps whose
        program read and wrote ONE packed row for each touched id (the
        tree the step took was a :class:`PackedTables`): every step on
        one device, none on a mesh. ``dmlc_fit_lane_row_steps_total``
        counts, from the same tree, the steps that read and wrote that
        row in one piece, as lanes of a row-major lane row (the one
        layout a :class:`PackedTables` has since PR 38; a tree of this
        program's parent has the first counter and not the second).
        ``dmlc_fit_dma_row_write_steps_total`` counts the steps whose
        lane rows went back by one DMA a row and not by XLA's scatter
        (:attr:`row_writer`: the platform the step's builder was handed
        and the lanes it chose by)."""
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
        reg.counter(
            "dmlc_fit_packed_row_steps_total",
            "optimizer steps that read and wrote one packed row (weights "
            "and optimizer state side by side) for each touched id",
            model=self.name).inc(self._packed_steps)
        reg.counter(
            "dmlc_fit_lane_row_steps_total",
            "optimizer steps that read and wrote each touched id's packed "
            "row as lanes of one row-major lane row, several ids to a row",
            model=self.name).inc(self._packed_steps)
        reg.counter(
            "dmlc_fit_dma_row_write_steps_total",
            "optimizer steps that wrote each distinct lane row back by one "
            "DMA of the row, not by a scatter",
            model=self.name).inc(nstep if self.row_writer == "dma" else 0)
        self._steps_of.clear()
        self._packed_steps = 0

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
        self._packed_steps = 0
        return fitloop.fit_feed(self, feed, *args, **kw)

    def snapshot_model(self) -> Dict:
        """The params by LOGICAL table, whatever array holds them: a
        table sharded over chips reaches the host as the ONE logical
        ``[F, K]`` array (``collective.checkpoint._to_host`` assembles
        the device arrays handed over here shard by shard), a packed row
        is copied to the host once and cut there into its tables. A
        snapshot has the same keys under every placement and restores
        under any."""
        params = self.params
        if not isinstance(params, PackedTables):
            return {"params": dict(params)}
        host = _from_lane_rows(
            np.asarray(params.rows), params.columns, params.num_ids)
        tables = {name: np.ascontiguousarray(_span(host, params.span(name)))
                  for name, _ in params.layout}
        return {"params": dict(tables, **params.scalars)}

    def restore_snapshot_model(self, model: Dict) -> None:
        """Re-place a snapshot's host FM params on device: straight from
        the host arrays to this learner's placement (each chip receives
        only the part its rules give it; one device receives the tables
        joined on the host into its packed row; the snapshot's own
        placement does not matter, a table is one logical array)."""
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
        if self.packs:
            self.params = jax.tree_util.tree_map(
                jnp.asarray, PackedTables.pack(
                    {k: np.asarray(v) for k, v in params.items()},
                    self.table_layout()))
        else:
            self.params = shard_params(
                params, self.mesh, rules=self.partition_rules())

    def predict_batch(self, batch) -> np.ndarray:
        _, _, vw, row_ids, values = _entries_in_id_order(
            *_head_tables(self.params, SGD_TABLES), batch)
        _, s, q, linear = _row_sums(
            vw, row_ids, values, int(batch["label"].shape[0]))
        return np.asarray(
            self.params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
        )

    # what the benchmark's check reads a learner's tables through
    # (benchmarks/harness/tables.py), with :meth:`init_tables`

    def table_names(self) -> Tuple[str, ...]:
        """The logical per-id tables, weights and state, by the names the
        model's equations use."""
        return tuple(name for name, _ in self.table_layout())

    def scalars(self) -> Dict[str, float]:
        names = self.table_names()
        return {k: float(self.params[k]) for k in self.params
                if k not in names}

    def _held(self, name: str):
        """(what holds logical table ``name``: the packed tree or the
        table's own array; its columns among an id's words, None where
        the array is the table)."""
        if isinstance(self.params, PackedTables):
            return self.params, self.params.span(name)
        return self.params[name], None

    def table_rows(self, name: str, ids):
        """Logical table ``name`` at ``ids`` (``[n]`` or ``[n, K]``, a
        device array): one jitted gather from the array that holds it
        (of a packed array: the ids' whole rows, the table's columns cut
        from them here)."""
        held, span = self._held(name)
        if isinstance(held, PackedTables):
            return _span(_rows_at(held, ids), span)
        return _rows_at(held, ids, span=span)

    def table_fingerprints(self, name: str):
        """``uint32[F]``: the wrapping sum of the bit patterns of each
        row of logical table ``name``, inside one jit, with no copy of a
        table."""
        held, span = self._held(name)
        prints = _row_fingerprints(held, span=span)
        if isinstance(held, PackedTables):
            return _places_in_id_order(prints, held.num_ids)
        return prints


class AdaptiveFMParam(FMParam):
    """:class:`FMParam` and difacto's memory-adaptive constraints
    (``src/sgd/sgd_param.h``: ``V_threshold``, ``l1_shrk``)."""

    optimizer = field(
        str, "ftrl_adagrad", enum={"ftrl_adagrad": "ftrl_adagrad"})
    # an id is given factors once MORE than this many entries named it
    v_threshold = field(int, 10, lower_bound=0)
    # ... and, with l1_shrk, only while its linear weight is not 0
    l1_shrk = field(bool, True)
    # rows of the factor table: the ids that can hold factors at a time
    factor_capacity = field(int, 0, lower_bound=0)
    # counts are taken over the first count_rows rows the learner sees
    # (difacto pushes them with the first epoch's batches)
    count_rows = field(int, 0, lower_bound=0)


class AdaptiveFMLearner(FMLearner):
    """difacto's memory-adaptive FM on ONE device (Li et al., WSDM 2016;
    ``github.com/dmlc/difacto`` ``V_threshold``, ``l1_shrk``): an
    :class:`FMLearner` under ``optimizer="ftrl_adagrad"`` whose ids hold
    factors only once they have earned them. Every id keeps FTRL's ``w``,
    ``z``, ``n``, an exact count ``cnt`` and the number of its factor row;
    the factors ``[v | a]`` live in a table of ``factor_capacity`` rows,
    handed out inside the step, on the device, to the ids a batch names
    whose count passed ``v_threshold`` and (``l1_shrk``) whose ``w`` is
    not 0. A model whose id space times width is far beyond a chip
    (54.7 M ids x 128 factors with AdaGrad's accumulator: 56 GB) fits one
    (:class:`AdaptiveTables`: 1.12 + 4.29 GB at 2^22 rows). Slots are
    never given back: an id whose ``w`` returns to 0 keeps its row and is
    left out of the forward pass and the update until ``w`` moves again.

    ``params`` is an :class:`AdaptiveTables`; the step
    (:func:`_adaptive_step`), the fit loop, the counters of
    :class:`FMLearner` and, beside them, what :meth:`epoch_closed` lists.
    A snapshot holds ``w``, ``z``, ``n``, ``cnt`` whole and the factor
    rows in use with their ids (a logical ``v`` would be ``F x K``), and
    restores under any capacity that holds them, slots rebuilt in id
    order. :meth:`start_from_counts` starts from counts taken elsewhere
    (difacto's counting pass): every id past the threshold holds its row
    before the first step. A mesh is refused.

    What a learner with such an indirection owes the benchmark's five
    calls: ``table_names`` are the LOGICAL tables (:data:`ADAPTIVE_TABLES`,
    the slot map itself is not one); ``table_rows("v", ids)`` answers for
    an id without a row with the factors it would start from, so the
    reference needs no generator; a fingerprint of ``v`` or ``a`` is
    taken a slot and carried to the ids; ``scalars`` holds the counts the
    step keeps (``active_ids``, ``refused``, ``counted_rows``)."""

    param_class = AdaptiveFMParam

    def __init__(self, mesh: Optional[Mesh] = None, **hyper):
        super().__init__(mesh, **hyper)
        check(self.param.factor_capacity > 0,
              "factor_capacity required: the rows of the factor table")
        check(self.param.count_rows > 0,
              "count_rows required: the rows the ids' counts are taken "
              "over (the data's rows: its first epoch)")
        # the step's running counts as the last pass end read them
        self._seen = dict.fromkeys(
            ("active_ids", "refused", "active_entries"), 0)
        # (the tree, its fingerprints behind the slot map not yet asked for)
        self._slot_prints = (None, {})

    def check_mesh(self, mesh: Mesh) -> None:
        _check_adaptive_placement(self.optimizer, mesh)

    @property
    def adaptive(self) -> Adaptive:
        return Adaptive(**{f: getattr(self.param, f)
                           for f in Adaptive._fields})

    @property
    def row_columns(self) -> int:
        """0: no one row holds an id's weights and state."""
        return 0

    @property
    def _written_lanes(self) -> int:
        """The base rows' (the factor rows, ``2K`` wide, go through the
        same writer at their own width)."""
        return lane_geometry(len(BASE_WORDS))[0]

    def _initialiser(self, num_features: int):
        return partial(
            init_adaptive, num_features, self.param.num_factors,
            self.param.factor_capacity, self.param.init_scale, self.adaptive)

    def _make_step(self, num_features: int):
        return make_fm_train_step(
            None, num_features, objective=self.param.objective,
            learning_rate=self.param.learning_rate, l2=self.param.l2,
            donate_batch=True, rule=self.rule,
            platform=self._step_platform)

    def init_tables(self, seed, num_features: int = 0) -> None:
        super().init_tables(seed, num_features)
        self._seen = dict.fromkeys(self._seen, 0)

    def start_from_counts(self, cnt, counted_rows: int) -> None:
        """Counts taken elsewhere (difacto's counting pass over the data,
        the epoch of an earlier run): ``cnt`` (``[F]`` whole numbers, a
        host array) becomes every id's count, ``counted_rows`` the rows
        they were taken over, and every id whose count passed
        ``v_threshold`` holds a factor row ``[v0(id) | 0]``, rows handed
        out in id order as a restore hands them; a capacity too small
        for them is refused by name. Weights and state stay as they are
        (after :meth:`init_tables`: 0), so under ``l1_shrk`` a row is
        masked until its id's ``w`` moves. One program for every ``cnt``."""
        nf, capacity = self._nf, self.param.factor_capacity
        cnt = np.asarray(cnt)
        check(cnt.shape == (nf,),
              "counts for %s ids, this learner trains %d", cnt.shape, nf)
        ids = np.flatnonzero(cnt > self.param.v_threshold).astype(np.int32)
        check(len(ids) <= capacity,
              "%d ids were counted past v_threshold=%d, factor_capacity=%d "
              "has no room for them", len(ids), self.param.v_threshold,
              capacity)
        self.params = _grant_counted(
            self.params, jnp.asarray(cnt, jnp.int32),
            jnp.asarray(np.pad(ids, (0, capacity - len(ids)))),
            jnp.int32(len(ids)), jnp.int32(counted_rows))
        self._seen["active_ids"] = len(ids)

    def epoch_span_args(self) -> Dict:
        return dict(
            super().epoch_span_args(),
            v_threshold=self.param.v_threshold, l1_shrk=self.param.l1_shrk,
            factor_capacity=self.param.factor_capacity,
            factor_columns=2 * self.param.num_factors,
            base_columns=len(BASE_WORDS))

    def audit_params(self):
        params = self.params
        return dict(params.scalars, base=params.base, factors=params.factors)

    def state_bytes(self) -> int:
        """``z`` and ``n`` of every id and ``a`` of every factor row."""
        if self.params is None:
            return 0
        return 4 * (2 * self._nf
                    + self.param.factor_capacity * self.param.num_factors)

    def pass_scalars(self) -> Dict:
        """The step's running counts, summed on the device since the
        learner's start: read once a pass, with its losses."""
        return {name: self.params.scalars[name] for name in self._seen}

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """:class:`FMLearner`'s counters, and the memory-adaptive ones,
        from the running counts the pass's end read (:meth:`pass_scalars`;
        a pass's share is the difference to the last read):
        ``dmlc_fit_adaptive_steps_total`` (steps through a slot map: every
        one), ``dmlc_fit_active_entries_total`` (entries whose id's
        factors took part, ``u`` = 1; over ``dmlc_fit_entries_total`` the
        share of the batch that ran at full width),
        ``dmlc_fit_activations_total`` / ``..._refused_total`` (ids given
        a factor row; ids that had earned one when none was free) and the
        gauge ``dmlc_fit_factor_in_use_rows``."""
        super().epoch_closed(reg, nstep, sums)
        reg.counter(
            "dmlc_fit_adaptive_steps_total",
            "optimizer steps that read factor rows through a slot map and "
            "handed out rows to the ids that earned them",
            model=self.name).inc(nstep)
        now = {name: int(sums.get(name, self._seen[name]))
               for name in self._seen}
        # int32 on the device: a long run's count of entries wraps
        passed = {name: (now[name] - self._seen[name]) % (1 << 32)
                  for name in now}
        self._seen = now
        reg.counter(
            "dmlc_fit_active_entries_total",
            "entries of the steps' batches whose id held factors that "
            "took part in the step",
            model=self.name).inc(passed["active_entries"])
        reg.counter(
            "dmlc_fit_activations_total",
            "ids given a factor row by a step",
            model=self.name).inc(passed["active_ids"])
        reg.counter(
            "dmlc_fit_activations_refused_total",
            "ids that had earned a factor row in a step that had none "
            "free (counted each step they ask)",
            model=self.name).inc(passed["refused"])
        reg.gauge(
            "dmlc_fit_factor_in_use_rows",
            "factor rows handed out, of factor_capacity",
            model=self.name).set(now["active_ids"])

    def snapshot_model(self) -> Dict:
        """``w``, ``z``, ``n``, ``cnt`` whole, the scalars, the seed's
        key, and the factor rows in use as (``factor_ids`` ascending,
        their ``v`` rows, their ``a`` rows): host arrays, whatever the
        capacity and the order the rows were handed out in."""
        params = self.params
        words = _from_lane_rows(
            np.asarray(params.base), len(BASE_WORDS), params.num_ids)
        held = {name: np.ascontiguousarray(words[:, j])
                for j, name in enumerate(BASE_WORDS)}
        ids = np.flatnonzero(held["slot"] >= 0).astype(np.int32)
        rows = np.asarray(jnp.take(
            params.factors, jnp.asarray(held["slot"][ids]), axis=0))
        k = params.num_factors
        model = {name: held[name].view(np.float32)
                 for name in ("w", "z", "n")}
        model.update(
            cnt=held["cnt"], factor_ids=ids,
            v=np.ascontiguousarray(rows[:, :k]),
            a=np.ascontiguousarray(rows[:, k:]), key=np.asarray(params.key),
            **{name: np.asarray(value)
               for name, value in params.scalars.items()})
        return {"params": model}

    def restore_snapshot_model(self, model: Dict) -> None:
        """A snapshot's host arrays into this learner's storage: the
        factor rows into the first slots, in id order."""
        params = {name: np.asarray(value)
                  for name, value in model["params"].items()}
        ids = params["factor_ids"]
        k, capacity = self.param.num_factors, self.param.factor_capacity
        check(params["v"].shape == (len(ids), k),
              "snapshot holds factor rows of shape %s, this learner "
              "trains %d factors", params["v"].shape, k)
        check(len(ids) <= capacity,
              "snapshot holds %d factor rows, factor_capacity=%d has no "
              "room for them", len(ids), capacity)
        nf = self.param.num_features or len(params["w"])
        check(len(params["w"]) == nf,
              "snapshot holds %d ids, this learner trains %d",
              len(params["w"]), nf)
        slot = np.full(nf, -1, np.int32)
        slot[ids] = np.arange(len(ids), dtype=np.int32)
        words = {name: params[name].astype(np.float32).view(np.int32)
                 for name in ("w", "z", "n")}
        words.update(cnt=params["cnt"].astype(np.int32), slot=slot)
        scalars = {"b": jnp.asarray(params["b"], jnp.float32)}
        scalars.update(
            {name: jnp.asarray(params.get(name, 0), jnp.int32)
             for name in _ADAPTIVE_COUNTS})
        scalars["active_ids"] = jnp.asarray(len(ids), jnp.int32)
        self._nf = nf
        self.params = AdaptiveTables(
            jnp.asarray(PackedTables.pack(words, _BASE_LAYOUT).rows),
            jnp.pad(
                jnp.asarray(np.concatenate(
                    [params["v"], params["a"]], axis=1), jnp.float32),
                ((0, capacity - len(ids)), (0, 0))),
            scalars, jnp.asarray(params["key"], jnp.uint32), nf,
            self.param.init_scale, self.adaptive)
        self._seen = {name: int(scalars[name]) for name in self._seen}

    def predict_batch(self, batch) -> np.ndarray:
        _, _, vw, row_ids, values = _adaptive_head(self.params, batch)
        _, s, q, linear = _row_sums(
            vw, row_ids, values, int(batch["label"].shape[0]))
        return np.asarray(
            self.params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1))

    def table_names(self) -> Tuple[str, ...]:
        return ADAPTIVE_TABLES

    def scalars(self) -> Dict[str, float]:
        return {name: float(self.params.scalars[name])
                for name in ("b", "active_ids", "refused", "counted_rows")}

    def table_rows(self, name: str, ids):
        return _adaptive_rows_at(self.params, ids, name=name)

    def table_fingerprints(self, name: str):
        """A word of the base rows by :func:`_row_fingerprints`; the three
        tables behind the slot map (``has_v``, ``v``, ``a``) from ONE
        program a tree (:func:`_prints_by_slot`), each handed out once:
        asked for all three of one tree, the slot words are read once."""
        params = self.params

        def words(held):
            base = params.base_rows
            return _places_in_id_order(
                _row_fingerprints(base, span=base.span(held)), base.num_ids)

        if name in BASE_WORDS:
            return words(name)
        tree, kept = self._slot_prints
        if tree is not params or name not in kept:
            kept = _prints_by_slot(params.factors, words("slot"))
        self._slot_prints = (params, kept)
        return kept.pop(name)

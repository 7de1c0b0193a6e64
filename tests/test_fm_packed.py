"""The packed row (``models/fm.py`` ``PackedTables``): where one device
holds whole rows of every per-id table, an FM or FFM learner keeps an
id's weights and optimizer state side by side in ONE array, laid
row-major with several ids to a lane row (``lane_geometry``), and a step
reads each touched id's words once and writes them once, in one piece.
On the suite's CPU devices, small sizes, at the narrow rows of K = 4 and
at the cells' three widths (17, 35 and 44 columns: 7 and 3 ids to a row
of 128 lanes, 5 to a row of 256):

(a) N steps over a packed tree against the same steps over the tables
    apart, from the same seed: every logical table, ``b`` and the losses
    equal to the bit;
(b) padded entries, an id under the L1 threshold, a slot whose entries
    all have value 0, rows no batch names;
(c) the check's five calls (``benchmarks/harness/tables.py``) on the
    learners under both groupings and on a factor-sharded mesh;
(d) the structure the speed rests on, from the lowered step: one gather
    and one scatter over the lane rows, nothing of the table's height;
    the mesh programs' indexed passes and collectives as they were;
(e) snapshots by logical table, across groupings and layouts;
(f) the counters, the span arguments, ``state_bytes``;
(g) the lane rows themselves: the geometry, every id of one lane row in
    a batch, a lane row's neighbours untouched, the last, partly filled
    lane row, the slots past the distinct ids;
(h) the writer: every slot of a chunk names a target of its own.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dmlc_tpu.collective.checkpoint import _to_host
from dmlc_tpu.models import FFMLearner, FMLearner, FtrlAdagrad
from dmlc_tpu.models import fm as fm_module
from dmlc_tpu.models.ffm import init_ffm_params, make_ffm_train_step
from dmlc_tpu.models.fm import (
    FTRL_TABLES,
    SGD_TABLES,
    PackedTables,
    init_fm_params,
    lane_geometry,
    make_fm_train_step,
)
from dmlc_tpu.utils.logging import DMLCError

F, K = 2003, 4
ROWS, NNZ = 32, 6
FIELD_SIZES = (500, 700, 802)  # end at F
CHIPS = 2
SEED = 2147483659  # beyond 32 signed bits, like the benchmark driver's
RULE = FtrlAdagrad(l1=2e-3, lr_beta=0.1, v_learning_rate=0.1,
                   v_lr_beta=0.1, v_l2=1e-3)
CASES = ("sgd", "ftrl_adagrad", "adagrad")
#: the same three rules at the widths of the benchmark's cells: 16
#: factors (17 and 35 columns), 11 fields x 2 factors (2 x 22 columns)
WIDE = ("sgd-17", "ftrl_adagrad-35", "adagrad-44")
WIDE_FIELD_SIZES = (3, 3, 18, 30, 50, 100, 150, 250, 300, 500, 599)
#: (L, p) of each case's row
GEOMETRY = {"sgd": (128, 25), "ftrl_adagrad": (128, 11), "adagrad": (128, 10),
            "sgd-17": (128, 7), "ftrl_adagrad-35": (128, 3),
            "adagrad-44": (256, 5)}


def _sizes(case):
    """(the rule's name, factors, the FFM's field sizes) of ``case``."""
    rule, _, wide = case.partition("-")
    if rule == "adagrad":
        return rule, 2, WIDE_FIELD_SIZES if wide else FIELD_SIZES
    return rule, 16 if wide else K, None


def _learner(case, mesh=None, **more):
    rule, k, fields = _sizes(case)
    if mesh is not None:
        more["table_sharding"] = "factors"
    if rule == "adagrad":
        hyper = dict(num_features=F, field_sizes=fields, num_factors=k,
                     learning_rate=0.2, l2=1e-3, a_init=1e-4)
        return FFMLearner(mesh=mesh, **dict(hyper, **more))
    hyper = dict(num_features=F, num_factors=k, learning_rate=0.1, l2=0.01,
                 init_scale=0.1)
    if rule != "sgd":
        hyper.update(optimizer="ftrl_adagrad", **RULE._asdict())
    return FMLearner(mesh=mesh, **dict(hyper, **more))


def _step(case, mesh=None, l2=None):
    rule, _, fields = _sizes(case)
    sharding = "replicated" if mesh is None else "factors"
    if rule == "adagrad":
        return make_ffm_train_step(
            mesh, F, fields, learning_rate=0.2,
            l2=1e-3 if l2 is None else l2, table_sharding=sharding)
    return make_fm_train_step(
        mesh, F, learning_rate=0.1, l2=0.01 if l2 is None else l2,
        table_sharding=sharding, rule=None if rule == "sgd" else RULE)


def _apart(case, seed=3):
    """(the logical tables from the models' own initialisers, the packed
    row's layout)."""
    rule, k, fields = _sizes(case)
    if rule == "adagrad":
        c = k * len(fields)
        return (init_ffm_params(F, k, len(fields), 0.5, 1e-4, seed),
                (("v", c), ("a", c)))
    optimizer = "sgd" if rule == "sgd" else "ftrl_adagrad"
    names = SGD_TABLES if rule == "sgd" else FTRL_TABLES
    return (init_fm_params(F, k, 0.1, seed, optimizer=optimizer),
            tuple((n, k if n in ("v", "a") else 0) for n in names))


def _columns(layout):
    return sum(max(w, 1) for _, w in layout)


def _lane_shape(layout):
    """The shape of the packed array of ``layout`` over ``F`` ids."""
    lanes, per_row = lane_geometry(_columns(layout))
    return -(-F // per_row), lanes


def _batch(seed, pad=5, silent=(1501, 1502)):
    """One csr batch over a few hundred ids, so that ids repeat: its
    first ``pad`` entries are padding (feature 0, value 0) and every
    entry of the ``silent`` ids has value 0."""
    rng = np.random.default_rng(seed)
    idx = np.concatenate([
        rng.integers(1, 120, ROWS * NNZ // 2),
        rng.integers(600, 900, ROWS * NNZ // 4),
        rng.integers(1300, 1600, ROWS * NNZ - 3 * (ROWS * NNZ // 4)),
    ]).astype(np.int32)
    rng.shuffle(idx)
    val = (0.5 + rng.random(ROWS * NNZ)).astype(np.float32)
    idx[:pad], val[:pad] = 0, 0.0
    idx[pad:pad + len(silent)] = silent
    val[np.isin(idx, silent)] = 0.0
    return {
        "label": jnp.asarray(rng.integers(0, 2, ROWS).astype(np.float32)),
        "weight": jnp.ones(ROWS, jnp.float32),
        "indices": jnp.asarray(idx), "values": jnp.asarray(val),
        "offsets": jnp.asarray(np.arange(ROWS + 1, dtype=np.int32) * NNZ)}


def _bits(array):
    return np.ascontiguousarray(np.asarray(array)).view(np.uint32)


def _fingerprints(table):
    bits = _bits(table)
    return bits if bits.ndim == 1 else bits.sum(axis=1, dtype=np.uint32)


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:CHIPS]), ("dp",))


# ---- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES + WIDE)
def test_a_packed_steps_equal_the_tables_apart_to_the_bit(case):
    apart, layout = _apart(case)
    packed = PackedTables.pack(apart, layout)
    assert packed.rows.shape == _lane_shape(layout)
    assert (packed.rows.shape[1], packed.per_row) == GEOMETRY[case]
    assert packed.columns == _columns(layout) and packed.num_ids == F
    step = _step(case)
    for i in range(6):
        batch = _batch(i)
        apart, m_apart = step(apart, batch)
        packed, m_packed = step(packed, batch)
        assert isinstance(packed, PackedTables) and packed.layout == layout
        for key in ("loss_sum", "weight_sum", "touched_rows"):
            assert np.asarray(m_apart[key]) == np.asarray(m_packed[key]), key
    assert sorted(packed) == sorted(apart)
    for name in apart:
        np.testing.assert_array_equal(
            _bits(packed[name]), _bits(apart[name]), err_msg=name)


@pytest.mark.parametrize("case", ["sgd", "sgd-17"])
def test_a_an_ids_entries_are_summed_before_they_meet_its_row(case):
    """Ids named hundreds of times in a batch: ``old + sum`` with the sum
    made first, the one add the scatter-add over tables apart makes. (A
    compiler left to fold the add into the sum's scatter adds the entries
    into the row one by one: other roundings, many times the error.)"""
    rows, nnz = 512, 8
    rng = np.random.default_rng(11)
    batch = {
        "label": jnp.asarray(rng.integers(0, 2, rows).astype(np.float32)),
        "weight": jnp.ones(rows, jnp.float32),
        "indices": jnp.asarray(
            rng.choice(np.r_[3:40, 700:710], rows * nnz).astype(np.int32)),
        "values": jnp.asarray(
            (0.5 + rng.random(rows * nnz)).astype(np.float32)),
        "offsets": jnp.asarray(np.arange(rows + 1, dtype=np.int32) * nnz)}
    apart, layout = _apart(case)
    packed = PackedTables.pack(apart, layout)
    step = _step(case, l2=0.0)
    for _ in range(2):
        apart, _ = step(apart, batch)
        packed, _ = step(packed, batch)
    for name in apart:
        np.testing.assert_array_equal(
            _bits(packed[name]), _bits(apart[name]), err_msg=name)


def test_a_a_packed_tree_is_a_mapping_of_the_logical_tables():
    apart, layout = _apart("ftrl_adagrad")
    packed = PackedTables.pack(apart, layout)
    assert list(packed) == ["v", "w", "a", "z", "n", "b"]
    assert len(packed) == 6 and packed["w"].shape == (F,)
    assert packed["a"].shape == (F, K) and packed["b"].shape == ()
    assert packed.span("z") == (2 * K + 1, 0)
    with pytest.raises(KeyError):
        packed["q"]
    # a pytree whose leaves are the one array and the scalars
    leaves, tree = jax.tree_util.tree_flatten(packed)
    assert sorted(np.shape(leaf) for leaf in leaves) == [
        (), _lane_shape(layout)]
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert again.layout == layout and again.rows is packed.rows
    assert again.num_ids == F
    # and numpy parts pack on the host (a snapshot's way back)
    host = PackedTables.pack(
        {k: np.asarray(v) for k, v in apart.items()}, layout)
    assert isinstance(host.rows, np.ndarray)
    np.testing.assert_array_equal(host.rows, np.asarray(packed.rows))


def test_a_the_step_refuses_a_row_in_another_order():
    apart, _ = _apart("sgd")
    swapped = PackedTables.pack(apart, (("w", 0), ("v", K)))
    with pytest.raises(DMLCError, match="the packed row"):
        _step("sgd")(swapped, _batch(0))


# ---- (b) ------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES + WIDE)
def test_b_padding_silent_ids_and_rows_no_batch_names(case):
    # l2 = 0: under plain SGD l2 is a scaling pass over every row
    model = _learner(case, l2=0.0)
    model.init_tables(SEED)
    assert isinstance(model.params, PackedTables)
    model._ensure(F)
    names = model.table_names()
    start = {k: np.asarray(v) for k, v in dict(model.params).items()}
    prints = {k: np.asarray(model.table_fingerprints(k)) for k in names}
    batches = [_batch(i) for i in range(4)]
    named = np.unique(np.concatenate(
        [np.asarray(b["indices"]) for b in batches]))
    for batch in batches:
        model.train_step(dict(batch))
    after = {k: np.asarray(v) for k, v in dict(model.params).items()}
    # rows no batch names: neither read nor written
    quiet = np.setdiff1d(np.arange(F), named)
    for k in names:
        changed = np.asarray(model.table_fingerprints(k)) != prints[k]
        assert not changed[quiet].any(), k
        assert changed[named].any(), k
    # a slot whose entries all have value 0 (and the padding's feature 0)
    # keeps weights and state to the bit: a rule that SETS rows keeps the
    # old row, plain SGD adds 0
    for k in names:
        for i in (0, 1501, 1502):
            np.testing.assert_array_equal(
                _bits(after[k][i]), _bits(start[k][i]), err_msg=k)
    if case.startswith("ftrl_adagrad"):
        # an id under the L1 threshold holds an exact 0, others do not
        touched = after["n"] > 0
        assert (after["w"][touched] == 0.0).any()
        assert (after["w"][touched] != 0.0).any()
        assert (after["z"][touched] != 0.0).all()


# ---- (c) ------------------------------------------------------------------

def _apart_learner(case, seed=None):
    """A one-device learner whose tables lie apart: every method goes by
    the tree ``params`` is, and ``dict(packed)`` is the tree with one
    array a table."""
    model = _learner(case)
    if seed is not None:
        model.init_tables(seed)
        model.params = dict(model.params)
    return model


@pytest.mark.parametrize("place", ["packed", "apart", "factor-sharded"])
@pytest.mark.parametrize("case", CASES + WIDE)
def test_c_the_five_calls_tell_the_truth_about_the_tables(case, place, mesh):
    if place == "apart":
        model = _apart_learner(case, SEED)
    else:
        model = _learner(case, mesh if place == "factor-sharded" else None)
        model.init_tables(SEED)
    assert isinstance(model.params, PackedTables) == (place == "packed")
    assert isinstance(model.params, dict) == (place != "packed")
    # the draws of the models' own initialisers, column for column
    seed = jnp.uint32(SEED % (1 << 32))
    want, layout = jax.jit(lambda s: _apart(case, s)[0])(seed), \
        _apart(case)[1]
    assert model.table_layout() == layout
    assert model.table_names() == tuple(n for n, _ in layout)
    for name in want:
        np.testing.assert_array_equal(
            _bits(model.params[name]), _bits(want[name]), err_msg=name)
    model._ensure(F)
    for i in range(3):
        model.train_step(dict(_batch(i)) if mesh is None or place !=
                         "factor-sharded" else _mesh_batch(i, mesh))
    stored = _to_host(model.snapshot_model())["params"]
    assert sorted(stored) == sorted(want)
    assert model.scalars() == {
        k: float(v) for k, v in stored.items() if not np.ndim(v)}
    ids = jnp.asarray(np.r_[0, 3:90:7, 1501, F - 1], jnp.int32)
    for name in model.table_names():
        np.testing.assert_array_equal(
            np.asarray(model.table_rows(name, ids)),
            stored[name][np.asarray(ids)], err_msg=name)
        prints = model.table_fingerprints(name)
        assert prints.dtype == jnp.uint32 and prints.shape == (F,)
        np.testing.assert_array_equal(
            np.asarray(prints), _fingerprints(stored[name]), err_msg=name)


def _mesh_batch(seed, mesh):
    """``_batch`` as a mesh step takes it: row-split sections with local
    offsets (``ShardedCSRBatch``'s arrays), placed over ``dp``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch = {k: np.asarray(v) for k, v in _batch(seed).items()}
    batch["offsets"] = np.tile(
        np.arange(ROWS // CHIPS + 1, dtype=np.int32) * NNZ, CHIPS)
    return jax.device_put(batch, NamedSharding(mesh, P("dp")))


def test_c_the_packed_initialiser_draws_in_blocks(monkeypatch):
    """Blocks that do not divide the table, one that holds it all, one
    smaller than a lane row's ids: the same array (the last block starts
    early and draws some lane rows again), which holds the draws of the
    models' own initialisers to the bit and 0 in every lane no id uses."""
    want = {}
    for block in (3, 64, 1000, 1 << 20):
        monkeypatch.setattr(fm_module, "_INIT_BLOCK", block)
        for case in CASES + WIDE:
            model = _learner(case)
            model.init_tables(7)
            got = np.asarray(model.params.rows)
            np.testing.assert_array_equal(
                got, want.setdefault(case, got), err_msg=case)
    for case in CASES + WIDE:
        apart, layout = jax.jit(lambda s: _apart(case, s)[0])(
            jnp.uint32(7)), _apart(case)[1]
        assert want[case].shape == _lane_shape(layout)
        np.testing.assert_array_equal(
            _bits(want[case]),
            _bits(PackedTables.pack(apart, layout).rows), err_msg=case)


# ---- (d) ------------------------------------------------------------------

def _indexed_passes(step, params, batch):
    """[(primitive, the shape of what it reads or writes)] of every sort,
    gather, scatter and collective of the traced step, sorted."""
    wanted = ("sort", "gather", "scatter", "scatter-add", "all_gather",
              "psum", "psum_invariant", "psum2")
    return sorted(
        (e.primitive.name, tuple(e.invars[0].aval.shape))
        for e in _walk(jax.make_jaxpr(step)(params, batch).jaxpr)
        if e.primitive.name in wanted)


@pytest.mark.parametrize("case", CASES + WIDE)
def test_d_one_gather_and_one_scatter_over_the_lane_rows(case):
    apart, layout = _apart(case)
    lane_shape = _lane_shape(layout)
    # plain SGD's weight decay is a pass over the array and a second read
    # of the touched lane rows, from the scaled array
    step = _step(case, l2=0.0 if case.startswith("sgd") else None)

    def table_passes(params):
        found = _indexed_passes(step, params, _batch(0))
        return [(name, shape) for name, shape in found
                if shape[0] in (F, lane_shape[0])]

    # one read of lane rows and ONE writer, a SET of whole lane rows,
    # under the rule that adds as under the rules that set
    assert table_passes(PackedTables.pack(apart, layout)) == [
        ("gather", lane_shape), ("scatter", lane_shape)]
    # the tables apart, as a mesh holds them: a read and a write each
    write = "scatter-add" if case.startswith("sgd") else "scatter"
    shapes = sorted((F, w) if w else (F,) for _, w in layout)
    assert table_passes(apart) == (
        [("gather", s) for s in shapes] + [(write, s) for s in shapes])
    # and the lowered text of the packed step agrees: one scatter whose
    # operand is the array of lane rows, nothing of the table's height
    text = step.lower(
        PackedTables.pack(apart, layout), _batch(0)).as_text()
    scattered = re.findall(
        r"\}\) : \(tensor<(\d+)x(\d+)xf32>, tensor<[^>]*xi32>, "
        r"tensor<[^>]*xf32>\) -> tensor<\1x\2xf32>", text)
    assert [dims for dims in scattered
            if int(dims[0]) in (F, lane_shape[0])] == [
        tuple(str(n) for n in lane_shape)], scattered
    assert "tensor<%dx" % F not in text


#: what the parent commit's mesh programs traced to (289007f; F = 2003,
#: K = 4 or 3 fields x 2 factors, two chips, 32 rows of 6 entries):
#: (primitive, its first operand's shape) of every sort, indexed pass
#: and collective, sorted
_GATHERS = [("all_gather", (16,)), ("all_gather", (16,)),
            ("all_gather", (17,)), ("all_gather", (96,)),
            ("all_gather", (96,))]
MESH_PASSES = {
    "sgd": _GATHERS + [
        ("gather", (32, 3)), ("gather", (2003,)), ("gather", (2003, 2)),
        ("gather", (2048, 3)), ("psum", (32,)), ("scatter-add", (2, 96)),
        ("scatter-add", (32, 5)), ("scatter-add", (192, 3)),
        ("scatter-add", (2003,)), ("scatter-add", (2003, 2)),
        ("sort", (192,)), ("sort", (192,))],
    "ftrl_adagrad": _GATHERS + [
        ("gather", (32, 3)), ("gather", (2003,)), ("gather", (2003,)),
        ("gather", (2003,)), ("gather", (2003, 2)), ("gather", (2003, 2)),
        ("gather", (2048, 3)), ("psum", (32,)), ("scatter", (2003,)),
        ("scatter", (2003,)), ("scatter", (2003,)), ("scatter", (2003, 2)),
        ("scatter", (2003, 2)), ("scatter-add", (2, 96)),
        ("scatter-add", (32, 5)), ("scatter-add", (192, 4)),
        ("sort", (192,)), ("sort", (192,))],
    "adagrad": _GATHERS + [
        ("gather", (32, 9)), ("gather", (96, 4)), ("gather", (2003, 3)),
        ("gather", (2003, 3)), ("gather", (2048, 3)), ("psum", (32,)),
        ("scatter", (2003, 3)), ("scatter", (2003, 3)),
        ("scatter-add", (2, 96)), ("scatter-add", (96, 5)),
        ("scatter-add", (192, 4)), ("sort", (192,)), ("sort", (192,))],
    "replicated": [
        ("gather", (16, 5)), ("gather", (2003,)), ("gather", (2003, 4)),
        ("gather", (2048, 5)), ("psum_invariant", (10019,)),
        ("scatter-add", (16, 9)), ("scatter-add", (96,)),
        ("scatter-add", (2003,)), ("scatter-add", (2003, 4)),
        ("sort", (96,)), ("sort", (96,))],
}


@pytest.mark.parametrize("case", sorted(MESH_PASSES))
def test_d_the_mesh_programs_pass_over_what_they_passed_over(case, mesh):
    """The factor-sharded and the replicated mesh steps keep one array a
    table: the same sorts, gathers, scatters and collectives over the
    same shapes as before the packed row."""
    from dmlc_tpu.models.ffm import FFM_FACTOR_PARTITION_RULES
    from dmlc_tpu.models.fm import fm_partition_rules
    from dmlc_tpu.parallel.partition import shard_params

    if case == "replicated":
        step = make_fm_train_step(mesh, F, learning_rate=0.1)
        params = shard_params(_apart("sgd")[0], mesh)
    else:
        step = _step(case, mesh)
        params = shard_params(
            _apart(case)[0], mesh,
            rules=FFM_FACTOR_PARTITION_RULES if case == "adagrad"
            else fm_partition_rules("factors"))
    assert _indexed_passes(step, params, _mesh_batch(0, mesh)) == \
        MESH_PASSES[case]


# ---- (e) ------------------------------------------------------------------

def _trained(case, mesh=None, steps=3):
    model = _learner(case, mesh)
    model.init_tables(SEED)
    model._ensure(F)
    for i in range(steps):
        model.train_step(
            dict(_batch(i)) if mesh is None else _mesh_batch(i, mesh))
    return model


@pytest.mark.parametrize("case", CASES + WIDE)
def test_e_snapshots_cross_the_groupings_to_the_bit(case, mesh):
    packed = _trained(case)
    snap = _to_host(packed.snapshot_model())
    # by logical table, in the format of every snapshot so far
    apart, _ = _apart(case)
    assert sorted(snap["params"]) == sorted(apart)
    for name, table in snap["params"].items():
        assert isinstance(table, np.ndarray), name
        assert table.shape == np.shape(apart[name]), name
        np.testing.assert_array_equal(
            _bits(table), _bits(packed.params[name]), err_msg=name)
    # packed -> factor-sharded mesh
    sharded = _learner(case, mesh)
    sharded.restore_snapshot_model(snap)
    assert not isinstance(sharded.params, PackedTables)
    wide = sharded.params["v"]
    assert {s.data.shape for s in wide.addressable_shards} == {
        (F, wide.shape[1] // CHIPS)}
    back = _to_host(sharded.snapshot_model())
    # mesh -> packed
    again = _learner(case)
    again.restore_snapshot_model(back)
    assert isinstance(again.params, PackedTables)
    assert again.params.layout == packed.params.layout
    np.testing.assert_array_equal(
        _bits(again.params.rows), _bits(packed.params.rows))
    for name in snap["params"]:
        np.testing.assert_array_equal(
            _bits(back["params"][name]), _bits(snap["params"][name]),
            err_msg=name)
    # and both go on from it alike
    batch = _batch(9)
    again._ensure(F)
    again.train_step(dict(batch))
    packed.train_step(dict(batch))
    np.testing.assert_array_equal(
        _bits(again.params.rows), _bits(packed.params.rows))


@pytest.mark.parametrize("case", CASES + WIDE)
def test_e_a_snapshot_in_the_format_before_the_packed_row_restores(
        case, mesh):
    """What a learner that kept one array a table wrote: ``{"params":
    {table: array}}`` of its device arrays through ``_to_host``."""
    apart, layout = _apart(case)
    step = _step(case)
    for i in range(2):
        apart, _ = step(apart, _batch(i))
    old = _to_host({"params": dict(apart)})
    sharded = _learner(case, mesh)
    sharded.restore_snapshot_model(old)
    for name, table in _to_host(sharded.snapshot_model())["params"].items():
        np.testing.assert_array_equal(
            _bits(table), _bits(apart[name]), err_msg=name)
    model = _learner(case)
    model.restore_snapshot_model(old)
    assert isinstance(model.params, PackedTables)
    assert model.params.layout == layout
    for name in apart:
        np.testing.assert_array_equal(
            _bits(model.params[name]), _bits(apart[name]), err_msg=name)
    model._ensure(F)
    model.train_step(dict(_batch(5)))
    apart, _ = step(apart, _batch(5))
    for name in apart:
        np.testing.assert_array_equal(
            _bits(model.params[name]), _bits(apart[name]), err_msg=name)


def test_e_a_snapshot_of_another_optimizer_is_still_refused():
    plain = _to_host(_trained("sgd", steps=1).snapshot_model())
    stateful = _learner("ftrl_adagrad")
    with pytest.raises(DMLCError, match=r"snapshot holds the optimizer "
                       r"state \[\], optimizer='ftrl_adagrad' keeps "
                       r"\['a', 'n', 'z'\]"):
        stateful.restore_snapshot_model(plain)
    held = _to_host(_trained("ftrl_adagrad", steps=1).snapshot_model())
    with pytest.raises(DMLCError, match="snapshot holds the optimizer state"):
        _learner("sgd").restore_snapshot_model(held)
    with pytest.raises(DMLCError, match="factor table of shape"):
        FMLearner(num_features=F, num_factors=K + 1).restore_snapshot_model(
            plain)


def test_e_predict_reads_the_heads_columns_of_the_packed_row():
    batch = _batch(2)
    for case in CASES + WIDE:
        packed = _trained(case)
        apart = _apart_learner(case)
        apart.params = {k: jnp.asarray(v) for k, v in _to_host(
            packed.snapshot_model())["params"].items()}
        apart._nf = F
        np.testing.assert_array_equal(
            packed.predict_batch(batch), apart.predict_batch(batch))


# ---- (f) ------------------------------------------------------------------

def _libsvm(path, rows=4 * ROWS):
    rng = np.random.default_rng(5)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = np.sort(rng.choice(np.arange(1, 400), NNZ, replace=False))
            f.write("%d %s\n" % (rng.integers(0, 2), " ".join(
                "%d:%.3f" % (i, rng.random() + 0.5) for i in ids)))
    return path


@pytest.mark.parametrize("place", ["one-device", "factor-sharded"])
@pytest.mark.parametrize("case", CASES + WIDE)
def test_f_the_counters_the_span_arguments_and_state_bytes(
        case, place, mesh, tmp_path):
    from dmlc_tpu import obs
    from dmlc_tpu.obs import trace as obs_trace

    name = "ffm" if case.startswith("adagrad") else "fm"

    def read():
        flat = obs.registry().flat_values()
        return [flat.get('dmlc_fit_%s_total{model="%s"}' % (k, name), 0.0)
                for k in ("steps", "packed_row_steps", "lane_row_steps")]

    model = _learner(case, mesh if place == "factor-sharded" else None)
    spans = []
    obs_trace.add_listener(spans.append)
    try:
        before = read()
        model.fit_uri(_libsvm(str(tmp_path / "rows.libsvm")),
                      batch_size=ROWS, epochs=2)
        steps, packed, lane = (a - b for a, b in zip(read(), before))
    finally:
        obs_trace.remove_listener(spans.append)
    assert steps == 8
    # every step of one device took the packed tree, whose rows are lane
    # rows: both shares 1.0; neither counts a step of a mesh
    assert packed == lane == (steps if place == "one-device" else 0)
    columns = _columns(model.table_layout())
    geometry = GEOMETRY[case]
    if place != "one-device":
        columns, geometry = 0, (0, 0)
    epochs = [e for e in spans if e.get("name") == "epoch"]
    assert epochs
    for e in epochs:
        assert e["args"]["row_columns"] == columns
        assert (e["args"]["row_lanes"],
                e["args"]["ids_per_lane_row"]) == geometry
    assert model.row_columns == columns
    assert model.lane_geometry == geometry
    # the logical columns of a, z, n, wherever they lie
    shards = CHIPS if place == "factor-sharded" else 1
    rule, k, fields = _sizes(case)
    want = {"sgd": 0, "ftrl_adagrad": 4 * F * (k // shards + 2),
            "adagrad": 4 * F * k * len(fields or ()) // shards}[rule]
    assert model.state_bytes() == want
    flat = obs.registry().flat_values()
    assert flat['dmlc_fit_optimizer_state_bytes{model="%s"}' % name] == want


# ---- (g) ------------------------------------------------------------------

def test_g_the_geometry_follows_from_the_rows_columns():
    assert [lane_geometry(c) for c in (17, 35, 44)] == [
        (128, 7), (128, 3), (256, 5)]
    assert lane_geometry(32) == (128, 4) and lane_geometry(128) == (128, 1)
    for columns in range(1, 400):
        lanes, per_row = lane_geometry(columns)
        assert lanes % 128 == 0 and per_row == lanes // columns >= 1

        def spare(n):
            return n - n // columns * columns

        # under a fifth of the row unused, and no shorter row does that
        assert 5 * spare(lanes) < lanes
        assert all(5 * spare(n) >= n for n in range(128, lanes, 128))


def _batch_of(ids, seed=0, pad=5):
    """A csr batch whose entries name exactly ``ids`` (each at least
    once, most several times) and, ``pad`` times, the padding's feature
    0 with value 0."""
    rng = np.random.default_rng(seed)
    ids = np.asarray(ids, np.int32)
    idx = np.concatenate(
        [ids, rng.choice(ids, ROWS * NNZ - pad - ids.size)])
    rng.shuffle(idx)
    idx = np.concatenate([np.zeros(pad, np.int32), idx]).astype(np.int32)
    val = (0.5 + rng.random(ROWS * NNZ)).astype(np.float32)
    val[:pad] = 0.0
    return {
        "label": jnp.asarray(rng.integers(0, 2, ROWS).astype(np.float32)),
        "weight": jnp.ones(ROWS, jnp.float32),
        "indices": jnp.asarray(idx), "values": jnp.asarray(val),
        "offsets": jnp.asarray(np.arange(ROWS + 1, dtype=np.int32) * NNZ)}


@pytest.mark.parametrize("case", WIDE)
def test_g_lane_rows_whole_shared_partly_filled_and_left_alone(case):
    """One batch names every id of one lane row, ONE id in the middle of
    another, two neighbours of a third, the table's last id (F is no
    multiple of p: the last lane row is partly filled) and the padding's
    feature 0. Against the tables apart to the bit; and in the array
    itself the lanes of a named id's neighbours, the lanes no id uses,
    the last lane row's places past the last id and every lane row no
    entry names come back as they were."""
    lanes, p = GEOMETRY[case]
    apart, layout = _apart(case)
    columns = _columns(layout)
    assert F % p, "the last lane row is to be partly filled"
    whole, alone, pair, last = 40, 60, 90, (F - 1) // p
    ids = np.r_[whole * p + np.arange(p), alone * p + p // 2,
                pair * p + np.arange(2), F - 1,
                np.arange(150, 150 + 9 * p, p + 1)]
    named = np.unique(np.r_[0, ids])
    packed = PackedTables.pack(apart, layout)
    before = np.asarray(packed.rows).copy()
    step = _step(case, l2=0.0 if case.startswith("sgd") else None)
    for i in range(3):
        batch = _batch_of(ids, seed=i)
        apart, m_apart = step(apart, batch)
        packed, m_packed = step(packed, batch)
        assert int(m_packed["touched_rows"]) == named.size
        assert np.asarray(m_apart["loss_sum"]) == np.asarray(
            m_packed["loss_sum"])
    for name in apart:
        np.testing.assert_array_equal(
            _bits(packed[name]), _bits(apart[name]), err_msg=name)
    after = np.asarray(packed.rows)
    assert after.shape == before.shape == (-(-F // p), lanes)
    # which lanes hold a named id's words
    touched = np.zeros(after.shape, bool)
    for i in named:
        touched[i // p, (i % p) * columns:(i % p + 1) * columns] = True
    assert touched[whole, :p * columns].all()
    assert touched[alone].sum() == columns and touched[pair].sum() == 2 * columns
    assert touched[last].sum() == columns
    np.testing.assert_array_equal(_bits(after[~touched]),
                                  _bits(before[~touched]))
    # the named ids but feature 0 (its entries carry value 0) did move
    moved = (_bits(after) != _bits(before)) & touched
    assert moved[whole].any() and moved[alone].any() and moved[last].any()
    assert not moved[0].any()
    # the lanes no id uses and the places past the last id hold 0
    assert not after[:, p * columns:].any()
    assert not after[last, (F - last * p) * columns:].any()


# ---- (h) the writer's targets ---------------------------------------------

@pytest.mark.parametrize("past", [(), (1,), (2, 700), (5000,)])
@pytest.mark.parametrize("case", WIDE)
def test_h_every_slot_hands_the_writer_a_target_of_its_own(case, past):
    """The writer is XLA's scatter under ``unique_indices``, so no two
    slots of a chunk may name one row, the slots that write nothing
    included: a run's other slots, the slots past the distinct ids, and
    the slots of ids past the table (``F + 1`` is also what the second
    slot's filler is called), beside lane rows that several ids share."""
    lanes, p = GEOMETRY[case]
    apart, layout = _apart(case)
    packed = PackedTables.pack(apart, layout)
    columns, height = packed.columns, packed.rows.shape[0]
    inside = np.r_[40 * p + np.arange(p), 60 * p + p // 2,
                   90 * p + np.arange(2), F - 1]
    named = np.r_[inside, F + np.asarray(past, np.int64)].astype(np.int32)
    idx = np.random.default_rng(len(past)).permutation(np.tile(named, 3))
    seen = []

    def recorded(array, target, new):
        jax.debug.callback(lambda t: seen.append(np.asarray(t)), target)
        return fm_module._write_rows(array, target, new)

    @jax.jit
    def add_one(packed, idx):
        order, _, _ = fm_module._in_id_order(
            idx, jnp.zeros_like(idx), jnp.ones(idx.shape, jnp.float32), F)
        read = fm_module._take_lane_rows(packed, order)
        return fm_module._put_lane_rows(
            packed, order, read.lanes, read.words + 1.0, recorded)

    before = np.asarray(packed.rows).copy()
    after = np.asarray(add_one(packed, jnp.asarray(idx)))
    jax.effects_barrier()
    (target,) = seen  # one chunk holds every distinct id
    assert np.unique(target).size == target.size == fm_module._UPDATE_CHUNK
    np.testing.assert_array_equal(
        np.sort(target[target < height]), np.unique(inside // p))
    want = before.copy()
    for i in inside:
        want[i // p, (i % p) * columns:(i % p + 1) * columns] += 1.0
    np.testing.assert_array_equal(_bits(after), _bits(want))


def test_h_the_writer_is_a_scatter_not_flagged_sorted():
    """On the chip a row-major array's scatter flagged sorted passes over
    the whole array for every chunk (PERF.md, PR 38): the flag stays
    off, and a target past the array writes nothing."""
    table = jnp.arange(12 * 128, dtype=jnp.float32).reshape(12, 128)
    target = jnp.asarray([3, 12, 7, 40], jnp.int32)
    new = -jnp.ones((4, 128), jnp.float32)
    text = jax.jit(fm_module._write_rows).lower(table, target, new).as_text()
    assert "indices_are_sorted = false" in text
    assert "unique_indices = true" in text
    got = np.asarray(fm_module._write_rows(table, target, new))
    want = np.asarray(table).copy()
    want[[3, 7]] = -1.0
    np.testing.assert_array_equal(got, want)

"""The packed row (``models/fm.py`` ``PackedTables``): where one device
holds whole rows of every per-id table, an FM or FFM learner keeps an
id's weights and optimizer state side by side in ONE array, and a step
reads each touched row once and writes it once. On the suite's CPU
devices, small sizes:

(a) N steps over a packed tree against the same steps over the tables
    apart, from the same seed: every logical table, ``b`` and the losses
    equal to the bit;
(b) padded entries, an id under the L1 threshold, a slot whose entries
    all have value 0, rows no batch names;
(c) the check's five calls (``benchmarks/harness/tables.py``) on the
    learners under both groupings and on a factor-sharded mesh;
(d) the structure the speed rests on, from the lowered step: one scatter
    over the table's rows for each physical array, no 1-D scatter; the
    mesh programs' indexed passes and collectives as they were;
(e) snapshots by logical table, across groupings;
(f) the counter, the span argument, ``state_bytes``.
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


def _learner(case, mesh=None, **more):
    if mesh is not None:
        more["table_sharding"] = "factors"
    if case == "adagrad":
        hyper = dict(num_features=F, field_sizes=FIELD_SIZES, num_factors=2,
                     learning_rate=0.2, l2=1e-3, a_init=1e-4)
        return FFMLearner(mesh=mesh, **dict(hyper, **more))
    hyper = dict(num_features=F, num_factors=K, learning_rate=0.1, l2=0.01,
                 init_scale=0.1)
    if case != "sgd":
        hyper.update(optimizer="ftrl_adagrad", **RULE._asdict())
    return FMLearner(mesh=mesh, **dict(hyper, **more))


def _step(case, mesh=None):
    sharding = "replicated" if mesh is None else "factors"
    if case == "adagrad":
        return make_ffm_train_step(
            mesh, F, FIELD_SIZES, learning_rate=0.2, l2=1e-3,
            table_sharding=sharding)
    return make_fm_train_step(
        mesh, F, learning_rate=0.1, l2=0.01, table_sharding=sharding,
        rule=None if case == "sgd" else RULE)


def _apart(case, seed=3):
    """(the logical tables from the models' own initialisers, the packed
    row's layout)."""
    if case == "adagrad":
        c = 2 * len(FIELD_SIZES)
        return (init_ffm_params(F, 2, len(FIELD_SIZES), 0.5, 1e-4, seed),
                (("v", c), ("a", c)))
    optimizer = "sgd" if case == "sgd" else "ftrl_adagrad"
    names = SGD_TABLES if case == "sgd" else FTRL_TABLES
    return (init_fm_params(F, K, 0.1, seed, optimizer=optimizer),
            tuple((n, K if n in ("v", "a") else 0) for n in names))


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

@pytest.mark.parametrize("case", CASES)
def test_a_packed_steps_equal_the_tables_apart_to_the_bit(case):
    apart, layout = _apart(case)
    packed = PackedTables.pack(apart, layout)
    assert packed.rows.shape == (F, sum(max(w, 1) for _, w in layout))
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
    assert sorted(np.shape(leaf) for leaf in leaves) == [(), (F, 2 * K + 3)]
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert again.layout == layout and again.rows is packed.rows
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

@pytest.mark.parametrize("case", CASES)
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
    if case == "ftrl_adagrad":
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
@pytest.mark.parametrize("case", CASES)
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
    """Blocks that do not divide the table, one that holds it all: the
    same array (the last block starts early and draws some rows again)."""
    want = {}
    for block in (64, 1000, 1 << 20):
        monkeypatch.setattr(fm_module, "_INIT_BLOCK", block)
        for case in CASES:
            model = _learner(case)
            model.init_tables(7)
            got = np.asarray(model.params.rows)
            np.testing.assert_array_equal(
                got, want.setdefault(case, got), err_msg=case)
    for case in CASES:
        apart, layout = jax.jit(lambda s: _apart(case, s)[0])(
            jnp.uint32(7)), _apart(case)[1]
        np.testing.assert_array_equal(
            want[case], np.asarray(PackedTables.pack(apart, layout).rows))


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


@pytest.mark.parametrize("case", CASES)
def test_d_one_scatter_over_the_tables_rows_and_none_one_dimensional(case):
    apart, layout = _apart(case)
    columns = sum(max(w, 1) for _, w in layout)
    step = _step(case)

    def table_passes(params):
        found = _indexed_passes(step, params, _batch(0))
        return [(name, shape) for name, shape in found if shape[0] == F]

    write = "scatter-add" if case == "sgd" else "scatter"
    assert table_passes(PackedTables.pack(apart, layout)) == [
        ("gather", (F, columns)), (write, (F, columns))]
    # the tables apart, as a mesh holds them: a read and a write each
    shapes = sorted((F, w) if w else (F,) for _, w in layout)
    assert table_passes(apart) == (
        [("gather", s) for s in shapes] + [(write, s) for s in shapes])
    # and the lowered text of the packed step agrees: one scatter whose
    # operand has the table's rows, nothing 1-D of the table's height
    text = step.lower(
        PackedTables.pack(apart, layout), _batch(0)).as_text()
    scattered = re.findall(
        r"\}\) : \(tensor<(\d+)x(\d+)xf32>, tensor<[^>]*xi32>, "
        r"tensor<[^>]*xf32>\) -> tensor<\1x\2xf32>", text)
    assert [dims for dims in scattered if int(dims[0]) == F] == [
        (str(F), str(columns))], scattered
    assert "tensor<%dxf32>" % F not in text


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


@pytest.mark.parametrize("case", CASES)
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


@pytest.mark.parametrize("case", CASES)
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
    for case in CASES:
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
@pytest.mark.parametrize("case", CASES)
def test_f_the_counter_the_span_argument_and_state_bytes(
        case, place, mesh, tmp_path):
    from dmlc_tpu import obs
    from dmlc_tpu.obs import trace as obs_trace

    name = "ffm" if case == "adagrad" else "fm"

    def read():
        flat = obs.registry().flat_values()
        return [flat.get('dmlc_fit_%s_total{model="%s"}' % (k, name), 0.0)
                for k in ("steps", "packed_row_steps")]

    model = _learner(case, mesh if place == "factor-sharded" else None)
    spans = []
    obs_trace.add_listener(spans.append)
    try:
        before = read()
        model.fit_uri(_libsvm(str(tmp_path / "rows.libsvm")),
                      batch_size=ROWS, epochs=2)
        steps, packed = (a - b for a, b in zip(read(), before))
    finally:
        obs_trace.remove_listener(spans.append)
    assert steps == 8
    assert packed == (steps if place == "one-device" else 0)
    columns = sum(max(w, 1) for _, w in model.table_layout())
    epochs = [e for e in spans if e.get("name") == "epoch"]
    assert epochs and all(
        e["args"]["row_columns"] == (
            columns if place == "one-device" else 0) for e in epochs)
    assert model.row_columns == (columns if place == "one-device" else 0)
    # the logical columns of a, z, n, wherever they lie
    shards = CHIPS if place == "factor-sharded" else 1
    want = {"sgd": 0, "ftrl_adagrad": 4 * F * (K // shards + 2),
            "adagrad": 4 * F * 2 * len(FIELD_SIZES) // shards}[case]
    assert model.state_bytes() == want
    flat = obs.registry().flat_values()
    assert flat['dmlc_fit_optimizer_state_bytes{model="%s"}' % name] == want

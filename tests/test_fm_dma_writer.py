"""The writer of pure writes (``models/fm.py`` ``_dma_write_rows``): on a
TPU a lane row of 128 lanes goes back by ONE async copy of the row, a
Pallas kernel; everywhere else by XLA's scatter, which stays the portable
form and is what everything here is compared with. On the suite's CPU
devices the kernel runs in Pallas' interpreter, which the tests pass
(``interpret=True``); tables and chunks of a few dozen rows.

(a) the kernel equals ``.at[].set(..., mode="drop")`` to the bit: targets
    sorted and unsorted, slots past the table, no live slot, more live
    rows than semaphores, a height that is no multiple of 8, both dtypes
    a lane row has;
(b) who takes it: the platform the tree lies on and the row's lanes, and
    nothing else (no environment read), in ``row_writer``, in the lowered
    steps and in the learners;
(c) whole steps with the writer forced through the interpreter equal the
    scatter's steps to the bit, SGD, ``ftrl_adagrad`` and memory-adaptive;
(d) the counter and the span argument.

That Mosaic takes the kernel at the cells' sizes and writes in place is
checked where a TPU is certain: ``chip_smoke.py`` ``dma_row_writer``.
"""

import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dmlc_tpu.models import (
    AdaptiveFMLearner,
    FFMLearner,
    FMLearner,
    FtrlAdagrad,
)
from dmlc_tpu.models import fm as fm_module
from dmlc_tpu.models.ffm import make_ffm_train_step
from dmlc_tpu.models.fm import (
    FTRL_TABLES,
    SGD_TABLES,
    PackedTables,
    init_fm_params,
    make_fm_train_step,
    row_writer,
)

F = 2003
ROWS, NNZ = 32, 6
CHUNK = 64  # the batch's 192 entries are three passes of the chunk loops
RULE = FtrlAdagrad(l1=2e-3, lr_beta=0.1, v_learning_rate=0.1,
                   v_lr_beta=0.1, v_l2=1e-3)
FIELD_SIZES = (3, 3, 18, 30, 50, 100, 150, 250, 300, 500, 599)  # end at F


def _bits(array):
    return np.ascontiguousarray(np.asarray(array)).view(np.uint32)


def _scatter(table, target, new):
    return table.at[target].set(new, unique_indices=True, mode="drop")


def _dma(table, target, new):
    return fm_module._write_rows(
        table, target, new, platform="tpu", interpret=True)


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _primitives(step, params, batch):
    return {eqn.primitive.name
            for eqn in _walk(jax.make_jaxpr(step)(params, batch).jaxpr)}


# ---- (a) the kernel -------------------------------------------------------

def _targets(case, height, slots, rng):
    """``s32[slots]``, distinct: the live slots name rows of the table,
    the others the row ``height`` + their own number, as
    ``_put_lane_rows`` hands them over."""
    target = height + np.arange(slots, dtype=np.int32)
    live = {"sorted": 20, "unsorted": 20, "past": 5, "none": 0,
            "nine": 9, "seventeen": 17, "all": slots}[case]
    at = np.sort(rng.choice(slots, live, replace=False))
    rows = rng.choice(height, live, replace=False).astype(np.int32)
    target[at] = rows if case == "unsorted" else np.sort(rows)
    return target


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("height", [40, 37])
@pytest.mark.parametrize(
    "case", ["sorted", "unsorted", "past", "none", "nine", "seventeen", "all"])
def test_a_the_kernel_equals_the_scatter_to_the_bit(case, height, dtype):
    rng = np.random.default_rng(len(case) + height)
    slots = 24
    table = jnp.asarray(
        rng.integers(-2**31, 2**31, (height, 128)).astype(np.int32).view(dtype))
    new = jnp.asarray(
        rng.integers(-2**31, 2**31, (slots, 128)).astype(np.int32).view(dtype))
    target = jnp.asarray(_targets(case, height, slots, rng))
    got = _bits(jax.jit(_dma)(table, target, new))
    np.testing.assert_array_equal(got, _bits(_scatter(table, target, new)))
    # and row by row: a live slot's row is its target's, nothing else moved
    want = _bits(table).copy()
    for j, row in enumerate(np.asarray(target)):
        if row < height:
            want[row] = _bits(new)[j]
    np.testing.assert_array_equal(got, want)


def test_a_the_kernel_writes_in_place_in_a_chunk_loop():
    """As the step calls it: once a chunk inside a ``fori_loop`` that
    carries the array, the array donated."""
    rng = np.random.default_rng(7)
    height, slots, chunks = 90, 16, 4
    table = jnp.asarray(rng.normal(size=(height, 128)).astype(np.float32))
    new = jnp.asarray(
        rng.normal(size=(chunks * slots, 128)).astype(np.float32))
    target = height + np.arange(chunks * slots, dtype=np.int32)
    live = rng.choice(chunks * slots, 50, replace=False)
    target[live] = rng.choice(height, 50, replace=False)
    target = jnp.asarray(target)

    def loop(write, table):
        def put(i, array):
            return write(
                array, jax.lax.dynamic_slice_in_dim(target, i * slots, slots),
                jax.lax.dynamic_slice_in_dim(new, i * slots, slots))

        return jax.lax.fori_loop(0, chunks, put, table)

    want = _bits(loop(_scatter, table))
    got = jax.jit(partial(loop, _dma), donate_argnums=0)(table)
    np.testing.assert_array_equal(_bits(got), want)


# ---- (b) who takes it -----------------------------------------------------

class _Recording(dict):
    """``os.environ`` that lists the names asked of it."""

    def __init__(self, env):
        super().__init__(env)
        self.asked = []

    def __getitem__(self, key):
        self.asked.append(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.asked.append(key)
        return super().get(key, default)

    def __contains__(self, key):
        self.asked.append(key)
        return super().__contains__(key)


def _batch(seed):
    rng = np.random.default_rng(seed)
    idx = np.concatenate([
        rng.integers(1, 120, ROWS * NNZ // 2),
        rng.integers(600, 900, ROWS * NNZ // 4),
        rng.integers(1300, 1600, ROWS * NNZ - 3 * (ROWS * NNZ // 4)),
    ]).astype(np.int32)
    rng.shuffle(idx)
    val = (0.5 + rng.random(ROWS * NNZ)).astype(np.float32)
    idx[:5], val[:5] = 0, 0.0  # padding
    return {
        "label": jnp.asarray(rng.integers(0, 2, ROWS).astype(np.float32)),
        "weight": jnp.ones(ROWS, jnp.float32),
        "indices": jnp.asarray(idx), "values": jnp.asarray(val),
        "offsets": jnp.asarray(np.arange(ROWS + 1, dtype=np.int32) * NNZ)}


def _packed(optimizer, k, seed=3):
    names = SGD_TABLES if optimizer == "sgd" else FTRL_TABLES
    layout = tuple((n, k if n in ("v", "a") else 0) for n in names)
    return PackedTables.pack(
        init_fm_params(F, k, 0.1, seed, optimizer=optimizer), layout)


def _fm_step(optimizer, **kw):
    return make_fm_train_step(
        None, F, learning_rate=0.1, l2=0.01,
        rule=None if optimizer == "sgd" else RULE, **kw)


def test_b_the_platform_and_the_lanes_choose_and_nothing_else(monkeypatch):
    env = _Recording(os.environ)
    monkeypatch.setattr(os, "environ", env)
    assert row_writer("tpu", 128) == "dma"
    assert row_writer("cpu", 128) == "scatter"
    assert row_writer("gpu", 128) == "scatter"
    assert row_writer(None, 128) == "scatter"
    assert row_writer("tpu", 256) == "scatter"
    assert env.asked == []
    # in the steps as they are traced: the kernel where the two facts say
    # so, XLA's scatter everywhere else
    batch = _batch(0)
    for optimizer, k in (("sgd", 16), ("ftrl_adagrad", 16), ("sgd", 4)):
        params = _packed(optimizer, k)
        assert params.rows.shape[1] == 128
        on_tpu = _primitives(
            _fm_step(optimizer, platform="tpu", interpret=True), params, batch)
        assert "pallas_call" in on_tpu and "scatter" not in on_tpu
        for platform in ("cpu", None):
            here = _primitives(
                _fm_step(optimizer, platform=platform), params, batch)
            assert "scatter" in here and "pallas_call" not in here
    # of the package's knobs a step's trace asks for the two its jit
    # wrapper has always asked for (the telemetry's), no other
    assert {k for k in env.asked if k.startswith("DMLC")} <= {
        "DMLC_TPU_DEVICE_TELEMETRY", "DMLC_TPU_METRICS"}


def test_b_256_lanes_and_a_mesh_keep_what_they_had():
    """The FFM's row of 44 columns lies in 256 lanes: XLA's scatter on
    every platform. Tables divided over a mesh lie apart and never reach
    the writer."""
    wide = FFMLearner(num_features=F, field_sizes=FIELD_SIZES, num_factors=2,
                      learning_rate=0.2)
    wide.init_tables(3)
    assert wide.params.rows.shape[1] == 256
    step = make_ffm_train_step(
        None, F, FIELD_SIZES, learning_rate=0.2, platform="tpu")
    found = _primitives(step, wide.params, _batch(0))
    assert "scatter" in found and "pallas_call" not in found

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    apart = FMLearner(mesh=mesh, num_features=F, num_factors=4,
                      table_sharding="factors")
    apart.init_tables(3)
    step = make_fm_train_step(
        mesh, F, table_sharding="factors", platform="tpu")
    batch = {k: jnp.concatenate([v, v]) for k, v in _batch(0).items()}
    assert "pallas_call" not in _primitives(step, apart.params, batch)
    apart._ensure(F)
    assert apart.row_writer == "none" and apart._step_platform == "cpu"


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_b_a_learner_hands_on_the_platform_its_tree_lies_on(
        platform, monkeypatch):
    """A learner tells the step's builder the platform of the devices its
    params lie on, and reports the writer from what it told: as ``tpu``,
    a 128-lane tree takes the kernel and 256 lanes the scatter."""
    models = {
        "fm": FMLearner(num_features=F, num_factors=16),
        "difacto": FMLearner(num_features=F, num_factors=16,
                             optimizer="ftrl_adagrad"),
        "ffm": FFMLearner(num_features=F, field_sizes=FIELD_SIZES,
                          num_factors=2),
        "adaptive": _adaptive(),
    }
    for model in models.values():
        assert model._params_platform() is None  # no tree yet
        model.init_tables(1)
        assert model._params_platform() == "cpu"
    if platform == "tpu":
        monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "tpu")
    told = []

    def record(*args, **kw):
        told.append(kw["platform"])
        return lambda params, batch: (params, {})

    monkeypatch.setattr(fm_module, "make_fm_train_step", record)
    monkeypatch.setattr("dmlc_tpu.models.ffm.make_ffm_train_step", record)
    for model in models.values():
        model._ensure(F)
    assert told == [platform] * 4
    # reported from what the builder was told, and from nothing else
    monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "other")
    taken = "dma" if platform == "tpu" else "scatter"
    assert {k: m.row_writer for k, m in models.items()} == {
        "fm": taken, "difacto": taken, "ffm": "scatter", "adaptive": taken}
    # a step built without the learner's ``_ensure`` was told nothing
    bare = FMLearner(num_features=F, num_factors=16)
    bare.init_tables(1)
    bare._step = bare._make_step(F)
    assert told[-1] is None and bare.row_writer == "scatter"


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_b_only_a_learner_that_will_trace_the_kernel_imports_pallas(
        platform, monkeypatch):
    """``init_tables`` imports Pallas (while the device writes the tables)
    where the tree it made will take the kernel, nowhere else: a process
    that keeps the scatter imports nothing new."""
    started = []
    monkeypatch.setattr(fm_module, "import_pallas",
                        lambda: started.append(1))
    if platform == "tpu":
        monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "tpu")
    for model in (FMLearner(num_features=F, num_factors=16), _adaptive()):
        model.init_tables(1)
    assert len(started) == (2 if platform == "tpu" else 0)
    wide = FFMLearner(num_features=F, field_sizes=FIELD_SIZES, num_factors=2)
    wide.init_tables(1)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    apart = FMLearner(mesh=mesh, num_features=F, num_factors=4,
                      table_sharding="factors")
    apart.init_tables(1)
    assert len(started) == (2 if platform == "tpu" else 0)


# ---- (c) whole steps ------------------------------------------------------

@pytest.mark.parametrize("case", ["sgd-16", "ftrl_adagrad-16", "sgd-4"])
def test_c_steps_through_the_kernel_equal_the_scatters_to_the_bit(
        case, monkeypatch):
    monkeypatch.setattr(fm_module, "_UPDATE_CHUNK", CHUNK)
    optimizer, k = case.split("-")
    ours = theirs = _packed(optimizer, int(k))
    kernel = _fm_step(optimizer, platform="tpu", interpret=True)
    scatter = _fm_step(optimizer)
    for i in range(3):
        ours, got = kernel(ours, _batch(i))
        theirs, want = scatter(theirs, _batch(i))
        np.testing.assert_array_equal(_bits(ours.rows), _bits(theirs.rows))
        assert _bits(ours["b"]) == _bits(theirs["b"])
        for name in want:
            assert _bits(got[name]) == _bits(want[name]), name


def _adaptive():
    return AdaptiveFMLearner(
        num_features=F, num_factors=4, learning_rate=0.1, init_scale=0.1,
        optimizer="ftrl_adagrad", v_threshold=1, l1_shrk=True,
        factor_capacity=256, count_rows=1 << 20, **RULE._asdict())


def test_c_memory_adaptive_steps_through_the_kernel(monkeypatch):
    """The base rows (``s32[R, 128]``) go through the kernel, the factor
    rows (8 lanes here) through the scatter, in one step."""
    monkeypatch.setattr(fm_module, "_UPDATE_CHUNK", CHUNK)
    model = _adaptive()
    model.init_tables(5)
    ours = theirs = model.params
    kernel = _fm_step("ftrl_adagrad", platform="tpu", interpret=True)
    scatter = _fm_step("ftrl_adagrad")
    found = _primitives(kernel, ours, _batch(0))
    assert {"pallas_call", "scatter"} <= found
    for i in range(3):
        ours, _ = kernel(ours, _batch(i))
        theirs, _ = scatter(theirs, _batch(i))
        for got, want in zip(jax.tree_util.tree_leaves(ours),
                             jax.tree_util.tree_leaves(theirs)):
            np.testing.assert_array_equal(_bits(got), _bits(want))
    assert int(ours.scalars["active_ids"]) > 0  # rows were handed out


# ---- (d) the counter and the span argument --------------------------------

def _libsvm(path, rows=4 * ROWS):
    rng = np.random.default_rng(5)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = np.sort(rng.choice(np.arange(1, 400), NNZ, replace=False))
            f.write("%d %s\n" % (rng.integers(0, 2), " ".join(
                "%d:%.3f" % (i, rng.random() + 0.5) for i in ids)))
    return path


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_d_the_counter_and_the_span_argument(platform, tmp_path, monkeypatch):
    from dmlc_tpu import obs
    from dmlc_tpu.obs import trace as obs_trace

    def read():
        flat = obs.registry().flat_values()
        return [flat.get('dmlc_fit_%s_total{model="fm"}' % k, 0.0)
                for k in ("steps", "dma_row_write_steps")]

    path = _libsvm(str(tmp_path / "rows.libsvm"))
    hyper = dict(num_features=F, num_factors=16, learning_rate=0.1)
    plain = FMLearner(**hyper)
    want = plain.fit_uri(path, batch_size=ROWS, epochs=2)
    if platform == "tpu":
        # the tree reported on a TPU, the kernel in the interpreter
        monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "tpu")
        monkeypatch.setattr(
            fm_module, "make_fm_train_step",
            partial(make_fm_train_step, interpret=True))
    model = FMLearner(**hyper)
    spans = []
    obs_trace.add_listener(spans.append)
    try:
        before = read()
        history = model.fit_uri(path, batch_size=ROWS, epochs=2)
        steps, dma = (a - b for a, b in zip(read(), before))
    finally:
        obs_trace.remove_listener(spans.append)
    assert steps == 8
    assert dma == (steps if platform == "tpu" else 0)
    epochs = [e for e in spans if e.get("name") == "epoch"]
    assert epochs
    for e in epochs:
        assert e["args"]["row_writer"] == (
            "dma" if platform == "tpu" else "scatter")
    assert history == want
    np.testing.assert_array_equal(
        _bits(model.params.rows), _bits(plain.params.rows))

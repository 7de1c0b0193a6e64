"""An id's entries summed along its sorted run (``models/fm.py``
``_sum_by_slot``): on a TPU a segmented inclusive scan, a Pallas kernel
(``_scan_runs``) that also lays each run's total at its slot; everywhere
else XLA's ``segment_sum`` by sorted slot, which stays the portable form. On the suite's CPU devices the kernel runs
in Pallas' interpreter, which the tests pass (``interpret=True``).

(a) the scan against a float64 sum, within float32 rounding of a tree of
    adds: runs of one entry, one id for the whole batch, runs across
    blocks (the carry), padded entries at the front, the cells' power laws
    at 16 and 17 columns;
(b) who takes it: the platform and the VMEM the kernel's output takes,
    in ``id_summer`` (both sides of ``_RUN_SUMS_VMEM``), in the lowered
    steps, and in the learners, which report what their step's trace
    recorded;
(c) whole steps through the scan and the DMA writer equal the portable
    steps within float32 rounding: the FM's SGD step and a test-size DLRM
    step;
(d) the counter and the span argument, counted from the step's record:
    the steps that took the scan, none where the batch is past the
    budget.

That Mosaic takes the kernel at the cells' sizes is checked where a TPU is
certain: ``chip_smoke.py`` ``run_sums``.
"""

import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.models import AdaptiveFMLearner, FFMLearner, FMLearner
from dmlc_tpu.models import fm as fm_module
from dmlc_tpu.models.dlrm import (
    DLRMLearner,
    make_dlrm_train_step,
    table_lows,
)
from dmlc_tpu.models.fm import (
    SGD_TABLES,
    PackedTables,
    _slots_in_id_order,
    _sum_by_slot,
    id_summer,
    init_fm_params,
    make_fm_train_step,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
F = 2003
ROWS, NNZ = 32, 6
EPS = np.finfo(np.float32).eps


def _config(name):
    """The benchmark configuration's rehearse size and its generator."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec

        path = os.path.join(BENCH, "configs", name)
        with open(path + ".json") as f:
            cfg = json.load(f)
        cfg.update(cfg["rehearse"])
        return cfg, spec.load_module(path + ".py")
    finally:
        sys.path.remove(BENCH)


def _power_law_ids(name, rows):
    """The table ids of ``rows`` rows of a cell's generator, at the
    configuration's rehearse size."""
    cfg, config = _config(name)
    sys.path.insert(0, BENCH)
    try:
        ids = config.rows(dict(cfg, rows=rows), 2147483659)["ids"]
    finally:
        sys.path.remove(BENCH)
    return ids[:, int(cfg.get("dense_features", 0)):].ravel()


def _sorted_ids(case, rng):
    """(sorted feature ids, how many of the first are padding)."""
    if case == "runs_of_one":
        return np.sort(rng.choice(50000, 3000, replace=False)), 0
    if case == "one_id":
        return np.full(5000, 17), 0
    if case in ("across_blocks", "many_blocks"):
        lengths = rng.integers(1, 5000, 8)
        lengths[3] = 2 * 2048 + 5  # a run over three blocks at least
        return np.repeat(np.arange(1, 9) * 11, lengths), 0
    if case == "padded_front":
        ids = np.sort(rng.integers(1, 400, 2500))
        return np.concatenate([np.zeros(300, ids.dtype), ids]), 300
    if case == "criteo_power_law":
        return np.sort(_power_law_ids("criteo-dlrm", 256)), 0
    assert case == "kdd12_power_law"
    return np.sort(_power_law_ids("kdd12-fm", 512)), 0


CASES = [("runs_of_one", 16), ("one_id", 16), ("across_blocks", 16),
         ("many_blocks", 17), ("padded_front", 17),
         ("criteo_power_law", 16), ("kdd12_power_law", 17)]


@pytest.mark.parametrize("case,columns", CASES)
def test_a_the_scan_sums_each_run_as_float64_does(case, columns, monkeypatch):
    if case == "many_blocks":
        # a carry at every 128 entries: runs over dozens of blocks
        monkeypatch.setattr(fm_module, "_RUN_BLOCK", 128)
    block = fm_module._RUN_BLOCK
    rng = np.random.default_rng(len(case) + columns)
    ids, padding = _sorted_ids(case, rng)
    n = ids.size
    upd = rng.normal(size=(n, columns)).astype(np.float32)
    upd[:padding] = 0.0  # padding names feature 0 with value 0
    order = _slots_in_id_order(jnp.asarray(ids, jnp.int32), 1 << 20)
    slot = np.asarray(order.slot)
    distinct = int(order.distinct)
    want = np.zeros((order.ids.shape[0], columns))
    np.add.at(want, slot, upd.astype(np.float64))
    size = np.zeros(order.ids.shape[0])
    np.add.at(size, slot, np.abs(upd).astype(np.float64).sum(axis=1))

    got = np.asarray(jax.jit(partial(
        _sum_by_slot, platform="tpu", interpret=True))(jnp.asarray(upd), order))
    portable = np.asarray(jax.jit(_sum_by_slot)(jnp.asarray(upd), order))
    assert got.shape == want.shape == portable.shape
    # a tree of adds inside a block, then one add a block the run crossed
    depth = np.log2(block) + -(-n // block) + 2
    bound = depth * EPS * size[:, None]
    assert np.all(np.abs(got - want) <= bound), case
    assert np.all(np.abs(portable - want) <= n * EPS * size[:, None])
    # the slots past the distinct ids hold 0, as the portable form's do
    assert not got[distinct:].any() and not portable[distinct:].any()
    if padding:
        assert not got[0].any()
    if case == "one_id":
        assert distinct == 1


# ---- (b) who takes it -----------------------------------------------------

def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _kernels(step, params, batch):
    """The names of the Pallas kernels in the step's trace."""
    step = getattr(step, "__wrapped__", step)
    return sorted(
        eqn.params["name"] for eqn in _walk(jax.make_jaxpr(step)(params, batch).jaxpr)
        if eqn.primitive.name == "pallas_call")


def _batch(seed):
    rng = np.random.default_rng(seed)
    idx = np.concatenate([
        rng.integers(1, 8, ROWS * NNZ // 2),  # long runs
        rng.integers(600, 900, ROWS * NNZ - ROWS * NNZ // 2),
    ]).astype(np.int32)
    rng.shuffle(idx)
    val = (0.5 + rng.random(ROWS * NNZ)).astype(np.float32)
    idx[:5], val[:5] = 0, 0.0  # padding
    return {
        "label": jnp.asarray(rng.integers(0, 2, ROWS).astype(np.float32)),
        "weight": jnp.ones(ROWS, jnp.float32),
        "indices": jnp.asarray(idx), "values": jnp.asarray(val),
        "offsets": jnp.asarray(np.arange(ROWS + 1, dtype=np.int32) * NNZ)}


def _packed(k=16, seed=3):
    layout = tuple((n, k if n == "v" else 0) for n in SGD_TABLES)
    return PackedTables.pack(init_fm_params(F, k, 0.1, seed), layout)


def test_b_the_platform_chooses_and_nothing_else(monkeypatch):
    asked = []
    environ = dict(os.environ)

    class Recording(dict):
        def get(self, key, default=None):
            asked.append(key)
            return super().get(key, default)

        def __getitem__(self, key):
            asked.append(key)
            return super().__getitem__(key)

    monkeypatch.setattr(os, "environ", Recording(environ))
    for entries, columns in ((212992, 16), (90112, 17)):  # the two cells'
        assert id_summer("tpu", entries, columns) == "scan"
        assert id_summer("cpu", entries, columns) == "segment_sum"
        assert id_summer("gpu", entries, columns) == "segment_sum"
        assert id_summer(None, entries, columns) == "segment_sum"
    assert asked == []
    # the budget's two sides: at 16 columns 522,112 entries take the scan
    # and one lane tile more does not; at 17 (24 sublanes) 347,264
    budget = fm_module._RUN_SUMS_VMEM
    for last, columns in ((522112, 16), (347264, 17)):
        slots = -(-last // 2048) * 2048
        assert fm_module._run_sums_vmem(last, columns, slots) <= budget
        assert id_summer("tpu", last, columns, slots) == "scan"
        assert fm_module._run_sums_vmem(last + 128, columns) > budget
        assert id_summer("tpu", last + 128, columns) == "segment_sum"
    params, batch = _packed(), _batch(0)
    on_tpu = make_fm_train_step(None, F, platform="tpu", interpret=True)
    assert _kernels(on_tpu, params, batch) == ["run_sums", "write_rows"]
    for platform in ("cpu", None):
        here = make_fm_train_step(None, F, platform=platform)
        assert _kernels(here, params, batch) == []
    # a batch past the budget keeps segment_sum on a TPU
    monkeypatch.setattr(fm_module, "_RUN_SUMS_VMEM", fm_module._run_sums_vmem(
        ROWS * NNZ, 17, 2048) - 1)
    past = make_fm_train_step(None, F, platform="tpu", interpret=True)
    assert _kernels(past, params, batch) == ["write_rows"]
    monkeypatch.setattr(fm_module, "_RUN_SUMS_VMEM", budget)
    # a rule sums by segment_sum on a TPU too (its writer is the kernel)
    rule = make_fm_train_step(
        None, F, platform="tpu", interpret=True,
        rule=fm_module.FtrlAdagrad(l1=1e-3, lr_beta=0.1, v_learning_rate=0.1,
                                   v_lr_beta=0.1, v_l2=1e-3))
    stateful = FMLearner(num_features=F, num_factors=16,
                         optimizer="ftrl_adagrad")
    stateful.init_tables(1)
    assert _kernels(rule, stateful.params, batch) == ["write_rows"]


def _dlrm_batch(sizes, seed=0):
    """``ROWS`` rows of 13 dense values and one id of every table."""
    rng = np.random.default_rng(seed)
    lows = table_lows(13, sizes)
    ids = np.concatenate([
        np.tile(np.arange(1, 14), (ROWS, 1)),
        lows + rng.integers(0, sizes, (ROWS, len(sizes)))], axis=1)
    values = np.concatenate([rng.random((ROWS, 13)),
                             np.ones((ROWS, len(sizes)))], axis=1)
    return {
        "label": jnp.asarray(rng.integers(0, 2, ROWS).astype(np.float32)),
        "weight": jnp.ones(ROWS, jnp.float32),
        "indices": jnp.asarray(ids.ravel(), jnp.int32),
        "values": jnp.asarray(values.ravel(), jnp.float32),
        "offsets": jnp.arange(ROWS + 1, dtype=jnp.int32) * ids.shape[1]}


def test_b_the_learners_report_what_their_step_sums_by(monkeypatch):
    """Plain SGD over a packed tree (the FM, the DLRM) takes the scan on a
    TPU; a rule (difacto's, the FFM's, the memory-adaptive FM's) and
    tables divided over a mesh keep ``segment_sum``. A learner reports
    what its step's traces recorded, under the batch's entry count, and
    forgets it with the step."""
    sizes = (3, 3, 18, 30, 50, 100, 150, 250, 300, 500, 599)
    models = {
        "fm": FMLearner(num_features=F, num_factors=16),
        "difacto": FMLearner(num_features=F, num_factors=16,
                             optimizer="ftrl_adagrad"),
        "ffm": FFMLearner(num_features=F, field_sizes=sizes, num_factors=2),
        "adaptive": AdaptiveFMLearner(
            num_features=F, num_factors=4, optimizer="ftrl_adagrad",
            factor_capacity=256, count_rows=1 << 20),
        "dlrm": DLRMLearner(
            num_features=1 + 13 + sum(sizes), num_factors=8, field_sizes=sizes,
            mlp_bot=(13, 16, 8), mlp_top=(8 + 11 * 12 // 2, 8, 1)),
        "mesh": FMLearner(mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]), ("dp",)), num_features=F,
            num_factors=4, table_sharding="factors"),
    }
    monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "tpu")
    recorded = {}
    for name, model in models.items():
        model.init_tables(1)
        model._ensure(model.param.num_features)
        assert model.id_sum == "segment_sum" and model._id_sums == {}
        batch = _dlrm_batch(sizes) if name == "dlrm" else _batch(0)
        if name == "mesh":
            # a section of the batch for each chip, offsets of its own
            batch = dict(batch, offsets=jnp.concatenate(
                [batch["offsets"][:ROWS // 2 + 1]] * 2))
        # a trace is all the record needs (the kernels are not run)
        jax.eval_shape(lambda p, b: model._step(p, b), model.params, batch)
        recorded[name] = (model.id_sum, dict(model._id_sums))
    dlrm_entries = ROWS * (13 + len(sizes))
    assert recorded == {
        "fm": ("scan", {ROWS * NNZ: "scan"}),
        "difacto": ("segment_sum", {}), "ffm": ("segment_sum", {}),
        "adaptive": ("segment_sum", {}),
        "dlrm": ("scan", {dlrm_entries: "scan"}),
        "mesh": ("segment_sum", {})}
    # a new step starts a new record
    model = models["fm"]
    model._step = None
    model._ensure(F)
    assert model.id_sum == "segment_sum" and model._id_sums == {}


# ---- (c) whole steps ------------------------------------------------------

def _close(ours, theirs, before):
    """Within float32 rounding of a reordered sum: the new values differ
    by a ten-thousandth of the update at most, or by the last bits of the
    parameter the sum met."""
    ours, theirs, before = (np.asarray(a, np.float64)
                            for a in (ours, theirs, before))
    moved = np.max(np.abs(theirs - before))
    assert moved > 0
    ulp = np.spacing(np.float32(np.max(np.abs(before))))
    assert np.max(np.abs(ours - theirs)) <= 1e-4 * moved + 2 * ulp


@pytest.mark.parametrize("chunk", [64, 2048])
def test_c_the_fm_step_through_the_scan(chunk, monkeypatch):
    monkeypatch.setattr(fm_module, "_UPDATE_CHUNK", chunk)
    start = _packed()
    ours = theirs = start
    scan = make_fm_train_step(None, F, learning_rate=0.1, l2=0.01,
                              platform="tpu", interpret=True)
    portable = make_fm_train_step(None, F, learning_rate=0.1, l2=0.01)
    for i in range(3):
        ours, got = scan(ours, _batch(i))
        theirs, want = portable(theirs, _batch(i))
        _close(ours.rows, theirs.rows, start.rows)
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6)


def test_c_a_test_size_dlrm_step_through_the_scan():
    """The ``criteo-dlrm`` configuration's rehearse tables and generator,
    the published widths cut for the test; two steps of 256 rows."""
    cfg, config = _config("criteo-dlrm")
    fields = len(cfg["field_sizes"])
    model = DLRMLearner(
        learning_rate=cfg["learning_rate"], num_factors=8,
        num_features=cfg["num_features"], dense_features=13,
        field_sizes=cfg["field_sizes"], mlp_bot=(13, 32, 16, 8),
        mlp_top=(8 + fields * (fields + 1) // 2, 24, 8, 1))
    model.init_tables(4)
    sys.path.insert(0, BENCH)
    try:
        data = config.rows(dict(cfg, rows=512), 2147483659)
    finally:
        sys.path.remove(BENCH)
    steps = [make_dlrm_train_step(
        cfg["num_features"], 13, cfg["field_sizes"], cfg["learning_rate"],
        **how) for how in ({}, {"platform": "tpu", "interpret": True})]
    width = data["ids"].shape[1]
    trees = [model.params, model.params]
    for at in range(2):
        part = slice(at * 256, (at + 1) * 256)
        arrays = {
            "label": jnp.asarray(data["label"][part], jnp.float32),
            "weight": jnp.ones((256,), jnp.float32),
            "indices": jnp.asarray(data["ids"][part].ravel(), jnp.int32),
            "values": jnp.asarray(data["values"][part].ravel(), jnp.float32),
            "offsets": jnp.arange(257, dtype=jnp.int32) * width}
        if at == 0:
            assert _kernels(steps[1], trees[1], arrays) == [
                "run_sums", "write_rows"]
        trees = [step(tree, arrays)[0] for step, tree in zip(steps, trees)]
    portable, scanned = trees
    _close(scanned.rows, portable.rows, model.params.rows)
    for k in portable.scalars:
        # the dense net met the table's rows of the first step only
        np.testing.assert_allclose(
            scanned.scalars[k], portable.scalars[k], rtol=1e-5, atol=1e-7)


# ---- (d) the counter and the span argument --------------------------------

def _libsvm(path, rows=4 * ROWS):
    rng = np.random.default_rng(5)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = np.sort(rng.choice(np.arange(1, 60), NNZ, replace=False))
            f.write("%d %s\n" % (rng.integers(0, 2), " ".join(
                "%d:%.3f" % (i, rng.random() + 0.5) for i in ids)))
    return path


@pytest.mark.parametrize("platform", ["cpu", "tpu", "tpu_past_budget"])
def test_d_the_counter_and_the_span_argument(platform, tmp_path, monkeypatch):
    from dmlc_tpu import obs
    from dmlc_tpu.obs import trace as obs_trace

    def read():
        flat = obs.registry().flat_values()
        return [flat.get('dmlc_fit_%s_total{model="fm"}' % k, 0.0)
                for k in ("steps", "run_sum_steps")]

    path = _libsvm(str(tmp_path / "rows.libsvm"))
    hyper = dict(num_features=F, num_factors=16, learning_rate=0.1)
    plain = FMLearner(**hyper)
    want = plain.fit_uri(path, batch_size=ROWS, epochs=2)
    scan = platform == "tpu"
    if platform != "cpu":
        # the tree reported on a TPU, the kernels in the interpreter
        monkeypatch.setattr(FMLearner, "_params_platform", lambda _: "tpu")
        monkeypatch.setattr(
            fm_module, "make_fm_train_step",
            partial(make_fm_train_step, interpret=True))
    if platform == "tpu_past_budget":
        # every batch's sums past the kernel's VMEM: segment_sum
        monkeypatch.setattr(fm_module, "_RUN_SUMS_VMEM", 0)
    model = FMLearner(**hyper)
    spans = []
    obs_trace.add_listener(spans.append)
    try:
        before = read()
        history = model.fit_uri(path, batch_size=ROWS, epochs=2)
        steps, scanned = (a - b for a, b in zip(read(), before))
    finally:
        obs_trace.remove_listener(spans.append)
    assert steps == 8
    assert scanned == (steps if scan else 0)
    epochs = [e for e in spans if e.get("name") == "epoch"]
    assert len(epochs) == 2
    # the first pass's span opens before the step's first trace
    assert epochs[0]["args"]["id_sum"] == "segment_sum"
    assert epochs[1]["args"]["id_sum"] == ("scan" if scan else "segment_sum")
    assert model.id_sum == epochs[1]["args"]["id_sum"]
    np.testing.assert_allclose(history, want, rtol=1e-6)
    if platform == "cpu":
        assert history == want
    start = FMLearner(**hyper)
    start.init_tables(0)
    _close(model.params.rows, plain.params.rows, start.params.rows)

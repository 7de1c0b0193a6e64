"""difacto's memory-adaptive FM (``AdaptiveFMLearner``: ``V_threshold``,
``l1_shrk``; a base row for every id, a slot table of factor rows): on the
suite's CPU devices, at the ``rehearse`` size of the
``kdd12-fm-k128-adaptive`` configuration (F=100,001, K=16, batches of
1024, 4096 factor rows), against that configuration's float64 numpy
reference, which imports nothing of ``dmlc_tpu.models``.
"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dmlc_tpu import obs, resilience
from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import (
    AdaptiveFMLearner,
    AdaptiveTables,
    FMLearner,
    FtrlAdagrad,
    make_fm_train_step,
)
from dmlc_tpu.models import fm as fm_module
from dmlc_tpu.models.fm import ADAPTIVE_TABLES, init_fm_params
from dmlc_tpu.resilience import Preempted, preempt
from dmlc_tpu.utils.logging import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "kdd12-fm-k128-adaptive")
CELL = "kdd12-fm-k128-adaptive.libsvm"
STEPS = 6
SCALARS = ("b", "active_ids", "refused", "counted_rows")
HYPER = ("objective", "learning_rate", "l2", "num_factors", "num_features",
         "init_scale", "optimizer", "v_threshold", "l1_shrk",
         "factor_capacity", "count_rows") + FtrlAdagrad._fields


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own generator, readers and reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec, textgen, timeline, window, xplane

        yield types.SimpleNamespace(
            spec=spec, textgen=textgen, timeline=timeline, xplane=xplane,
            window=window, config=spec.load_module(CONFIG + ".py"))
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG + ".json") as f:
        out = json.load(f)
    out.update(out["rehearse"])
    out["rows"] = STEPS * out["batch_rows_per_chip"]
    out["count_rows"] = out["rows"]
    return out


@pytest.fixture(scope="module")
def data(harness, cfg, tmp_path_factory):
    """The configuration's rows, as arrays and as one LIBSVM file. The
    last five fields are folded onto a few hundred ids, so that ids cross
    the threshold in every step and sit on both sides of the L1 limit."""
    rows = harness.config.rows(cfg, 2147483659)
    rows["ids"][:, 6:] = 40000 + rows["ids"][:, 6:] % 300
    path = str(tmp_path_factory.mktemp("adaptive") / "rows.libsvm")
    harness.textgen.write_libsvm(
        path, rows["label"], rows["ids"], rows["value_text"],
        rows["pool_index"])
    return dict(rows, path=path)


def _learner(cfg, mesh=None, **over):
    hyper = {k: cfg[k] for k in HYPER}
    hyper.update(over)
    return AdaptiveFMLearner(mesh=mesh, **hyper)


def _feed(cfg, path):
    return DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=cfg["batch_rows_per_chip"], layout="csr",
                  num_features=cfg["num_features"]))


def _steps(model, cfg, path, steps=None):
    """Batches of the file through ``train_step``; each step's mean loss."""
    feed = _feed(cfg, path)
    losses = []
    for arrays in feed:
        model.ensure_step(feed.spec)
        m = model.train_step(
            {k: v for k, v in arrays.items() if k != "num_rows"})
        losses.append(float(m["loss_sum"]) / float(m["weight_sum"]))
        if len(losses) == steps:
            break
    feed.close()
    return losses


def _logical(model, ids):
    """What the check reads: every logical table at ``ids`` and the
    scalars, float64."""
    at = jnp.asarray(ids, jnp.int32)
    out = {k: np.asarray(model.table_rows(k, at), np.float64)
           for k in model.table_names()}
    out.update({k: np.float64(v) for k, v in model.scalars().items()})
    return out


def _batches(cfg, data, steps=STEPS):
    batch = cfg["batch_rows_per_chip"]
    ids = data["ids"][:steps * batch]
    touched = np.unique(ids)
    compact = np.searchsorted(touched, ids)
    return touched, [
        {"label": data["label"][i * batch:(i + 1) * batch],
         "ids": compact[i * batch:(i + 1) * batch],
         "values": np.ones((batch, ids.shape[1]), np.float32)}
        for i in range(steps)]


@pytest.fixture(scope="module", params=[True, False],
                ids=["l1_shrk", "no_shrk"])
def trained(request, harness, cfg, data):
    """``STEPS`` batches through the learner and through the float64
    reference, from the same start, read through the five calls."""
    # an L1 limit that some ids seen more than ten times stay under
    cfg = dict(cfg, l1_shrk=request.param, l1=0.008)
    model = _learner(cfg)
    model.init_tables(11)
    touched, batches = _batches(cfg, data)
    before = _logical(model, touched)
    prints = {k: np.asarray(model.table_fingerprints(k))
              for k in model.table_names()}
    losses = _steps(model, cfg, data["path"])
    ref_losses, ref = harness.config.reference_steps(cfg, before, batches)
    return types.SimpleNamespace(
        cfg=cfg, model=model, touched=touched, before=before,
        after=_logical(model, touched), prints=prints, losses=losses,
        ref=ref, ref_losses=ref_losses, batches=batches)


class TestAgainstTheReference:
    """(a)"""

    def test_a_each_steps_loss(self, trained):
        assert len(trained.losses) == STEPS
        np.testing.assert_allclose(
            trained.losses, trained.ref_losses, rtol=2e-6)

    @pytest.mark.parametrize("key", ADAPTIVE_TABLES + SCALARS)
    def test_a_every_logical_table_and_scalar(self, trained, key):
        """In units of the table's largest change, as the check counts;
        the whole numbers (``cnt``, ``has_v``, the counts) exactly."""
        ref, got = trained.ref[key], trained.after[key]
        if key in ("cnt", "has_v") + SCALARS[1:]:
            np.testing.assert_array_equal(got, ref)
            return
        moved = np.max(np.abs(ref - trained.before[key]))
        assert moved > 0
        assert np.max(np.abs(got - ref)) / moved < 2e-5

    def test_a_ids_were_activated_in_several_steps(self, trained):
        has = trained.ref["has_v"].astype(bool)
        assert 100 < has.sum() == trained.after["active_ids"]
        assert not trained.before["has_v"].any()
        # their factors and accumulators moved after they were taken
        assert np.any(trained.after["a"][has] > 0)
        assert np.all(trained.after["a"][~has] == 0)
        # ... and an id without factors still answers with its v0
        np.testing.assert_array_equal(
            trained.after["v"][~has], trained.before["v"][~has])

    def test_a_l1_shrk_decides_who_is_taken(self, trained):
        """Ids counted past the threshold whose ``w`` is 0: without
        factors under ``l1_shrk``, with them without it."""
        after = trained.after
        earned = (after["cnt"] > trained.cfg["v_threshold"])
        still = earned & (after["w"] == 0)
        assert still.sum() > 5
        if trained.cfg["l1_shrk"]:
            assert not after["has_v"][still].any()
        else:
            assert after["has_v"][earned].all()

    @pytest.mark.parametrize("key", ADAPTIVE_TABLES)
    def test_a_no_other_row_changed(self, trained, key):
        now = np.asarray(trained.model.table_fingerprints(key))
        assert now.shape == (trained.cfg["num_features"],)
        changed = np.flatnonzero(now != trained.prints[key])
        assert np.isin(changed, trained.touched).all()
        if key != "has_v":
            assert len(changed) > 0

    def test_a_predict_is_the_masked_forward(self, trained, cfg, data):
        feed = _feed(cfg, data["path"])
        arrays = next(iter(feed))
        got = trained.model.predict_batch(
            {k: v for k, v in arrays.items() if k != "num_rows"})
        feed.close()
        a, batch = trained.after, trained.batches[0]
        u = a["has_v"].astype(bool)
        if trained.cfg["l1_shrk"]:
            u &= a["w"] != 0
        xv = (u[:, None] * a["v"])[batch["ids"]]
        s = xv.sum(axis=1)
        want = a["b"] + a["w"][batch["ids"]].sum(axis=1) + 0.5 * (
            (s * s).sum(axis=1) - (xv * xv).sum(axis=(1, 2)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


class TestCapacity:
    """(b)"""

    ROWS = 64

    @pytest.fixture(scope="class")
    def full(self, cfg, data):
        model = _learner(cfg, factor_capacity=self.ROWS)
        model.init_tables(11)
        _steps(model, cfg, data["path"], steps=3)
        return model

    def test_b_refusals_are_counted(self, full):
        scalars = full.scalars()
        assert scalars["active_ids"] == self.ROWS
        assert scalars["refused"] > 0

    def test_b_no_slot_past_the_array(self, full):
        slots = np.asarray(full.params.base_rows["slot"])
        held = np.sort(slots[slots >= 0])
        np.testing.assert_array_equal(held, np.arange(self.ROWS))
        assert full.params.factors.shape == (
            self.ROWS, 2 * full.param.num_factors)
        assert int(np.asarray(full.params["has_v"]).sum()) == self.ROWS

    def test_b_a_refused_id_is_taken_once_there_is_room(self, full, cfg,
                                                        data):
        small = full.snapshot_model()
        wide = _learner(cfg)
        wide.restore_snapshot_model(small)
        again = _learner(cfg, factor_capacity=self.ROWS)
        again.restore_snapshot_model(small)
        for model in (wide, again):
            _steps(model, cfg, data["path"], steps=1)
        # the same batch: refused again where there is no room, taken
        # where the restoring learner has some
        assert again.scalars()["active_ids"] == self.ROWS
        asked = again.scalars()["refused"] - full.scalars()["refused"]
        assert asked > 0
        assert wide.scalars()["active_ids"] == self.ROWS + asked
        assert wide.scalars()["refused"] == full.scalars()["refused"]

    def test_b_a_capacity_too_small_is_refused_by_name(self, full, cfg):
        tiny = _learner(cfg, factor_capacity=self.ROWS - 1)
        with pytest.raises(DMLCError, match="factor_capacity=63"):
            tiny.restore_snapshot_model(full.snapshot_model())


class TestCounting:
    """(c)"""

    def test_c_counting_stops_at_count_rows(self, cfg, data):
        batch = cfg["batch_rows_per_chip"]
        model = _learner(cfg, count_rows=2 * batch)
        model.init_tables(3)
        _steps(model, cfg, data["path"], steps=4)
        assert model.scalars()["counted_rows"] == 2 * batch
        cnt = np.asarray(model.params["cnt"])
        assert cnt.dtype == np.int32
        assert cnt.sum() == 2 * batch * cfg["nnz_per_row"]
        want = np.bincount(data["ids"][:2 * batch].ravel(),
                           minlength=cfg["num_features"])
        np.testing.assert_array_equal(cnt, want)

    def test_c_no_count_rows_is_refused(self, cfg):
        with pytest.raises(DMLCError, match="count_rows required"):
            _learner(cfg, count_rows=0)


class TestTheDenseModelBesideIt:
    """(d): ``V_threshold`` 0, ``l1_shrk`` off and a row for every id is
    ``kdd12-fm-difacto``'s model."""

    @pytest.fixture(scope="class")
    def both(self, cfg, data):
        rule = {k: cfg[k] for k in (
            "objective", "learning_rate", "l2", "num_factors",
            "num_features", "init_scale", "optimizer") + FtrlAdagrad._fields}
        nf, k = cfg["num_features"], cfg["num_factors"]
        start = {name: np.asarray(value) for name, value in init_fm_params(
            nf, k, cfg["init_scale"], seed=5,
            optimizer="ftrl_adagrad").items()}
        dense = FMLearner(**rule)
        dense.restore_snapshot_model({"params": start})
        ours = _learner(cfg, v_threshold=0, l1_shrk=False,
                        factor_capacity=nf)
        ours.restore_snapshot_model({"params": dict(
            {name: start[name] for name in ("w", "z", "n", "v", "a", "b")},
            cnt=np.zeros(nf, np.int32),
            factor_ids=np.arange(nf, dtype=np.int32),
            key=np.zeros(2, np.uint32))})
        return types.SimpleNamespace(
            dense=dense, ours=ours,
            dense_losses=_steps(dense, cfg, data["path"]),
            our_losses=_steps(ours, cfg, data["path"]))

    def test_d_the_losses(self, both):
        np.testing.assert_allclose(
            both.our_losses, both.dense_losses, rtol=1e-6)

    @pytest.mark.parametrize("key", ["w", "z", "n", "v", "a", "b"])
    def test_d_the_logical_tables(self, both, key):
        want = np.asarray(both.dense.params[key])
        np.testing.assert_allclose(
            np.asarray(both.ours.params[key]), want, rtol=1e-5,
            atol=1e-6 * np.max(np.abs(want)))

    def test_d_nothing_was_handed_out(self, both, cfg):
        scalars = both.ours.scalars()
        assert scalars["active_ids"] == cfg["num_features"]
        assert scalars["refused"] == 0


class TestSnapshot:
    """(e)"""

    @pytest.fixture(autouse=True)
    def _clean_state(self):
        resilience.reset()
        preempt.reset()
        yield
        resilience.reset()
        preempt.reset()
        preempt.uninstall()

    def test_e_a_snapshot_holds_the_rows_in_use(self, trained):
        held = trained.model.snapshot_model()["params"]
        n = int(trained.after["active_ids"])
        k = trained.cfg["num_factors"]
        assert held["v"].shape == held["a"].shape == (n, k)
        assert np.all(np.diff(held["factor_ids"]) > 0)
        assert held["cnt"].dtype == np.int32
        assert held["w"].shape == (trained.cfg["num_features"],)
        assert "slot" not in held

    @pytest.mark.parametrize("capacity", [1024, 8192])
    def test_e_restore_under_another_capacity_next_step_equal(
            self, trained, cfg, data, capacity):
        cfg = trained.cfg
        snap = trained.model.snapshot_model()
        clean = _learner(cfg)
        clean.restore_snapshot_model(snap)
        other = _learner(cfg, factor_capacity=capacity)
        other.restore_snapshot_model(snap)
        assert other.params.capacity == capacity
        assert _steps(other, cfg, data["path"], steps=2) == _steps(
            clean, cfg, data["path"], steps=2)
        ids = trained.touched
        want, got = _logical(clean, ids), _logical(other, ids)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)

    def test_e_kill_and_resume_is_bit_identical(self, cfg, data, tmp_path):
        from dmlc_tpu.collective import JobSnapshot

        kw = dict(batch_size=cfg["batch_rows_per_chip"], epochs=4)
        clean = _learner(cfg)
        want = clean.fit_uri(data["path"], **kw)
        snap_uri = str(tmp_path / "snap")
        # one poll a step: killed in epoch 2, with the boundary snapshots
        # of epochs 0 and 1 committed
        resilience.configure("preempt.notice:nth=%d" % (2 * STEPS + 3))
        try:
            with pytest.raises(Preempted):
                _learner(cfg).fit_uri(
                    data["path"], snapshot_uri=snap_uri, **kw)
        finally:
            resilience.reset()
            preempt.reset()
        _version, _state, meta = JobSnapshot(snap_uri).restore()
        assert meta["epoch"] == 1
        resumed = _learner(cfg)
        history = resumed.fit_uri(
            data["path"], snapshot_uri=snap_uri, resume=True, **kw)
        assert history == want
        ours, theirs = (m.snapshot_model()["params"]
                        for m in (resumed, clean))
        assert sorted(ours) == sorted(theirs)
        for key in ours:
            np.testing.assert_array_equal(
                np.asarray(ours[key]), np.asarray(theirs[key]), err_msg=key)


class TestRefusals:
    """(f)"""

    def test_f_a_mesh_refuses_the_learner(self, cfg):
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
        with pytest.raises(DMLCError, match="memory-adaptive.*ONE device"):
            _learner(cfg, mesh)

    def test_f_a_tree_without_the_rule_is_refused(self, cfg):
        """The step is chosen by the tree alone: one built for plain SGD
        refuses a memory-adaptive tree by name."""
        model = _learner(cfg)
        model.init_tables(0)
        step = make_fm_train_step(None, cfg["num_features"])
        arrays = {
            "label": jnp.zeros((8,)), "weight": jnp.ones((8,)),
            "indices": jnp.zeros((16,), jnp.int32), "values": jnp.ones((16,)),
            "offsets": jnp.zeros((9,), jnp.int32)}
        with pytest.raises(DMLCError, match="optimizer='ftrl_adagrad'"):
            step(model.params, arrays)

    def test_f_plain_sgd_is_refused(self, cfg):
        with pytest.raises(Exception, match="optimizer"):
            _learner(cfg, optimizer="sgd")

    def test_f_no_capacity_is_refused(self, cfg):
        with pytest.raises(DMLCError, match="factor_capacity required"):
            _learner(cfg, factor_capacity=0)


class TestFactorStart:
    """(g): ``v0`` is a pure function of the seed and the id."""

    def test_g_an_id_draws_the_same_factors_anywhere(self):
        key = jnp.asarray([7, 9], jnp.uint32)
        ids = jnp.asarray([5, 70000, 5, 54686452], jnp.int32)
        rows = np.asarray(fm_module._factor_start(key, ids, 16, 0.01))
        np.testing.assert_array_equal(rows[0], rows[2])
        assert not np.array_equal(rows[0], rows[1])
        alone = np.asarray(fm_module._factor_start(key, ids[3:], 16, 0.01))
        np.testing.assert_array_equal(alone[0], rows[3])
        other = np.asarray(fm_module._factor_start(
            jnp.asarray([7, 10], jnp.uint32), ids, 16, 0.01))
        assert not np.array_equal(other, rows)

    def test_g_the_draws_are_normal_at_init_scale(self):
        rows = np.asarray(fm_module._factor_start(
            jnp.asarray([1, 2], jnp.uint32), jnp.arange(4096), 128, 0.01))
        assert np.isfinite(rows).all()
        assert abs(rows.mean()) < 1e-4
        assert abs(rows.std() - 0.01) < 1e-4
        assert 0.035 < np.abs(rows).max() < 0.06


class TestCountersAndTheFitLoop:
    """(h)"""

    NAMES = ("steps", "adaptive_steps", "active_entries", "entries",
             "activations", "activations_refused")

    @staticmethod
    def _read():
        flat = obs.registry().flat_values()
        out = {name: flat.get(
            'dmlc_fit_%s_total{model="fm"}' % name, 0.0)
            for name in TestCountersAndTheFitLoop.NAMES}
        out["in_use"] = flat.get('dmlc_fit_factor_in_use_rows{model="fm"}')
        out["bytes"] = flat.get('dmlc_fit_optimizer_state_bytes{model="fm"}')
        return out

    def test_h_counters_gauges_and_span_args(self, cfg, data, monkeypatch):
        from dmlc_tpu.obs import trace as obs_trace

        gets = []
        real = jax.device_get
        monkeypatch.setattr(
            jax, "device_get", lambda x: gets.append(1) or real(x))
        spans = []
        obs_trace.add_listener(spans.append)
        try:
            before = self._read()
            model = _learner(cfg, factor_capacity=128)
            feed = _feed(cfg, data["path"])
            model.fit_feed(feed, epochs=2)
            feed.close()
            after = self._read()
        finally:
            obs_trace.remove_listener(spans.append)
        moved = {k: after[k] - before[k] for k in self.NAMES}
        assert moved["steps"] == moved["adaptive_steps"] == 2 * STEPS
        scalars = model.scalars()
        assert moved["activations"] == scalars["active_ids"] == 128
        assert moved["activations_refused"] == scalars["refused"] > 0
        assert after["in_use"] == 128
        # entries whose id had u = 1: none in the first step, most by the
        # second pass, never all (ids without a row, ids at w = 0)
        assert 0 < moved["active_entries"] < moved["entries"]
        assert moved["active_entries"] == int(
            model.params.scalars["active_entries"])
        assert after["bytes"] == 4 * (
            2 * cfg["num_features"] + 128 * cfg["num_factors"])
        epochs = [e for e in spans if e["name"] == "epoch"
                  and e.get("ph") == "X"]
        assert len(epochs) == 2
        for name, want in (
                ("v_threshold", cfg["v_threshold"]), ("l1_shrk", True),
                ("factor_capacity", 128), ("base_columns", 5),
                ("factor_columns", 2 * cfg["num_factors"]),
                ("optimizer", "ftrl_adagrad"), ("row_columns", 0)):
            assert epochs[0]["args"][name] == want, name
        # the counts ride with the pass's losses: three more scalars a
        # pass, no read of their own
        fetches = [e for e in spans if e["name"] == "loss_fetch"
                   and e.get("ph") == "X"]
        assert [e["args"]["scalars"] for e in fetches] == [
            2 * STEPS + 3] * 2
        assert not gets

    def test_h_a_learner_without_such_counts_fetches_what_it_did(
            self, cfg, data):
        from dmlc_tpu.obs import trace as obs_trace

        spans = []
        obs_trace.add_listener(spans.append)
        try:
            model = FMLearner(num_features=cfg["num_features"],
                              num_factors=4)
            assert model.pass_scalars() == {}
            feed = _feed(cfg, data["path"])
            model.fit_feed(feed, epochs=1)
            feed.close()
        finally:
            obs_trace.remove_listener(spans.append)
        (fetch,) = [e for e in spans if e["name"] == "loss_fetch"
                    and e.get("ph") == "X"]
        assert fetch["args"]["scalars"] == 2 * STEPS


class TestLoweredStep:
    """(i): two reads and two row writes, no pass over either array."""

    def test_i_the_indexed_passes_over_the_two_arrays(self, cfg):
        # a capacity no buffer of the step shares its shape with
        model = _learner(cfg, factor_capacity=5000)
        model.init_tables(0)
        model._ensure(cfg["num_features"])
        batch, nnz = 256, 256 * 11
        arrays = {
            "label": jnp.zeros((batch,)), "weight": jnp.ones((batch,)),
            "indices": jnp.zeros((nnz,), jnp.int32),
            "values": jnp.ones((nnz,)),
            "offsets": jnp.zeros((batch + 1,), jnp.int32)}
        step = model._step
        while hasattr(step, "__wrapped__"):
            step = step.__wrapped__
        jaxpr = jax.make_jaxpr(lambda p, b: step(p, b))(model.params, arrays)
        base, factors = model.params.base.shape, model.params.factors.shape
        seen = []

        def walk(jp):
            for eqn in jp.eqns:
                shape = getattr(eqn.invars[0].aval, "shape", None) \
                    if eqn.invars else None
                if shape in (base, factors):
                    seen.append((eqn.primitive.name, shape == base))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        passes = sorted(p for p in seen
                        if p[0] not in ("while", "jit", "pjit", "cond"))
        assert passes == [("gather", False), ("gather", True),
                          ("scatter", False), ("scatter", True)]

    def test_i_the_tree_decides_the_program(self, cfg):
        """ONE ``make_fm_train_step`` takes a dense tree and a
        memory-adaptive one: no keyword chooses, the tree carries the
        threshold, ``l1_shrk`` and ``count_rows`` (static: another
        threshold is another program)."""
        rule = FtrlAdagrad(1e-4, 1e-3, 0.01, 1e-3, 1e-5)
        step = make_fm_train_step(None, 1000, rule=rule)
        while hasattr(step, "__wrapped__"):
            step = step.__wrapped__
        arrays = {
            "label": jnp.zeros((8,)), "weight": jnp.ones((8,)),
            "indices": jnp.zeros((16,), jnp.int32), "values": jnp.ones((16,)),
            "offsets": jnp.zeros((9,), jnp.int32)}
        small = dict(cfg, num_features=1000, num_factors=4,
                     factor_capacity=64)
        trees = {
            "dense": init_fm_params(1000, 4, optimizer="ftrl_adagrad"),
            "ten": _learner(small)._initialiser(1000)(0),
            "three": _learner(small, v_threshold=3)._initialiser(1000)(0)}
        assert isinstance(trees["ten"], AdaptiveTables)
        assert trees["three"].adaptive == (3, True, cfg["count_rows"])
        text = {name: step.lower(tree, arrays).as_text()
                for name, tree in trees.items()}
        assert "step.activate" not in text["dense"]
        assert len(set(text.values())) == 3


class TestStartFromCounts:
    """(l): counts taken elsewhere; every id past the threshold holds its
    factor row before the first step (how the cell's run starts)."""

    SEEN = 20000  # rows the counts were taken over

    @pytest.fixture(scope="class")
    def counts(self, cfg):
        rng = np.random.default_rng(3)
        cnt = rng.poisson(0.3, cfg["num_features"]).astype(np.int64)
        heavy = rng.choice(cfg["num_features"], 900, replace=False)
        cnt[heavy] = rng.integers(5, 4000, 900)
        cnt[40000:40300] = rng.integers(0, 22, 300)  # the folded ids
        return cnt

    @pytest.fixture(scope="class")
    def started(self, cfg, counts):
        model = _learner(cfg, count_rows=self.SEEN + cfg["rows"])
        model.init_tables(11)
        ids = jnp.arange(cfg["num_features"], dtype=jnp.int32)
        v0 = np.asarray(model.table_rows("v", ids))
        model.start_from_counts(counts, self.SEEN)
        return types.SimpleNamespace(model=model, v0=v0, ids=ids)

    def test_l_counts_rows_and_scalars(self, started, cfg, counts):
        model, ids = started.model, started.ids
        np.testing.assert_array_equal(
            np.asarray(model.table_rows("cnt", ids)), counts)
        earned = counts > cfg["v_threshold"]
        assert 500 < earned.sum() < cfg["factor_capacity"]
        np.testing.assert_array_equal(
            np.asarray(model.table_rows("has_v", ids)), earned)
        # rows in id order, as a restore hands them
        slot = np.asarray(model.params.base_rows["slot"])
        np.testing.assert_array_equal(
            slot[earned], np.arange(earned.sum()))
        assert np.all(slot[~earned] == -1)
        scalars = model.scalars()
        assert scalars["active_ids"] == earned.sum()
        assert scalars["counted_rows"] == self.SEEN
        assert scalars["refused"] == 0

    def test_l_a_row_starts_at_v0_and_the_rest_stays(self, started):
        model, ids = started.model, started.ids
        np.testing.assert_array_equal(
            np.asarray(model.table_rows("v", ids)), started.v0)
        held = np.asarray(model.params.factors)
        n = int(model.scalars()["active_ids"])
        k = model.param.num_factors
        assert np.all(held[:n, k:] == 0) and np.all(held[n:] == 0)
        assert np.all(held[:n, :k] != 0)
        for name in ("w", "z", "n", "a"):
            assert not np.asarray(model.table_rows(name, ids)).any(), name

    def test_l_the_steps_after_it_agree_with_the_reference(
            self, started, harness, cfg, data):
        """Counting goes on over the file's rows, ids cross the threshold
        in them, and rows held from the start take part once ``w``
        moves: against the float64 reference from the same state."""
        run = dict(cfg, count_rows=self.SEEN + cfg["rows"], l1=0.008)
        model = _learner(run)
        model.restore_snapshot_model(started.model.snapshot_model())
        touched, batches = _batches(cfg, data)
        before = _logical(model, touched)
        assert before["has_v"].sum() > 100
        losses = _steps(model, run, data["path"])
        ref_losses, ref = harness.config.reference_steps(
            run, before, batches)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-6)
        after = _logical(model, touched)
        for key in ("cnt", "has_v") + SCALARS[1:]:
            np.testing.assert_array_equal(after[key], ref[key], err_msg=key)
        assert after["active_ids"] > before["active_ids"]
        assert after["counted_rows"] == self.SEEN + cfg["rows"]
        # rows held from the start were trained
        assert np.any(after["a"][before["has_v"].astype(bool)] > 0)
        for key in ("w", "z", "n", "v", "a"):
            moved = np.max(np.abs(ref[key] - before[key]))
            assert np.max(np.abs(after[key] - ref[key])) / moved < 2e-5, key

    def test_l_a_pass_counts_its_own_activations(self, started, cfg, data):
        model = _learner(dict(cfg, count_rows=self.SEEN + cfg["rows"]))
        model.init_tables(11)
        model.start_from_counts(
            np.asarray(started.model.params["cnt"]), self.SEEN)
        held = model.scalars()["active_ids"]

        def activations():
            return obs.registry().flat_values().get(
                'dmlc_fit_activations_total{model="fm"}', 0.0)

        before = activations()
        feed = _feed(cfg, data["path"])
        model.fit_feed(feed, epochs=1)
        feed.close()
        grown = model.scalars()["active_ids"] - held
        assert 0 < grown == activations() - before

    def test_l_more_ids_than_rows_is_refused_by_name(self, cfg, counts):
        model = _learner(cfg, factor_capacity=64)
        model.init_tables(0)
        with pytest.raises(DMLCError, match="factor_capacity=64 has no room"):
            model.start_from_counts(counts, self.SEEN)

    def test_l_counts_of_another_length_are_refused(self, cfg, counts):
        model = _learner(cfg)
        model.init_tables(0)
        with pytest.raises(DMLCError, match="counts for"):
            model.start_from_counts(counts[:-1], self.SEEN)

    def test_l_the_cells_counts_follow_the_generator(self, harness, cfg):
        """``counts_at_start`` of the configuration against the rows the
        generator itself draws: the ids it lands on and how often."""
        seen = 1 << 16
        got = harness.config.counts_at_start(
            dict(cfg, start_counted_rows=seen), 5)
        assert got.dtype == np.int32 and got.shape == (cfg["num_features"],)
        assert abs(got.sum() / (seen * cfg["nnz_per_row"]) - 1) < 2e-3
        rng = np.random.default_rng(7)
        drawn = harness.textgen.field_power_law_ids(
            rng, 8 * seen, cfg["field_sizes"],
            cfg["id_power_law_exponent"])
        mean = np.bincount(
            drawn.ravel(), minlength=cfg["num_features"]) / 8.0
        top = np.argsort(-got)[:300]
        assert np.max(np.abs(mean[top] - got[top])
                      / np.sqrt(got[top] + 1.0)) < 2.0
        assert np.corrcoef(mean, got)[0, 1] > 0.9999
        # another seed rounds otherwise, by the same law
        other = harness.config.counts_at_start(
            dict(cfg, start_counted_rows=seen), 6)
        assert 0 < np.max(np.abs(other - got)) <= 2


class TestFingerprints:
    """(m)"""

    def test_m_the_three_behind_the_slot_map_from_one_program(
            self, trained, monkeypatch):
        model = trained.model
        calls = []
        real = fm_module._prints_by_slot
        monkeypatch.setattr(
            fm_module, "_prints_by_slot",
            lambda *a: calls.append(1) or real(*a))
        first = {k: np.asarray(model.table_fingerprints(k))
                 for k in model.table_names()}
        assert len(calls) == 1
        again = np.asarray(model.table_fingerprints("v"))
        assert len(calls) == 2
        np.testing.assert_array_equal(again, first["v"])
        # a row's print is the sum of its words' bits; none reads 0
        has = first["has_v"].astype(bool)
        assert has.sum() == trained.after["active_ids"]
        assert np.all(first["v"][has] != 0) and not first["v"][~has].any()


def test_j_the_new_cell_rehearses():
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data, init, check against the reference, window, result line)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "1", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True and "metrics" not in result
    assert {"rows_per_s", "setup_s"} <= set(result["metric_names"])
    detail = json.loads(lines[-2].split("[bench] detail ", 1)[1])
    check = detail["check"]
    assert check["untouched_changed"] == 0
    assert check["update_rel"] < 2e-5 and check["loss_rel"] < 2e-6
    assert check["update_rel_of"]["has_v"] == 0 == check["update_rel_of"]["cnt"]
    assert sorted(check["update_rel_of"]) == sorted(ADAPTIVE_TABLES + SCALARS)


class TestTheThreeReaders:
    """(k): the new per-layer readers say nothing, and raise nothing,
    where the program has no such scope or counter (a recorded v5e trace
    of a plain FM run: what a parent commit gives them)."""

    @pytest.fixture(scope="class")
    def run(self, harness, tmp_path_factory):
        import shutil

        here = os.path.join(BENCH, "testdata")
        with open(os.path.join(here, "expected_restart.json")) as f:
            want = json.load(f)
        with open(os.path.join(here, want["spans"])) as f:
            spans = json.load(f)
        root = str(tmp_path_factory.mktemp("trace"))
        where = os.path.join(root, want["cell"], "trace", "plugins",
                             "profile", "recorded")
        os.makedirs(where)
        shutil.copy(os.path.join(here, want["trace"]), where)
        kept = harness.timeline.RUN_DIR
        harness.timeline.RUN_DIR = root
        try:
            trace = harness.xplane.reduce(
                harness.xplane.find_trace(
                    os.path.join(root, want["cell"], "trace")),
                span_names=sorted({s["name"] for s in spans}),
                window="bench.trace")
            yield {"cell": want["cell"], "trace": trace, "spans": spans,
                   "counters": {'dmlc_fit_steps_total{model="fm"}': 10.0,
                                'dmlc_fit_entries_total{model="fm"}': 90.0}}
        finally:
            harness.timeline.RUN_DIR = kept

    @pytest.mark.parametrize("name", [
        "adaptive_step_share", "active_entry_share", "step_activate_ms"])
    def test_k_no_scope_or_counter_no_value(self, harness, run, name):
        reader = harness.spec.load_module(
            os.path.join(BENCH, "metrics", name + ".py"))
        assert reader.read(run) is None

    @pytest.mark.parametrize("name,counter,want", [
        ("adaptive_step_share", "dmlc_fit_adaptive_steps_total", 1.0),
        ("active_entry_share", "dmlc_fit_active_entries_total", 0.5)])
    def test_k_the_shares(self, harness, run, name, counter, want):
        reader = harness.spec.load_module(
            os.path.join(BENCH, "metrics", name + ".py"))
        counters = dict(run["counters"])
        counters['%s{model="fm"}' % counter] = want * (
            10.0 if "steps" in counter else 90.0)
        assert reader.read(dict(run, counters=counters)) == want

"""The field-aware factorization machine (``FFMLearner``: libffm's model
and its AdaGrad, an entry's field from its id's range): on the suite's CPU
devices, at the ``rehearse`` size of the ``kdd12-ffm`` configuration
(F=100,001, 11 fields, 2 factors held of 4, batches of 1024), against
that configuration's float64 numpy reference and against a brute-force
double loop over a row's pairs, neither of which imports anything of
``dmlc_tpu.models``.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu import resilience
from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import FFMLearner, make_ffm_train_step
from dmlc_tpu.models.ffm import field_lows, init_ffm_params
from dmlc_tpu.resilience import Preempted, preempt
from dmlc_tpu.utils.logging import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "kdd12-ffm")
CELL = "kdd12-ffm.libsvm"
CHIPS, STEPS = 2, 6
TABLES = ("v", "a")


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own generator, readers and reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import spec, textgen, timeline, xplane

        yield types.SimpleNamespace(
            spec=spec, textgen=textgen, timeline=timeline, xplane=xplane,
            config=spec.load_module(CONFIG + ".py"))
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG + ".json") as f:
        out = json.load(f)
    out.update(out["rehearse"])
    out["rows"] = 8 * out["batch_rows_per_chip"]
    return out


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:CHIPS]), ("dp",))


@pytest.fixture(scope="module")
def data(harness, cfg, tmp_path_factory):
    """The configuration's rows, as arrays and as one LIBSVM file. The
    last five fields are folded onto a few hundred ids of their own
    ranges, so that ids repeat within a batch and across batches."""
    rows = harness.config.rows(cfg, 2147483659)
    lows = (1,) + field_lows(cfg["field_sizes"], cfg["num_features"])
    for j in range(6, 11):
        rows["ids"][:, j] = lows[j] + (rows["ids"][:, j] - lows[j]) % 60
    path = str(tmp_path_factory.mktemp("ffm") / "rows.libsvm")
    harness.textgen.write_libsvm(
        path, rows["label"], rows["ids"], rows["value_text"],
        rows["pool_index"])
    return dict(rows, path=path)


def _learner(cfg, mesh=None, **over):
    hyper = {k: cfg[k] for k in (
        "objective", "learning_rate", "l2", "num_factors", "num_features",
        "field_sizes", "init_scale", "a_init")}
    hyper.update(over)
    return FFMLearner(mesh=mesh, **hyper)


def _feed(cfg, path, mesh=None):
    return DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=cfg["batch_rows_per_chip"], layout="csr",
                  num_features=cfg["num_features"]),
        mesh=mesh)


def _host(params):
    return {k: np.asarray(v) for k, v in params.items()}


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _start(cfg, seed=7, **over):
    c = dict(cfg, **over)
    return _host(init_ffm_params(
        c["num_features"], c["num_factors"], len(c["field_sizes"]),
        c["init_scale"], c["a_init"], seed=seed))


def _fit(cfg, data, mesh=None, epochs=1, **over):
    """``epochs`` passes of ``fit_feed`` over the file from the program's
    own initialiser (one device's, so that every placement starts from
    the same bits); (learner, start, losses)."""
    model = _learner(cfg, mesh, **over)
    start = _start(cfg, **{k: v for k, v in over.items() if k in cfg})
    model.restore_snapshot_model({"params": start})
    feed = _feed(cfg, data["path"], mesh)
    losses = model.fit_feed(feed, epochs=epochs)
    feed.close()
    return model, start, losses


@pytest.fixture(scope="module")
def trained(harness, cfg, data):
    """``STEPS`` batches through ``fit_feed`` on one device and through
    the float64 reference, from the same parameters."""
    batch = cfg["batch_rows_per_chip"]
    need = STEPS * batch
    short = dict(cfg, rows=need)
    path = os.path.join(os.path.dirname(data["path"]), "six.libsvm")
    harness.textgen.write_libsvm(
        path, data["label"][:need], data["ids"][:need], data["value_text"],
        None)
    model, start, _ = _fit(short, dict(data, path=path))
    # one pass is the six steps; their mean losses one by one
    again = _learner(cfg)
    again.restore_snapshot_model({"params": start})
    feed = _feed(cfg, path)
    losses = []
    for arrays in feed:
        again.ensure_step(feed.spec)
        m = again.train_step({k: v for k, v in arrays.items()
                              if k != "num_rows"})
        losses.append(float(m["loss_sum"]) / float(m["weight_sum"]))
    feed.close()
    ids = data["ids"][:need]
    touched = np.unique(ids)
    compact = np.searchsorted(touched, ids)
    batches = [
        {"label": data["label"][i * batch:(i + 1) * batch],
         "ids": compact[i * batch:(i + 1) * batch],
         "values": np.ones((batch, ids.shape[1]), np.float32)}
        for i in range(STEPS)]
    before = {k: start[k][touched].astype(np.float64) for k in TABLES}
    ref_losses, ref = harness.config.reference_steps(cfg, before, batches)
    return types.SimpleNamespace(
        model=model, again=again, start=start, after=_host(model.params),
        losses=losses, touched=touched, before=before, ref=ref,
        ref_losses=ref_losses, batches=batches)


class TestAgainstTheReference:
    """(a): the learner's step against the configuration's
    ``reference_steps`` (the pairwise form, fields from the columns)."""

    def test_a_each_steps_loss(self, trained):
        assert len(trained.losses) == STEPS
        np.testing.assert_allclose(
            trained.losses, trained.ref_losses, rtol=2e-6)
        # libffm's all-positive start: the margins sit well above 0
        assert trained.ref_losses[0] > 0.9

    def test_a_step_by_step_is_the_fit_loop(self, trained):
        for k, v in trained.after.items():
            np.testing.assert_array_equal(
                _bits(v), _bits(trained.again.params[k]), err_msg=k)

    def test_a_params_are_two_2d_tables(self, trained, cfg):
        want = (cfg["num_features"],
                cfg["num_factors"] * len(cfg["field_sizes"]))
        assert sorted(trained.after) == ["a", "v"]
        assert all(t.shape == want for t in trained.after.values())

    @pytest.mark.parametrize("key", TABLES)
    def test_a_weights_and_state_of_every_touched_row(self, trained, key):
        got = np.float64(trained.after[key])[trained.touched]
        moved = np.max(np.abs(trained.ref[key] - trained.before[key]))
        assert moved > 0
        # in units of the table's largest change, as the benchmark's check
        assert np.max(np.abs(got - trained.ref[key])) / moved < 2e-5

    @pytest.mark.parametrize("key", TABLES)
    def test_a_no_other_row_changed(self, trained, key):
        other = np.ones(len(trained.start[key]), bool)
        other[trained.touched] = False
        assert other.sum() > trained.touched.size
        np.testing.assert_array_equal(
            _bits(trained.after[key][other]),
            _bits(trained.start[key][other]))

    def test_a_ids_repeat_within_and_across_batches(self, trained):
        named = np.bincount(np.concatenate(
            [np.unique(b["ids"]) for b in trained.batches]))
        assert (named > 1).any()
        assert any(np.unique(b["ids"]).size < b["ids"].size
                   for b in trained.batches)


# rows the one-entry-a-field generator never makes. Fields over ids
# 1..12: 1-3, 4-7, 8-12; (id, value) pairs
RAGGED = (
    [(1, 0.5), (2, 1.5), (5, 1.0)],            # field 0 twice, field 2 absent
    [(4, 2.0), (9, 1.0), (12, 0.3), (9, 0.7)],  # an id twice in one row
    [(8, 1.0)],                                  # one entry: no pair
    [(1, 1.0), (6, 1.0), (10, 1.0)],            # one of each, values 1
    [(3, 0.2), (11, 0.9), (7, -1.1)],           # real values, out of order
)


class TestRaggedRows:
    """(b): any CSR row, against a brute-force double loop over the
    row's pairs; and entries of value 0."""

    F, K, SIZES, PAD = 13, 3, (3, 4, 5), 4
    LR, L2 = 0.2, 0.01

    def _field(self, i):
        return int(i >= 4) + int(i >= 8)

    def _start(self):
        params = _host(init_ffm_params(
            self.F, self.K, len(self.SIZES), 0.5, 1e-4, seed=3))
        rng = np.random.RandomState(0)
        params["a"] = (1e-4 + 1e-3 * rng.rand(*params["a"].shape)).astype(
            np.float32)
        return params

    def _batch(self, rows):
        ind, val, off = [], [], [0]
        for row in rows:
            ind += [i for i, _ in row]
            val += [x for _, x in row]
            off.append(len(ind))
        ind += [0] * self.PAD  # the feed's padding names feature 0
        val += [0.0] * self.PAD
        label = np.arange(len(rows)) % 2 == 0
        return {"label": jnp.asarray(label, jnp.float32),
                "weight": jnp.ones(len(rows)),
                "indices": jnp.asarray(ind, jnp.int32),
                "values": jnp.asarray(val, jnp.float32),
                "offsets": jnp.asarray(off, jnp.int32)}, label

    def _double_loop(self, params, rows, label):
        nf = len(self.SIZES)
        v = params["v"].astype(np.float64).reshape(self.F, self.K, nf)
        a = params["a"].astype(np.float64).reshape(self.F, self.K, nf)
        grad, loss = np.zeros_like(v), 0.0
        for row, y in zip(rows, label):
            norm = 1.0 / sum(x * x for _, x in row)
            phi = 0.0
            for p, (i, x) in enumerate(row):
                for j, z in row[p + 1:]:
                    phi += norm * x * z * np.dot(
                        v[i, :, self._field(j)], v[j, :, self._field(i)])
            sign = 1.0 if y else -1.0
            loss += np.log1p(np.exp(-sign * phi))
            kappa = -sign / (1.0 + np.exp(sign * phi))
            for p, (i, x) in enumerate(row):
                for q, (j, z) in enumerate(row):
                    if p != q:
                        grad[i, :, self._field(j)] += (
                            kappa * norm * x * z * v[j, :, self._field(i)])
        grad /= len(rows)
        for i in sorted({i for row in rows for i, x in row if x != 0}):
            g = grad[i] + self.L2 * v[i]
            a[i] += g * g
            v[i] -= self.LR * g / np.sqrt(a[i])
        shape = params["v"].shape
        return loss / len(rows), v.reshape(shape), a.reshape(shape)

    @pytest.fixture(scope="class")
    def stepped(self):
        start = self._start()
        batch, label = self._batch(RAGGED)
        step = make_ffm_train_step(
            None, self.F, self.SIZES, learning_rate=self.LR, l2=self.L2)
        after, m = step({k: jnp.asarray(t) for k, t in start.items()}, batch)
        loss, v, a = self._double_loop(start, RAGGED, label)
        return types.SimpleNamespace(
            start=start, after=_host(after), want={"v": v, "a": a},
            loss=float(m["loss_sum"]) / float(m["weight_sum"]),
            want_loss=loss, touched=int(m["touched_rows"]))

    def test_b_loss(self, stepped):
        assert stepped.loss == pytest.approx(stepped.want_loss, rel=2e-6)

    @pytest.mark.parametrize("key", TABLES)
    def test_b_tables_against_the_double_loop(self, stepped, key):
        moved = np.max(np.abs(stepped.want[key] - stepped.start[key]))
        assert moved > 0
        got = np.float64(stepped.after[key])
        assert np.max(np.abs(got - stepped.want[key])) / moved < 2e-5

    @pytest.mark.parametrize("key", TABLES)
    def test_b_padding_and_unnamed_rows_keep_their_bits(self, stepped, key):
        # feature 0 is named by the padding only; the distinct ids count it
        named = {i for row in RAGGED for i, _ in row}
        assert stepped.touched == len(named) + 1
        for i in sorted(set(range(self.F)) - named):
            np.testing.assert_array_equal(
                _bits(stepped.after[key][i]), _bits(stepped.start[key][i]))

    @pytest.mark.parametrize("l2", [0.0, 0.05])
    @pytest.mark.parametrize("key", TABLES)
    def test_b_a_slot_of_zero_values_keeps_its_row(self, l2, key):
        rows = [list(row) for row in RAGGED]
        rows[0].append((6, 0.0))  # id 6 named twice, both times with 0
        rows[4].append((6, 0.0))
        rows[3] = [(1, 1.0), (10, 1.0)]
        start = self._start()
        batch, _ = self._batch(rows)
        step = make_ffm_train_step(
            None, self.F, self.SIZES, learning_rate=self.LR, l2=l2)
        after = _host(step(
            {k: jnp.asarray(t) for k, t in start.items()}, batch)[0])
        for i in (0, 6):
            np.testing.assert_array_equal(
                _bits(after[key][i]), _bits(start[key][i]))
        assert (_bits(after[key][1]) != _bits(start[key][1])).any()

    def test_b_a_value_beside_the_zeros_counts(self):
        """One entry with a value among an id's zero entries: the row is
        the rule's, decay included."""
        rows = [[(6, 0.0), (1, 1.0), (6, 1.0), (9, 1.0)], [(6, 0.0)]]
        start = self._start()
        batch, _ = self._batch(rows)
        step = make_ffm_train_step(
            None, self.F, self.SIZES, learning_rate=self.LR, l2=0.05)
        after = _host(step(
            {k: jnp.asarray(t) for k, t in start.items()}, batch)[0])
        for key in TABLES:
            assert (_bits(after[key][6]) != _bits(start[key][6])).all()


class TestTheUnitsOfTheAccumulator:
    """(c): libffm's ``a`` from 1 is in per-instance units. Against mean
    gradients a float32 accumulator at 1 does not move for an id a batch
    names once (most of them: its mean gradient squared is below the last
    bit of 1), and the step is plain SGD there; at ``1 / rows^2`` it
    moves."""

    @pytest.mark.parametrize("units", ["per-instance", "mean-gradient"])
    def test_c_a_init_of_one_freezes_a_in_float32(self, harness, cfg, units):
        rows = cfg["batch_rows_per_chip"]
        assert cfg["a_init"] == 1.0 / rows ** 2
        a_init = 1.0 if units == "per-instance" else cfg["a_init"]
        ids = harness.config.rows(dict(cfg, rows=rows), 11)["ids"]
        named, count = np.unique(ids, return_counts=True)
        once = named[count == 1]
        assert once.size > named.size // 2
        start = _start(cfg, a_init=a_init)
        step = make_ffm_train_step(
            None, cfg["num_features"], cfg["field_sizes"],
            learning_rate=cfg["learning_rate"], l2=cfg["l2"])
        batch = {
            "label": jnp.zeros(rows), "weight": jnp.ones(rows),
            "indices": jnp.asarray(ids.ravel(), jnp.int32),
            "values": jnp.ones(ids.size),
            "offsets": jnp.arange(rows + 1, dtype=jnp.int32) * ids.shape[1]}
        after = _host(step(
            {k: jnp.asarray(t) for k, t in start.items()}, batch)[0])
        assert (after["v"][once] != start["v"][once]).any(axis=1).all()
        moved = (after["a"][once] != start["a"][once]).any(axis=1)
        if units == "per-instance":
            assert not moved.any()
            # the rule was plain SGD there: v' = v - lr * G / sqrt(1)
            step_v = (start["v"][once] - after["v"][once]) / cfg[
                "learning_rate"]
            assert 0 < np.abs(step_v).max() ** 2 < np.finfo(np.float32).eps
        else:
            assert moved.all()


class TestTheShareAndTheWhole:
    """(d): the chip's share tied to the model. The margins of the two
    column shares (factors 0-1 and 2-3 of the published 4, each run as
    the one-chip learner) add up to the margin of the uncut float64
    reference; and a 2-device mesh under ``table_sharding="factors"``
    holds those two shares and trains them as one device trains the
    whole."""

    @pytest.fixture(scope="class")
    def whole(self, cfg):
        return dict(cfg, num_factors=cfg["num_factors_published"])

    def test_d_the_shares_margins_add_up_to_the_whole(
            self, harness, cfg, whole, data):
        fields = len(cfg["field_sizes"])
        start = _start(whole)
        half = cfg["num_factors"] * fields
        assert start["v"].shape[1] == 2 * half
        feed = _feed(cfg, data["path"])
        batch = next(iter(feed))
        margins = []
        for lo in (0, half):
            share = _learner(cfg)
            share.restore_snapshot_model({"params": {
                k: t[:, lo:lo + half] for k, t in start.items()}})
            margins.append(np.float64(share.predict_batch(batch)))
        feed.close()
        # the uncut model's margin by the pairwise form, in float64
        rows = cfg["batch_rows_per_chip"]
        ids = data["ids"][:rows]
        v = start["v"].astype(np.float64).reshape(
            -1, whole["num_factors"], fields)[ids]  # [B, e, k, field]
        want = np.zeros(rows)
        for e in range(fields):
            for e2 in range(e + 1, fields):
                want += (v[:, e, :, e2] * v[:, e2, :, e]).sum(axis=1)
        want /= fields  # every value is 1: r = 1 / fields
        assert np.abs(margins[0]).min() > 0.05  # neither share is idle
        np.testing.assert_allclose(margins[0] + margins[1], want, rtol=2e-6)

    def test_d_a_mesh_of_two_holds_the_two_shares(self, whole, data, mesh):
        one, start, h1 = _fit(whole, data, None, epochs=2)
        two, _, h2 = _fit(whole, data, mesh, epochs=2,
                          table_sharding="factors")
        np.testing.assert_allclose(h2, h1, rtol=2e-6)
        half = whole["num_factors"] * len(whole["field_sizes"]) // CHIPS
        for key in TABLES:
            assert two.params[key].sharding.spec == P(None, "dp")
            shards = sorted(two.params[key].addressable_shards,
                            key=lambda s: s.index[1].start)
            want = np.float64(one.params[key])
            moved = np.max(np.abs(want - start[key]))
            for c, shard in enumerate(shards):
                # chip c holds factors 2c, 2c + 1 of every field; in units
                # of the table's largest change, as the benchmark's check
                assert shard.data.shape[1] == half
                off = np.abs(np.float64(shard.data)
                             - want[:, c * half:(c + 1) * half])
                assert moved > 0 and off.max() / moved < 2e-5, key


class TestRefusals:
    """(e)"""

    def test_e_no_field_sizes(self, cfg):
        with pytest.raises(DMLCError, match="no libfm field column"):
            FFMLearner(num_features=cfg["num_features"])

    def test_e_fields_wider_than_the_ids(self):
        with pytest.raises(DMLCError, match="field_sizes cover 12 ids"):
            FFMLearner(num_features=10, field_sizes=[3, 4, 5])

    def test_e_field_sizes_from_text(self):
        model = FFMLearner(num_features=13, field_sizes="(3, 4, 5)")
        assert model.param.field_sizes == (3, 4, 5)
        assert model.fields == 3 and model.columns == 12
        assert field_lows(model.param.field_sizes, 13) == (4, 8)

    def test_e_a_mesh_of_replicas_refuses_the_learner(self, cfg, mesh):
        with pytest.raises(DMLCError, match="replicated mesh step"):
            _learner(cfg, mesh)

    def test_e_a_mesh_of_replicas_refuses_the_step(self, mesh):
        with pytest.raises(DMLCError, match="optimizer='adagrad'"):
            make_ffm_train_step(mesh, 13, (3, 4, 5))

    def test_e_factors_that_do_not_divide(self, cfg):
        three = Mesh(np.asarray(jax.devices()[:3]), ("dp",))
        with pytest.raises(DMLCError, match="num_factors divisible"):
            _learner(cfg, three, table_sharding="factors")

    def test_e_an_accumulator_from_zero(self, cfg):
        with pytest.raises(DMLCError, match="a_init must be positive"):
            _learner(cfg, a_init=0.0)

    @pytest.mark.parametrize("held", ["another-width", "no-state"])
    def test_e_a_snapshot_of_another_model_is_refused(self, cfg, held):
        small = dict(cfg, num_features=64, field_sizes=[3, 20, 40])
        params = _start(small)
        if held == "another-width":
            params = {k: t[:, :4] for k, t in params.items()}
            match = "factor table of shape"
        else:
            del params["a"]
            match = "optimizer state"
        with pytest.raises(DMLCError, match=match):
            _learner(small).restore_snapshot_model({"params": params})


class TestSnapshot:
    """(f): ``v`` and ``a`` through ``params``, as the FM's state."""

    @pytest.fixture(autouse=True)
    def _clean_state(self):
        resilience.reset()
        preempt.reset()
        yield
        resilience.reset()
        preempt.reset()
        preempt.uninstall()

    @pytest.mark.parametrize("placement", ["one-device", "factors"])
    def test_f_kill_and_resume_is_bit_identical(self, cfg, data, mesh,
                                                tmp_path, placement):
        from dmlc_tpu.collective import JobSnapshot

        on = None if placement == "one-device" else mesh
        over = {} if on is None else {"table_sharding": "factors"}
        kw = dict(batch_size=cfg["batch_rows_per_chip"], epochs=4)
        clean = _learner(cfg, on, **over)
        want = clean.fit_uri(data["path"], **kw)

        snap_uri = str(tmp_path / "snap")
        steps = cfg["rows"] // cfg["batch_rows_per_chip"]
        # one poll a step: killed in epoch 2, with the boundary snapshots
        # of epochs 0 and 1 committed
        resilience.configure("preempt.notice:nth=%d" % (2 * steps + 3))
        try:
            with pytest.raises(Preempted):
                _learner(cfg, on, **over).fit_uri(
                    data["path"], snapshot_uri=snap_uri, **kw)
        finally:
            resilience.reset()
            preempt.reset()
        _version, state, meta = JobSnapshot(snap_uri).restore()
        assert meta["epoch"] == 1
        held = state["model"]["params"]
        assert sorted(held) == sorted(TABLES)
        # each table as the one logical array it is, the state not at rest
        assert held["a"].shape == held["v"].shape == (
            cfg["num_features"], clean.columns)
        assert (held["a"] != np.float32(cfg["a_init"])).any()

        resumed = _learner(cfg, on, **over)
        history = resumed.fit_uri(
            data["path"], snapshot_uri=snap_uri, resume=True, **kw)
        assert history == want
        for k in TABLES:
            np.testing.assert_array_equal(
                _bits(resumed.params[k]), _bits(clean.params[k]), err_msg=k)

    @pytest.mark.parametrize("to", ["one-device", "factors"])
    def test_f_a_snapshot_restores_under_another_placement(
            self, cfg, data, mesh, trained, to):
        from dmlc_tpu.collective.checkpoint import _to_host

        source = trained.model
        if to == "one-device":
            source, _, _ = _fit(cfg, data, mesh, table_sharding="factors")
        part = _to_host({"params": dict(source.params)})
        assert all(type(v) is np.ndarray for v in part["params"].values())
        target = _learner(cfg, mesh, table_sharding="factors") \
            if to == "factors" else _learner(cfg)
        target.restore_snapshot_model(part)
        for k in TABLES:
            np.testing.assert_array_equal(
                _bits(target.params[k]), _bits(part["params"][k]))
        if to == "factors":
            assert target.params["a"].sharding.spec == P(None, "dp")
        feed = _feed(cfg, data["path"], target.mesh)
        (loss,) = target.fit_feed(feed, epochs=1)
        feed.close()
        assert np.isfinite(loss)

    def test_f_reshard_carries_the_state(self, cfg, data, mesh):
        model, _, _ = _fit(cfg, data, mesh, table_sharding="factors")
        before = _host(model.params)
        model.reshard(Mesh(np.asarray(jax.devices()[2:4]), ("dp",)))
        assert model.params["a"].sharding.spec == P(None, "dp")
        for k, v in before.items():
            np.testing.assert_array_equal(_bits(model.params[k]), _bits(v))


class TestCounters:
    """(g)"""

    @pytest.mark.parametrize("placement", ["one-device", "factors"])
    def test_g_counters_gauge_and_span_args(self, cfg, data, mesh,
                                            placement):
        from dmlc_tpu import obs
        from dmlc_tpu.obs import trace as obs_trace

        def read():
            flat = obs.registry().flat_values()
            return {key: flat.get(name, 0.0) for key, name in {
                "steps": 'dmlc_fit_steps_total{model="ffm"}',
                "aware": 'dmlc_fit_field_aware_steps_total{model="ffm"}',
                "stateful": 'dmlc_fit_stateful_update_steps_total'
                            '{model="ffm",optimizer="adagrad"}',
                "sharded":
                    'dmlc_fit_sharded_table_steps_total{model="ffm"}',
                "bytes": 'dmlc_fit_optimizer_state_bytes{model="ffm"}',
            }.items()}

        on = None if placement == "one-device" else mesh
        over = {} if on is None else {"table_sharding": "factors"}
        spans = []
        obs_trace.add_listener(spans.append)
        try:
            before = read()
            model = _learner(cfg, on, **over)
            feed = _feed(cfg, data["path"], on)
            model.fit_feed(feed, epochs=1)
            feed.close()
            after = read()
        finally:
            obs_trace.remove_listener(spans.append)
        steps = cfg["rows"] // cfg["batch_rows_per_chip"]
        shards = 1 if on is None else CHIPS
        for key in ("steps", "aware", "stateful"):
            assert after[key] - before[key] == steps, key
        assert after["sharded"] - before["sharded"] == (
            steps if shards > 1 else 0)
        assert after["bytes"] == 4 * cfg["num_features"] * model.columns \
            // shards
        (epoch,) = [e for e in spans if e["name"] == "epoch"
                    and e.get("ph") == "X"]
        assert epoch["args"]["model"] == "ffm"
        assert epoch["args"]["optimizer"] == "adagrad"
        assert epoch["args"]["fields"] == len(cfg["field_sizes"])
        assert epoch["args"]["table_shards"] == shards


class TestLoweredStep:
    """(h): the step's structure, from its jaxpr and its lowered text."""

    F, K, ROWS, SIZES = 1003, 2, 64, (2, 100, 900)

    def _lowered(self, mesh):
        shapes = jax.eval_shape(lambda: init_ffm_params(
            self.F, self.K, len(self.SIZES)))
        sections = 1 if mesh is None else CHIPS
        batch = {
            "label": jnp.zeros(self.ROWS), "weight": jnp.ones(self.ROWS),
            "indices": jnp.ones(self.ROWS * 3, jnp.int32),
            "values": jnp.ones(self.ROWS * 3),
            "offsets": jnp.tile(jnp.arange(
                self.ROWS // sections + 1, dtype=jnp.int32) * 3, sections)}
        kw = {} if mesh is None else {"table_sharding": "factors"}
        step = make_ffm_train_step(mesh, self.F, self.SIZES, **kw)
        step = getattr(step, "__wrapped__", step)
        return (step.lower(shapes, batch).as_text(debug_info=True),
                jax.make_jaxpr(step)(shapes, batch))

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["one-device", "factors"])
    def test_h_one_sort_rows_read_once_rows_set(self, mesh, sharded):
        text, jaxpr = self._lowered(mesh if sharded else None)
        for scope in ("step.order", "step.gather", "step.fields",
                      "step.forward", "step.backward", "step.state",
                      "step.update"):
            assert scope in text, scope
        assert ("step.exchange" in text) == sharded

        def walk(j):
            for eqn in j.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        eqns = list(walk(jaxpr.jaxpr))
        names = [e.primitive.name for e in eqns]
        # the head's two sorts (entries; the distinct ids to the front)
        assert names.count("sort") == 2
        # both tables are SET at the distinct ids and none is added into:
        # the scatter-adds left have batch-sized targets (the offsets'
        # marks, the (row, field) sums, an id's entries)
        assert names.count("scatter") == 2
        adds = [e for e in eqns if e.primitive.name == "scatter-add"]
        assert len(adds) == 3
        assert all(self.F not in e.outvars[0].aval.shape for e in adds)
        # gathers from a table: v in the head's loop, a in the state's
        from_table = [e for e in eqns if e.primitive.name == "gather"
                      and e.invars[0].aval.shape[0] == self.F]
        assert len(from_table) == 2
        # nothing of a table's shape is made besides the tables
        made = [e for e in eqns
                if e.primitive.name not in ("scatter", "while", "pjit",
                                            "shard_map", "jit")
                and any(getattr(v.aval, "shape", ())[:1] == (self.F,)
                        for v in e.outvars)]
        assert made == [], [e.primitive.name for e in made]
        # one psum of f32[rows] on a mesh, none on one device
        psums = [e for e in eqns if e.primitive.name == "psum"]
        assert [e.outvars[0].aval.shape for e in psums] == (
            [(self.ROWS,)] if sharded else [])


def test_i_the_new_cell_rehearses():
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data, init, check against the reference, window, result line)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "1", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True and "metrics" not in result
    assert result["device"]["count"] == 1
    assert {"rows_per_s", "setup_s"} <= set(result["metric_names"])
    detail = json.loads(lines[-2].split("[bench] detail ", 1)[1])
    assert detail["check"]["untouched_changed"] == 0
    assert detail["check"]["update_rel"] < 2e-5
    assert detail["check"]["loss_rel"] < 2e-6


class TestTheTwoReaders:
    """(j): ``step_fields_ms`` and ``field_aware_step_share`` on the
    recorded v5e trace of ``benchmarks/testdata`` (a plain FM run)."""

    @pytest.fixture(scope="class")
    def run(self, harness, tmp_path_factory):
        here = os.path.join(BENCH, "testdata")
        with open(os.path.join(here, "expected_restart.json")) as f:
            want = json.load(f)
        with open(os.path.join(here, want["spans"])) as f:
            spans = json.load(f)
        root = str(tmp_path_factory.mktemp("trace"))
        # laid out as harness/main.py leaves a traced run
        where = os.path.join(root, want["cell"], "trace", "plugins",
                             "profile", "recorded")
        os.makedirs(where)
        shutil.copy(os.path.join(here, want["trace"]), where)
        kept = harness.timeline.RUN_DIR
        harness.timeline.RUN_DIR = root
        harness.timeline._cache.clear()
        run = dict(want["run"], cell=want["cell"], spans=spans)
        run["trace"] = harness.xplane.reduce(
            harness.xplane.find_trace(
                os.path.join(root, want["cell"], "trace")),
            span_names=sorted({s["name"] for s in spans}),
            window=harness.timeline.WINDOW)
        yield run
        harness.timeline.RUN_DIR = kept
        harness.timeline._cache.clear()

    def _reader(self, harness, name):
        return harness.spec.load_module(
            os.path.join(BENCH, "metrics", name + ".py"))

    def test_j_no_scope_no_value(self, harness, run):
        """On a program without the scope (the parent's), and on a run
        with no trace, the reader gives nothing and does not raise."""
        reader = self._reader(harness, "step_fields_ms")
        assert reader.read(run) is None
        assert reader.read(dict(run, trace=None)) is None

    def test_j_the_scopes_device_time(self, harness, run):
        update = self._reader(harness, "step_update_ms")
        want = update.phases(run)["step.update"]
        scopes = harness.timeline.of_run(run).op_scopes()
        kept = dict(scopes)
        try:
            for op, scope in kept.items():
                scopes[op] = scope.replace("step.update", "step.fields")
            got = self._reader(harness, "step_fields_ms").read(run)
        finally:
            scopes.update(kept)
        assert got == pytest.approx(want) and got > 0

    @pytest.mark.parametrize("counters, want", [
        ({}, None),
        ({'dmlc_fit_steps_total{model="fm"}': 128.0}, None),
        ({'dmlc_fit_steps_total{model="ffm"}': 128.0,
          'dmlc_fit_field_aware_steps_total{model="ffm"}': 128.0}, 1.0),
        ({'dmlc_fit_steps_total{model="ffm"}': 64.0,
          'dmlc_fit_steps_total{model="fm"}': 64.0,
          'dmlc_fit_field_aware_steps_total{model="ffm"}': 64.0}, 0.5),
    ], ids=["a-parent", "another-model", "field-aware", "half"])
    def test_j_the_share_of_field_aware_steps(self, harness, run, counters,
                                              want):
        reader = self._reader(harness, "field_aware_step_share")
        assert reader.read(dict(run, counters=counters)) == want

    def test_j_the_benchmark_lists_both_for_the_new_cell_only(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
        assert listed["step_fields_ms"] == [CELL]
        assert listed["field_aware_step_share"] == [CELL]
        (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            "kdd12-ffm", "libsvm", 1)

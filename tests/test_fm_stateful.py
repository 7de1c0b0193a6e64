"""An FM under a rule that keeps state for every parameter row
(``FMLearner(optimizer="ftrl_adagrad")``: difacto's FTRL-proximal on ``w``
and AdaGrad on ``v``): on the suite's CPU devices, at the ``rehearse``
size of the ``kdd12-fm-difacto`` configuration (F=100,001, K=16, batches
of 1024), against that configuration's float64 numpy reference, which
imports nothing of ``dmlc_tpu.models``.
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
from dmlc_tpu.models import FMLearner, FtrlAdagrad, make_fm_train_step
from dmlc_tpu.models.fm import STATE_TABLES, init_fm_params
from dmlc_tpu.resilience import Preempted, preempt
from dmlc_tpu.utils.logging import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "kdd12-fm-difacto")
CELL = "kdd12-fm-difacto.libsvm"
CHIPS, STEPS = 4, 6
TABLES = ("w", "v") + STATE_TABLES
RULE = FtrlAdagrad._fields
SOME_RULE = FtrlAdagrad(l1=0.01, lr_beta=0.1, v_learning_rate=0.1,
                        v_lr_beta=0.1, v_l2=0.0)


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
    last five fields are folded onto a few hundred ids, so that ids
    repeat within a batch and across batches on both sides of the L1
    threshold."""
    rows = harness.config.rows(cfg, 2147483659)
    rows["ids"][:, 6:] = 40000 + rows["ids"][:, 6:] % 300
    path = str(tmp_path_factory.mktemp("difacto") / "rows.libsvm")
    harness.textgen.write_libsvm(
        path, rows["label"], rows["ids"], rows["value_text"],
        rows["pool_index"])
    return dict(rows, path=path)


def _learner(cfg, mesh=None, **over):
    hyper = {k: cfg[k] for k in (
        "objective", "learning_rate", "l2", "num_factors", "num_features",
        "init_scale", "optimizer") + RULE}
    hyper.update(over)
    return FMLearner(mesh=mesh, **hyper)


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


def _fit(cfg, data, mesh=None, seed=7, epochs=1, **over):
    """``epochs`` passes of ``fit_feed`` over the file from the program's
    own initialiser (one device's, so that every placement starts from
    the same bits); (learner, start, losses)."""
    model = _learner(cfg, mesh, **over)
    start = _host(init_fm_params(
        cfg["num_features"], cfg["num_factors"], cfg["init_scale"],
        seed=seed, optimizer=model.param.optimizer))
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
    before["b"] = np.float64(start["b"])
    ref_losses, ref = harness.config.reference_steps(cfg, before, batches)
    return types.SimpleNamespace(
        model=model, again=again, start=start, after=_host(model.params),
        losses=losses, touched=touched, before=before, ref=ref,
        ref_losses=ref_losses, batches=batches)


class TestAgainstTheReference:
    """(a)"""

    def test_a_each_steps_loss(self, trained):
        assert len(trained.losses) == STEPS
        np.testing.assert_allclose(
            trained.losses, trained.ref_losses, rtol=2e-6)

    def test_a_step_by_step_is_the_fit_loop(self, trained):
        for k, v in trained.after.items():
            np.testing.assert_array_equal(
                _bits(v), _bits(trained.again.params[k]), err_msg=k)

    @pytest.mark.parametrize("key", TABLES + ("b",))
    def test_a_weights_and_state_of_every_touched_row(self, trained, key):
        got = np.float64(trained.after[key])
        if key != "b":
            got = got[trained.touched]
        moved = np.max(np.abs(trained.ref[key] - trained.before[key]))
        assert moved > 0
        # in units of the table's largest change, as the benchmark's check
        assert np.max(np.abs(got - trained.ref[key])) / moved < 2e-5

    def test_a_both_sides_of_the_l1_threshold(self, trained):
        ref_zero = trained.ref["w"] == 0
        got_zero = trained.after["w"][trained.touched] == 0
        assert 0.1 < ref_zero.mean() < 0.9
        # an id an entry's rounding away from the threshold may differ
        assert np.mean(ref_zero != got_zero) < 1e-3
        # ids named in several batches among both
        named = np.bincount(np.concatenate(
            [np.unique(b["ids"]) for b in trained.batches]))
        assert (named[ref_zero] > 1).any() and (named[~ref_zero] > 1).any()

    @pytest.mark.parametrize("key", TABLES)
    def test_a_no_other_row_changed(self, trained, key):
        other = np.ones(len(trained.start[key]), bool)
        other[trained.touched] = False
        np.testing.assert_array_equal(
            _bits(trained.after[key][other]),
            _bits(trained.start[key][other]))
        if key in STATE_TABLES:
            assert not trained.start[key].any()  # the state starts at 0


class TestPaddedSlots:
    """(b): entries of value 0 (the feed's padding names feature 0; a file
    may write ``id:0``) change neither weights nor state."""

    F, K, B = 64, 4, 8

    def _step(self, v_l2, params, indices, values):
        step = make_fm_train_step(
            None, self.F, learning_rate=0.1,
            rule=SOME_RULE._replace(v_l2=v_l2))
        batch = {
            "label": jnp.asarray(np.arange(self.B) % 2, jnp.float32),
            "weight": jnp.ones(self.B),
            "indices": jnp.asarray(indices, jnp.int32),
            "values": jnp.asarray(values, jnp.float32),
            "offsets": jnp.arange(self.B + 1, dtype=jnp.int32) * 3}
        return _host(step(params, batch)[0])

    def _params(self):
        rng = np.random.RandomState(5)
        params = _host(init_fm_params(
            self.F, self.K, 0.3, seed=1, optimizer="ftrl_adagrad"))
        # a state no rule would have left: z and w that do not agree
        params["w"] = rng.randn(self.F).astype(np.float32)
        params["z"] = rng.randn(self.F).astype(np.float32)
        params["n"] = rng.rand(self.F).astype(np.float32)
        params["a"] = rng.rand(self.F, self.K).astype(np.float32)
        return params

    @pytest.mark.parametrize("v_l2", [0.0, 0.05])
    @pytest.mark.parametrize("key", TABLES)
    def test_b_a_slot_of_zero_values_keeps_its_row(self, v_l2, key):
        rng = np.random.RandomState(3)
        indices = rng.randint(10, 30, size=3 * self.B + 6)
        values = 0.5 + rng.rand(len(indices))
        # padding at feature 0; id 40 named twice, both times with value 0
        indices[-6:], values[-6:] = 0, 0.0
        indices[[1, 7]], values[[1, 7]] = 40, 0.0
        start = self._params()
        after = self._step(v_l2, start, indices, values)
        for row in (0, 40):
            np.testing.assert_array_equal(
                _bits(after[key][row]), _bits(start[key][row]))
        assert (_bits(after[key][10:30]) != _bits(start[key][10:30])).any()

    def test_b_a_value_beside_the_zeros_counts(self):
        """One entry with a value among an id's zero entries: the row is
        the rule's, decay included."""
        indices = np.full(3 * self.B, 40)
        values = np.zeros(len(indices))
        values[4] = 1.0
        start = self._params()
        after = self._step(0.05, start, indices, values)
        for key in TABLES:
            assert (_bits(after[key][40]) != _bits(start[key][40])).all()


class TestPlainSgdIsAsItWas:
    """(c): ``optimizer="sgd"`` (the default) builds the step it built
    before the field existed."""

    @pytest.mark.parametrize("placement", ["one-device", "factors"])
    def test_c_the_default_and_sgd_are_one_program(self, mesh, placement):
        args = (None, 1003) if placement == "one-device" else (mesh, 1003)
        kw = {} if placement == "one-device" else {
            "table_sharding": "factors"}
        shapes = jax.eval_shape(lambda: init_fm_params(1003, 16))
        assert sorted(shapes) == ["b", "v", "w"]
        batch = {
            "label": jnp.zeros(64), "weight": jnp.ones(64),
            "indices": jnp.ones(64 * 11, jnp.int32),
            "values": jnp.ones(64 * 11),
            "offsets": jnp.tile(jnp.arange(17, dtype=jnp.int32) * 11, 4)[
                :65 if placement == "one-device" else 68]}
        texts = []
        for more in ({}, {"rule": None}):
            step = make_fm_train_step(*args, **kw, **more)
            step = getattr(step, "__wrapped__", step)
            texts.append(step.lower(shapes, batch).as_text(debug_info=True))
        assert texts[0] == texts[1]
        assert "step.state" not in texts[0]
        assert "step.update" in texts[0]

    @pytest.mark.parametrize("optimizer", ["default", "sgd"])
    def test_c_fits_bit_equal_with_no_state(self, cfg, data, optimizer):
        over = {} if optimizer == "default" else {"optimizer": "sgd"}
        hyper = dict(num_features=cfg["num_features"], num_factors=16,
                     learning_rate=0.05)
        want = FMLearner(**hyper)
        got = FMLearner(**hyper, **over)
        for model in (want, got):
            feed = _feed(cfg, data["path"])
            model.fit_feed(feed, epochs=1)
            feed.close()
        assert sorted(got.params) == ["b", "v", "w"]
        assert got.rule is None and got.state_bytes() == 0
        for k in got.params:
            np.testing.assert_array_equal(
                _bits(got.params[k]), _bits(want.params[k]))


class TestFactorShardedMesh:
    """(d): the rule on a factor-sharded mesh, every chip its columns of
    ``v`` and ``a`` and a replica of ``w``, ``z``, ``n``."""

    @pytest.fixture(scope="class")
    def both(self, cfg, data, mesh):
        one, _, h1 = _fit(cfg, data, None, epochs=2)
        four, _, h4 = _fit(
            cfg, data, mesh, epochs=2, table_sharding="factors")
        return types.SimpleNamespace(one=one, four=four, h1=h1, h4=h4)

    def test_d_losses(self, both):
        np.testing.assert_allclose(both.h4, both.h1, rtol=2e-6)

    @pytest.mark.parametrize("key", TABLES + ("b",))
    def test_d_equals_the_single_device_step(self, both, key):
        np.testing.assert_allclose(
            np.asarray(both.four.params[key]),
            np.asarray(both.one.params[key]), rtol=2e-4, atol=1e-9)

    @pytest.mark.parametrize("key", ["w", "z", "n", "b"])
    def test_d_replicas_stay_bit_equal(self, both, key):
        first, *rest = [np.asarray(s.data)
                        for s in both.four.params[key].addressable_shards]
        assert len(rest) == CHIPS - 1
        for other in rest:
            np.testing.assert_array_equal(_bits(first), _bits(other))

    @pytest.mark.parametrize("key", ["v", "a"])
    def test_d_the_state_is_split_as_its_weights_are(self, both, cfg, key):
        arr = both.four.params[key]
        assert arr.sharding.spec == P(None, "dp")
        assert arr.addressable_shards[0].data.shape == (
            cfg["num_features"], cfg["num_factors"] // CHIPS)

    def test_d_state_bytes_are_one_chips(self, both, cfg):
        f, k = cfg["num_features"], cfg["num_factors"]
        assert both.one.state_bytes() == 4 * f * (k + 2)
        assert both.four.state_bytes() == 4 * f * (k // CHIPS + 2)


class TestRefusals:
    """(e)"""

    def test_e_a_mesh_of_replicas_refuses_the_learner(self, cfg, mesh):
        with pytest.raises(DMLCError, match="replicated mesh step"):
            _learner(cfg, mesh)

    def test_e_a_mesh_of_replicas_refuses_the_step(self, mesh):
        with pytest.raises(DMLCError, match="table_sharding='factors'"):
            make_fm_train_step(mesh, 1003, rule=SOME_RULE)

    def test_e_an_unknown_optimizer_is_refused(self):
        with pytest.raises(Exception, match="optimizer"):
            FMLearner(num_features=8, optimizer="adam")

    @pytest.mark.parametrize("held", ["sgd", "ftrl_adagrad"])
    def test_e_a_snapshot_of_the_other_rule_is_refused(self, cfg, held):
        other = "sgd" if held == "ftrl_adagrad" else "ftrl_adagrad"
        small = dict(cfg, num_features=64)
        params = _host(init_fm_params(64, cfg["num_factors"],
                                      optimizer=held))
        with pytest.raises(DMLCError, match="optimizer state"):
            _learner(small, optimizer=other).restore_snapshot_model(
                {"params": params})


class TestSnapshot:
    """(f)"""

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
        assert sorted(held) == sorted(TABLES + ("b",))
        # the state as the one logical table each is, and not at rest
        assert held["a"].shape == (cfg["num_features"], cfg["num_factors"])
        assert held["a"].any() and held["z"].any() and held["n"].any()

        resumed = _learner(cfg, on, **over)
        history = resumed.fit_uri(
            data["path"], snapshot_uri=snap_uri, resume=True, **kw)
        assert history == want
        for k in TABLES + ("b",):
            np.testing.assert_array_equal(
                _bits(resumed.params[k]), _bits(clean.params[k]), err_msg=k)

    @pytest.mark.parametrize("to", ["one-device", "factors"])
    def test_f_a_snapshot_restores_under_another_placement(
            self, cfg, data, mesh, trained, to):
        """The part holds each table whole: a mesh takes what one device
        wrote and one device what a mesh wrote, and goes on as the other
        would have."""
        from dmlc_tpu.collective.checkpoint import _to_host

        source = trained.model
        if to == "one-device":
            source, _, _ = _fit(cfg, data, mesh, table_sharding="factors")
        part = _to_host({"params": dict(source.params)})
        assert all(type(v) is np.ndarray for v in part["params"].values())
        target = _learner(cfg, mesh, table_sharding="factors") \
            if to == "factors" else _learner(cfg)
        target.restore_snapshot_model(part)
        for k in TABLES + ("b",):
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
        model.reshard(Mesh(np.asarray(jax.devices()[:2]), ("dp",)))
        assert model.params["a"].sharding.spec == P(None, "dp")
        assert model.params["a"].addressable_shards[0].data.shape[1] == \
            cfg["num_factors"] // 2
        for k, v in before.items():
            np.testing.assert_array_equal(_bits(model.params[k]), _bits(v))

    def test_f_predict_ignores_the_state(self, trained, data, cfg):
        model = trained.model
        feed = _feed(cfg, data["path"])
        batch = next(iter(feed))
        plain = _learner(cfg, optimizer="sgd")
        plain.restore_snapshot_model({"params": {
            k: v for k, v in trained.after.items()
            if k not in STATE_TABLES}})
        np.testing.assert_array_equal(
            model.predict_batch(batch), plain.predict_batch(batch))
        feed.close()


class TestCounters:
    """(g)"""

    @pytest.mark.parametrize("optimizer", ["ftrl_adagrad", "sgd"])
    def test_g_counter_gauge_and_span_arg(self, cfg, data, optimizer):
        from dmlc_tpu import obs
        from dmlc_tpu.obs import trace as obs_trace

        def read():
            flat = obs.registry().flat_values()
            return {
                "steps": flat.get('dmlc_fit_steps_total{model="fm"}', 0.0),
                "stateful": flat.get(
                    'dmlc_fit_stateful_update_steps_total'
                    '{model="fm",optimizer="%s"}' % optimizer),
                "bytes": flat.get(
                    'dmlc_fit_optimizer_state_bytes{model="fm"}')}

        spans = []
        obs_trace.add_listener(spans.append)
        try:
            before = read()
            model = _learner(cfg, optimizer=optimizer)
            feed = _feed(cfg, data["path"])
            model.fit_feed(feed, epochs=1)
            feed.close()
            after = read()
        finally:
            obs_trace.remove_listener(spans.append)
        steps = cfg["rows"] // cfg["batch_rows_per_chip"]
        stateful = optimizer != "sgd"
        assert after["steps"] - before["steps"] == steps
        assert after["stateful"] - (before["stateful"] or 0.0) == (
            steps if stateful else 0)
        assert after["bytes"] == (
            4 * cfg["num_features"] * (cfg["num_factors"] + 2)
            if stateful else 0)
        (epoch,) = [e for e in spans if e["name"] == "epoch"
                    and e.get("ph") == "X"]
        assert epoch["args"]["optimizer"] == optimizer
        assert epoch["args"]["table_shards"] == 1


class TestLoweredStep:
    """(h): the stateful step's structure, from its jaxpr and its lowered
    text."""

    F, K, ROWS = 1003, 16, 64

    def _lowered(self, mesh):
        shapes = jax.eval_shape(lambda: init_fm_params(
            self.F, self.K, optimizer="ftrl_adagrad"))
        sections = 1 if mesh is None else CHIPS
        batch = {
            "label": jnp.zeros(self.ROWS), "weight": jnp.ones(self.ROWS),
            "indices": jnp.ones(self.ROWS * 11, jnp.int32),
            "values": jnp.ones(self.ROWS * 11),
            "offsets": jnp.tile(jnp.arange(
                self.ROWS // sections + 1, dtype=jnp.int32) * 11, sections)}
        kw = {} if mesh is None else {"table_sharding": "factors"}
        step = make_fm_train_step(mesh, self.F, rule=SOME_RULE, **kw)
        step = getattr(step, "__wrapped__", step)
        return (step.lower(shapes, batch).as_text(debug_info=True),
                jax.make_jaxpr(step)(shapes, batch))

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["one-device", "factors"])
    def test_h_one_sort_state_read_once_rows_set(self, mesh, sharded):
        text, jaxpr = self._lowered(mesh if sharded else None)
        assert "step.state" in text and "step.update" in text

        def walk(j):
            for eqn in j.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        eqns = list(walk(jaxpr.jaxpr))
        names = [e.primitive.name for e in eqns]
        # the head's two sorts (entries; the distinct ids to the front)
        assert names.count("sort") == 2
        # every table is SET at the distinct ids (five scatters) and none
        # is added into: the scatter-adds left have batch-sized targets
        # (the offsets' marks, the row sums, an id's entries)
        assert names.count("scatter") == 5
        adds = [e for e in eqns if e.primitive.name == "scatter-add"]
        assert len(adds) == 3
        assert all(self.F not in e.outvars[0].aval.shape for e in adds)
        # gathers from a table: v, w in one loop, a, z, n in another
        from_table = [e for e in eqns if e.primitive.name == "gather"
                      and e.invars[0].aval.shape[0] == self.F]
        assert len(from_table) == 5
        # nothing of a table's shape is made besides the tables
        made = [e for e in eqns
                if e.primitive.name not in ("scatter", "while", "pjit",
                                            "shard_map", "jit")
                and any(getattr(v.aval, "shape", ())[:1] == (self.F,)
                        for v in e.outvars)]
        assert made == [], [e.primitive.name for e in made]


def test_i_the_new_cell_rehearses():
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data, init, check against the reference with the state's tables
    among those compared, window, result line)."""
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


class TestTheTwoReaders:
    """(j): ``step_state_ms`` and ``stateful_update_step_share`` on the
    recorded v5e trace of ``benchmarks/testdata`` (a plain SGD run)."""

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
        reader = self._reader(harness, "step_state_ms")
        assert reader.read(run) is None
        assert reader.read(dict(run, trace=None)) is None

    def test_j_the_scopes_device_time(self, harness, run):
        """With the recorded run's ``step.update`` operations renamed, the
        reader gives what the update took there."""
        update = self._reader(harness, "step_update_ms")
        want = update.phases(run)["step.update"]
        scopes = harness.timeline.of_run(run).op_scopes()
        kept = dict(scopes)
        try:
            for op, scope in kept.items():
                scopes[op] = scope.replace("step.update", "step.state")
            got = self._reader(harness, "step_state_ms").read(run)
        finally:
            scopes.update(kept)
        assert got == pytest.approx(want) and got > 0

    @pytest.mark.parametrize("counters, want", [
        ({}, None),
        ({'dmlc_fit_steps_total{model="fm"}': 128.0}, None),
        ({'dmlc_fit_steps_total{model="fm"}': 128.0,
          'dmlc_fit_stateful_update_steps_total'
          '{model="fm",optimizer="sgd"}': 0.0}, 0.0),
        ({'dmlc_fit_steps_total{model="fm"}': 128.0,
          'dmlc_fit_stateful_update_steps_total'
          '{model="fm",optimizer="ftrl_adagrad"}': 128.0}, 1.0),
    ], ids=["a-parent", "no-counter", "plain-sgd", "stateful"])
    def test_j_the_share_of_stateful_steps(self, harness, run, counters,
                                           want):
        reader = self._reader(harness, "stateful_update_step_share")
        assert reader.read(dict(run, counters=counters)) == want

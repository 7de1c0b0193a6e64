"""The check reads a learner's tables through five calls
(``harness/tables.py``). Pinned here, on the CPU at ``--rehearse`` sizes:

(a) the calls tell the truth about the storage: rows and fingerprints
    agree with ``snapshot_model()``'s arrays after the check's steps;
(b) ``check.run`` never reads ``params``: a learner whose ``params``
    raises when the check asks for it still passes;
(c) a layout double that keeps ``[v | w]`` as ONE ``f32[F, K+1]`` passes
    the check against kdd12-fm's own reference with the unpacked
    learner's readings to the last digit: the five calls are enough for a
    learner that packs its tables;
(d) a whole ``--rehearse`` run (only the look for a chip skipped) with the
    timed path broken underneath comes out with ``correct`` false, by the
    reading meant for that fault;
(e) ``init_tables(seed)`` gives the learner's own shapes and placement,
    the same values for the same seed and other values for another.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dmlc_tpu import models
from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import FFMLearner, FMLearner, LinearLearner
from dmlc_tpu.models.fm import init_fm_params
from harness import check, main, spec, tables, textgen

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")
SEED = 2147483659  # beyond 32 signed bits, like the driver's
CHIPS = 2  # of the virtual mesh: kdd12-ffm's rehearsal keeps two factors

FM = ("objective", "learning_rate", "l2", "num_factors", "num_features",
      "init_scale")
RULE = ("optimizer", "l1", "lr_beta", "v_learning_rate", "v_lr_beta", "v_l2")
#: case -> (configuration, learner, the hyper-parameters it takes from it)
LEARNERS = {
    "fm-sgd": ("kdd12-fm", FMLearner, FM),
    "fm-ftrl_adagrad": ("kdd12-fm-difacto", FMLearner, FM + RULE),
    "ffm": ("kdd12-ffm", FFMLearner, FM + ("field_sizes", "a_init")),
    "linear": ("criteo-linear", LinearLearner,
               ("objective", "learning_rate", "l2", "momentum",
                "num_features")),
}
PLACES = ("one-device", "mesh")


def _configuration(name):
    """The configuration at its ``--rehearse`` size, and its module."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg.get("rehearse", {}))
    return cfg, spec.load_module(os.path.join(CONFIGS, name + ".py"))


def _mesh(place):
    if place == "one-device":
        return None
    return Mesh(np.asarray(jax.devices()[:CHIPS]), ("dp",))


def _learner(case, cfg, mesh, cls=None):
    _, learner, names = LEARNERS[case]
    hyper = {n: cfg[n] for n in names}
    if mesh is not None and case != "linear":
        hyper["table_sharding"] = "factors"
    return (cls or learner)(mesh=mesh, **hyper)


def _drive(case, place, tmp_path, cls=None, seed=SEED):
    """The check's steps through a learner of ``case``; returns the
    learner, the check's facts and the run's data and sizes."""
    mesh = _mesh(place)
    chips = 1 if mesh is None else mesh.size
    cfg, config = _configuration(LEARNERS[case][0])
    cfg["rows"] = 8 * chips * cfg["batch_rows_per_chip"]  # the check takes 4
    data = config.rows(cfg, seed)
    path = str(tmp_path / "rows.libsvm")
    textgen.write_libsvm(path, data["label"], data["ids"],
                         data["value_text"], data["pool_index"])
    model = _learner(case, cfg, mesh, cls)
    config.init_params(cfg, seed, model, mesh)
    feed = DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=cfg["batch_rows_per_chip"] * chips,
                  layout=cfg["layout"], num_features=cfg["num_features"]),
        mesh=mesh)
    try:
        facts = check.run(types.SimpleNamespace(cfg=cfg, config=config),
                          model, feed, data, int(cfg["check"]["steps"]))
    finally:
        feed.close()
    return types.SimpleNamespace(model=model, facts=facts, data=data, cfg=cfg)


def _fingerprints(table):
    bits = np.ascontiguousarray(table).view(np.uint32)
    return bits if bits.ndim == 1 else bits.sum(axis=1, dtype=np.uint32)


def _sample_ids(run):
    """Ids the check's batches touched, and as many that none did."""
    steps = int(run.cfg["check"]["steps"])
    rows = steps * run.cfg["batch_rows_per_chip"] * (
        1 if run.model.mesh is None else run.model.mesh.size)
    touched = np.unique(run.data["ids"][:rows])
    others = np.setdiff1d(
        np.arange(run.cfg["num_features"]), touched)[:: 97][:256]
    assert len(touched) > 100 and len(others) > 100
    return touched[::7], others


# ---- (a) the calls against the storage ------------------------------------

@pytest.mark.parametrize("place", PLACES)
@pytest.mark.parametrize("case", sorted(LEARNERS))
def test_a_rows_and_fingerprints_agree_with_the_snapshot(
        case, place, tmp_path):
    run = _drive(case, place, tmp_path)
    assert run.facts["ok"], run.facts
    stored = {k: np.asarray(v) for k, v in
              run.model.snapshot_model()["params"].items()}
    learner = tables.of(run.model)
    names = learner.table_names()
    assert sorted(names) == sorted(k for k, v in stored.items() if v.ndim)
    assert learner.scalars() == {
        k: float(v) for k, v in stored.items() if not v.ndim}
    for name in names:
        for ids in _sample_ids(run):
            got = learner.table_rows(name, jnp.asarray(ids, jnp.int32))
            np.testing.assert_array_equal(np.asarray(got), stored[name][ids])
        prints = learner.table_fingerprints(name)
        assert prints.dtype == jnp.uint32
        np.testing.assert_array_equal(
            np.asarray(prints), _fingerprints(stored[name]))
    if place == "mesh" and case != "linear":
        # the readers work on the column slices where they lie
        wide = run.model.params["v"]
        assert {s.data.shape for s in wide.addressable_shards} == {
            (wide.shape[0], wide.shape[1] // CHIPS)}


def test_a_a_learner_with_some_of_the_calls_is_refused():
    class Half(FMLearner):
        def table_rows(self, name, ids):
            raise NotImplementedError

    cfg, _ = _configuration("kdd12-fm")
    with pytest.raises(SystemExit, match="owes the check all of them"):
        tables.of(_learner("fm-sgd", cfg, None, Half))


# ---- (b), (c) a layout of the learner's own --------------------------------

class PackedFM(FMLearner):
    """The FM with ``[v | w]`` as ONE ``f32[F, K+1]`` array: the
    program's own step on the parts, re-packed after it (plain
    ``jax.numpy``; speed is no object). ``params`` is the packed tree, for
    the fit loop and the window, and raises when the check reads it."""

    def __init__(self, mesh=None, **hyper):
        self._packed = None
        super().__init__(mesh, **hyper)

    @property
    def params(self):
        asker = sys._getframe(1).f_code.co_filename
        if os.path.dirname(asker) == os.path.dirname(check.__file__):
            raise AssertionError("%s read the learner's params" % asker)
        return self._packed

    @params.setter
    def params(self, tree):
        self._packed = tree

    def _pack(self, parts):
        return {"vw": jnp.concatenate(
            [parts["v"], parts["w"][:, None]], axis=1), "b": parts["b"]}

    def ensure_step(self, spec_):
        if self._step is None:
            self._step = self._make_step(self.param.num_features)

    def train_step(self, arrays):
        k = self.param.num_factors
        vw = self._packed["vw"]
        parts, metrics = self._step(
            {"v": vw[:, :k], "w": vw[:, k], "b": self._packed["b"]}, arrays)
        self._packed = self._pack(parts)
        return metrics

    # the five calls
    def init_tables(self, seed):
        self._packed = jax.jit(lambda key: self._pack(init_fm_params(
            self.param.num_features, self.param.num_factors,
            self.param.init_scale, key)))(jnp.uint32(int(seed) % (1 << 32)))

    def table_names(self):
        return ("v", "w")

    def scalars(self):
        return {"b": float(self._packed["b"])}

    def _columns(self, rows, name):
        k = self.param.num_factors
        return rows[:, :k] if name == "v" else rows[:, k]

    def table_rows(self, name, ids):
        return self._columns(jnp.take(self._packed["vw"], ids, axis=0), name)

    def table_fingerprints(self, name):
        bits = self._columns(jax.lax.bitcast_convert_type(
            self._packed["vw"], jnp.uint32), name)
        return bits if bits.ndim == 1 else jnp.sum(
            bits, axis=1, dtype=jnp.uint32)


def test_bc_a_packed_learner_passes_with_the_unpacked_readings(tmp_path):
    plain = _drive("fm-sgd", "one-device", tmp_path)
    packed = _drive("fm-sgd", "one-device", tmp_path, cls=PackedFM)
    assert tables.of(packed.model) is packed.model
    assert packed.model._packed["vw"].shape == (
        packed.cfg["num_features"], packed.cfg["num_factors"] + 1)
    with pytest.raises(AssertionError, match="read the learner's params"):
        tables.StoredParams(packed.model).table_names()
    assert packed.facts["ok"], packed.facts
    assert packed.facts == plain.facts
    # and the storage went where the unpacked learner's went
    k = packed.cfg["num_factors"]
    np.testing.assert_array_equal(
        np.asarray(packed.model._packed["vw"][:, :k]),
        np.asarray(plain.model.params["v"]))
    np.testing.assert_array_equal(
        np.asarray(packed.model._packed["vw"][:, k]),
        np.asarray(plain.model.params["w"]))


# ---- (d) a whole run over a broken step ------------------------------------

def _kept(array):
    """A copy the donating step cannot take."""
    return jnp.array(array, copy=True)


class TouchesASpareRow(FMLearner):
    """Every step also moves one row of ``w`` that no batch names."""

    spare = None

    def train_step(self, arrays):
        metrics = super().train_step(arrays)
        self.params = dict(
            self.params, w=self.params["w"].at[self.spare].add(1.0))
        return metrics


class OverstepsTouchedRows(FMLearner):
    """The touched rows of ``v`` move by 1.001 of their update."""

    def train_step(self, arrays):
        old, at = _kept(self.params["v"]), arrays["indices"]
        metrics = super().train_step(arrays)
        new = self.params["v"]
        self.params = dict(self.params, v=new.at[at].set(
            old[at] + 1.001 * (new[at] - old[at])))
        return metrics


class SkipsTheStateWrite(FMLearner):
    """AdaGrad's accumulator ``a`` is never written."""

    def train_step(self, arrays):
        a = _kept(self.params["a"])
        metrics = super().train_step(arrays)
        self.params = dict(self.params, a=a)
        return metrics


class ReturnsItsStateUnchanged(FMLearner):
    """The step runs and its new state is dropped."""

    def train_step(self, arrays):
        kept = jax.tree_util.tree_map(_kept, self.params)
        metrics = super().train_step(arrays)
        self.params = kept
        return metrics


class LeavesHalfTheBatchOut(FMLearner):
    """The second half of every batch has weight 0: the step's mean is
    taken over the rest."""

    def train_step(self, arrays):
        weight = jnp.asarray(arrays["weight"])
        return super().train_step(dict(
            arrays, weight=weight.at[weight.shape[0] // 2:].set(0.0)))


def _over(reading):
    return lambda compared, _: compared[reading][0] > compared[reading][1]


#: the double, its cell, and what the run's numbers must show
FAULTS = {
    "a_row_no_batch_names": (
        TouchesASpareRow, "kdd12-fm.libsvm",
        lambda compared, _: compared["untouched_changed"] == [1, 0]
        and not _over("update_rel")(compared, _)),
    "a_touched_row_oversteps": (
        OverstepsTouchedRows, "kdd12-fm.libsvm",
        lambda compared, _: _over("update_rel")(compared, _)
        and not _over("loss_rel")(compared, _)
        and compared["untouched_changed"] == [0, 0]),
    "the_state_table_unwritten": (
        SkipsTheStateWrite, "kdd12-fm-difacto.libsvm",
        lambda compared, facts: _over("update_rel")(compared, facts)
        and facts["update_rel_of"]["a"] == 1.0),
    "the_state_returned_unchanged": (
        ReturnsItsStateUnchanged, "kdd12-fm.libsvm",
        lambda compared, facts: facts["update_rel_of"]["v"] == 1.0
        and facts["update_rel_of"]["w"] == 1.0),
    "half_the_batch_left_out": (
        LeavesHalfTheBatchOut, "kdd12-fm.libsvm",
        lambda compared, _: _over("loss_rel")(compared, _)
        and _over("update_rel")(compared, _)),
}


@pytest.fixture
def a_run(tmp_path, monkeypatch, capsys):
    """``main.run`` in this process, its scratch and its compile cache
    under ``tmp_path``; returns (exit code, result line, detail line)."""
    settings = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
    kept = {name: getattr(jax.config, name) for name in settings}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    monkeypatch.setattr(main, "RUN_DIR", str(tmp_path / "run"))

    def run(workload, seed=SEED):
        code = main.run(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        detail = next(line for line in lines
                      if line.startswith("[bench] detail "))
        return code, json.loads(lines[-1]), json.loads(
            detail[len("[bench] detail "):]), err

    yield run
    for name, value in kept.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_d_a_broken_step_comes_out_not_correct(fault, a_run, monkeypatch):
    double, workload, shows = FAULTS[fault]
    cfg, config = _configuration(workload.rsplit(".", 1)[0])
    named = np.unique(config.rows(cfg, SEED)["ids"])
    monkeypatch.setattr(double, "spare", int(np.setdiff1d(
        np.arange(cfg["num_features"]), named)[0]), raising=False)
    monkeypatch.setattr(models, "FMLearner", double)
    code, result, detail, _ = a_run(workload)
    assert code == 1 and result["correct"] is False
    assert list(result)[-1] == "compared"
    assert shows(result["compared"], detail["check"]), (
        result["compared"], detail["check"])


def test_d_the_sound_step_comes_out_correct_and_says_what_it_compared(a_run):
    code, result, detail, err = a_run("kdd12-fm.libsvm")
    assert code == 0 and result["correct"] is True
    assert list(result)[-1] == "compared"
    for name, (value, limit) in result["compared"].items():
        assert value <= limit, name
        assert "[bench] compared %s %r limit %r" % (name, value, limit) in err
    assert err.strip().splitlines()[-1].startswith("[bench] compared ")
    # the window's deltas of the counters no metric reads
    counters = detail["counters"]
    assert sorted(counters) == sorted(main.PRINTED_COUNTERS)
    assert counters["dmlc_feed_restarts_total"] == detail["full_passes"]
    assert 0 < counters["dmlc_fit_touched_rows_total"] \
        < counters["dmlc_fit_entries_total"]


# ---- (e) the learner's storage from a seed ---------------------------------

@pytest.mark.parametrize("place", PLACES)
@pytest.mark.parametrize("case", sorted(LEARNERS))
def test_e_init_tables_is_the_learners_own_start(case, place):
    mesh = _mesh(place)
    cfg, config = _configuration(LEARNERS[case][0])

    def started(seed):
        model = _learner(case, cfg, mesh)
        config.init_params(cfg, seed, model, mesh)
        return model

    own = _learner(case, cfg, mesh)
    own.ensure_step(BatchSpec(batch_size=64, layout=cfg["layout"],
                              num_features=cfg["num_features"]))
    first, again, other = started(SEED), started(SEED), started(SEED + 1)
    for attr in own.state_trees:
        want, got = getattr(own, attr), getattr(first, attr)
        assert sorted(got) == sorted(want)
        for k in want:
            assert got[k].shape == want[k].shape, (attr, k)
            assert got[k].dtype == want[k].dtype, (attr, k)
            if mesh is not None:
                assert got[k].sharding.is_equivalent_to(
                    want[k].sharding, got[k].ndim), (attr, k)
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(getattr(again, attr)[k]))
    if case != "linear":  # which starts at zero whatever the seed
        assert not np.array_equal(np.asarray(first.params["v"]),
                                  np.asarray(other.params["v"]))

"""The one fit loop (models/fitloop.py fit_feed) driven through each
learner that delegates to it: preemption, resume, the spans and counters
of a pass, and ``log_every``. Whatever holds here holds for linear and FM
alike, because there is one loop."""

import jax
import numpy as np
import pytest

from dmlc_tpu import obs, resilience
from dmlc_tpu.collective import JobSnapshot
from dmlc_tpu.models import FMLearner, LinearLearner, fitloop
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.resilience import EXIT_PREEMPTED, Preempted, preempt

NFEAT = 12
ROWS = 160
BATCH = 16
STEPS = ROWS // BATCH  # a pass
EPOCHS = 4


@pytest.fixture(autouse=True)
def _clean_state():
    resilience.reset()
    preempt.reset()
    yield
    resilience.reset()
    preempt.reset()
    preempt.uninstall()


@pytest.fixture
def train_file(tmp_path):
    rng = np.random.RandomState(7)
    path = tmp_path / "fit.svm"
    with open(path, "w") as fh:
        for i in range(ROWS):
            ids = np.sort(rng.choice(NFEAT, size=4, replace=False))
            fh.write("%d %s\n" % (i % 2, " ".join(
                "%d:%.4f" % (j, rng.rand()) for j in ids)))
    return str(path)


def _learner(model):
    if model == "linear":
        return LinearLearner(learning_rate=0.5, num_features=NFEAT)
    return FMLearner(learning_rate=0.1, num_features=NFEAT, num_factors=4)


def _fit(model, path, epochs, **kw):
    learner = _learner(model)
    if model == "linear":  # FM's is csr by definition
        kw["layout"] = "csr"
    history = learner.fit_uri(path, batch_size=BATCH, epochs=epochs,
                              drop_remainder=True, **kw)
    return learner, history


def _preempt_mid_epoch_2(model, path, snap_uri):
    # one poll a step: pass 25 is epoch 2, step 5, with the boundary
    # snapshots of epochs 0 and 1 committed
    resilience.configure("preempt.notice:nth=%d" % (2 * STEPS + 5))
    try:
        with pytest.raises(Preempted) as excinfo:
            _fit(model, path, EPOCHS, snapshot_uri=snap_uri)
    finally:
        resilience.reset()
        preempt.reset()
    return excinfo.value


@pytest.mark.parametrize("model", ["linear", "fm"])
class TestOneLoopForEveryLearner:
    def test_preemption_notice_finalizes_and_raises(self, train_file,
                                                    tmp_path, model):
        snap_uri = str(tmp_path / "snap")
        err = _preempt_mid_epoch_2(model, train_file, snap_uri)
        assert err.code == EXIT_PREEMPTED
        assert err.message == ("preempted in epoch 2 after 5 steps; last "
                               "committed snapshot epoch 1")
        # the partial epoch was never committed; the boundary before it was
        _version, state, meta = JobSnapshot(snap_uri).restore()
        assert meta["epoch"] == 1 and state["epoch"] == 1
        assert len(state["history"]) == 2
        assert set(state["model"]) == (
            {"params", "velocity"} if model == "linear" else {"params"})

    def test_resume_is_bit_identical(self, train_file, tmp_path, model):
        clean, clean_history = _fit(model, train_file, EPOCHS)
        snap_uri = str(tmp_path / "snap")
        _preempt_mid_epoch_2(model, train_file, snap_uri)
        resumed, history = _fit(model, train_file, EPOCHS,
                                snapshot_uri=snap_uri, resume=True)
        assert history == clean_history
        assert sorted(resumed.params) == sorted(clean.params)
        for key in clean.params:
            np.testing.assert_array_equal(
                np.asarray(clean.params[key]), np.asarray(resumed.params[key]))

    @pytest.mark.parametrize("source", ["text", "dtsh-shuffled"])
    @pytest.mark.parametrize("polls", [2 * STEPS, 2 * STEPS + 1])
    def test_kill_at_an_epoch_boundary_resumes_bit_identical(
            self, train_file, tmp_path, model, source, polls):
        """The notice falls on the last step of epoch 1 or the first of
        epoch 2: the feed's producer had by then wound the parser on to
        the next epoch, and the snapshot of the boundary must still hold
        the boundary's read plan (the shuffle's epoch), not the parser's
        of the moment."""
        uri = train_file
        if source == "dtsh-shuffled":
            from dmlc_tpu.tools.bake import bake_dataset

            baked = str(tmp_path / "fit.dtsh")
            bake_dataset(train_file, baked, data_format="libsvm",
                         rows_per_window=BATCH)
            uri = baked + "?shuffle_chunks=7"
        clean, clean_history = _fit(model, uri, EPOCHS)
        snap_uri = str(tmp_path / "snap")
        resilience.configure("preempt.notice:nth=%d" % polls)
        try:
            with pytest.raises(Preempted):
                _fit(model, uri, EPOCHS, snapshot_uri=snap_uri)
        finally:
            resilience.reset()
            preempt.reset()
        _version, state, meta = JobSnapshot(snap_uri).restore()
        # a pass cut at its last step is a partial pass: never committed
        assert meta["epoch"] == (0 if polls == 2 * STEPS else 1)
        if source == "dtsh-shuffled":
            assert state["data"]["parser"]["epoch"] == meta["epoch"]
        resumed, history = _fit(model, uri, EPOCHS,
                                snapshot_uri=snap_uri, resume=True)
        assert history == clean_history
        for key in clean.params:
            np.testing.assert_array_equal(
                np.asarray(clean.params[key]), np.asarray(resumed.params[key]))

    def test_spans_and_counters_of_two_epochs(self, train_file, model):
        def counters():
            flat = obs.registry().flat_values()
            return [flat.get('dmlc_fit_%s_total{model="%s"}' % (k, model), 0)
                    for k in ("steps", "epochs")]

        seen = []
        before = counters()
        obs_trace.add_listener(seen.append)
        try:
            _fit(model, train_file, 2)
        finally:
            obs_trace.remove_listener(seen.append)
        assert [a - b for a, b in zip(counters(), before)] == [2 * STEPS, 2]
        loop = ("epoch", "train_step", "loss_readback", "epoch_close",
                "feed_restart")
        closed = [e for e in seen if e.get("ph") == "X" and e["name"] in loop]
        # in the order the spans close: a pass's steps inside its epoch
        # span, then the read-back, the close, and one restart between
        # the two passes
        one_pass = ["train_step"] * STEPS + [
            "epoch", "loss_readback", "epoch_close"]
        assert [e["name"] for e in closed] == (
            one_pass + ["feed_restart"] + one_pass)
        steps = [e for e in closed if e["name"] == "train_step"]
        assert [e["args"]["step"] for e in steps] == 2 * list(range(STEPS))
        assert [(e["args"]["pass_"], e["args"]["batch"]) for e in steps] == [
            (p, b) for p in (0, 1) for b in range(STEPS)]
        for e in closed:
            if e["name"] != "feed_restart":
                assert e["args"]["model"] == model, e
        epochs = [e for e in closed if e["name"] == "epoch"]
        assert [e["args"]["epoch"] for e in epochs] == [0, 1]
        for e in epochs:
            assert ("table_shards" in e["args"]) == (model == "fm")
            inside = [s for s in steps if e["ts"] <= s["ts"]
                      and s["ts"] + s["dur"] <= e["ts"] + e["dur"] + 1]
            assert len(inside) == STEPS

    def test_log_every_counts_epochs_and_never_syncs_in_a_pass(
            self, train_file, monkeypatch, model):
        """``log_every=1``: one line an epoch, and the host reads the
        device once an epoch, after the pass's last step."""
        events = []
        real_get = jax.device_get

        def device_get(tree):
            events.append("device_get")
            return real_get(tree)

        monkeypatch.setattr(jax, "device_get", device_get)
        monkeypatch.setattr(
            fitloop, "log_info",
            lambda msg, *args: events.append("log: " + (msg % args)))

        def on_span(e):
            if e.get("ph") == "X" and e["name"] in ("train_step", "epoch"):
                events.append(e["name"])

        obs_trace.add_listener(on_span)
        try:
            _, history = _fit(model, train_file, 2, log_every=1)
        finally:
            obs_trace.remove_listener(on_span)
        logs = [e for e in events if e.startswith("log: ")]
        assert len(logs) == 2
        for epoch, (line, loss) in enumerate(zip(logs, history)):
            assert line.startswith(
                "log: %s epoch %d loss %.6f" % (model, epoch, loss)), line
        shape = [e if not e.startswith("log: ") else "log" for e in events]
        one_pass = ["train_step"] * STEPS + ["epoch", "device_get", "log"]
        assert shape == one_pass + one_pass

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
        device once an epoch, after the pass's last step (the read-back's
        ``drain_wait``; a ``jax.device_get`` anywhere would show too)."""
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
            if e.get("ph") == "X" and e["name"] in (
                    "train_step", "epoch", "drain_wait"):
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
            # the pass's mean lead over the chip, beside the stall breakdown
            lead = float(line.split(" lead ")[1].split()[0])
            assert 0.0 <= lead <= STEPS, line
        shape = [e if not e.startswith("log: ") else "log" for e in events]
        one_pass = ["train_step"] * STEPS + ["epoch", "drain_wait", "log"]
        assert shape == one_pass + one_pass


class _ParentEpochMetrics:
    """``EpochMetrics`` as it was before the pass boundary was split: one
    ``device_get`` of everything pending, then the sums."""

    def __init__(self):
        self._pending = {}
        self.sums = {}

    def add(self, metrics):
        for name, scalar in metrics.items():
            self._pending.setdefault(name, []).append(scalar)

    def inflight(self, max_polls=None):
        return 0

    pending_scalars = 0

    def start_fetch(self):
        pass

    def drain(self):
        pass  # the one device_get below waits, as it used to

    def mean_loss(self):
        if self._pending:
            for name, values in jax.device_get(self._pending).items():
                self.sums[name] = self.sums.get(name, 0) + np.sum(
                    values).item()
            self._pending.clear()
        return self.sums.get("loss_sum", 0.0) / max(
            self.sums.get("weight_sum", 0.0), 1e-12)


@pytest.mark.parametrize("model", ["linear", "fm"])
class TestThePassBoundaryAndTheLead:
    def _hist(self, name, model):
        flat = obs.registry().flat_values()
        key = 'dmlc_fit_%s{model="%s"}' % (name, model)
        return flat.get(key + ":sum", 0.0), flat.get(key + ":count", 0.0)

    def test_readback_holds_the_drain_then_the_fetch(
            self, train_file, monkeypatch, model):
        """``drain_wait`` and ``loss_fetch`` inside ``loss_readback``, in
        that order, once a pass; what is fetched, and so every loss, is
        the parent's to the bit."""
        names = ("loss_readback", "drain_wait", "loss_fetch")
        seen = []
        obs_trace.add_listener(seen.append)
        try:
            _, history = _fit(model, train_file, 3)
        finally:
            obs_trace.remove_listener(seen.append)
        closed = [e for e in seen if e.get("ph") == "X"
                  and e["name"] in names]
        assert [e["name"] for e in closed] == 3 * [
            "drain_wait", "loss_fetch", "loss_readback"]
        scalars = {"linear": 2, "fm": 3}[model]  # a step's metrics
        for k in range(3):
            drain, fetch, outer = closed[3 * k: 3 * k + 3]
            assert outer["args"] == {"model": model, "epoch": k}
            for inner in (drain, fetch):
                assert outer["ts"] <= inner["ts"]
                assert (inner["ts"] + inner["dur"]
                        <= outer["ts"] + outer["dur"] + 1e-3)
            assert drain["ts"] + drain["dur"] <= fetch["ts"] + 1e-3
            assert 0 <= drain["args"]["steps"] <= STEPS
            assert fetch["args"] == {"scalars": (scalars - 1) * STEPS}
        monkeypatch.setattr(fitloop, "EpochMetrics", _ParentEpochMetrics)
        _, parent = _fit(model, train_file, 3)
        assert history == parent  # floats, to the bit

    def test_every_launch_carries_the_lead(self, train_file, model):
        lead_before = self._hist("inflight_steps", model)
        seen = []
        obs_trace.add_listener(seen.append)
        try:
            _fit(model, train_file, 2)
        finally:
            obs_trace.remove_listener(seen.append)
        steps = [e for e in seen if e.get("ph") == "X"
                 and e["name"] == "train_step"]
        assert len(steps) == 2 * STEPS
        leads = [e["args"]["inflight"] for e in steps]
        # read before the launch: nothing is in flight at a pass's head,
        # and never more than the steps launched so far
        for e, lead in zip(steps, leads):
            assert 0 <= lead <= e["args"]["step"]
        total, count = self._hist("inflight_steps", model)
        assert count - lead_before[1] == 2 * STEPS
        assert total - lead_before[0] == sum(leads)

    def test_tracing_off_the_lead_fills_and_the_spans_are_inert(
            self, train_file, monkeypatch, model):
        """No listener, no trace file: the lead is counted all the same,
        and the fit loop's spans, which have no counter, are the shared
        inert object: nothing is timed and nothing recorded."""
        assert not obs_trace._listeners
        obs.clear_trace()
        inert, real = {}, obs.span

        def span(name, hist=None, **args):
            made = real(name, hist=hist, **args)
            inert.setdefault(name, set()).add(made is obs.NOOP_SPAN)
            return made

        monkeypatch.setattr(obs, "span", span)
        before = self._hist("inflight_steps", model)[1]
        _fit(model, train_file, 2)
        assert self._hist("inflight_steps", model)[1] - before == 2 * STEPS
        for name in ("epoch", "train_step", "loss_readback", "drain_wait",
                     "loss_fetch", "epoch_close", "deliver"):
            assert inert[name] == {True}, name
        assert obs.trace_events() == []


class _Scalar:
    """A step's device scalar that turns ready on command."""

    def __init__(self):
        self.ready = False
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


class TestInflight:
    def _launch(self, acc, n):
        made = [_Scalar() for _ in range(n)]
        for s in made:
            # a step's scalars are outputs of one run: ready together
            acc.add({"loss_sum": s, "weight_sum": s})
        return made

    def test_the_lead_rises_with_launches_and_falls_as_steps_finish(self):
        acc = fitloop.EpochMetrics()
        assert acc.inflight() == 0
        steps = self._launch(acc, 5)
        assert acc.inflight() == 5
        for s in steps[:2]:
            s.ready = True
        assert acc.inflight() == 3
        steps += self._launch(acc, 2)
        assert acc.inflight() == 5
        for s in steps:
            s.ready = True
        assert acc.inflight(None) == 0
        # what turned ready is not asked again
        asked = [s.asked for s in steps]
        assert acc.inflight() == 0
        assert [s.asked for s in steps] == asked

    def test_steps_finish_in_order_so_the_first_unready_ends_the_poll(self):
        acc = fitloop.EpochMetrics()
        steps = self._launch(acc, 4)
        steps[2].ready = True  # cannot be: a later step before an earlier
        assert acc.inflight() == 4
        assert [s.asked for s in steps] == [1, 0, 0, 0]

    def test_a_call_asks_a_bounded_number_of_questions(self):
        acc = fitloop.EpochMetrics()
        steps = self._launch(acc, 20)
        for s in steps:
            s.ready = True
        bound = fitloop.EpochMetrics.MAX_POLLS
        assert acc.inflight() == 20 - bound
        assert sum(s.asked for s in steps) == bound
        assert acc.inflight() == 20 - 2 * bound
        assert acc.inflight(None) == 0

    def test_host_values_are_done_and_a_read_starts_over(self):
        acc = fitloop.EpochMetrics()
        for k in range(3):
            acc.add({"loss_sum": np.float32(k), "weight_sum": np.float32(1)})
        assert acc.pending_scalars == 6
        assert acc.inflight() == 0
        acc.drain()  # reads the first metric's, leaves the other's
        assert acc.pending_scalars == 3 and acc.sums == {"loss_sum": 3.0}
        assert acc.mean_loss() == 1.0
        assert acc.pending_scalars == 0 and acc.inflight() == 0
        late = self._launch(acc, 2)
        assert acc.inflight() == 2
        late[0].ready = True
        assert acc.inflight() == 1

    class _Copied(_Scalar):
        """A device scalar that tells what is asked of it."""

        def __init__(self, calls):
            super().__init__()
            self.calls = calls

        def copy_to_host_async(self):
            self.calls.append("copy")

        def __array__(self, dtype=None, copy=None):
            self.calls.append("read")
            return np.asarray(1.0, dtype=np.float32)

    def _copied(self, acc, calls, n=3):
        for _ in range(n):
            acc.add({"loss_sum": self._Copied(calls),
                     "weight_sum": self._Copied(calls)})

    def test_the_copies_are_queued_before_the_wait(self):
        """As ``jax.device_get`` queues a tree's before it reads the first:
        each rides behind the step that makes its scalar while the chip
        drains. Queued after the wait they run on an idle chip."""
        calls = []
        acc = fitloop.EpochMetrics()
        self._copied(acc, calls)
        seen = []
        obs_trace.add_listener(seen.append)
        try:
            history = []
            fitloop.FitLoopObs("linear").finish_epoch(
                0, 3, 0, acc, history)
        finally:
            obs_trace.remove_listener(seen.append)
        # every copy first, asked for once; the wait then reads the first
        # metric's scalars and the fetch the other's
        assert calls == ["copy"] * 6 + ["read"] * 6
        assert history == [1.0]
        assert [e["name"] for e in seen if e.get("ph") == "X"][:3] == [
            "drain_wait", "loss_fetch", "loss_readback"]

    def test_a_read_alone_reads_every_scalar_once(self):
        """``mean_loss`` with no ``start_fetch`` before it is right, if
        slower on a device (each scalar fetched as it is read); a second
        read finds nothing pending."""
        calls = []
        acc = fitloop.EpochMetrics()
        self._copied(acc, calls)
        assert acc.mean_loss() == 1.0
        assert calls == ["read"] * 6
        assert acc.pending_scalars == 0
        assert acc.mean_loss() == 1.0 and len(calls) == 6

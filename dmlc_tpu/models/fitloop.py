"""The streaming learners' fit loop, written once.

:func:`fit_feed` is the loop over passes of a ``DeviceFeed`` that
``LinearLearner.fit_feed`` and ``FMLearner.fit_feed`` delegate to, and
:func:`fit_uri` the path from a data URI to it (parser, feed, snapshots,
resume). GBDT keeps its own loop (one ``lax.scan``) and shares only the
epoch boundary, :class:`FitLoopObs`.

What a learner supplies to the loop (:class:`FeedLearner` is the base
that declares it; linear and FM fill it in):

- ``name``: the ``model`` label of its spans and ``dmlc_fit_*`` metrics;
- ``mesh``: where its state lives (the feed must have been built over
  the same one); ``params``: its parameter tree, for the audit's sample;
  ``param``: its hyper-parameters (``fit_uri`` takes ``num_features``
  from there when the caller gives none);
- ``ensure_step(spec)``: make sure parameters and the compiled step
  exist for batches of ``feed.spec`` (called before every batch: a
  membership change may have dropped the step mid-pass);
- ``train_step(arrays)``: run one step on a delivered batch's arrays
  (the loop has stripped the feed's metadata, :func:`step_batch`), rebind
  the learner's own state (the step donates it), return the metrics dict
  (``loss_sum``, ``weight_sum`` and any of the model's own: device
  scalars, not read here);
- ``snapshot_model()``: the ``model`` subtree of a job snapshot, and
  ``restore_snapshot_model(model)`` its way back;
- optionally ``audit_params()``: the arrays the audit samples at an
  epoch's end where ``params`` itself is not that tree (FM's packed row);
  ``epoch_span_args()``: more attributes for the ``epoch``
  span, and ``epoch_closed(reg, nstep, sums)``: called inside
  ``epoch_close`` for counters only this model has (FM's five); ``sums``
  holds the pass's sum of every scalar its steps returned.

The loop never waits for the device inside a pass: the one wait is the
pass's loss read-back in :meth:`FitLoopObs.finish_epoch`, under the
``loss_readback`` span, which holds the wait for the last step
(``drain_wait``) apart from the fetch of the pass's scalars
(``loss_fetch``); the bookkeeping after it is ``epoch_close``.
Device time per step comes from the profile (the benchmark's
``step_device_ms``), not from a host-side sync. What the loop does ask
the device, once a step and without waiting, is which of the steps it has
launched are done (:meth:`EpochMetrics.inflight`): the host's lead over
the chip, ``dmlc_fit_inflight_steps``. ``log_every`` counts epochs, here
and in every learner.

:class:`FitLoopObs` is the epoch boundary every learner shares: the four
``dmlc_fit_*`` metrics, a goodput-ledger window per epoch
(obs/goodput.py) fed to the SLO watchdog (obs/watchdog.py), the audit's
model chain, the stall/goodput log line, the registry export and the
snapshot capture. Under ``DMLC_TPU_METRICS=0`` the registry hands back
no-op children and the ledger/watchdog collapse to the shared no-op
child, so the hot path stays allocation-free.
"""

from __future__ import annotations

import contextlib
import functools
import time
import warnings
import weakref
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from dmlc_tpu import collective, obs
from dmlc_tpu.collective import JobSnapshot, Snapshotter, load_snapshot
from dmlc_tpu.data import create_parser
from dmlc_tpu.device.feed import BatchSpec, DeviceFeed, stall_breakdown
from dmlc_tpu.obs import audit, goodput, xla_cost
from dmlc_tpu.obs.watchdog import make_watchdog
from dmlc_tpu.parallel.partition import shard_params
from dmlc_tpu.resilience import Preempted, preempt
from dmlc_tpu.utils.logging import check, log_info

# steps in flight: the runtime's queue caps the lead at about 31
_INFLIGHT_BUCKETS = (0, 1, 2, 4, 8, 16, 24, 32, 48, 64, 128)
_DENSE_KEYS = ("x", "label", "weight")
_CSR_KEYS = ("label", "weight", "indices", "values", "offsets")


def step_batch(batch: Dict, layout: str) -> Dict:
    """Strip DeviceFeed metadata (num_rows/num_nonzero ints) down to the
    array fields a jitted train step consumes."""
    keys = _DENSE_KEYS if layout == "dense" else _CSR_KEYS
    return {k: batch[k] for k in keys}


def suppress_donation_warnings(step):
    """Batch leaves ([B,F] x, per-entry arrays) can never alias a donating
    step's outputs (w [F], scalars), so XLA warns "donated buffers were
    not usable" per compiled shape — the donation is still worth it for
    the early buffer release. The suppression is scoped to THIS step's
    call sites via catch_warnings, not installed process-globally: a
    user's own jitted function emitting the same message may be flagging
    a real missed donation, and this package must not eat that signal.

    The warnings fire only at trace/compile time (once per argument-shape
    signature), so the suppression engages only on calls with an unseen
    signature: steady-state steps call straight through — no per-step
    catch_warnings, whose filter-version bump would invalidate every
    module's __warningregistry__ and make unrelated once-per-location
    warnings re-fire each iteration. (catch_warnings swaps the global
    filter list for the compile call's duration; the swap is not atomic
    across threads — the stdlib limitation — but the window is one
    compile, not every step.)"""
    seen = set()

    @functools.wraps(step)
    def wrapped(*args, **kwargs):
        key = tuple(
            (getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
            for x in jax.tree_util.tree_leaves((args, kwargs))
        )
        if key in seen:
            return step(*args, **kwargs)
        seen.add(key)
        with warnings.catch_warnings():
            for msg in ("Some donated buffers were not usable",
                        "Donation is not implemented"):
                warnings.filterwarnings("ignore", message=msg)
            return step(*args, **kwargs)

    return wrapped


class EpochMetrics:
    """Collect per-step device metric scalars with no per-step dispatch or
    host sync; reading does one batched device_get (a per-step ``float()``
    stalls the feed's batch-in-flight overlap; a per-step device add pays
    dispatch overhead per step). Every scalar a step returns is kept:
    ``loss_sum`` and ``weight_sum`` give the mean loss, and a model's own
    (FM's ``touched_rows``) reach its ``epoch_closed`` through
    :attr:`sums`."""

    #: scalars asked ``is_ready()`` at most, one :meth:`inflight` call
    MAX_POLLS = 4

    def __init__(self):
        self._pending: Dict[str, list] = {}
        #: name -> the sum of that metric over the steps read so far
        self.sums: Dict[str, float] = {}
        # how many of the steps' scalars have been seen ready
        self._ready = 0

    def add(self, metrics: Dict) -> None:
        for name, scalar in metrics.items():
            self._pending.setdefault(name, []).append(scalar)

    def inflight(self, max_polls: Optional[int] = MAX_POLLS) -> int:
        """Steps launched and not yet done on the device: the host's lead
        over the chip, without a wait. A pointer into the first metric's
        pending scalars, one a step in launch order (every scalar of a
        step is an output of one run of one program, so any of them tells
        when the step is done), moves on while ``scalar.is_ready()``, at
        most ``max_polls`` questions a call (``None``: as many as there
        are): a call once a step asks about two where host and chip keep
        pace, and the reading lags by a step or two after the chip has
        caught up many at once."""
        watch = next(iter(self._pending.values()), ())
        n = len(watch)
        ready = self._ready
        polls = n if max_polls is None else max_polls
        while ready < n and polls > 0:
            is_ready = getattr(watch[ready], "is_ready", None)
            if is_ready is not None and not is_ready():
                break
            ready += 1
            polls -= 1
        self._ready = ready
        return n - ready

    @property
    def pending_scalars(self) -> int:
        """Device scalars the next :meth:`mean_loss` will fetch."""
        return sum(len(values) for values in self._pending.values())

    def start_fetch(self) -> None:
        """Queue the device-to-host copy of every pending scalar, without
        waiting: what ``jax.device_get`` does first, for the whole tree.
        Queued while the device still runs the pass's last steps, each
        copy rides behind the step that makes its scalar; a scalar read
        with no copy queued is fetched there and then, one after another
        on an idle chip (measured on a v5e: 384 scalars, 30-40 ms a
        pass)."""
        for values in self._pending.values():
            for scalar in values:
                start = getattr(scalar, "copy_to_host_async", None)
                if start is not None:
                    start()

    def _read(self, pending: Dict[str, list]) -> None:
        """Sum ``pending`` into :attr:`sums`, scalar by scalar, as
        ``jax.device_get`` reads a tree once it has queued the copies."""
        for name, values in pending.items():
            got = [np.asarray(scalar) for scalar in values]
            self.sums[name] = self.sums.get(name, 0) + np.sum(got).item()

    def drain(self) -> None:
        """Read the first metric's scalars, one of every step in launch
        order: the read returns when the last step's has arrived, so it
        is the wait for the device to drain (where one ``jax.device_get``
        of the whole tree waits, which reads this list first)."""
        for name in list(self._pending)[:1]:
            self._read({name: self._pending.pop(name)})
        self._ready = 0

    def mean_loss(self) -> float:
        if self._pending:
            # drain the pending scalars into the running totals: a repeated
            # read never re-fetches what was already summed, and the device
            # scalars are released here, where they were read
            self._read(self._pending)
            self._pending.clear()
            self._ready = 0
        return self.sums.get("loss_sum", 0.0) / max(
            self.sums.get("weight_sum", 0.0), 1e-12)


class FitLoopObs:
    """Per-fit observability bundle: fit metrics, stall logging, the
    goodput ledger, and the runtime watchdog."""

    def __init__(self, model: str, reg=None):
        self.model = model
        self.reg = reg if reg is not None else obs.registry()
        self.m_steps = self.reg.counter(
            "dmlc_fit_steps_total", "optimizer steps taken", model=model)
        self.m_epochs = self.reg.counter(
            "dmlc_fit_epochs_total", "epochs completed", model=model)
        self.g_loss = self.reg.gauge(
            "dmlc_fit_loss_value", "last epoch mean loss", model=model)
        self.h_epoch = self.reg.histogram(
            "dmlc_fit_epoch_ns", "wall time per epoch", model=model)
        # the host's lead over the chip, observed once a step before the
        # launch; (sum, count) at the last epoch boundary give the pass's
        # mean for the log line
        self.h_inflight = self.reg.histogram(
            "dmlc_fit_inflight_steps",
            "steps launched and not yet done on the device, read before "
            "each launch", buckets=_INFLIGHT_BUCKETS, model=model)
        self._lead_at = (0.0, 0)
        # the fit owns a device, so its roofline reads that device's
        # published peaks (knob overrides win; an unknown kind gives no
        # MFU at all)
        self.ledger = goodput.ledger(
            self.reg, ceilings=xla_cost.device_peaks())
        self.watchdog = make_watchdog(self.reg)
        # determinism audit: the model digest chain + numeric sentinel
        # (the shared no-op child when DMLC_TPU_AUDIT is off)
        self.audit = audit.auditor()

    def note_step(self, n: int = 1) -> None:
        """Hot-path progress marker (one no-op call under
        ``DMLC_TPU_METRICS=0``)."""
        self.ledger.note_step(n)

    def pass_lead(self) -> Optional[float]:
        """Mean of ``dmlc_fit_inflight_steps`` since the last call: the
        pass's mean lead at an epoch boundary. None where no step was
        observed (GBDT's one-scan fit; metrics off)."""
        hist = self.h_inflight
        total, count = hist.sum, hist.count
        base_total, base_count = self._lead_at
        self._lead_at = (total, count)
        if count <= base_count:
            return None
        return (total - base_total) / (count - base_count)

    def finish_epoch(self, epoch: int, nstep: int, t0_ns: int, acc,
                     history: list, **end_epoch_kw) -> float:
        """The epoch boundary of the streaming learners (linear, FM):
        read the pass's mean loss back from ``acc`` (an
        ``EpochMetrics``) — the one point where the loop waits for the
        device to drain, under the ``loss_readback`` span — append it to
        ``history`` and close the epoch (:meth:`end_epoch`). Inside the
        span, apart: ``drain_wait``, the read of the first metric's
        scalars, which ends with the last step's (the chip is busy until
        then), and ``loss_fetch``, the read of the other metrics' and the
        sums, once the chip has drained (the chip is idle). Every copy is
        queued before the wait, as one ``jax.device_get`` of the whole
        tree queues them before it reads the first: each rides behind the
        step that makes its scalar, none waits for an idle chip. Neither
        span has a counter: nothing would read one."""
        with obs.span("loss_readback", model=self.model, epoch=epoch):
            acc.start_fetch()
            with obs.span("drain_wait", steps=acc.inflight(None)):
                acc.drain()
            with obs.span("loss_fetch", scalars=acc.pending_scalars):
                loss = acc.mean_loss()
        history.append(loss)
        self.end_epoch(epoch, nstep, t0_ns, loss, **end_epoch_kw)
        return loss

    def end_epoch(self, epoch: int, nstep: int, t0_ns: int,
                  loss: Optional[float], feed=None,
                  log_every: int = 0, params=None,
                  snapshotter=None, snap_state=None,
                  on_close: Optional[Callable] = None) -> Optional[dict]:
        """Close one epoch: fit metrics, a goodput-ledger window fed to
        the watchdog, the unified stall/goodput log line (every
        ``log_every``-th epoch), and the registry export. Returns the
        ledger window (None when metrics are disabled).

        ``params`` (optional dict of device arrays) extends the audit
        model-digest chain over a strided parameter sample — one small
        epoch-cadence fetch that doubles as the numeric-health sentinel
        (non-finite counts feed the watchdog's ``numeric`` alert).

        ``snapshotter`` + ``snap_state`` (a zero-arg state-tree builder)
        arm job snapshotting: after the audit roll, the boundary's state
        is host-captured and handed to the async writer
        (collective/snapshot.py) — capture after the roll so the
        exported audit state describes the *closed* epoch and a resume
        re-arms the chains exactly where an uninterrupted run would
        be.

        ``on_close(reg, nstep)`` runs inside the span, after the step
        counter: where a learner counts what only it has
        (:meth:`FeedLearner.epoch_closed`)."""
        with obs.span("epoch_close", model=self.model, epoch=epoch):
            self.h_epoch.observe(time.monotonic_ns() - t0_ns)
            self.m_steps.inc(nstep)
            if on_close is not None:
                on_close(self.reg, nstep)
            self.m_epochs.inc()
            if loss is not None:
                self.g_loss.set(loss)
            nonfinite = self.audit.note_model(epoch, loss, params)
            win = self.ledger.tick()
            if win is not None:
                win["nonfinite"] = nonfinite
                self.watchdog.observe(win)
            lead = self.pass_lead()
            if log_every and (epoch + 1) % log_every == 0:
                parts = ["%s epoch %d" % (self.model, epoch)]
                if loss is not None:
                    parts.append("loss %.6f" % loss)
                if feed is not None:
                    parts.append(stall_breakdown(feed.stats()))
                if lead is not None:
                    # steps launched ahead of the chip, the pass's mean:
                    # near the runtime queue's cap the host has room,
                    # near 1 the job is host-bound
                    parts.append("lead %.1f" % lead)
                if win is not None:
                    parts.append("goodput %.2f binding=%s" % (
                        win["goodput"]["ratio"], win["binding"]))
                log_info("%s", " ".join(parts))
            obs.export_epoch(self.reg)
            # roll AFTER the export/publish so the epoch's full data chains
            # rode the heartbeat; this also runs the epoch-over-epoch
            # self-check (first divergence writes the replay bundle)
            self.audit.roll_epoch(epoch)
            if snapshotter is not None and snap_state is not None:
                snapshotter.capture(epoch, snap_state)
        return win


class FeedLearner:
    """What :func:`fit_feed` asks of a streaming learner (see the module
    docstring), with the parts linear and FM would otherwise each write:
    the mesh-membership listener and :meth:`reshard`.

    A mesh learner registers a ``collective.on_membership_change``
    listener: elastic re-entry / recovery re-places its state on a mesh
    rebuilt over the surviving devices."""

    #: the ``model`` label of the learner's spans and ``dmlc_fit_*`` metrics
    name = ""
    #: attributes holding the trees :meth:`partition_rules` places
    state_trees = ("params",)

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh
        self.params = None
        self._step = None
        self._unlisten = None
        if mesh is not None:
            self.check_mesh(mesh)
            ref = weakref.ref(self)

            def _membership_cb():
                learner = ref()
                if learner is not None and learner.params is not None:
                    learner.reshard()

            self._unlisten = collective.on_membership_change(_membership_cb)

    def partition_rules(self):
        """The rule table (parallel/partition.py) that places every tree
        of ``state_trees`` on the mesh."""
        raise NotImplementedError

    def check_mesh(self, mesh: Mesh) -> None:
        """Raise if this learner's state cannot be placed on ``mesh``."""

    def reshard(self, mesh: Optional[Mesh] = None) -> None:
        """Re-place the state trees on ``mesh`` (default: a fresh mesh
        over the CURRENT device set, same axis names) and drop the traced
        step — the elastic re-entry hook. Leaves round-trip through host
        copies because the old placement may reference devices that no
        longer exist."""
        if self.mesh is None or self.params is None:
            return
        if mesh is None:
            check(
                len(self.mesh.axis_names) == 1,
                "pass mesh= to reshard a multi-axis mesh",
            )
            mesh = Mesh(np.asarray(jax.devices()), self.mesh.axis_names)
        self.check_mesh(mesh)
        self.mesh = mesh
        for attr in self.state_trees:
            tree = getattr(self, attr)
            if tree is not None:
                setattr(self, attr, shard_params(
                    jax.device_get(tree), mesh, rules=self.partition_rules()))
        self._step = None  # retrace against the new mesh on next batch

    def ensure_step(self, spec) -> None:
        raise NotImplementedError

    def train_step(self, arrays: Dict) -> Dict:
        raise NotImplementedError

    def snapshot_model(self) -> Dict:
        """The ``model`` subtree of a job snapshot: the device arrays as
        they are (the snapshotter host-copies them before the next
        epoch's donating steps run)."""
        raise NotImplementedError

    def restore_snapshot_model(self, model: Dict) -> None:
        raise NotImplementedError

    def epoch_span_args(self) -> Dict:
        return {}

    def audit_params(self):
        """The arrays the audit samples at an epoch's end."""
        return self.params

    def pass_scalars(self) -> Dict:
        """Device scalars the learner's steps kept running on the device
        (counts a step adds to in its own state), asked once at a pass's
        end: they ride to the host with the pass's losses (no read of
        their own, none inside a pass) and reach :meth:`epoch_closed` in
        ``sums`` under their names. None by default."""
        return {}

    def epoch_closed(self, reg, nstep: int, sums: Dict) -> None:
        """Inside ``epoch_close``: count what only this model has.
        ``sums``: the pass's sum of each scalar :meth:`train_step`
        returned (:attr:`EpochMetrics.sums`), and :meth:`pass_scalars`."""


def _snapshot_state(learner, feed, epoch: int, history) -> Dict:
    """The job-snapshot state tree at one epoch boundary (built on the
    training thread)."""
    state = {
        "model": learner.snapshot_model(),
        "epoch": int(epoch),
        "history": [float(x) for x in history],
        "rng": None,  # neither SGD path draws step-time randomness
        "audit": audit.auditor().export_state(),
    }
    # through the feed: its producer may have wound the parser to the
    # next epoch already, and the feed keeps the boundary's plan
    plan = feed.snapshot_state()
    if plan is not None:
        state["data"] = {"parser": plan}
    return state


def fit_feed(learner, feed, epochs: int = 1, log_every: int = 0,
             snapshotter=None, start_epoch: int = 0, history=None):
    """Train ``learner`` over a DeviceFeed for N epochs; returns per-epoch
    losses. Every ``log_every``-th epoch logs the loss and the feed's
    per-stage stall breakdown (device.feed.stall_breakdown).

    With ``snapshotter`` armed, epoch boundaries hand a state tree to the
    async snapshot writer and the loop polls for preemption notices
    between steps (SIGTERM via resilience/preempt.py, or the injectable
    ``preempt.notice`` faultpoint): a notice stops the partial epoch,
    finalizes the freshest epoch-boundary snapshot within the grace
    window, and raises :class:`~dmlc_tpu.resilience.Preempted` so the
    process exits with the launcher's relaunch code (see
    docs/robustness.md "Preemption & resume"). ``start_epoch``/``history``
    continue a resumed run (the returned history covers ALL epochs,
    restored ones included)."""
    # mesh csr steps consume the SHARDED entry layout (local row ids);
    # a feed built without the mesh would deliver replicated entries
    # whose global row ids silently corrupt every shard's segment-sum
    check(
        getattr(feed, "_mesh", None) is learner.mesh,
        "feed mesh and learner mesh must match (csr entry layouts "
        "differ between mesh and single-device runs)",
    )
    name = learner.name
    spec = feed.spec
    layout = spec.layout
    fl = FitLoopObs(name)
    history = list(history) if history else []
    for epoch in range(start_epoch, epochs):
        acc = EpochMetrics()
        nstep = 0
        preempted = False
        t0 = time.monotonic_ns()
        with obs.span("epoch", model=name, epoch=epoch,
                      **learner.epoch_span_args()):
            for batch in feed:
                learner.ensure_step(spec)
                # the host's lead over the chip before this launch
                lead = acc.inflight()
                fl.h_inflight.observe(lead)
                # train_step closes the chunk's arrow chain: the feed
                # set the thread's current flow around this yield
                with obs.span("train_step", model=name, step=nstep,
                              inflight=lead, **obs.current_batch()):
                    obs.flow_step(obs.current_flow(), "chunk")
                    # the last reference to the previous batch's arrays
                    # goes here, so they are released inside this span
                    arrays = step_batch(batch, layout)
                    metrics = learner.train_step(arrays)
                acc.add(metrics)
                fl.note_step()
                nstep += 1
                if snapshotter is not None and preempt.poll():
                    preempted = True
                    break
        if preempted:
            # a partial epoch is never snapshotted (resume replays it
            # in full — that is what keeps the relaunch bit-identical);
            # commit the freshest epoch-boundary capture and exit with
            # the relaunch code
            snapshotter.finalize()
            raise Preempted(
                "preempted in epoch %d after %d steps; last committed "
                "snapshot epoch %d"
                % (epoch, nstep, snapshotter.committed_epoch))
        acc.add(learner.pass_scalars())
        fl.finish_epoch(
            epoch, nstep, t0, acc, history, feed=feed,
            log_every=log_every, params=learner.audit_params(),
            snapshotter=snapshotter,
            snap_state=(None if snapshotter is None else
                        lambda e=epoch: _snapshot_state(
                            learner, feed, e, history)),
            on_close=functools.partial(
                learner.epoch_closed, sums=acc.sums),
        )
        if epoch + 1 < epochs:
            feed.before_first()
    return history


def fit_uri(learner, uri: str, *, batch_size: int = 4096,
            epochs: int = 1, layout: str = "dense", num_features: int = 0,
            part_index: Optional[int] = None,
            num_parts: Optional[int] = None, drop_remainder: bool = False,
            log_every: int = 0, snapshot_uri: Optional[str] = None,
            resume: bool = False, snap_every_epochs: int = 1):
    """One call from data URI to fitted params, the learners'
    ``fit_uri``: InputSplit part → parser → DeviceFeed over
    ``learner.mesh`` → ``learner.fit_feed``. The part defaults to this
    worker's collective rank/world (each worker reads its own byte range
    — the reference's ``InputSplit::Create(uri, rank, world)`` contract),
    so the same line works single-process, on a mesh, or under
    dmlc-submit with the socket engine. ``num_features`` defaults to the
    learner's hyper-parameter of that name.

    ``snapshot_uri`` arms preemption-proof job snapshots: every
    ``snap_every_epochs`` epoch boundary (plus the
    ``DMLC_TPU_SNAP_EVERY_S`` wall-clock trigger) commits model +
    optimizer + read-plan + audit state through the async
    two-phase-commit writer, and a SIGTERM mid-epoch finalizes a
    just-in-time snapshot and exits with the relaunch code.
    ``resume=True`` loads the newest committed snapshot first: the model
    restores (``learner.restore_snapshot_model``), the shuffle re-derives
    the interrupted epoch permutation, the audit chains re-arm, and
    training continues at the next epoch — bit-identical to a run that
    was never killed (see docs/robustness.md "Preemption & resume")."""
    num_features = num_features or learner.param.num_features
    check(num_features > 0, "fit_uri requires num_features")
    if part_index is None:
        part_index = collective.rank()
    if num_parts is None:
        num_parts = collective.world_size()
    feed = DeviceFeed(
        create_parser(uri, part_index, num_parts),
        BatchSpec(batch_size=batch_size, layout=layout,
                  num_features=num_features, drop_remainder=drop_remainder),
        mesh=learner.mesh,
    )
    # closed on the way out: the feed's producer has by then staged the
    # start of a pass nobody will ask for
    with contextlib.closing(feed):
        if snapshot_uri is None:
            check(not resume, "resume=True requires snapshot_uri")
            return learner.fit_feed(feed, epochs=epochs, log_every=log_every)
        snap = JobSnapshot(snapshot_uri, rank=collective.rank(),
                           world_size=collective.world_size())
        start_epoch = 0
        history = None
        snapshotter = Snapshotter(snap, every_epochs=snap_every_epochs)
        try:
            if resume:
                version, state, _meta = load_snapshot(snap)
                if version and state is not None:
                    learner.restore_snapshot_model(state["model"])
                    start_epoch = int(state.get("epoch", -1)) + 1
                    history = list(state.get("history", ()))
                    pst = (state.get("data") or {}).get("parser")
                    if pst:
                        feed.restore_state(pst)
                    snapshotter.mark_restored(start_epoch - 1)
            return learner.fit_feed(
                feed, epochs=epochs, log_every=log_every,
                snapshotter=snapshotter, start_epoch=start_epoch,
                history=history,
            )
        finally:
            snapshotter.close()

"""Shared fit-loop observability: one epoch-boundary helper for every
learner.

Before this module each learner hand-rolled the same block — register
the four ``dmlc_fit_*`` metrics, observe the epoch histogram, log the
feed's stall breakdown (linear only, behind a function-local import),
export the registry. :class:`FitLoopObs` is that block once, plus the
runtime instruments this layer gained: a goodput ledger window per
epoch (obs/goodput.py) and the SLO watchdog over those windows
(obs/watchdog.py). linear, FM, and GBDT all funnel through it, so the
epoch log line and the binding-constraint verdict are uniform across
models.

Usage::

    fl = FitLoopObs("linear")
    for epoch in range(epochs):
        t0 = time.monotonic_ns()
        for batch in feed:
            ...
            fl.note_step()
        fl.finish_epoch(epoch, nstep, t0, acc, history, feed=feed,
                        log_every=log_every)

The loop never waits for the device inside a pass: the one wait is the
pass's loss read-back in :meth:`FitLoopObs.finish_epoch`, under the
``loss_readback`` span; the bookkeeping after it is ``epoch_close``.
Device time per step comes from the profile (the benchmark's
``step_device_ms``), not from a host-side sync.

Under ``DMLC_TPU_METRICS=0`` the registry hands back no-op children and
the ledger/watchdog collapse to the shared no-op child, so the hot path
stays allocation-free.
"""

from __future__ import annotations

import time
from typing import Optional

from dmlc_tpu import obs
from dmlc_tpu.device.feed import stall_breakdown
from dmlc_tpu.obs import audit, goodput, xla_cost
from dmlc_tpu.obs.watchdog import make_watchdog
from dmlc_tpu.utils.logging import log_info


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

    def finish_epoch(self, epoch: int, nstep: int, t0_ns: int, acc,
                     history: list, **end_epoch_kw) -> float:
        """The epoch boundary of the streaming learners (linear, FM):
        read the pass's mean loss back from ``acc`` (an
        ``EpochMetrics``) — the one point where the loop waits for the
        device to drain, under the ``loss_readback`` span — append it to
        ``history`` and close the epoch (:meth:`end_epoch`)."""
        with obs.span("loss_readback", model=self.model, epoch=epoch):
            loss = acc.mean_loss()
        history.append(loss)
        self.end_epoch(epoch, nstep, t0_ns, loss, **end_epoch_kw)
        return loss

    def end_epoch(self, epoch: int, nstep: int, t0_ns: int,
                  loss: Optional[float], feed=None,
                  log_every: int = 0, params=None,
                  snapshotter=None, snap_state=None,
                  sparse_update_steps: Optional[int] = None,
                  sharded_table_steps: Optional[int] = None,
                  exchange_bytes: Optional[int] = None
                  ) -> Optional[dict]:
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

        ``sparse_update_steps`` (learners whose step can update only the
        rows a batch touches: FM) is how many of this epoch's ``nstep``
        took that path; over ``dmlc_fit_steps_total`` it is the share of
        steps that engaged it. ``sharded_table_steps`` likewise counts the
        steps taken over a table divided over the mesh's chips, and
        ``exchange_bytes`` the bytes one chip contributed to those steps'
        collectives (from the shapes; the gradient psum of a replicated
        model is not among them, ``dmlc_xla_collective_bytes`` has it)."""
        with obs.span("epoch_close", model=self.model, epoch=epoch):
            self.h_epoch.observe(time.monotonic_ns() - t0_ns)
            self.m_steps.inc(nstep)
            if sparse_update_steps is not None:
                self.reg.counter(
                    "dmlc_fit_sparse_update_steps_total",
                    "optimizer steps that scatter-added into the touched "
                    "rows instead of applying a dense gradient",
                    model=self.model).inc(sparse_update_steps)
            if sharded_table_steps is not None:
                self.reg.counter(
                    "dmlc_fit_sharded_table_steps_total",
                    "optimizer steps over a parameter table sharded over "
                    "the mesh's chips (no chip holds the whole table)",
                    model=self.model).inc(sharded_table_steps)
            if exchange_bytes is not None:
                self.reg.counter(
                    "dmlc_fit_exchange_bytes_total",
                    "bytes one chip contributed to the collectives of "
                    "sharded-table steps (batch gather + interaction psum)",
                    model=self.model).inc(exchange_bytes)
            self.m_epochs.inc()
            if loss is not None:
                self.g_loss.set(loss)
            nonfinite = self.audit.note_model(epoch, loss, params)
            win = self.ledger.tick()
            if win is not None:
                win["nonfinite"] = nonfinite
                self.watchdog.observe(win)
            if log_every and (epoch + 1) % log_every == 0:
                parts = ["%s epoch %d" % (self.model, epoch)]
                if loss is not None:
                    parts.append("loss %.6f" % loss)
                if feed is not None:
                    parts.append(stall_breakdown(feed.stats()))
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


def fit_uri(learner, uri: str, *, batch_size: int = 4096,
            epochs: int = 1, layout: str = "dense", num_features: int = 0,
            part_index: Optional[int] = None,
            num_parts: Optional[int] = None, drop_remainder: bool = False,
            log_every: int = 0, snapshot_uri: Optional[str] = None,
            resume: bool = False, snap_every_epochs: int = 1):
    """The learners' ``fit_uri``: InputSplit part → parser → DeviceFeed
    over ``learner.mesh`` → ``learner.fit_feed``. The part defaults to
    this worker's collective rank/world. With ``snapshot_uri`` the fit
    runs under a :class:`~dmlc_tpu.collective.Snapshotter`;
    ``resume=True`` first loads the newest committed snapshot, hands its
    model to ``learner.restore_snapshot_model`` and continues at the next
    epoch (see
    :meth:`LinearLearner.fit_uri` for the contract)."""
    from dmlc_tpu import collective
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.utils.logging import check

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
    if snapshot_uri is None:
        check(not resume, "resume=True requires snapshot_uri")
        return learner.fit_feed(feed, epochs=epochs, log_every=log_every)
    from dmlc_tpu.collective import JobSnapshot, Snapshotter, load_snapshot

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
                parser = getattr(feed, "_parser", None)
                if pst and hasattr(parser, "restore_state"):
                    parser.restore_state(pst)
                snapshotter.mark_restored(start_epoch - 1)
        return learner.fit_feed(
            feed, epochs=epochs, log_every=log_every,
            snapshotter=snapshotter, start_epoch=start_epoch,
            history=history,
        )
    finally:
        snapshotter.close()

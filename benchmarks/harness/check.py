"""The comparison that decides ``correct``: outside the window, in set-up.

The cell's first batches go through the system (its parser, its feed, its
learner's own ``fit_feed`` and compiled step) and through the
configuration's float64 reference, from the same parameters. The reference
reads the rows from the arrays the file was printed from, not from the
system's batches, so the parser and the batch assembly are under test too.

Compared: the loss of each step; the updated values of every row the batches
touch, in every logical table (weights and optimizer state); and that no
other row of a table changed (a 32-bit fingerprint of every row before and
after, so no second copy of a table is held).

The tables are read through the learner (``tables.py``: by a table's name
and ids), never from its storage, so their layout is the learner's own.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from harness import tables
from harness.window import FeedProxy


class _OneBatch(FeedProxy):
    """A feed that yields one batch an 'epoch' to ``fit_feed``."""

    def __init__(self, feed, batches):
        super().__init__(feed)
        self._batches = batches

    def __iter__(self):
        yield next(self._batches)


def run(cell, model, feed, data, steps):
    """Run ``steps`` batches through the system and the reference.
    Returns the facts; ``ok`` is the verdict."""
    cfg = cell.cfg
    tol = cfg["check"]
    batch_rows = feed.spec.batch_size
    need = steps * batch_rows
    label, ids, values = data["label"][:need], data["ids"][:need], \
        data["values"]
    values = (np.ones(ids.shape, np.float32) if values is None
              else values[:need])
    touched = np.unique(ids)
    compact = np.searchsorted(touched, ids)
    batches = [
        {"label": label[i * batch_rows:(i + 1) * batch_rows],
         "ids": compact[i * batch_rows:(i + 1) * batch_rows],
         "values": values[i * batch_rows:(i + 1) * batch_rows]}
        for i in range(steps)]

    # padded to the most rows the batches could touch, so that the
    # programs below have one shape whatever the seed
    padded = np.full(ids.size, touched[-1], dtype=np.int32)
    padded[: len(touched)] = touched
    at = jnp.asarray(padded)
    n = len(touched)
    learner = tables.of(model)

    def touched_rows(name):
        return np.asarray(learner.table_rows(name, at), dtype=np.float64)[:n]

    def scalars():
        return {k: np.float64(v) for k, v in learner.scalars().items()}

    names = learner.table_names()
    before = {k: touched_rows(k) for k in names}
    before.update(scalars())
    prints = {k: learner.table_fingerprints(k) for k in names}

    one = _OneBatch(feed, iter(feed))
    losses = [float(model.fit_feed(one, epochs=1)[0]) for _ in range(steps)]

    ref_losses, ref = cell.config.reference_steps(cfg, before, batches)

    touched_mask = jnp.zeros((int(cfg["num_features"]),), bool).at[at].set(True)
    after = {k: touched_rows(k) for k in names}
    after.update(scalars())
    # the system's distance from the reference, in units of the update
    update_rel_of = {
        k: float(np.max(np.abs(after[k] - ref[k]))
                 / max(np.max(np.abs(ref[k] - before[k])), 1e-30))
        for k in before}
    update_rel = max(update_rel_of.values())
    untouched_changed = sum(
        int(jnp.sum((learner.table_fingerprints(k) != prints[k])
                    & ~touched_mask)) for k in names)
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    # every number the verdict rests on, beside its limit
    compared = {
        "loss_rel": [loss_rel, tol["loss_rel_tol"]],
        "update_rel": [update_rel, tol["update_rel_tol"]],
        "untouched_changed": [untouched_changed, 0],
        "losses_not_finite": [int(np.sum(~np.isfinite(losses))), 0],
    }
    ok = all(value <= limit for value, limit in compared.values())
    return {"ok": bool(ok), "steps": steps, "loss_rel": loss_rel,
            "update_rel": update_rel, "update_rel_of": update_rel_of,
            "untouched_changed": untouched_changed,
            "touched_rows": int(len(touched)), "losses": losses,
            "compared": compared}

"""The comparison that decides ``correct``: outside the window, in set-up.

The cell's first batches go through the system (its parser, its feed, its
learner's own ``fit_feed`` and compiled step) and through the
configuration's float64 reference, from the same parameters. The reference
reads the rows from the arrays the file was printed from, not from the
system's batches, so the parser and the batch assembly are under test too.

Compared: the loss of each step; the updated values of every parameter row
the batches touch; and that no other row of a table changed (a 32-bit
fingerprint of every row before and after, so no second copy of a table is
held).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.window import FeedProxy


class _OneBatch(FeedProxy):
    """A feed that yields one batch an 'epoch' to ``fit_feed``."""

    def __init__(self, feed, batches):
        super().__init__(feed)
        self._batches = batches

    def __iter__(self):
        yield next(self._batches)


@jax.jit
def _fingerprint(table):
    """[rows] uint32: the wrapping sum of each row's bit patterns."""
    bits = jax.lax.bitcast_convert_type(table, jnp.uint32)
    return bits if bits.ndim == 1 else jnp.sum(bits, axis=1, dtype=jnp.uint32)


@jax.jit
def _rows_of(table, at):
    return jnp.take(table, at, axis=0)


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
    tables = {k: v for k, v in model.params.items() if v.ndim >= 1}
    before = {k: np.asarray(_rows_of(t, at), dtype=np.float64)[:n]
              for k, t in tables.items()}
    scalars = {k: np.float64(v) for k, v in model.params.items()
               if v.ndim == 0}
    prints = {k: _fingerprint(t) for k, t in tables.items()}
    del tables  # the learner's step donates them

    one = _OneBatch(feed, iter(feed))
    losses = [float(model.fit_feed(one, epochs=1)[0]) for _ in range(steps)]

    ref_losses, ref = cell.config.reference_steps(
        cfg, dict(before, **scalars), batches)

    touched_mask = jnp.zeros((int(cfg["num_features"]),), bool).at[at].set(True)
    after = {k: np.asarray(_rows_of(model.params[k], at),
                           dtype=np.float64)[:n] for k in before}
    after.update({k: np.float64(model.params[k]) for k in scalars})
    before.update(scalars)
    # the system's distance from the reference, in units of the update
    update_rel = max(
        float(np.max(np.abs(after[k] - ref[k]))
              / max(np.max(np.abs(ref[k] - before[k])), 1e-30))
        for k in before)
    untouched_changed = sum(
        int(jnp.sum((_fingerprint(model.params[k]) != prints[k])
                    & ~touched_mask)) for k in prints)
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    ok = (loss_rel <= tol["loss_rel_tol"]
          and update_rel <= tol["update_rel_tol"]
          and untouched_changed == 0
          and all(np.isfinite(losses)))
    return {"ok": bool(ok), "steps": steps, "loss_rel": loss_rel,
            "update_rel": update_rel, "untouched_changed": untouched_changed,
            "touched_rows": int(len(touched)), "losses": losses}

"""What a learner owes the check: its tables by name, through five calls.

The check (``check.py``) and the configurations' ``init_params`` reach a
learner's parameters and optimizer state only through these, so how the
tables lie on the device (one array a table, several packed in one row,
rows permuted over chips) is the learner's to choose:

- ``init_tables(seed)``: build the learner's own storage from a seed, in
  ONE jitted program that takes the seed as an argument (one program for
  every seed), its outputs placed by the learner's own shardings, so that
  a chip writes only its part and no whole table passes through the host;
- ``table_names()`` -> the LOGICAL tables of rank >= 1 by the names the
  model's equations use (``"w"``, ``"v"``; under a stateful rule also
  ``"a"``, ``"z"``, ``"n"``), weights and state alike;
- ``scalars()`` -> ``{name: float}`` (``"b"``);
- ``table_rows(name, ids)`` -> the logical table's rows at ``ids``
  (``[n]`` or ``[n, K]``, a device array) by one jitted gather whose
  program is the same for every seed (the check pads ``ids`` to one
  length). A layout that packs tables gathers packed rows and slices the
  RESULT's columns: no array of a table's shape is made;
- ``table_fingerprints(name)`` -> ``uint32[F]``, the wrapping sum of the
  bit patterns of each logical row, inside one jit from wherever the
  columns lie, again with no copy of a table.

A learner with a layout of its own defines all five as methods and
:func:`of` hands it back as it is. For every other learner
:class:`StoredParams` gives them over ``params`` as the learners store it
today: a dict with one array for each logical table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CALLS = ("init_tables", "table_names", "scalars", "table_rows",
         "table_fingerprints")


@jax.jit
def _fingerprint(table):
    """[rows] uint32: the wrapping sum of each row's bit patterns."""
    bits = jax.lax.bitcast_convert_type(table, jnp.uint32)
    return bits if bits.ndim == 1 else jnp.sum(bits, axis=1, dtype=jnp.uint32)


@jax.jit
def _rows_of(table, at):
    return jnp.take(table, at, axis=0)


class StoredParams:
    """The five calls over a learner that keeps ``params`` as a dict of
    named arrays, one for each logical table. ``init``: the
    configuration's initialiser, ``seed (uint32 scalar) -> {attribute:
    tree}`` for every attribute the learner keeps state under (its
    ``state_trees``); only ``init_tables`` needs it."""

    def __init__(self, model, init=None):
        self._model = model
        self._init = init

    def init_tables(self, seed):
        from dmlc_tpu.parallel.partition import (
            match_partition_rules,
            sharding_tree,
        )

        model = self._model
        arg = jnp.uint32(int(seed) % (1 << 32))
        placed = None
        if model.mesh is not None:
            # the learner's own rules, by a leaf's name and rank: each
            # chip generates the part it holds
            rules = model.partition_rules()
            placed = {
                attr: sharding_tree(
                    model.mesh, match_partition_rules(rules, tree))
                for attr, tree in jax.eval_shape(self._init, arg).items()}
        state = jax.jit(self._init, out_shardings=placed)(arg)
        for attr in model.state_trees:
            setattr(model, attr, state[attr])

    def table_names(self):
        return tuple(
            k for k, v in self._model.params.items() if v.ndim >= 1)

    def scalars(self):
        return {k: float(v) for k, v in self._model.params.items()
                if v.ndim == 0}

    def table_rows(self, name, ids):
        return _rows_of(self._model.params[name], ids)

    def table_fingerprints(self, name):
        return _fingerprint(self._model.params[name])


def of(model, init=None):
    """``model`` seen through the five calls: itself where it defines
    them, else :class:`StoredParams` over its ``params``."""
    have = [hasattr(model, call) for call in CALLS]
    if all(have):
        return model
    if any(have):
        raise SystemExit(
            "%s defines %s and not %s: a learner with a layout of its own "
            "owes the check all of them (benchmarks/README.md)" % (
                type(model).__name__,
                [c for c, h in zip(CALLS, have) if h],
                [c for c, h in zip(CALLS, have) if not h]))
    return StoredParams(model, init)

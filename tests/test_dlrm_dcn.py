"""DLRM-DCNv2 (``models/dlrm.py`` under ``bag_sizes``, ``interaction="dcn"``
and ``optimizer="adagrad"``: multi-hot bags pooled by sum, a low-rank cross
net, Adagrad on the dense tree and row-wise Adagrad on the table) on the
suite's CPU devices, at a small size: the ``mlperf-dlrm-dcnv2``
configuration's rehearse tables (26 tables of 3 to 5,000 rows) with
MLPerf's bags (214 ids a row), the widths cut FOR THE TEST ONLY to 8
columns, a bottom MLP 13-16-8, a cross net of 3 layers of rank 4 over 216
features and a top MLP 216-16-8-1, 64 rows a batch. Held to the
configuration's float64 reference (``benchmarks/configs/
mlperf-dlrm-dcnv2.py``), whose parts are held to ``jax.vjp`` of the
program's own functions here too.
"""

import hashlib
import json
import os
import re
import subprocess
import sys
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dmlc_tpu import obs
from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import dlrm, fm
from dmlc_tpu.models.dlrm import DLRMLearner, make_dlrm_train_step, table_lows
from dmlc_tpu.models.fm import PackedTables
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.utils.logging import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "mlperf-dlrm-dcnv2")
STEPS, ROWS, K, RANK = 3, 64, 8, 4
DENSE = 13
HYPER = ("learning_rate", "num_factors", "num_features", "dense_features",
         "field_sizes", "mlp_bot", "mlp_top", "bag_sizes", "interaction",
         "dcn_num_layers", "dcn_low_rank_dim", "optimizer", "eps")


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own generator, check and reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec

        yield types.SimpleNamespace(
            spec=spec, check=check, config=spec.load_module(CONFIG + ".py"))
    finally:
        sys.path.remove(BENCH)


def _cfg():
    with open(CONFIG + ".json") as f:
        out = json.load(f)
    out.update(out["rehearse"])
    fields = len(out["field_sizes"])
    out.update(rows=STEPS * ROWS, batch_rows_per_chip=ROWS, num_factors=K,
               mlp_bot=[DENSE, 16, K], mlp_top=[K * (fields + 1), 16, 8, 1],
               dcn_low_rank_dim=RANK)
    return out


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


def _write_text(path, label, ids, values, keep):
    """A LIBSVM file a row a line, the entries where ``keep`` is set."""
    with open(path, "w") as f:
        for y, row_ids, row_values, row_keep in zip(label, ids, values, keep):
            f.write("%d %s\n" % (y, " ".join(
                "%d:%s" % (i, "%.4f" % v if i <= DENSE else "1")
                for i, v, k in zip(row_ids, row_values, row_keep) if k)))


@pytest.fixture(scope="module")
def data(harness, cfg, tmp_path_factory):
    """The configuration's rows as arrays and as one LIBSVM file, a
    twentieth of the bags' members left out of the file (``keep``), so
    that a bag lacks members and its batch is laid out by the scatters."""
    rows = harness.config.rows(cfg, 2147483659)
    keep = np.random.default_rng(5).random(rows["ids"].shape) > 0.05
    keep[:, :DENSE] = True
    path = str(tmp_path_factory.mktemp("dcn") / "rows.libsvm")
    _write_text(path, rows["label"], rows["ids"], rows["values"], keep)
    return dict(rows, path=path, keep=keep)


def _learner(cfg, **over):
    hyper = {k: cfg[k] for k in HYPER}
    hyper.update(over)
    return DLRMLearner(**hyper)


def _feed(cfg, path, rows=ROWS):
    return DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=rows, layout="csr",
                  num_features=cfg["num_features"]))


def _logical(model, at=None):
    """Every parameter and accumulator, float64: ``emb`` and ``m`` at
    ``at`` (every id when None)."""
    if at is None:
        at = np.arange(model.param.num_features)
    ids = jnp.asarray(at, jnp.int32)
    out = {k: np.asarray(model.table_rows(k, ids), np.float64)
           for k in model.table_names()}
    out.update({k: np.asarray(v, np.float64)
                for k, v in model.scalars().items()})
    return out


def _reference_batches(data, touched, steps=STEPS):
    """The steps' batches as the reference reads them: positions into
    ``touched``, a member left out of the file a value of 0."""
    out = []
    for step in range(steps):
        part = slice(step * ROWS, (step + 1) * ROWS)
        values = np.where(data["keep"][part], data["values"][part], 0.0)
        out.append({"label": data["label"][part],
                    "ids": np.searchsorted(touched, data["ids"][part]),
                    "values": values})
    return out


def _moved_rel(after, want, before):
    """{name: the largest |after - want| over the largest |want -
    before|}: the check's measure."""
    return {k: float(np.max(np.abs(after[k] - want[k]))
                     / max(np.max(np.abs(want[k] - before[k])), 1e-30))
            for k in want}


@pytest.fixture(scope="module")
def trained(harness, cfg, data):
    """The learner through ``fit_feed``, a batch a call, and the float64
    reference over the same batches from the same start."""
    model = _learner(cfg)
    model.init_tables(7)
    touched = np.unique(data["ids"])
    start = _logical(model, touched)
    feed = _feed(cfg, data["path"])
    one = harness.check._OneBatch(feed, iter(feed))
    losses = [float(model.fit_feed(one, epochs=1)[0]) for _ in range(STEPS)]
    feed.close()
    ref_losses, ref = harness.config.reference_steps(
        cfg, start, _reference_batches(data, touched))
    return types.SimpleNamespace(
        model=model, touched=touched, start=start, losses=losses,
        after=_logical(model, touched), ref_losses=ref_losses, ref=ref)


def test_a_three_steps_of_both_adagrad_rules_match_the_reference(trained):
    """The loss of each of 3 steps and, after them, every dense parameter,
    every accumulator ``G.<name>``, the touched rows of ``emb`` and of
    ``m``, each in units of its own change (Adagrad's first update is
    about ``lr * sign(g)``: the accumulators, which hold ``g^2``, and the
    later steps are where a gradient's error shows)."""
    rel = _moved_rel(trained.after, trained.ref, trained.start)
    assert set(rel) >= {"emb", "m", "G.cross.0.u", "cross.2.v", "top.2.w"}
    np.testing.assert_allclose(trained.losses, trained.ref_losses, rtol=2e-6)
    assert max(rel.values()) < 2e-3, sorted(rel.items(), key=lambda kv: kv[1])
    assert max(v for k, v in rel.items() if k.startswith("G.") or k == "m") \
        < 1e-4


def test_a_rows_no_batch_names_keep_their_bits(trained, cfg, data):
    """Rows 0-13, every row no kept entry names and its ``m``: unchanged
    to the bit; every named row's ``m`` grew."""
    model = trained.model
    named = np.unique(data["ids"][:, DENSE:][data["keep"][:, DENSE:]])
    every = jnp.arange(model.param.num_features, dtype=jnp.int32)
    prints = {k: np.asarray(model.table_fingerprints(k))
              for k in model.table_names()}
    fresh = _learner(cfg)
    fresh.init_tables(7)
    unnamed = np.setdiff1d(np.arange(model.param.num_features), named)
    for k in model.table_names():
        start = np.asarray(fresh.table_fingerprints(k))
        np.testing.assert_array_equal(prints[k][unnamed], start[unnamed])
    m = np.asarray(model.table_rows("m", every))
    assert np.all(m[named] > 0) and not m[unnamed].any()
    assert not np.asarray(model.table_rows("emb", every))[:DENSE + 1].any()


@pytest.mark.parametrize("whole", [True, False])
def test_a_predict_is_the_forward_pass(harness, trained, cfg, data, whole):
    """``predict_batch`` reads the places as the step lays them: the
    margins of a batch (every row whole, by the reshape; or a twentieth of
    the bags' members left out, by the scatters) are the float64 forward
    pass over the bags' sums of the trained rows."""
    model, config = trained.model, harness.config
    ids, values = data["ids"][:ROWS], data["values"][:ROWS].copy()
    if not whole:
        values[~data["keep"][:ROWS]] = 0.0
    p = {k: np.asarray(v, np.float64) for k, v in model.scalars().items()}
    emb = np.asarray(model.table_rows("emb", jnp.asarray(
        ids[:, DENSE:].ravel(), jnp.int32)), np.float64).reshape(ROWS, -1, K)
    e = np.add.reduceat(emb * (values[:, DENSE:] != 0)[:, :, None],
                        dlrm._bag_starts(cfg["bag_sizes"]), axis=1)
    z, _ = config._mlp_forward(p, "bot", values[:, :DENSE].astype(
        np.float64), last_relu=True)
    top_in, _ = config._cross_forward(
        p, np.concatenate([z, e.reshape(ROWS, -1)], axis=1),
        cfg["dcn_num_layers"])
    s, _ = config._mlp_forward(p, "top", top_in, last_relu=False)
    np.testing.assert_allclose(
        model.predict_batch(_arrays(ids, values, data["label"][:ROWS])),
        s[:, 0], rtol=2e-4, atol=2e-5)


def test_b_pooling_sums_a_bag_an_id_twice_and_a_missing_member(cfg):
    """``_pool`` over member-major places against float64 sums: a bag
    that names one id twice counts it twice; a place that names no id (id
    0) adds 0 whatever row 0 holds; backward, every member of a bag takes
    the bag's gradient and a missing one none."""
    bags, r = (3, 1, 2), 4
    rng = np.random.default_rng(3)
    # [place, row]: the program's member-major order
    rows = rng.standard_normal((6, r, K)).astype(np.float32)
    ids = rng.integers(1, 50, (6, r)).astype(np.int32)
    ids[0, 0] = ids[1, 0] = 17   # row 0, bag 0: the same id twice
    rows[1, 0] = rows[0, 0]
    ids[5, 0] = 0                # row 0, bag 2: a missing member
    ids[3, 1] = 0                # row 1, the bag of one: no id at all
    pooled, back = jax.vjp(
        partial(dlrm._pool, jnp.asarray(ids.reshape(-1)), bags),
        jnp.asarray(rows.reshape(-1, K)))
    # the row-major view [row, place] the reference sums along
    held = np.where(ids.T[:, :, None] != 0, rows.transpose(1, 0, 2),
                    0.0).astype(np.float64)
    want = np.add.reduceat(held, [0, 3, 4], axis=1)
    np.testing.assert_allclose(
        np.asarray(pooled), want.transpose(1, 2, 0), rtol=1e-6, atol=1e-7)
    assert np.allclose(np.asarray(pooled)[0, :, 0],
                       2 * rows[0, 0] + rows[2, 0])
    assert not np.asarray(pooled)[1, :, 1].any()
    grad = rng.standard_normal(pooled.shape).astype(np.float32)
    (got,) = back(jnp.asarray(grad))
    by_member = np.repeat(grad.transpose(0, 2, 1), bags, axis=0) * (
        ids != 0)[:, :, None]
    np.testing.assert_array_equal(np.asarray(got), by_member.reshape(-1, K))


@pytest.mark.parametrize("bags_given", ["bags", "none", "ones"])
def test_b_the_layout_is_the_same_by_the_reshape_and_by_the_scatters(
        harness, cfg, bags_given):
    """``_lay_out``'s ids and dense features by the reshape (every row
    whole and in order) and by the scatters (the same rows with their
    dense entries listed last to first; and with one bag member dropped,
    the bag's later members moving up a place): with the configuration's
    bags member-major, place ``(start of f's bag + member) * rows + row``;
    with one id a table and ``bags`` None row-major, ``row * F + f``, as
    before bags came; with ``bags`` of one given, member-major too."""
    sizes = cfg["field_sizes"]
    data = harness.config.rows(dict(cfg, rows=ROWS), 7)
    ids, values = data["ids"], data["values"]
    bags = tuple(cfg["bag_sizes"])
    if bags_given != "bags":
        # one id a table: each bag's first member
        keep = np.r_[0:DENSE, DENSE + dlrm._bag_starts(bags)]
        ids, values = ids[:, keep], values[:, keep]
        bags = None if bags_given == "none" else (1,) * len(sizes)
    assert np.all(values[:, DENSE:] != 0)
    lay = jax.jit(lambda batch: dlrm._lay_out(
        batch, DENSE, table_lows(DENSE, sizes), sizes, bags))
    block = ids[:, DENSE:]  # [rows, P]: each row's places in order

    def laid(block):
        return (block if bags is None else block.T).reshape(-1)

    reverse = np.r_[np.arange(DENSE)[::-1], DENSE:ids.shape[1]]
    for order in (np.arange(ids.shape[1]), reverse):
        x, got, left_out = lay(_arrays(ids[:, order], values[:, order],
                                       data["label"]))
        np.testing.assert_array_equal(np.asarray(got), laid(block))
        np.testing.assert_array_equal(np.asarray(x), values[:, :DENSE].T)
        assert int(left_out) == 0
    # row 3 lacks the first member of table 0's bag (of 3 under bags)
    dropped = values.copy()
    dropped[3, DENSE] = 0.0
    want = block.copy()
    n = 1 if bags is None else bags[0]
    want[3, :n] = np.r_[block[3, 1:n], 0]
    x, got, left_out = lay(_arrays(ids, dropped, data["label"]))
    np.testing.assert_array_equal(np.asarray(got), laid(want))
    np.testing.assert_array_equal(np.asarray(x), values[:, :DENSE].T)
    assert int(left_out) == 0


def test_b_the_cross_net_forward_and_backward_is_the_references(
        harness, cfg):
    """``dlrm._cross`` and ``jax.vjp`` of it against the reference's
    ``_cross_forward`` and ``_cross_backward`` in float64, on seeded
    random weights at the test's widths."""
    width = cfg["mlp_top"][0]
    shapes = {k: v for k, v in dlrm.dense_shapes(
        cfg["mlp_bot"], cfg["mlp_top"], (3, RANK)).items()
        if k.startswith("cross.")}
    rng = np.random.default_rng(9)
    p = {k: rng.standard_normal(s) * 0.2 for k, s in shapes.items()}
    x0 = rng.standard_normal((32, width))
    out, back = jax.vjp(
        dlrm._cross, {k: jnp.asarray(v, jnp.float32) for k, v in p.items()},
        jnp.asarray(x0.T, jnp.float32))
    want, kept = harness.config._cross_forward(p, x0, 3)
    np.testing.assert_allclose(np.asarray(out).T, want, rtol=1e-5, atol=1e-5)
    dx = rng.standard_normal(want.shape)
    grads = {}
    dx0 = harness.config._cross_backward(p, x0, kept, dx, grads)
    got_grads, got_dx0 = back(jnp.asarray(dx.T, jnp.float32))
    np.testing.assert_allclose(np.asarray(got_dx0).T, dx0, rtol=1e-4,
                               atol=1e-4 * np.abs(dx0).max())
    for k, g in grads.items():
        np.testing.assert_allclose(np.asarray(got_grads[k]), g, rtol=1e-4,
                                   atol=1e-4 * np.abs(g).max(), err_msg=k)


def _arrays(rows_ids, values, label):
    """A batch as the feed hands it, from ``[rows, width]`` arrays whose
    value-0 entries are left out."""
    keep = values != 0
    keep[:, :DENSE] = True
    indices, vals = rows_ids[keep], values[keep]
    pad = rows_ids.size + 64 - len(indices)
    return {"label": jnp.asarray(label, jnp.float32),
            "weight": jnp.ones(len(label)),
            "indices": jnp.asarray(np.pad(indices, (0, pad)), jnp.int32),
            "values": jnp.asarray(np.pad(vals, (0, pad)), jnp.float32),
            "offsets": jnp.asarray(
                np.concatenate([[0], np.cumsum(keep.sum(1))]), jnp.int32)}


def _row_half(params, batch, cfg, d_vectors=None):
    """The step's table side alone (``step.gather`` to the row write):
    (the pooled vectors, their gradient, the new ``emb`` and ``m``); the
    gradient from the dense side of the same step unless given."""
    lows = table_lows(DENSE, cfg["field_sizes"])
    bags = tuple(cfg["bag_sizes"])
    head = dlrm._head(params, batch, DENSE, lows, cfg["field_sizes"], bags)
    vectors, back = jax.vjp(partial(dlrm._pool, head.ids, bags), head.rows)
    if d_vectors is None:
        d_vectors = dlrm._dcn_grads(
            dlrm._dense_of(params), vectors, head.x, batch["label"],
            batch["weight"])[3]
    (upd,) = back(d_vectors)
    rows, m = fm._rowwise_adagrad(
        params["emb"], params["m"], head.order,
        upd.at[head.place].get(), head.read, cfg["learning_rate"],
        cfg["eps"])
    return vectors, d_vectors, rows, m


def _gathers_from(jaxpr, shape, in_loop=False):
    """[(in a loop, ...)] for each gather of ``jaxpr`` (and of the jaxprs
    inside it) whose operand has ``shape``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and \
                eqn.invars[0].aval.shape == shape:
            found.append(in_loop)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _gathers_from(
                sub, shape, in_loop or eqn.primitive.name == "while")
    return found


def test_d_the_row_state_is_read_in_one_gather():
    """``m`` is read at every slot of the batch in ONE gather, outside any
    loop, where the distinct ids fill three passes of ``_UPDATE_CHUNK``:
    the new rows and ``m`` are the float64 rule's at every id, and an id
    no entry names keeps both to the bit."""
    n_ids, cols, lr, eps = 20_000, 8, 0.05, 1e-8
    rng = np.random.default_rng(3)
    indices = rng.integers(1, n_ids, 4 * fm._UPDATE_CHUNK).astype(np.int32)
    table = rng.standard_normal((n_ids, cols)).astype(np.float32)
    state = rng.random(n_ids).astype(np.float32)
    grads = rng.standard_normal((indices.size, cols)).astype(np.float32)

    def rule(table, state, indices, grads):
        order, at, _ = fm._in_id_order(
            indices, jnp.arange(indices.size), jnp.zeros(indices.size),
            n_ids)
        grads = grads[at]
        read = fm._Read(fm._take_distinct((table,), order))
        return fm._rowwise_adagrad(table, state, order, grads, read, lr,
                                   eps)

    args = (jnp.asarray(table), jnp.asarray(state), jnp.asarray(indices),
            jnp.asarray(grads))
    assert _gathers_from(jax.make_jaxpr(rule)(*args).jaxpr,
                         (n_ids,)) == [False]
    rows, m = jax.jit(rule)(*args)
    distinct = np.unique(indices)
    assert distinct.size > 2 * fm._UPDATE_CHUNK
    g = np.zeros((n_ids, cols))
    np.add.at(g, indices, grads.astype(np.float64))
    want_m = state.astype(np.float64)
    want_m[distinct] += np.mean(g[distinct] ** 2, axis=1)
    want_rows = table.astype(np.float64)
    want_rows[distinct] -= lr * g[distinct] / (
        np.sqrt(want_m[distinct]) + eps)[:, None]
    np.testing.assert_allclose(np.asarray(m), want_m, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(rows), want_rows, rtol=1e-5,
                               atol=1e-6)
    untouched = np.setdiff1d(np.arange(n_ids), distinct)
    assert np.array_equal(np.asarray(m)[untouched], state[untouched])
    assert np.array_equal(np.asarray(rows)[untouched], table[untouched])


def test_c_the_slices_of_a_row_split_tie_to_the_uncut_step(harness, cfg):
    """The deployment's share: the 8 tables that the configuration splits
    (published over ``split_above`` rows) cut into S = 4 row slices, each
    slice a table tree of its own (its rows of the large tables, every
    small table whole) and a batch of the ids it owns (the others left
    out). The slices' partial pooled sums of a large table add up to the
    uncut pooled vector and a small table's is the uncut one on every
    slice; handed the uncut step's gradient of the pooled vectors (what
    the deployment's all-to-all brings back), each slice's new rows and
    ``m`` are the uncut step's at the ids it owns."""
    slices = 4
    sizes = np.asarray(cfg["field_sizes"])
    split = np.asarray(cfg["table_rows"]["published"]) > \
        cfg["table_rows"]["split_above"]
    assert split.sum() == 8
    height = -(-sizes // slices)
    bags = np.asarray(cfg["bag_sizes"])
    data = harness.config.rows(dict(cfg, rows=ROWS), 11)
    tree = dlrm.init_dlrm_params(
        cfg["num_features"], K, DENSE, sizes, cfg["mlp_bot"], cfg["mlp_top"],
        seed=4, cross=(3, RANK), optimizer="adagrad")
    tree["m"] = jnp.asarray(np.random.default_rng(1).random(
        cfg["num_features"]), jnp.float32)
    batch = _arrays(data["ids"], data["values"], data["label"])
    pooled, d_vectors, rows, m = _row_half(tree, batch, cfg)
    # the learner's own step writes what the table side here writes (one
    # program against operations one at a time: within a rounding)
    step = make_dlrm_train_step(
        cfg["num_features"], DENSE, sizes, cfg["learning_rate"],
        bag_sizes=bags, interaction="dcn", optimizer="adagrad")
    after, _ = step(tree, batch)
    for got, want in ((after["emb"], rows), (after["m"], m)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-9)

    lows = table_lows(DENSE, sizes)
    table_of = np.repeat(np.arange(len(sizes)), bags)
    ids = data["ids"][:, DENSE:]
    within = ids - lows[table_of]
    owner = np.where(split[table_of], within // height[table_of], -1)
    held = np.where(split, height, sizes)
    local_lows = table_lows(DENSE, held)
    total = np.zeros(pooled.shape)
    for s in range(slices):
        # a local id's global one; the last slice's rows past its
        # table's end are none (0, never named)
        global_of = np.arange(1 + DENSE + held.sum())
        for f in range(len(sizes)):
            first = s * height[f] if split[f] else 0
            rows_of = first + np.arange(held[f])
            global_of[local_lows[f]:local_lows[f] + held[f]] = np.where(
                rows_of < sizes[f], lows[f] + rows_of, 0)
        part = {k: v for k, v in tree.items() if k not in ("emb", "m")}
        part["emb"] = tree["emb"][global_of].at[0].set(0.0)
        part["m"] = tree["m"][global_of].at[0].set(0.0)
        mine = (owner == s) | (owner == -1)
        local = local_lows[table_of] + np.where(
            split[table_of], within - s * height[table_of], within)
        values = data["values"].copy()
        values[:, DENSE:] = np.where(mine, values[:, DENSE:], 0.0)
        row_ids = np.concatenate(
            [data["ids"][:, :DENSE], np.where(mine, local, 0)], axis=1)
        vectors, _, new_rows, new_m = _row_half(
            part, _arrays(row_ids, values, data["label"]),
            dict(cfg, field_sizes=held), d_vectors)
        vectors = np.asarray(vectors, np.float64)
        total += np.where(split[:, None, None], vectors, 0.0)
        np.testing.assert_array_equal(
            vectors[~split], np.asarray(pooled)[~split])
        named = np.unique(local[mine & (owner == s)])
        assert named.size
        np.testing.assert_array_equal(
            np.asarray(new_rows)[named], np.asarray(rows)[global_of[named]])
        np.testing.assert_array_equal(
            np.asarray(new_m)[named], np.asarray(m)[global_of[named]])
    np.testing.assert_allclose(
        total[split], np.asarray(pooled, np.float64)[split], rtol=1e-5,
        atol=1e-6)


def test_d_a_snapshot_restores_under_both_layouts(trained, cfg, data):
    """The snapshot holds ``emb``, ``m``, every dense parameter and every
    accumulator by name; restored into lane rows (one id of 128 lanes at
    the configuration's widths, 16 of 8 here) it is the learner to the
    bit, and the step over the tables apart (the dict) and over lane
    rows agree to the bit."""
    model = trained.model
    snap = jax.device_get(model.snapshot_model())
    shapes = dlrm.dense_shapes(cfg["mlp_bot"], cfg["mlp_top"], (3, RANK))
    want = dict(shapes, **{"G." + k: v for k, v in shapes.items()},
                emb=(cfg["num_features"], K), m=(cfg["num_features"],))
    assert {k: v.shape for k, v in snap["params"].items()} == want
    again = _learner(cfg)
    again.restore_snapshot_model(snap)
    assert isinstance(again.params, PackedTables)
    for k in ("emb", "m", "G.top.0.w", "cross.1.u"):
        np.testing.assert_array_equal(
            np.asarray(again.params[k]).view(np.uint32),
            np.asarray(model.params[k]).view(np.uint32), err_msg=k)
    apart = {k: jnp.asarray(v) for k, v in snap["params"].items()}
    step = make_dlrm_train_step(
        cfg["num_features"], DENSE, cfg["field_sizes"], cfg["learning_rate"],
        bag_sizes=cfg["bag_sizes"], interaction="dcn", optimizer="adagrad")
    part = slice(0, ROWS)
    values = np.where(data["keep"][part], data["values"][part], 0.0)
    arrays = _arrays(data["ids"][part], values, data["label"][part])
    lane_rows, sums = step(again.params, arrays)
    dict_tree, sums_apart = step(apart, arrays)
    assert isinstance(lane_rows, PackedTables) and isinstance(dict_tree, dict)
    assert float(sums["loss_sum"]) == float(sums_apart["loss_sum"])
    for k in apart:
        np.testing.assert_array_equal(
            np.asarray(lane_rows[k]).view(np.uint32),
            np.asarray(dict_tree[k]).view(np.uint32), err_msg=k)
    bad = dict(snap["params"])
    del bad["m"]
    with pytest.raises(DMLCError, match="snapshot holds"):
        _learner(cfg).restore_snapshot_model({"params": bad})


def test_d_at_128_columns_an_id_is_a_lane_row_the_dma_writer_serves(cfg):
    """At the configuration's 128 columns ``emb`` keeps one id a 128-lane
    row, the one geometry the DMA writer takes (``row_writer`` on a TPU);
    ``m`` lies apart, a word an id."""
    model = _learner(cfg, num_factors=128, mlp_bot=[DENSE, 16, 128],
                     mlp_top=[128 * 27, 8, 1])
    assert model.lane_geometry == (128, 1)
    assert fm.row_writer("tpu", model.lane_geometry[0]) == "dma"
    model.init_tables(0)
    assert model.params.rows.shape == (cfg["num_features"], 128)
    assert model.params["m"].shape == (cfg["num_features"],)
    assert model.table_names() == ("emb", "m")
    # the accumulators start at 0, G and m alike, as the MLPerf rules fix
    for k in ("m", "G.cross.0.u", "G.top.1.b"):
        assert not np.asarray(model.params[k]).any(), k


def _one_bfloat16_pass(patch):
    """Every matrix product of the MLPs and the cross net in ONE bfloat16
    pass, as ``precision=DEFAULT`` runs a float32 product on a TPU: the
    operands rounded to bfloat16, the sums in float32 (the CPU takes no
    notice of ``precision``, so the rounding is written out)."""
    def rounded(a):
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def product(a, b):
        return jnp.matmul(rounded(a), rounded(b),
                          precision=lax.Precision.DEFAULT)

    def mlp(dense, net, h, last_relu):
        layers = sum(name.startswith(net + ".") for name in dense) // 2
        for layer in range(layers):
            h = product(dense["%s.%d.w" % (net, layer)], h) \
                + dense["%s.%d.b" % (net, layer)][:, None]
            if last_relu or layer + 1 < layers:
                h = jax.nn.relu(h)
        return h

    def cross(dense, x0):
        x = x0
        for layer in range(sum(k.startswith("cross.") for k in dense) // 3):
            x = x0 * (product(dense["cross.%d.u" % layer], product(
                dense["cross.%d.v" % layer], x))
                + dense["cross.%d.c" % layer][:, None]) + x
        return x

    patch(dlrm, "_mlp", mlp)
    patch(dlrm, "_cross", cross)


def _a_table_dropped(patch, table):
    """The model without one table in ``x0``: its pooled vectors read as
    0."""
    grads = dlrm._dcn_grads
    patch(dlrm, "_dcn_grads", lambda dense, vectors, *rest: grads(
        dense, vectors.at[table].set(0.0), *rest))


def _dense_state_dropped(patch):
    """The dense rule reads every ``G_old`` as 0: ``G = g^2``."""
    rule = dlrm._dense_rule
    patch(dlrm, "_dense_rule", lambda params, dense, *rest: rule(
        {dlrm.ACC + k: jnp.zeros_like(params[dlrm.ACC + k]) for k in dense},
        dense, *rest))


def _row_state_not_written(patch):
    """The row rule keeps ``m`` as it was: a step reads a stale ``m``."""
    rule = dlrm._rowwise_adagrad
    patch(dlrm, "_rowwise_adagrad", lambda table, state, *rest: (
        rule(table, state, *rest)[0], state))


#: faults planted in the step, and the limit of the configuration's
#: ``check`` that has to refuse each at its rehearse size (the chip's
#: readings at full size are in the configuration's check.why)
PLANTED = {
    "bfloat16_products": (_one_bfloat16_pass, "update_rel"),
    "bag_of_100_dropped": (partial(_a_table_dropped, table=20), "update_rel"),
    "first_table_dropped": (partial(_a_table_dropped, table=0), "update_rel"),
    "dense_state_dropped": (_dense_state_dropped, "update_rel"),
    "row_state_not_written": (_row_state_not_written, "update_rel"),
}


@pytest.fixture(scope="module")
def rehearsal(harness, tmp_path_factory):
    """The configuration at its own rehearse size (every width the
    published one, the rehearse tables and batch) and two batches' rows
    as one LIBSVM file, a twentieth of the bags' members left out."""
    cell = harness.spec.Cell("mlperf-dlrm-dcnv2.libsvm", rehearse=True)
    cfg = dict(cell.cfg, rows=2 * int(cell.cfg["batch_rows_per_chip"]))
    rows = harness.config.rows(cfg, 2147483659)
    keep = np.random.default_rng(5).random(rows["ids"].shape) > 0.05
    keep[:, :DENSE] = True
    path = str(tmp_path_factory.mktemp("rehearsal") / "rows.libsvm")
    _write_text(path, rows["label"], rows["ids"], rows["values"], keep)
    # the members left out of the file: a value of 0 to the reference
    return cfg, dict(rows, path=path,
                     values=np.where(keep, rows["values"], 0.0))


@pytest.mark.parametrize("fault", [None] + sorted(PLANTED))
def test_e_the_committed_limits_pass_the_step_and_refuse_a_fault(
        harness, rehearsal, monkeypatch, fault):
    """``benchmarks/harness/check.py``'s own ``run`` over the learner and
    the float64 reference under the limits and the start of
    ``mlperf-dlrm-dcnv2.json`` as committed, at the configuration's
    rehearse size (the published widths: a bfloat16 product's error
    grows with them, and at this file's cut widths it stays under the
    limit): the steps as they are pass; with a fault planted they are
    refused by the limit that is there for it (the DEFAULT-precision
    control among them)."""
    cfg, data = rehearsal
    if fault is not None:
        plant, limit = PLANTED[fault]
        plant(monkeypatch.setattr)
    model = harness.config.learner(cfg, None)
    harness.config.init_params(cfg, 5, model, None)
    # the check's own start: every accumulator at accumulator_start
    start = np.float32(cfg["check"]["accumulator_start"])
    for k in ("m", "G.cross.0.u", "G.top.4.b"):
        assert np.all(np.asarray(model.params[k]) == start), k
    feed = _feed(cfg, data["path"], rows=int(cfg["batch_rows_per_chip"]))
    cell = types.SimpleNamespace(cfg=cfg, config=harness.config)
    facts = harness.check.run(cell, model, feed, data,
                              int(cfg["check"]["steps"]))
    feed.close()
    assert facts["untouched_changed"] == 0
    assert set(facts["update_rel_of"]) >= {"emb", "m", "G.cross.0.v"}
    if fault is None:
        assert facts["ok"], facts["compared"]
        return
    value, bound = facts["compared"][limit]
    assert not facts["ok"] and value > bound, facts["compared"]


def _read_at_the_start(patch, cfg):
    """Both rules read the accumulators the check started, every step,
    where they should read what the step before wrote: right at the
    first step, stale from the second on."""
    start = np.float32(cfg["check"]["accumulator_start"])
    dense_rule, row_rule = dlrm._dense_rule, dlrm._rowwise_adagrad
    patch(dlrm, "_dense_rule", lambda params, dense, *rest: dense_rule(
        {dlrm.ACC + k: jnp.full_like(params[dlrm.ACC + k], start)
         for k in dense}, dense, *rest))
    patch(dlrm, "_rowwise_adagrad", lambda table, state, *rest: row_rule(
        table, jnp.full_like(state, start), *rest))


@pytest.mark.parametrize("stale", [False, True])
def test_e_a_state_read_stale_at_the_second_step_is_refused(
        harness, rehearsal, monkeypatch, stale):
    """The carry from one step to the next, which the committed check's
    one step cannot see: two steps through ``check.run`` at the rehearse
    size, under the committed limits, pass as they are and are refused
    when each rule reads the accumulators the check started instead of
    those the first step wrote (``G`` and ``m`` read back short of the
    first step's ``g^2``)."""
    cfg, data = rehearsal
    if stale:
        _read_at_the_start(monkeypatch.setattr, cfg)
    model = harness.config.learner(cfg, None)
    harness.config.init_params(cfg, 5, model, None)
    feed = _feed(cfg, data["path"], rows=int(cfg["batch_rows_per_chip"]))
    cell = types.SimpleNamespace(cfg=cfg, config=harness.config)
    facts = harness.check.run(cell, model, feed, data, 2)
    feed.close()
    assert facts["untouched_changed"] == 0
    assert facts["ok"] is not stale, facts["compared"]
    if stale:
        assert facts["update_rel"] > cfg["check"]["update_rel_tol"]


def test_f_criteo_dlrms_program_is_the_one_the_defaults_give():
    """``bag_sizes`` of one, ``dot`` and ``sgd`` said out loud lower to the
    program ``tests/test_fm_step_programs.py`` pins for ``criteo-dlrm``:
    the new keys change nothing of the older cell's step."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        import test_fm_step_programs as pinned
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    if jax.__version__ != pinned.PINNED_JAX:
        pytest.skip("the digests are of jax %s's lowering"
                    % pinned.PINNED_JAX)
    sizes = pinned.DLRM_TABLES
    model = DLRMLearner(
        num_factors=8, field_sizes=sizes, mlp_bot=(13, 16, 8),
        mlp_top=(8 + len(sizes) * (len(sizes) + 1) // 2, 8, 1),
        bag_sizes=(1,) * len(sizes), interaction="dot", optimizer="sgd")
    nnz = 13 + len(sizes)
    batch = {
        "label": jnp.zeros((8,)), "weight": jnp.ones((8,)),
        "indices": jnp.zeros((8 * nnz,), jnp.int32),
        "values": jnp.ones((8 * nnz,)),
        "offsets": jnp.arange(9, dtype=jnp.int32) * nnz}
    model.init_tables(0)
    model._ensure(1003)
    step = model._step
    while hasattr(step, "__wrapped__"):
        step = step.__wrapped__
    text = step.lower(model.params, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        pinned.PROGRAMS["criteo-dlrm"]


class TestLoweredStep:
    """The step's scopes, from its lowered text."""

    @pytest.fixture(scope="class")
    def text(self, cfg):
        model = _learner(cfg)
        model.init_tables(0)
        n = ROWS * cfg["nnz_per_row"]
        batch = {
            "label": jnp.zeros(ROWS), "weight": jnp.ones(ROWS),
            "indices": jnp.zeros(n + 256, jnp.int32),
            "values": jnp.ones(n + 256),
            "offsets": jnp.zeros(ROWS + 1, jnp.int32)}
        model._ensure(cfg["num_features"])
        step = model._step
        while hasattr(step, "__wrapped__"):
            step = step.__wrapped__
        return step.lower(model.params, batch).as_text(debug_info=True)

    def test_g_every_scope_is_there_and_none_inside_another(self, text):
        """``step.pool``, ``step.cross``, ``step.dense``, ``step.state`` and
        the head's and the update's scopes each name operations, forward
        and backward; no path names two of them."""
        paths = re.findall(r'loc\("([^"]*/step\.[^"]*)"', text)
        firsts = {}
        for path in paths:
            scopes = [p for p in path.split("/") if p.startswith("step.")]
            assert len(scopes) == 1, path
            backward = "transpose(" in path
            firsts.setdefault(scopes[0], set()).add(backward)
        assert set(firsts) == {"step.order", "step.gather", "step.pool",
                               "step.cross", "step.dense", "step.state",
                               "step.update"}
        for scope in ("step.pool", "step.cross", "step.dense"):
            assert firsts[scope] == {False, True}, scope

    def test_g_every_product_is_at_highest(self, text):
        dots = [line for line in text.splitlines()
                if "stablehlo.dot_general" in line]
        assert dots and all("HIGHEST" in line for line in dots)


def _made_under(jaxpr, scope, inside=False):
    """[(primitive, operand shapes, result shapes)] of each equation of
    ``jaxpr`` (and of the jaxprs inside it) made under ``scope``, forward
    or backward."""
    found = []
    for eqn in jaxpr.eqns:
        under = inside or scope in str(eqn.source_info.name_stack).split("/")
        if under:
            found.append((eqn.primitive.name,
                          [getattr(v, "aval", v).shape for v in eqn.invars],
                          [v.aval.shape for v in eqn.outvars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _made_under(sub, scope, under)
    return found


def test_g_the_pool_adds_whole_slabs(cfg):
    """``step.pool`` of the ``dcn`` step, forward and backward, holds no
    ``pad`` (jax's transpose of a slice) and no reshape of the places' rows
    ``[P * R, K]`` to ``[R, P, K]`` (a relayout on the chip at 214 places
    a row): the rows are ``[P, R, K]``, a bag's members whole ``[R, K]``
    slabs that its sum adds and its backward pass concatenates."""
    model = _learner(cfg)
    model.init_tables(0)
    model._ensure(cfg["num_features"])
    n = ROWS * cfg["nnz_per_row"]
    batch = {
        "label": jnp.zeros(ROWS), "weight": jnp.ones(ROWS),
        "indices": jnp.zeros(n + 256, jnp.int32), "values": jnp.ones(n + 256),
        "offsets": jnp.zeros(ROWS + 1, jnp.int32)}
    step = model._step
    while hasattr(step, "__wrapped__"):
        step = step.__wrapped__
    pool = _made_under(jax.make_jaxpr(step)(model.params, batch).jaxpr,
                       "step.pool")
    places = sum(cfg["bag_sizes"])
    assert ("reshape", [(places * ROWS, K)], [(places, ROWS, K)]) in pool
    assert not [e for e in pool if e[0] == "pad"]
    assert not [e for e in pool if e[0] == "reshape"
                and e[2] == [(ROWS, places, K)]]


def test_h_span_arguments_counters_and_gauges(cfg, data):
    """``fit_uri`` end to end: the ``epoch`` span's new arguments, the
    bag, cross-net and stateful counters, the two state gauges."""
    spans = []
    obs_trace.add_listener(spans.append)

    def counters():
        flat = obs.registry().flat_values()
        out = {}
        for k, v in flat.items():
            if 'model="dlrm"' in k:
                out[k.split("{")[0]] = out.get(k.split("{")[0], 0.0) + v
        return out

    before = counters()
    try:
        model = _learner(cfg)
        model.fit_uri(data["path"], batch_size=ROWS, epochs=1)
    finally:
        obs_trace.remove_listener(spans.append)
    after = counters()

    def grew(name):
        return after[name] - before.get(name, 0.0)

    multi = [k for k in cfg["bag_sizes"] if k > 1]
    places = np.repeat(np.asarray(cfg["bag_sizes"]) > 1, cfg["bag_sizes"])
    kept = data["keep"][:, DENSE:][:, places].sum()
    assert multi and grew("dmlc_fit_bag_entries_total") == kept
    assert grew("dmlc_fit_cross_net_steps_total") == STEPS
    assert grew("dmlc_fit_dense_net_steps_total") == STEPS
    assert grew("dmlc_fit_stateful_update_steps_total") == STEPS
    assert grew("dmlc_fit_run_sum_steps_total") == 0
    assert after["dmlc_fit_dense_state_bytes"] == 4 * model.dense_params
    assert after["dmlc_fit_optimizer_state_bytes"] == \
        4 * cfg["num_features"]
    args = [e for e in spans if e["name"] == "epoch"
            and e.get("ph") == "X"][-1]["args"]
    assert args["optimizer"] == "adagrad" and args["interaction"] == "dcn"
    assert args["bag_ids"] == 214 and args["dense_params"] == \
        model.dense_params
    assert args["place_order"] == "member"


def test_h_the_published_net_is_counted(cfg):
    """The published widths hold 16,044,545 dense values, of which the
    cross net 10,627,200; a row's products are 96 MFLOP forward and
    backward, 394 GFLOP a step of 4,096 rows, the cross net's 261."""
    with open(CONFIG + ".json") as f:
        full = json.load(f)
    model = DLRMLearner(**{k: full[k] for k in HYPER})
    assert model.dense_params == 16044545
    assert model.param.num_features == 12875176
    assert sum(full["bag_sizes"]) + DENSE == full["nnz_per_row"]
    sys.path.insert(0, BENCH)
    try:
        from harness import spec

        config = spec.load_module(CONFIG + ".py")
    finally:
        sys.path.remove(BENCH)
    cross = config.cross_needs(full, 4096)["flops"]
    dense = config.dense_needs(full, 4096)["flops"]
    assert cross == 2 * 3 * 3 * 2 * 512 * 3456 * 4096
    assert round((cross + dense) / 1e9) == 394 and round(cross / 1e9) == 261
    assert config.step_needs(full, 4096)["flops"] > cross + dense
    assert full["field_sizes"] == full["table_rows"]["held"]
    held = [-(-n // full["chips_in_deployment"])
            if n > full["table_rows"]["split_above"] else n
            for n in full["table_rows"]["published"]]
    assert held == full["table_rows"]["held"]


@pytest.mark.parametrize("over,match", [
    ({"bag_sizes": (1, 2)}, "bag_sizes"),
    ({"mlp_top": (100, 8, 1)}, "cross net's width"),
    ({"eps": 0.0}, "eps"),
    ({"interaction": "dot", "mlp_top": (8 + 26 * 27 // 2, 8, 1)},
     "cross net only"),
])
def test_h_hyperparameters_that_do_not_fit_are_refused(cfg, over, match):
    with pytest.raises(DMLCError, match=match):
        _learner(cfg, **over)


def test_h_a_bag_overfull_refuses_the_pass(cfg, tmp_path):
    """A row that names more ids of a table than its bag holds: the step
    keeps the bag's worth and the pass is refused by name."""
    lows = table_lows(DENSE, cfg["field_sizes"])
    path = str(tmp_path / "over.libsvm")
    with open(path, "w") as f:
        for _ in range(8):  # table 1's bag holds 2
            f.write("1 1:0.5 %d:1 %d:1 %d:1\n"
                    % (lows[1], lows[1] + 1, lows[1] + 2))
    with pytest.raises(DMLCError, match="more ids than its bag holds"):
        _learner(cfg).fit_uri(path, batch_size=8)


class TestReaders:
    """The three new metrics' readers over what a run hands them."""

    def _reader(self, harness, name):
        return harness.spec.load_module(
            os.path.join(BENCH, "metrics", name + ".py"))

    @pytest.mark.parametrize(
        "name", ["step_pool_ms", "step_cross_ms", "step_cross_roofline"])
    def test_i_no_trace_no_value(self, harness, name):
        """A run that was not traced, a program without the scope, a
        configuration without ``cross_needs``: nothing, and no error
        (what a parent commit gives the new readers)."""
        reader = self._reader(harness, name)
        run = {"trace": None, "cell": "mlperf-dlrm-dcnv2.libsvm",
               "spans": [], "config": types.SimpleNamespace(), "cfg": {},
               "peaks": None, "batch_rows": 4096, "chips": 1}
        assert reader.read(run) is None

    def test_i_the_roofline_is_the_needs_over_the_time(
            self, harness, monkeypatch):
        reader = self._reader(harness, "step_cross_roofline")
        monkeypatch.setattr(reader, "_cross_ms", lambda run: 80.0)
        run = {"config": harness.config, "cfg": json.load(open(
            CONFIG + ".json")), "peaks": {"flops_per_s": 197e12},
            "batch_rows": 4096, "chips": 1}
        flops = harness.config.cross_needs(run["cfg"], 4096)["flops"]
        assert reader.read(run) == pytest.approx(
            100 * flops / 197e12 / 0.080, rel=1e-12)


def test_j_the_new_cell_rehearses():
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data with its bags, init, the check against the float64 reference
    with every accumulator under its name, window, result line) at the
    rehearse size, every width the published one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mlperf-dlrm-dcnv2.libsvm", "--rehearse", "--seconds", "1",
         "--seed", "2147483659"],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    detail = json.loads(lines[-2].split("[bench] detail ", 1)[1])
    of = detail["check"]["update_rel_of"]
    assert {"emb", "m", "cross.2.u", "G.top.4.b"} <= set(of)
    assert detail["check"]["untouched_changed"] == 0

"""DLRM (``models/dlrm.py`` ``DLRMLearner``: one per-id table and a tree
of dense parameters, two MLPs and a dot interaction in the step) on the
suite's CPU devices, at a small size: the ``criteo-dlrm`` configuration's
rehearse tables (26 tables of 3 to 5,000 rows), the published MLP widths
cut to 13-32-16-8 and 24-8-1 FOR THE TEST ONLY, 4 steps of 256 rows.
Held to a test-side float64 copy of the equations (pair by pair, where
the configuration's reference takes the triangle), to that reference, and
to ``jax.grad`` of a plain ``jax.numpy`` forward. (That the older cells'
steps did not move is ``tests/test_fm_step_programs.py``'s.)
"""

import json
import os
import subprocess
import sys
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

from dmlc_tpu import obs
from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import dlrm
from dmlc_tpu.models.dlrm import (
    DLRMLearner,
    dense_shapes,
    dlrm_forward,
    init_dlrm_params,
    make_dlrm_train_step,
    table_lows,
)
from dmlc_tpu.models.fm import PackedTables
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.utils.logging import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "criteo-dlrm")
STEPS, ROWS, K = 4, 256, 8
DENSE = 13
HYPER = ("learning_rate", "num_factors", "num_features", "dense_features",
         "field_sizes", "mlp_bot", "mlp_top")


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own generator, check, readers and reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, textgen

        yield types.SimpleNamespace(
            spec=spec, textgen=textgen, check=check,
            config=spec.load_module(CONFIG + ".py"))
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG + ".json") as f:
        out = json.load(f)
    out.update(out["rehearse"])
    fields = len(out["field_sizes"])
    out.update(
        rows=STEPS * ROWS, batch_rows_per_chip=ROWS, num_factors=K,
        mlp_bot=[DENSE, 32, 16, K],
        mlp_top=[K + fields * (fields + 1) // 2, 24, 8, 1])
    return out


def _write_text(path, label, ids, values, keep):
    """A LIBSVM file a row a line, the entries where ``keep`` is set."""
    with open(path, "w") as f:
        for y, row_ids, row_values, row_keep in zip(label, ids, values, keep):
            f.write("%d %s\n" % (y, " ".join(
                "%d:%s" % (i, "%.4f" % v if i <= DENSE else "1")
                for i, v, k in zip(row_ids, row_values, row_keep) if k)))


def _data(harness, cfg, tmp_path_factory, case):
    rows = harness.config.rows(cfg, 2147483659)
    keep = np.ones(rows["ids"].shape, bool)
    if case == "missing":
        keep = np.random.default_rng(5).random(keep.shape) > 0.1
    path = str(tmp_path_factory.mktemp("dlrm") / "rows.libsvm")
    _write_text(path, rows["label"], rows["ids"], rows["values"], keep)
    return dict(rows, path=path, keep=keep)


@pytest.fixture(scope="module", params=["whole", "missing"])
def data(request, harness, cfg, tmp_path_factory):
    """The configuration's rows as arrays and as one LIBSVM file;
    ``missing``: a tenth of the entries, dense and table alike, are not in
    the file (``keep`` says which are), so a row lacks fields and the
    batch is laid out by the scatters."""
    return _data(harness, cfg, tmp_path_factory, request.param)


@pytest.fixture(scope="module")
def whole(harness, cfg, tmp_path_factory):
    return _data(harness, cfg, tmp_path_factory, "whole")


def _learner(cfg, **over):
    hyper = {k: cfg[k] for k in HYPER}
    hyper.update(over)
    return DLRMLearner(**hyper)


def _feed(cfg, path, rows=ROWS):
    return DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=rows, layout="csr",
                  num_features=cfg["num_features"]))


def _logical(model):
    """Every parameter whole, float64: ``emb`` as the logical table."""
    ids = jnp.arange(model.param.num_features, dtype=jnp.int32)
    out = {"emb": np.asarray(model.table_rows("emb", ids), np.float64)}
    out.update({k: np.float64(v) for k, v in model.scalars().items()})
    return out


def _relu_net(p, net, h):
    """``h [B, in]`` through ``<net>.<l>``; returns every layer's input
    and pre-activation too."""
    seen = []
    n = sum(k.startswith(net) for k in p) // 2
    for at in range(n):
        a = h @ p["%s.%d.w" % (net, at)].T + p["%s.%d.b" % (net, at)]
        seen.append((h, a))
        h = a if net == "top" and at + 1 == n else np.maximum(a, 0)
    return h, seen


def _net_back(p, net, seen, dh, grads):
    n = len(seen)
    for at in reversed(range(n)):
        h, a = seen[at]
        da = dh if net == "top" and at + 1 == n else dh * (a > 0)
        grads["%s.%d.w" % (net, at)] = np.einsum("bo,bi->oi", da, h)
        grads["%s.%d.b" % (net, at)] = da.sum(0)
        dh = da @ p["%s.%d.w" % (net, at)]
    return dh


def _float64_step(p, x, ids, named, y, lr):
    """One SGD step of the module's equations in float64, the interaction
    PAIR BY PAIR. ``ids [B, F]`` whole-table ids, ``named`` which of them
    the row holds. Returns the loss; ``p`` is updated in place."""
    z, bot = _relu_net(p, "bot", x)
    e = p["emb"][ids] * named[:, :, None]
    t = np.concatenate([z[:, None], e], axis=1)
    pairs = [(i, j) for i in range(t.shape[1]) for j in range(i)]
    under = np.stack([(t[:, i] * t[:, j]).sum(1) for i, j in pairs], axis=1)
    s, top = _relu_net(p, "top", np.concatenate([z, under], axis=1))
    s = s[:, 0]
    loss = float(np.mean(np.logaddexp(0, s) - y * s))
    grads = {}
    dr = _net_back(p, "top", top, ((1 / (1 + np.exp(-s)) - y) / len(y))[:, None],
                   grads)
    dt = np.zeros_like(t)
    for at, (i, j) in enumerate(pairs):
        g = dr[:, z.shape[1] + at, None]
        dt[:, i] += g * t[:, j]
        dt[:, j] += g * t[:, i]
    _net_back(p, "bot", bot, dr[:, :z.shape[1]] + dt[:, 0], grads)
    for k, g in grads.items():
        p[k] -= lr * g
    np.subtract.at(p["emb"], ids.ravel(),
                   lr * (dt[:, 1:] * named[:, :, None]).reshape(-1, K))
    return loss


def _batch_of(data, step):
    part = slice(step * ROWS, (step + 1) * ROWS)
    keep = data["keep"][part]
    return (np.where(keep[:, :DENSE], data["values"][part, :DENSE], 0.0)
            .astype(np.float64),
            data["ids"][part, DENSE:], keep[:, DENSE:],
            data["label"][part].astype(np.float64))


@pytest.fixture(scope="module")
def trained(harness, cfg, data):
    """The learner through ``fit_feed``, a batch a call, with what it held
    after each step; the test-side float64 steps from the same start."""
    model = _learner(cfg)
    model.init_tables(7)
    start = _logical(model)
    feed = _feed(cfg, data["path"])
    one = harness.check._OneBatch(feed, iter(feed))
    got, want = [], []
    ref = {k: v.copy() for k, v in start.items()}
    for step in range(STEPS):
        loss = float(model.fit_feed(one, epochs=1)[0])
        got.append((loss, _logical(model)))
        ref_loss = _float64_step(
            ref, *_batch_of(data, step), lr=cfg["learning_rate"])
        want.append((ref_loss, {k: v.copy() for k, v in ref.items()}))
    feed.close()
    return types.SimpleNamespace(
        model=model, start=start, got=got, want=want)


def test_a_every_step_matches_the_float64_equations(trained):
    """The loss and EVERY parameter after each of the 4 steps; a table of
    3 rows is named by every row of a batch, so an id's entries repeat
    some 85 times and are summed once."""
    moved = {k: np.max(np.abs(trained.want[-1][1][k] - v))
             for k, v in trained.start.items()}
    for (loss, after), (ref_loss, ref) in zip(trained.got, trained.want):
        assert abs(loss - ref_loss) < 2e-6 * abs(ref_loss)
        for k in ref:
            assert np.max(np.abs(after[k] - ref[k])) < 2e-4 * moved[k], k


def test_a_the_configurations_reference_is_the_same_float64_step(
        trained, harness, cfg, data):
    """``benchmarks/configs/criteo-dlrm.py`` ``reference_steps`` (the
    triangle by index, compacted positions) against the test's own."""
    touched = np.unique(data["ids"])
    before = {k: (v[touched] if k == "emb" else v)
              for k, v in trained.start.items()}
    batches = []
    for step in range(STEPS):
        x, ids, named, y = _batch_of(data, step)
        part = slice(step * ROWS, (step + 1) * ROWS)
        batches.append({
            "label": y, "ids": np.searchsorted(touched, data["ids"][part]),
            "values": np.concatenate([x, named.astype(np.float64)], axis=1)})
    losses, ref = harness.config.reference_steps(cfg, before, batches)
    np.testing.assert_allclose(
        losses, [loss for loss, _ in trained.want], rtol=1e-12)
    for k, v in trained.want[-1][1].items():
        np.testing.assert_allclose(
            ref[k], v[touched] if k == "emb" else v, rtol=1e-9, atol=1e-15,
            err_msg=k)


def test_b_rows_no_batch_names_keep_their_bits(trained, data):
    """Rows 0-13 (no table's) and every row no entry of the file names:
    unchanged to the bit, as are they under the float64 steps."""
    named = np.unique(data["ids"][:, DENSE:][data["keep"][:, DENSE:]])
    unnamed = np.setdiff1d(
        np.arange(trained.model.param.num_features), named)
    assert set(range(DENSE + 1)) <= set(unnamed)
    start, end = trained.start["emb"], trained.got[-1][1]["emb"]
    np.testing.assert_array_equal(
        end[unnamed].view(np.uint64), start[unnamed].view(np.uint64))
    assert not start[:DENSE + 1].any()
    assert np.all(np.any(end[named] != start[named], axis=1))


def _plain_batch(cfg, data, step, bucket=None):
    """(the step's batch arrays, the batch as a plain model reads it)."""
    x, ids, named, y = _batch_of(data, step)
    part = slice(step * ROWS, (step + 1) * ROWS)
    keep = data["keep"][part]
    indices = data["ids"][part][keep]
    values = data["values"][part][keep]
    offsets = np.concatenate([[0], np.cumsum(keep.sum(1))])
    pad = (bucket or ROWS * keep.shape[1] + 64) - len(indices)
    arrays = {
        "label": jnp.asarray(y, jnp.float32), "weight": jnp.ones(ROWS),
        "indices": jnp.asarray(np.pad(indices, (0, pad)), jnp.int32),
        "values": jnp.asarray(np.pad(values, (0, pad)), jnp.float32),
        "offsets": jnp.asarray(offsets, jnp.int32)}
    return arrays, (jnp.asarray(x, jnp.float32), ids, named, arrays["label"])


def test_c_the_update_is_jax_grad_of_a_plain_forward(cfg, data):
    """One step against ``jax.grad`` of the model written plainly in
    ``jax.numpy`` (a gather of the table by id, ``dlrm_forward``, the mean
    of the BCE) under ``jax.default_matmul_precision("highest")``."""
    tree = init_dlrm_params(
        cfg["num_features"], K, DENSE, cfg["field_sizes"], cfg["mlp_bot"],
        cfg["mlp_top"], seed=3)
    arrays, (x, ids, named, y) = _plain_batch(cfg, data, 0)

    def loss_of(tree):
        vectors = jnp.where(named[:, :, None], tree["emb"][ids], 0.0)
        s = dlrm_forward({k: v for k, v in tree.items() if k != "emb"},
                         vectors.transpose(1, 2, 0), x.T)
        return jnp.mean(jax.nn.softplus(s) - y * s)

    with jax.default_matmul_precision("highest"):
        loss, grad = jax.value_and_grad(loss_of)(tree)
    step = make_dlrm_train_step(
        cfg["num_features"], DENSE, cfg["field_sizes"], cfg["learning_rate"])
    after, sums = step(tree, arrays)
    assert int(sums["left_out"]) == 0
    np.testing.assert_allclose(
        float(sums["loss_sum"]) / float(sums["weight_sum"]), float(loss),
        rtol=1e-6)
    for k, g in grad.items():
        moved = cfg["learning_rate"] * np.asarray(g)
        np.testing.assert_allclose(
            np.asarray(after[k]), np.asarray(tree[k]) - moved,
            atol=2e-5 * np.abs(moved).max() + 1e-9, err_msg=k)


def test_d_an_id_named_by_every_row_is_summed_once(cfg):
    """64 rows that all name the SAME id of every table: the row's update
    is the sum of its 64 entries' gradients (float64 beside it)."""
    rows = 64
    sizes = cfg["field_sizes"]
    lows = table_lows(DENSE, sizes)
    rng = np.random.default_rng(11)
    x = rng.random((rows, DENSE)).astype(np.float32)
    ids = np.tile(lows + np.asarray(sizes) // 2, (rows, 1))
    y = (rng.random(rows) < 0.5).astype(np.float64)
    every = np.concatenate(
        [np.tile(np.arange(1, DENSE + 1), (rows, 1)), ids], axis=1)
    arrays = {
        "label": jnp.asarray(y, jnp.float32), "weight": jnp.ones(rows),
        "indices": jnp.asarray(every.ravel(), jnp.int32),
        "values": jnp.asarray(np.concatenate(
            [x, np.ones(ids.shape, np.float32)], axis=1).ravel()),
        "offsets": jnp.arange(rows + 1, dtype=jnp.int32) * every.shape[1]}
    model = _learner(cfg)
    model.init_tables(2)
    ref = _logical(model)
    start = ref["emb"].copy()
    model._ensure(cfg["num_features"])
    sums = model.train_step(arrays)
    assert int(sums["touched_rows"]) == len(sizes)
    _float64_step(ref, x.astype(np.float64), ids, np.ones(ids.shape, bool),
                  y, cfg["learning_rate"])
    after = _logical(model)["emb"]
    moved = np.abs(ref["emb"] - start).max()
    assert moved > 0
    assert np.abs(after - ref["emb"]).max() < 1e-4 * moved
    assert (np.any(after != start, axis=1).sum()) == len(sizes)


def test_d_a_second_id_of_one_table_in_a_row_refuses_the_pass(
        cfg, tmp_path):
    """A multi-hot bag: the step keeps one id, counts the other, and the
    pass is refused by name at its end."""
    lows = table_lows(DENSE, cfg["field_sizes"])
    path = str(tmp_path / "two.libsvm")
    with open(path, "w") as f:
        for _ in range(8):
            f.write("1 1:0.5 %d:1 %d:1\n" % (lows[1], lows[1] + 1))
    model = _learner(cfg)
    with pytest.raises(DMLCError, match="more than one id of one table"):
        model.fit_uri(path, batch_size=8)


def test_e_a_snapshot_restores_across_the_layouts(trained, cfg, data):
    """The snapshot holds the logical ``emb`` and the dense tree by name;
    restored into lane rows it is the learner to the bit, and the step
    over the tables apart (the dict) and over lane rows agree to the bit."""
    model = trained.model
    snap = jax.device_get(model.snapshot_model())
    want = dict(dense_shapes(cfg["mlp_bot"], cfg["mlp_top"]),
                emb=(cfg["num_features"], K))
    assert {k: v.shape for k, v in snap["params"].items()} == want
    again = _learner(cfg)
    again.restore_snapshot_model(snap)
    assert isinstance(again.params, PackedTables)
    np.testing.assert_array_equal(
        np.asarray(again.params.rows).view(np.uint32),
        np.asarray(model.params.rows).view(np.uint32))
    apart = {k: jnp.asarray(v) for k, v in snap["params"].items()}
    step = make_dlrm_train_step(
        cfg["num_features"], DENSE, cfg["field_sizes"], cfg["learning_rate"])
    arrays, _ = _plain_batch(cfg, data, 1)
    lane_rows, sums = step(again.params, arrays)
    dict_tree, sums_apart = step(apart, arrays)
    assert isinstance(lane_rows, PackedTables) and isinstance(dict_tree, dict)
    assert float(sums["loss_sum"]) == float(sums_apart["loss_sum"])
    for k in apart:
        np.testing.assert_array_equal(
            np.asarray(lane_rows[k]).view(np.uint32),
            np.asarray(dict_tree[k]).view(np.uint32), err_msg=k)
    bad = dict(snap["params"], emb=snap["params"]["emb"][:-1])
    with pytest.raises(DMLCError, match="snapshot holds"):
        _learner(cfg).restore_snapshot_model({"params": bad})


def test_e_the_dma_row_writer_writes_what_the_scatter_writes(cfg, data):
    """The step as a TPU builds it (``platform="tpu"``: the distinct lane
    rows go back by the DMA writer of models/fm.py), the kernel in
    Pallas' interpreter, beside the step that scatters: the same tree to
    the bit after two steps."""
    model = _learner(cfg)
    model.init_tables(4)
    steps = [make_dlrm_train_step(
        cfg["num_features"], DENSE, cfg["field_sizes"], cfg["learning_rate"],
        **how) for how in ({}, {"platform": "tpu", "interpret": True})]
    first, _ = _plain_batch(cfg, data, 0)
    kernels = ["pallas_call" in str(jax.make_jaxpr(
        getattr(step, "__wrapped__", step))(model.params, first))
        for step in steps]
    assert kernels == [False, True]
    trees = [model.params, model.params]
    for at in range(2):
        arrays, _ = _plain_batch(cfg, data, at)
        trees = [step(tree, arrays)[0] for step, tree in zip(steps, trees)]
    scattered, written = trees
    assert np.any(np.asarray(written.rows) != np.asarray(model.params.rows))
    np.testing.assert_array_equal(
        np.asarray(written.rows).view(np.uint32),
        np.asarray(scattered.rows).view(np.uint32))
    for k in scattered.scalars:
        np.testing.assert_array_equal(
            np.asarray(written.scalars[k]), np.asarray(scattered.scalars[k]))


def test_f_the_check_reads_arrays_through_scalars(harness, cfg, whole):
    """``benchmarks/harness/check.py``'s own ``run``: ``scalars()`` hands
    it every dense parameter WHOLE and it compares each under its name as
    it compares a table's touched rows."""
    data = whole
    model = harness.config.learner(cfg, None)
    harness.config.init_params(cfg, 5, model, None)
    assert model.table_names() == ("emb",)
    held = model.scalars()
    assert {k: np.shape(v) for k, v in held.items()} == dense_shapes(
        cfg["mlp_bot"], cfg["mlp_top"])
    feed = _feed(cfg, data["path"])
    cell = types.SimpleNamespace(cfg=cfg, config=harness.config)
    facts = harness.check.run(cell, model, feed, data, STEPS)
    feed.close()
    assert facts["ok"], facts["compared"]
    assert set(facts["update_rel_of"]) == {"emb"} | set(held)
    assert facts["untouched_changed"] == 0
    assert all(v < 2e-4 for v in facts["update_rel_of"].values())
    assert facts["loss_rel"] < 2e-6


def _one_bfloat16_pass(patch):
    """Every matrix product of the net in ONE bfloat16 pass, as
    ``precision=DEFAULT`` runs a float32 product on a TPU: the operands
    rounded to bfloat16, the sums in float32 (the CPU takes no notice of
    ``precision``, so the rounding is written out; on a TPU the two
    together are ``DEFAULT`` itself, the cotangents rounded too)."""
    def rounded(a):
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def mlp(dense, net, h, last_relu):
        layers = sum(name.startswith(net + ".") for name in dense) // 2
        for layer in range(layers):
            h = jnp.matmul(rounded(dense["%s.%d.w" % (net, layer)]),
                           rounded(h), precision=lax.Precision.DEFAULT) \
                + dense["%s.%d.b" % (net, layer)][:, None]
            if last_relu or layer + 1 < layers:
                h = jax.nn.relu(h)
        return h

    patch(dlrm, "_mlp", mlp)


def _a_table_dropped(patch, table):
    """The model without one table: its vectors read as 0."""
    forward = dlrm.dlrm_forward
    patch(dlrm, "dlrm_forward", lambda dense, emb, x: forward(
        dense, emb.at[table].set(0.0), x))


#: faults planted in the step, and the limit of the configuration's
#: ``check`` that has to refuse each. A scratch run of the benchmark on the
#: chip plants the same ones at full size (PERF.md, PR 42): the loss sees
#: a table of 3 rows dropped (its vectors start at +-0.58) and not one of
#: 1,460 (+-0.026: the margin hardly moves), which the update sees
PLANTED = {
    "bfloat16_products": (_one_bfloat16_pass, "update_rel"),
    "first_table_dropped": (partial(_a_table_dropped, table=0), "update_rel"),
    "smallest_table_dropped": (partial(_a_table_dropped, table=8), "loss_rel"),
}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_f_the_committed_limits_refuse_a_planted_fault(
        harness, cfg, whole, monkeypatch, fault):
    """The check's control, kept: the step with a fault planted in it
    goes through ``check.run`` against the float64 reference and the
    limits of ``criteo-dlrm.json`` as committed, and is refused by the
    limit that is there for it."""
    plant, limit = PLANTED[fault]
    plant(monkeypatch.setattr)
    model = harness.config.learner(cfg, None)
    harness.config.init_params(cfg, 5, model, None)
    feed = _feed(cfg, whole["path"])
    cell = types.SimpleNamespace(cfg=cfg, config=harness.config)
    facts = harness.check.run(cell, model, feed, whole, STEPS)
    feed.close()
    value, bound = facts["compared"][limit]
    assert not facts["ok"] and value > bound, facts["compared"]
    assert facts["untouched_changed"] == 0


def test_g_an_older_cell_imports_no_dlrm():
    """``dmlc_tpu.models`` does not import the module: a run of an older
    cell imports nothing it did not."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import dmlc_tpu.models; "
         "print('dmlc_tpu.models.dlrm' in sys.modules)"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "False", out.stderr[-2000:]


class TestLoweredStep:
    """(h): the step's structure, from its jaxpr and its lowered text."""

    @pytest.fixture(scope="class")
    def lowered(self, cfg):
        model = _learner(cfg)
        model.init_tables(0)
        n = ROWS * (DENSE + len(cfg["field_sizes"]))
        batch = {
            "label": jnp.zeros(ROWS), "weight": jnp.ones(ROWS),
            "indices": jnp.zeros(n + 256, jnp.int32),
            "values": jnp.ones(n + 256),
            "offsets": jnp.zeros(ROWS + 1, jnp.int32)}
        step = make_dlrm_train_step(
            cfg["num_features"], DENSE, cfg["field_sizes"])
        step = getattr(step, "__wrapped__", step)
        return (step.lower(model.params, batch).as_text(debug_info=True),
                jax.make_jaxpr(step)(model.params, batch), model)

    def test_h_the_backward_pass_lies_under_step_dense(self, lowered):
        """An operation ``jax.vjp`` makes carries ``transpose(jvp(.))`` in
        its path: ``step.dense`` comes BEFORE it, so a reader that takes
        the first part that starts with ``step.`` gives it to the phase
        (``benchmarks/metrics/step_update_ms.py`` ``phases``)."""
        text = lowered[0]
        for scope in ("step.order", "step.gather", "step.dense",
                      "step.update"):
            assert scope in text, scope
        paths = [line.split('"')[1] for line in text.splitlines()
                 if "transpose(jvp(" in line and line.count('"') >= 2]
        assert paths
        for path in paths:
            parts = path.split("/")
            first = next(p for p in parts if p.startswith("step.")
                         or p.startswith("transpose("))
            assert first == "step.dense", path

    def test_h_the_sorts_leave_the_dense_entries_out(self, lowered, cfg):
        """Three sorts, all over the ``rows x tables`` table entries (the
        13 dense entries a row take no part); the table is read once and
        written once, as lane rows; a matrix product a layer there and
        two back, but for the first layer's input, which needs none."""
        _, jaxpr, model = lowered

        def walk(j):
            for eqn in j.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

        eqns = list(walk(jaxpr.jaxpr))
        places = ROWS * len(cfg["field_sizes"])
        sorts = [e.invars[0].aval.shape for e in eqns
                 if e.primitive.name == "sort"]
        assert sorts == [(places,)] * 3
        height = model.params.rows.shape
        passes = [e.primitive.name for e in eqns
                  if e.primitive.name in ("gather", "scatter", "scatter-add")
                  and e.invars[0].aval.shape == height]
        assert passes == ["gather", "scatter"]
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        layers = len(cfg["mlp_bot"]) + len(cfg["mlp_top"]) - 2
        assert len(dots) == 3 * layers - 1
        assert all(str(e.params["precision"][0]) == "HIGHEST" for e in dots
                   if e.params["precision"] is not None)
        assert all(e.params["precision"] is not None for e in dots)


def test_i_a_mesh_is_refused_by_name(cfg):
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    with pytest.raises(DMLCError, match="ONE device"):
        DLRMLearner(mesh=mesh, **{k: cfg[k] for k in HYPER})


@pytest.mark.parametrize("over,match", [
    ({"field_sizes": ()}, "field_sizes"),
    ({"num_features": 99}, "num_features is 99"),
    ({"mlp_bot": [13, 32, 7]}, "mlp_bot runs"),
    ({"mlp_top": [100, 8, 1]}, "mlp_top runs"),
])
def test_i_hyperparameters_that_do_not_fit_are_refused(cfg, over, match):
    with pytest.raises(DMLCError, match=match):
        _learner(cfg, **over)


def test_i_the_published_net_is_counted(cfg):
    """The published widths hold 475,985 dense values; ``field_sizes`` as
    the text ``str`` of the tuple gives (``FFMParam``'s own parser)."""
    fields = len(cfg["field_sizes"])
    model = _learner(
        cfg, num_factors=16, mlp_bot=[13, 512, 256, 64, 16],
        mlp_top=[16 + fields * (fields + 1) // 2, 512, 256, 1],
        field_sizes=str(tuple(cfg["field_sizes"])))
    assert model.param.field_sizes == tuple(cfg["field_sizes"])
    assert model.dense_params == 475985


def test_j_span_arguments_counters_and_gauge(cfg, data):
    """``fit_uri`` end to end: the ``epoch`` span's arguments, the dense
    counter beside the ones it inherits, the gauge."""
    spans = []
    obs_trace.add_listener(spans.append)

    def counters():
        flat = obs.registry().flat_values()
        return {k.split("{")[0]: v for k, v in flat.items()
                if 'model="dlrm"' in k}

    before = counters()
    try:
        model = _learner(cfg)
        losses = model.fit_uri(data["path"], batch_size=ROWS, epochs=2)
    finally:
        obs_trace.remove_listener(spans.append)
    assert len(losses) == 2 and losses[1] < losses[0] < 1.0
    after = counters()

    def grew(name):
        return after[name] - before.get(name, 0.0)

    steps = 2 * STEPS
    assert grew("dmlc_fit_dense_net_steps_total") == steps
    assert grew("dmlc_fit_packed_row_steps_total") == steps
    assert grew("dmlc_fit_lane_row_steps_total") == steps
    assert grew("dmlc_fit_sparse_update_steps_total") == steps
    assert grew("dmlc_fit_dma_row_write_steps_total") == 0  # the CPU scatters
    assert after["dmlc_fit_dense_param_bytes"] == 4 * model.dense_params
    epochs = [e for e in spans if e["name"] == "epoch" and e.get("ph") == "X"]
    args = epochs[-1]["args"]
    assert args["model"] == "dlrm" and args["optimizer"] == "sgd"
    assert args["dense_params"] == model.dense_params
    assert args["dense_features"] == DENSE
    assert args["fields"] == len(cfg["field_sizes"])
    assert (args["row_columns"], args["row_lanes"],
            args["ids_per_lane_row"]) == (K, 128, 128 // K)
    assert args["row_writer"] == "scatter"


def test_j_predict_is_the_forward_pass(trained, cfg, data):
    arrays, (x, ids, named, _) = _plain_batch(cfg, data, 0)
    tree = trained.got[-1][1]
    p = {k: v for k, v in tree.items()}
    z, _ = _relu_net(p, "bot", np.asarray(x, np.float64))
    e = p["emb"][ids] * named[:, :, None]
    t = np.concatenate([z[:, None], e], axis=1)
    under = np.stack([(t[:, i] * t[:, j]).sum(1)
                      for i in range(t.shape[1]) for j in range(i)], axis=1)
    s, _ = _relu_net(p, "top", np.concatenate([z, under], axis=1))
    np.testing.assert_allclose(
        trained.model.predict_batch(arrays), s[:, 0], rtol=2e-4, atol=2e-5)


class TestReaders:
    """(k): the three metrics' readers over what a run hands them."""

    def _reader(self, harness, name):
        return harness.spec.load_module(
            os.path.join(BENCH, "metrics", name + ".py"))

    def test_k_the_share_reads_the_two_counters(self, harness):
        reader = self._reader(harness, "dense_net_step_share")
        run = {"counters": {
            'dmlc_fit_dense_net_steps_total{model="dlrm"}': 30.0,
            'dmlc_fit_steps_total{model="dlrm"}': 40.0}}
        assert reader.read(run) == 0.75
        assert reader.read({"counters": {
            'dmlc_fit_steps_total{model="fm"}': 40.0}}) is None

    @pytest.mark.parametrize(
        "name", ["step_dense_ms", "step_dense_roofline"])
    def test_k_no_trace_no_value(self, harness, name):
        """A run that was not traced, a program with no ``step.dense``
        scope, a configuration with no ``dense_needs``: nothing, and no
        error (what a parent commit gives the new readers)."""
        reader = self._reader(harness, name)
        run = {"trace": None, "cell": "kdd12-fm.libsvm", "spans": [],
               "config": types.SimpleNamespace(), "cfg": {}, "peaks": None,
               "batch_rows": 8192, "chips": 1}
        assert reader.read(run) is None

    def test_k_the_needs_are_the_products(self, harness, cfg):
        full = dict(cfg, mlp_bot=[13, 512, 256, 64, 16],
                    mlp_top=[367, 512, 256, 1], num_factors=16)
        assert harness.config.dense_needs(full, 8192)["flops"] == \
            2 * 3 * 474368 * 8192
        needs = harness.config.step_needs(full, 8192)
        assert needs["flops"] > harness.config.dense_needs(full, 8192)["flops"]
        assert needs["bytes"] > 8192 * 26 * 16 * 4 * 2


def test_l_the_new_cell_rehearses():
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data with its ``%.4f`` values, init, the check against the float64
    reference with the dense parameters under their names, window, result
    line) at the rehearse size, the MLPs at their published widths."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "criteo-dlrm.libsvm", "--rehearse", "--seconds", "1", "--seed",
         "2147483659"],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True and "metrics" not in result
    assert {"rows_per_s", "setup_s"} <= set(result["metric_names"])
    detail = json.loads(lines[-2].split("[bench] detail ", 1)[1])
    of = detail["check"]["update_rel_of"]
    assert "emb" in of and "bot.0.w" in of and "top.2.b" in of
    assert detail["check"]["untouched_changed"] == 0
    assert detail["check"]["loss_rel"] < 2e-6

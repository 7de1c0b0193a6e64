"""Linear/FM learners: convergence, mesh-vs-single-device parity, graft entry."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.models import (
    LinearLearner,
    init_fm_params,
    init_linear_params,
    make_fm_train_step,
    make_linear_train_step,
)
from dmlc_tpu.parallel import data_parallel_mesh


def _dense_batch(rng, batch, nfeat, w_true):
    x = rng.rand(batch, nfeat).astype(np.float32)
    margin = x @ w_true
    y = (margin > np.median(margin)).astype(np.float32)
    return {
        "x": jnp.asarray(x),
        "label": jnp.asarray(y),
        "weight": jnp.ones(batch, dtype=jnp.float32),
    }


class TestLinearSingleDevice:
    def test_logistic_converges(self):
        rng = np.random.RandomState(0)
        nfeat = 16
        w_true = rng.randn(nfeat).astype(np.float32)
        step = make_linear_train_step(None, learning_rate=1.0, momentum=0.9)
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        losses = []
        batch = _dense_batch(rng, 256, nfeat, w_true)
        for _ in range(100):
            params, velocity, m = step(params, velocity, batch)
            losses.append(float(m["loss_sum"]) / float(m["weight_sum"]))
        assert losses[-1] < losses[0] * 0.5, losses[-1]

    @pytest.mark.parametrize("objective", ["squared", "hinge"])
    def test_objectives_decrease(self, objective):
        rng = np.random.RandomState(1)
        nfeat = 8
        w_true = rng.randn(nfeat).astype(np.float32)
        step = make_linear_train_step(
            None, objective=objective, learning_rate=0.1
        )
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        batch = _dense_batch(rng, 128, nfeat, w_true)
        first = last = None
        for i in range(40):
            params, velocity, m = step(params, velocity, batch)
            loss = float(m["loss_sum"]) / float(m["weight_sum"])
            first = loss if first is None else first
            last = loss
        assert last < first


class TestLinearMeshParity:
    def test_dense_mesh_matches_single(self):
        rng = np.random.RandomState(2)
        nfeat = 12
        w_true = rng.randn(nfeat).astype(np.float32)
        batch = _dense_batch(rng, 64, nfeat, w_true)
        mesh = data_parallel_mesh()

        single = make_linear_train_step(None, learning_rate=0.3)
        sharded = make_linear_train_step(mesh, learning_rate=0.3)

        p1 = init_linear_params(nfeat)
        v1 = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        p2 = init_linear_params(nfeat)
        v2 = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        from jax.sharding import NamedSharding, PartitionSpec as P

        b2 = {
            "x": jax.device_put(batch["x"], NamedSharding(mesh, P("dp"))),
            "label": jax.device_put(batch["label"], NamedSharding(mesh, P("dp"))),
            "weight": jax.device_put(batch["weight"], NamedSharding(mesh, P("dp"))),
        }
        for _ in range(5):
            p1, v1, m1 = single(p1, v1, batch)
            p2, v2, m2 = sharded(p2, v2, b2)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            float(m1["loss_sum"]), float(m2["loss_sum"]), rtol=1e-5
        )

    def test_csr_mesh_matches_single(self):
        from dmlc_tpu.data.row_block import RowBlockContainer
        from dmlc_tpu.device.csr import pad_to_bucket, pad_to_bucket_sharded

        rng = np.random.RandomState(3)
        nfeat = 40
        cont = RowBlockContainer()
        for i in range(32):
            feats = sorted(rng.choice(nfeat, size=5, replace=False))
            cont.push_row(
                float(rng.randint(0, 2)), feats, value=rng.rand(5).astype(np.float32)
            )
        block = cont.to_block()
        dev = pad_to_bucket(block, 32, nnz_bucket=256)
        batch = {
            "label": jnp.asarray(dev.labels),
            "weight": jnp.asarray(dev.weights),
            "indices": jnp.asarray(dev.indices),
            "values": jnp.asarray(dev.values),
            "offsets": jnp.asarray(dev.offsets),
        }
        mesh = data_parallel_mesh()
        nshards = mesh.shape["dp"]
        single = make_linear_train_step(
            None, layout="csr", num_features=nfeat, learning_rate=0.2
        )
        sharded = make_linear_train_step(
            mesh, layout="csr", num_features=nfeat, learning_rate=0.2
        )
        p1 = init_linear_params(nfeat)
        v1 = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        p2 = jax.tree.map(jnp.copy, p1)
        v2 = jax.tree.map(jnp.copy, v1)
        from jax.sharding import NamedSharding, PartitionSpec as P

        # mesh step consumes SHARDED entries: per-shard sections, local ids
        sh = pad_to_bucket_sharded(block, 32, nshards)
        b2 = {
            "label": jax.device_put(
                jnp.asarray(sh.labels), NamedSharding(mesh, P("dp"))
            ),
            "weight": jax.device_put(
                jnp.asarray(sh.weights), NamedSharding(mesh, P("dp"))
            ),
            "indices": jax.device_put(
                jnp.asarray(sh.indices), NamedSharding(mesh, P("dp"))
            ),
            "values": jax.device_put(
                jnp.asarray(sh.values), NamedSharding(mesh, P("dp"))
            ),
            "offsets": jax.device_put(
                jnp.asarray(sh.offsets), NamedSharding(mesh, P("dp"))
            ),
        }
        # per-device H2D ∝ global_nnz / world: each device holds one
        # bucket of entries, not the global nnz
        assert b2["values"].addressable_shards[0].data.shape[0] == sh.nnz_bucket
        for _ in range(3):
            p1, v1, _ = single(p1, v1, batch)
            p2, v2, _ = sharded(p2, v2, b2)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5, atol=1e-6
        )


class TestExpandRowIds:
    def test_matches_host_row_ids_and_clamps_padding(self):
        """Device-side offsets→row_ids expansion == the host row_ids on
        valid entries; padded entries clamp to the last row (out-of-range
        ids under jnp.take's fill mode would inject NaN)."""
        from dmlc_tpu.data.row_block import RowBlockContainer
        from dmlc_tpu.device.csr import pad_to_bucket, pad_to_bucket_sharded
        from dmlc_tpu.ops.spmv import expand_row_ids

        rng = np.random.RandomState(11)
        cont = RowBlockContainer()
        n = 48
        for i in range(n):
            k = rng.randint(0, 5)  # ragged, including EMPTY rows
            feats = sorted(rng.choice(32, size=k, replace=False)) if k else []
            cont.push_row(float(i % 2), feats,
                          value=np.ones(k, dtype=np.float32))
        block = cont.to_block()

        # short batch: valid rows < batch_size exercises offset tail fill
        dev = pad_to_bucket(block, 64, nnz_bucket=256)
        rid = np.asarray(expand_row_ids(jnp.asarray(dev.offsets), 256))
        nnz = dev.num_nonzero
        np.testing.assert_array_equal(rid[:nnz], dev.row_ids[:nnz])
        assert rid.max() <= 63  # clamped in range

        sh = pad_to_bucket_sharded(block, 64, 4)
        rows_local = 64 // 4
        for s in range(4):
            off = sh.offsets[s * (rows_local + 1):(s + 1) * (rows_local + 1)]
            sec = slice(s * sh.nnz_bucket, (s + 1) * sh.nnz_bucket)
            rid = np.asarray(
                expand_row_ids(jnp.asarray(off), sh.nnz_bucket)
            )
            valid = int(off[-1])
            np.testing.assert_array_equal(
                rid[:valid], sh.row_ids[sec][:valid]
            )
            assert rid.max() <= rows_local - 1


class TestFM:
    def test_fm_converges_and_mesh_parity(self):
        from dmlc_tpu.data.row_block import RowBlockContainer
        from dmlc_tpu.device.csr import pad_to_bucket

        rng = np.random.RandomState(4)
        nfeat = 24
        cont = RowBlockContainer()
        for i in range(64):
            feats = sorted(rng.choice(nfeat, size=4, replace=False))
            label = float((feats[0] % 2) == 0)
            cont.push_row(label, feats, value=np.ones(4, dtype=np.float32))
        dev = pad_to_bucket(cont.to_block(), 64, nnz_bucket=512)
        batch = {
            "label": jnp.asarray(dev.labels),
            "weight": jnp.asarray(dev.weights),
            "indices": jnp.asarray(dev.indices),
            "values": jnp.asarray(dev.values),
            "offsets": jnp.asarray(dev.offsets),
        }
        single = make_fm_train_step(None, nfeat, learning_rate=0.2)
        p1 = init_fm_params(nfeat, 4)
        losses = []
        for _ in range(30):
            p1, m = single(p1, batch)
            losses.append(float(m["loss_sum"]) / float(m["weight_sum"]))
        assert losses[-1] < losses[0]

        mesh = data_parallel_mesh()
        sharded = make_fm_train_step(mesh, nfeat, learning_rate=0.2)
        from dmlc_tpu.device.csr import pad_to_bucket_sharded
        from jax.sharding import NamedSharding, PartitionSpec as P

        p2 = init_fm_params(nfeat, 4)
        sh = pad_to_bucket_sharded(cont.to_block(), 64, mesh.shape["dp"])
        b2 = {
            k: jax.device_put(jnp.asarray(v), NamedSharding(mesh, P("dp")))
            for k, v in (
                ("label", sh.labels), ("weight", sh.weights),
                ("indices", sh.indices), ("values", sh.values),
                ("offsets", sh.offsets),
            )
        }
        p1b = init_fm_params(nfeat, 4)
        for _ in range(3):
            p1b, _ = single(p1b, batch)
            p2, _ = sharded(p2, b2)
        np.testing.assert_allclose(
            np.asarray(p1b["v"]), np.asarray(p2["v"]), rtol=1e-4, atol=1e-6
        )


def _fm_step_f64(params, dev, lr, l2):
    """One FM SGD step in float64 numpy, written from the equations
    (models/fm.py header) over a padded DeviceCSRBatch; imports nothing
    from the model. Returns (params, loss_sum, weight_sum)."""
    w = np.asarray(params["w"], np.float64).copy()
    v = np.asarray(params["v"], np.float64).copy()
    b = float(params["b"])
    gw, gv, gb = np.zeros_like(w), np.zeros_like(v), 0.0
    loss_sum = 0.0
    wsum = float(np.sum(dev.weights, dtype=np.float64))
    for r in range(len(dev.labels)):
        lo, hi = int(dev.offsets[r]), int(dev.offsets[r + 1])
        idx = dev.indices[lo:hi]
        x = dev.values[lo:hi].astype(np.float64)
        xv = x[:, None] * v[idx]
        s = xv.sum(axis=0)
        score = b + x @ w[idx] + 0.5 * float(np.sum(s * s) - np.sum(xv * xv))
        y, wt = float(dev.labels[r]), float(dev.weights[r])
        loss_sum += wt * np.logaddexp(0.0, score) - wt * y * score
        g = wt * (1.0 / (1.0 + np.exp(-score)) - y)
        gb += g
        np.add.at(gw, idx, g * x)
        np.add.at(gv, idx, (g * x)[:, None] * (s[None, :] - xv))
    denom = max(wsum, 1e-12)
    return (
        {"w": w - lr * (gw / denom + l2 * w),
         "b": b - lr * gb / denom,
         "v": v - lr * (gv / denom + l2 * v)},
        loss_sum, wsum,
    )


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr that holds no jaxpr of its own, through
    pjit / shard_map / custom-call bodies."""
    for eqn in jaxpr.eqns:
        subs = [
            getattr(sub, "jaxpr", sub)
            for val in eqn.params.values()
            for sub in (val if isinstance(val, (list, tuple)) else (val,))
            if hasattr(getattr(sub, "jaxpr", sub), "eqns")
        ]
        # a scatter's update_jaxpr is its combiner, not a body to descend
        if subs and not eqn.primitive.name.startswith("scatter"):
            for sub in subs:
                yield from _walk_eqns(sub)
        else:
            yield eqn


class TestFMSparseUpdate:
    """The single-device step scatter-adds into the rows the batch names
    and builds no gradient of the table's shape."""

    NFEAT, NFACT, ROWS = 37, 4, 16

    def _block(self, nrows):
        """Ids repeated within a row and across rows; features 0 and
        23.. never named."""
        from dmlc_tpu.data.row_block import RowBlockContainer

        rng = np.random.RandomState(7)
        cont = RowBlockContainer()
        for i in range(nrows):
            feats = rng.randint(1, 12, size=5)
            feats[1] = feats[0]  # the same id twice in one row
            feats[4] = 20 + (i % 3)  # and one shared by a third of the rows
            cont.push_row(
                float(i % 2), feats,
                value=rng.rand(5).astype(np.float32) + 0.5,
                weight=float(1 + i % 2))
        return cont.to_block()

    def _batch(self, padded):
        """``padded``: 13 of 16 rows and a bucket wider than the entries,
        so padded entries sit at feature 0 with value 0 and padded rows
        weigh 0; else the bucket is filled exactly."""
        from dmlc_tpu.device.csr import pad_to_bucket

        if padded:
            return pad_to_bucket(self._block(13), self.ROWS, nnz_bucket=128)
        return pad_to_bucket(
            self._block(self.ROWS), self.ROWS, nnz_bucket=self.ROWS * 5)

    @staticmethod
    def _device_batch(dev):
        return {
            "label": jnp.asarray(dev.labels),
            "weight": jnp.asarray(dev.weights),
            "indices": jnp.asarray(dev.indices),
            "values": jnp.asarray(dev.values),
            "offsets": jnp.asarray(dev.offsets),
        }

    def _params(self):
        p = init_fm_params(self.NFEAT, self.NFACT, init_scale=0.3, seed=3)
        rng = np.random.RandomState(9)
        p["w"] = jnp.asarray(rng.randn(self.NFEAT).astype(np.float32) * 0.2)
        p["b"] = jnp.asarray(0.1, dtype=jnp.float32)
        return p

    @pytest.mark.parametrize("padded", [False, True], ids=["filled", "padded"])
    @pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
    @pytest.mark.parametrize("l2", [0.0, 0.01])
    def test_step_matches_float64_and_leaves_other_rows(
            self, l2, donate, padded):
        dev = self._batch(padded)
        params = self._params()
        before = {k: np.asarray(a).copy() for k, a in params.items()}
        want, want_loss, want_wsum = _fm_step_f64(before, dev, 0.2, l2)

        step = make_fm_train_step(
            None, self.NFEAT, learning_rate=0.2, l2=l2, donate_batch=donate)
        got, metrics = step(params, self._device_batch(dev))
        if not donate:  # the caller's tree is untouched
            for k, a in before.items():
                np.testing.assert_array_equal(np.asarray(params[k]), a)

        np.testing.assert_allclose(
            float(metrics["loss_sum"]), want_loss, rtol=2e-6)
        assert float(metrics["weight_sum"]) == want_wsum
        for k in ("w", "b", "v"):
            np.testing.assert_allclose(
                np.asarray(got[k]), want[k], rtol=1e-5, atol=1e-7)

        touched = np.zeros(self.NFEAT, bool)
        touched[dev.indices[: dev.num_nonzero]] = True
        assert not touched[0] and 5 < touched.sum() < self.NFEAT - 5
        moved = np.abs(np.asarray(got["v"]) - before["v"]).max(axis=1) > 0
        assert moved[touched].all()
        if l2 == 0.0:
            # bit for bit, the padded entries' feature 0 included
            for k in ("w", "v"):
                np.testing.assert_array_equal(
                    np.asarray(got[k])[~touched].view(np.uint32),
                    before[k][~touched].view(np.uint32))

    @staticmethod
    def _shaped_block(shape, nfeat):
        """``ragged``: rows of 0, 1 and many entries, in turn; ``hot``: one
        id named four times by every row (2048 entries of 4096) beside
        ids that repeat less; ``sparse``: 9 of 64 rows and a bucket wider
        than their entries (the rest is padding)."""
        from dmlc_tpu.data.row_block import RowBlockContainer

        rng = np.random.RandomState(17)
        cont = RowBlockContainer()
        nrows = {"ragged": 64, "hot": 512, "sparse": 9}[shape]
        for i in range(nrows):
            if shape == "ragged":
                feats = rng.randint(1, nfeat - 8, size=(0, 1, 40)[i % 3])
            elif shape == "hot":
                feats = np.concatenate(
                    [np.full(4, 7), rng.randint(1, nfeat - 8, size=4)])
            else:
                feats = rng.randint(1, nfeat - 8, size=6)
            cont.push_row(
                float(i % 2), feats,
                value=rng.rand(len(feats)).astype(np.float32) + 0.5,
                weight=float(1 + i % 2))
        return cont.to_block()

    @pytest.mark.parametrize("l2", [0.0, 0.01])
    @pytest.mark.parametrize("nfact", [1, 16, 32])
    @pytest.mark.parametrize("shape", ["ragged", "hot", "sparse"])
    def test_merged_passes_match_float64(self, shape, nfact, l2):
        """The step sums over concatenated columns and updates in
        feature-id order (one sort): whatever the rows' lengths, however
        often an id is named and whatever the width of the concatenated
        passes, it is the float64 step written row by row."""
        from dmlc_tpu.device.csr import pad_to_bucket

        nfeat = 203
        block = self._shaped_block(shape, nfeat)
        rows = max(64, len(block.offset) - 1)  # sparse: 9 rows of 64
        dev = pad_to_bucket(block, rows, nnz_bucket=rows * 8 + 1024)
        assert (dev.num_nonzero < len(dev.indices)) and rows * 2 < len(
            dev.indices)
        params = init_fm_params(nfeat, nfact, init_scale=0.3, seed=5)
        rng = np.random.RandomState(19)
        params["w"] = jnp.asarray(rng.randn(nfeat).astype(np.float32) * 0.2)
        params["b"] = jnp.asarray(-0.2, dtype=jnp.float32)
        before = {k: np.asarray(a).copy() for k, a in params.items()}
        want, want_loss, want_wsum = _fm_step_f64(before, dev, 0.2, l2)

        step = make_fm_train_step(None, nfeat, learning_rate=0.2, l2=l2)
        got, metrics = step(params, self._device_batch(dev))
        np.testing.assert_allclose(
            float(metrics["loss_sum"]), want_loss, rtol=5e-6)
        assert float(metrics["weight_sum"]) == want_wsum
        for k in ("w", "b", "v"):
            np.testing.assert_allclose(
                np.asarray(got[k]), want[k], rtol=1e-5, atol=2e-7)
        named = np.zeros(nfeat, bool)
        named[dev.indices[: dev.num_nonzero]] = True
        assert not named[0] and not named[nfeat - 8:].any()
        if shape == "hot":
            counts = np.bincount(dev.indices[: dev.num_nonzero])
            assert counts[7] >= 2048
        if l2 == 0.0:  # bit for bit, the padded entries' feature 0 too
            for k in ("w", "v"):
                np.testing.assert_array_equal(
                    np.asarray(got[k])[~named].view(np.uint32),
                    before[k][~named].view(np.uint32))
        # (a row's only entry has no factor gradient, a saturated row no
        # gradient at all: the reference tells who had to move)
        must = np.abs(want["w"] - before["w"]) > 1e-5
        assert must.sum() > 20 and (l2 or not must[~named].any())
        assert (np.asarray(got["w"]) != before["w"])[must].all()

    def test_no_value_of_the_tables_shape_but_the_scatter_adds(self):
        """A dense gradient, a zero fill or a whole-table update would
        each be an equation whose result has the table's shape."""
        step = make_fm_train_step(None, self.NFEAT, learning_rate=0.2)
        jaxpr = jax.make_jaxpr(step)(
            self._params(), self._device_batch(self._batch(False))).jaxpr
        shapes = {(self.NFEAT, self.NFACT), (self.NFEAT,)}
        makers = [
            eqn.primitive.name for eqn in _walk_eqns(jaxpr)
            for out in eqn.outvars if tuple(out.aval.shape) in shapes
        ]
        assert sorted(makers) == ["scatter-add", "scatter-add"], makers

    def test_mesh_step_keeps_the_dense_gradient_and_one_psum(self):
        from dmlc_tpu.device.csr import pad_to_bucket_sharded

        mesh = data_parallel_mesh()
        step = make_fm_train_step(mesh, self.NFEAT, learning_rate=0.2)
        sh = pad_to_bucket_sharded(
            self._block(self.ROWS), self.ROWS, mesh.shape["dp"])
        jaxpr = jax.make_jaxpr(step)(
            self._params(), self._device_batch(sh)).jaxpr
        eqns = list(_walk_eqns(jaxpr))
        names = [eqn.primitive.name for eqn in eqns]
        assert sum(n.startswith("psum") for n in names) == 1, names
        dense = [
            eqn.primitive.name for eqn in eqns for out in eqn.outvars
            if tuple(out.aval.shape) == (self.NFEAT, self.NFACT)
        ]
        assert "scatter-add" in dense and "sub" in dense, dense

    @pytest.mark.parametrize("on_mesh", [False, True], ids=["single", "mesh"])
    def test_counter_is_the_share_of_steps_on_the_sparse_path(
            self, tmp_path, on_mesh):
        from dmlc_tpu import obs
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed
        from dmlc_tpu.models import FMLearner

        rng = np.random.RandomState(13)
        path = tmp_path / "train.svm"
        with open(path, "w") as fh:
            for i in range(192):
                ids = np.sort(rng.choice(self.NFEAT, size=4, replace=False))
                fh.write("%d %s\n" % (i % 2, " ".join(
                    "%d:%.4f" % (j, rng.rand()) for j in ids)))
        mesh = data_parallel_mesh() if on_mesh else None
        feed = DeviceFeed(
            create_parser(str(path)),
            BatchSpec(batch_size=64, layout="csr", num_features=self.NFEAT),
            mesh=mesh,
        )

        def read():
            flat = obs.registry().flat_values()
            return [flat.get('dmlc_fit_%s_total{model="fm"}' % k, 0.0)
                    for k in ("steps", "sparse_update_steps")]

        steps0, sparse0 = read()
        learner = FMLearner(
            mesh=mesh, num_features=self.NFEAT, num_factors=self.NFACT)
        learner.fit_feed(feed, epochs=2)
        feed.close()
        steps, sparse = read()
        assert steps - steps0 == 6  # 192 rows in batches of 64, two passes
        assert sparse - sparse0 == (0 if on_mesh else 6)
        assert 'dmlc_fit_sparse_update_steps_total{model="fm"}' in (
            obs.registry().flat_values())

    @pytest.mark.parametrize(
        "placement", ["single", "factors", "replicated"])
    def test_touched_rows_over_entries_and_one_read_a_pass(
            self, tmp_path, monkeypatch, placement):
        """``dmlc_fit_touched_rows_total`` is the distinct ids of each
        batch as the chip that sorts them sees them (padded entries name
        feature 0), ``dmlc_fit_entries_total`` the batches' shapes, and
        the count rides the pass's one read of the device (the read-back's
        ``loss_fetch``; a ``jax.device_get`` beside it would show)."""
        from jax.sharding import Mesh

        from dmlc_tpu import obs
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed
        from dmlc_tpu.models import FMLearner

        rng = np.random.RandomState(37)
        path = tmp_path / "train.svm"
        with open(path, "w") as fh:
            for i in range(200):  # three batches of 64 and one of 8
                ids = rng.randint(1, 12, size=5)
                fh.write("%d %s\n" % (i % 2, " ".join(
                    "%d:%.4f" % (j, rng.rand()) for j in ids)))
        mesh = None if placement == "single" else Mesh(
            np.asarray(jax.devices()[:4]), ("dp",))
        hyper = {} if mesh is None else {"table_sharding": placement}

        def feed():
            return DeviceFeed(
                create_parser(str(path)),
                BatchSpec(batch_size=64, layout="csr",
                          num_features=self.NFEAT), mesh=mesh)

        seen = feed()
        batches = [np.asarray(b["indices"]) for b in seen]
        seen.close()
        assert len(batches) == 4 and (batches[-1] == 0).sum() > 100
        # a mesh of replicas sorts a chip's section, every other step the
        # whole batch
        parts = 4 if placement == "replicated" else 1
        want_touched = sum(len(np.unique(part)) for b in batches
                           for part in np.split(b, parts))
        want_entries = sum(b.size for b in batches)

        def read():
            flat = obs.registry().flat_values()
            return [flat.get('dmlc_fit_%s_total{model="fm"}' % k, 0.0)
                    for k in ("touched_rows", "entries", "steps")]

        reads = []
        real_get = jax.device_get
        monkeypatch.setattr(
            jax, "device_get", lambda tree: reads.append(1) or real_get(tree))
        before = read()
        learner = FMLearner(mesh=mesh, num_features=self.NFEAT,
                            num_factors=self.NFACT, **hyper)
        train = feed()
        spans = []
        obs.trace.add_listener(spans.append)
        try:
            learner.fit_feed(train, epochs=1)
        finally:
            obs.trace.remove_listener(spans.append)
        train.close()
        touched, entries, steps = [a - b for a, b in zip(read(), before)]
        assert (touched, entries, steps) == (want_touched, want_entries, 4)
        assert 0 < touched / entries < 0.2
        # weight_sum's and touched_rows' of the four steps, after
        # loss_sum's, which the wait for the device read
        assert [e["args"]["scalars"] for e in spans
                if e.get("ph") == "X" and e["name"] == "loss_fetch"] == [8]
        assert reads == []


def _plain_fm_step(params, batch, lr, l2, in_id_order):
    """The FM step with EVERY ENTRY's rows gathered from the parameters
    (``jnp.take(params[k], indices)``), in the arithmetic of the step
    before the distinct-row gather: the merged row sums, one stable sort
    of the ids, an id's entries summed, one add a touched row.
    ``in_id_order``: the entries are sorted by id first, as the step has
    them (what it must then reproduce bit for bit); else the forward and
    backward passes run in the feed's order, as they did before (the row
    sums then add in another order)."""
    from dmlc_tpu.models.linear import margin_grad
    from dmlc_tpu.ops.spmv import expand_row_ids

    indices, values = batch["indices"], batch["values"]
    label, weight = batch["label"], batch["weight"]
    n, k = indices.shape[0], params["v"].shape[1]
    row_ids = expand_row_ids(batch["offsets"], n)
    order = jnp.argsort(indices, stable=True)
    if in_id_order:
        indices, values, row_ids = indices[order], values[order], row_ids[order]
        order = jnp.arange(n)
    v_e = jnp.take(params["v"], indices, axis=0)
    w_e = jnp.take(params["w"], indices, axis=0)
    xv = values[:, None] * v_e
    sums = jax.ops.segment_sum(
        jnp.concatenate([xv, xv * xv, (values * w_e)[:, None]], axis=1),
        row_ids, num_segments=label.shape[0])
    s, q, linear = sums[:, :k], sums[:, k:2 * k], sums[:, 2 * k]
    margin = params["b"] + linear + 0.5 * jnp.sum(s * s - q, axis=-1)
    loss, gmargin = margin_grad("logistic", margin, label)
    wg = weight * gmargin
    back = jnp.take(jnp.concatenate([s, wg[:, None]], axis=1), row_ids, axis=0)
    dw = back[:, -1] * values
    dv = dw[:, None] * (back[:, :-1] - xv)
    denom = jnp.maximum(jnp.sum(weight), 1e-12)
    upd = (-lr / denom) * jnp.concatenate([dv, dw[:, None]], axis=1)[order]
    ids, slot = jnp.unique(
        indices[order], return_inverse=True, size=n,
        fill_value=params["w"].shape[0])
    per_id = jax.ops.segment_sum(upd, slot, num_segments=n)
    w, v = params["w"], params["v"]
    if l2:
        w, v = w * (1.0 - lr * l2), v * (1.0 - lr * l2)
    return {
        "w": w.at[ids].add(per_id[:, -1], mode="drop"),
        "b": params["b"] - lr * (jnp.sum(wg) / denom),
        "v": v.at[ids].add(per_id[:, :-1], mode="drop"),
    }, jnp.sum(weight * loss)


class TestFMDistinctRowGather:
    """The step sorts its entries by feature id, gathers ``v`` and ``w``
    at the batch's distinct ids only, ``_UPDATE_CHUNK`` slots a pass, and
    hands every entry its rows from that buffer: the same float32 values
    reach the same sums, so the result is the per-entry gather's to the
    last bit, and within float32 rounding of the step that ran its
    forward pass in the feed's order."""

    ROWS = 512

    def _case(self, case):
        """(ids [rows, per_row], how many of the rows are real, nfeat)"""
        from dmlc_tpu.models.fm import _UPDATE_CHUNK

        rng = np.random.RandomState(23)
        rows, chunk = self.ROWS, _UPDATE_CHUNK
        if case in ("thousands", "l2"):  # 4 ids, ~2800 entries each
            return rng.randint(1, 5, size=(2 * rows, 11)), 2 * rows, 50
        if case == "distinct":
            return 1 + rng.permutation(rows * 11).reshape(rows, 11), rows, 6000
        if case == "equal":  # one distinct id
            return np.full((rows, 11), 7), rows, 50
        if case == "padded":  # 300 real rows; feature 0 takes the padding
            return rng.randint(1, 900, size=(rows, 11)), 300, 1000
        extra = {"chunk": 0, "chunk+1": 1}[case]  # 2048 or 2049 distinct
        ids = 1 + np.arange(rows * 8) % (chunk + extra)
        return rng.permutation(ids).reshape(rows, 8), rows, 3000

    @pytest.mark.parametrize("nfact", [4, 16])
    @pytest.mark.parametrize("case", [
        "thousands", "distinct", "equal", "padded", "chunk", "chunk+1", "l2"])
    def test_equals_the_per_entry_gather(self, case, nfact):
        from dmlc_tpu.models.fm import _UPDATE_CHUNK

        ids, real, nfeat = self._case(case)
        rows, per_row = ids.shape
        rng = np.random.RandomState(29)
        indices = ids.astype(np.int32).ravel()
        values = rng.rand(indices.size).astype(np.float32) + 0.5
        indices[real * per_row:] = 0
        values[real * per_row:] = 0.0
        offsets = np.minimum(np.arange(rows + 1), real) * per_row
        batch = {
            "label": jnp.asarray((rng.rand(rows) < 0.4).astype(np.float32)),
            "weight": jnp.asarray(
                (np.arange(rows) < real) * (1.0 + np.arange(rows) % 2),
                jnp.float32),
            "indices": jnp.asarray(indices),
            "values": jnp.asarray(values),
            "offsets": jnp.asarray(offsets.astype(np.int32)),
        }
        params = init_fm_params(nfeat, nfact, init_scale=0.3, seed=5)
        params["w"] = jnp.asarray(rng.randn(nfeat).astype(np.float32) * 0.2)
        params["b"] = jnp.asarray(-0.2, dtype=jnp.float32)
        l2 = 0.01 if case == "l2" else 0.0

        plain = jax.jit(_plain_fm_step, static_argnums=(2, 3, 4))
        want, want_loss = plain(params, batch, 0.2, l2, True)
        near, near_loss = plain(params, batch, 0.2, l2, False)
        step = make_fm_train_step(None, nfeat, learning_rate=0.2, l2=l2)
        got, metrics = step(params, batch)

        distinct = len(np.unique(indices))
        assert int(metrics["touched_rows"]) == distinct
        assert distinct == {
            "thousands": 4, "l2": 4, "distinct": rows * 11, "equal": 1,
            "chunk": _UPDATE_CHUNK, "chunk+1": _UPDATE_CHUNK + 1,
        }.get(case, distinct)
        if case == "padded":
            assert 0 in indices and (np.bincount(indices)[0]
                                     == (rows - real) * per_row)
        assert float(metrics["loss_sum"]) == float(want_loss)
        for k in ("w", "b", "v"):
            np.testing.assert_array_equal(
                np.asarray(got[k]).view(np.uint32),
                np.asarray(want[k]).view(np.uint32), err_msg=k)
        # the feed's order adds a row's terms in another order
        np.testing.assert_allclose(
            float(metrics["loss_sum"]), float(near_loss), rtol=2e-6)
        for k in ("w", "b", "v"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(near[k]), rtol=1e-5, atol=1e-7,
                err_msg=k)
        moved = np.abs(np.asarray(got["v"]) - np.asarray(params["v"])).max(1)
        # (an update below a row's last bit moves nothing)
        assert (moved[np.unique(indices[:real * per_row])] > 0).mean() > 0.9


class TestLearnerEndToEnd:
    def test_fit_feed_and_checkpoint(self, tmp_path):
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed

        rng = np.random.RandomState(5)
        nfeat = 10
        w_true = rng.randn(nfeat)
        path = tmp_path / "train.svm"
        with open(path, "w") as fh:
            for _ in range(400):
                x = rng.rand(nfeat)
                y = int(x @ w_true > 0)
                fh.write(
                    f"{y} " + " ".join(f"{j}:{x[j]:.5f}" for j in range(nfeat)) + "\n"
                )
        feed = DeviceFeed(
            create_parser(str(path)),
            BatchSpec(batch_size=64, layout="dense", num_features=nfeat,
                      drop_remainder=True),
        )
        learner = LinearLearner(learning_rate=0.5)
        history = learner.fit_feed(feed, epochs=3)
        assert history[-1] < history[0]

        ckpt = tmp_path / "model.bin"
        learner.save(str(ckpt))
        other = LinearLearner()
        other.load(str(ckpt))
        x = rng.rand(8, nfeat).astype(np.float32)
        np.testing.assert_allclose(
            learner.predict(x), other.predict(x), rtol=1e-6
        )


class TestBatchIdentityAndEpochBoundary:
    """One batch followed through the program's spans by (pass_, batch),
    and the once-a-pass spans of the epoch boundary, over a two-pass fit."""

    @pytest.fixture
    def spans(self):
        from dmlc_tpu.obs import trace as obs_trace

        seen = []
        obs_trace.add_listener(seen.append)
        yield seen
        obs_trace.remove_listener(seen.append)

    @staticmethod
    def _feed(tmp_path, layout):
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed

        rng = np.random.RandomState(11)
        nfeat = 12
        path = tmp_path / "train.svm"
        with open(path, "w") as fh:
            for i in range(320):
                ids = np.sort(rng.choice(nfeat, size=4, replace=False))
                fh.write("%d %s\n" % (i % 2, " ".join(
                    "%d:%.4f" % (j, rng.rand()) for j in ids)))
        return DeviceFeed(
            create_parser(str(path)),
            BatchSpec(batch_size=64, layout=layout, num_features=nfeat),
        )

    @pytest.mark.parametrize("model", ["linear", "fm"])
    def test_spans_join_by_pass_and_batch(self, tmp_path, spans, model):
        from dmlc_tpu.models import FMLearner

        if model == "linear":
            feed = self._feed(tmp_path, "dense")
            learner = LinearLearner(learning_rate=0.1)
        else:
            feed = self._feed(tmp_path, "csr")
            learner = FMLearner(num_features=12, num_factors=4)
        history = learner.fit_feed(feed, epochs=2)
        feed.close()
        assert len(history) == 2

        def ident(e):
            return e["args"]["pass_"], e["args"]["batch"]

        by_name = {}
        for e in spans:
            if e.get("ph") == "X":
                by_name.setdefault(e["name"], []).append(e)
        dispatched = [ident(e) for e in by_name["dispatch"]]
        # 320 rows in batches of 64, two passes: ids unique in the run
        assert dispatched == [(p, b) for p in (0, 1) for b in range(5)]
        for name in ("consume", "train_step"):
            assert [ident(e) for e in by_name[name]] == dispatched, name
        # feed_batch also wraps the pull that finds the source exhausted
        assert set(dispatched) <= {ident(e) for e in by_name["feed_batch"]}
        # a train_step runs inside the consume span of its own batch
        for step, held in zip(by_name["train_step"], by_name["consume"]):
            assert held["ts"] <= step["ts"]
            assert step["ts"] + step["dur"] <= held["ts"] + held["dur"] + 1
            assert step["args"]["model"] == model
        # the epoch boundary: each span once a pass, in this order; the
        # fit restarts the feed once, between its two passes
        boundary = [e["name"] for e in spans if e["name"] in
                    ("loss_readback", "epoch_close", "feed_restart")]
        assert boundary == ["loss_readback", "epoch_close", "feed_restart",
                            "loss_readback", "epoch_close"]
        (restart,) = by_name["feed_restart"]
        assert restart["args"] == {"pass_": 1}
        # every span under the read-back's wait is over before it starts
        first_wait = by_name["loss_readback"][0]
        assert all(e["ts"] + e["dur"] <= first_wait["ts"] + 1
                   for e in by_name["consume"][:5])

    def test_python_rebatch_path_carries_ids_on_stage(
            self, tmp_path, spans, monkeypatch):
        from dmlc_tpu.device.csr import BlockBatcher

        monkeypatch.setenv("DMLC_TPU_NATIVE", "0")
        feed = self._feed(tmp_path, "dense")
        assert isinstance(feed._host.reader, BlockBatcher)
        for _ in feed:
            pass
        feed.before_first()
        for _ in feed:
            pass
        feed.close()
        staged = [(e["args"]["pass_"], e["args"]["batch"])
                  for e in spans if e["name"] == "stage"]
        # the producer stages on into the head of a third pass
        assert staged[:10] == [(p, b) for p in (0, 1) for b in range(5)]
        assert staged[10:] == [(2, b) for b in range(len(staged) - 10)]

    def test_tracing_off_sets_no_batch(self, tmp_path):
        from dmlc_tpu import obs

        feed = self._feed(tmp_path, "dense")
        held = [obs.current_batch() for _ in feed]
        feed.close()
        assert held == [{}] * 5


class TestGraftEntry:
    def test_entry_and_dryrun(self):
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        import __graft_entry__ as ge

        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (256,)
        ge.dryrun_multichip(8)


class TestFeatureShardedStep:
    """dp×mp step (make_feature_sharded_train_step) — the PS-analog layout:
    w sharded over mp, batch sharded over dp, psum(margin) over mp."""

    def test_matches_single_device(self):
        import jax.numpy as jnp
        from dmlc_tpu.models.linear import make_feature_sharded_train_step
        from dmlc_tpu.parallel import make_mesh

        rng = np.random.RandomState(5)
        nfeat, batch = 32, 64
        w_true = rng.randn(nfeat).astype(np.float32)
        b = _dense_batch(rng, batch, nfeat, w_true)

        mesh = make_mesh({"dp": 4, "mp": 2})
        step, sh = make_feature_sharded_train_step(mesh, learning_rate=0.3)
        single = make_linear_train_step(None, learning_rate=0.3)

        p1 = init_linear_params(nfeat)
        v1 = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        p2 = {
            "w": jax.device_put(jnp.zeros(nfeat), sh["w"]),
            "b": jax.device_put(jnp.zeros(()), sh["b"]),
        }
        xs = jax.device_put(b["x"], sh["x"])
        ys = jax.device_put(b["label"], sh["label"])
        ws = jax.device_put(b["weight"], sh["weight"])

        for _ in range(5):
            p1, v1, m1 = single(p1, v1, b)
            p2, m2 = step(p2, xs, ys, ws)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            float(m1["loss_sum"]), float(m2["loss_sum"]), rtol=1e-5
        )

    def test_w_stays_sharded(self):
        """Parameter state remains sharded over mp across steps (the whole
        point of the PS-analog: no device holds the full model)."""
        import jax.numpy as jnp
        from dmlc_tpu.models.linear import make_feature_sharded_train_step
        from dmlc_tpu.parallel import make_mesh

        rng = np.random.RandomState(6)
        mesh = make_mesh({"dp": 2, "mp": 4})
        step, sh = make_feature_sharded_train_step(mesh)
        nfeat, batch = 64, 32
        p = {
            "w": jax.device_put(jnp.zeros(nfeat), sh["w"]),
            "b": jax.device_put(jnp.zeros(()), sh["b"]),
        }
        xs = jax.device_put(
            rng.rand(batch, nfeat).astype(np.float32), sh["x"])
        ys = jax.device_put(
            (rng.rand(batch) > 0.5).astype(np.float32), sh["label"])
        ws = jax.device_put(np.ones(batch, np.float32), sh["weight"])
        p, _ = step(p, xs, ys, ws)
        assert p["w"].sharding.spec == sh["w"].spec


class TestShardedCSRFeed:
    """Entries partitioned per shard through the whole stack: native
    sharded COO fetch == pure-python pad_to_bucket_sharded, and a DeviceFeed
    + mesh train run matches the single-device run (VERDICT r2 item 3)."""

    def _svm_file(self, tmp_path, rows=512, nfeat=24):
        rng = np.random.RandomState(11)
        path = tmp_path / "s.svm"
        with open(path, "w") as fh:
            for i in range(rows):
                nf = 1 + (i * 7) % 6
                feats = sorted(rng.choice(nfeat, size=nf, replace=False))
                fh.write(
                    f"{i % 2} "
                    + " ".join(f"{j}:{rng.rand():.4f}" for j in feats)
                    + "\n"
                )
        return str(path)

    def test_native_sharded_fetch_matches_python(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.data.parsers import NativePipelineParser
        from dmlc_tpu.device.csr import pad_to_bucket_sharded

        if not native.available():
            pytest.skip("native library not built")
        path = self._svm_file(tmp_path)
        blocks = list(create_parser(path, 0, 1))

        parser = create_parser(path, 0, 1)
        assert isinstance(parser, NativePipelineParser)
        got = parser.read_batch_coo_sharded(512, 4)
        parser.close()

        from dmlc_tpu.data.row_block import RowBlockContainer

        cont = RowBlockContainer()
        for b in blocks:
            cont.push_block(b)
        want = pad_to_bucket_sharded(
            cont.to_block(), 512, 4, nnz_bucket=got.nnz_bucket
        )
        np.testing.assert_array_equal(got.labels, want.labels)
        np.testing.assert_array_equal(got.indices, want.indices)
        np.testing.assert_allclose(got.values, want.values, rtol=1e-6)
        np.testing.assert_array_equal(got.row_ids, want.row_ids)
        np.testing.assert_array_equal(got.offsets, want.offsets)
        assert got.num_nonzero == want.num_nonzero

    def test_feed_mesh_csr_end_to_end_matches_single(self, tmp_path):
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed

        path = self._svm_file(tmp_path)
        nfeat = 24
        mesh = data_parallel_mesh()

        def run(mesh_arg):
            feed = DeviceFeed(
                create_parser(path, 0, 1),
                BatchSpec(batch_size=128, layout="csr", num_features=nfeat),
                mesh=mesh_arg,
            )
            learner = LinearLearner(
                mesh=mesh_arg, learning_rate=0.3, num_features=nfeat
            )
            learner.fit_feed(feed, epochs=2)
            feed.close()
            return np.asarray(learner.params["w"])

        w_single = run(None)
        w_mesh = run(mesh)
        np.testing.assert_allclose(w_single, w_mesh, rtol=1e-4, atol=1e-6)


class TestFeedPrefetchWindow:
    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_prefetch_depths_yield_identical_batches(self, tmp_path, depth):
        """spec.prefetch only changes pipelining, never content/order."""
        from dmlc_tpu.data import create_parser
        from dmlc_tpu.device import BatchSpec, DeviceFeed

        path = tmp_path / "d.svm"
        rng = np.random.RandomState(5)
        with open(path, "w") as fh:
            for i in range(700):
                fh.write(f"{i % 2} 1:{rng.rand():.4f} 3:{rng.rand():.4f}\n")
        ref_spec = BatchSpec(batch_size=128, layout="dense", num_features=8)
        spec = BatchSpec(batch_size=128, layout="dense", num_features=8,
                         prefetch=depth)
        ref = DeviceFeed(create_parser(str(path), 0, 1, nthread=1), ref_spec)
        got = DeviceFeed(create_parser(str(path), 0, 1, nthread=1), spec)
        ref_batches = [np.asarray(b["x"]) for b in ref]
        got_batches = [np.asarray(b["x"]) for b in got]
        ref.close()
        got.close()
        assert len(ref_batches) == len(got_batches) == 6
        for a, b in zip(ref_batches, got_batches):
            np.testing.assert_array_equal(a, b)

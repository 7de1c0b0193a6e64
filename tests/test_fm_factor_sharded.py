"""An FM whose factor table is sharded by factors over a mesh
(``FMLearner(mesh, table_sharding="factors")``): on the suite's virtual
CPU devices, at the ``rehearse`` size of the ``kdd12-fm-k128``
configuration (F=100,001, K=16 over 4 devices, batches of 1024), against
that configuration's float64 numpy reference, which imports nothing of
``dmlc_tpu.models``.
"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models import FMLearner, make_fm_train_step
from dmlc_tpu.models.fm import init_fm_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = os.path.join(BENCH, "configs", "kdd12-fm-k128")
CHIPS, STEPS = 4, 4


@pytest.fixture(scope="module")
def harness():
    """The benchmark's own generator, check and reference."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, textgen

        yield types.SimpleNamespace(
            check=check, textgen=textgen,
            config=spec.load_module(CONFIG + ".py"))
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG + ".json") as f:
        out = json.load(f)
    out.update(out["rehearse"])
    out["rows"] = 8 * CHIPS * out["batch_rows_per_chip"]
    return out


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:CHIPS]), ("dp",))


@pytest.fixture(scope="module")
def data(harness, cfg, tmp_path_factory):
    """The configuration's rows, as arrays and as one LIBSVM file."""
    rows = harness.config.rows(cfg, 2147483659)
    path = str(tmp_path_factory.mktemp("k128") / "rows.libsvm")
    harness.textgen.write_libsvm(
        path, rows["label"], rows["ids"], rows["value_text"],
        rows["pool_index"])
    return dict(rows, path=path)


def _learner(cfg, mesh, **over):
    hyper = dict(
        objective=cfg["objective"], learning_rate=cfg["learning_rate"],
        l2=cfg["l2"], num_factors=cfg["num_factors"],
        num_features=cfg["num_features"], init_scale=cfg["init_scale"])
    if mesh is not None:
        hyper["table_sharding"] = cfg["table_sharding"]
    hyper.update(over)
    return FMLearner(mesh=mesh, **hyper)


def _feed(cfg, path, mesh):
    return DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=cfg["batch_rows_per_chip"] * CHIPS,
                  layout="csr", num_features=cfg["num_features"]),
        mesh=mesh)


def _host(params):
    return {k: np.asarray(v) for k, v in params.items()}


def _replicas(arr):
    return [np.asarray(s.data) for s in arr.addressable_shards]


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


@pytest.fixture(scope="module")
def trained(harness, cfg, mesh, data):
    """(a)'s run, kept for the cases that look at its state: the
    benchmark's own check drives ``STEPS`` batches through
    ``FMLearner.fit_feed`` over a mesh ``DeviceFeed`` and through the
    float64 reference."""
    model = _learner(cfg, mesh)
    harness.config.init_params(cfg, 7, model, mesh)
    start = _host(model.params)
    feed = _feed(cfg, data["path"], mesh)
    cell = types.SimpleNamespace(cfg=cfg, config=harness.config)
    facts = harness.check.run(cell, model, feed, data, STEPS)
    feed.close()
    return types.SimpleNamespace(model=model, start=start, facts=facts)


class TestAgainstTheReference:
    def test_a_losses_touched_rows_and_no_other_row(self, trained, cfg):
        facts = trained.facts
        assert facts["ok"], facts
        assert facts["loss_rel"] < 2e-6 and facts["update_rel"] < 5e-5, facts
        assert facts["untouched_changed"] == 0
        # a power law: thousands of rows touched, most of the table not
        assert 1000 < facts["touched_rows"] < cfg["num_features"] // 4
        v = trained.model.params["v"]
        assert v.sharding.spec == P(None, "dp")
        assert {s.data.shape for s in v.addressable_shards} == {
            (cfg["num_features"], cfg["num_factors"] // CHIPS)}

    def test_b_replicas_stay_bit_equal(self, trained):
        for k in ("w", "b"):
            first, *rest = _replicas(trained.model.params[k])
            assert len(rest) == CHIPS - 1
            for other in rest:
                np.testing.assert_array_equal(
                    first.view(np.uint32), other.view(np.uint32))
        # and the steps did move them
        assert np.abs(_host(trained.model.params)["w"]).max() > 0

    def test_b_the_chips_shares_add_up_to_the_interaction_term(
            self, cfg, mesh, data):
        """Each chip's m_c from its own columns; their sum is the
        unsharded model's interaction term."""
        f, k, rows = cfg["num_features"], cfg["num_factors"], 256
        ids = jnp.asarray(data["ids"][:rows].astype(np.int32))
        v = jax.random.normal(jax.random.PRNGKey(5), (f, k)) * 0.3

        def share(v_c):
            xv = jnp.take(v_c, ids, axis=0)  # [rows, 11, K/4], x = 1
            s = xv.sum(axis=1)
            return 0.5 * ((s * s).sum(-1) - (xv * xv).sum((1, 2)))[None]

        placed = jax.device_put(v, NamedSharding(mesh, P(None, "dp")))
        m_c = np.asarray(shard_map(
            share, mesh=mesh, in_specs=P(None, "dp"),
            out_specs=P("dp"))(placed), np.float64)
        assert m_c.shape == (CHIPS, rows)
        xv = np.asarray(v, np.float64)[np.asarray(ids)]
        s = xv.sum(axis=1)
        whole = 0.5 * ((s * s).sum(-1) - (xv * xv).sum((1, 2)))
        assert np.abs(whole).max() > 1e-2
        np.testing.assert_allclose(m_c.sum(axis=0), whole, rtol=0, atol=1e-5)
        # no chip's share is the whole (the columns do differ)
        assert np.abs(m_c[0] - whole).max() > 1e-3

    def test_c_single_device_step_gives_the_same_parameters(
            self, trained, cfg, data):
        single = _learner(cfg, None)
        single.params = {k: jnp.asarray(v) for k, v in trained.start.items()}
        feed = _feed(cfg, data["path"], None)
        batches = iter(feed)
        for _ in range(STEPS):
            single._ensure(cfg["num_features"])
            batch = next(batches)
            single.params, _ = single._step(
                single.params,
                {k: batch[k] for k in
                 ("label", "weight", "indices", "values", "offsets")})
        batches.close()
        feed.close()
        got, want = _host(trained.model.params), _host(single.params)
        for k in ("w", "b", "v"):
            # the psum adds the chips' shares in another order than one
            # chip sums 16 factors: float32 rounding of the margin only
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=2e-8)
        moved = np.abs(want["v"] - trained.start["v"]).max(axis=1) > 0
        assert 1000 < moved.sum()


class TestOneBatchOnEveryPlacement:
    """(c): one batch through the three steps ``make_fm_train_step``
    builds. All gather ``v`` and ``w`` at the distinct ids of what a chip
    sorts: the whole batch on one device and on a factor-sharded mesh (so
    the replicas of ``w`` stay bit-equal), its own section on a mesh of
    replicas."""

    F, K, ROWS, PER_ROW = 1003, 16, 256, 11

    def _batches(self, mesh):
        rng = np.random.RandomState(31)
        # a few hot ids beside a long tail, as a power law gives
        ids = np.where(rng.rand(self.ROWS, self.PER_ROW) < 0.5,
                       rng.randint(1, 9, size=(self.ROWS, self.PER_ROW)),
                       rng.randint(9, self.F, size=(self.ROWS, self.PER_ROW)))
        whole = {
            "label": jnp.asarray((rng.rand(self.ROWS) < 0.4), jnp.float32),
            "weight": jnp.ones(self.ROWS),
            "indices": jnp.asarray(ids.ravel(), jnp.int32),
            "values": jnp.asarray(rng.rand(ids.size) + 0.5, jnp.float32),
            "offsets": jnp.arange(self.ROWS + 1, dtype=jnp.int32)
            * self.PER_ROW}
        # the feed's sections: every chip its rows' entries, local offsets
        sections = dict(whole, offsets=jnp.tile(
            whole["offsets"][:self.ROWS // CHIPS + 1], CHIPS))
        return ids, whole, jax.device_put(
            sections, NamedSharding(mesh, P("dp")))

    @pytest.mark.parametrize("table_sharding", ["factors", "replicated"])
    def test_c_matches_the_single_device_step(self, mesh, table_sharding):
        from dmlc_tpu.models.fm import fm_partition_rules
        from dmlc_tpu.parallel.partition import shard_params

        ids, whole, sections = self._batches(mesh)
        start = init_fm_params(self.F, self.K, 0.3, seed=3)
        start["w"] = jnp.linspace(-0.2, 0.2, self.F, dtype=jnp.float32)
        want, want_m = make_fm_train_step(
            None, self.F, learning_rate=0.3)(start, whole)
        assert int(want_m["touched_rows"]) == len(np.unique(ids))

        step = make_fm_train_step(
            mesh, self.F, learning_rate=0.3, table_sharding=table_sharding)
        got, got_m = step(
            shard_params(_host(start), mesh,
                         rules=fm_partition_rules(table_sharding)), sections)
        # every chip reads the distinct ids of what it sorts
        per_chip = [len(np.unique(part)) for part in np.split(ids, CHIPS)]
        assert int(got_m["touched_rows"]) == (
            len(np.unique(ids)) if table_sharding == "factors"
            else sum(per_chip))
        np.testing.assert_allclose(
            float(got_m["loss_sum"]), float(want_m["loss_sum"]), rtol=2e-6)
        for k in ("w", "b", "v"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), rtol=2e-5, atol=1e-7)
        first, *rest = _replicas(got["w"])
        for other in rest:
            np.testing.assert_array_equal(
                first.view(np.uint32), other.view(np.uint32))
        assert np.abs(np.asarray(want["v"]) - np.asarray(start["v"])).max() > 0


class TestRepeatedIdsAndPadding:
    """(d): ids repeated within a row and across rows, a last batch of
    fewer rows than the batch (zero-weight rows) whose entries do not fill
    the sections' bucket."""

    F, K, ROWS, BATCH = 41, 8, 88, 32

    def _file(self, tmp_path, ragged=False):
        """``ragged``: every fourth row has no entry, the next one entry,
        the next twelve."""
        rng = np.random.RandomState(11)
        path = tmp_path / "rep.libsvm"
        with open(path, "w") as fh:
            for i in range(self.ROWS):
                size = (0, 1, 12, 4)[i % 4] if ragged else 4
                ids = list(rng.randint(1, 14, size=size))
                if size > 1:
                    ids[1] = ids[0]  # twice in one row
                if size:
                    ids.append(20 + i % 3)  # shared by a third of the rows
                fh.write("%d %s\n" % (i % 2, " ".join(
                    "%d:%.3f" % (j, 0.5 + rng.rand()) for j in ids)))
        return str(path)

    def _fit(self, path, mesh, start, **hyper):
        model = FMLearner(mesh=mesh, num_features=self.F, num_factors=self.K,
                          learning_rate=0.3, **hyper)
        model.restore_snapshot_model({"params": start})
        feed = DeviceFeed(
            create_parser(path, 0, 1),
            BatchSpec(batch_size=self.BATCH, layout="csr",
                      num_features=self.F), mesh=mesh)
        history = model.fit_feed(feed, epochs=2)
        feed.close()
        return history, _host(model.params)

    @pytest.mark.parametrize("ragged", [False, True], ids=["even", "ragged"])
    @pytest.mark.parametrize("l2", [0.0, 0.01])
    def test_d_matches_one_device(self, tmp_path, mesh, l2, ragged):
        path = self._file(tmp_path, ragged)
        start = _host(init_fm_params(self.F, self.K, 0.3, seed=3))
        start["w"] = np.linspace(-0.2, 0.2, self.F).astype(np.float32)
        h1, p1 = self._fit(path, None, start, l2=l2)
        h4, p4 = self._fit(path, mesh, start, l2=l2,
                           table_sharding="factors")
        np.testing.assert_allclose(h4, h1, rtol=2e-6)
        for k in ("w", "b", "v"):
            np.testing.assert_allclose(p4[k], p1[k], rtol=2e-5, atol=1e-7)
        named = np.zeros(self.F, bool)
        named[list(range(1, 14)) + [20, 21, 22]] = True
        moved = np.abs(p4["v"] - start["v"]).max(axis=1) > 0
        assert moved[named].all()
        if l2 == 0.0:  # feature 0 takes the padded entries' zeros
            np.testing.assert_array_equal(
                p4["v"][~named].view(np.uint32),
                start["v"][~named].view(np.uint32))
        else:
            assert moved.all()


class TestSnapshot:
    def test_e_save_and_resume_round_trip(self, cfg, mesh, data, tmp_path):
        """Two epochs, a snapshot at each boundary, then a new learner
        resumes for the third: its losses are the uninterrupted run's."""
        from dmlc_tpu.collective import JobSnapshot, load_snapshot

        kw = dict(batch_size=cfg["batch_rows_per_chip"] * CHIPS)
        whole = _learner(cfg, mesh)
        want = whole.fit_uri(data["path"], epochs=3, **kw)

        snap_uri = str(tmp_path / "snap")
        first = _learner(cfg, mesh)
        got = first.fit_uri(data["path"], epochs=2, snapshot_uri=snap_uri,
                            **kw)
        assert got == want[:2]

        version, state, _ = load_snapshot(JobSnapshot(snap_uri))
        assert version and state["epoch"] == 1
        held = state["model"]
        # one logical table, whatever held it, and nothing about how
        assert set(held) == {"params"}
        assert held["params"]["v"].shape == (
            cfg["num_features"], cfg["num_factors"])
        np.testing.assert_array_equal(
            held["params"]["v"], _host(first.params)["v"])

        resumed = _learner(cfg, mesh)
        history = resumed.fit_uri(
            data["path"], epochs=3, snapshot_uri=snap_uri, resume=True, **kw)
        assert history == want
        assert resumed.params["v"].sharding.spec == P(None, "dp")
        for k in ("w", "b", "v"):
            np.testing.assert_array_equal(
                _host(resumed.params)[k], _host(whole.params)[k])

    def test_e_a_snapshot_restores_under_another_placement(
            self, cfg, mesh, trained):
        """The part holds the logical table: one device takes it too, and
        a table of another shape is refused."""
        from dmlc_tpu.collective.checkpoint import _to_host
        from dmlc_tpu.utils.logging import DMLCError

        model = _to_host({"params": dict(trained.model.params)})
        assert type(model["params"]["v"]) is np.ndarray
        single = _learner(cfg, None)
        single.restore_snapshot_model(model)
        np.testing.assert_array_equal(
            np.asarray(single.params["v"]), model["params"]["v"])
        with pytest.raises(DMLCError, match="factor table of shape"):
            _learner(cfg, mesh, num_factors=8).restore_snapshot_model(model)


    def test_e_to_host_assembles_one_copy_of_each_part(self):
        """An array divided over one axis of a mesh and replicated over
        another: every part once, a real copy; replicated and host arrays
        go the old way."""
        from dmlc_tpu.collective.checkpoint import _is_divided, _to_host

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
        x = np.arange(6 * 8, dtype=np.float32).reshape(6, 8)
        divided = jax.device_put(x, NamedSharding(mesh, P(None, "b")))
        whole = jax.device_put(x, NamedSharding(mesh, P()))
        assert _is_divided(divided)
        assert not _is_divided(whole) and not _is_divided(x)
        assert not _is_divided(jnp.asarray(x))
        got = _to_host({"t": divided, "r": whole, "n": 3})
        assert type(got["t"]) is np.ndarray and got["n"] == 3
        np.testing.assert_array_equal(got["t"], x)
        np.testing.assert_array_equal(got["r"], x)


class TestRefusals:
    def test_f_factors_that_do_not_divide_are_refused(self, mesh):
        from dmlc_tpu.utils.logging import DMLCError

        with pytest.raises(DMLCError, match=(
                "num_factors divisible by the 4 chips.*num_factors=10")):
            FMLearner(mesh=mesh, num_features=64, num_factors=10,
                      table_sharding="factors")
        # replicated, any number of factors goes
        FMLearner(mesh=mesh, num_features=64, num_factors=10)

    def test_f_an_unknown_sharding_is_refused(self, mesh):
        from dmlc_tpu.params.parameter import ParamError

        with pytest.raises(ParamError):
            FMLearner(mesh=mesh, num_features=64, table_sharding="rows")


class TestLoweredStep:
    """(g)"""

    F, K, ROWS = 1003, 16, 64

    def _args(self, mesh):
        from dmlc_tpu.models.fm import fm_partition_rules
        from dmlc_tpu.parallel.partition import shard_params

        params = shard_params(
            init_fm_params(self.F, self.K), mesh,
            rules=fm_partition_rules("factors"))
        row = NamedSharding(mesh, P("dp"))
        bucket = self.ROWS // CHIPS * 11
        batch = {
            "label": jnp.zeros(self.ROWS), "weight": jnp.ones(self.ROWS),
            "indices": jnp.ones(CHIPS * bucket, jnp.int32),
            "values": jnp.ones(CHIPS * bucket),
            "offsets": jnp.tile(
                jnp.arange(self.ROWS // CHIPS + 1, dtype=jnp.int32) * 11,
                CHIPS)}
        return params, jax.device_put(batch, row)

    def test_g_exchange_scope_and_nothing_of_the_tables_shape(self, mesh):
        step = make_fm_train_step(
            mesh, self.F, table_sharding="factors", learning_rate=0.1)
        params, batch = self._args(mesh)
        text = step.lower(params, batch).as_text(debug_info=True)
        for scope in ("step.exchange", "step.order", "step.gather",
                      "step.forward", "step.backward", "step.update"):
            assert scope in text, scope
        assert "step.scatter" not in text  # no dense gradient is built

        eqns = list(_walk_eqns(jax.make_jaxpr(step)(params, batch).jaxpr))
        names = [e.primitive.name for e in eqns]
        assert sum(n.startswith("psum") for n in names) == 1, names
        assert sum(n == "all_gather" for n in names) == 5, names
        # inside shard_map a chip's table is [F, K/4]; w is [F]
        table = {(self.F, self.K), (self.F, self.K // CHIPS), (self.F,)}
        makers = sorted(
            e.primitive.name for e in eqns for out in e.outvars
            if tuple(out.aval.shape) in table
            and e.primitive.name not in ("shard_map", "pjit", "jit"))
        # w's one scatter-add, and v's inside the chunk loop with the
        # loop that carries it
        assert makers == ["scatter-add", "scatter-add", "while"], makers
        psum = next(e for e in eqns if e.primitive.name.startswith("psum"))
        assert [tuple(v.aval.shape) for v in psum.outvars] == [(self.ROWS,)]

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single-device", "factor-sharded"])
    def test_g_one_sort_and_eleven_indexed_passes(self, mesh, sharded):
        """Passes over the entries that share an index vector are one
        pass over concatenated columns, the step sorts its entries once,
        at its head, and the parameters are read at the distinct ids
        only. On the chip an indexed pass costs per index, not per column
        (PERF.md, PRs 29 and 31), so what can silently regress is the
        NUMBER of sorts, gathers and scatters (18 before PR 29) and the
        count of indices a gather from the table takes: 2 sorts (the
        entries by id; one array, to bring the distinct ids to the front)
        + 4 gathers (v and w at ``_UPDATE_CHUNK`` distinct ids a pass of
        the loop, every entry's rows from that buffer, a row's s and wg)
        + 5 scatters (the offsets' marks, the row sums, the id sums, w,
        v)."""
        from dmlc_tpu.models.fm import _UPDATE_CHUNK

        params, batch = self._args(mesh)
        if sharded:
            step = make_fm_train_step(
                mesh, self.F, table_sharding="factors", learning_rate=0.1)
        else:
            step = make_fm_train_step(None, self.F, learning_rate=0.1)
            params = init_fm_params(self.F, self.K)
            nnz = self.ROWS * 11
            batch = {
                "label": jnp.zeros(self.ROWS), "weight": jnp.ones(self.ROWS),
                "indices": jnp.ones(nnz, jnp.int32),
                "values": jnp.ones(nnz),
                "offsets": jnp.arange(self.ROWS + 1, dtype=jnp.int32) * 11}
        eqns = list(_walk_eqns(jax.make_jaxpr(step)(params, batch).jaxpr))
        passes = [e.primitive.name for e in eqns if e.primitive.name in (
            "sort", "gather", "scatter", "scatter-add")]
        assert passes.count("sort") == 2, passes
        assert passes.count("gather") <= 4, passes
        assert len(passes) <= 11, passes
        # the entries' sort carries each one's row and value with its id;
        # the other sorts one array and carries nothing
        by_id, compact = [e for e in eqns if e.primitive.name == "sort"]
        assert len(by_id.invars) == 3 and by_id.params["num_keys"] == 1
        assert by_id.params["is_stable"] and len(compact.invars) == 1
        # it opens the step: nothing is read from the parameters before
        # it, and after it only at one chunk of distinct ids a pass
        assert "gather" not in passes[:passes.index("sort")], passes
        table = {(self.F, self.K), (self.F, self.K // CHIPS), (self.F,)}
        reads = sorted(
            tuple(e.outvars[0].aval.shape) for e in eqns
            if e.primitive.name == "gather"
            and tuple(e.invars[0].aval.shape) in table)
        cols = self.K // CHIPS if sharded else self.K
        assert reads == [(_UPDATE_CHUNK,), (_UPDATE_CHUNK, cols)], reads
        makers = sorted(
            e.primitive.name for e in eqns for out in e.outvars
            if tuple(out.aval.shape) in table
            and e.primitive.name not in ("shard_map", "pjit", "jit"))
        # w's scatter-add, v's in its chunk loop; the gather's loop
        # carries the distinct rows' buffer, nothing of the table's shape
        assert makers == ["scatter-add", "scatter-add", "while"], makers

    def test_g_the_replicated_mesh_step_is_as_it_was(self, mesh):
        step = make_fm_train_step(mesh, self.F, learning_rate=0.1)
        params, batch = self._args(mesh)
        text = step.lower(
            jax.device_put(params, NamedSharding(mesh, P())),
            batch).as_text(debug_info=True)
        assert "step.scatter" in text and "step.exchange" not in text


class TestCounters:
    def test_counters_and_span_arg(self, cfg, mesh, data):
        from dmlc_tpu import obs
        from dmlc_tpu.obs import trace as obs_trace

        def read():
            flat = obs.registry().flat_values()
            return {k: flat.get('dmlc_fit_%s_total{model="fm"}' % k, 0.0)
                    for k in ("steps", "sparse_update_steps",
                              "sharded_table_steps", "exchange_bytes")}

        spans = []
        obs_trace.add_listener(spans.append)
        try:
            before = read()
            model = _learner(cfg, mesh)
            feed = _feed(cfg, data["path"], mesh)
            model.fit_feed(feed, epochs=1)
            feed.close()
            after = read()
        finally:
            obs_trace.remove_listener(spans.append)
        delta = {k: after[k] - before[k] for k in after}
        steps = cfg["rows"] // (cfg["batch_rows_per_chip"] * CHIPS)
        assert delta["steps"] == steps
        assert delta["sparse_update_steps"] == steps
        assert delta["sharded_table_steps"] == steps
        rows, bucket = cfg["batch_rows_per_chip"], cfg["batch_rows_per_chip"] * 11
        # a chip's section (entries, offsets, labels, weights) and its
        # f32[rows of the step] share of the psum
        a_step = bucket * 8 + (rows + 1) * 4 + rows * 8 + rows * CHIPS * 4
        assert delta["exchange_bytes"] == steps * a_step
        (epoch,) = [e for e in spans if e["name"] == "epoch"
                    and e.get("ph") == "X"]
        assert epoch["args"]["table_shards"] == CHIPS

    def test_a_replicated_mesh_counts_no_sharded_step(self, cfg, mesh, data):
        from dmlc_tpu import obs

        key = 'dmlc_fit_sharded_table_steps_total{model="fm"}'
        before = obs.registry().flat_values().get(key, 0.0)
        model = _learner(cfg, mesh, table_sharding="replicated",
                         num_features=2048)
        assert model.table_shards == 1
        path = os.path.join(os.path.dirname(data["path"]), "small.libsvm")
        with open(path, "w") as fh:
            for i in range(64):
                fh.write("%d %d:1 %d:1\n" % (i % 2, 1 + i, 100 + i))
        feed = DeviceFeed(
            create_parser(path, 0, 1),
            BatchSpec(batch_size=32, layout="csr", num_features=2048),
            mesh=mesh)
        model.fit_feed(feed, epochs=1)
        feed.close()
        assert obs.registry().flat_values().get(key, 0.0) == before


@pytest.mark.parametrize(
    "workload", ["kdd12-fm-k128.mesh4", "kdd12-fm.dtsh"])
def test_h_the_new_cells_rehearse(workload):
    """``run.py --rehearse``: the cell's whole control flow off the chip
    (data, mesh, init, check against the reference, window, result
    line)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--rehearse", "--seconds", "1", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True and "metrics" not in result
    assert result["device"]["count"] == (4 if "mesh4" in workload else 1)
    assert {"rows_per_s", "setup_s"} <= set(result["metric_names"])

"""Three-way collective parity + SPMD-step contracts.

The tentpole claim of the device-collective path is that every sync
flavor computes the SAME bits:

- the socket engine's tree reduce (a REAL 2-process world),
- the DeviceEngine host path's jitted [world, ...] reduction,
- the in-graph SPMD primitives (psum/pmax/pmin/pbitor inside shard_map)

must agree bit-for-bit at world 2 (sum is one addition per element on
every path; max/min/bitor are order-insensitive at any world). Plus: the
hostsync train step vs the mesh SPMD step, the engine-selection knob,
membership listeners, and the one-trace-per-bucket recompile contract.
"""

import gc
import multiprocessing as mp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.collective import device as dev
from jax import shard_map

WORLD = 2

# (op, shape, dtype): odd, non-power-of-two shapes on purpose
CASES = {
    "sum_f32": ("sum", (1031,), np.float32),
    "sum_f64": ("sum", (257,), np.float64),
    "sum_i32": ("sum", (3, 17), np.int32),
    "max_f32": ("max", (1031,), np.float32),
    "max_f64": ("max", (257,), np.float64),
    "max_i32": ("max", (3, 17), np.int32),
    "min_f32": ("min", (1031,), np.float32),
    "min_f64": ("min", (257,), np.float64),
    "min_i32": ("min", (3, 17), np.int32),
    "bitor_i32": ("bitor", (129,), np.int32),
}


def _rank_array(case: str, rank: int) -> np.ndarray:
    op, shape, dtype = CASES[case]
    # index-based seed: str hash is per-process randomized and the socket
    # workers are separate processes
    rng = np.random.RandomState(1000 * rank + sorted(CASES).index(case))
    if op == "bitor":
        return rng.randint(0, 1 << 30, size=shape).astype(dtype)
    if np.issubdtype(dtype, np.integer):
        return rng.randint(-1000, 1000, size=shape).astype(dtype)
    return rng.randn(*shape).astype(dtype)


def _socket_worker(uri, port, world, q):
    """Real socket-engine rank: allreduce every case, rank 0 reports the
    result bytes. No jax import — the reference side is pure numpy."""
    from dmlc_tpu.collective.socket_engine import SocketEngine

    engine = SocketEngine(tracker_uri=uri, tracker_port=port,
                          world_size=world)
    try:
        out = {}
        for case, (op, _, _) in CASES.items():
            res = engine.allreduce(_rank_array(case, engine.rank), op=op)
            out[case] = (res.tobytes().hex(), str(res.dtype))
        if engine.rank == 0:
            q.put(out)
    finally:
        engine.shutdown()


def _socket_reference():
    """Run the 2-process socket world once per test session."""
    from dmlc_tpu.tracker.rendezvous import RabitTracker

    tracker = RabitTracker("127.0.0.1", WORLD, port=19200, port_end=19290)
    tracker.start(WORLD)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_socket_worker,
                    args=("127.0.0.1", tracker.port, WORLD, q))
        for _ in range(WORLD)
    ]
    for p in procs:
        p.start()
    out = q.get(timeout=120)
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    tracker.join()
    tracker.close()
    return out


@pytest.fixture(scope="module")
def socket_results():
    return _socket_reference()


_SPMD_OPS = {
    "sum": dev.psum,
    "max": dev.pmax,
    "min": dev.pmin,
    "bitor": dev.pbitor,
}


def _spmd_allreduce(op: str, stacked: np.ndarray) -> np.ndarray:
    """The in-graph path: [world, ...] sharded over a world-sized
    submesh, reduced by the axis-name primitive inside shard_map."""
    mesh = Mesh(np.asarray(jax.devices()[:WORLD]), ("dp",))

    def f(x):
        return _SPMD_OPS[op](x, "dp")[0]

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P()))
    return np.asarray(fn(stacked))


def _engine_reduce(op: str, stacked: np.ndarray) -> np.ndarray:
    """The DeviceEngine host path's jitted reduction (what world>1
    allreduce dispatches), fed the same [world, ...] stack."""
    return np.asarray(dev.DeviceEngine()._reduce_fn(op)(stacked))


class TestThreeWayParity:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_socket_vs_device_vs_spmd_bitexact(self, case, socket_results):
        op, _, dtype = CASES[case]
        stacked = np.stack([_rank_array(case, r) for r in range(WORLD)])
        ref_hex, ref_dtype = socket_results[case]
        from contextlib import nullcontext

        # f64 cases need x64 on for the device paths; the socket engine
        # reduces in native numpy and needs nothing
        ctx = jax.enable_x64(True) if dtype == np.float64 else nullcontext()
        with ctx:
            got_engine = _engine_reduce(op, stacked)
            got_spmd = _spmd_allreduce(op, stacked)
        assert str(got_engine.dtype) == ref_dtype
        assert str(got_spmd.dtype) == ref_dtype
        assert got_engine.tobytes().hex() == ref_hex, \
            f"{case}: DeviceEngine reduction != socket tree"
        assert got_spmd.tobytes().hex() == ref_hex, \
            f"{case}: in-graph SPMD collective != socket tree"


class TestBucketedPsum:
    def test_bucketed_bitexact_vs_per_leaf(self):
        """Bucketing concatenates before the psum but never reorders the
        elementwise additions — fused and per-leaf reductions must be
        IDENTICAL, not merely close."""
        n = len(jax.devices())
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        rng = np.random.RandomState(3)
        tree = {
            "w": rng.randn(n, 37, 3).astype(np.float32),
            "b": rng.randn(n, 5).astype(np.float32),
            "i": rng.randint(-9, 9, size=(n, 11)).astype(np.int32),
        }

        def run(bucket):
            def f(t):
                return dev.bucketed_psum(t, axis="dp", bucket=bucket)

            fn = jax.jit(shard_map(
                f, mesh=mesh,
                in_specs=P("dp"), out_specs=P("dp"),
            ))
            return {k: np.asarray(v) for k, v in fn(dict(tree)).items()}

        fused, per = run(True), run(False)
        for k in tree:
            assert fused[k].tobytes() == per[k].tobytes(), k
            assert fused[k].dtype == tree[k].dtype


class TestHostsyncVsSpmdStep:
    def test_train_loops_agree(self):
        """make_hostsync_train_step (local-engine world=1 allreduce pass-
        through) vs the mesh SPMD step over the same global batches. The
        shard count differs from 1, so the partial-sum fold order does
        too — allclose, not bit-equality, is the in-process contract
        (bit-exactness at matched shard/process counts is pinned by the
        scripts/ci_checks.sh SPMD smoke)."""
        from dmlc_tpu import collective
        from dmlc_tpu.models.linear import (
            init_linear_params,
            make_hostsync_train_step,
            make_linear_train_step,
        )

        collective.finalize()
        collective.init("local")
        try:
            nf, rows = 8, 64
            rng = np.random.RandomState(11)
            batches = [
                {
                    "x": rng.randn(rows, nf).astype(np.float32),
                    "label": (rng.rand(rows) > 0.5).astype(np.float32),
                    "weight": np.ones(rows, dtype=np.float32),
                }
                for _ in range(4)
            ]
            host = make_hostsync_train_step(num_features=nf)
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
            spmd = make_linear_train_step(mesh, num_features=nf)

            hp, hv = init_linear_params(nf), None
            hv = {"w": jnp.zeros((nf,)), "b": jnp.zeros(())}
            sp = jax.device_get(hp)
            sp = {k: jnp.asarray(v) for k, v in sp.items()}
            sv = {"w": jnp.zeros((nf,)), "b": jnp.zeros(())}
            for b in batches:
                hp, hv, hm = host(hp, hv, dict(b))
                sp, sv, sm = spmd(sp, sv, dict(b))
                np.testing.assert_allclose(
                    float(hm["loss_sum"]), float(sm["loss_sum"]),
                    rtol=1e-5)
            np.testing.assert_allclose(
                np.asarray(hp["w"]), np.asarray(sp["w"]), rtol=1e-5,
                atol=1e-6)
            np.testing.assert_allclose(
                float(hp["b"]), float(sp["b"]), rtol=1e-5)
        finally:
            collective.finalize()


class TestEngineKnob:
    def test_knob_parsing(self, monkeypatch):
        from dmlc_tpu.params.knobs import collective_engine

        for val in ("auto", "device", "socket", "local"):
            monkeypatch.setenv("DMLC_TPU_COLLECTIVE", val)
            assert collective_engine() == val
        monkeypatch.setenv("DMLC_TPU_COLLECTIVE", "DeViCe")
        assert collective_engine() == "device"  # case-insensitive
        monkeypatch.setenv("DMLC_TPU_COLLECTIVE", "bogus")
        assert collective_engine() == "auto"  # invalid falls back
        monkeypatch.delenv("DMLC_TPU_COLLECTIVE")
        assert collective_engine() == "auto"

    def test_knob_selects_device_engine(self, monkeypatch):
        from dmlc_tpu import collective

        collective.finalize()
        monkeypatch.setenv("DMLC_TPU_COLLECTIVE", "device")
        try:
            collective.init()
            assert collective.engine_kind() == "device"
        finally:
            collective.finalize()

    def test_explicit_engine_beats_knob(self, monkeypatch):
        from dmlc_tpu import collective

        collective.finalize()
        monkeypatch.setenv("DMLC_TPU_COLLECTIVE", "device")
        try:
            collective.init("local")
            assert collective.engine_kind() == "local"
        finally:
            collective.finalize()

    def test_invalid_knob_falls_back_to_auto(self, monkeypatch):
        from dmlc_tpu import collective

        collective.finalize()
        monkeypatch.setenv("DMLC_TPU_COLLECTIVE", "nonsense")
        monkeypatch.delenv("DMLC_TRACKER_URI", raising=False)
        try:
            collective.init()
            # single process, no tracker: auto resolves to local
            assert collective.engine_kind() == "local"
        finally:
            collective.finalize()


class TestMembershipListeners:
    def test_listener_fires_and_unregisters(self):
        from dmlc_tpu import collective

        calls = []
        unlisten = collective.on_membership_change(lambda: calls.append(1))
        try:
            collective._notify_membership()
            assert calls == [1]
        finally:
            unlisten()
        collective._notify_membership()
        assert calls == [1]  # unregistered: no second fire

    def test_learner_reshards_on_membership_change(self):
        from dmlc_tpu import collective
        from dmlc_tpu.models import LinearLearner

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
        learner = LinearLearner(mesh=mesh, num_features=4)
        learner._ensure(4, "dense")
        assert learner._step is not None
        w_before = np.asarray(learner.params["w"]).copy()
        try:
            collective._notify_membership()
            # resharded: step dropped for a retrace, values preserved,
            # mesh rebuilt over the CURRENT device set
            assert learner._step is None
            assert learner.mesh is not mesh
            assert learner.mesh.devices.size == len(jax.devices())
            np.testing.assert_array_equal(
                np.asarray(learner.params["w"]), w_before)
        finally:
            if learner._unlisten:
                learner._unlisten()

    def test_dead_learner_listener_is_harmless(self):
        from dmlc_tpu import collective
        from dmlc_tpu.models import FMLearner

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
        learner = FMLearner(mesh=mesh, num_features=4)
        del learner
        gc.collect()
        # the weakref callback must not keep the learner alive nor raise
        collective._notify_membership()


class TestRecompileSentinel:
    def test_one_trace_per_batch_shape(self):
        """The SPMD step must compile exactly once per batch bucket shape
        — a recompile on a repeated shape is the regression the PR 8
        sentinel exists to catch."""
        from dmlc_tpu.models.linear import (
            init_linear_params,
            make_linear_train_step,
        )
        from dmlc_tpu.obs.device_telemetry import compile_counts

        if os.environ.get("DMLC_TPU_DEVICE_TELEMETRY") == "0":
            pytest.skip("device telemetry disabled")
        nf = 6
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        step = make_linear_train_step(mesh, num_features=nf)
        n = len(jax.devices())

        def batch(rows, seed):
            rng = np.random.RandomState(seed)
            return {
                "x": rng.randn(rows, nf).astype(np.float32),
                "label": (rng.rand(rows) > 0.5).astype(np.float32),
                "weight": np.ones(rows, dtype=np.float32),
            }

        before = compile_counts().get("linear.step", 0)
        params = init_linear_params(nf)
        velocity = {"w": jnp.zeros((nf,)), "b": jnp.zeros(())}
        for seed in range(3):  # one bucket shape, three batches
            params, velocity, _ = step(params, velocity, batch(8 * n, seed))
        assert compile_counts().get("linear.step", 0) - before == 1
        for seed in range(2):  # second bucket shape
            params, velocity, _ = step(params, velocity, batch(16 * n, seed))
        assert compile_counts().get("linear.step", 0) - before == 2

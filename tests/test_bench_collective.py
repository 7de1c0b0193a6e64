"""The collective benchmark tier must stay runnable: tiny-size smoke of
the measurements (socket loopback allreduce GB/s, device psum step, the
in-graph SPMD step) plus the topology-override restore contract."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench_collective  # noqa: E402


class TestSocketTier:
    def test_tree_and_ring_metrics(self):
        out = bench_collective.socket_allreduce_metrics(
            world=2,
            cases=(("tree_4k", 4096, "tree"), ("ring_1m", 1 << 20, "ring")),
            iters=2,
        )
        assert out["socket_world"] == 2
        assert out["tree_4k_gbps"] > 0
        assert out["ring_1m_gbps"] > 0


class TestDeviceTier:
    def test_psum_metrics_on_mesh(self):
        out = bench_collective.device_psum_metrics(payload_mb=1.0, iters=2)
        # conftest pins 8 virtual CPU devices
        assert out["psum_devices"] == 8
        assert out["psum_step_ms"] > 0
        assert out["psum_algo_gbps"] > 0
        assert "psum_ici_utilization" not in out  # cpu: no ICI estimate

    def test_engine_allreduce_metric(self):
        from bench_collective import device_engine_allreduce_metrics

        out = device_engine_allreduce_metrics(payload_mb=1.0, iters=3)
        assert out["engine_allreduce_world"] >= 1
        key = ("engine_allreduce_gbps" if out["engine_allreduce_world"] > 1
               else "engine_reduce_single_process_gbps")
        assert out[key] > 0

    def test_algo_estimator_scores_against_a_known_peak(self):
        """The ICI-utilization estimator as a pure function: ring algo
        volume 2(n-1)/n × size, utilization = achieved / the device's
        interconnect peak — and NO utilization when the peak is unknown
        (a device kind outside xla_cost.DEVICE_PEAKS, such as this CPU
        mesh), never a made-up denominator."""
        from bench_collective import allreduce_algo_metrics

        n, nbytes, dt = 8, 32 << 20, 0.001
        out = allreduce_algo_metrics(n, nbytes, dt, ici_gbps=200.0)
        algo = 2 * (n - 1) / n * nbytes
        assert out["psum_algo_gbps"] == round(algo / dt / 1e9, 3)
        assert out["psum_ici_utilization"] == round(
            (algo / dt) / 200e9, 3)
        assert "psum_ici_utilization" not in allreduce_algo_metrics(
            n, nbytes, dt)
        assert "psum_ici_utilization" not in \
            bench_collective.device_psum_metrics(payload_mb=1.0, iters=2)

    def test_grad_bucket_tier(self):
        out = bench_collective.grad_bucket_metrics(iters=2)
        assert out["bucket_leaves"] > 20
        assert out["bucket_fused_ms"] > 0
        assert out["bucket_per_tensor_ms"] > 0


class TestCrossoverSweep:
    def test_sweep_reports_both_topologies_and_crossover(self):
        out = bench_collective.crossover_sweep(
            world=2, sizes=(4096, 65536), iters=2)
        assert out["tree_4096_gbps"] > 0
        assert out["ring_4096_gbps"] > 0
        assert "crossover_bytes" in out  # may be None: tree can win both


class TestBucketedAllreduce:
    def test_bucketed_matches_per_tensor(self):
        """bucket=True must be numerically identical to per-leaf psums,
        across mixed shapes and dtypes (dtype-grouped concat)."""
        import jax
        import numpy as np

        from dmlc_tpu.collective.device import make_allreduce_step
        from dmlc_tpu.parallel.mesh import (
            batch_sharding,
            data_parallel_mesh,
        )

        mesh = data_parallel_mesh()
        n = len(jax.devices())
        sharding = batch_sharding(mesh)
        rng = np.random.RandomState(5)
        grads = {
            "w": rng.randn(n, 4, 3).astype(np.float32),
            "b": rng.randn(n, 7).astype(np.float32),
            # f16 exercises the dtype-grouped concat (f64 would silently
            # downcast at device_put under default jax_enable_x64=False)
            "emb": rng.randn(n, 2, 5).astype(np.float16),
            "scale": rng.randn(n, 1).astype(np.float32),
        }
        put = {k: jax.device_put(v, sharding) for k, v in grads.items()}
        fused = make_allreduce_step(mesh, bucket=True)(put)
        put2 = {k: jax.device_put(v, sharding) for k, v in grads.items()}
        per = make_allreduce_step(mesh, bucket=False)(put2)
        for k in grads:
            tol = 1e-2 if grads[k].dtype == np.float16 else 1e-5
            np.testing.assert_allclose(
                np.asarray(fused[k]), np.asarray(per[k]), rtol=tol
            )
            np.testing.assert_allclose(  # leading dim stays shard-local
                np.asarray(fused[k])[0],
                grads[k].astype(np.float32).sum(axis=0),
                rtol=tol, atol=tol,
            )
            assert fused[k].dtype == grads[k].dtype


class TestForcedTopology:
    """The bench's topology override must restore the CONSTRUCTED
    threshold — including env overrides and on the exception path —
    so post-block collectives honor the engine's real crossover."""

    class _FakeEngine:
        ring_threshold_bytes = 12345  # stands in for a constructed value

    def test_forces_and_restores(self):
        eng = self._FakeEngine()
        with bench_collective.forced_topology(eng, "ring"):
            assert eng.ring_threshold_bytes == 0
        assert eng.ring_threshold_bytes == 12345
        with bench_collective.forced_topology(eng, "tree"):
            assert eng.ring_threshold_bytes == 1 << 62
        assert eng.ring_threshold_bytes == 12345

    def test_restores_on_exception(self):
        eng = self._FakeEngine()
        with pytest.raises(RuntimeError):
            with bench_collective.forced_topology(eng, "ring"):
                raise RuntimeError("bench worker died mid-loop")
        assert eng.ring_threshold_bytes == 12345


class TestSpmdStepTier:
    def test_spmd_psum_step_metrics_on_mesh(self):
        out = bench_collective.spmd_psum_step_metrics(
            payload_mb=0.5, iters=2)
        assert out["spmd_devices"] == 8  # conftest's virtual CPU mesh
        assert out["spmd_platform"] == "cpu"
        assert out["spmd_step_ms"] > 0
        assert out["spmd_psum_step_gbps"] > 0
        assert "ici_utilization" not in out  # cpu: no ICI peak estimate

    def test_sentry_gates_spmd_keys_higher_is_better(self):
        """The new bench keys must be wired into the perf sentry as
        higher-is-better: a drop past tolerance is a regression."""
        from dmlc_tpu.obs import sentry

        hist = [
            {"metric": "m", "value": 1.0,
             "extra": {"spmd_psum_step_gbps": g, "ici_utilization": u}}
            for g, u in ((10.0, 0.9), (10.2, 0.91), (10.1, 0.92))
        ]
        series = sentry.metric_series(hist)
        fresh = sentry.record_values(
            {"metric": "m", "value": 1.0,
             "extra": {"spmd_psum_step_gbps": 5.0,
                       "ici_utilization": 0.4}})
        names = {r["metric"] for r in sentry.gate(fresh, series)}
        assert {"spmd_psum_step_gbps", "ici_utilization"} <= names

"""Collective tests: loopback tracker + multi-process socket tree allreduce
(the multi-node smoke test the reference lacks in-repo — SURVEY §4), plus
link-map topology unit tests and the rabit-style API."""

import multiprocessing as mp

import numpy as np
import pytest

from dmlc_tpu.tracker.rendezvous import (
    RabitTracker,
    build_link_maps,
    build_ring,
    build_tree,
)


class TestLinkMaps:
    @pytest.mark.parametrize("world", [1, 2, 3, 4, 7, 8, 16, 33])
    def test_tree_shape(self, world):
        tree, parent = build_tree(world)
        assert parent[0] == -1
        for r in range(1, world):
            assert parent[r] in tree[r]
            assert r in tree[parent[r]]
        # tree is connected: BFS from 0 reaches everyone
        seen = {0}
        frontier = [0]
        while frontier:
            nxt = []
            for node in frontier:
                for peer in tree[node]:
                    if peer not in seen:
                        seen.add(peer)
                        nxt.append(peer)
            frontier = nxt
        assert seen == set(range(world))

    @pytest.mark.parametrize("world", [2, 3, 4, 7, 8, 16, 33])
    def test_ring_is_hamiltonian(self, world):
        tree, parent = build_tree(world)
        ring = build_ring(tree, parent)
        cur, seen = 0, [0]
        for _ in range(world - 1):
            cur = ring[cur][1]
            seen.append(cur)
        assert sorted(seen) == list(range(world))
        assert ring[seen[-1]][1] == 0  # closes the loop
        for r in range(world):
            prev, nxt = ring[r]
            assert ring[nxt][0] == r
            assert ring[prev][1] == r

    @pytest.mark.parametrize("world", [2, 5, 8, 13])
    def test_relabeled_ring_is_sequential(self, world):
        tree, parent, ring = build_link_maps(world)
        for r in range(world):
            assert ring[r] == ((r - 1) % world, (r + 1) % world)
        assert parent[0] == -1


def _worker_main(tracker_uri, tracker_port, world, results):
    """Subprocess body: rendezvous + collectives through the socket engine."""
    from dmlc_tpu.collective.socket_engine import SocketEngine

    engine = SocketEngine(
        tracker_uri=tracker_uri,
        tracker_port=tracker_port,
        world_size=world if True else -1,
    )
    rank = engine.rank
    try:
        # 1. float sum allreduce (the BASELINE smoke config)
        out = engine.allreduce(np.full(16, rank + 1, dtype=np.float32), op="sum")
        expected_sum = world * (world + 1) / 2
        ok_sum = np.allclose(out, expected_sum)
        # 2. max
        out_max = engine.allreduce(np.asarray([float(rank)]), op="max")
        ok_max = out_max[0] == world - 1
        # 3. broadcast from non-zero root
        root = 1 % world
        payload = np.arange(5, dtype=np.int64) * 100 if rank == root else None
        got = engine.broadcast(payload, root=root)
        ok_bcast = np.array_equal(got, np.arange(5, dtype=np.int64) * 100) if world > 1 else True
        # 4. allgather
        gathered = engine.allgather(np.asarray([rank], dtype=np.int32))
        ok_gather = [int(g[0]) for g in gathered] == list(range(world))
        # 5. deterministic sum: run twice, bit-compare
        a = np.random.RandomState(rank).rand(64).astype(np.float32)
        s1 = engine.allreduce(a)
        s2 = engine.allreduce(a)
        ok_det = np.array_equal(s1, s2)
        # 5b. the rest of the rabit op surface: min / prod / bitwise-OR
        # (engine.h op::Min/Prod/BitOR)
        out_min = engine.allreduce(np.asarray([float(rank)]), op="min")
        ok_det = ok_det and out_min[0] == 0.0
        out_prod = engine.allreduce(
            np.asarray([2.0], dtype=np.float64), op="prod"
        )
        ok_det = ok_det and out_prod[0] == float(2 ** world)
        out_bitor = engine.allreduce(
            np.asarray([1 << rank], dtype=np.int64), op="bitor"
        )
        ok_det = ok_det and int(out_bitor[0]) == (1 << world) - 1
        # 6. ring allreduce (long-message path): force the ring by dropping
        # the threshold; must agree with the tree result elementwise and be
        # bit-stable across calls. Shape chosen to not divide evenly.
        ok_ring = True
        if world > 1:
            big = np.random.RandomState(100 + rank).rand(4097).astype(np.float32)
            tree_out = engine.allreduce(big)
            engine.ring_threshold_bytes = 0
            ring1 = engine.allreduce(big)
            ring2 = engine.allreduce(big)
            ring_max = engine.allreduce(big, op="max")
            engine.ring_threshold_bytes = SocketEngine.ring_threshold_bytes
            tree_max = engine.allreduce(big, op="max")
            ok_ring = (
                np.array_equal(ring1, ring2)
                and np.allclose(ring1, tree_out, rtol=1e-6, atol=1e-6)
                and np.array_equal(ring_max, tree_max)
            )
        engine.tracker_print(f"worker {rank} done")
        results.put((
            rank,
            ok_sum and ok_max and ok_bcast and ok_gather and ok_det and ok_ring,
        ))
    finally:
        engine.shutdown()


@pytest.mark.parametrize("world", [1, 2, 4, 5])
def test_socket_engine_loopback(world):
    tracker = RabitTracker("127.0.0.1", world, port=19091, port_end=19191)
    tracker.start(world)
    ctx = mp.get_context("spawn")
    results = ctx.Queue()
    procs = [
        ctx.Process(
            target=_worker_main,
            args=("127.0.0.1", tracker.port, world, results),
        )
        for _ in range(world)
    ]
    for p in procs:
        p.start()
    oks = {}
    for _ in range(world):
        rank, ok = results.get(timeout=60)
        oks[rank] = ok
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    tracker.join()
    tracker.close()
    assert sorted(oks) == list(range(world))
    assert all(oks.values())


class TestRabitApi:
    def test_local_engine_api(self):
        from dmlc_tpu import collective as C

        C.finalize()
        C.init("local")
        try:
            assert C.rank() == 0
            assert C.world_size() == 1
            np.testing.assert_array_equal(
                C.allreduce(np.asarray([1.0, 2.0])), [1.0, 2.0]
            )
            np.testing.assert_array_equal(
                C.broadcast(np.asarray([5])), [5]
            )
            assert len(C.allgather(np.asarray([3]))) == 1
            C.barrier()
            C.tracker_print("hello")
        finally:
            C.finalize()

    def test_checkpoint_roundtrip(self, tmp_path):
        from dmlc_tpu import collective as C

        C.finalize()
        C.init("local")
        try:
            state = {"weights": np.arange(4, dtype=np.float32), "epoch": 3}
            assert C.version_number() == 0
            C.checkpoint(state, uri=str(tmp_path / "ckpt.bin"))
            assert C.version_number() == 1
            loaded = C.load_checkpoint()
            np.testing.assert_array_equal(loaded["weights"], state["weights"])
            assert loaded["epoch"] == 3
        finally:
            C.finalize()
        # fresh engine recovers from uri
        C.init("local")
        try:
            loaded = C.load_checkpoint(uri=str(tmp_path / "ckpt.bin"))
            assert loaded is not None and loaded["epoch"] == 3
            # the snapshot carries its version: a restarted process
            # resynchronizes version_number() with what it resumes from
            assert C.version_number() == 1
        finally:
            C.finalize()


class TestDeviceEngineOps:
    def test_op_validation_and_world1_semantics(self):
        """DeviceEngine: unknown op / bitor-on-float raise before any
        transport; world=1 valid ops return the input unchanged (rabit
        world=1 semantics)."""
        from dmlc_tpu.collective.device import DeviceEngine

        eng = DeviceEngine()
        assert eng.world_size == 1
        with pytest.raises(ValueError):
            eng.allreduce(np.ones(3, dtype=np.float32), op="bogus")
        with pytest.raises(TypeError):
            eng.allreduce(np.ones(3, dtype=np.float32), op="bitor")
        got = eng.allreduce(np.asarray([3, 5], dtype=np.int64), op="bitor")
        np.testing.assert_array_equal(got, [3, 5])
        got = eng.allreduce(np.asarray([2.0]), op="prod")
        np.testing.assert_array_equal(got, [2.0])


class TestDeviceCollectives:
    def test_psum_on_virtual_mesh(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from dmlc_tpu.collective import psum

        devs = np.asarray(jax.devices())
        assert devs.size == 8, "conftest must provide 8 virtual devices"
        mesh = Mesh(devs, ("dp",))

        def f(x):
            return psum(jnp.sum(x), "dp")

        g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P()))
        x = jnp.arange(16.0)
        assert float(g(x)) == float(x.sum())

    def test_make_allreduce_step(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from dmlc_tpu.collective import make_allreduce_step

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        step = make_allreduce_step(mesh)
        grads = {"w": jnp.ones((8, 4)), "b": jnp.arange(8.0)}
        out = step(grads)
        np.testing.assert_allclose(np.asarray(out["w"]), np.full((1, 4), 8.0))
        np.testing.assert_allclose(np.asarray(out["b"]), [np.arange(8.0).sum()])

    def test_ppermute_ring(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from dmlc_tpu.collective import ppermute_next

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        f = jax.jit(
            shard_map(
                lambda x: ppermute_next(x, "dp"),
                mesh=mesh,
                in_specs=P("dp"),
                out_specs=P("dp"),
            )
        )
        x = jnp.arange(8.0)
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

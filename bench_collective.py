#!/usr/bin/env python
"""Collective benchmark tier (BASELINE north star: grad-allreduce ICI
bandwidth utilization; reference analog: the tier-2 throughput harnesses,
test/libsvm_parser_test.cc:23-35, rebuilt for the collective layer).

Four measurements, all hermetic on one host:

- socket tree allreduce GB/s (loopback multi-process, latency-bound size)
- socket ring allreduce GB/s (loopback multi-process, bandwidth-bound size)
- device psum: jit-compiled allreduce step time and achieved bytes/s over
  the mesh axis on the devices jax reports — payload re-staged from host
  numpy each step, i.e. the legacy DeviceEngine round-trip shape. With >1
  device of a kind whose interconnect peak is known, ICI utilization =
  achieved algorithm bandwidth / peak (the ``device_kind`` row of
  ``obs.xla_cost.DEVICE_PEAKS``; ``DMLC_TPU_ICI_PEAK_GBPS`` overrides; an
  unknown kind reports no utilization).
- SPMD in-graph step (``spmd_psum_step_gbps``, ``ici_utilization``): the
  training hot path — donated device-resident params, sharded grads, the
  allreduce a psum traced INSIDE the jitted step; zero host bytes moved.

``collective_metrics()`` returns a flat dict merged into bench.py's JSON
line; ``python bench_collective.py`` prints it standalone.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import sys
import time
from contextlib import contextmanager

REPO = os.path.dirname(os.path.abspath(__file__))

# (metric key, payload bytes, forced topology)
DEFAULT_SOCKET_CASES = (
    ("socket_tree_64k", 64 << 10, "tree"),
    ("socket_ring_8m", 8 << 20, "ring"),
)
# DMLC_TPU_BENCH_SOCKET_WORLD re-derives the tree/ring crossover at other
# world sizes on capable hosts (socket_engine.ring_threshold_bytes notes
# why the world=4 figure shouldn't be trusted at 8+)
DEFAULT_SOCKET_WORLD = int(os.environ.get("DMLC_TPU_BENCH_SOCKET_WORLD", 4))
DEFAULT_SOCKET_ITERS = 10


@contextmanager
def forced_topology(engine, topo: str):
    """Force one allreduce topology on ``engine`` for the block: "ring"
    (threshold 0) or "tree" (threshold 2**62). Restores the CONSTRUCTED
    ``ring_threshold_bytes`` on exit — including any
    DMLC_TPU_RING_THRESHOLD_BYTES override the engine applied at build
    time, and on the exception path — so collectives after the block
    (the straggler-max allreduce below) honor the engine's real
    crossover. Previously a comment-only contract inline in the bench
    worker; as a context manager the restore is unit-testable
    (tests/test_bench_collective.py)."""
    constructed = engine.ring_threshold_bytes
    engine.ring_threshold_bytes = 0 if topo == "ring" else (1 << 62)
    try:
        yield engine
    finally:
        engine.ring_threshold_bytes = constructed


def _socket_bench_worker(uri, port, world, cases, iters, q):
    """Subprocess body: rendezvous, then timed allreduce loops per case.
    Per-case time is the max across ranks (allreduce 'max' of the local
    time), so the reported bandwidth is the straggler-bound figure."""
    sys.path.insert(0, REPO)
    import numpy as np

    from dmlc_tpu.collective.socket_engine import SocketEngine

    engine = SocketEngine(
        tracker_uri=uri, tracker_port=port, world_size=world
    )
    try:
        out = {}
        for name, nbytes, topo in cases:
            arr = np.ones(max(1, nbytes // 4), dtype=np.float32)
            with forced_topology(engine, topo):
                engine.allreduce(arr)  # warmup (first ring call opens buffers)
                t0 = time.perf_counter()
                for _ in range(iters):
                    engine.allreduce(arr)
                local_dt = (time.perf_counter() - t0) / iters
            worst = float(
                engine.allreduce(
                    np.array([local_dt], dtype=np.float64), op="max"
                )[0]
            )
            out[name + "_gbps"] = round(nbytes / worst / 1e9, 6)
        if engine.rank == 0:
            q.put(out)
    finally:
        engine.shutdown()


def socket_allreduce_metrics(
    world: int = DEFAULT_SOCKET_WORLD,
    cases=DEFAULT_SOCKET_CASES,
    iters: int = DEFAULT_SOCKET_ITERS,
    timeout: float = 120.0,
) -> dict:
    """Loopback tracker + ``world`` worker processes; tree and ring
    allreduce payload GB/s at latency- and bandwidth-bound sizes."""
    from dmlc_tpu.tracker.rendezvous import RabitTracker

    tracker = RabitTracker("127.0.0.1", world, port=19290, port_end=19390)
    tracker.start(world)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(
            target=_socket_bench_worker,
            args=("127.0.0.1", tracker.port, world, tuple(cases), iters, q),
        )
        for _ in range(world)
    ]
    for p in procs:
        p.start()
    try:
        out = q.get(timeout=timeout)
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
        tracker.close()
    out["socket_world"] = world
    # honesty marker: `world` processes + tracker share this host's CPUs,
    # so loopback figures are contention floors, not network bandwidth
    out["socket_note"] = (
        f"loopback, {world} procs on {os.cpu_count() or 1} cpu(s): "
        "contention floor"
    )
    return out


def allreduce_algo_metrics(n: int, nbytes: int, dt: float,
                           ici_gbps=None) -> dict:
    """Pure estimator for the >1-device psum tier (factored out so the
    virtual-mesh tests exercise it without real multi-chip hardware).
    Ring-allreduce moves 2(n-1)/n × size per device, so achieved
    algorithm bandwidth = that volume / step time; the ICI utilization
    is achieved / ``ici_gbps`` (the device's per-chip interconnect peak,
    ``xla_cost.device_peaks()["ici_gbps"]``) and is absent when the peak
    is unknown."""
    algo_bytes = 2 * (n - 1) / n * nbytes  # per-device wire volume
    metrics = {"psum_algo_gbps": round(algo_bytes / dt / 1e9, 3)}
    if ici_gbps:
        metrics["psum_ici_utilization"] = round(
            (algo_bytes / dt) / (ici_gbps * 1e9), 3)
    return metrics


def crossover_sweep(world: int = 4,
                    sizes=(64 << 10, 256 << 10, 1 << 20, 2 << 20, 4 << 20),
                    iters: int = 4) -> dict:
    """Tree vs ring allreduce at a ladder of sizes → the measured
    crossover (how SocketEngine.ring_threshold_bytes was derived; rerun
    on a new host/network to re-justify it). Returns per-size GB/s for
    both topologies plus ``crossover_bytes``: the first size where the
    ring at least matches the tree (None if the tree wins everywhere)."""
    cases = []
    for s in sizes:
        cases.append((f"tree_{s}", s, "tree"))
        cases.append((f"ring_{s}", s, "ring"))
    out = socket_allreduce_metrics(world=world, cases=tuple(cases),
                                   iters=iters)
    crossover = None
    for s in sizes:
        if out[f"ring_{s}_gbps"] >= out[f"tree_{s}_gbps"]:
            crossover = s
            break
    out["crossover_bytes"] = crossover
    return out


def device_psum_metrics(payload_mb: float = 32.0, iters: int = 20) -> dict:
    """Jitted psum-allreduce step over the device mesh axis: per-step time
    and achieved algorithm bytes/s. Ring-allreduce moves 2(n-1)/n × size
    per device, so achieved_bw = that volume / step time; utilization is
    reported only where the device kind's interconnect peak is known."""
    import jax
    import numpy as np

    from dmlc_tpu.collective.device import make_allreduce_step
    from dmlc_tpu.obs.xla_cost import device_peaks
    from dmlc_tpu.parallel.mesh import batch_sharding, data_parallel_mesh

    devices = jax.devices()
    n = len(devices)
    mesh = data_parallel_mesh(devices)
    step = make_allreduce_step(mesh, axis="dp")

    elems = (int(payload_mb * (1 << 20) // 4) // n) * n
    host = np.ones(elems, dtype=np.float32)
    sharding = batch_sharding(mesh)

    def one_step():
        # donation consumes the input each call; re-placing from a host
        # array is itself pipelined H2D, kept outside the timed region
        x = jax.device_put(host, sharding)
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        out = step(x)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    one_step()  # compile + warmup
    dt = min(one_step() for _ in range(iters))

    nbytes = elems * 4
    metrics = {
        "psum_devices": n,
        "psum_platform": devices[0].platform,
        "psum_payload_mb": round(nbytes / (1 << 20), 1),
        "psum_step_ms": round(dt * 1e3, 3),
    }
    if n > 1:
        metrics.update(allreduce_algo_metrics(
            n, nbytes, dt, device_peaks().get("ici_gbps")))
    else:
        # single device: psum over a size-1 axis is a pass-through; this
        # measures step dispatch + donation only, not a collective
        metrics["psum_single_device_gbps"] = round(nbytes / dt / 1e9, 3)
    return metrics


def spmd_psum_step_metrics(payload_mb: float = 32.0, iters: int = 20) -> dict:
    """The tentpole hot path in isolation: a jitted SPMD SGD-shaped step
    whose gradient allreduce is an in-graph psum over the mesh axis.
    Contrast ``device_psum_metrics``, which re-stages its payload from
    host numpy every step (the legacy DeviceEngine round-trip): here the
    params are DONATED and carried device-to-device across iterations and
    the sharded grads stay resident, exactly like LinearLearner's fit
    loop — the measured figure is the in-graph collective + update with
    zero host bytes on the path.

    Reports ``spmd_psum_step_gbps`` (achieved algorithm bytes/s through
    the psum: ring volume 2(n-1)/n × payload per device) and, on >1
    device of a kind whose interconnect peak is known,
    ``ici_utilization`` (achieved / ``device_peaks()["ici_gbps"]``).
    Both are gated higher-is-better by bench-gate (obs/sentry.py)."""
    import jax
    import numpy as np

    from dmlc_tpu.obs.device_telemetry import instrumented_jit
    from dmlc_tpu.obs.xla_cost import device_peaks
    from dmlc_tpu.parallel.mesh import (
        batch_sharding, data_parallel_mesh, replicated_sharding,
    )
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    mesh = data_parallel_mesh(devices)
    elems = int(payload_mb * (1 << 20) // 4)

    def _sharded(w, g):
        # the train-step shape: in-graph allreduce then SGD apply; the
        # reduced grads never exist on the host
        red = jax.lax.psum(g, "dp")
        return w - 0.01 * red[0]

    step = instrumented_jit(
        shard_map(
            _sharded, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P()
        ),
        "bench.spmd_step",
        donate_argnums=(0,),
    )
    w = jax.device_put(
        np.zeros(elems, dtype=np.float32), replicated_sharding(mesh)
    )
    g = jax.device_put(
        np.ones((n, elems), dtype=np.float32), batch_sharding(mesh)
    )
    w = step(w, g)
    float(w[0])  # compile + warmup + readback fence
    # amortized pipelined timing (see device_engine_allreduce_metrics):
    # back-to-back dispatch, ended on a 1-element D2H read that cannot
    # complete early
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            w = step(w, g)
        float(w[0])
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)

    nbytes = elems * 4
    metrics = {
        "spmd_devices": n,
        "spmd_platform": devices[0].platform,
        "spmd_payload_mb": round(nbytes / (1 << 20), 1),
        "spmd_step_ms": round(best * 1e3, 3),
    }
    if n > 1:
        algo_bytes = 2 * (n - 1) / n * nbytes
        metrics["spmd_psum_step_gbps"] = round(algo_bytes / best / 1e9, 3)
        ici_gbps = device_peaks().get("ici_gbps")
        if ici_gbps:
            metrics["ici_utilization"] = round(
                (algo_bytes / best) / (ici_gbps * 1e9), 3)
    else:
        # size-1 axis: the psum is a pass-through — step dispatch + apply
        # rate only, still useful as the key's single-device floor
        metrics["spmd_psum_step_gbps"] = round(nbytes / best / 1e9, 3)
    return metrics


def grad_bucket_metrics(iters: int = 8) -> dict:
    # min-of-8: each iter moves a ~25 MB pytree; the within-run
    # fused-vs-per-tensor A/B is the quantity of record, not the absolute ms
    """Fused-bucket vs per-tensor gradient allreduce A/B on whatever
    devices exist (preparing for the ICI-utilization target before
    multi-chip hardware does: one concatenated psum per step vs one psum
    per leaf). The pytree mimics a small transformer's grad structure —
    many leaves of very different sizes — where combiner behavior actually
    matters."""
    import jax
    import numpy as np

    from dmlc_tpu.collective.device import make_allreduce_step
    from dmlc_tpu.parallel.mesh import batch_sharding, data_parallel_mesh

    devices = jax.devices()
    n = len(devices)
    mesh = data_parallel_mesh(devices)
    sharding = batch_sharding(mesh)

    rng = np.random.RandomState(0)
    # ~24 MB over 26 leaves: embeddings, per-layer qkvo + mlp + norms
    shapes = [(1024, 512), (512, 512), (512, 512), (512, 512), (512, 512),
              (512, 2048), (2048, 512), (512,), (512,)] * 2 + [
        (1024, 512), (8, 512), (512,), (512,), (2048,), (2048,), (512, 512),
        (512,)]
    grads = {
        f"g{i}": rng.randn(n, *s).astype(np.float32)
        for i, s in enumerate(shapes)
    }  # leading dim shards over dp
    nbytes = sum(g.nbytes for g in grads.values())

    out = {"bucket_payload_mb": round(nbytes / (1 << 20), 1),
           "bucket_leaves": len(shapes)}
    for key, bucket in (("bucket_fused_ms", True),
                        ("bucket_per_tensor_ms", False)):
        step = make_allreduce_step(mesh, axis="dp", bucket=bucket)

        def one():
            x = {k: jax.device_put(v, sharding) for k, v in grads.items()}
            jax.block_until_ready(x)
            t0 = time.perf_counter()
            y = step(x)
            jax.block_until_ready(y)
            return time.perf_counter() - t0

        one()  # compile + warmup
        out[key] = round(min(one() for _ in range(iters)) * 1e3, 3)
    return out


def device_engine_allreduce_metrics(
    payload_mb: float = 32.0, iters: int = 20
) -> dict:
    """DeviceEngine.allreduce's jitted reduction path: a [world, N] array
    with its leading dim sharded over the process axis, reduced to a
    replicated output (the O(N) XLA AllReduce the engine runs for host
    arrays — the data plane, not just control scalars). With one process
    the measured figure is the on-chip reduction + replication rate; with
    more it is the cross-host AllReduce."""
    import jax
    import numpy as np

    from dmlc_tpu.collective.device import DeviceEngine

    eng = DeviceEngine()
    elems = int(payload_mb * (1 << 20) // 4)
    arr = np.ones(elems, dtype=np.float32)

    if eng.world_size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(eng._process_mesh(), P("proc"))
        garr = jax.make_array_from_process_local_data(
            sharding, arr[None], (eng.world_size,) + arr.shape
        )
        moved = elems * 4  # per-link payload of the cross-host AllReduce
        key = "engine_allreduce_gbps"
    else:
        # one process: the engine short-circuits, and a [1, N] reduce
        # compiles to a no-op — measure a real W-way on-chip reduction
        # instead (the compute half of the allreduce; HBM-bound figure)
        W = 8
        garr = jax.device_put(np.ones((W, elems), dtype=np.float32))
        moved = W * elems * 4
        key = "engine_reduce_single_process_gbps"
    fn = eng._reduce_fn("sum")
    # amortized pipelined timing with a value readback fence: dispatch
    # iters back-to-back and end on a 1-element D2H read, which cannot
    # complete early — converges to the HBM-bound figure
    float(fn(garr)[0])  # compile + warmup + fence
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(garr)
        float(out[0])  # readback fence
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return {
        "engine_allreduce_world": eng.world_size,
        "engine_allreduce_payload_mb": round(elems * 4 / (1 << 20), 1),
        key: round(moved / best / 1e9, 3),
    }


#: error keys of the tiers that need the device — bench.py exits non-zero
#: after printing its line when one of them is present on a TPU
DEVICE_TIER_ERRORS = (
    "psum_error", "spmd_step_error", "bucket_error",
    "engine_allreduce_error",
)


def collective_metrics(device_tiers: bool = True) -> dict:
    """The bench.py hook: flat metric dict; failures are per-tier (an
    ``*_error`` key) so one broken tier cannot hide the other.
    ``device_tiers=False`` (no chip: bench.py's ``not measured`` case)
    runs only the socket tier, which never initializes a jax backend —
    its workers import ``dmlc_tpu.collective`` (and so ``jax``) but touch
    no device, so they are safe beside a parent that holds the chip."""
    out = {}
    try:
        out.update(socket_allreduce_metrics())
    except Exception as err:  # noqa: BLE001
        out["socket_allreduce_error"] = str(err)
    if not device_tiers:
        return out
    for tier, err_key in zip(
        (device_psum_metrics, spmd_psum_step_metrics, grad_bucket_metrics,
         device_engine_allreduce_metrics),
        DEVICE_TIER_ERRORS,
    ):
        try:
            out.update(tier())
        except Exception as err:  # noqa: BLE001
            out[err_key] = str(err)
    return out


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    print(json.dumps(collective_metrics()))
